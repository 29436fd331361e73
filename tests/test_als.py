"""ALS kernel tests: exact normal-equation parity vs a numpy reference,
convergence on a synthetic low-rank matrix, implicit mode, segment-packing
edge cases, and mesh-sharded execution on the virtual 8-device CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops.als import (
    ALSConfig,
    pack_segments,
    predict_ratings,
    recommend_batch,
    rmse,
    train_als,
)
from predictionio_tpu.parallel import default_mesh


def synthetic(n_users=60, n_items=40, k=4, density=0.4, seed=1, noise=0.0):
    rng = np.random.default_rng(seed)
    U = rng.standard_normal((n_users, k)) / np.sqrt(k)
    V = rng.standard_normal((n_items, k)) / np.sqrt(k)
    R = U @ V.T + 3.0
    mask = rng.random((n_users, n_items)) < density
    u, i = np.nonzero(mask)
    r = R[u, i] + noise * rng.standard_normal(len(u))
    return u.astype(np.int32), i.astype(np.int32), r.astype(np.float32)


def dense_mask(side):
    """Per-slot validity reconstructed from the per-segment prefix count
    (PackedSide.rem replaced the uint8 mask plane in round 4)."""
    L = side.cols.shape[2]
    return (np.arange(L)[None, None, :] < side.rem[:, :, None]).astype(np.uint8)


class TestPackSegments:
    def test_segments_cover_all_ratings(self):
        u, i, r = synthetic()
        L = 8
        side = pack_segments(u, i, r, 60, segment_length=L, pad_segments_to=8)
        assert int(dense_mask(side).sum()) == len(u)
        assert side.seg_rows.shape[1] % 8 == 0  # shards evenly
        seg_rows = side.seg_rows.reshape(-1)
        cols = side.cols.reshape(-1, L)
        vals = side.vals.reshape(-1, L)
        mask = dense_mask(side).reshape(-1, L)
        for rid in range(60):
            sel = seg_rows == rid
            got_cols = cols[sel][mask[sel] > 0]
            expect = i[u == rid]
            assert sorted(got_cols.tolist()) == sorted(expect.tolist())
            # values travel with their columns
            got = dict(zip(got_cols.tolist(), vals[sel][mask[sel] > 0].tolist()))
            for cc, vv in zip(expect.tolist(), r[u == rid].tolist()):
                assert got[cc] == pytest.approx(vv)

    def test_long_row_spans_consecutive_segments(self):
        u = np.zeros(100, np.int32)
        i = np.arange(100, dtype=np.int32)
        r = np.ones(100, np.float32)
        side = pack_segments(u, i, r, 1, segment_length=16)
        seg_rows = side.seg_rows.reshape(-1)
        assert int((seg_rows == 0).sum()) == 7  # 6 full + 1 partial
        assert int(dense_mask(side).sum()) == 100

    def test_empty_rows_get_no_segments(self):
        u = np.array([5], np.int32)
        i = np.array([0], np.int32)
        r = np.array([1.0], np.float32)
        side = pack_segments(u, i, r, 10, segment_length=4)
        seg_rows = side.seg_rows.reshape(-1)
        assert int((seg_rows == 5).sum()) == 1
        assert side.counts[5] == 1 and side.counts.sum() == 1
        # every other segment is padding, pointing at the sentinel row
        assert (seg_rows[seg_rows != 5] == 10).all()

    def test_chunk_grid_bounds_slots(self):
        u, i, r = synthetic()
        side = pack_segments(u, i, r, 60, segment_length=8, chunk_slots=64)
        assert side.cols.shape[1] * side.cols.shape[2] <= 64
        assert int(dense_mask(side).sum()) == len(u)


def numpy_als_half_step(Y, u, i, r, n_users, reg, weighted):
    """Reference explicit normal-equation solve for every user."""
    k = Y.shape[1]
    X = np.zeros((n_users, k), np.float32)
    for uu in range(n_users):
        sel = u == uu
        if not sel.any():
            continue
        Ys = Y[i[sel]]
        A = Ys.T @ Ys
        lam = reg * sel.sum() if weighted else reg
        A += lam * np.eye(k)
        b = Ys.T @ r[sel]
        X[uu] = np.linalg.solve(A, b)
    return X


class TestExplicitALS:
    def test_single_half_step_matches_numpy(self):
        u, i, r = synthetic(n_users=30, n_items=20, seed=2)
        cfg = ALSConfig(rank=4, iterations=1, reg=0.1, segment_length=8)
        model = train_als(u, i, r, 30, 20, cfg)
        # after iter 1: X solved against Y0; recompute X from returned Y? No —
        # instead verify the fixpoint property on a fresh solve: the returned
        # user factors must satisfy the normal equations for the *pre-update*
        # item factors only in a 1-iteration run if we re-derive Y0. Easier and
        # equally strong: run 0-iteration + manual numpy comparison on the
        # final returned factors' item-side equations.
        Xh = numpy_als_half_step(
            model.item_factors, u, i, r, 30, reg=0.1, weighted=True
        )
        # user factors were solved against the *final* item factors in the
        # last half-step? (ordering: user then item). So instead check the
        # item side: item factors solved against final user factors.
        Yh = numpy_als_half_step(
            model.user_factors, i, u, r, 20, reg=0.1, weighted=True
        )
        np.testing.assert_allclose(model.item_factors, Yh, rtol=2e-3, atol=2e-4)

    def test_converges_on_low_rank_matrix(self):
        u, i, r = synthetic(n_users=80, n_items=50, k=4, density=0.5)
        cfg = ALSConfig(rank=8, iterations=12, reg=0.01)
        model = train_als(u, i, r, 80, 50, cfg)
        assert rmse(model, u, i, r) < 0.08

    def test_plain_reg_mode(self):
        u, i, r = synthetic(n_users=30, n_items=20)
        cfg = ALSConfig(rank=4, iterations=3, reg=0.05, reg_mode="plain")
        model = train_als(u, i, r, 30, 20, cfg)
        Yh = numpy_als_half_step(
            model.user_factors, i, u, r, 20, reg=0.05, weighted=False
        )
        np.testing.assert_allclose(model.item_factors, Yh, rtol=2e-3, atol=2e-4)

    def test_deterministic_given_seed(self):
        u, i, r = synthetic()
        cfg = ALSConfig(rank=4, iterations=2, seed=42)
        m1 = train_als(u, i, r, 60, 40, cfg)
        m2 = train_als(u, i, r, 60, 40, cfg)
        np.testing.assert_array_equal(m1.user_factors, m2.user_factors)


class TestImplicitALS:
    def test_implicit_fits_preferences(self):
        rng = np.random.default_rng(3)
        n_users, n_items = 50, 30
        # two user groups preferring two item groups
        u_list, i_list, c_list = [], [], []
        for uu in range(n_users):
            group = uu % 2
            items = rng.choice(
                np.arange(group * 15, group * 15 + 15), size=8, replace=False
            )
            for it in items:
                u_list.append(uu)
                i_list.append(it)
                c_list.append(rng.integers(1, 5))
        u = np.array(u_list, np.int32)
        i = np.array(i_list, np.int32)
        r = np.array(c_list, np.float32)
        cfg = ALSConfig(rank=8, iterations=8, reg=0.01, alpha=2.0, implicit_prefs=True)
        model = train_als(u, i, r, n_users, n_items, cfg)
        # predicted preference for observed pairs should beat cross-group items
        pred_obs = predict_ratings(model, u, i).mean()
        cross_i = (i + 15) % 30
        pred_cross = predict_ratings(model, u, cross_i).mean()
        assert pred_obs > 0.5
        assert pred_obs > pred_cross + 0.3

    def test_implicit_normal_equations(self):
        u, i, r = synthetic(n_users=25, n_items=15, density=0.3)
        r = np.abs(r)
        cfg = ALSConfig(
            rank=4, iterations=2, reg=0.1, alpha=1.5, implicit_prefs=True,
            reg_mode="plain",
        )
        model = train_als(u, i, r, 25, 15, cfg)
        X, Y = model.user_factors, model.item_factors
        k = 4
        G = X.T @ X
        for it in range(15):
            sel = i == it
            if not sel.any():
                continue
            Xs = X[u[sel]]
            c = 1.5 * np.abs(r[sel])
            A = G + (Xs * c[:, None]).T @ Xs + 0.1 * np.eye(k)
            b = (Xs * ((r[sel] > 0) * (1 + c))[:, None]).sum(0)
            np.testing.assert_allclose(Y[it], np.linalg.solve(A, b), rtol=2e-3, atol=2e-4)

    def test_implicit_dislikes_hukoren_semantics(self):
        """Dislike ratings (r<0, the similarproduct LikeAlgorithm encoding)
        must contribute confidence alpha*|r| to A (PSD-safe) and nothing to
        b — MLlib trainImplicit semantics. With the pre-fix signed-weight
        math, alpha=3 here drives A indefinite and the solve to NaN."""
        rng = np.random.default_rng(7)
        n_users, n_items, k = 30, 20, 4
        u = np.repeat(np.arange(n_users, dtype=np.int32), 6)
        i = rng.integers(0, n_items, len(u)).astype(np.int32)
        r = rng.choice([-1.0, 1.0], size=len(u), p=[0.4, 0.6]).astype(np.float32)
        cfg = ALSConfig(
            rank=k, iterations=3, reg=0.1, alpha=3.0, implicit_prefs=True,
            reg_mode="plain",
        )
        model = train_als(u, i, r, n_users, n_items, cfg)
        X, Y = model.user_factors, model.item_factors
        assert np.isfinite(X).all() and np.isfinite(Y).all()
        # the item phase runs last, so final Y must satisfy the Hu-Koren
        # normal equations against final X
        G = X.T @ X
        for it in range(n_items):
            sel = i == it
            if not sel.any():
                continue
            Xs = X[u[sel]]
            c = 3.0 * np.abs(r[sel])
            A = G + (Xs * c[:, None]).T @ Xs + 0.1 * np.eye(k)
            b = (Xs * ((r[sel] > 0) * (1 + c))[:, None]).sum(0)
            np.testing.assert_allclose(
                Y[it], np.linalg.solve(A, b), rtol=2e-3, atol=2e-4
            )


class TestMeshALS:
    def test_sharded_training_matches_single_device(self):
        u, i, r = synthetic(n_users=64, n_items=40)
        cfg = ALSConfig(rank=4, iterations=3, reg=0.05)
        single = train_als(u, i, r, 64, 40, cfg)
        mesh = default_mesh("data")
        assert mesh.shape["data"] == 8
        sharded = train_als(u, i, r, 64, 40, cfg, mesh=mesh)
        np.testing.assert_allclose(
            single.user_factors, sharded.user_factors, rtol=1e-4, atol=1e-5
        )
        np.testing.assert_allclose(
            single.item_factors, sharded.item_factors, rtol=1e-4, atol=1e-5
        )

    def test_sharded_implicit_matches_single_device(self):
        # exercises the sharded Gramian all-reduce (psum over the mesh axis)
        u, i, r = synthetic(n_users=64, n_items=40)
        cfg = ALSConfig(rank=4, iterations=3, reg=0.05, implicit_prefs=True)
        single = train_als(u, i, r, 64, 40, cfg)
        sharded = train_als(u, i, r, 64, 40, cfg, mesh=default_mesh("data"))
        np.testing.assert_allclose(
            single.user_factors, sharded.user_factors, rtol=1e-4, atol=1e-5
        )
        np.testing.assert_allclose(
            single.item_factors, sharded.item_factors, rtol=1e-4, atol=1e-5
        )


class TestServingOps:
    def test_recommend_batch_topn(self):
        u, i, r = synthetic(n_users=20, n_items=30)
        cfg = ALSConfig(rank=4, iterations=4)
        model = train_als(u, i, r, 20, 30, cfg)
        scores, idx = recommend_batch(model.user_factors[:5], model.item_factors, 7)
        assert scores.shape == (5, 7) and idx.shape == (5, 7)
        # scores descending, and they match the factors' dot products
        assert (np.diff(scores, axis=1) <= 1e-6).all()
        full = model.user_factors[:5] @ model.item_factors.T
        np.testing.assert_allclose(scores[:, 0], full.max(axis=1), rtol=1e-5)
        # indices decode to the true argmax ordering (regression: packed
        # int32 bits must be viewed, not float-cast)
        np.testing.assert_array_equal(idx[:, 0], full.argmax(axis=1))
        np.testing.assert_array_equal(
            idx, np.argsort(-full, axis=1, kind="stable")[:, :7]
        )


class TestPackShapeBucketing:
    def test_near_equal_segment_counts_share_shapes(self):
        """k-fold/grid eval packs near-identical segment counts (402 vs
        408); bucketed Sc must give them the SAME array shapes so they
        share one compiled executable instead of one each."""
        shapes = set()
        for n in (402, 403, 408):
            u = np.arange(n, dtype=np.int32) % 450
            i = np.arange(n, dtype=np.int32) % 30
            r = np.ones(n, np.float32)
            side = pack_segments(u, i, r, 450, segment_length=8)
            shapes.add(side.cols.shape)
        assert len(shapes) == 1, shapes

    def test_bucketing_keeps_shard_divisibility(self):
        u = np.arange(100, dtype=np.int32)
        i = np.zeros(100, np.int32)
        r = np.ones(100, np.float32)
        side = pack_segments(u, i, r, 100, segment_length=8, pad_segments_to=8)
        assert side.seg_rows.shape[1] % 8 == 0
        assert int(dense_mask(side).sum()) == 100

    def test_nibble_wire_round_trip(self):
        """Half-step ratings in [0, 7.5] travel two-per-byte; the device
        unpack restores them exactly. Negatives and >7.5 fall back."""
        from predictionio_tpu.ops.als import (
            _nibble_packable, _pack_nibbles_host, _unpack_nibbles,
        )

        rng = np.random.default_rng(6)
        vw = rng.integers(0, 16, 1000).astype(np.int8)
        assert _nibble_packable(vw)
        packed = _pack_nibbles_host(vw)
        assert packed.nbytes == 500
        np.testing.assert_array_equal(np.asarray(_unpack_nibbles(packed)), vw)
        assert not _nibble_packable(np.array([1, -2], np.int8))  # dislike
        assert not _nibble_packable(np.array([1, 16], np.int8))  # > 7.5
        assert not _nibble_packable(np.array([1, 2, 3], np.int8))  # odd

    def test_near_equal_cardinalities_share_iteration_executable(self):
        """The system-ROW dimension buckets too (round 5): a store scan
        seeing 0.04% fewer distinct users than the direct path — or a
        retrain after new signups — must reuse the compiled iteration
        program instead of paying a multi-second XLA pause (the round-4
        store->train seam)."""
        from predictionio_tpu.ops.als import _bucket_count, _run_iterations

        assert _bucket_count(138_493 + 1) == _bucket_count(138_432 + 1)

        rng = np.random.default_rng(5)
        cfg = ALSConfig(rank=4, iterations=2, reg=0.1)

        def train(nu):
            u = rng.integers(0, nu, 3000).astype(np.int32)
            i = rng.integers(0, 200, 3000).astype(np.int32)
            r = np.ones(3000, np.float32)
            train_als(u, i, r, nu, 200, cfg)

        train(1000)
        before = _run_iterations._cache_size()
        train(997)  # same 4-significant-bit bucket as 1000
        assert _run_iterations._cache_size() == before


class TestSpdSolve:
    """_spd_solve replaced XLA's cho_solve in round 4 (502 ms/solve at
    ML-20M scale on TPU — half the device loop). Parity with scipy on
    random SPD batches, odd ranks included, plus under vmap (grid path)."""

    @pytest.mark.parametrize("k", [1, 2, 7, 10, 32, 33])
    def test_matches_cho_solve(self, k):
        from predictionio_tpu.ops.als import _spd_solve

        rng = np.random.default_rng(k)
        R = 50
        M = rng.standard_normal((R, k, k)).astype(np.float32)
        A = np.einsum("rij,rkj->rik", M, M) + 2.0 * np.eye(k, dtype=np.float32)
        b = rng.standard_normal((R, k)).astype(np.float32)
        x = np.asarray(jax.jit(_spd_solve)(jnp.asarray(A), jnp.asarray(b)))
        expect = np.linalg.solve(A, b[..., None])[..., 0]
        np.testing.assert_allclose(x, expect, rtol=2e-3, atol=2e-4)

    def test_vmapped(self):
        from predictionio_tpu.ops.als import _spd_solve

        rng = np.random.default_rng(0)
        V, R, k = 3, 20, 8
        M = rng.standard_normal((V, R, k, k)).astype(np.float32)
        A = np.einsum("vrij,vrkj->vrik", M, M) + 2.0 * np.eye(k, dtype=np.float32)
        b = rng.standard_normal((V, R, k)).astype(np.float32)
        x = np.asarray(jax.jit(jax.vmap(_spd_solve))(jnp.asarray(A), jnp.asarray(b)))
        expect = np.linalg.solve(A, b[..., None])[..., 0]
        np.testing.assert_allclose(x, expect, rtol=2e-3, atol=2e-4)


class TestGridALS:
    def test_grid_matches_serial_per_reg(self):
        """train_als_grid == train_als per variant, explicit + implicit
        (the device-side grid path must be a pure speedup, VERDICT r2 #7)."""
        import dataclasses

        from predictionio_tpu.ops.als import train_als_grid

        u, i, r = synthetic(noise=0.1)
        regs = [0.01, 0.1, 1.0]
        for implicit in (False, True):
            cfg = ALSConfig(rank=4, iterations=4, implicit_prefs=implicit)
            grid = train_als_grid(u, i, r, 60, 40, cfg, regs)
            assert len(grid) == 3
            for v, reg in enumerate(regs):
                single = train_als(
                    u, i, r, 60, 40, dataclasses.replace(cfg, reg=reg)
                )
                np.testing.assert_allclose(
                    grid[v].user_factors, single.user_factors,
                    rtol=2e-4, atol=2e-5,
                )
                np.testing.assert_allclose(
                    grid[v].item_factors, single.item_factors,
                    rtol=2e-4, atol=2e-5,
                )

    def test_one_device_mesh_uses_grid_path(self):
        """The default workflow context carries a 1-device mesh; the grid
        must still train batched there (nothing to shard)."""
        from unittest import mock

        from predictionio_tpu.ops.als import _run_iterations_grid, train_als_grid
        from predictionio_tpu.parallel import make_mesh

        import jax

        mesh = make_mesh({"data": 1}, jax.devices()[:1])
        u, i, r = synthetic()
        cfg = ALSConfig(rank=4, iterations=2)
        with mock.patch(
            "predictionio_tpu.ops.als._run_iterations_grid",
            wraps=_run_iterations_grid,
        ) as spy:
            out = train_als_grid(u, i, r, 60, 40, cfg, [0.01, 0.1], mesh=mesh)
        assert len(out) == 2
        assert spy.call_count == 1  # one batched program, not serial falls

    def test_multi_device_mesh_trains_grid_in_one_program(self):
        """VERDICT r3 #6: 4 reg variants on an 8-device mesh train in ONE
        vmapped program (rounds 1-3 fell back to serial per-variant
        training there), numerically equal to serial single-device."""
        import dataclasses
        from unittest import mock

        from predictionio_tpu.ops.als import (
            _run_iterations_grid,
            train_als_grid,
        )
        from predictionio_tpu.parallel import make_mesh

        import jax

        if len(jax.devices()) < 8:
            pytest.skip("needs the virtual 8-device CPU platform")
        mesh = make_mesh({"data": 8}, jax.devices()[:8])
        u, i, r = synthetic(noise=0.1)
        regs = [0.01, 0.05, 0.1, 1.0]
        cfg = ALSConfig(rank=4, iterations=3)
        with mock.patch(
            "predictionio_tpu.ops.als._run_iterations_grid",
            wraps=_run_iterations_grid,
        ) as spy:
            grid = train_als_grid(u, i, r, 60, 40, cfg, regs, mesh=mesh)
        assert spy.call_count == 1  # one program for the whole grid
        assert len(grid) == 4
        for v, reg in enumerate(regs):
            single = train_als(
                u, i, r, 60, 40, dataclasses.replace(cfg, reg=reg)
            )
            np.testing.assert_allclose(
                grid[v].user_factors, single.user_factors,
                rtol=2e-4, atol=2e-5,
            )
            np.testing.assert_allclose(
                grid[v].item_factors, single.item_factors,
                rtol=2e-4, atol=2e-5,
            )

class TestSubspaceSolver:
    """iALS++ blocked subspace solver (solver="subspace"): full-rank-block
    equivalence to the exact solver, convergence in explicit and implicit
    mode, mesh parity, and config validation."""

    def test_full_rank_block_matches_exact(self):
        """With block_size == rank the residual-form block solve collapses
        to x_new = A^-1 b — the exact normal-equation update — so factors
        must agree with solver="exact" to float tolerance, explicit and
        implicit."""
        import dataclasses

        u, i, r = synthetic(noise=0.1)
        for implicit in (False, True):
            cfg = ALSConfig(
                rank=4, iterations=3, reg=0.05, implicit_prefs=implicit,
                solver="subspace", block_size=4,
            )
            sub = train_als(u, i, r, 60, 40, cfg)
            exact = train_als(
                u, i, r, 60, 40,
                dataclasses.replace(cfg, solver="exact", block_size=0),
            )
            np.testing.assert_allclose(
                sub.user_factors, exact.user_factors, rtol=2e-4, atol=2e-5
            )
            np.testing.assert_allclose(
                sub.item_factors, exact.item_factors, rtol=2e-4, atol=2e-5
            )

    def test_subspace_explicit_converges(self):
        u, i, r = synthetic(n_users=80, n_items=50, k=4, density=0.5)
        cfg = ALSConfig(
            rank=8, iterations=16, reg=0.01, solver="subspace", block_size=2
        )
        model = train_als(u, i, r, 80, 50, cfg)
        assert rmse(model, u, i, r) < 0.1

    def test_subspace_implicit_fits_preferences(self):
        rng = np.random.default_rng(3)
        n_users, n_items = 50, 30
        u_list, i_list, c_list = [], [], []
        for uu in range(n_users):
            group = uu % 2
            items = rng.choice(
                np.arange(group * 15, group * 15 + 15), size=8, replace=False
            )
            for it in items:
                u_list.append(uu)
                i_list.append(it)
                c_list.append(rng.integers(1, 5))
        u = np.array(u_list, np.int32)
        i = np.array(i_list, np.int32)
        r = np.array(c_list, np.float32)
        cfg = ALSConfig(
            rank=8, iterations=12, reg=0.01, alpha=2.0, implicit_prefs=True,
            solver="subspace", block_size=2,
        )
        model = train_als(u, i, r, n_users, n_items, cfg)
        pred_obs = predict_ratings(model, u, i).mean()
        cross_i = (i + 15) % 30
        pred_cross = predict_ratings(model, u, cross_i).mean()
        assert pred_obs > 0.5
        assert pred_obs > pred_cross + 0.3

    def test_subspace_deterministic_given_seed(self):
        u, i, r = synthetic()
        cfg = ALSConfig(
            rank=4, iterations=2, seed=42, solver="subspace", block_size=2
        )
        m1 = train_als(u, i, r, 60, 40, cfg)
        m2 = train_als(u, i, r, 60, 40, cfg)
        np.testing.assert_array_equal(m1.user_factors, m2.user_factors)

    def test_subspace_mesh_matches_single_device(self):
        u, i, r = synthetic(n_users=64, n_items=40)
        cfg = ALSConfig(
            rank=4, iterations=3, reg=0.05, implicit_prefs=True,
            solver="subspace", block_size=2,
        )
        single = train_als(u, i, r, 64, 40, cfg)
        sharded = train_als(u, i, r, 64, 40, cfg, mesh=default_mesh("data"))
        np.testing.assert_allclose(
            single.user_factors, sharded.user_factors, rtol=1e-4, atol=1e-5
        )
        np.testing.assert_allclose(
            single.item_factors, sharded.item_factors, rtol=1e-4, atol=1e-5
        )

    def test_config_validation(self):
        with pytest.raises(ValueError, match="block_size > 0"):
            ALSConfig(rank=4, solver="subspace")
        with pytest.raises(ValueError, match="must divide rank"):
            ALSConfig(rank=4, solver="subspace", block_size=3)
        with pytest.raises(ValueError, match="'exact' or 'subspace'"):
            ALSConfig(rank=4, solver="cg")

    def test_grid_rejects_subspace(self):
        from predictionio_tpu.ops.als import train_als_grid

        u, i, r = synthetic()
        cfg = ALSConfig(rank=4, iterations=2, solver="subspace", block_size=2)
        with pytest.raises(ValueError, match="solver='exact'"):
            train_als_grid(u, i, r, 60, 40, cfg, [0.01, 0.1])


class TestWarmupCompile:
    def test_refused_warmup_compile_is_logged_at_error_level(
        self, monkeypatch, caplog
    ):
        """A background warm-up compile the device refuses must surface:
        a one-shot `pio train` takes its metrics registry (and the
        `error` outcome counted there) with it when it exits, so the log
        line is what an operator — and chip_smoke.py — can see."""
        import logging

        from predictionio_tpu.ops import als

        def refuse(*args, **kwargs):
            raise RuntimeError("RESOURCE_EXHAUSTED: program does not fit")

        monkeypatch.setattr(als, "_run_iterations", refuse)
        counts = np.array([3, 1, 2], np.int32)
        geo = als._segment_geometry(counts, 3, 8, 1, 4096)
        with caplog.at_level(logging.ERROR, logger=als.logger.name):
            rec = als.start_compile_async(
                3, 3, geo, geo, 8, 8, als.ALSConfig(rank=7)
            )()
        assert "RESOURCE_EXHAUSTED" in rec["error"]
        errors = [r for r in caplog.records if r.levelno == logging.ERROR]
        assert len(errors) == 1
        assert "ALS warm-up compile failed" in errors[0].getMessage()
        assert errors[0].exc_info is not None


class TestPackedTopN:
    def test_packed_buffer_is_integer_typed(self):
        """The one buffer that carries scores AND indices must be int32:
        a TPU flushes float32 subnormals to zero, and a small index's
        bits read as a float32 ARE a subnormal — in a float buffer every
        served index came back 0 on the chip (the CPU never flushes, so
        only the dtype can be held here; chip_smoke.py holds the lists)."""
        from predictionio_tpu.ops.als import _pack_topn, unpack_topn

        scores = jnp.asarray([[3.5, -jnp.inf, 1e-42]], jnp.float32)
        idx = jnp.asarray([[7, 26_743, 2**24 + 1]], jnp.int32)
        packed = _pack_topn(scores, idx)
        assert packed.dtype == jnp.int32
        s, i = unpack_topn(packed, 3)
        assert s.dtype == np.float32 and i.dtype == np.int32
        np.testing.assert_array_equal(s, np.asarray(scores))
        np.testing.assert_array_equal(i, np.asarray(idx))
