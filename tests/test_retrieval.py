"""Sharded on-device top-N retrieval (ops/retrieval.py) — exact-parity
tests against the naive full-matmul reference across 1/2/4-way shard
counts (mask semantics included: blacklist, unavailable, seen-item
exclusion, whitelist/categories, and the k > live-candidate-count edge),
the TTL constraint cache, the ecommerce/similarproduct serving paths,
and the resident-factors-survive-hot-reload regression."""

import copy
import datetime as dt
import threading
import time

import jax
import numpy as np
import pytest

from predictionio_tpu.data import storage as storage_mod
from predictionio_tpu.data.event import DataMap, Event
from predictionio_tpu.data.storage.base import App
from predictionio_tpu.ops import retrieval
from predictionio_tpu.ops.retrieval import (
    ItemRetriever,
    naive_topn_reference,
)
from predictionio_tpu.parallel import make_mesh
from predictionio_tpu.utils import metrics as metrics_mod
from predictionio_tpu.workflow.context import WorkflowContext, workflow_context
from tests import retrieval_blocks as blocks


def _mesh_or_none(shards):
    if shards == 1:
        return None
    if len(jax.devices()) < shards:
        pytest.skip(f"needs {shards} virtual devices")
    return make_mesh({"data": shards}, jax.devices()[:shards])


def _family_value(name, **labels):
    samples = metrics_mod.parse_exposition(
        metrics_mod.get_registry().render()
    )
    if labels:
        inner = ",".join(
            f'{k}="{v}"' for k, v in sorted(labels.items())
        )
        return samples.get(f"{name}{{{inner}}}", 0.0)
    return samples.get(name, 0.0)


class TestRetrieverParity:
    """Sharded retrieval == naive full matmul top-N, id-for-id."""

    @pytest.mark.parametrize("shards", [1, 2, 4])
    def test_exact_parity_with_masks(self, shards):
        mesh = _mesh_or_none(shards)
        rng = np.random.default_rng(shards)
        N, k, B, n = 57, 8, 5, 12  # 57 does not divide 2 or 4 (padding)
        Y = rng.standard_normal((N, k)).astype(np.float32)
        q = rng.standard_normal((B, k)).astype(np.float32)
        # blacklist / empty-whitelist / whitelist / heavy exclusion mixes
        exclude = [
            None,
            np.array([0, 1, 2]),
            np.array([], np.int64),
            np.arange(50),
            None,
        ]
        include = [
            None,
            None,
            np.array([3, 4, 5, 9]),
            None,
            np.array([], np.int64),
        ]
        r = ItemRetriever(Y, mesh=mesh, component=f"parity{shards}")
        for positive_only in (False, True):
            for normalize in (False, True):
                s, i = r.topn(
                    q, n, exclude=exclude, include=include,
                    positive_only=positive_only, normalize=normalize,
                )
                es, ei = naive_topn_reference(
                    Y, q, n, exclude=exclude, include=include,
                    positive_only=positive_only, normalize=normalize,
                )
                live = es > -np.inf
                assert (s > -np.inf).sum() == live.sum()
                np.testing.assert_array_equal(i[live], ei[live])
                np.testing.assert_allclose(
                    s[live], es[live], rtol=1e-5, atol=1e-6
                )

    @pytest.mark.parametrize("shards", [2, 4])
    def test_global_mask_parity(self, shards):
        mesh = _mesh_or_none(shards)
        rng = np.random.default_rng(10 + shards)
        Y = rng.standard_normal((41, 6)).astype(np.float32)
        q = rng.standard_normal((3, 6)).astype(np.float32)
        banned = np.array([1, 7, 20, 39])
        r = ItemRetriever(Y, mesh=mesh, component=f"gmask{shards}")
        assert r.set_excluded_ids(banned) is True
        s, i = r.topn(q, 10)
        es, ei = naive_topn_reference(Y, q, 10, exclude=[banned] * 3)
        live = es > -np.inf
        np.testing.assert_array_equal(i[live], ei[live])

    def test_k_exceeds_live_candidates(self):
        rng = np.random.default_rng(2)
        Y = rng.standard_normal((10, 4)).astype(np.float32)
        r = ItemRetriever(Y, component="edge")
        s, i = r.topn(
            rng.standard_normal((1, 4)).astype(np.float32), 8,
            exclude=[np.arange(7)],
        )
        # only 3 live candidates: the rest of the requested 8 slots are
        # -inf (the caller's filter contract)
        assert int((s[0] > -np.inf).sum()) == 3
        assert set(i[0][: 3]) == {7, 8, 9}

    def test_factors_actually_sharded_and_output_replicated(self):
        mesh = _mesh_or_none(4)
        Y = np.eye(12, 4, dtype=np.float32)
        r = ItemRetriever(Y, mesh=mesh, component="shardcheck")
        assert not r._y_dev.sharding.is_fully_replicated
        assert len(r._y_dev.sharding.device_set) == 4
        # padded to whole blocks a shard
        assert {
            s.data.shape[0] for s in r._y_dev.addressable_shards
        } == {retrieval._ROW_BLOCK}
        assert r.resident_bytes > 0

    @pytest.mark.parametrize("mask", blocks.MASKS)
    @pytest.mark.parametrize("n_items", blocks.ITEM_COUNTS)
    @pytest.mark.parametrize("shards", [1, 4])
    def test_whole_blocks_change_no_answer(self, shards, n_items, mask):
        """Float32 over a table padded to whole blocks at build (tests/
        retrieval_blocks.py says what each case holds), on one device
        and row-sharded over four."""
        blocks.check(n_items, "float32", mask, _mesh_or_none(shards))

    @pytest.mark.parametrize("kind", blocks.TOP_K_KINDS)
    @pytest.mark.parametrize("batch", blocks.TOP_K_BATCHES)
    @pytest.mark.parametrize("n", blocks.TOP_K_WIDTHS)
    def test_two_level_top_k_is_lax_top_k_over_the_row(self, n, batch, kind):
        """``_top_k`` where it takes its second level (the best
        sub-blocks inside the best blocks) against ``lax.top_k`` over
        the whole row: the same scores and the same indices, ties to the
        lowest index and the dead slots' indices included
        (tests/retrieval_blocks.py says what each kind holds)."""
        import jax.numpy as jnp

        scores = jnp.asarray(blocks.top_k_scores(n, batch, kind))
        assert retrieval._two_level(scores.shape[1], n)
        want_s, want_i = jax.lax.top_k(scores, n)
        got_s, got_i = jax.jit(retrieval._top_k, static_argnums=1)(scores, n)
        np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
        np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
        got_s, got_i = np.asarray(got_s), np.asarray(got_i)
        if kind == "ties":
            np.testing.assert_array_equal(got_i[0], np.arange(n))
            start = 3 * 1024 - n // 2 - 5
            np.testing.assert_array_equal(got_i[1], np.arange(start, start + n))
        elif kind == "dead":
            assert (got_s[0] > -np.inf).sum() == 7
            assert (got_s[1] == -np.inf).all() and (got_s[2] > -np.inf).all()
        else:
            assert (got_i[0] // 1024 == 7).all()
            assert (got_i[1, : min(n, 128)] // 128 == 9 * 8 + 2).all()

    @pytest.mark.parametrize("n, shortlist", [(16, 64), (64, 256)])
    def test_the_int8_program_serves_what_one_level_served(
            self, n, shortlist, monkeypatch):
        """``_fused_topn_single_2s`` over a seeded int8 table of 614,400
        rows returns the packed buffer, ids and order, bit for bit, that
        the same program returns with the top-k of one level of blocks:
        the device hands the refine the same candidates."""
        import jax.numpy as jnp

        rows, k, b, widths = blocks.TOP_K_ROWS, 16, 8, (16, 1, 4)
        rng = np.random.default_rng(shortlist)
        yq = jnp.asarray(rng.integers(-127, 128, (rows, k), dtype=np.int8))
        scale = jnp.asarray(rng.uniform(0.005, 0.02, rows), jnp.float32)
        rn = jnp.asarray(rng.uniform(0.5, 2.0, rows), jnp.float32)
        allow = jnp.asarray(np.arange(rows) < rows - 300)
        codes = jnp.asarray(rng.integers(0, 24, (rows, 1)), jnp.int32)
        q = rng.standard_normal((b, k)).astype(np.float32)
        excl = [rng.integers(0, rows, 1 + i) for i in range(b)]
        cats = [np.array([i], np.int32) if i % 3 == 0 else None
                for i in range(b)]
        operand = jnp.asarray(retrieval._pack_operand(
            q, b, widths, rows, excl, (), cats, ()))
        args = (operand, yq, scale, rn, allow, codes)
        kw = dict(n=n, shortlist=shortlist, positive_only=True,
                  normalize=True, precision="int8", widths=widths)
        assert retrieval._two_level(rows, shortlist)
        got = np.asarray(retrieval._fused_topn_single_2s(*args, **kw))
        monkeypatch.setattr(retrieval, "_top_k", blocks.one_level_top_k)
        one_level = jax.jit(
            retrieval._fused_topn_single_2s.__wrapped__,
            static_argnames=tuple(kw),
        )
        want = np.asarray(one_level(*args, **kw))
        np.testing.assert_array_equal(got, want)
        assert (want[:, :n].view(np.float32) > 0).all()  # all live

    @pytest.mark.parametrize("n", [4, 16, 32, 64])
    def test_a_narrow_top_k_lowers_as_it_did(self, n):
        """Below ``_SUB_FROM`` winners (the float32 programs' top-16,
        each shard's) ``_top_k`` over a block wide enough to leave the
        narrow shortcut traces to the very program of one level of
        blocks, op for op; from there up it adds the sub-blocks'
        reduction."""
        import functools

        import jax.numpy as jnp

        scores = jax.ShapeDtypeStruct((8, 200 * 1024), jnp.float32)
        assert 200 > 2 * n  # past the narrow shortcut

        def ops(top_k):
            jaxpr = jax.make_jaxpr(functools.partial(top_k, n=n))(scores)
            return str(jaxpr), sum(
                e.primitive.name == "reduce_max" for e in jaxpr.eqns)

        got, reductions = ops(retrieval._top_k)
        want, one = ops(blocks.one_level_top_k)
        if n < retrieval._SUB_FROM:
            assert got == want and reductions == one == 1
        else:
            assert got != want and reductions == 2

    @pytest.mark.parametrize("shards", [1, 4])
    def test_the_two_level_counter(self, shards):
        """``pio_retrieval_topk_two_level_total`` counts a run of a
        program whose top-k took its second level: an int8 ``topn`` at
        a shortlist of 256 on one device (of 64 a shard over four, whose
        answer is the float32 reference's), not a float32 one."""
        mesh = _mesh_or_none(shards)
        rng = np.random.default_rng(40)
        Y = rng.standard_normal((blocks.TOP_K_ROWS - 5, 8)).astype(np.float32)
        q = Y[:3]
        n, shortlist = (16, 256) if shards == 1 else (4, 64)
        family = "pio_retrieval_topk_two_level_total"
        int8, f32 = f"two-level-8x{shards}", f"two-level-32x{shards}"
        quantized = ItemRetriever(
            Y, mesh=mesh, precision="int8", component=int8)
        exact = ItemRetriever(Y, mesh=mesh, component=f32)
        try:
            rows = quantized._n_pad // shards
            n_dev = min(quantized._shortlist_width(n, quantized.n_items), rows)
            assert quantized._shortlist_width(n_dev, rows) == shortlist
            before = _family_value(family, component=int8)
            s, i = quantized.topn(q, n)
            quantized.topn(q, n)
            assert _family_value(family, component=int8) == before + 2
            ref_s, ref_i = naive_topn_reference(Y, q, n)
            np.testing.assert_array_equal(i, ref_i)
            exact.topn(q, n)
            assert _family_value(family, component=f32) == 0
        finally:
            quantized.free()
            exact.free()

    @pytest.mark.parametrize("precision", ["float32", "bf16", "int8"])
    def test_a_mapped_table_is_padded_on_the_device_alone(
            self, precision, tmp_path):
        """Rows that are not whole blocks, in a read-only mapped file:
        the retriever pads what it uploads and keeps the caller's table
        as it is, for the refine (quantized tiers) and for
        ``dequantized_factors()`` (float32). Building allocates no
        second table on the host, and the gauge says how much of the
        resident rows is padding."""
        import tracemalloc

        n, k = 50_001, 256
        path = str(tmp_path / "table.npy")
        table = np.lib.format.open_memmap(
            path, mode="w+", dtype=np.float32, shape=(n, k))
        table[:] = np.random.default_rng(2).standard_normal(
            (n, k), np.float32)
        table.flush()
        del table
        mapped = np.load(path, mmap_mode="r")
        tracemalloc.start()
        try:
            r = ItemRetriever(
                mapped, precision=precision, component="mapped-rows")
            _, peak = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
        n_pad = r._n_pad
        assert n_pad == 25 * retrieval._ROW_BLOCK and r._y_dev.shape[0] == n_pad
        assert _family_value(
            "pio_padding_waste_ratio", site="retrieval_rows"
        ) == pytest.approx((n_pad - n) / n_pad)
        host = (
            r.dequantized_factors() if precision == "float32"
            else r._y_f32_host
        )
        assert host.shape == (n, k) and not host.flags.writeable
        assert np.shares_memory(host, mapped)
        # float32 uploads block views of the map; a quantized tier
        # stages its own (smaller) rows once
        staged = {"float32": 0, "bf16": 2, "int8": 1}[precision] * n_pad * k
        assert peak < staged + mapped.nbytes // 2, (peak, mapped.nbytes)
        s, i = r.topn(np.asarray(mapped[:3]), 16)
        ref_s, ref_i = naive_topn_reference(
            np.asarray(mapped), np.asarray(mapped[:3]), 16)
        assert np.array_equal(i, ref_i)
        r.free()

    @pytest.mark.parametrize("precision", ["float32", "int8"])
    def test_the_fused_programs_neither_pad_nor_slice(self, precision):
        """The mechanism itself, in the lowered programs: over a table
        of whole blocks no op pads a ``[B, rows]`` array and none slices
        one back (``_membership`` returns its grid as it is made,
        ``_top_k`` reshapes)."""
        import re

        import jax.numpy as jnp

        b, k, rows, widths = 8, 16, 20 * retrieval._ROW_BLOCK, (64, 16, 1)
        operand = jnp.zeros((b, k + sum(widths) + 3), jnp.int32)
        resident = (
            jnp.zeros((rows,), jnp.float32), jnp.zeros((rows,), bool),
            jnp.zeros((rows, 1), jnp.int32),
        )
        if precision == "float32":
            lowered = retrieval._fused_topn_single.lower(
                operand, jnp.zeros((rows, k), jnp.float32), *resident,
                n=4, positive_only=True, normalize="rows", widths=widths)
        else:
            lowered = retrieval._fused_topn_single_2s.lower(
                operand, jnp.zeros((rows, k), jnp.int8),
                jnp.zeros((rows,), jnp.float32), *resident,
                n=4, shortlist=8, positive_only=True, normalize=True,
                precision="int8", widths=widths)
        text = lowered.as_text()
        assert "stablehlo.dot_general" in text
        for line in text.splitlines():
            if not re.search(r"stablehlo\.(pad|slice|dynamic_slice)\b", line):
                continue
            shape = re.findall(r"tensor<([0-9x]+)x\w+>", line)[-1]
            assert np.prod([int(d) for d in shape.split("x")]) < rows, line

    def test_one_device_mesh_keeps_its_device_pin(self):
        """A `pio deploy --workers` worker pinned to ONE device arrives
        as a 1-device mesh; collapsing it to the fused single-device
        path must keep that device — dropping it would land every
        fleet worker's resident factors on the default device 0."""
        if len(jax.devices()) < 2:
            pytest.skip("needs 2 virtual devices")
        dev1 = jax.devices()[1]
        mesh = make_mesh({"data": 1}, [dev1])
        r = ItemRetriever(
            np.eye(6, 4, dtype=np.float32), mesh=mesh, component="pincheck"
        )
        assert r.mesh is None  # collapsed to the fused path
        assert r._y_dev.sharding.device_set == {dev1}
        assert r._allow_dev.sharding.device_set == {dev1}
        s, i = r.topn(np.ones((1, 4), np.float32), 3)
        ref_s, ref_i = naive_topn_reference(
            np.eye(6, 4, dtype=np.float32), np.ones((1, 4), np.float32), 3
        )
        assert np.array_equal(i, ref_i)
        r.set_excluded_ids(np.array([0]))  # mask re-upload stays pinned
        assert r._allow_dev.sharding.device_set == {dev1}

    def test_mask_refresh_metrics_and_semantics(self):
        rng = np.random.default_rng(5)
        Y = rng.standard_normal((20, 4)).astype(np.float32)
        r = ItemRetriever(Y, mesh=_mesh_or_none(2), component="maskmetrics")
        before_ref = _family_value(
            "pio_retrieval_mask_refresh_total",
            component="maskmetrics", outcome="refreshed",
        )
        before_unch = _family_value(
            "pio_retrieval_mask_refresh_total",
            component="maskmetrics", outcome="unchanged",
        )
        assert r.set_excluded_ids(np.array([3, 4])) is True
        assert r.set_excluded_ids(np.array([4, 3])) is False  # same set
        assert r.set_excluded_ids(np.array([5])) is True
        assert (
            _family_value(
                "pio_retrieval_mask_refresh_total",
                component="maskmetrics", outcome="refreshed",
            )
            - before_ref
            == 2
        )
        assert (
            _family_value(
                "pio_retrieval_mask_refresh_total",
                component="maskmetrics", outcome="unchanged",
            )
            - before_unch
            == 1
        )
        q = rng.standard_normal((1, 4)).astype(np.float32)
        _, i = r.topn(q, 19)
        assert 5 not in i[0][: int((_[0] > -np.inf).sum())]

    def test_mesh_topn_takes_no_barrier_and_still_samples_skew(
        self, monkeypatch
    ):
        """The sharded program and the merge run back to back: nothing
        waits on the shards' candidates between them, and the skew gauge
        is still set, from candidates fetched after the answer."""
        rng = np.random.default_rng(6)
        Y = rng.standard_normal((16, 4)).astype(np.float32)
        r = ItemRetriever(Y, mesh=_mesh_or_none(2), component="timing")
        skew = retrieval._m_shard_skew().labels(kind="candidates")
        skew.set(-1.0)
        barriers = []
        real = jax.block_until_ready
        monkeypatch.setattr(
            jax, "block_until_ready",
            lambda x: barriers.append(x) or real(x),
        )
        r.topn(rng.standard_normal((2, 4)).astype(np.float32), 4)
        assert barriers == []
        assert skew.value >= 1.0  # the first batch is sampled
        names = {f.name for f in metrics_mod.get_registry().families()}
        assert not names & {"pio_retrieval_shard_topk_seconds",
                            "pio_retrieval_merge_seconds"}

    @pytest.mark.parametrize("path", ["single", "mesh", "serving_factors"])
    def test_upload_is_recorded_inside_dispatch(self, path, monkeypatch):
        from predictionio_tpu.ops.als import ServingFactors
        from predictionio_tpu.utils import tracing

        opened, seen = [], []

        class Recording(tracing.stage):
            __slots__ = ()

            def __enter__(self):
                seen.append((self.name, tuple(opened)))
                opened.append(self.name)
                return super().__enter__()

            def __exit__(self, *exc):
                opened.pop()
                return super().__exit__(*exc)

        rng = np.random.default_rng(7)
        Y = rng.standard_normal((32, 4)).astype(np.float32)
        q = rng.standard_normal((3, 4)).astype(np.float32)
        if path == "serving_factors":
            run = ServingFactors(q, Y).topn_by_rows
        else:
            run = ItemRetriever(
                Y, mesh=_mesh_or_none(2 if path == "mesh" else 1),
                component="upload",
            ).topn
        monkeypatch.setattr(tracing, "stage", Recording)
        with tracing.stage_totals() as totals:
            run(q, 4)
        assert (tracing.UPLOAD, (tracing.DISPATCH,)) in seen
        assert [name for name, _ in seen].count(tracing.UPLOAD) == 1
        assert totals[tracing.UPLOAD] <= totals[tracing.DISPATCH]


class _CountedPuts:
    """``jax.device_put`` as ``ops/retrieval.py`` calls it, counting the
    calls made while the block runs."""

    def __enter__(self):
        real = self.real = jax.device_put
        calls = self.calls = []

        def counting(x, *a, **kw):
            calls.append(np.shape(x))
            return real(x, *a, **kw)

        jax.device_put = counting
        return calls

    def __exit__(self, *exc):
        jax.device_put = self.real


def _transfers(component):
    return retrieval._m_operand_transfers().labels(component=component).value


class TestPackedOperand:
    """A batch's query rows, id lists, category codes and flags travel
    as one int32 buffer and one transfer; the program takes them apart."""

    @pytest.mark.parametrize("widths", [
        (1, 1, 1), (4, 1, 1), (8, 8, 4), (1024, 1, 1), (16, 1024, 2),
    ])
    def test_pack_then_unpack_is_bit_exact(self, widths):
        k, b, b_pad = 12, 5, 8
        sentinel = 2**24 + 11  # a catalog past what a float32 counts
        w_excl, w_incl, w_cat = widths
        rng = np.random.default_rng(sum(widths))
        q = rng.standard_normal((b, k)).astype(np.float32)
        q[0, :6] = [-0.0, 0.0, 1e-40, -1e-45, np.inf, -np.inf]
        # bits that are small integers (subnormals as floats), and the
        # largest and smallest normal magnitudes
        q[1, :5] = np.array([1, 2, 7, 2**23 - 1, 2**23], np.int32).view(
            np.float32)
        q[1, 5:7] = [np.finfo(np.float32).max, np.finfo(np.float32).tiny]
        exclude = [
            np.array([2**24, 2**24 + 1, 5][:w_excl]), None,
            np.zeros(0, np.int64),
            rng.integers(0, sentinel, w_excl), [sentinel - 1][:w_excl],
        ]
        include = [
            None, np.array([2**24 + 3, 0][:w_incl]), np.zeros(0, np.int64),
            None, rng.integers(0, sentinel, w_incl),
        ]
        categories = [
            None, np.arange(w_cat), np.zeros(0, np.int32), None, [9437][:w_cat]
        ]
        row_norm = np.array([True, False, False, True, True])
        buf = retrieval._pack_operand(
            q, b_pad, widths, sentinel, exclude, include, categories, row_norm)
        assert buf.dtype == np.int32
        assert buf.shape == (b_pad, k + sum(widths) + 3)
        got = [np.asarray(a) for a in jax.jit(
            retrieval._unpack_operand, static_argnums=(1, 2)
        )(buf, k, widths)]
        rows, excl, incl, has_incl, cats, has_cat, norm = got
        assert rows.dtype == np.float32 and excl.dtype == np.int32
        np.testing.assert_array_equal(
            rows[:b].view(np.int32), q.view(np.int32))  # bit for bit
        assert not rows[b:].view(np.int32).any()
        for lists, block, pad in (
            (exclude, excl, sentinel), (include, incl, sentinel),
            (categories, cats, -2),
        ):
            for r in range(b_pad):
                a = lists[r] if r < b and lists[r] is not None else []
                assert block[r, : len(a)].tolist() == list(a)
                assert (block[r, len(a):] == pad).all()
        assert has_incl.tolist() == [
            False, True, True, False, True, False, False, False]
        assert has_cat.tolist() == [
            False, True, True, False, True, False, False, False]
        assert norm.tolist() == [
            True, False, False, True, True, False, False, False]

    def test_wide_parts_start_on_lane_multiples_at_the_serving_widths(self):
        for widths in ((1024, 1, 1), (8192, 1, 1), (1024, 1024, 1),
                       (8192, 1024, 4)):
            rows, excl, incl, cats, flags = retrieval._operand_slices(
                512, widths)
            assert rows.start == 0 and excl.start % 128 == 0
            assert incl.start % 128 == 0
            assert flags.stop - flags.start == 3
            assert flags.stop == 512 + sum(widths) + 3

    @pytest.mark.parametrize("branch", ["float32", "int8", "bf16", "mesh"])
    def test_topn_is_one_transfer_a_call(self, branch):
        rng = np.random.default_rng(3)
        Y = rng.standard_normal((96, 8)).astype(np.float32)
        codes = rng.integers(0, 5, (96, 1)).astype(np.int32)
        component = f"oneput-{branch}"
        r = ItemRetriever(
            Y, mesh=_mesh_or_none(4) if branch == "mesh" else None,
            precision=branch if branch in ("int8", "bf16") else "float32",
            component=component, category_codes=codes, category_width=2,
        )
        q = rng.standard_normal((3, 8)).astype(np.float32)
        calls = [
            {},
            {"exclude": [np.arange(9), None, np.arange(40)]},
            {"include": [None, np.arange(20, 60), None],
             "categories": [np.array([1, 3]), None, None],
             "normalize": [True, False, True], "positive_only": True},
        ]
        for kw in calls:
            before = _transfers(component)
            with _CountedPuts() as puts:
                r.topn(q, 4, **kw)
            assert len(puts) == 1, puts
            assert _transfers(component) - before == 1

    def test_warm_compiles_the_ladder_and_live_batches_add_none(self):
        """A catalog size no other test of this file uses: every
        executable met here is compiled by this test's own warm()."""
        rng = np.random.default_rng(8)
        n_items, k = 311, 8
        Y = rng.standard_normal((n_items, k)).astype(np.float32)
        codes = rng.integers(0, 5, (n_items, 1)).astype(np.int32)
        r = ItemRetriever(
            Y, component="packed-ladder", category_codes=codes,
            category_width=2, exclude_ladder=(16, 64),
            include_ladder=(1, 32), max_batch=16,
        )
        size0 = retrieval._fused_topn_single._cache_size()
        r.warm(n=16, flag_combos=((True, "rows"),))
        assert r.ladder_size() == 2 * 2 * 2
        assert (
            retrieval._fused_topn_single._cache_size() - size0
            == r.ladder_size()
        )
        live = [  # (batch, exclude, include, categories)
            (1, None, None, None),
            (3, [np.arange(5), None, np.arange(60)], None, None),
            (9, None, [np.arange(30)] + [None] * 8, None),
            (16, [np.arange(17)] * 16, [np.arange(3)] * 16,
             [np.array([2])] * 16),
            (2, None, None, [np.array([0, 4]), None]),
        ]
        before = _transfers("packed-ladder")
        for b, exclude, include, categories in live:
            q = rng.standard_normal((b, k)).astype(np.float32)
            cosine = rng.random(b) < 0.5
            s, i = r.topn(
                q, 16, exclude=exclude, include=include,
                categories=categories, positive_only=True, normalize=cosine,
            )
            # the reference knows no categories and no per-row flags:
            # categories become a whitelist, each row runs on its own
            for row in range(b):
                wl = None if include is None else include[row]
                if categories is not None and categories[row] is not None:
                    member = np.flatnonzero(
                        np.isin(codes[:, 0], categories[row]))
                    wl = member if wl is None else np.intersect1d(wl, member)
                es, ei = naive_topn_reference(
                    Y, q[row: row + 1], 16,
                    exclude=None if exclude is None else [exclude[row]],
                    include=None if wl is None else [wl],
                    positive_only=True, normalize=bool(cosine[row]),
                )
                alive = es[0] > -np.inf
                assert (s[row] > -np.inf).sum() == alive.sum()
                np.testing.assert_array_equal(i[row][alive], ei[0][alive])
        assert _transfers("packed-ladder") - before == len(live)
        assert (
            retrieval._fused_topn_single._cache_size() - size0
            == r.ladder_size()
        )
        with pytest.raises(ValueError, match="over the ladder's top"):
            r.topn(q, 16, exclude=[np.arange(65)] + [None] * (len(q) - 1))
        with pytest.raises(ValueError, match="3 categories in one query"):
            r.topn(q, 16, categories=[np.arange(3)] + [None] * (len(q) - 1))


class TestConstraintCache:
    def _storage_with_constraint(self, items):
        s = storage_mod.memory_storage()
        storage_mod.set_storage(s)
        app_id = s.get_meta_data_apps().insert(App(id=0, name="capp"))
        ev = s.get_l_events()
        ev.init(app_id)
        ev.insert(
            Event(
                event="$set", entity_type="constraint",
                entity_id="unavailableItems",
                properties=DataMap({"items": list(items)}),
            ),
            app_id,
        )
        return s, app_id

    def test_miss_then_hit_counting(self, mem_storage):
        from predictionio_tpu.data.constraints import ConstraintCache

        s, _ = self._storage_with_constraint(["x", "y"])
        try:
            cache = ConstraintCache("capp", ttl_s=60.0, storage=s)
            miss0 = _family_value(
                "pio_constraint_cache_total", outcome="miss"
            )
            hit0 = _family_value(
                "pio_constraint_cache_total", outcome="hit"
            )
            assert cache.get() == {"x", "y"}  # first read: miss
            assert cache.get() == {"x", "y"}  # cached: hit
            assert cache.get() == {"x", "y"}
            assert (
                _family_value("pio_constraint_cache_total", outcome="miss")
                - miss0
                == 1
            )
            assert (
                _family_value("pio_constraint_cache_total", outcome="hit")
                - hit0
                == 2
            )
        finally:
            storage_mod.set_storage(None)

    def test_stale_get_serves_cached_and_never_blocks(self):
        """A store stall past the TTL cannot block a batch: get()
        returns the cached set immediately and refreshes out-of-band."""
        from predictionio_tpu.data.constraints import ConstraintCache

        release = threading.Event()
        calls = []

        def slow_reader():
            calls.append(time.monotonic())
            if len(calls) > 1:
                release.wait(10.0)  # the 'stalled store'
            return frozenset({"a"}) if len(calls) == 1 else frozenset(
                {"a", "b"}
            )

        cache = ConstraintCache("app", ttl_s=0.01, reader=slow_reader)
        assert cache.get() == {"a"}
        time.sleep(0.05)  # expire the TTL
        t0 = time.monotonic()
        assert cache.get() == {"a"}  # stale value served instantly
        assert time.monotonic() - t0 < 1.0
        changed = []
        cache.on_change(lambda items: changed.append(set(items)))
        release.set()
        deadline = time.monotonic() + 5.0
        while not changed and time.monotonic() < deadline:
            time.sleep(0.01)
        assert changed == [{"a", "b"}]
        assert cache.get() == {"a", "b"}

    def test_error_serves_cached_and_counts(self):
        from predictionio_tpu.data.constraints import ConstraintCache

        state = {"fail": False}

        def reader():
            if state["fail"]:
                raise RuntimeError("store down")
            return frozenset({"k"})

        cache = ConstraintCache("app", ttl_s=0.0, reader=reader)
        assert cache.get() == {"k"}
        state["fail"] = True
        err0 = _family_value("pio_constraint_cache_total", outcome="error")
        assert cache.get() == {"k"}  # cached value survives the error
        assert (
            _family_value("pio_constraint_cache_total", outcome="error")
            - err0
            == 1
        )

    def test_failed_first_read_error_primes(self):
        """A store that is down at deploy must not leave the cache
        unprimed — that would put a blocking inline read on EVERY
        batch. The failed first read primes the empty set; the TTL tick
        retries out-of-band and listeners fire once the store
        recovers."""
        from predictionio_tpu.data.constraints import ConstraintCache

        state = {"fail": True}
        calls = []

        def reader():
            calls.append(1)
            if state["fail"]:
                raise RuntimeError("store down at deploy")
            return frozenset({"z"})

        cache = ConstraintCache("app", ttl_s=0.2, reader=reader)
        assert cache.get() == frozenset()  # failed prime -> empty set
        n_after_prime = len(calls)
        assert cache.get() == frozenset()  # HIT: no inline read per batch
        assert len(calls) == n_after_prime
        changed = []
        cache.on_change(lambda items: changed.append(set(items)))
        state["fail"] = False
        time.sleep(0.25)  # expire the TTL
        deadline = time.monotonic() + 5.0
        while not changed and time.monotonic() < deadline:
            cache.get()  # the TTL tick that kicks the background retry
            time.sleep(0.01)
        assert changed == [{"z"}]
        assert cache.get() == {"z"}


@pytest.fixture(scope="module")
def ecomm_world():
    """One trained ecommerce model + populated store shared by the
    serving-parity tests (module-scoped: training is the expensive
    part)."""
    s = storage_mod.memory_storage()
    storage_mod.set_storage(s)
    app_id = s.get_meta_data_apps().insert(App(id=0, name="ecapp"))
    ev = s.get_l_events()
    ev.init(app_id)
    t0 = dt.datetime(2026, 7, 1, tzinfo=dt.timezone.utc)

    def put(event, etype, eid, target=None, props=None, t=t0):
        ev.insert(
            Event(
                event=event, entity_type=etype, entity_id=eid,
                target_entity_type="item" if target else None,
                target_entity_id=target,
                properties=DataMap(props or {}), event_time=t,
            ),
            app_id,
        )

    rng = np.random.default_rng(3)
    for i in range(12):
        put(
            "$set", "item", f"i{i}",
            props={
                "categories": ["electronics"] if i < 6 else ["books"]
            },
        )
    for uid in range(20):
        put("$set", "user", f"u{uid}")
        pref = 0 if uid % 2 == 0 else 6
        for j in range(5):
            put(
                "rate", "user", f"u{uid}",
                target=f"i{pref + int(rng.integers(0, 5))}",
                props={"rating": float(rng.integers(3, 6))},
                t=t0 + dt.timedelta(minutes=j),
            )
    put("view", "user", "newbie", target="i0")
    put(
        "$set", "constraint", "unavailableItems",
        props={"items": ["i2"]},
    )

    from predictionio_tpu.models.ecommerce.engine import (
        DataSource,
        DataSourceParams,
        ECommAlgorithm,
        ECommAlgorithmParams,
        Preparator,
    )

    ctx = WorkflowContext(mode="training", storage=s)
    td = DataSource(DataSourceParams(app_name="ecapp")).read_training(ctx)
    pd = Preparator().prepare(ctx, td)
    algo = ECommAlgorithm(
        ECommAlgorithmParams(
            app_name="ecapp", rank=8, num_iterations=10, seed=4,
            unseen_only=True, seen_events=("rate",),
        )
    )
    model = algo.train(ctx, pd)
    yield s, app_id, algo, model
    storage_mod.set_storage(None)


class TestECommerceRetrievalServing:
    QUERY_MIX = [
        dict(user="u0", num=5),
        dict(user="u1", num=3, black_list=("i7",)),
        dict(user="u2", num=8, categories=("books",)),
        dict(user="u3", num=4, white_list=("i0", "i1", "i2", "i9")),
        dict(user="newbie", num=5),       # unknown user: cosine fallback
        dict(user="ghost", num=5),        # no history at all
        dict(user="u4", num=5, white_list=()),  # empty whitelist
    ]

    @pytest.mark.parametrize("shards", [1, 4])
    def test_device_path_matches_host_path(self, ecomm_world, shards):
        """The full serving semantics — unavailable constraint (resident
        mask), seen-item exclusion (unseen_only), blacklist, categories,
        whitelist, unknown-user cosine fallback — byte-identical item
        lists between the prepared (on-device) and legacy (host
        post-filter) paths, on 1 device and on a 4-way mesh."""
        from predictionio_tpu.models.ecommerce.engine import Query

        _, _, algo, model = ecomm_world
        mesh = _mesh_or_none(shards)
        legacy = copy.deepcopy(model)
        prepped = algo.prepare_serving(
            workflow_context(mode="Serving", mesh=mesh)
            if mesh is not None
            else None,
            copy.deepcopy(model),
        )
        assert prepped._retriever is not None
        queries = [Query(**kw) for kw in self.QUERY_MIX]
        dev = dict(algo.batch_predict(prepped, list(enumerate(queries))))
        host = dict(algo.batch_predict(legacy, list(enumerate(queries))))
        for i in range(len(queries)):
            assert [x.item for x in dev[i].item_scores] == [
                x.item for x in host[i].item_scores
            ], queries[i]
            np.testing.assert_allclose(
                [x.score for x in dev[i].item_scores],
                [x.score for x in host[i].item_scores],
                rtol=1e-4,
            )

    def test_constraint_change_refreshes_resident_mask(self, ecomm_world):
        from predictionio_tpu.models.ecommerce.engine import Query

        s, app_id, algo, model = ecomm_world
        prepped = algo.prepare_serving(None, copy.deepcopy(model))
        baseline = algo.predict(prepped, Query(user="u0", num=3))
        banned = baseline.item_scores[0].item
        s.get_l_events().insert(
            Event(
                event="$set", entity_type="constraint",
                entity_id="unavailableItems",
                properties=DataMap({"items": ["i2", banned]}),
            ),
            app_id,
        )
        # drive the out-of-band refresh deterministically (in production
        # the TTL kick from a later batch does this on a background
        # thread; refresh() is the same code path, inline)
        assert prepped._constraints.refresh() is True
        result = algo.predict(prepped, Query(user="u0", num=3))
        assert all(x.item != banned for x in result.item_scores)

    def test_store_stall_does_not_block_serving(self, ecomm_world):
        """The satellite fix: predict_batch never reads the constraint
        entity inline once the cache is primed — a wedged store changes
        nothing about batch latency."""
        from predictionio_tpu.models.ecommerce.engine import Query

        _, _, algo, model = ecomm_world
        prepped = algo.prepare_serving(None, copy.deepcopy(model))

        def wedged():
            raise AssertionError(
                "serving read the constraint store inline"
            )

        # cache primed at prepare_serving; replace the reader with a
        # tripwire and expire the TTL: get() must serve cached and only
        # the BACKGROUND thread may touch (and trip) the reader
        prepped._constraints._reader = wedged
        prepped._constraints._loaded_at = -1e9
        result = algo.predict(prepped, Query(user="u0", num=3))
        assert result.item_scores


class TestSimilarProductRetrievalServing:
    @pytest.fixture(scope="class")
    def sp_world(self):
        s = storage_mod.memory_storage()
        storage_mod.set_storage(s)
        app_id = s.get_meta_data_apps().insert(App(id=0, name="spapp"))
        ev = s.get_l_events()
        ev.init(app_id)
        rng = np.random.default_rng(7)
        for i in range(15):
            ev.insert(
                Event(
                    event="$set", entity_type="item", entity_id=f"p{i}",
                    properties=DataMap(
                        {"categories": ["a"] if i < 8 else ["b"]}
                    ),
                ),
                app_id,
            )
        for uid in range(25):
            for _ in range(6):
                ev.insert(
                    Event(
                        event="view", entity_type="user",
                        entity_id=f"v{uid}",
                        target_entity_type="item",
                        target_entity_id=f"p{int(rng.integers(0, 15))}",
                    ),
                    app_id,
                )
        from predictionio_tpu.models.similarproduct import engine as sp

        ctx = WorkflowContext(mode="training", storage=s)
        td = sp.DataSource(
            sp.DataSourceParams(app_name="spapp")
        ).read_training(ctx)
        pd = sp.Preparator().prepare(ctx, td)
        algo = sp.ALSAlgorithm(
            sp.ALSAlgorithmParams(rank=8, num_iterations=10, seed=1)
        )
        model = algo.train(ctx, pd)
        yield algo, model
        storage_mod.set_storage(None)

    @pytest.mark.parametrize("shards", [1, 2])
    def test_similar_parity(self, sp_world, shards):
        from predictionio_tpu.models.similarproduct.engine import Query

        algo, model = sp_world
        mesh = _mesh_or_none(shards)
        legacy = copy.deepcopy(model)
        prepped = algo.prepare_serving(
            workflow_context(mode="Serving", mesh=mesh)
            if mesh is not None
            else None,
            copy.deepcopy(model),
        )
        assert prepped._retriever is not None
        queries = [
            Query(items=("p0", "p3"), num=5),
            Query(items=("p1",), num=4, black_list=("p2",)),
            Query(items=("p5", "p9"), num=6, categories=("b",)),
            Query(items=("p4",), num=3, white_list=("p6", "p7", "p8")),
            Query(items=("zzz",), num=3),  # no factors -> empty
        ]
        dev = dict(algo.batch_predict(prepped, list(enumerate(queries))))
        for i, q in enumerate(queries):
            host = legacy.similar(q)
            assert [x.item for x in dev[i].item_scores] == [
                x.item for x in host.item_scores
            ], q
            np.testing.assert_allclose(
                [x.score for x in dev[i].item_scores],
                [x.score for x in host.item_scores],
                rtol=1e-4,
            )
        # query items never come back
        for i, q in enumerate(queries):
            assert not set(q.items) & {
                x.item for x in dev[i].item_scores
            }


class TestHotReloadResidentFactors:
    def test_pickle_roundtrip_then_prepare_deploy_rebuilds(
        self, ecomm_world
    ):
        """Model persistence drops device state by contract
        (__getstate__); prepare_deploy must rebuild the resident
        retriever, and serving through the rebuilt state must match."""
        import pickle

        from predictionio_tpu.models.ecommerce.engine import Query

        _, _, algo, model = ecomm_world
        prepped = algo.prepare_serving(None, copy.deepcopy(model))
        before = algo.predict(prepped, Query(user="u0", num=3))
        revived = pickle.loads(pickle.dumps(prepped))
        assert revived._retriever is None  # device state never pickles
        revived = algo.prepare_serving(None, revived)
        assert revived._retriever is not None
        assert revived._retriever.resident_bytes > 0
        after = algo.predict(revived, Query(user="u0", num=3))
        assert [s.item for s in after.item_scores] == [
            s.item for s in before.item_scores
        ]

    def test_engine_server_reload_keeps_factors_resident(
        self, ecomm_world
    ):
        """The regression gate: after an EngineServer hot reload the NEW
        prepared serving state has its own device-resident factors (no
        silent fallback to the host path) and the OLD snapshot still
        serves in-flight queries."""
        import datetime as _dt
        import json as _json

        from predictionio_tpu.api.engine_server import (
            EngineServer,
            ServerConfig,
        )
        from predictionio_tpu.data.storage.base import EngineInstance
        from predictionio_tpu.models.ecommerce.engine import (
            ecommerce_engine,
        )
        from predictionio_tpu.workflow.core_workflow import CoreWorkflow

        s, _, _, _ = ecomm_world
        engine = ecommerce_engine()
        params = engine.jvalue_to_engine_params(
            {
                "datasource": {"params": {"app_name": "ecapp"}},
                "algorithms": [
                    {
                        "name": "ecomm",
                        "params": {
                            "app_name": "ecapp", "rank": 8,
                            "num_iterations": 5, "seed": 4,
                        },
                    }
                ],
            }
        )
        now = _dt.datetime.now(_dt.timezone.utc)
        iid = CoreWorkflow.run_train(
            engine, params,
            EngineInstance(
                id="", status="", start_time=now, end_time=now,
                engine_id="ec", engine_version="1",
                engine_variant="engine.json",
                engine_factory=(
                    "predictionio_tpu.models.ecommerce.engine."
                    "ECommerceEngineFactory"
                ),
            ),
            ctx=WorkflowContext(mode="training", storage=s),
        )
        assert iid
        server = EngineServer(
            engine, ServerConfig(port=0), storage=s
        ).start()
        try:
            old_model = server.api.deployed.models[0]
            assert old_model._retriever is not None
            old_bytes = old_model._retriever.resident_bytes

            def query():
                status, body, _ = server.api.handle(
                    "POST", "/queries.json",
                    body=_json.dumps({"user": "u0", "num": 3}).encode(),
                )
                assert status == 200
                return [x["item"] for x in body["itemScores"]]

            before = query()
            server.reload()
            fresh_model = server.api.deployed.models[0]
            assert fresh_model is not old_model
            assert fresh_model._retriever is not None
            assert fresh_model._retriever is not old_model._retriever
            assert fresh_model._retriever.resident_bytes == old_bytes
            assert query() == before
            # the old snapshot (in-flight queries during a reload) still
            # has ITS resident factors and still serves
            from predictionio_tpu.models.ecommerce.engine import Query

            algo = server.api.deployed.algorithms[0]
            old_result = algo.predict(old_model, Query(user="u0", num=3))
            assert [x.item for x in old_result.item_scores] == before
        finally:
            server.shutdown()
