"""Quantized residency + two-stage approximate retrieval
(ops/retrieval.py ``precision=bf16|int8``): recall@n >= 0.999 against
``naive_topn_reference`` across 1/2/4-way shard counts with full mask
semantics, exact-score and id parity through the host refinement,
float tie-break edges at the shortlist boundary, the promotion swap
float32<->int8 leaving the ledger scope at zero, the quantized-footprint
mask re-upload regression (reconcile reads ~zero drift), the
bytes-per-item gauge, and warm()'s precision x shortlist ladder.
"""

import jax
import numpy as np
import pytest

from predictionio_tpu.ops.retrieval import (
    ItemRetriever,
    dequantize_rows_int8,
    naive_topn_reference,
    pow2_topk_width,
    quantize_rows_int8,
)
from predictionio_tpu.parallel import make_mesh
from predictionio_tpu.utils import device_ledger as dl
from predictionio_tpu.utils import metrics as metrics_mod
from tests import retrieval_blocks as blocks


def _mesh_or_none(shards):
    if shards == 1:
        return None
    if len(jax.devices()) < shards:
        pytest.skip(f"needs {shards} virtual devices")
    return make_mesh({"data": shards}, jax.devices()[:shards])


def _recall(idx, ref_idx):
    rows, n = ref_idx.shape
    hits = sum(
        len(set(idx[r].tolist()) & set(ref_idx[r].tolist()))
        for r in range(rows)
    )
    return hits / (rows * n)


def _gauge(name, **labels):
    samples = metrics_mod.parse_exposition(
        metrics_mod.get_registry().render()
    )
    inner = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    return samples.get(f"{name}{{{inner}}}", 0.0)


class TestQuantization:
    def test_int8_roundtrip_error_bounded(self):
        rng = np.random.default_rng(0)
        f = rng.standard_normal((100, 16)).astype(np.float32)
        q, scale = quantize_rows_int8(f)
        assert q.dtype == np.int8 and scale.dtype == np.float32
        deq = dequantize_rows_int8(q, scale)
        # symmetric per-row: error bounded by half a quantization step
        step = np.abs(f).max(axis=1, keepdims=True) / 127.0
        assert np.all(np.abs(deq - f) <= step / 2 + 1e-7)

    def test_zero_rows_stay_zero(self):
        f = np.zeros((3, 4), np.float32)
        q, scale = quantize_rows_int8(f)
        assert np.all(q == 0) and np.all(scale == 1.0)
        assert np.all(dequantize_rows_int8(q, scale) == 0)

    def test_invalid_params_rejected(self):
        Y = np.eye(4, 3, dtype=np.float32)
        with pytest.raises(ValueError, match="precision"):
            ItemRetriever(Y, component="badprec", precision="fp8")
        with pytest.raises(ValueError, match="shortlist_mult"):
            ItemRetriever(Y, component="badmult", shortlist_mult=0)


class TestQuantizedRecall:
    """recall@n >= 0.999 and exact-score parity vs the float32 naive
    reference: the host refinement rescores the merged c.n candidates
    against the ORIGINAL factor rows, so surviving ids carry exact
    scores and only whole-shortlist misses can cost recall."""

    @pytest.mark.parametrize("shards", [1, 2, 4])
    @pytest.mark.parametrize("precision", ["bf16", "int8"])
    def test_recall_and_exact_scores(self, shards, precision):
        mesh = _mesh_or_none(shards)
        rng = np.random.default_rng(7 + shards)
        N, k, B, n = 3001, 16, 24, 25  # 3001 does not divide 2 or 4
        Y = rng.standard_normal((N, k)).astype(np.float32)
        q = rng.standard_normal((B, k)).astype(np.float32)
        r = ItemRetriever(
            Y, mesh=mesh, component=f"qrec-{precision}{shards}",
            precision=precision,
        )
        try:
            for positive_only in (False, True):
                for normalize in (False, True):
                    s, i = r.topn(
                        q, n, positive_only=positive_only,
                        normalize=normalize,
                    )
                    es, ei = naive_topn_reference(
                        Y, q, n, positive_only=positive_only,
                        normalize=normalize,
                    )
                    assert _recall(i, ei) >= 0.999
                    # surviving ids are rescored against the original
                    # rows: exact scores, not dequantized approximations
                    live = es > -np.inf
                    np.testing.assert_array_equal(i[live], ei[live])
                    np.testing.assert_allclose(
                        s[live], es[live], rtol=1e-5, atol=1e-6
                    )
        finally:
            r.free()

    @pytest.mark.parametrize("shards", [1, 2])
    def test_mask_semantics_survive_quantized_path(self, shards):
        mesh = _mesh_or_none(shards)
        rng = np.random.default_rng(11)
        N, k, n = 257, 8, 12
        Y = rng.standard_normal((N, k)).astype(np.float32)
        q = rng.standard_normal((5, k)).astype(np.float32)
        exclude = [
            None, np.array([0, 1, 2]), np.array([], np.int64),
            np.arange(200), None,
        ]
        include = [
            None, None, np.array([3, 4, 5, 9]), None,
            np.array([], np.int64),
        ]
        r = ItemRetriever(
            Y, mesh=mesh, component=f"qmasks{shards}", precision="int8",
        )
        try:
            assert r.set_excluded_ids(np.array([7, 8])) is True
            s, i = r.topn(q, n, exclude=exclude, include=include)
            es, ei = naive_topn_reference(
                Y, q, n,
                exclude=[
                    np.union1d(e, [7, 8]) if e is not None
                    else np.array([7, 8])
                    for e in exclude
                ],
                include=include,
            )
            live = es > -np.inf
            assert (s > -np.inf).sum() == live.sum()
            np.testing.assert_array_equal(i[live], ei[live])
            np.testing.assert_allclose(
                s[live], es[live], rtol=1e-5, atol=1e-6
            )
        finally:
            r.free()

    @pytest.mark.parametrize("mask", blocks.MASKS)
    @pytest.mark.parametrize("n_items", blocks.ITEM_COUNTS)
    @pytest.mark.parametrize("precision", ["bf16", "int8"])
    @pytest.mark.parametrize("shards", [1, 4])
    def test_whole_blocks_change_no_answer(
            self, shards, precision, n_items, mask):
        """The quantized tiers over a table padded to whole blocks at
        build: the ids and order of the float32 reference (the host
        refine reads the caller's rows, which have no padding row)."""
        blocks.check(n_items, precision, mask, _mesh_or_none(shards))

    def test_k_exceeds_live_candidates_quantized(self):
        rng = np.random.default_rng(3)
        Y = rng.standard_normal((10, 4)).astype(np.float32)
        r = ItemRetriever(Y, component="qedge", precision="int8")
        try:
            s, i = r.topn(
                rng.standard_normal((1, 4)).astype(np.float32), 8,
                exclude=[np.arange(7)],
            )
            assert int((s[0] > -np.inf).sum()) == 3
            assert set(i[0][:3]) == {7, 8, 9}
        finally:
            r.free()


class TestQuantizedPackedOperand:
    """The two-stage kernels take the batch's one packed operand apart
    as the float32 program does: flags, category codes and id lists
    arrive whole, and a call is one transfer."""

    @pytest.mark.parametrize("precision", ["bf16", "int8"])
    @pytest.mark.parametrize("shards", [1, 2, 4])
    def test_flags_codes_and_lists_arrive_in_one_transfer(
        self, shards, precision
    ):
        mesh = _mesh_or_none(shards)
        rng = np.random.default_rng(40 + shards)
        N, k, n = 203, 8, 8
        Y = rng.standard_normal((N, k)).astype(np.float32)
        codes = rng.integers(0, 6, (N, 1)).astype(np.int32)
        q = rng.standard_normal((6, k)).astype(np.float32)
        kw = dict(
            exclude=[None, np.arange(90), None, np.array([5]), None, None],
            include=[None, None, np.arange(40, 160), None,
                     np.zeros(0, np.int64), None],
            categories=[None, None, np.array([1, 4]), np.array([2]), None,
                        np.zeros(0, np.int32)],
            positive_only=True,
            normalize=[True, False, True, False, False, True],
        )
        component = f"qpacked-{precision}-{shards}"
        exact = ItemRetriever(
            Y, component=f"{component}-f32", category_codes=codes,
            category_width=2,
        )
        r = ItemRetriever(
            Y, mesh=mesh, component=component, precision=precision,
            category_codes=codes, category_width=2, shortlist_mult=8,
        )
        transfers = lambda: _gauge(
            "pio_retrieval_operand_transfers_total", component=component)
        try:
            before = transfers()
            s, i = r.topn(q, n, **kw)
            assert transfers() - before == 1
            es, ei = exact.topn(q, n, **kw)
            live = es > -np.inf
            assert (s > -np.inf).sum() == live.sum()
            # rows 4 (an empty whitelist) and 5 (an empty category list)
            # have no candidate at all
            assert not live[4:].any() and live[:4].any(axis=1).all()
            np.testing.assert_array_equal(i[live], ei[live])
            np.testing.assert_allclose(
                s[live], es[live], rtol=1e-5, atol=1e-6
            )
        finally:
            r.free()
            exact.free()


class TestShortlistBoundaryTies:
    """Float tie-break at the shortlist boundary: a tie group wider
    than the device candidate width must resolve exactly as the naive
    reference does (lowest global id wins), through stage-1's top_k,
    the cross-shard merge, and the host refinement's lexsort."""

    @pytest.mark.parametrize("shards", [1, 2, 4])
    def test_tied_scores_break_to_lowest_ids(self, shards):
        mesh = _mesh_or_none(shards)
        rng = np.random.default_rng(42)
        N, k, n = 400, 8, 16
        # rows 0..199 identical (one big tie group, wider than the
        # c.n = 64 device candidate list), the rest strictly weaker
        strong = rng.standard_normal(k).astype(np.float32)
        Y = np.tile(strong, (N, 1)).astype(np.float32)
        Y[200:] = 0.1 * rng.standard_normal((200, k)).astype(np.float32)
        q = np.tile(strong, (3, 1)).astype(np.float32)
        r = ItemRetriever(
            Y, mesh=mesh, component=f"qties{shards}", precision="int8",
        )
        try:
            s, i = r.topn(q, n)
            es, ei = naive_topn_reference(Y, q, n)
            np.testing.assert_array_equal(i, ei)
            np.testing.assert_array_equal(
                np.sort(i, axis=1), np.tile(np.arange(n), (3, 1))
            )
            np.testing.assert_allclose(s, es, rtol=1e-5)
        finally:
            r.free()


class TestQuantizedLedger:
    def test_resident_bytes_reduction(self):
        rng = np.random.default_rng(5)
        Y = rng.standard_normal((2000, 32)).astype(np.float32)
        r32 = ItemRetriever(Y, component="qcap32", precision="float32")
        r8 = ItemRetriever(Y, component="qcap8", precision="int8")
        try:
            assert r32.resident_bytes / r8.resident_bytes >= 3.0
        finally:
            r32.free()
            r8.free()

    def test_ledger_attributes_per_precision(self):
        led = dl.get_ledger()
        rng = np.random.default_rng(6)
        Y = rng.standard_normal((2000, 16)).astype(np.float32)
        r = ItemRetriever(Y, component="qattr", precision="int8")
        try:
            assert led.total_bytes(component="qattr/int8") > 0
            assert led.total_bytes(component="qattr-mask") > 0
            # the plain component name carries NO factor bytes — the
            # per-precision suffix is the attribution
            assert led.total_bytes(component="qattr") == 0
            bpi = _gauge(
                "pio_retrieval_bytes_per_item",
                component="qattr", precision="int8",
            )
            # int8 rank-16: ~16B rows + 4B scale + 4B norm (+ pad/mask)
            assert 0 < bpi < 16 * 4  # strictly below the f32 rows alone
        finally:
            r.free()
        assert led.total_bytes(component="qattr/int8") == 0
        assert _gauge(
            "pio_retrieval_bytes_per_item",
            component="qattr", precision="int8",
        ) == 0.0

    def test_promotion_swap_f32_int8_releases_scope(self):
        """The promotion contract on a precision flip: deploy v2 (int8)
        while v1 (float32) serves, then drain/release v1 — v1's ledger
        scope must read zero, and the reverse rollback direction must
        too (the displaced int8 instance frees its quantized buffers)."""
        led = dl.get_ledger()
        rng = np.random.default_rng(8)
        Y = rng.standard_normal((800, 16)).astype(np.float32)
        scope1 = led.scope("qswap-v1")
        with scope1.activate():
            v1 = ItemRetriever(Y, component="qswap", precision="float32")
        scope2 = led.scope("qswap-v2")
        with scope2.activate():
            v2 = ItemRetriever(Y, component="qswap", precision="int8")
        assert scope1.bytes() > 0 and scope2.bytes() > 0
        v1.free()
        assert scope1.check_released() == 0
        # rollback direction: the int8 instance is displaced next
        v2.free()
        assert scope2.check_released() == 0

    def test_mask_reupload_resets_quantized_footprint(self):
        """The satellite-6 regression: a constraint-driven mask
        re-upload re-`set`s the ledger mask handle AND the resident
        gauge from the FRESH device footprint — so the ledger total
        keeps matching the actual device arrays (what reconcile()
        probes) instead of any prepare-time f32 staging size."""
        led = dl.get_ledger()
        rng = np.random.default_rng(9)
        Y = rng.standard_normal((600, 16)).astype(np.float32)
        r = ItemRetriever(Y, component="qmaskset", precision="int8")
        try:
            for excl in ([3, 4, 5], np.arange(100), [1]):
                assert r.set_excluded_ids(np.asarray(excl)) is True
                ledger_total = led.total_bytes(
                    component="qmaskset/int8"
                ) + led.total_bytes(component="qmaskset-mask")
                # ledger == actual device arrays == the gauge: zero
                # drift for a reconcile() probe of these buffers
                assert ledger_total == r.resident_bytes
                assert _gauge(
                    "pio_retrieval_resident_bytes", component="qmaskset"
                ) == r.resident_bytes
        finally:
            r.free()


class TestQuantizedWarm:
    @pytest.mark.parametrize("shards", [1, 2])
    def test_warm_ladder_precompiles_quantized_serving(self, shards):
        """After warm(), serving batches inside the covered envelope
        (any pow2 top-k tier x batch x warmed flag combo/exclude width)
        compile nothing — the cold-compile counter for the serving
        sites stays flat (the PR 8 blacklist-width lesson extended to
        the precision x shortlist combo space)."""
        mesh = _mesh_or_none(shards)
        rng = np.random.default_rng(13 + shards)
        Y = rng.standard_normal((300, 8)).astype(np.float32)
        r = ItemRetriever(
            Y, mesh=mesh, component=f"qwarm{shards}", precision="int8",
        )
        try:
            r.warm(n=16, max_batch=16, flag_combos=((False, False),))
            cache = (
                "retrieval-fused" if shards == 1 else "retrieval-stage1"
            )
            before = _gauge(
                "pio_executable_cache_compiles_total", cache=cache
            )
            for num in (3, 9, 16):
                # production call sites route the width through the
                # pow2 ladder (tests/test_lint.py) — warm() covers
                # exactly that envelope
                n_req = pow2_topk_width(num, r.n_items)
                for b in (2, 8, 16):
                    r.topn(
                        rng.standard_normal((b, 8)).astype(np.float32),
                        n_req,
                    )
            assert _gauge(
                "pio_executable_cache_compiles_total", cache=cache
            ) == before
        finally:
            r.free()


class TestRecommendationQuantizedServing:
    """The recommendation engine's quantized serving path: params plumb
    precision/shortlist_mult into an ItemRetriever at prepare_serving,
    recommend_many returns the same item lists as the exact
    ServingFactors path, serving_precision reports the active tier, and
    release_serving drives the retriever's ledger bytes to zero."""

    def _model(self, rec, rng, n_users=30, n_items=200, k=8):
        from predictionio_tpu.data.bimap import BiMap
        from predictionio_tpu.ops.als import ALSModelArrays

        return rec.ALSModel(
            arrays=ALSModelArrays(
                user_factors=rng.standard_normal(
                    (n_users, k)
                ).astype(np.float32),
                item_factors=rng.standard_normal(
                    (n_items, k)
                ).astype(np.float32),
            ),
            user_index=BiMap({f"u{i}": i for i in range(n_users)}),
            item_index=BiMap({f"i{i}": i for i in range(n_items)}),
        )

    def test_quantized_matches_exact_path(self):
        import copy

        from predictionio_tpu.models.recommendation import engine as rec

        rng = np.random.default_rng(21)
        model = self._model(rec, rng)
        exact_algo = rec.ALSAlgorithm(rec.ALSAlgorithmParams(rank=8))
        q_algo = rec.ALSAlgorithm(
            rec.ALSAlgorithmParams(rank=8, precision="int8")
        )
        exact = exact_algo.prepare_serving(None, copy.deepcopy(model))
        quant = q_algo.prepare_serving(None, copy.deepcopy(model))
        try:
            assert quant._retriever is not None
            assert q_algo.serving_precision(quant) == "int8"
            assert exact_algo.serving_precision(exact) is None
            queries = [
                (i, rec.Query(user=f"u{i}", num=7)) for i in range(6)
            ] + [(9, rec.Query(user="stranger", num=5))]
            got_q = dict(q_algo.batch_predict(quant, list(queries)))
            got_e = dict(exact_algo.batch_predict(exact, list(queries)))
            assert got_q.keys() == got_e.keys()
            for qx in got_q:
                assert [x.item for x in got_q[qx].item_scores] == [
                    x.item for x in got_e[qx].item_scores
                ]
                np.testing.assert_allclose(
                    [x.score for x in got_q[qx].item_scores],
                    [x.score for x in got_e[qx].item_scores],
                    rtol=1e-5,
                )
            assert got_q[9].item_scores == ()  # unknown user
        finally:
            q_algo.release_serving(quant)
            exact_algo.release_serving(exact)
        assert quant._retriever is None
        assert dl.get_ledger().total_bytes(
            component="recommendation/int8"
        ) == 0

    def test_warm_covers_quantized_ladder(self):
        from predictionio_tpu.models.recommendation import engine as rec

        rng = np.random.default_rng(22)
        model = self._model(rec, rng)
        algo = rec.ALSAlgorithm(
            rec.ALSAlgorithmParams(
                rank=8, precision="bf16", warm_num=16, warm_max_batch=8,
            )
        )
        prepped = algo.prepare_serving(None, model)
        try:
            algo.warm(prepped)
            before = _gauge(
                "pio_executable_cache_compiles_total",
                cache="retrieval-fused",
            )
            algo.batch_predict(
                prepped, [(0, rec.Query(user="u1", num=10))]
            )
            assert _gauge(
                "pio_executable_cache_compiles_total",
                cache="retrieval-fused",
            ) == before
        finally:
            algo.release_serving(prepped)
