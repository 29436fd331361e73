"""The Similar Product engine's serving path against the plain float64
reference (models/similarproduct/reference.py) on seeded factors, through
``DeployedEngine.serve_batch``: every shape of the detail-page traffic at
float32, bfloat16 and int8 residency, the closed warm ladder and the host
path past its top, the model as a ``PersistentModel`` over a mapped file,
and the host memory the quantized retriever takes over that map."""

from __future__ import annotations

import os
import time
import tracemalloc
import types

import numpy as np
import pytest

from predictionio_tpu.api.engine_server import DeployedEngine
from predictionio_tpu.controller.engine import EngineParams
from predictionio_tpu.controller.persistent_model import (
    PersistentModelManifest,
)
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models.similarproduct import reference
from predictionio_tpu.models.similarproduct.engine import (
    ALSAlgorithm, ALSAlgorithmParams, DataSourceParams, Item, LikeAlgorithm,
    LikeSPModel, Query, SPModel, similarproduct_engine,
)
from predictionio_tpu.ops import retrieval, similarity
from predictionio_tpu.utils import compilation_cache as _cc
from predictionio_tpu.utils import metrics as _metrics
from predictionio_tpu.utils import tracing as tr

N_ITEMS, RANK = 3000, 32
CATS = [f"cat{j:02d}" for j in range(24)]
PRECISIONS = ("float32", "bf16", "int8")
SHAPES = ("plain", "categories", "blackList", "whiteList",
          "category_blackList")
LADDER = {"exclude_widths": (16, 64), "include_widths": (256,),
          "warm_num": 16, "warm_max_batch": 16}


def item_cats(j):
    """One category an item, two for every ninth item, none for item 0."""
    if j == 0:
        return ()
    return (CATS[j % 24], CATS[(j // 24) % 24]) if j % 9 == 0 else (CATS[j % 24],)


def seeded_model(n_items=N_ITEMS, rank=RANK, seed=33, cls=SPModel):
    rng = np.random.default_rng(seed)
    Y = rng.standard_normal((n_items, rank)).astype(np.float32)
    Y[5] = 0.0  # an item no event ever touched
    return cls(
        item_factors=Y,
        item_index=BiMap({f"i{j}": j for j in range(n_items)}),
        items={j: Item(categories=item_cats(j)) for j in range(n_items)},
    )


def deployed(model, precision, **params):
    algo_params = ALSAlgorithmParams(
        rank=model.item_factors.shape[1], precision=precision,
        **{**LADDER, **params})
    engine = similarproduct_engine()
    engine_params = EngineParams(
        data_source_params=("", DataSourceParams(app_name="shop")),
        algorithm_params_list=(("als", algo_params),),
    )
    _, _, (algo,), _ = engine.make_components(engine_params)
    return DeployedEngine(
        engine, engine_params, types.SimpleNamespace(id=f"sp-{precision}"),
        [algo.prepare_serving(None, model)],
    )


@pytest.fixture(scope="module", params=PRECISIONS)
def served(request):
    """(precision, a DeployedEngine over the seeded model, warm)."""
    return request.param, deployed(seeded_model(), request.param)


def query_of(shape, k, rng, model):
    """Request ``k`` of the detail-page mix: 1, 2-5 or 6-10 query items,
    num 4/10/16, the filters of ``shape``."""
    n_query = [1, 1, 1, int(rng.integers(2, 6)), int(rng.integers(6, 11))][k % 5]
    items = [int(i) for i in rng.integers(1, N_ITEMS, n_query)]
    fields = {"items": tuple(f"i{i}" for i in items), "num": (4, 10, 16)[k % 3]}
    if shape in ("categories", "category_blackList"):
        fields["categories"] = (item_cats(items[0])[0],)
    if shape in ("blackList", "category_blackList"):
        # what the session has seen: the query's own best answers among
        # them, so that the list bites
        near = reference.predict(
            model.item_factors, model.item_index.to_dict(),
            {"items": fields["items"], "num": 6})
        seen = [name for name, _ in near] + [
            f"i{i}" for i in rng.integers(0, N_ITEMS, int(rng.integers(4, 45)))]
        fields["black_list"] = tuple(seen)
    if shape == "whiteList":
        members = [j for j in range(N_ITEMS)
                   if item_cats(j)[:1] == (CATS[k % 24],)]
        size = min(len(members), int(rng.integers(50, 201)))
        fields["white_list"] = tuple(
            f"i{j}" for j in rng.choice(members, size, replace=False))
    return Query(**fields)


def as_json(q: Query) -> dict:
    body = {"items": list(q.items), "num": q.num}
    for ours, theirs in (("categories", "categories"),
                         ("white_list", "whiteList"),
                         ("black_list", "blackList")):
        if getattr(q, ours) is not None:
            body[theirs] = list(getattr(q, ours))
    return body


def expected(model, q: Query, num=None):
    body = as_json(q)
    if num is not None:
        body["num"] = num
    return reference.predict(
        model.item_factors, model.item_index.to_dict(), body,
        item_categories=model.item_categories,
        category_names=model.category_names)


def holds_the_filters(model, q: Query, items) -> bool:
    """Every served item passes the query's own filters, exactly."""
    idx = model.item_index
    for name in items:
        if name in q.items or name in (q.black_list or ()):
            return False
        if q.white_list is not None and name not in q.white_list:
            return False
        if q.categories is not None and not (
                set(item_cats(idx[name])) & set(q.categories)):
            return False
    return len(set(items)) == len(items)


def compare(precision, model, queries, results):
    """float32: ids and order equal, scores to 1e-5. Quantized: every
    served score within 1e-5 of the reference's for that item, filters
    exact; returns (reference items served, reference items) for the
    recall over the caller's queries."""
    hit = total = 0
    for q, got in zip(queries, results):
        want = expected(model, q)
        items = [s.item for s in got.item_scores]
        assert holds_the_filters(model, q, items), q
        assert all(s.score > 0 for s in got.item_scores)
        if precision == "float32":
            assert items == [name for name, _ in want], q
        assert len(items) == len(want), q
        every = dict(expected(model, q, num=N_ITEMS))
        for s in got.item_scores:
            assert abs(s.score - every[s.item]) <= 1e-5, (q, s)
        hit += len(set(items) & {name for name, _ in want})
        total += len(want)
    return hit, total


@pytest.mark.parametrize("batch", [1, 3, 8, 9])
@pytest.mark.parametrize("shape", SHAPES)
def test_a_served_batch_matches_the_reference(served, shape, batch):
    precision, dep = served
    model = dep.models[0]
    rng = np.random.default_rng([SHAPES.index(shape), batch])
    # the shape under test leads; a live batch mixes them
    queries = [query_of(SHAPES[(SHAPES.index(shape) + k) % 5 if k % 2 else
                               SHAPES.index(shape)], k, rng, model)
               for k in range(batch)]
    results = dep.serve_batch(queries)
    hit, total = compare(precision, model, queries, results)
    assert total > 0 and hit / total >= 0.999


def test_recall_over_many_queries_of_the_mix(served):
    """recall@num >= 0.999 over 150 queries of the mix, the query items
    never served, an unknown item skipped, an unknown query empty."""
    precision, dep = served
    model = dep.models[0]
    rng = np.random.default_rng(7)
    queries = [query_of(SHAPES[k % 5], k, rng, model) for k in range(150)]
    hit = total = 0
    for s in range(0, len(queries), 16):
        part = queries[s:s + 16]
        h, t = compare(precision, model, part, dep.serve_batch(part))
        hit, total = hit + h, total + t
    assert hit / total >= 0.999, (hit, total)
    mixed = Query(items=("i7", "nobody"), num=4)
    got, none = dep.serve_batch([mixed, Query(items=("nobody",), num=4)])
    assert [s.item for s in got.item_scores] == [
        name for name, _ in expected(model, Query(items=("i7",), num=4))]
    assert none.item_scores == ()


def counter(name, **labels):
    fam = _metrics.get_registry().counter(name, "", labels=tuple(labels))
    return fam.labels(**labels)


def test_lists_at_the_ladders_top_ride_the_device_and_one_past_the_host(served):
    precision, dep = served
    model = dep.models[0]
    fallbacks = counter("pio_similar_host_fallback_total")
    cold = counter("pio_cold_compiles_total", site="serving")
    program = (retrieval._fused_topn_single if precision == "float32"
               else retrieval._fused_topn_single_2s)
    items = tuple(f"i{j}" for j in range(10, 20))  # ten query items
    black = [f"i{j}" for j in range(100, 155)]  # 54 + 10 = 64, then 65
    members = [f"i{j}" for j in range(N_ITEMS) if j % 24 == 3]
    white = members[:125] + [f"i{j}" for j in range(200, 332)]  # 257
    at_top = [
        Query(items=items, num=16, black_list=tuple(black[:54])),
        Query(items=("i50",), num=10, white_list=tuple(white[:256])),
        Query(items=("i51",), num=4, categories=tuple(CATS[:4])),
        Query(items=items[:5], num=16, black_list=tuple(black[:54]),
              categories=(CATS[4],)),
    ]
    past = [
        Query(items=items, num=16, black_list=tuple(black[:55])),
        Query(items=("i50",), num=10, white_list=tuple(white)),
        Query(items=("i51",), num=4, categories=tuple(CATS[:5])),
        Query(items=("i52",), num=17),  # over warm_num
        Query(items=("i52",), num=5, white_list=tuple(white),
              black_list=tuple(black), categories=(CATS[3],)),
    ]
    before, cold0, size0 = fallbacks.value, cold.value, program._cache_size()
    with _cc.compile_site("serving"):
        compare(precision, model, at_top, dep.serve_batch(at_top))
        assert fallbacks.value == before
        results = dep.serve_batch(past + at_top[:1])
    assert fallbacks.value - before == len(past)
    assert program._cache_size() == size0 and cold.value == cold0
    # the host path is float32 numpy: ids and order as the reference's
    for q, got in zip(past, results):
        assert [s.item for s in got.item_scores] == [
            name for name, _ in expected(model, q)], q
    compare(precision, model, past, results[:len(past)])


def test_warm_compiles_the_whole_ladder_and_traffic_compiles_nothing():
    """A catalog of more whole blocks (7 of 2,048) than any other test's:
    every executable this test meets is compiled by its own warm(), for
    the int8 tier."""
    model = seeded_model(n_items=12_999)
    size0 = retrieval._fused_topn_single_2s._cache_size()
    dep = deployed(model, "int8")
    retriever = dep.models[0]._retriever
    assert retriever.ladder_size() == 2 * 2 * 2
    assert retrieval._fused_topn_single_2s._cache_size() - size0 == 8
    cold = counter("pio_cold_compiles_total", site="serving")
    cold0, size1 = cold.value, retrieval._fused_topn_single_2s._cache_size()
    rng = np.random.default_rng(3)
    with _cc.compile_site("serving"):
        for batch in (1, 7, 16, 21):  # 21: over the top, split
            dep.serve_batch([
                Query(items=(f"i{int(rng.integers(1, 2999))}",),
                      num=(4, 10, 16)[k % 3],
                      black_list=tuple(f"i{j}" for j in range(40, 40 + 6 * k)),
                      categories=(CATS[k % 24],) if k % 2 else None)
                for k in range(batch)])
    assert cold.value == cold0
    assert retrieval._fused_topn_single_2s._cache_size() == size1


def test_a_quantized_batch_names_its_refine_and_counts_it():
    dep = deployed(seeded_model(), "int8")
    rows = counter("pio_retrieval_shortlist_rows_total",
                   component="similarproduct")
    changed = counter("pio_retrieval_refine_changed_total",
                      component="similarproduct")
    asked = _metrics.get_registry().histogram(
        "pio_similar_query_items", "",
        buckets=(1, 2, 3, 4, 5, 6, 8, 10, 16, 32)).labels()
    rows0, changed0, asked0 = rows.value, changed.value, asked.snapshot()
    queries = [Query(items=("i9", "i10", "nobody"), num=10),
               Query(items=("i11",), num=4, categories=(CATS[2],)),
               Query(items=("i12",), num=16)]
    with tr.stage_totals() as totals:
        dep.serve_batch(queries)
    assert set(totals) == {tr.HOST_PREP, tr.MASK_PREP, tr.DISPATCH,
                           tr.UPLOAD, tr.DEVICE_WAIT, tr.REFINE, tr.BUILD}
    assert all(v > 0 for v in totals.values())
    # the device hands back pow2(4 x 16) = 64 candidates a query
    assert rows.value - rows0 == 3 * 64
    assert 0 <= changed.value - changed0 <= 3
    after = asked.snapshot()
    assert after.count - asked0.count == 3
    assert after.sum - asked0.sum == 2 + 1 + 1  # the unknown item is none


def test_the_query_rows_gather_is_host_prep(served, monkeypatch):
    """``_spec`` (the name lookups and the gather of the query rows from
    the table) runs inside the batch's host_prep stage, once a query."""
    _, dep = served
    model = dep.models[0]
    calls = []
    real = SPModel._spec

    def slow_spec(self, query):
        calls.append(query)
        time.sleep(0.005)
        return real(self, query)

    monkeypatch.setattr(SPModel, "_spec", slow_spec)
    queries = [Query(items=("i9", "i10"), num=4), Query(items=("i11",), num=10)]
    with tr.stage_totals() as totals:
        model.similar_batch(list(enumerate(queries)))
    assert len(calls) == 2
    assert totals[tr.HOST_PREP] >= 2 * 0.005


def test_release_leaves_the_numpy_path_and_no_device(served):
    precision, _ = served
    model = seeded_model()
    algo = ALSAlgorithm(ALSAlgorithmParams(
        rank=RANK, precision=precision, **LADDER))
    algo.prepare_serving(None, model)
    q = Query(items=("i40", "i41"), num=10, categories=(CATS[16],))
    algo.release_serving(model)
    assert model._retriever is None and model._rn is not None
    got = algo.predict(model, q)
    assert [s.item for s in got.item_scores] == [
        name for name, _ in expected(model, q)]


# --- the model: columns, a PersistentModel over a mapped file ---


def test_the_model_holds_columns_and_no_object_an_item():
    model = seeded_model()
    assert model.items is None
    assert model.category_names == tuple(CATS)
    assert model.item_categories.shape == (N_ITEMS, 2)
    assert model.item_categories.dtype == np.int32
    assert (model.item_categories[0] == -1).all()
    assert model.category_codes(("cat03", "nobody")).tolist() == [3]
    assert not hasattr(model, "normed_host")


def test_save_and_load_round_trip_through_the_mapped_file(
        tmp_path, monkeypatch):
    monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path / "fs"))
    model = seeded_model()
    engine = similarproduct_engine()
    params = ALSAlgorithmParams(rank=RANK, precision="int8", **LADDER)
    engine_params = EngineParams(
        data_source_params=("", DataSourceParams(app_name="shop")),
        algorithm_params_list=(("als", params),),
    )
    (manifest,) = engine.make_serializable_models(
        None, "inst-1", engine_params, [model])
    assert isinstance(manifest, PersistentModelManifest)
    d = tmp_path / "fs" / "pmodels" / "inst-1-SPModel"
    assert sorted(os.listdir(d)) == [
        "index.pkl", "item_categories.npy", "item_factors.npy"]
    (loaded,) = engine.prepare_deploy(
        None, engine_params, "inst-1", [manifest], None)
    assert isinstance(loaded.item_factors, np.memmap)
    assert not loaded.item_factors.flags.writeable
    assert loaded._retriever.precision == "int8"
    # the refine reads the map itself, not a copy of it
    assert np.shares_memory(
        loaded._retriever._y_f32_host, loaded.item_factors)
    np.testing.assert_array_equal(loaded.item_factors, model.item_factors)
    np.testing.assert_array_equal(loaded.item_categories, model.item_categories)
    assert loaded.category_names == model.category_names
    assert loaded.item_index == model.item_index
    dep = DeployedEngine(
        engine, engine_params, types.SimpleNamespace(id="inst-1"), [loaded])
    rng = np.random.default_rng(11)
    queries = [query_of(SHAPES[k % 5], k, rng, model) for k in range(10)]
    hit, total = compare("int8", model, queries, dep.serve_batch(queries))
    assert hit == total


def test_a_table_written_in_place_is_flushed_not_copied(tmp_path, monkeypatch):
    monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path / "fs"))
    base = seeded_model(n_items=500)
    os.makedirs(SPModel.model_dir("inst-2"))
    table = np.lib.format.open_memmap(
        SPModel.factors_path("inst-2"), mode="w+", dtype=np.float32,
        shape=base.item_factors.shape)
    table[:] = base.item_factors
    inode = os.stat(SPModel.factors_path("inst-2")).st_ino
    model = SPModel(item_factors=table, item_index=base.item_index,
                    category_names=base.category_names,
                    item_categories=base.item_categories)
    assert model.save("inst-2", None, None)
    assert os.stat(SPModel.factors_path("inst-2")).st_ino == inode
    loaded = SPModel.load("inst-2", None, None)
    np.testing.assert_array_equal(loaded.item_factors, base.item_factors)


def test_the_like_algorithms_model_persists_beside_the_other(
        tmp_path, monkeypatch):
    monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path / "fs"))
    assert LikeAlgorithm.model_class is LikeSPModel
    a, b = seeded_model(n_items=50), seeded_model(50, seed=4, cls=LikeSPModel)
    assert a.save("inst-3", None, None) and b.save("inst-3", None, None)
    assert LikeSPModel.model_dir("inst-3") != SPModel.model_dir("inst-3")
    back = LikeSPModel.load("inst-3", None, None)
    assert isinstance(back, LikeSPModel)
    np.testing.assert_array_equal(back.item_factors, b.item_factors)


def test_the_int8_retriever_over_a_map_holds_under_one_and_a_half_tables(
        tmp_path):
    """The host's peak over the mapped float32 table while the int8
    retriever is built: the int8 rows once, the per-item vectors and a
    few blocks' temporaries: never a float32 copy, never the
    dequantized table."""
    n, k = 200_000, 128
    path = str(tmp_path / "table.npy")
    table = np.lib.format.open_memmap(
        path, mode="w+", dtype=np.float32, shape=(n, k))
    rng = np.random.default_rng(1)
    for a in range(0, n, 20_000):
        table[a:a + 20_000] = rng.standard_normal((20_000, k), np.float32)
    table.flush()
    del table
    mapped = np.load(path, mmap_mode="r")
    int8_table = n * k
    tracemalloc.start()
    try:
        retriever = retrieval.ItemRetriever(
            mapped, precision="int8", component="sp-map",
            exclude_ladder=(16,), include_ladder=(1,), max_batch=8)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak < 1.5 * int8_table, (peak, int8_table)
    assert np.shares_memory(retriever._y_f32_host, mapped)
    # what is resident is what quantize_rows_int8 makes of the table
    rows, scale = retrieval.quantize_rows_int8(mapped[:4096])
    np.testing.assert_array_equal(
        retriever.dequantized_factors()[:4096],
        retrieval.dequantize_rows_int8(rows, scale))
    s, i = retriever.topn(
        np.asarray(mapped[:2]), 16, exclude=[np.array([0]), np.array([1])],
        positive_only=True, normalize=True)
    want_s, want_i = retrieval.naive_topn_reference(
        np.asarray(mapped), np.asarray(mapped[:2]), 16,
        exclude=[np.array([0]), np.array([1])], positive_only=True,
        normalize=True)
    assert (i == want_i).mean() >= 0.999
    retriever.free()


@pytest.mark.parametrize("precision", ["bf16", "int8"])
def test_blockwise_residency_is_the_whole_table_residency(precision):
    """Row blocks and threads change nothing: rows, scales and both
    norm vectors are those of one pass over the table."""
    rng = np.random.default_rng(2)
    Y = rng.standard_normal((3 * retrieval._QUANT_BLOCK_ROWS + 17, 8)).astype(
        np.float32)
    Y[100] = 0.0
    rows, scale, rn, rn_exact = retrieval._quantize_resident(
        Y, len(Y) + 3, precision)
    if precision == "int8":
        want, want_scale = retrieval.quantize_rows_int8(Y)
        deq = retrieval.dequantize_rows_int8(want, want_scale)
        np.testing.assert_array_equal(scale[:len(Y)], want_scale)
    else:
        import jax.numpy as jnp

        want = Y.astype(jnp.bfloat16)
        deq = want.astype(np.float32)
    np.testing.assert_array_equal(np.asarray(rows[:len(Y)]), np.asarray(want))
    np.testing.assert_array_equal(rn[:len(Y)], retrieval._reciprocal_norms(deq))
    np.testing.assert_array_equal(
        rn_exact[:len(Y)], retrieval._reciprocal_norms(Y))
    assert not np.asarray(rows[len(Y):]).any() and not rn[len(Y):].any()


# --- the satellites: float32 cosines at "highest", a table that cannot fit ---


def test_the_cosine_sum_asks_for_the_highest_precision():
    import jax
    import jax.numpy as jnp

    text = similarity._cosine_sum.lower(
        jax.ShapeDtypeStruct((4, 8), jnp.float32),
        jax.ShapeDtypeStruct((64, 8), jnp.float32)).as_text()
    assert "HIGHEST" in text


def test_the_numpy_path_is_a_float32_product_in_the_references_order():
    """No prepared state: ``SPModel.similar`` is numpy over the float32
    table in row blocks, ids and order as the float64 reference's."""
    model = seeded_model()
    algo = ALSAlgorithm(ALSAlgorithmParams(rank=RANK))
    rng = np.random.default_rng(5)
    for k in range(20):
        q = query_of(SHAPES[k % 5], k, rng, model)
        got = algo.predict(model, q)
        want = expected(model, q)
        assert [s.item for s in got.item_scores] == [n for n, _ in want]
        np.testing.assert_allclose(
            [s.score for s in got.item_scores], [s for _, s in want],
            atol=1e-5)


def test_a_table_the_device_cannot_hold_is_refused_by_name(monkeypatch):
    monkeypatch.setattr(similarity, "_device_bytes_limit", lambda d: 1000)
    with pytest.raises(ValueError, match="precision"):
        similarity.SimilarityScorer(np.ones((100, 8), np.float32))
    monkeypatch.setattr(similarity, "_device_bytes_limit", lambda d: 10_000)
    similarity.SimilarityScorer(np.ones((100, 8), np.float32))


def test_the_query_takes_upstreams_field_names():
    algo = ALSAlgorithm(ALSAlgorithmParams(rank=RANK))
    q = algo.query_from_json({
        "items": ["i1", "i3"], "num": 4, "categories": ["c1"],
        "whiteList": ["i1", "i2"], "blackList": ["i3"]})
    assert q == Query(items=("i1", "i3"), num=4, categories=("c1",),
                      white_list=("i1", "i2"), black_list=("i3",))
