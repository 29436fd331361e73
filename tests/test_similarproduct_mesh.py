"""The Similar Product engine in float32 over a mesh of four devices, rows
sharded, the deployment of a catalog that one chip cannot hold in float32:
``prepare_serving`` on a mesh and ``batch_predict`` against
``ops/retrieval.py::naive_topn_reference`` under every shape of the
detail-page traffic, a tie across a shard boundary, the upload that builds
each shard from a view of the caller's table (no padded host copy), and the
merge's stage and counter."""

from __future__ import annotations

import tracemalloc

import jax
import numpy as np
import pytest

from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models.similarproduct.engine import (
    ALSAlgorithm, ALSAlgorithmParams, Item, Query, SPModel,
)
from predictionio_tpu.ops import retrieval
from predictionio_tpu.parallel import make_mesh
from predictionio_tpu.utils import tracing as tr
from predictionio_tpu.workflow.context import workflow_context

SHARDS, RANK = 4, 32
# 7,000 rows: whole blocks of 2,048 a shard make 8,192, so the last shard
# holds 856 of the catalog's rows and 1,192 of padding
N_ITEMS = 7000
CATS = [f"cat{j:02d}" for j in range(24)]
SHAPES = ("plain", "categories", "blackList", "whiteList",
          "category_blackList")
LADDER = {"exclude_widths": (16, 64), "include_widths": (256,),
          "warm_num": 16, "warm_max_batch": 16}


@pytest.fixture(scope="module")
def mesh():
    """Four of the suite's virtual devices, in an order of their own: the
    shard a device holds is its place in the mesh, not its id."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return make_mesh({"data": SHARDS}, jax.devices()[4:8][::-1])


def item_cats(j):
    return (CATS[j % 24],) if j % 7 else (CATS[j % 24], CATS[(j // 7) % 24])


def seeded_model(n_items=N_ITEMS, seed=39):
    rng = np.random.default_rng(seed)
    Y = rng.standard_normal((n_items, RANK)).astype(np.float32)
    return SPModel(
        item_factors=Y,
        item_index=BiMap({f"i{j}": j for j in range(n_items)}),
        items={j: Item(categories=item_cats(j)) for j in range(n_items)},
    )


def prepared(mesh, model):
    algo = ALSAlgorithm(ALSAlgorithmParams(
        rank=RANK, precision="float32", **LADDER))
    # no context: one device (a context without a mesh makes one of all)
    algo.prepare_serving(
        None if mesh is None else workflow_context(mode="Serving", mesh=mesh),
        model)
    return algo, model


@pytest.fixture(scope="module")
def served(mesh):
    algo, model = prepared(mesh, seeded_model())
    algo.warm(model)
    return algo, model


def query_of(shape, k, rng):
    n_query = [1, 1, 1, int(rng.integers(2, 6)), int(rng.integers(6, 11))][k % 5]
    items = [int(i) for i in rng.choice(N_ITEMS, n_query, replace=False)]
    fields = {"items": tuple(f"i{i}" for i in items), "num": (4, 10, 16)[k % 3]}
    if shape in ("categories", "category_blackList"):
        fields["categories"] = (item_cats(items[0])[0],)
    if shape in ("blackList", "category_blackList"):
        fields["black_list"] = tuple(
            f"i{i}" for i in rng.integers(0, N_ITEMS, int(rng.integers(10, 51))))
    if shape == "whiteList":
        fields["white_list"] = tuple(
            f"i{i}" for i in rng.choice(N_ITEMS, int(rng.integers(50, 201)),
                                        replace=False))
    return Query(**fields)


def naive(model, q: Query):
    """(ids, scores) of ``naive_topn_reference`` for the query: the sum of
    the query items' normalized rows against every row's cosine, the
    query items and the blackList excluded, the whiteList and the
    category as the inclusion list, positive scores alone."""
    Y = model.item_factors
    rn = retrieval._reciprocal_norms(Y)
    at = np.asarray([int(i[1:]) for i in q.items])
    qvec = (Y[at] * rn[at][:, None]).sum(axis=0)
    exclude = np.union1d(at, [int(i[1:]) for i in q.black_list or ()])
    allow = np.ones(len(Y), bool)
    if q.white_list is not None:
        allow[:] = False
        allow[[int(i[1:]) for i in q.white_list]] = True
    if q.categories is not None:
        allow &= np.array([bool(set(item_cats(j)) & set(q.categories))
                           for j in range(len(Y))])
    s, i = retrieval.naive_topn_reference(
        Y, qvec[None], q.num, exclude=[exclude],
        include=[np.flatnonzero(allow)], positive_only=True, normalize=True)
    live = s[0] > -np.inf
    return i[0][live], s[0][live]


@pytest.mark.parametrize("shape", SHAPES)
def test_a_mesh_batch_matches_the_naive_reference(served, shape):
    algo, model = served
    assert model._retriever.mesh is not None
    rng = np.random.default_rng(SHAPES.index(shape))
    queries = [query_of(shape, k, rng) for k in range(10)]
    got = dict(algo.batch_predict(model, list(enumerate(queries))))
    for k, q in enumerate(queries):
        ids, scores = naive(model, q)
        served_ids = [int(s.item[1:]) for s in got[k].item_scores]
        assert served_ids == ids.tolist(), q
        np.testing.assert_allclose(
            [s.score for s in got[k].item_scores], scores, rtol=1e-6)


@pytest.mark.parametrize("boundary", [1, 2, 3])
def test_a_tie_across_a_shard_boundary_goes_to_the_lowest_id(mesh, boundary):
    """The last row of one shard and the first of the next are the same
    row, and the query item's: both score the most, equally, and the
    lower id comes first, as a full-matrix top_k orders them."""
    model = seeded_model(seed=40 + boundary)
    rows_l = 8192 // SHARDS
    a, b = boundary * rows_l - 1, boundary * rows_l
    model.item_factors[[a, b]] = model.item_factors[11]
    algo, model = prepared(mesh, model)
    [(_, got)] = algo.batch_predict(model, [(0, Query(items=("i11",), num=4))])
    assert [s.item for s in got.item_scores[:2]] == [f"i{a}", f"i{b}"]
    assert got.item_scores[0].score == got.item_scores[1].score
    ids, _ = naive(model, Query(items=("i11",), num=4))
    assert [int(s.item[1:]) for s in got.item_scores] == ids.tolist()


@pytest.mark.parametrize("n_items", [N_ITEMS, 5000])
def test_the_mesh_upload_gives_back_the_table_row_for_row(mesh, n_items):
    """Each device holds its own rows (5,000: the last shard is all
    padding), in the mesh's order, zero past the catalog."""
    Y = seeded_model(n_items).item_factors
    r = retrieval.ItemRetriever(Y, mesh=mesh, component="mesh-upload")
    rows_l = r._n_pad // SHARDS
    shards = r._y_dev.addressable_shards
    assert sorted(d.id for d in (s.device for s in shards)) == sorted(
        d.id for d in mesh.devices.flat)
    for shard in shards:
        start = shard.index[0].start or 0
        assert shard.device == mesh.devices.flat[start // rows_l]
        want = np.zeros((rows_l, RANK), np.float32)
        mine = Y[start:start + rows_l]
        want[:len(mine)] = mine
        np.testing.assert_array_equal(np.asarray(shard.data), want)
    np.testing.assert_array_equal(np.asarray(r._y_dev)[:n_items], Y)
    r.free()


def test_the_mesh_upload_makes_no_padded_host_copy(mesh):
    """The host's peak while a float32 retriever is built on the mesh is
    under one shard's bytes: each shard goes up from a view of the
    caller's table, its padding made on its device (a padded copy of the
    whole table would be four shards)."""
    n, k = 30_000, 32
    Y = np.random.default_rng(5).standard_normal((n, k)).astype(np.float32)
    # the same shapes once before, so that no compile is in the reading
    retrieval.ItemRetriever(Y, mesh=mesh, component="mesh-peak").free()
    tracemalloc.start()
    try:
        r = retrieval.ItemRetriever(Y, mesh=mesh, component="mesh-peak")
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    one_shard = r._n_pad // SHARDS * k * 4
    assert peak < one_shard, (peak, one_shard)
    r.free()


def merge_rows(component):
    return retrieval._m_merge_rows().labels(component=component).value


@pytest.mark.parametrize("on_mesh", [True, False])
def test_a_mesh_batch_counts_its_merge_and_one_device_none(mesh, on_mesh):
    algo, model = prepared(mesh if on_mesh else None, seeded_model(n_items=3000))
    r = model._retriever
    component = r.component
    before = merge_rows(component)
    queries = [Query(items=("i3",), num=10), Query(items=("i4", "i5"), num=4),
               Query(items=("i6",), num=16, categories=(CATS[2],))]
    with tr.stage_totals() as totals:
        algo.batch_predict(model, list(enumerate(queries)))
    if not on_mesh:
        assert r.mesh is None
        assert tr.MERGE not in totals and merge_rows(component) == before
        return
    b_pad = r.last_padded[0]
    n_local = min(retrieval.pow2_topk_width(16, r.n_items), r._n_pad // SHARDS)
    assert (b_pad, n_local) == (8, 16)
    assert merge_rows(component) - before == b_pad * SHARDS * n_local
    assert totals[tr.MERGE] > 0
    assert {tr.DISPATCH, tr.DEVICE_WAIT, tr.BUILD} <= set(totals)
