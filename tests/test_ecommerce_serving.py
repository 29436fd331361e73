"""The e-commerce engine's device serving path against the plain float64
reference (models/ecommerce/reference.py) on seeded factors: every query
field alone and combined, the store read a batch, the closed warm ladder,
the model's blob, and the sqlite store's read by entity."""

from __future__ import annotations

import datetime as dt
import os
import pickle
import subprocess
import sys
import time

import numpy as np
import pytest

from predictionio_tpu.data import storage as storage_mod
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.data.event import DataMap, Event
from predictionio_tpu.data.storage import Storage
from predictionio_tpu.data.storage.base import App
from predictionio_tpu.models.ecommerce import reference
from predictionio_tpu.models.ecommerce.engine import (
    ECommAlgorithm, ECommAlgorithmParams, ECommModel, Item, Query,
)
from predictionio_tpu.ops import retrieval
from predictionio_tpu.utils import compilation_cache as _cc
from predictionio_tpu.utils import metrics as _metrics

N_USERS, N_ITEMS, RANK = 40, 300, 16
CATS = [f"c{j}" for j in range(7)]
T0 = dt.datetime(2026, 9, 1, tzinfo=dt.timezone.utc)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def storage_config(tmp):
    return {
        "PIO_STORAGE_SOURCES_SQLITE_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQLITE_PATH": os.path.join(tmp, "pio.db"),
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "pio_meta",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQLITE",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "pio_event",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "SQLITE",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "pio_model",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "SQLITE",
    }


def item_cats(j):
    """One category an item, two for every fifth item, none for item 0."""
    if j == 0:
        return ()
    return (CATS[j % 7], CATS[(j // 7) % 7]) if j % 5 == 0 else (CATS[j % 7],)


def seeded_model(n_items=N_ITEMS, seed=11):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((N_USERS, RANK)).astype(np.float32)
    X[[7, 15]] = 0.0  # users the index knows and no rating ever touched
    return ECommModel(
        user_factors=X,
        item_factors=rng.standard_normal((n_items, RANK)).astype(np.float32),
        user_index=BiMap({f"u{j}": j for j in range(N_USERS)}),
        item_index=BiMap({f"i{j}": j for j in range(n_items)}),
        items={j: Item(categories=item_cats(j)) for j in range(n_items)},
    )


class World:
    """A sqlite store with bulk-imported histories beside single events,
    a seeded model prepared for serving, and the same facts kept plainly
    for the reference."""

    def __init__(self, tmp, n_items=N_ITEMS, **params):
        self.config = storage_config(tmp)
        self.storage = Storage(self.config)
        storage_mod.set_storage(self.storage)
        self.app_id = self.storage.get_meta_data_apps().insert(
            App(id=0, name="shop"))
        self.events = self.storage.get_l_events()
        self.events.init(self.app_id)
        self.n_items = n_items
        self.seen = {}  # user -> set of item names
        self.views = {}  # user -> [(ms, item)]
        self.n_events = {}  # user -> view and buy events in the store
        self.unavailable = set()
        rng = np.random.default_rng(5)
        users = [f"u{j}" for j in range(N_USERS)] + ["visitor", "manyviews"]
        u, i, ms, kinds = [], [], [], []
        for k, user in enumerate(users):
            n = 25 if user == "manyviews" else int(rng.integers(0, 12))
            if user in ("u3", "u7"):
                n = 0
            if user == "u15":
                n = 6
            for _ in range(n):
                u.append(k)
                i.append(int(rng.integers(0, n_items)))
                ms.append(int(T0.timestamp() * 1000) + len(ms) * 1000)
                kinds.append("view" if rng.random() < 0.8 else "buy")
        u, i, ms = np.asarray(u), np.asarray(i), np.asarray(ms)
        for kind in ("view", "buy"):
            pick = np.asarray([k == kind for k in kinds])
            self.events.insert_columns_encoded(
                self.app_id, event=kind, entity_type="user",
                target_entity_type="item", entity_names=users,
                entity_codes=u[pick].astype(np.int32),
                target_names=[f"i{j}" for j in range(n_items)],
                target_codes=i[pick].astype(np.int32),
                values=np.ones(int(pick.sum()), np.float32),
                event_times_ms=ms[pick],
            )
        for k, j, t, kind in zip(u, i, ms, kinds):
            self.note(users[k], f"i{j}", kind, int(t))
        self.set_unavailable(["i1", "i2", "i250"])
        self.algo = ECommAlgorithm(ECommAlgorithmParams(
            app_name="shop", unseen_only=True, rank=RANK,
            **{"constraint_ttl_s": 0.05, "exclude_widths": (16, 64),
               "include_widths": (8,), "warm_max_batch": 16, **params}))
        self.model = self.algo.prepare_serving(None, seeded_model(n_items))

    def note(self, user, item, kind, ms):
        self.n_events[user] = self.n_events.get(user, 0) + 1
        self.seen.setdefault(user, set()).add(item)
        if kind == "view":
            self.views.setdefault(user, []).append((ms, item))

    def set_unavailable(self, items):
        self.events.insert(Event(
            event="$set", entity_type="constraint",
            entity_id="unavailableItems",
            properties=DataMap({"items": list(items)}),
            event_time=dt.datetime.now(dt.timezone.utc),
        ), self.app_id)
        self.unavailable = set(items)

    def expected(self, q: Query):
        m = self.model
        recent = [it for _, it in sorted(
            self.views.get(q.user, []), key=lambda r: -r[0])]
        query = {"user": q.user, "num": q.num}
        for ours, theirs in (("categories", "categories"),
                             ("white_list", "whiteList"),
                             ("black_list", "blackList")):
            if getattr(q, ours) is not None:
                query[theirs] = list(getattr(q, ours))
        return reference.predict(
            m.user_factors, m.item_factors, m.user_index.to_dict(),
            m.item_index.to_dict(), query, seen=self.seen.get(q.user, ()),
            recent=recent, unavailable=self.unavailable,
            item_categories=m.item_categories,
            category_names=m.category_names,
        )

    def check(self, queries, prepared=None):
        got = dict(self.algo.batch_predict(
            self.model, list(enumerate(queries)), prepared))
        assert sorted(got) == list(range(len(queries)))
        for k, q in enumerate(queries):
            want = self.expected(q)
            served = [(s.item, s.score) for s in got[k].item_scores]
            assert [i for i, _ in served] == [i for i, _ in want], (q, want)
            np.testing.assert_allclose(
                [s for _, s in served], [s for _, s in want], rtol=2e-5,
                atol=2e-5)
        return got


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    w = World(str(tmp_path_factory.mktemp("ecom")))
    yield w
    storage_mod.set_storage(None)


WHITE = tuple(f"i{j}" for j in (3, 10, 17, 24, 31, 250, 299))
CASES = {
    "plain": Query(user="u0", num=10),
    "upstream_num": Query(user="u1", num=4),
    "no_history": Query(user="u3", num=16),
    "category": Query(user="u2", num=10, categories=("c3",)),
    "two_categories": Query(user="u4", num=10, categories=("c1", "c5")),
    "multi_category_item": Query(user="u5", num=16, categories=("c0",)),
    "empty_category": Query(user="u6", num=5, categories=("nosuch",)),
    "black_list": Query(user="u8", num=5, black_list=("i4", "i9", "nosuch")),
    "white_list": Query(user="u9", num=5, white_list=WHITE),
    "empty_white_list": Query(user="u10", num=5, white_list=()),
    "white_and_category": Query(
        user="u11", num=5, white_list=WHITE, categories=("c3",)),
    "white_and_category_empty": Query(
        user="u12", num=5, white_list=("i3",), categories=("c6",)),
    "num_over_live_candidates": Query(
        user="u13", num=16, white_list=("i3", "i10", "i17")),
    "all_fields": Query(
        user="u14", num=4, white_list=WHITE, categories=("c3", "c2"),
        black_list=("i10",)),
    "zero_factor_row_no_views": Query(user="u7", num=5),
    "zero_factor_row_recent_views": Query(user="u15", num=5),
    "unknown_user_recent_views": Query(user="visitor", num=10),
    "unknown_user_over_ten_views": Query(user="manyviews", num=10),
    "unknown_user_with_category": Query(
        user="visitor", num=5, categories=("c2",)),
    "unknown_user_no_views": Query(user="ghost", num=5),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_device_path_matches_the_reference(world, case):
    got = world.check([CASES[case]])
    if case in ("empty_category", "empty_white_list",
                "white_and_category_empty", "unknown_user_no_views",
                "zero_factor_row_no_views"):
        assert got[0].item_scores == ()
    if case == "multi_category_item":
        names = {s.item for s in got[0].item_scores}
        assert any(int(n[1:]) % 7 != 0 for n in names) or len(names) < 16


class _CountedReads:
    """The store's find_by_entities, counting the entities of each call
    while the block runs."""

    def __init__(self, world):
        self.store = type(world.events)
        self.calls = []

    def __enter__(self):
        real = self.real = self.store.find_by_entities
        calls = self.calls

        def counting(self, *a, **kw):
            calls.append(len(kw["entity_ids"]))
            return real(self, *a, **kw)

        self.store.find_by_entities = counting
        return calls

    def __exit__(self, *exc):
        self.store.find_by_entities = self.real


def _events_read() -> float:
    return _metrics.get_registry().counter(
        "pio_ecom_store_events_read_total", "").labels().value


@pytest.mark.parametrize("prepared", [False, True])
def test_a_batch_of_mixed_shapes_is_one_store_read_a_query_and_one_run(
    world, prepared
):
    """Every query reads its own user's history, once: inside the batch
    where nothing was prepared, before it where the values come with
    the queries (the batch then reads nothing)."""
    queries = [CASES[k] for k in sorted(CASES)][:16]
    values = None
    events0 = _events_read()
    with _CountedReads(world) as before_the_batch:
        if prepared:
            values = [world.algo.prepare_query(world.model, q)
                      for q in queries]
    events_prepared = _events_read() - events0
    runs = retrieval._m_operand_transfers().labels(component="ecommerce")
    runs0 = runs.value
    with _CountedReads(world) as in_the_batch:
        world.check(queries, values)
    assert before_the_batch + in_the_batch == [1] * 16
    assert (in_the_batch == []) == prepared
    # the same events whichever side of the batch they were read on
    assert _events_read() - events0 == sum(
        world.n_events.get(q.user, 0) for q in queries)
    assert (events_prepared > 0) == prepared
    # known users and recent-view users rode ONE fused program run
    assert runs.value - runs0 == 1


CELL_SHAPES = {  # the query shapes of `ecom-taobao-d512.query-filtered`
    "plain": ["plain"],
    "one_category": ["category"],
    "black_list": ["black_list"],
    "white_list": ["white_list"],
    "cosine_visitor": ["unknown_user_recent_views"],
    "mixed_in_one_batch": [
        "plain", "category", "black_list", "white_list",
        "unknown_user_recent_views", "upstream_num",
    ],
}


@pytest.mark.parametrize("shape", sorted(CELL_SHAPES))
def test_a_batch_goes_up_as_one_packed_operand_and_answers_as_the_host_path(
    world, shape
):
    """Rows, id lists, category codes and the cosine flags of a batch
    are one transfer, and what the program makes of them is what the
    float64 reference (``check``) and the host ``_finish`` path make of
    the same query, id for id."""
    queries = [CASES[k] for k in CELL_SHAPES[shape]]
    retriever = world.model._retriever
    sent = retrieval._m_operand_transfers().labels(
        component=retriever.component)
    before = sent.value
    got = world.check(queries)
    assert sent.value - before == 1
    for k, q in enumerate(queries):
        pq = world.algo.prepare_query(world.model, q)
        scores = world.model.item_factors @ pq.row
        if pq.cosine:
            scores = scores * retriever.reciprocal_norms
        host = world.algo._finish(
            world.model, q, scores, world.model._constraints.get(), pq.seen)
        assert [s.item for s in got[k].item_scores] == [
            s.item for s in host.item_scores]
        assert got[k].item_scores  # no shape of the cell answers nothing


PREPARED_CASES = {
    "plain": CASES["plain"],
    "category": CASES["category"],
    "black_list": CASES["black_list"],
    "white_list": CASES["white_list"],
    "visitor_cosine": CASES["unknown_user_recent_views"],
    "no_recent_item": CASES["unknown_user_no_views"],
    "over_the_ladder": Query(
        user="u22", num=5,
        black_list=tuple(f"i{j}" for j in range(100, 200))),
}


@pytest.mark.parametrize("case", sorted(PREPARED_CASES))
def test_answers_with_prepared_values_equal_answers_prepared_inline(
    world, case
):
    """One code path, two call sites: the value made when the query
    arrives serves the answer the batch would have made for itself
    (and the reference's)."""
    q = PREPARED_CASES[case]
    fallbacks = _metrics.get_registry().counter(
        "pio_ecom_host_fallback_total", "").labels()
    before = fallbacks.value
    inline = world.check([q])[0]
    value = world.algo.prepare_query(world.model, q)
    with _CountedReads(world) as reads:
        served = world.check([q], [value])[0]
    assert served == inline and reads == []
    assert value.on_host == (case == "over_the_ladder")
    assert fallbacks.value - before == 2 * (case == "over_the_ladder")
    assert (value.row is None) == (case == "no_recent_item")
    assert value.cosine == (case in ("visitor_cosine", "no_recent_item"))


def test_an_event_committed_before_the_query_is_enqueued_is_excluded(world):
    """The configuration's first guarantee, through the executor: the
    history is read when the query arrives (here while another engine's
    batch holds the one serve slot), which is after it was sent, so a
    view acknowledged before that is out of the answer; and the batch,
    when its turn comes, reads nothing more."""
    import threading
    import types

    from predictionio_tpu.api.engine_server import (
        DeployedEngine, _BatchingExecutor,
    )
    from predictionio_tpu.controller.engine import EngineParams
    from predictionio_tpu.models.ecommerce.engine import (
        DataSourceParams, ecommerce_engine,
    )

    dep = DeployedEngine(
        ecommerce_engine(),
        EngineParams(
            data_source_params=("", DataSourceParams(app_name="shop")),
            algorithm_params_list=(("ecomm", world.algo.params),),
        ),
        types.SimpleNamespace(id="ecom-arrival"), [world.model],
    )
    assert dep.prepares_queries

    class Holder:
        entered, go = threading.Event(), threading.Event()

        def serve_batch(self, queries):
            self.entered.set()
            assert self.go.wait(10.0)
            return list(queries)

    q = Query(user="u26", num=5)
    first = [s.item for s in world.check([q])[0].item_scores]
    prepared = _metrics.get_registry().histogram(
        "pio_serving_prepare_seconds", "", labels=("version",),
        buckets=_metrics.LATENCY_BUCKETS_S).labels(version="ecom-arrival")
    ex = _BatchingExecutor(max_batch=8)
    try:
        held = ex.submit_nowait(Holder(), "held")
        assert Holder.entered.wait(10.0)
        world.events.insert(Event(
            event="view", entity_type="user", entity_id="u26",
            target_entity_type="item", target_entity_id=first[0],
            event_time=dt.datetime.now(dt.timezone.utc)), world.app_id)
        world.note("u26", first[0], "view", int(time.time() * 1000))
        with _CountedReads(world) as reads:
            fut = ex.submit_nowait(dep, q)
            deadline = time.time() + 10
            while prepared.count < 1 and time.time() < deadline:
                time.sleep(0.005)
            assert reads == [1] and prepared.count == 1
            Holder.go.set()
            served = [s.item for s in fut.result(timeout=10).item_scores]
            assert reads == [1]
        assert held.result(timeout=10) == "held"
    finally:
        Holder.go.set()
        ex.close()
    assert first[0] not in served and served[:4] == first[1:5]
    assert served == [
        s.item for s in world.check([q])[0].item_scores]


def test_a_constraint_change_is_honoured_after_the_ttl(world):
    q = Query(user="u20", num=5)
    first = [s.item for s in world.check([q])[0].item_scores]
    world.set_unavailable(["i1", "i2", "i250", first[0], first[1]])
    deadline = time.time() + 10
    while time.time() < deadline:  # the TTL tick refreshes out of band
        world.algo.batch_predict(world.model, [(0, q)])
        time.sleep(0.1)
        now = [s.item for s in world.algo.batch_predict(
            world.model, [(0, q)])[0][1].item_scores]
        if first[0] not in now and first[1] not in now:
            break
    later = [s.item for s in world.check([q])[0].item_scores]
    assert first[0] not in later and first[1] not in later


def test_an_event_written_by_another_process_is_seen_by_the_next_query(world):
    q = Query(user="u21", num=5)
    first = [s.item for s in world.check([q])[0].item_scores]
    code = (
        "import datetime as dt, json, sys\n"
        "from predictionio_tpu.data.event import Event\n"
        "from predictionio_tpu.data.storage import Storage\n"
        "s = Storage(json.loads(sys.argv[1]))\n"
        "s.get_l_events().insert(Event(event='view', entity_type='user',"
        " entity_id='u21', target_entity_type='item',"
        " target_entity_id=sys.argv[2],"
        " event_time=dt.datetime.now(dt.timezone.utc)), int(sys.argv[3]))\n"
    )
    import json

    subprocess.run(
        [sys.executable, "-c", code, json.dumps(world.config), first[0],
         str(world.app_id)],
        check=True, env=dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu"),
    )
    world.note("u21", first[0], "view", int(time.time() * 1000))
    second = [s.item for s in world.check([q])[0].item_scores]
    assert first[0] not in second and second[:4] == first[1:5]


def test_a_list_over_the_ladder_is_answered_on_the_host_not_compiled(world):
    black = tuple(f"i{j}" for j in range(100, 200))  # 100 > the top, 64
    cold = _metrics.get_registry().counter(
        "pio_cold_compiles_total", "", labels=("site",))
    fallbacks = _metrics.get_registry().counter(
        "pio_ecom_host_fallback_total", "")
    before = fallbacks.labels().value
    size0 = retrieval._fused_topn_single._cache_size()
    with _cc.compile_site("serving"):
        world.check([
            Query(user="u22", num=5, black_list=black),
            Query(user="u23", num=40),  # num over warm_num
            Query(user="u24", num=5, categories=tuple(CATS[:5])),  # 5 > 4
            Query(user="visitor", num=5, black_list=black),
            # a whiteList over the top (8): its rows alone are scored
            Query(user="u25", num=5, white_list=black[:12]),
            Query(user="visitor", num=5, white_list=black[:12],
                  categories=tuple(CATS[:2])),
        ])
    assert fallbacks.labels().value - before == 6
    assert retrieval._fused_topn_single._cache_size() == size0
    assert cold.labels(site="serving").value == 0


def test_after_warm_no_corner_of_the_ladder_compiles(tmp_path):
    """A catalog size no other test uses: every executable this test
    meets is compiled by its own warm()."""
    w = World(str(tmp_path), n_items=307, exclude_widths=(16, 64),
              include_widths=(8,), warm_max_batch=16)
    try:
        w.algo.warm(w.model)
        assert w.model._retriever.ladder_size() == 2 * 2 * 2
        cold = _metrics.get_registry().counter(
            "pio_cold_compiles_total", "", labels=("site",))
        cold0 = cold.labels(site="serving").value
        size0 = retrieval._fused_topn_single._cache_size()
        long_black = tuple(f"i{j}" for j in range(200, 250))
        corners = [
            [Query(user="u0", num=1)],
            [Query(user="u0", num=16, black_list=long_black)],
            [Query(user="u1", num=4, white_list=WHITE)],
            [Query(user="u1", num=4, white_list=WHITE, black_list=long_black,
                   categories=("c1", "c2", "c3", "c4"))],
            [Query(user="visitor", num=10, categories=("c2",))],
            # a full batch, known and recent-view users mixed, and one
            # over the executor's max batch (split, not compiled)
            [Query(user=f"u{j}", num=4 + j % 3) for j in range(15)]
            + [Query(user="visitor", num=7)],
            [Query(user=f"u{j % 30}", num=10, black_list=long_black[:j])
             for j in range(21)],
        ]
        with _cc.compile_site("serving"):
            for batch in corners:
                w.check(batch)
        assert cold.labels(site="serving").value - cold0 == 0
        assert retrieval._fused_topn_single._cache_size() == size0
    finally:
        storage_mod.set_storage(None)


def test_the_model_pickles_as_arrays_with_no_object_an_item(world):
    model = seeded_model()
    assert model.items is None and model.item_categories.shape == (N_ITEMS, 2)
    blob = pickle.dumps(model, protocol=pickle.HIGHEST_PROTOCOL)
    assert b"Item" not in blob.replace(b"ItemRetriever", b"")
    back = pickle.loads(blob)
    assert back.category_names == model.category_names
    np.testing.assert_array_equal(back.item_categories, model.item_categories)
    # 300 items with a category tuple each would add ~60 bytes an item
    arrays = sum(a.nbytes for a in (
        model.user_factors, model.item_factors, model.item_categories))
    names = sum(len(k) + 12 for k in model.item_index) * 2
    assert len(blob) < arrays + names + 4096
    # a blob from before the arrays (an ``items`` dict) still loads
    state = model.__getstate__()
    state["items"] = {j: Item(categories=item_cats(j)) for j in range(N_ITEMS)}
    for name in ("category_names", "item_categories"):
        del state[name]
    old = ECommModel.__new__(ECommModel)
    old.__setstate__(state)
    np.testing.assert_array_equal(old.item_categories, model.item_categories)


@pytest.mark.parametrize("order", ["by_user", "shuffled"])
def test_a_read_by_entity_costs_the_entitys_events_not_the_store(
        tmp_path, order, monkeypatch):
    from predictionio_tpu.data.storage.sqlite import SQLiteLEvents

    monkeypatch.setattr(SQLiteLEvents, "_PAGE_ROWS", 500)
    storage = Storage(storage_config(str(tmp_path)))
    app_id = storage.get_meta_data_apps().insert(App(id=0, name="a"))
    ev = storage.get_l_events()
    ev.init(app_id)
    rng = np.random.default_rng(2)
    n, n_users = 20_000, 400  # 40 pages of 500 events
    u = np.sort(rng.integers(0, n_users, n)).astype(np.int32)
    if order == "shuffled":
        u = rng.permutation(u)
    i = rng.integers(0, 900, n).astype(np.int32)
    ev.insert_columns_encoded(
        app_id, event="view", entity_type="user", target_entity_type="item",
        entity_names=[f"u{j}" for j in range(n_users)], entity_codes=u,
        target_names=[f"i{j}" for j in range(900)], target_codes=i,
        values=np.ones(n, np.float32), event_times_ms=np.arange(n) * 10,
    )
    assert ev.build_entity_index(app_id) == 40
    assert ev.build_entity_index(app_id) == 0  # kept in the store
    for user in (5, 123, 399):
        mine = np.flatnonzero(u == user)
        pages = len(np.unique(mine // 500))
        before = dict(ev.read_stats)
        got = ev.find_by_entities(
            app_id, entity_type="user", entity_ids=[f"u{user}"],
            event_names=["view", "buy"], target_entity_type="item",
        )[f"u{user}"]
        assert sorted(t for _, t, _ in got) == sorted(f"i{j}" for j in i[mine])
        assert [ms for _, _, ms in got] == sorted(mine * 10, reverse=True)
        # no page decoded; index rows read = the pages the entity is in,
        # which is at most its events, however the events were ordered
        assert ev.read_stats["pages_decoded"] == before["pages_decoded"]
        assert ev.read_stats["index_rows"] - before["index_rows"] == pages
        assert pages <= len(mine)
        if order == "by_user":
            assert pages <= 2
        # the one-entity find() goes the same way, with the events' ids
        before = dict(ev.read_stats)
        found = list(ev.find(
            app_id, entity_type="user", entity_id=f"u{user}",
            event_names=["view"], target_entity_type="item"))
        assert ev.read_stats["pages_decoded"] == before["pages_decoded"]
        assert sorted(e.event_id for e in found) == sorted(
            f"pg-{m // 500 + 1}-{m % 500}" for m in mine)
    # a second process's store object builds nothing and reads the same
    other = Storage(storage_config(str(tmp_path))).get_l_events()
    assert len(other.find_by_entities(
        app_id, entity_type="user", entity_ids=["u5"], event_names=["view"],
        target_entity_type="item")["u5"]) == int((u == 5).sum())
    assert other.read_stats["pages_decoded"] == 0


def test_stage_names_cover_the_store_read_and_the_mask_prep(world):
    from predictionio_tpu.utils import tracing as tr

    with tr.stage_totals() as totals:
        world.algo.batch_predict(world.model, [
            (0, CASES["plain"]), (1, CASES["unknown_user_recent_views"])])
    # every stage but the quantized tier's refine and a mesh's merge:
    # this table is float32, on one device
    assert set(totals) == set(tr.BATCH_STAGES) - {tr.REFINE, tr.MERGE}
    assert all(v > 0 for v in totals.values())


def test_the_query_takes_upstreams_field_names(world):
    q = world.algo.query_from_json({
        "user": "u1", "num": 4, "categories": ["c1"],
        "whiteList": ["i1", "i2"], "blackList": ["i3"]})
    assert q == Query(user="u1", num=4, categories=("c1",),
                      white_list=("i1", "i2"), black_list=("i3",))
    assert world.algo.query_from_json(
        {"user": "u1", "white_list": ["i1"]}).white_list == ("i1",)


def test_a_constraint_written_just_after_a_read_is_served_inside_the_ttl():
    """The refresh is kicked at half the TTL: the worst case (a write
    just after the cache read the store) is honoured before the TTL is
    over, as long as queries tick the cache."""
    from predictionio_tpu.data.constraints import ConstraintCache

    value = {"now": frozenset({"a"})}
    cache = ConstraintCache("app", ttl_s=0.6, reader=lambda: value["now"])
    assert cache.get() == {"a"}  # the prime: an inline read
    value["now"], written = frozenset({"a", "b"}), time.monotonic()
    while cache.get() != {"a", "b"}:
        assert time.monotonic() - written < 0.6
        time.sleep(0.005)
    assert time.monotonic() - written >= 0.3  # never read on every batch


@pytest.mark.parametrize("rows,n,ties", [
    # whole blocks of 1,024, as every resident table is since PR 36
    (40_960, 16, False), (40_960, 16, True), (33_792, 64, True),
    (5_000, 16, True),  # too narrow for blocks: lax.top_k itself
])
def test_block_wise_top_k_is_lax_top_k_ties_and_dead_slots_included(
        rows, n, ties):
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(rows + n)
    scores = rng.standard_normal((5, rows)).astype(np.float32)
    if ties:  # few distinct values: every rank is a tie
        scores = np.round(scores * 2).astype(np.float32)
    scores[1, : rows - 7] = -np.inf  # fewer live candidates than n
    scores[2] = -np.inf  # none at all
    want_s, want_i = jax.lax.top_k(jnp.asarray(scores), n)
    got_s, got_i = retrieval._top_k(jnp.asarray(scores), n)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
    live = np.asarray(want_s) > -np.inf
    np.testing.assert_array_equal(
        np.asarray(got_i)[live], np.asarray(want_i)[live])
    assert np.asarray(got_i).max() < rows


@pytest.mark.parametrize("rows,width", [(6144, 16), (6144, 1536), (2048, 1)])
def test_membership_by_one_hot_product_is_the_scatter(rows, width):
    import jax.numpy as jnp

    rng = np.random.default_rng(width)
    ids = rng.integers(0, rows + 500, (6, width)).astype(np.int32)
    ids[0] = rows  # all sentinel
    ids[1, : width // 2] = ids[1, 0]  # repeats
    want = np.zeros((6, rows), bool)
    for b in range(6):
        want[b, ids[b][ids[b] < rows]] = True
    got = np.asarray(retrieval._membership(jnp.asarray(ids), rows))
    np.testing.assert_array_equal(got, want)
