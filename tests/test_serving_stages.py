"""The query path timed from inside: the per-request stage families add up
to the handler's latency sample, the per-batch families split ``predict``,
the header-traced span chain carries the same timestamps, the profiler
capture holds the ``pio:`` annotations without the Python tracer, and the
collector's pauses are counted."""

from __future__ import annotations

import datetime as dt
import gc
import glob
import http.client
import json
import threading

import pytest

from predictionio_tpu.api.engine_server import EngineServer, ServerConfig
from predictionio_tpu.data.storage.base import EngineInstance
from predictionio_tpu.utils import health
from predictionio_tpu.utils import metrics as _metrics
from predictionio_tpu.utils import profiling
from predictionio_tpu.utils import tracing as tr
from predictionio_tpu.utils.tracing import BATCH_STAGES
from predictionio_tpu.workflow import CoreWorkflow, WorkflowContext

REQUEST_STAGES = ("queue_wait", "slot_wait", "predict", "finish")
# what the handler does before the enqueue (a datetime, json.loads,
# query_from_json): 0.1 ms on a serving host, 0.2-0.3 on a loaded test
# box. No stage is this short on the test's 1 ms window but slot_wait
# and finish, whose place in the tiling the span tests hold to 0.01 ms
PARSE_ALLOWANCE_S = 0.0005


def _fake(storage):
    from tests.test_engine_server import make_engine, train_instance

    train_instance(storage)
    return make_engine(), {"qx": 1}


def _reco(storage):
    from predictionio_tpu.models.recommendation import recommendation_engine
    from tests.test_recommendation import engine_params, populate

    populate(storage)
    now = dt.datetime.now(dt.timezone.utc)
    CoreWorkflow.run_train(
        recommendation_engine(), engine_params(),
        EngineInstance(
            id="", status="", start_time=now, end_time=now,
            engine_id="rec", engine_version="1",
            engine_variant="engine.json",
            engine_factory="predictionio_tpu.models.recommendation",
        ),
        ctx=WorkflowContext(mode="training", storage=storage),
    )
    return recommendation_engine(), {"user": "u3", "num": 4}


def _ecom(storage):
    from predictionio_tpu.data.event import DataMap, Event
    from predictionio_tpu.data.storage.base import App
    from predictionio_tpu.models.ecommerce.engine import ecommerce_engine

    app_id = storage.get_meta_data_apps().insert(App(id=0, name="shop"))
    events = storage.get_l_events()
    events.init(app_id)
    t0 = dt.datetime(2026, 9, 1, tzinfo=dt.timezone.utc)

    def put(event, etype, eid, target=None, props=None):
        events.insert(Event(
            event=event, entity_type=etype, entity_id=eid,
            target_entity_type="item" if target else None,
            target_entity_id=target, properties=DataMap(props or {}),
            event_time=t0), app_id)

    for j in range(12):
        put("$set", "item", f"i{j}", props={"categories": [f"c{j % 3}"]})
    for u in range(8):
        put("$set", "user", f"u{u}")
        for j in range(4):
            put("rate", "user", f"u{u}", target=f"i{(u + 3 * j) % 12}",
                props={"rating": float(1 + (u + j) % 5)})
    engine = ecommerce_engine()
    params = engine.jvalue_to_engine_params({
        "datasource": {"params": {"app_name": "shop"}},
        "algorithms": [{"name": "ecomm", "params": {
            "app_name": "shop", "rank": 4, "num_iterations": 3, "seed": 1,
            "unseen_only": True, "seen_events": ["rate"],
            "exclude_widths": [16], "include_widths": [8],
            "warm_max_batch": 8}}],
    })
    now = dt.datetime.now(dt.timezone.utc)
    CoreWorkflow.run_train(
        engine, params,
        EngineInstance(
            id="", status="", start_time=now, end_time=now,
            engine_id="ecom", engine_version="1",
            engine_variant="engine.json",
            engine_factory=("predictionio_tpu.models.ecommerce.engine."
                            "ECommerceEngineFactory"),
        ),
        ctx=WorkflowContext(mode="training", storage=storage),
    )
    return engine, {"user": "u3", "num": 4, "categories": ["c1", "c2"]}


ENGINES = {"fake": _fake, "reco": _reco, "ecom": _ecom}
# the stages each engine brackets: every engine's serve_batch enters
# host_prep (supplement) and build (serving.serve); the recommendation
# engine's float32 path names its dispatch (the upload inside it) and
# its blocking fetch too;
# the e-commerce engine carves its store read and its list assembly
# out of host prep (its table is float32: nothing is refined), and its
# workflow's mesh holds the suite's eight devices, so its retriever is
# row-sharded and each batch merges the shards' candidates
ENTERED = {
    "fake": (tr.HOST_PREP, tr.BUILD),
    "reco": (tr.HOST_PREP, tr.DISPATCH, tr.UPLOAD, tr.DEVICE_WAIT, tr.BUILD),
    "ecom": tuple(s for s in BATCH_STAGES if s != tr.REFINE),
}


@pytest.fixture(params=sorted(ENGINES))
def served(request, mem_storage):
    """(engine name, running server, a query body) for each engine."""
    engine, body = ENGINES[request.param](mem_storage)
    server = EngineServer(
        engine,
        ServerConfig(port=0, access_key="sekrit"),
        storage=mem_storage,
    ).start()
    try:
        yield request.param, server, body
    finally:
        server.shutdown()
        health.unregister("serving-drain")


def family(name):
    return {f.name: f for f in _metrics.get_registry().families()}[name]


def total(name):
    """(sum, count) of a histogram family over all its label sets;
    (value, None) of a counter."""
    fam = family(name)
    children = [child for _, child in fam.children()]
    if fam.kind == "counter":
        return sum(c.value for c in children), None
    return sum(c.sum for c in children), sum(c.count for c in children)


def post(port, body, headers=None):
    conn = http.client.HTTPConnection("localhost", port, timeout=30)
    try:
        conn.request(
            "POST", "/queries.json", json.dumps(body),
            {"Content-Type": "application/json", **(headers or {})},
        )
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def get(port, path):
    conn = http.client.HTTPConnection("localhost", port, timeout=30)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read().decode("utf-8")
    finally:
        conn.close()


def test_request_stages_add_up_to_the_latency_sample(served):
    _, server, body = served
    names = [f"pio_serving_{s}_seconds" for s in REQUEST_STAGES]
    requests0, _ = total("pio_serving_requests_total")
    counts0 = [total(n)[1] for n in names]
    parses = []
    for _ in range(12):  # one at a time: each delta is one request's
        before = [total(n)[0] for n in names]
        latency0 = total("pio_serving_latency_seconds")[0]
        assert post(server.port, body)[0] == 200
        stages = sum(total(n)[0] - b for n, b in zip(names, before))
        latency = total("pio_serving_latency_seconds")[0] - latency0
        parses.append(latency - stages)
    # the sample is the parse and the four stages, on one clock: never
    # less than the stages, and the quietest request's parse is short (a
    # busy test box stretches some, so the least is what is held to it)
    assert min(parses) >= 0.0 and min(parses) < PARSE_ALLOWANCE_S
    assert max(parses) < 0.02
    served_now = total("pio_serving_requests_total")[0] - requests0
    assert served_now == 12
    assert [total(n)[1] - c for n, c in zip(names, counts0)] == [12] * 4


def test_stage_times_tile_enqueue_to_end_exactly():
    from predictionio_tpu.api.engine_server import _StageTimes

    times = _StageTimes()
    times.enqueued, times.closed, times.started, times.served = (
        10.0, 10.002, 10.0025, 10.004)
    stages = times.stages(10.0045)
    assert [name for name, _, _ in stages] == list(REQUEST_STAGES)
    assert [start for _, start, _ in stages] == [10.0, 10.002, 10.0025, 10.004]
    assert sum(s for _, _, s in stages) == pytest.approx(0.0045, abs=1e-12)
    tr.clear()
    times.record_spans(10.0045)  # untraced: nothing
    assert tr.high_water() == 0


def test_batch_stages_count_one_a_batch_inside_predict(served):
    name, server, body = served
    fams = {s: f"pio_serving_batch_{s}_seconds" for s in BATCH_STAGES}
    before = {s: total(n) for s, n in fams.items()}
    batches0 = total("pio_serving_batch_fill")[1]
    predict0 = total("pio_serving_predict_seconds")[0]
    unstaged0 = total("pio_serving_batch_unstaged_seconds")
    for _ in range(6):  # one at a time: a batch of one each
        assert post(server.port, body)[0] == 200
    batches = total("pio_serving_batch_fill")[1] - batches0
    assert batches == 6
    spent = 0.0
    for stage in BATCH_STAGES:
        seconds, count = (a - b for a, b in zip(total(fams[stage]), before[stage]))
        assert count == (batches if stage in ENTERED[name] else 0)
        if stage != tr.UPLOAD:  # inside dispatch: its seconds are there
            spent += seconds
    predict = total("pio_serving_predict_seconds")[0] - predict0
    assert 0.0 < spent <= predict
    # what no stage covers is the rest of predict, one sample a batch
    unstaged, count = (a - b for a, b in zip(
        total("pio_serving_batch_unstaged_seconds"), unstaged0))
    assert count == batches
    assert spent + unstaged == pytest.approx(predict, abs=1e-9)


def test_traced_request_chains_the_stages_under_batch(served):
    name, server, body = served
    tr.clear()
    assert post(server.port, body, {"X-PIO-Trace-Id": "stages-1"})[0] == 200
    status, text = get(
        server.port, "/debug/traces.json?traceId=stages-1&accessKey=sekrit")
    assert status == 200
    spans = {s["name"]: s for s in json.loads(text)["spans"]}
    assert set(spans) == {"http:/queries.json", "batch", *REQUEST_STAGES}
    assert spans["batch"]["parentId"] == spans["http:/queries.json"]["spanId"]
    for stage in REQUEST_STAGES:
        assert spans[stage]["parentId"] == spans["batch"]["spanId"]
    # the children tile their parent (rounded to the microsecond each)
    tiled = sum(spans[s]["durationMs"] for s in REQUEST_STAGES)
    assert tiled == pytest.approx(spans["batch"]["durationMs"], abs=0.01)
    assert spans["batch"]["durationMs"] <= (
        spans["http:/queries.json"]["durationMs"])
    # ... in order, on wall-clock starts derived from the one clock
    starts = [spans[s]["startMs"] for s in REQUEST_STAGES]
    assert starts == sorted(starts)
    attrs = spans["predict"]["attrs"]
    assert attrs["batch_size"] == 1
    assert set(attrs["stages_ms"]) == set(ENTERED[name])
    # upload lies inside dispatch: its milliseconds are there already
    assert sum(ms for stage, ms in attrs["stages_ms"].items()
               if stage != tr.UPLOAD) <= spans["predict"]["durationMs"] + 0.01
    assert "attrs" not in spans["queue_wait"]


def test_untraced_request_writes_nothing_to_the_span_ring(served):
    _, server, body = served
    tr.clear()
    assert post(server.port, body)[0] == 200
    assert tr.high_water() == 0 and tr.dump() == []


def test_http_family_times_the_query_route_alone(served):
    _, server, body = served
    _, count0 = total("pio_http_request_seconds")
    latency0 = total("pio_serving_latency_seconds")[0]
    http0 = total("pio_http_request_seconds")[0]
    for _ in range(3):
        assert post(server.port, body)[0] == 200
        assert get(server.port, "/metrics")[0] == 200
        assert get(server.port, "/status.json")[0] == 200
    assert total("pio_http_request_seconds")[1] - count0 == 3
    # the transport's span encloses the handler's
    assert (total("pio_http_request_seconds")[0] - http0
            > total("pio_serving_latency_seconds")[0] - latency0)


def test_handoff_and_respond_are_observed_once_a_timed_request(served):
    _, server, body = served
    names = ("pio_http_request_seconds", "pio_http_handoff_seconds",
             "pio_http_respond_seconds")
    before = [total(n) for n in names]
    for _ in range(3):
        assert post(server.port, body)[0] == 200
        assert get(server.port, "/metrics")[0] == 200
        assert get(server.port, "/status.json")[0] == 200
    (http, n_http), (handoff, n_handoff), (respond, n_respond) = (
        (a - b for a, b in zip(total(n), was))
        for n, was in zip(names, before)
    )
    assert n_http == n_handoff == n_respond == 3
    # both lie inside the transport's span, after the handler's own
    assert 0.0 < handoff and 0.0 < respond
    assert handoff + respond < http


def test_an_untimed_route_observes_neither_handoff_nor_respond():
    """A future that a handler returns on a route the server does not
    time is awaited and answered like any other, and counted nowhere."""
    import concurrent.futures

    from predictionio_tpu.api.aio_http import AsyncJsonHTTPServer

    def handle(method, path, query, body, form):
        fut = concurrent.futures.Future()
        fut.resolved_at = 0.0
        fut.set_result((200, {"path": path}))
        return fut

    server = AsyncJsonHTTPServer(
        handle, "127.0.0.1", 0, "untimed-test",
        timed_routes=(("POST", "/timed"),),
    ).start()
    names = ("pio_http_handoff_seconds", "pio_http_respond_seconds")
    try:
        before = [total(n)[1] for n in names]
        assert get(server.port, "/other") == (200, '{"path": "/other"}')
        assert [total(n)[1] for n in names] == before
    finally:
        server.shutdown()


def gc_child(generation):
    return dict(family("pio_gc_pause_seconds").children())[(generation,)]


def test_a_forced_collection_moves_the_gc_pause_count(served):
    health.install_gc_pause_hook()  # once a process, whoever asks
    assert sum(isinstance(cb, health._GcPauses) for cb in gc.callbacks) == 1
    before = gc_child("2").count, total("pio_gc_full_pause_seconds")[1]
    young = gc_child("0").count
    gc.collect()
    gc.collect(0)
    # a full collection is in both families, a young one in the first
    assert gc_child("2").count >= before[0] + 1
    assert total("pio_gc_full_pause_seconds")[1] == gc_child("2").count
    assert gc_child("0").count >= young + 1
    _, server, _ = served
    text = get(server.port, "/metrics")[1]
    assert 'pio_gc_pause_seconds_count{generation="2"}' in text
    assert "pio_gc_full_pause_seconds_count " in text


@pytest.mark.parametrize("reader", ["_render", "snapshot"])
def test_a_collection_inside_a_scrape_of_its_own_child_does_not_hang(reader):
    """A collection starts on whichever thread allocated last: here the
    one that reads the generation's child, inside the child's lock. The
    hook must not wait for that lock (the thread would wait for itself,
    and the interpreter would never collect again); the sample it could
    not place is placed at the next collection."""
    health.install_gc_pause_hook()
    gc.collect(0)  # the child exists, nothing is pending
    fam, child = family("pio_gc_pause_seconds"), gc_child("0")

    class CollectsWhenCopied(list):
        def __iter__(self):
            gc.collect(0)
            return super().__iter__()

    before = child.count
    child._counts = CollectsWhenCopied(child._counts)
    try:
        read = (
            (lambda: child._render(fam, ("0",))) if reader == "_render"
            else child.snapshot
        )
        thread = threading.Thread(target=read, daemon=True)
        thread.start()
        thread.join(timeout=10)
        assert not thread.is_alive()
    finally:
        child._counts = list(child._counts)
    gc.collect(0)
    assert child.count >= before + 2


def test_observe_n_times_over_and_try_observe():
    child = _metrics.Histogram("h", "h", (), (0.1, 1.0)).labels()
    child.observe(0.5, 3)
    child.observe(0.05)
    assert child.snapshot().counts == (1, 3, 0)
    assert (child.sum, child.count) == (pytest.approx(1.55), 4)
    assert child.try_observe(2.0)
    with child._lock:
        assert not child.try_observe(2.0)  # busy: the caller keeps it
    assert child.snapshot().counts == (1, 3, 1) and child.count == 5


def test_an_unknown_stage_name_is_refused():
    with pytest.raises(AssertionError):
        tr.stage("host_perp")


def batch_counts():
    return {s: total(f"pio_serving_batch_{s}_seconds")[1]
            for s in BATCH_STAGES}


def test_unstaged_is_predict_less_the_top_level_stages():
    """One batch whose stages nest: the executor's unstaged sample is
    its predict less the stages entered at depth 0, so the upload inside
    dispatch is not taken off twice, and the sleep between stages is
    what is left."""
    import time
    import types

    from predictionio_tpu.api.engine_server import _BatchingExecutor

    class Nested:
        engine_instance = types.SimpleNamespace(id="unstaged-test")

        def serve_batch(self, queries):
            with tr.stage(tr.HOST_PREP):
                time.sleep(0.005)
            time.sleep(0.02)  # in no stage
            with tr.stage(tr.DISPATCH):
                with tr.stage(tr.UPLOAD):
                    time.sleep(0.01)
                time.sleep(0.002)
            return list(queries)

    executor = _BatchingExecutor(8, 1)
    try:
        assert executor.submit(Nested(), "q") == "q"
    finally:
        executor.close()

    def child(stage):
        fam = family(f"pio_serving_{stage}_seconds")
        return dict(fam.children())[("unstaged-test",)]

    predict, unstaged = child("predict"), child("batch_unstaged")
    staged = [child(f"batch_{s}") for s in (tr.HOST_PREP, tr.DISPATCH)]
    upload = child("batch_upload")
    assert predict.count == unstaged.count == upload.count == 1
    assert upload.sum >= 0.01
    assert unstaged.sum == pytest.approx(
        predict.sum - sum(c.sum for c in staged), abs=1e-9)
    assert 0.02 <= unstaged.sum < 0.02 + upload.sum


def test_nested_stages_add_under_their_names_and_once_to_staged():
    with tr.stage_totals() as totals:
        with tr.stage(tr.DISPATCH):
            with tr.stage(tr.UPLOAD):
                pass
            with tr.stage(tr.UPLOAD):
                pass
        with tr.stage(tr.BUILD):
            pass
    assert set(totals) == {tr.DISPATCH, tr.UPLOAD, tr.BUILD}
    assert totals.depth == 0
    assert totals.staged == pytest.approx(
        totals[tr.DISPATCH] + totals[tr.BUILD], abs=1e-12)


def test_stage_outside_a_batch_observes_no_batch_family():
    from predictionio_tpu.api.engine_server import _BatchingExecutor

    executor = _BatchingExecutor(1.0, 8)  # registers the families
    try:
        before = batch_counts()
        for name in BATCH_STAGES:
            with tr.stage(name):
                pass
        with tr.stage_totals() as totals:
            with tr.stage("dispatch"):
                pass
            with tr.stage("dispatch"):  # entered twice: adds up
                pass
        assert set(totals) == {"dispatch"} and totals["dispatch"] > 0.0
        assert batch_counts() == before
    finally:
        executor.close()


def test_warm_up_outside_the_executor_observes_no_batch_family():
    import numpy as np

    from predictionio_tpu.api.engine_server import _BatchingExecutor
    from predictionio_tpu.ops.als import ServingFactors

    executor = _BatchingExecutor(1.0, 8)
    try:
        before = batch_counts()
        rng = np.random.default_rng(0)
        factors = ServingFactors(
            rng.random((20, 4), np.float32), rng.random((30, 4), np.float32))
        factors.warm(16, 8)
        assert len(factors.topn_by_user([1, 2, 3], 16)[0]) == 3
        assert batch_counts() == before
    finally:
        executor.close()


@pytest.mark.parametrize("python", [False, True], ids=["default", "python=1"])
def test_capture_under_load_holds_the_annotations(
    python, mem_storage, tmp_path
):
    import jax

    engine, body = _reco(mem_storage)
    server = EngineServer(
        engine, ServerConfig(port=0),
        storage=mem_storage,
    ).start()
    stop = threading.Event()

    def load():
        while not stop.is_set():
            post(server.port, body)

    thread = threading.Thread(target=load, daemon=True)
    thread.start()
    try:
        status, payload = profiling.ProfileCapture(str(tmp_path)).capture(
            0.5, python=python)
    finally:
        stop.set()
        thread.join(timeout=30)
        server.shutdown()
        health.unregister("serving-drain")
    assert not thread.is_alive() and status == 200
    [path] = glob.glob(f"{payload['dir']}/**/*.xplane.pb", recursive=True)
    names = set()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names |= {event.name for event in line.events}
    assert {"pio:predict", "pio:dispatch", "pio:device_wait", "pio:collect",
            "pio:finish"} <= names
    # the Python tracer's events are named "$<file>:<line> <function>"
    assert any(n.startswith("$") for n in names) == python


def test_profile_route_passes_python_through(monkeypatch):
    seen = []
    monkeypatch.setattr(
        profiling.ProfileCapture, "capture",
        lambda self, seconds, python=False: seen.append(python) or (200, {}),
    )
    profiling.profile_route("POST", {"seconds": "0.1"}, True)
    profiling.profile_route("POST", {"seconds": "0.1", "python": "1"}, True)
    assert seen == [False, True]


def test_a_scrape_refreshes_the_device_memory_gauges(served, monkeypatch):
    import jax

    class Device:
        id = 7

        def memory_stats(self):
            return {"bytes_in_use": 10, "peak_bytes_in_use": 30,
                    "bytes_limit": 100, "bytes_reserved": 40,
                    "peak_bytes_reserved": 50, "num_allocs": 3}

    monkeypatch.setattr(jax, "local_devices", lambda: [Device()])
    _, server, _ = served
    text = get(server.port, "/metrics")[1]
    for stat, value in (("peak_bytes_in_use", 30), ("peak_bytes_reserved", 50),
                        ("bytes_reserved", 40)):
        assert (f'pio_device_memory_bytes{{device="7",stat="{stat}"}} {value}'
                in text)
    assert 'stat="num_allocs"' not in text
