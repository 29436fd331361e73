"""Device-plane observability: the HBM residency ledger, the promotion
release invariant (retained-LRU eviction and rollback drive a displaced
instance's ledger bytes to zero, straggler race included), cold-compile
attribution inside a live serving batch, and the on-demand profiler
capture endpoint.
"""

import base64
import dataclasses
import datetime as dt
import http.client
import io
import json
import threading
import time
import zipfile

import numpy as np
import pytest

from predictionio_tpu.api.engine_server import (
    DeployedEngine,
    EngineServer,
    ServerConfig,
)
from predictionio_tpu.controller.engine import Engine, EngineParams
from predictionio_tpu.data.storage.base import EngineInstance
from predictionio_tpu.ops.retrieval import ItemRetriever
from predictionio_tpu.utils import compilation_cache as cc
from predictionio_tpu.utils import device_ledger as dl
from predictionio_tpu.utils import health as _health
from predictionio_tpu.utils import metrics as _metrics
from predictionio_tpu.utils import tracing
from predictionio_tpu.utils.profiling import profile_route
from predictionio_tpu.workflow.context import WorkflowContext
from predictionio_tpu.workflow.core_workflow import CoreWorkflow
from predictionio_tpu.workflow.promotion import (
    InProcessTarget,
    PromotionConfig,
    PromotionPipeline,
)

from tests import fake_engine as fe


def ledger():
    return dl.get_ledger()


class TestLedger:
    def test_register_update_close_and_gauge(self):
        led = ledger()
        before = led.total_bytes(component="unit-x")
        h = led.register("unit-x", 128, device="devA")
        assert led.total_bytes(component="unit-x") == before + 128
        h.set(64)
        assert led.total_bytes(component="unit-x") == before + 64
        h.add(36)
        assert h.nbytes == 100
        h.close()
        assert led.total_bytes(component="unit-x") == before
        # idempotent close
        h.close()
        g = _metrics.get_registry().gauge(
            "pio_device_ledger_bytes",
            "Bytes of long-lived buffers registered in the HBM residency "
            "ledger, by device, component, and owning engine-instance "
            "('-' = unowned)",
            labels=("device", "component", "owner"),
        )
        assert (
            g.labels(device="devA", component="unit-x", owner="-").value
            == 0.0
        )

    def test_scope_owns_handles_and_checks_release(self):
        led = ledger()
        scope = led.scope("inst-1")
        with scope.activate():
            h1 = led.register("unit-s", 10, device="devB")
            h2 = led.register("unit-s2", 20, device="devB")
        # outside the scope: unowned
        h3 = led.register("unit-s", 5, device="devB")
        assert scope.bytes() == 30
        assert led.owner_bytes("inst-1") == 30
        leaks = _metrics.get_registry().counter(
            "pio_device_ledger_leaks_total",
            "Release-invariant violations: a displaced instance whose "
            "ledger bytes were still nonzero after release_serving ran "
            "(the PR 13 leak class, per component)",
            labels=("component",),
        )
        base = leaks.labels(component="unit-s2").value
        h1.close()
        # one handle still open: the invariant trips and counts
        assert scope.check_released() == 20
        assert leaks.labels(component="unit-s2").value == base + 1
        h2.close()
        assert scope.check_released() == 0
        h3.close()

    def test_anchor_finalizer_closes_on_gc(self):
        led = ledger()
        before = led.total_bytes(component="unit-gc")

        class Holder:
            pass

        obj = Holder()
        led.register("unit-gc", 77, device="devC", anchor=obj)
        assert led.total_bytes(component="unit-gc") == before + 77
        del obj
        assert led.total_bytes(component="unit-gc") == before

    def test_leaked_buffer_is_visible_as_drift(self):
        """The acceptance gate: a deliberately leaked (never-registered)
        buffer shows as nonzero drift against the device's own
        accounting. XLA CPU reports no memory_stats, so the probe is
        injected: it plays the role of bytes_in_use, returning the
        ledger's registered total PLUS the leak."""
        led = ledger()
        leak = 4096
        h = led.register("unit-drift", 1000, device=None)
        import jax

        dev_label = str(jax.local_devices()[0])
        # the handle above is NOT on the jax device label; register one
        # that is, so the probe's device has ledger coverage too
        h2 = led.register("unit-drift2", 500, device=dev_label)
        try:
            def probe(device):
                covered = led.total_bytes(device=str(device))
                return covered + leak

            report = led.reconcile(probe=probe)
            assert report[dev_label]["drift"] == leak
            g = _metrics.get_registry().gauge(
                "pio_device_ledger_drift_bytes",
                "device.memory_stats() bytes_in_use minus the ledger's "
                "total for that device — sustained positive drift is "
                "untracked residency (a leak); unavailable on backends "
                "without memory stats",
                labels=("device",),
            )
            assert g.labels(device=dev_label).value == leak
        finally:
            h.close()
            h2.close()

    def test_retriever_registers_and_free_zeroes(self):
        led = ledger()
        r = ItemRetriever(
            np.random.default_rng(0)
            .standard_normal((50, 4))
            .astype(np.float32),
            component="ledger-probe",
        )
        assert led.total_bytes(component="ledger-probe") > 0
        assert led.total_bytes(component="ledger-probe-mask") > 0
        r.set_excluded_ids(np.asarray([1, 2, 3]))
        assert led.total_bytes(component="ledger-probe-mask") > 0
        r.free()
        assert led.total_bytes(component="ledger-probe") == 0
        assert led.total_bytes(component="ledger-probe-mask") == 0


# --- the promotion / retained-LRU release invariant ---


@dataclasses.dataclass
class ResidentModel:
    algo_id: int
    pd_id: int
    handle: object = None


class LedgerAlgo(fe.Algo0):
    """A fake algorithm whose prepare_serving parks 'device state' as a
    real ledger registration (adopted by the ambient DeployedEngine
    scope) and whose release_serving closes it — the GateAlgo shape of
    tests/test_promotion.py with the ledger wired through."""

    params_class = fe.AlgoParams
    query_class = fe.Query

    RESIDENT_BYTES = 1 << 20

    block = None  # threading.Event: batch_predict parks on it when set
    entered = None

    def train(self, ctx, pd) -> ResidentModel:
        return ResidentModel(self.params.id, pd.id)

    def prepare_serving(self, ctx, model: ResidentModel) -> ResidentModel:
        model.handle = ledger().register(
            "fake-resident", self.RESIDENT_BYTES, device="fake-dev"
        )
        return model

    def release_serving(self, model: ResidentModel) -> None:
        handle, model.handle = model.handle, None
        if handle is not None:
            handle.close()

    def predict(self, model: ResidentModel, query):
        cls = type(self)
        if cls.block is not None:
            if cls.entered is not None:
                cls.entered.set()
            cls.block.wait(30)
        return fe.Prediction(
            query.qx, models=((model.algo_id, model.handle is not None),)
        )


def make_engine() -> Engine:
    return Engine(
        data_source_classes=fe.DataSource0,
        preparator_classes=fe.Preparator0,
        algorithm_classes={"led": LedgerAlgo},
        serving_classes=fe.Serving0,
    )


def make_params() -> EngineParams:
    return EngineParams(
        data_source_params=("", fe.DSParams(id=7)),
        preparator_params=("", fe.PrepParams(offset=1)),
        algorithm_params_list=(("led", fe.AlgoParams(id=1)),),
        serving_params=("", fe.Params()),
    )


def train_instance(storage) -> str:
    now = dt.datetime.now(dt.timezone.utc)
    iid = CoreWorkflow.run_train(
        make_engine(),
        make_params(),
        EngineInstance(
            id="", status="", start_time=now, end_time=now,
            engine_id="led", engine_version="1",
            engine_variant="engine.json",
            engine_factory="tests.test_device_ledger",
        ),
        ctx=WorkflowContext(mode="training", storage=storage),
    )
    assert iid
    return iid


def http_query(port: int, qx: int, headers=None):
    conn = http.client.HTTPConnection("localhost", port, timeout=15)
    try:
        conn.request(
            "POST", "/queries.json", json.dumps({"qx": qx}).encode(),
            {"Content-Type": "application/json", **(headers or {})},
        )
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def http_get(port: int, path: str):
    conn = http.client.HTTPConnection("localhost", port, timeout=15)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def wait_until(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.05)
    return cond()


@pytest.fixture()
def ledger_world(mem_storage):
    LedgerAlgo.block = None
    LedgerAlgo.entered = threading.Event()
    v1 = train_instance(mem_storage)
    server = EngineServer(
        make_engine(),
        ServerConfig(port=0),
        storage=mem_storage,
    ).start()
    try:
        yield mem_storage, server, v1
    finally:
        if LedgerAlgo.block is not None:
            LedgerAlgo.block.set()
        LedgerAlgo.block = None
        server.shutdown()
        _health.unregister("promotion")
        _health.unregister("serving-drain")


class TestReleaseInvariant:
    def test_deployed_scope_owns_resident_bytes(self, ledger_world):
        storage, server, v1 = ledger_world
        assert server.api.deployed.ledger_bytes() == LedgerAlgo.RESIDENT_BYTES
        assert ledger().owner_bytes(v1) == LedgerAlgo.RESIDENT_BYTES

    def test_eviction_drives_displaced_ledger_to_zero(self, ledger_world):
        storage, server, v1 = ledger_world
        server.config.retained_states = 0  # evict immediately on swap
        v2 = train_instance(storage)
        pipeline = PromotionPipeline(
            InProcessTarget(server),
            PromotionConfig(observe_s=0.0, drain_timeout_s=5.0),
            storage=storage,
        )
        rep = pipeline.promote(v2)
        assert rep["outcome"] == "promoted"
        # drain-stage report: the displaced instance's residency at
        # drain time (retained_states=0 releases it in the background)
        assert rep["displaced_ledger_bytes"] in (
            0, LedgerAlgo.RESIDENT_BYTES
        )
        assert wait_until(lambda: ledger().owner_bytes(v1) == 0)
        assert (
            ledger().owner_bytes(v2) == LedgerAlgo.RESIDENT_BYTES
        )  # the live instance stays resident

    def test_rollback_then_eviction_zeroes_the_rolled_back_candidate(
        self, ledger_world
    ):
        storage, server, v1 = ledger_world
        v2 = train_instance(storage)
        pipeline = PromotionPipeline(
            InProcessTarget(server),
            PromotionConfig(
                observe_s=0.4, observe_poll_s=0.05, drain_timeout_s=5.0,
                max_error_rate=0.0001,
            ),
            storage=storage,
        )
        # force 5xx during the observation window so the candidate is
        # rolled back (transport-layer error counter drives the verdict)
        stop = threading.Event()

        def drive_errors():
            while not stop.is_set():
                try:
                    http_query(server.port, 1, headers={})
                    conn = http.client.HTTPConnection(
                        "localhost", server.port, timeout=5
                    )
                    try:
                        conn.request(
                            "POST", "/queries.json", b"{not json",
                            {"Content-Type": "application/json"},
                        )
                        conn.getresponse().read()
                    finally:
                        conn.close()
                except Exception:
                    return
                time.sleep(0.02)

        # simpler: fold a synthetic 5xx into the registry directly
        from predictionio_tpu.api.http import record_http_error

        def synth():
            while not stop.is_set():
                record_http_error("Engine Server", "/queries.json", 500)
                time.sleep(0.02)

        t = threading.Thread(target=synth, daemon=True)
        t.start()
        try:
            rep = pipeline.promote(v2)
        finally:
            stop.set()
            t.join(timeout=5)
        assert rep["outcome"] == "rolled_back"
        assert server.api.deployed.engine_instance.id == v1
        # rolling back re-deploys v1 from the retained LRU and retires
        # v2 into it; evict v2 by shutting the server down — every
        # owner's ledger must reach zero
        server.shutdown()
        assert wait_until(lambda: ledger().owner_bytes(v2) == 0)
        assert wait_until(lambda: ledger().owner_bytes(v1) == 0)

    def test_straggler_race_defers_release_then_zeroes(self, ledger_world):
        """The straggler-degrades-to-host-path race: an in-flight batch
        on the displaced instance blocks its release past the timeout;
        the ledger stays truthful (nonzero while wedged) and reaches
        zero once the straggler resolves and the bounded background
        drain retries."""
        storage, server, v1 = ledger_world
        server.config.retained_states = 0
        old = server.api.deployed
        LedgerAlgo.block = threading.Event()
        LedgerAlgo.entered.clear()
        results = []
        qt = threading.Thread(
            target=lambda: results.append(http_query(server.port, 5)),
            daemon=True,
        )
        qt.start()
        assert LedgerAlgo.entered.wait(10)
        # swap while the batch is wedged in the old instance
        v2 = train_instance(storage)
        server.reload(engine_instance_id=v2)
        # the displaced instance cannot release yet: its batch is live
        assert ledger().owner_bytes(v1) == LedgerAlgo.RESIDENT_BYTES
        release_now = old.release(timeout_s=0.1)
        assert release_now is False
        LedgerAlgo.block.set()
        qt.join(timeout=10)
        assert results and results[0][0] == 200
        # the background drain (or an explicit retry) completes now
        assert old.release(timeout_s=5.0) is True
        assert wait_until(lambda: ledger().owner_bytes(v1) == 0)


# --- cold-compile attribution through a live serving batch ---


@dataclasses.dataclass
class RetrieverModel:
    factors: np.ndarray
    retriever: object = None


class RetrieverAlgo(fe.Algo0):
    """A real device-serving algorithm: prepare_serving parks an
    ItemRetriever resident; each query's top-k is its qx, so a query
    with a NEVER-SEEN qx forces a fresh executable compile INSIDE the
    serving batch."""

    params_class = fe.AlgoParams
    query_class = fe.Query

    def train(self, ctx, pd) -> RetrieverModel:
        rng = np.random.default_rng(3)
        return RetrieverModel(
            rng.standard_normal((48, 4)).astype(np.float32)
        )

    def prepare_serving(self, ctx, model: RetrieverModel) -> RetrieverModel:
        model.retriever = ItemRetriever(
            model.factors, component="coldprobe"
        )
        return model

    def release_serving(self, model: RetrieverModel) -> None:
        r, model.retriever = model.retriever, None
        if r is not None:
            r.free()

    def predict(self, model: RetrieverModel, query):
        n = max(1, min(int(query.qx), 40))
        r = model.retriever
        if r is None:  # straggler host path
            return fe.Prediction(query.qx)
        scores, idx = r.topn(
            np.ones((1, 4), np.float32), n
        )
        return fe.Prediction(query.qx, models=(int(idx[0, 0]),))


def retriever_engine() -> Engine:
    return Engine(
        data_source_classes=fe.DataSource0,
        preparator_classes=fe.Preparator0,
        algorithm_classes={"ret": RetrieverAlgo},
        serving_classes=fe.Serving0,
    )


def retriever_params() -> EngineParams:
    return EngineParams(
        data_source_params=("", fe.DSParams(id=7)),
        preparator_params=("", fe.PrepParams(offset=1)),
        algorithm_params_list=(("ret", fe.AlgoParams(id=1)),),
        serving_params=("", fe.Params()),
    )


class TestColdCompileAttribution:
    def test_serving_cold_compile_counted_and_traced(self, mem_storage):
        now = dt.datetime.now(dt.timezone.utc)
        iid = CoreWorkflow.run_train(
            retriever_engine(), retriever_params(),
            EngineInstance(
                id="", status="", start_time=now, end_time=now,
                engine_id="ret", engine_version="1",
                engine_variant="engine.json",
                engine_factory="tests.test_device_ledger",
            ),
            ctx=WorkflowContext(mode="training", storage=mem_storage),
        )
        server = EngineServer(
            retriever_engine(),
            ServerConfig(port=0),
            storage=mem_storage,
        ).start()
        try:
            cold = _metrics.get_registry().counter(
                "pio_cold_compiles_total",
                "Compiles that happened inside a latency-critical site "
                "(a live serving batch, an ingest flush) instead of at "
                "warm-up — each one is tail latency a warm ladder "
                "should have absorbed",
                labels=("site",),
            )
            base = cold.labels(site="serving").value
            # qx=23: a top-k width no warm-up traced — the fused
            # executable compiles INSIDE this live batch
            trace_id = "coldcompiletrace"
            status, body = http_query(
                server.port, 23, headers={"X-PIO-Trace-Id": trace_id}
            )
            assert status == 200
            assert cold.labels(site="serving").value >= base + 1
            # end-to-end attribution via the public span dump
            status, body = http_get(
                server.port, f"/debug/traces.json?traceId={trace_id}"
            )
            assert status == 200
            spans = json.loads(body)["spans"]
            names = {s["name"] for s in spans}
            assert "compile:retrieval-fused" in names
            predict = [s for s in spans if s["name"] == "predict"]
            assert predict, names
            compiles = predict[0].get("attrs", {}).get("cold_compiles")
            assert compiles and compiles[0]["cache"] == "retrieval-fused"
            assert compiles[0]["site"] == "serving"
        finally:
            server.shutdown()
            _health.unregister("serving-drain")


# --- the on-demand profiler capture ---


class TestProfileCapture:
    def test_profile_route_requires_auth(self):
        status, payload = profile_route("POST", {"seconds": "0.2"}, False)
        assert status == 401

    def test_capture_returns_nonempty_archive(self):
        import jax
        import jax.numpy as jnp

        # some device work during the window so the trace is non-trivial
        stop = threading.Event()

        def churn():
            x = jnp.ones((64, 64))
            while not stop.is_set():
                jax.block_until_ready(jnp.dot(x, x))

        t = threading.Thread(target=churn, daemon=True)
        t.start()
        try:
            status, payload = profile_route(
                "POST", {"seconds": "0.4"}, True
            )
        finally:
            stop.set()
            t.join(timeout=5)
        assert status == 200
        assert payload["archiveBytes"] > 0
        assert payload["files"]
        data = base64.b64decode(payload["archive_b64"])
        with zipfile.ZipFile(io.BytesIO(data)) as zf:
            assert zf.namelist()
        # GET reports status without the archive body
        status, body = profile_route("GET", {}, True)
        assert status == 200 and body["running"] is False
        assert "archive_b64" not in (body["last"] or {})

    def test_engine_server_endpoint_gated_and_serving_clean(
        self, mem_storage
    ):
        v1 = train_instance(mem_storage)
        server = EngineServer(
            make_engine(),
            ServerConfig(
                port=0, access_key="sekrit"
            ),
            storage=mem_storage,
        ).start()
        try:
            # wrong key → 401; right key captures under live queries
            conn = http.client.HTTPConnection(
                "localhost", server.port, timeout=15
            )
            try:
                conn.request(
                    "POST", "/debug/profile?seconds=0.3&accessKey=nope",
                    b"",
                )
                assert conn.getresponse().status == 401
            finally:
                conn.close()
            errors = []
            stop = threading.Event()

            def load():
                while not stop.is_set():
                    s, _ = http_query(server.port, 2)
                    if s != 200:
                        errors.append(s)
                    time.sleep(0.01)

            t = threading.Thread(target=load, daemon=True)
            t.start()
            try:
                conn = http.client.HTTPConnection(
                    "localhost", server.port, timeout=30
                )
                try:
                    conn.request(
                        "POST",
                        "/debug/profile?seconds=0.4&accessKey=sekrit",
                        b"",
                    )
                    resp = conn.getresponse()
                    assert resp.status == 200
                    payload = json.loads(resp.read())
                finally:
                    conn.close()
            finally:
                stop.set()
                t.join(timeout=10)
            assert payload["archiveBytes"] > 0
            assert not errors  # zero serving errors during the window
        finally:
            server.shutdown()
            _health.unregister("serving-drain")


# --- collector federation of the ledger ---


class TestCollectorLedger:
    def test_fleet_json_carries_ledger_block_and_drift_alert(self):
        from predictionio_tpu.utils import telemetry

        c = telemetry.Collector()
        c.add_target("http://fake:1")
        state = c._targets["http://fake:1"]
        drift = telemetry.DRIFT_ALERT_BYTES + 1
        samples = {
            'pio_device_ledger_bytes{device="d0",component="x",owner="-"}':
                float(1 << 20),
            'pio_device_ledger_drift_bytes{device="d0"}': float(drift),
        }
        state.ring.append((time.time(), samples))
        state.up = True
        block = c.evaluate_ledger()
        assert block["hbm_mb"] == 1.0
        assert block["drift_alert"] is True
        fleet = c.fleet_json()
        assert fleet["ledger"]["drift_alert"] is True
        row = fleet["targets"][0]
        assert row["hbm_mb"] == 1.0
        assert row["hbm_components_mb"] == {"x": 1.0}
