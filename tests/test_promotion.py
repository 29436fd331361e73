"""Promotion-pipeline tests: the gated retrain→swap→rollback contract.

Covers the round-13 acceptance criteria at the unit/integration tier:
stage ordering and the shadow gate, crash consistency at every named
fault-injection point (exception AND kill), drain semantics (resident
state freed only after the last in-flight batch resolves; stragglers
degrade to the host path, never drop), the bounded-drain watchdog
degrading /readyz, automatic rollback to the retained previous
instance, pinned-id fleet convergence, and the continuous-loop wiring.
"""

import dataclasses
import datetime as dt
import http.client
import json
import threading
import time
import urllib.request

import pytest

from predictionio_tpu.api.engine_server import (
    DeployedEngine,
    EngineServer,
    ServerConfig,
)
from predictionio_tpu.controller import BaseAlgorithm
from predictionio_tpu.controller.engine import Engine, EngineParams
from predictionio_tpu.data.storage.base import EngineInstance
from predictionio_tpu.utils import health as _health
from predictionio_tpu.workflow.context import WorkflowContext
from predictionio_tpu.workflow.core_workflow import CoreWorkflow
from predictionio_tpu.workflow.promotion import (
    FAULT_STAGES,
    FleetTarget,
    InProcessTarget,
    PromotionConfig,
    PromotionPipeline,
    promotion_stats,
)

from tests import fake_engine as fe


@dataclasses.dataclass
class GateModel:
    """A fake model with an observable 'device state' lifecycle: set by
    prepare_serving, nulled by release_serving — the stand-in for the
    real engines' resident ItemRetriever."""

    algo_id: int
    pd_id: int
    device_state: object = None


class GateAlgo(BaseAlgorithm):
    params_class = fe.AlgoParams
    query_class = fe.Query

    # test knobs (class-level; reset by the fixture)
    block = None  # threading.Event: batch_predict parks on it when set
    entered = None  # threading.Event: set when a predict is in flight
    fail_qx = None  # queries with this qx raise (forced serving 500s)
    released_models = None  # list of models whose state was released

    def train(self, ctx, pd) -> GateModel:
        return GateModel(self.params.id, pd.id)

    def prepare_serving(self, ctx, model: GateModel) -> GateModel:
        model.device_state = {"resident": True}
        return model

    def release_serving(self, model: GateModel) -> None:
        state, model.device_state = model.device_state, None
        if state is not None:
            state["resident"] = False
        if type(self).released_models is not None:
            type(self).released_models.append(model)

    def predict(self, model: GateModel, query):
        cls = type(self)
        if cls.fail_qx is not None and query.qx == cls.fail_qx:
            raise RuntimeError("forced serving failure")
        if cls.block is not None:
            if cls.entered is not None:
                cls.entered.set()
            cls.block.wait(30)
        return fe.Prediction(
            query.qx,
            models=(
                (model.algo_id, model.pd_id, model.device_state is not None),
            ),
        )


def make_engine() -> Engine:
    return Engine(
        data_source_classes=fe.DataSource0,
        preparator_classes=fe.Preparator0,
        algorithm_classes={"g": GateAlgo},
        serving_classes=fe.Serving0,
    )


def make_params() -> EngineParams:
    return EngineParams(
        data_source_params=("", fe.DSParams(id=7)),
        preparator_params=("", fe.PrepParams(offset=1)),
        algorithm_params_list=(("g", fe.AlgoParams(id=1)),),
        serving_params=("", fe.Params()),
    )


def train_instance(storage) -> str:
    now = dt.datetime.now(dt.timezone.utc)
    iid = CoreWorkflow.run_train(
        make_engine(),
        make_params(),
        EngineInstance(
            id="", status="", start_time=now, end_time=now,
            engine_id="gate", engine_version="1",
            engine_variant="engine.json",
            engine_factory="tests.test_promotion",
        ),
        ctx=WorkflowContext(mode="training", storage=storage),
    )
    assert iid
    return iid


def http_query(port: int, qx: int):
    conn = http.client.HTTPConnection("localhost", port, timeout=10)
    try:
        conn.request(
            "POST", "/queries.json", json.dumps({"qx": qx}).encode(),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        body = resp.read()
        return resp.status, body
    finally:
        conn.close()


@pytest.fixture()
def promo_world(mem_storage):
    GateAlgo.block = None
    GateAlgo.entered = threading.Event()
    GateAlgo.fail_qx = None
    GateAlgo.released_models = []
    v1 = train_instance(mem_storage)
    server = EngineServer(
        make_engine(),
        ServerConfig(port=0),
        storage=mem_storage,
    ).start()
    try:
        yield mem_storage, server, v1
    finally:
        if GateAlgo.block is not None:
            GateAlgo.block.set()
        GateAlgo.block = None
        GateAlgo.fail_qx = None
        server.shutdown()
        _health.unregister("promotion")
        _health.unregister("serving-drain")


def make_pipeline(server, storage, **cfg) -> PromotionPipeline:
    defaults = dict(observe_s=0.0, drain_timeout_s=5.0)
    defaults.update(cfg)
    return PromotionPipeline(
        InProcessTarget(server), PromotionConfig(**defaults), storage=storage
    )


class TestPromote:
    def test_promote_swaps_retains_and_counts(self, promo_world):
        storage, server, v1 = promo_world
        v2 = train_instance(storage)
        base = promotion_stats()
        pipeline = make_pipeline(server, storage)
        rep = pipeline.promote(v2)
        assert rep["outcome"] == "promoted"
        assert rep["serving"] == v2
        assert rep["drained"] is True
        assert server.api.deployed.engine_instance.id == v2
        # the displaced instance is RETAINED (warm, unreleased) for
        # instant rollback — the multi-variant LRU
        assert server.retained_versions() == [v1]
        assert not GateAlgo.released_models
        # stage timings recorded in order
        for stage in ("gate", "persist", "prepare", "swap", "drain"):
            assert stage in rep["stages"]
        assert promotion_stats()["promoted"] == base["promoted"] + 1
        # serving still answers, on the new version
        status, body = http_query(server.port, 3)
        assert status == 200 and json.loads(body)["qx"] == 3

    def test_diverged_shadow_refuses_swap(self, promo_world):
        storage, server, v1 = promo_world
        v2 = train_instance(storage)
        base = promotion_stats()
        pipeline = make_pipeline(server, storage)
        rep = pipeline.promote(
            v2, shadow={"verdict": "diverged", "jaccard_mean": 0.05}
        )
        assert rep["outcome"] == "refused"
        assert "diverged" in rep["reason"]
        # the fleet keeps serving the live instance
        assert rep["serving"] == v1
        assert server.api.deployed.engine_instance.id == v1
        assert server.retained_versions() == []
        assert promotion_stats()["refused"] == base["refused"] + 1

    def test_require_shadow_refuses_ungated_round(self, promo_world):
        storage, server, v1 = promo_world
        v2 = train_instance(storage)
        pipeline = make_pipeline(server, storage, require_shadow=True)
        rep = pipeline.promote(v2, shadow=None)
        assert rep["outcome"] == "refused"
        assert server.api.deployed.engine_instance.id == v1

    def test_comparable_shadow_promotes(self, promo_world):
        storage, server, v1 = promo_world
        v2 = train_instance(storage)
        pipeline = make_pipeline(server, storage)
        rep = pipeline.promote(
            v2, shadow={"verdict": "comparable", "jaccard_mean": 0.98}
        )
        assert rep["outcome"] == "promoted"
        assert server.api.deployed.engine_instance.id == v2

    def test_persist_gate_blocks_unpersisted_candidate(self, promo_world):
        storage, server, v1 = promo_world
        pipeline = make_pipeline(server, storage)
        rep = pipeline.promote("no-such-instance")
        assert rep["outcome"] == "failed"
        assert rep["stage"] == "persist"
        assert "COMPLETED" in rep["error"]
        assert server.api.deployed.engine_instance.id == v1

    def test_skipped_when_candidate_already_serving(self, promo_world):
        storage, server, v1 = promo_world
        pipeline = make_pipeline(server, storage)
        rep = pipeline.promote(v1)
        assert rep["outcome"] == "skipped"
        assert server.api.deployed.engine_instance.id == v1


# fault stage -> the pipeline stage the failure is attributed to, and
# the version the fleet must be CONSISTENTLY serving afterwards
# ("old" = pre-swap failure, "new" = post-swap failure)
_FAULT_EXPECT = {
    "train_persist": ("gate", "old"),
    "persist_warm": ("persist", "old"),
    "warm_swap": ("prepare", "old"),
    "swap_drain": ("swap", "new"),
}


class TestFaultInjection:
    @pytest.mark.parametrize("fault_stage", sorted(_FAULT_EXPECT))
    def test_fault_leaves_consistent_version_and_recovers(
        self, promo_world, fault_stage
    ):
        storage, server, v1 = promo_world
        v2 = train_instance(storage)
        base = promotion_stats()
        pipeline = make_pipeline(server, storage)

        def boom():
            raise RuntimeError(f"injected fault at {fault_stage}")

        pipeline.faults[fault_stage] = boom
        rep = pipeline.promote(v2)
        assert rep["outcome"] == "failed"
        expect_stage, expect_version = _FAULT_EXPECT[fault_stage]
        assert rep["stage"] == expect_stage
        want = v1 if expect_version == "old" else v2
        # ONE consistent version, and it is what the target reports
        assert rep["serving"] == want
        assert server.api.deployed.engine_instance.id == want
        assert promotion_stats()["failed"] == base["failed"] + 1
        # zero dropped queries: serving answers correctly throughout
        status, body = http_query(server.port, 9)
        assert status == 200 and json.loads(body)["qx"] == 9
        # a prepared-but-unswapped candidate must not leak its device
        # state: the warm_swap fault releases it
        if fault_stage == "warm_swap":
            assert len(GateAlgo.released_models) == 1
            assert GateAlgo.released_models[0].device_state is None
        # recovery: the next round re-promotes the same candidate
        pipeline.faults[fault_stage] = None
        rep2 = pipeline.promote(v2)
        assert rep2["outcome"] in ("promoted", "skipped")
        assert server.api.deployed.engine_instance.id == v2

    @pytest.mark.parametrize("fault_stage", sorted(_FAULT_EXPECT))
    def test_kill_mid_promotion_leaves_no_half_promoted_state(
        self, promo_world, fault_stage
    ):
        """Crash consistency: a KILL (BaseException — the in-process
        analog of the continuous loop dying) at any fault point leaves
        the fleet serving one consistent version, and a fresh pipeline
        (the next loop incarnation) recovers without tripping on
        half-promoted state."""

        class Kill(BaseException):
            pass

        storage, server, v1 = promo_world
        v2 = train_instance(storage)
        pipeline = make_pipeline(server, storage)

        def die():
            raise Kill()

        pipeline.faults[fault_stage] = die
        with pytest.raises(Kill):
            pipeline.promote(v2)
        # consistent: the target serves exactly one version, and it is a
        # COMPLETED persisted instance
        serving = server.api.deployed.engine_instance.id
        assert serving in (v1, v2)
        inst = storage.get_meta_data_engine_instances().get(serving)
        assert inst is not None and inst.status == "COMPLETED"
        status, _ = http_query(server.port, 5)
        assert status == 200
        # the next incarnation recovers and converges on the candidate
        fresh = make_pipeline(server, storage)
        rep = fresh.promote(v2)
        assert rep["outcome"] in ("promoted", "skipped")
        assert server.api.deployed.engine_instance.id == v2

    def test_kill_interrupts_continuous_loop_then_next_round_recovers(
        self, promo_world
    ):
        """The loop-level kill: continuous_train dies mid-promotion
        (BaseException propagates), the serving fleet stays consistent,
        and a NEW loop's first round promotes cleanly."""
        from predictionio_tpu.workflow.continuous import continuous_train

        class Kill(BaseException):
            pass

        storage, server, v1 = promo_world
        pipeline = make_pipeline(server, storage)
        pipeline.faults["warm_swap"] = lambda: (_ for _ in ()).throw(Kill())
        template = EngineInstance(
            id="", status="", start_time=dt.datetime.now(dt.timezone.utc),
            end_time=dt.datetime.now(dt.timezone.utc),
            engine_id="gate", engine_version="1",
            engine_variant="engine.json",
            engine_factory="tests.test_promotion",
        )
        with pytest.raises(Kill):
            continuous_train(
                make_engine(), make_params(), template,
                storage=storage, interval_s=0.01, max_rounds=1,
                promotion=pipeline,
            )
        assert server.api.deployed.engine_instance.id == v1
        status, _ = http_query(server.port, 2)
        assert status == 200
        # next incarnation, no fault: trains a fresh round and promotes
        reports = []
        healthy = make_pipeline(server, storage)
        continuous_train(
            make_engine(), make_params(), template,
            storage=storage, interval_s=0.01, max_rounds=1,
            promotion=healthy, on_round=reports.append,
        )
        assert reports[-1].promotion["outcome"] == "promoted"
        assert (
            server.api.deployed.engine_instance.id
            == reports[-1].promotion["candidate"]
        )


class TestDrainSemantics:
    def test_drain_waits_for_inflight_then_release_frees(self, mem_storage):
        GateAlgo.block = threading.Event()
        GateAlgo.entered = threading.Event()
        GateAlgo.fail_qx = None
        GateAlgo.released_models = []
        try:
            train_instance(mem_storage)
            dep = DeployedEngine.from_storage(make_engine(), mem_storage)
            results = {}

            def serve():
                results["out"] = dep.serve_batch([fe.Query(1)])

            t = threading.Thread(target=serve)
            t.start()
            assert GateAlgo.entered.wait(10)
            assert dep.inflight == 1
            # bounded drain + release refuse while the batch is in
            # flight: resident state is never freed under a live batch
            assert dep.drain(0.3) is False
            assert dep.release(timeout_s=0.2) is False
            assert not dep.released
            assert dep.models[0].device_state is not None
            GateAlgo.block.set()
            t.join(timeout=10)
            assert results["out"][0].qx == 1
            assert dep.drain(5.0) is True
            assert dep.release(timeout_s=1.0) is True
            assert dep.released
            # the device state was freed exactly once
            assert dep.models[0].device_state is None
            assert len(GateAlgo.released_models) == 1
            # a straggler batch racing past the release still serves —
            # on the host fallback path (device_state flag False), with
            # zero dropped queries
            GateAlgo.block = None
            out = dep.serve_batch([fe.Query(2)])
            assert out[0].qx == 2
            assert out[0].models[0][2] is False
        finally:
            if GateAlgo.block is not None:
                GateAlgo.block.set()
            GateAlgo.block = None

    def test_wedged_drain_degrades_readyz_and_recovers(self, promo_world):
        """The bounded-drain watchdog: a drain stalled on a wedged
        in-flight batch flips /readyz (the 'promotion' heartbeat) once
        its deadline passes, and recovers when the batch resolves."""
        storage, server, v1 = promo_world
        GateAlgo.block = threading.Event()
        GateAlgo.entered.clear()
        # park one query inside the OLD snapshot's serve_batch
        qt = threading.Thread(
            target=http_query, args=(server.port, 1), daemon=True
        )
        qt.start()
        assert GateAlgo.entered.wait(10)
        # un-block new predicts (the new snapshot must serve) while the
        # parked one stays parked: swap the class event for a fresh,
        # already-set one; the parked thread still waits on the old
        parked = GateAlgo.block
        done = threading.Event()
        done.set()
        GateAlgo.block = done
        v2 = train_instance(storage)
        hb = _health.heartbeat("promotion")
        hb.deadline_s = 0.2
        pipeline = make_pipeline(server, storage, drain_timeout_s=10.0)
        rep_box = {}

        def run():
            rep_box["rep"] = pipeline.promote(v2)

        pt = threading.Thread(target=run)
        pt.start()
        # the drain stage wedges on the parked batch; past the deadline
        # the watchdog reports the stall through the readiness registry
        deadline = time.time() + 5
        stalled = False
        while time.time() < deadline:
            ok, payload = _health.readiness()
            if not ok and "promotion" in payload["stalledDaemons"]:
                stalled = True
                break
            time.sleep(0.05)
        assert stalled, "wedged drain never degraded readiness"
        # resolve the straggler: drain completes, promotion finishes,
        # readiness recovers
        parked.set()
        pt.join(timeout=15)
        assert rep_box["rep"]["outcome"] == "promoted"
        assert rep_box["rep"]["drained"] is True
        ok, payload = _health.readiness()
        assert ok, payload
        qt.join(timeout=5)


class TestRollback:
    def test_forced_regression_rolls_back_to_retained_instance(
        self, promo_world
    ):
        storage, server, v1 = promo_world
        v2 = train_instance(storage)
        base = promotion_stats()
        # every error triggers rollback; short observation window
        pipeline = make_pipeline(
            server, storage,
            observe_s=0.8, observe_poll_s=0.1, max_error_rate=0.0,
        )
        GateAlgo.fail_qx = 666
        stop = threading.Event()

        def drive_errors():
            while not stop.is_set():
                http_query(server.port, 666)  # real 500s through serving
                stop.wait(0.05)

        et = threading.Thread(target=drive_errors, daemon=True)
        et.start()
        try:
            rep = pipeline.promote(v2)
        finally:
            stop.set()
            et.join(timeout=5)
        assert rep["outcome"] == "rolled_back"
        assert "error rate" in rep["reason"]
        # back on the retained previous instance, instantly (LRU pop —
        # no store read); the failed candidate is retained in its place
        assert rep["serving"] == v1
        assert server.api.deployed.engine_instance.id == v1
        assert server.retained_versions() == [v2]
        assert promotion_stats()["rolled_back"] == base["rolled_back"] + 1
        GateAlgo.fail_qx = None
        status, body = http_query(server.port, 4)
        assert status == 200 and json.loads(body)["qx"] == 4

    def test_clean_observation_window_promotes(self, promo_world):
        storage, server, v1 = promo_world
        v2 = train_instance(storage)
        pipeline = make_pipeline(
            server, storage, observe_s=0.3, observe_poll_s=0.05,
            max_error_rate=0.0,
        )
        rep = pipeline.promote(v2)
        assert rep["outcome"] == "promoted"
        assert server.api.deployed.engine_instance.id == v2


class TestFleetTarget:
    def test_pinned_id_converges_fleet_and_rolls_back(self, mem_storage):
        GateAlgo.block = None
        GateAlgo.entered = threading.Event()
        GateAlgo.fail_qx = None
        GateAlgo.released_models = []
        v1 = train_instance(mem_storage)
        servers = [
            EngineServer(
                make_engine(), ServerConfig(port=0), storage=mem_storage
            ).start()
            for _ in range(2)
        ]
        try:
            urls = [f"http://localhost:{s.port}" for s in servers]
            target = FleetTarget(urls, converge_timeout_s=30, confirms=2)
            assert target.current_version() == v1
            v2 = train_instance(mem_storage)
            pipeline = PromotionPipeline(
                target, PromotionConfig(observe_s=0.0), storage=mem_storage
            )
            rep = pipeline.promote(v2)
            assert rep["outcome"] == "promoted"
            # every worker converged on the PINNED candidate id
            for s in servers:
                assert s.api.deployed.engine_instance.id == v2
                assert s.retained_versions() == [v1]
            # pinned rollback converges the fleet back, from each
            # worker's retained LRU
            target.rollback(None, v1)
            for s in servers:
                assert s.api.deployed.engine_instance.id == v1
        finally:
            for s in servers:
                s.shutdown()
            _health.unregister("promotion")
            _health.unregister("serving-drain")

    def test_worker_refusing_reload_names_the_cause(self, mem_storage):
        GateAlgo.block = None
        GateAlgo.fail_qx = None
        GateAlgo.released_models = []
        train_instance(mem_storage)
        server = EngineServer(
            make_engine(), ServerConfig(port=0), storage=mem_storage
        ).start()
        try:
            target = FleetTarget([f"http://localhost:{server.port}"])
            with pytest.raises(RuntimeError, match="refused reload"):
                target._post_reload(
                    f"http://localhost:{server.port}", "no-such-instance"
                )
        finally:
            server.shutdown()


class TestContinuousLoopWiring:
    def test_each_trained_round_promotes_and_live_follows_serving(
        self, promo_world
    ):
        from predictionio_tpu.workflow.continuous import continuous_train

        storage, server, v1 = promo_world
        pipeline = make_pipeline(server, storage)
        template = EngineInstance(
            id="", status="", start_time=dt.datetime.now(dt.timezone.utc),
            end_time=dt.datetime.now(dt.timezone.utc),
            engine_id="gate", engine_version="1",
            engine_variant="engine.json",
            engine_factory="tests.test_promotion",
        )
        reports = []
        continuous_train(
            make_engine(), make_params(), template,
            storage=storage, interval_s=0.01, max_rounds=2,
            promotion=pipeline, on_round=reports.append,
        )
        trained = [r for r in reports if not r.skipped]
        assert trained, "loop trained no rounds"
        for rep in trained:
            assert rep.promotion is not None
            assert rep.promotion["outcome"] == "promoted"
        last = trained[-1]
        assert server.api.deployed.engine_instance.id == last.instance_id
        assert last.promotion["serving"] == last.instance_id
