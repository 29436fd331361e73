"""Online experimentation plane (round 20): sticky multi-variant
serving, the always-valid sequential test, and verdict execution.

The acceptance spine at the unit/integration tier:

- allocation is a pure function of (salt, user_key, split): every
  worker of a REAL 2-server SO_REUSEPORT fleet stamps each response
  with exactly the variant the pure function predicts, and a restarted
  worker re-derives identical assignments (0 cross-variant
  reassignments, zero coordination);
- attribution churn: once a retired variant's prId entries pass their
  TTL, a late event resolves to ``unknown`` — it is NEVER credited to
  a surviving variant;
- the mSPRT decides against a degraded arm, promotes a better arm, and
  declares NO winner on an A/A comparison no matter how often it is
  peeked (always-valid under continuous peeking);
- the collector's federated evaluation reads per-variant counts as
  deltas-since-registration (restart clamps to zero) and its verdict
  is sticky; ``POST /api/experiments.json`` is admin-gated;
- the runner executes the verdict end to end on a live server: the
  winner goes through the gated promotion pipeline, losers drain.
"""

import json
import threading
import urllib.error
import urllib.request

import pytest

from predictionio_tpu.api.engine_server import EngineServer, ServerConfig
from predictionio_tpu.utils import health as _health
from predictionio_tpu.utils import metrics as m
from predictionio_tpu.utils.telemetry import Collector
from predictionio_tpu.workflow import quality as q
from predictionio_tpu.workflow.experiment import (
    ALLOCATION_BUCKETS,
    ExperimentRunner,
    ExperimentSpec,
    allocate,
    allocate_bucket,
    evaluate_sequential,
    msprt_log_lambda,
    user_key_from_query,
)
from predictionio_tpu.workflow.promotion import (
    InProcessTarget,
    PromotionConfig,
    PromotionPipeline,
)

from tests.test_promotion import (
    GateAlgo,
    http_query,
    make_engine,
    train_instance,
)


def spec2(name="exp", a="arm-a", b="arm-b", **kw):
    return ExperimentSpec(name=name, variants=(a, b), **kw)


# --- spec validation + sticky allocation (pure function) ---


class TestSpecAndAllocation:
    def test_spec_validation(self):
        with pytest.raises(ValueError):
            ExperimentSpec(name="x", variants=("only",))
        with pytest.raises(ValueError):
            ExperimentSpec(name="x", variants=("a", "a"))
        with pytest.raises(ValueError):
            spec2(split=(1.0,))
        with pytest.raises(ValueError):
            spec2(split=(0.0, 1.0))
        with pytest.raises(ValueError):
            spec2(alpha=1.5)
        with pytest.raises(ValueError):
            spec2(on_inconclusive="flip-a-coin")
        s = spec2(split=(3.0, 1.0))
        assert s.split == pytest.approx((0.75, 0.25))
        assert s.control == "arm-a"
        assert s.salt == "exp"  # defaults to the name
        assert s.split_edges()[-1] == ALLOCATION_BUCKETS

    def test_from_json_round_trip_and_unknown_keys(self):
        s = spec2(split=(0.5, 0.5), user_field="qx", min_samples=7)
        assert ExperimentSpec.from_json(s.to_json()) == s
        with pytest.raises(ValueError, match="unknown experiment spec"):
            ExperimentSpec.from_json({**s.to_json(), "surprise": 1})

    def test_allocation_is_sticky_and_salt_scoped(self):
        s = spec2()
        for uk in ("u1", "u2", "", "漢字", "a b c"):
            assert allocate(s, uk) == allocate(s, uk)
        # a different salt reshuffles; the same salt never does
        s2 = spec2(salt="other")
        keys = [f"user-{i}" for i in range(2000)]
        moved = sum(allocate(s, k) != allocate(s2, k) for k in keys)
        assert moved > 0
        assert allocate_bucket("s", "u") == allocate_bucket("s", "u")

    def test_split_shares_match_within_tolerance(self):
        s = spec2(split=(0.8, 0.2))
        n = 20000
        hits = sum(
            allocate(s, f"user-{i}") == "arm-b" for i in range(n)
        )
        assert hits / n == pytest.approx(0.2, abs=0.02)

    def test_every_bucket_maps_to_a_variant(self):
        # rounding can never orphan the tail bucket
        s = ExperimentSpec(
            name="three", variants=("a", "b", "c"), split=(1, 1, 1)
        )
        edges = s.split_edges()
        assert edges[-1] == ALLOCATION_BUCKETS
        assert allocate(s, "anything") in s.variants

    def test_user_key_fallback_is_canonical(self):
        assert user_key_from_query({"user": 42}, "user") == "42"
        assert user_key_from_query({"qx": 3}, "qx") == "3"
        # no user field: the canonical JSON of the query is the key, so
        # identical queries stay sticky regardless of dict ordering
        a = user_key_from_query({"b": 1, "a": 2}, "user")
        b = user_key_from_query({"a": 2, "b": 1}, "user")
        assert a == b


# --- the sequential engine (pure function) ---


class TestSequentialTest:
    def _stats(self, c_conv, c_n, v_conv, v_n, **extra):
        st = {
            "arm-a": {"converted": c_conv, "miss": c_n - c_conv},
            "arm-b": {"converted": v_conv, "miss": v_n - v_conv},
        }
        for vid, d in extra.items():
            st[vid].update(d)
        return st

    def test_better_arm_wins_and_names_promotion(self):
        s = spec2(min_samples=50, alpha=0.05, tau=0.3)
        rep = evaluate_sequential(
            s, self._stats(100, 500, 250, 500), elapsed_s=10.0
        )
        assert rep["status"] == "decided"
        assert rep["winner"] == "arm-b"
        assert rep["action"] == "promote:arm-b"
        assert rep["variants"]["arm-b"]["significant"]

    def test_degraded_arm_loses_to_control(self):
        s = spec2(min_samples=50, alpha=0.05, tau=0.3)
        rep = evaluate_sequential(
            s, self._stats(250, 500, 100, 500), elapsed_s=10.0
        )
        assert rep["status"] == "decided"
        assert rep["winner"] == "arm-a"  # control wins
        assert rep["action"] == "keep-control"

    def test_min_samples_gates_significance(self):
        s = spec2(min_samples=1000)
        rep = evaluate_sequential(
            s, self._stats(10, 50, 40, 50), elapsed_s=1.0
        )
        assert rep["status"] == "running"
        assert rep["winner"] is None

    def test_aa_never_declares_a_winner_under_continuous_peeking(self):
        """The always-valid property, empirically: two identical arms
        peeked at EVERY step of a long deterministic traffic stream
        never cross the decision threshold."""
        import random

        rng = random.Random(20)
        s = spec2(
            name="aa", min_samples=50, alpha=0.05, tau=0.2,
            horizon_s=1e9,
        )
        conv = {"arm-a": 0, "arm-b": 0}
        n = {"arm-a": 0, "arm-b": 0}
        for i in range(4000):
            vid = "arm-a" if i % 2 == 0 else "arm-b"
            n[vid] += 1
            conv[vid] += rng.random() < 0.3
            rep = evaluate_sequential(s, {
                v: {"converted": conv[v], "miss": n[v] - conv[v]}
                for v in ("arm-a", "arm-b")
            }, elapsed_s=float(i))
            assert rep["status"] == "running", (i, rep)

    def test_latency_guard_disqualifies_fast_converting_slow_arm(self):
        s = spec2(min_samples=50, tau=0.3, latency_guard_ms=100.0)
        stats = self._stats(
            100, 500, 250, 500,
            **{"arm-a": {"p99_s": 0.02}, "arm-b": {"p99_s": 0.5}},
        )
        rep = evaluate_sequential(s, stats, elapsed_s=10.0)
        assert rep["status"] == "running"
        assert not rep["variants"]["arm-b"]["guard_ok"]
        # ratio guard: candidate p99 > 2x control's
        s2 = spec2(min_samples=50, tau=0.3, latency_guard_ratio=2.0)
        stats2 = self._stats(
            100, 500, 250, 500,
            **{"arm-a": {"p99_s": 0.02}, "arm-b": {"p99_s": 0.05}},
        )
        rep2 = evaluate_sequential(s2, stats2, elapsed_s=10.0)
        assert not rep2["variants"]["arm-b"]["guard_ok"]

    def test_horizon_reports_on_inconclusive_action(self):
        s = spec2(horizon_s=60.0, on_inconclusive="keep-control")
        rep = evaluate_sequential(
            s, self._stats(3, 10, 3, 10), elapsed_s=61.0
        )
        assert rep["status"] == "horizon"
        assert rep["winner"] is None
        assert rep["action"] == "keep-control"

    def test_msprt_monotone_in_effect_and_zero_on_empty(self):
        assert msprt_log_lambda(0, 0, 0, 0, 0.2) == 0.0
        small = msprt_log_lambda(100, 500, 110, 500, 0.2)
        large = msprt_log_lambda(100, 500, 250, 500, 0.2)
        assert large > small


# --- attribution churn: retired variants never credit survivors ---


class _Evt:
    def __init__(self, pr_id, target):
        self.pr_id = pr_id
        self.target_entity_id = target


class TestAttributionChurn:
    def _counts(self, version):
        out = {}
        for (v, outcome), child in q._attributed_counter().children():
            if v == version:
                out[outcome] = child.value
        return out

    def test_expired_retired_variant_prid_never_credits_survivor(self):
        table = q.AttributionTable(ttl_s=60.0)
        retired, survivor = "churn-retired", "churn-survivor"
        table.register("pr-old", retired, ("i1", "i2"), t=1000.0)
        table.register("pr-new", survivor, ("i1", "i2"), t=1000.0)
        before = self._counts(survivor)
        # the retired arm's entry is past TTL: the join must resolve
        # to unknown, not to any surviving variant
        out = table.observe(_Evt("pr-old", "i1"), now=1000.0 + 61.0)
        assert out == "unknown"
        assert self._counts(retired) == {}
        assert self._counts(survivor) == before
        # the survivor's live entry still attributes normally
        assert table.observe(_Evt("pr-new", "i1"), now=1000.0 + 5.0) == (
            "converted"
        )
        after = self._counts(survivor)
        assert after.get("converted", 0) == before.get("converted", 0) + 1

    def test_eviction_drops_entry_entirely(self):
        table = q.AttributionTable(ttl_s=60.0)
        table.register("pr-x", "churn-evicted", ("i1",), t=0.0)
        assert table.observe(_Evt("pr-x", "i1"), now=100.0) == "unknown"
        # the expired entry was evicted: a second late event is still
        # unknown (no resurrection)
        assert table.observe(_Evt("pr-x", "i1"), now=100.0) == "unknown"
        assert len(table) == 0


# --- capture/replay variant awareness ---


class TestCaptureVariant:
    def test_record_carries_variant_and_dump_filters(self):
        cap = q.PredictionCapture(capacity=16)
        cap.record("v1", {"qx": 1}, {"qx": 1}, experiment="e", variant="v1")
        cap.record("v2", {"qx": 2}, {"qx": 2}, experiment="e", variant="v2")
        cap.record("v1", {"qx": 3}, {"qx": 3})  # no experiment running
        recs = cap.dump()
        assert [r.get("variant") for r in recs] == ["v1", "v2", None]
        only_v2 = cap.dump(variant="v2")
        assert len(only_v2) == 1 and only_v2[0]["query"] == {"qx": 2}
        # experiment/variant are volatile result keys for replay compare
        assert "experiment" in q._VOLATILE_RESULT_KEYS
        assert "variant" in q._VOLATILE_RESULT_KEYS


# --- the live serving plane: sticky fleet + lifecycle ---


@pytest.fixture()
def exp_world(mem_storage):
    GateAlgo.block = None
    GateAlgo.entered = threading.Event()
    GateAlgo.fail_qx = None
    GateAlgo.released_models = []
    # NOTE: a fresh server deploys the LATEST completed instance, so
    # ``live`` is the control arm and ``cand`` the candidate
    cand = train_instance(mem_storage)
    live = train_instance(mem_storage)
    servers = []

    def make_server(**cfg):
        defaults = dict(port=0)
        defaults.update(cfg)
        s = EngineServer(
            make_engine(), ServerConfig(**defaults), storage=mem_storage
        ).start()
        servers.append(s)
        return s

    try:
        yield mem_storage, make_server, live, cand
    finally:
        if GateAlgo.block is not None:
            GateAlgo.block.set()
        GateAlgo.block = None
        GateAlgo.fail_qx = None
        for s in servers:
            s.shutdown()
        _health.unregister("promotion")
        _health.unregister("serving-drain")


def _exp_spec(name, v1, v2, **kw):
    defaults = dict(user_field="qx", min_samples=5, horizon_s=3600.0)
    defaults.update(kw)
    return ExperimentSpec(name=name, variants=(v1, v2), **defaults)


class TestServingPlane:
    def test_fleet_workers_and_restart_agree_with_pure_allocation(
        self, exp_world
    ):
        """2 SO_REUSEPORT servers on ONE port, zero coordination: every
        response's stamped variant equals the pure allocation function,
        so both workers (and any restart) agree by construction."""
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        storage, make_server, live, cand = exp_world
        s1 = make_server(port=port, reuse_port=True)
        s2 = make_server(port=port, reuse_port=True)
        spec = _exp_spec("fleet", live, cand)
        s1.start_experiment(spec)
        s2.start_experiment(spec)
        seen = {}
        for qx in range(40):
            status, body = http_query(port, qx)
            assert status == 200
            got = json.loads(body)
            expected = allocate(spec, str(qx))
            assert got["variant"] == expected
            assert got["experiment"] == "fleet"
            assert got["modelVersion"] == expected
            seen[qx] = got["variant"]
        assert len(set(seen.values())) == 2  # both arms actually served
        # restart: a fresh worker joining the fleet re-derives the SAME
        # assignment for every user — 0 cross-variant reassignments
        s2.shutdown()
        s3 = make_server(port=port, reuse_port=True)
        s3.start_experiment(spec)
        for qx, variant in seen.items():
            status, body = http_query(port, qx)
            assert status == 200
            assert json.loads(body)["variant"] == variant

    def test_start_is_idempotent_and_refuses_second_experiment(
        self, exp_world
    ):
        storage, make_server, live, cand = exp_world
        server = make_server()
        spec = _exp_spec("one", live, cand)
        st = server.start_experiment(spec)
        assert st["variants"] == [live, cand]
        # identical re-post (fleet-converge nudge) is a no-op
        assert server.start_experiment(spec)["variants"] == [live, cand]
        with pytest.raises(ValueError, match="already running"):
            server.start_experiment(_exp_spec("two", live, cand))
        rep = server.stop_experiment()
        assert rep["stopped"] and rep["experiment"] == "one"
        # non-live arm retired warm into the retained LRU
        assert server.retained_versions() == [cand]

    def test_stop_with_winner_drains_loser_to_ledger_zero(self, exp_world):
        storage, make_server, live, cand = exp_world
        server = make_server()
        server.start_experiment(_exp_spec("w", live, cand))
        rep = server.stop_experiment(winner=live)
        assert rep["winner"] == live and rep["drained"] == [cand]
        # background drain releases the loser's device state
        deadline = 50
        while not GateAlgo.released_models and deadline:
            threading.Event().wait(0.1)
            deadline -= 1
        assert GateAlgo.released_models
        assert all(
            mdl.device_state is None for mdl in GateAlgo.released_models
        )

    def test_experiment_http_surface_and_access_key_gate(self, exp_world):
        storage, make_server, live, cand = exp_world
        server = make_server(access_key="sekrit")
        base = f"http://localhost:{server.port}/experiment.json"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base, timeout=10)
        assert ei.value.code == 401
        spec = _exp_spec("http", live, cand)
        req = urllib.request.Request(
            base + "?accessKey=sekrit",
            data=json.dumps({"spec": spec.to_json()}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=10) as resp:
            started = json.loads(resp.read())
        assert started["variants"] == [live, cand]
        with urllib.request.urlopen(
            base + "?accessKey=sekrit", timeout=10
        ) as resp:
            st = json.loads(resp.read())
        assert st["experiment"]["spec"]["name"] == "http"
        stop = urllib.request.Request(
            base + "?accessKey=sekrit",
            data=json.dumps({"stop": True}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(stop, timeout=10) as resp:
            rep = json.loads(resp.read())
        assert rep["stopped"] is True

    def test_shutdown_mid_experiment_releases_every_arm(self, exp_world):
        storage, make_server, live, cand = exp_world
        server = make_server()
        server.start_experiment(_exp_spec("down", live, cand))
        server.shutdown()
        assert GateAlgo.released_models
        assert all(
            mdl.device_state is None for mdl in GateAlgo.released_models
        )


# --- the runner: verdict execution end to end ---


class TestRunner:
    def _attr(self, vid, converted, miss):
        c = q._attributed_counter()
        if converted:
            c.labels(version=vid, outcome="converted").inc(converted)
        if miss:
            c.labels(version=vid, outcome="miss").inc(miss)

    def test_winner_promotes_through_gated_pipeline(self, exp_world):
        storage, make_server, live, cand = exp_world
        server = make_server()
        spec = _exp_spec("runner-win", live, cand, alpha=0.05, tau=0.3)
        pipeline = PromotionPipeline(
            InProcessTarget(server),
            PromotionConfig(observe_s=0.0, drain_timeout_s=5.0),
            storage=storage,
        )
        runner = ExperimentRunner(server, storage, spec, pipeline=pipeline)
        runner.start()
        # serve a little real traffic through both arms
        for qx in range(10):
            assert http_query(server.port, qx)[0] == 200
        # deltas-since-start: the candidate converts far better
        self._attr(live, 20, 80)
        self._attr(cand, 60, 40)
        final = runner.step()
        assert final is not None
        assert final["resolved_winner"] == cand
        assert final["promotion"]["outcome"] == "promoted"
        assert server.api.deployed.engine_instance.id == cand
        # allocation stopped: responses no longer stamped
        status, body = http_query(server.port, 99)
        assert status == 200 and "variant" not in json.loads(body)
        # finish is idempotent
        assert runner.step() is final or runner.step() == final

    def test_inconclusive_horizon_keeps_control(self, exp_world):
        storage, make_server, live, cand = exp_world
        server = make_server()
        t = [1000.0]
        spec = _exp_spec("runner-hzn", live, cand, horizon_s=30.0)
        runner = ExperimentRunner(
            server, storage, spec, pipeline=object(), clock=lambda: t[0]
        )
        runner.start()
        assert runner.step() is None  # still inside the horizon
        t[0] += 31.0
        final = runner.step()
        assert final["status"] == "horizon"
        # keep-control: the live control stays; no promotion attempted
        assert final["resolved_winner"] == live
        assert final["promotion"] is None
        assert server.api.deployed.engine_instance.id == live


# --- collector-side federated evaluation + admin gate ---


def _worker_text(vid, converted, miss, requests):
    reg = m.MetricsRegistry()
    c = reg.counter(
        "pio_online_attributed_total", "a", labels=("version", "outcome")
    )
    if converted:
        c.labels(version=vid, outcome="converted").inc(converted)
    if miss:
        c.labels(version=vid, outcome="miss").inc(miss)
    reg.counter(
        "pio_serving_requests_total", "r", labels=("version",)
    ).labels(version=vid).inc(requests)
    return reg.render()


def _inject(col, url, text):
    import time as _time

    state = col._targets[url.rstrip("/")]
    state.ring.append((_time.time(), m.parse_exposition(text)))
    state.families = m.parse_exposition_families(text)
    state.up = True
    state.ready = True


class TestCollectorPlane:
    def _collector(self):
        col = Collector([], poll_interval_s=0.1)
        col.add_target("http://wa:9001")
        col.add_target("http://wb:9002")
        return col

    def test_deltas_since_registration_and_sticky_verdict(self):
        col = self._collector()
        # pre-experiment history that must NOT count
        _inject(col, "http://wa:9001", _worker_text("arm-a", 500, 500, 1000))
        _inject(col, "http://wb:9002", _worker_text("arm-b", 500, 500, 1000))
        spec = spec2(name="fed", min_samples=50, tau=0.3)
        assert col.register_experiment(spec) is True
        # identical re-registration is the free fleet-converge nudge
        assert col.register_experiment(spec) is False
        reports = col.evaluate_experiments()
        assert reports[0]["status"] == "running"
        assert reports[0]["variants"]["arm-a"]["attributed"] == 0.0
        # post-registration traffic: candidate clearly better
        _inject(col, "http://wa:9001", _worker_text("arm-a", 600, 900, 2000))
        _inject(col, "http://wb:9002", _worker_text("arm-b", 750, 750, 2000))
        report = col.evaluate_experiments()[0]
        assert report["variants"]["arm-a"]["attributed"] == 500.0
        assert report["variants"]["arm-b"]["attributed"] == 500.0
        assert report["status"] == "decided"
        assert report["winner"] == "arm-b"
        # sticky: a later (even contradictory) scrape re-reports it
        _inject(col, "http://wb:9002", _worker_text("arm-b", 750, 7500, 9000))
        assert col.evaluate_experiments()[0] == report
        assert col.experiment_report("fed")["winner"] == "arm-b"
        assert col.remove_experiment("fed") is True
        assert col.experiment_reports() == []

    def test_restarted_worker_clamps_to_zero(self):
        col = self._collector()
        _inject(col, "http://wa:9001", _worker_text("arm-a", 900, 100, 1000))
        _inject(col, "http://wb:9002", _worker_text("arm-b", 100, 900, 1000))
        spec = spec2(name="clamp", min_samples=10, tau=0.3)
        col.register_experiment(spec)
        # wa restarts: counters reset BELOW the baseline — the delta
        # clamps to zero instead of going negative
        _inject(col, "http://wa:9001", _worker_text("arm-a", 5, 5, 10))
        report = col.evaluate_experiments()[0]
        assert report["variants"]["arm-a"]["converted"] == 0.0
        assert report["variants"]["arm-a"]["miss"] == 0.0

    def test_experiments_api_is_admin_gated(self):
        from predictionio_tpu.tools.collector import CollectorServer

        col = Collector([], poll_interval_s=0.1)
        srv = CollectorServer(
            col, ip="localhost", port=0, admin_secret="s3"
        ).start()
        try:
            base = f"http://localhost:{srv.port}/api/experiments.json"
            # GET is an open read
            with urllib.request.urlopen(base, timeout=10) as resp:
                assert json.loads(resp.read())["experiments"] == []
            payload = {"spec": spec2(name="gated").to_json()}
            req = urllib.request.Request(
                base, data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"},
            )
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=10)
            assert ei.value.code == 401
            ok = urllib.request.Request(
                base,
                data=json.dumps({**payload, "secret": "s3"}).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(ok, timeout=10) as resp:
                body = json.loads(resp.read())
            assert body == {"added": True, "experiment": "gated"}
            with urllib.request.urlopen(base, timeout=10) as resp:
                listed = json.loads(resp.read())["experiments"]
            assert listed[0]["spec"]["name"] == "gated"
            rm = urllib.request.Request(
                base,
                data=json.dumps(
                    {"remove": "gated", "secret": "s3"}
                ).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(rm, timeout=10) as resp:
                assert json.loads(resp.read())["removed"] is True
        finally:
            srv.shutdown()
