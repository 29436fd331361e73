"""Model-quality observability tests (workflow/quality.py + the serving/
ingest wiring): per-version serving attribution, the prId feedback join
on the event server's commit hook, prediction capture + replay, shadow
scoring in the continuous loop, and end-to-end trace continuity across
the serving→feedback→ingest chain.
"""

import http.client
import json
import logging
import threading
import time

import pytest

from predictionio_tpu.api.engine_server import (
    DeployedEngine,
    EngineServer,
    QueryAPI,
    ServerConfig,
)
from predictionio_tpu.api.event_server import (
    EventAPI,
    EventServer,
    EventServerConfig,
)
from predictionio_tpu.data.event import DataMap, Event
from predictionio_tpu.data.storage import Storage
from predictionio_tpu.data.storage.base import AccessKey, App
from predictionio_tpu.utils import metrics as m
from predictionio_tpu.utils import tracing as tr
from predictionio_tpu.workflow import quality as q

from tests import fake_engine as fe
from tests.test_engine_server import make_engine, train_instance


@pytest.fixture(autouse=True)
def _fresh_quality():
    """Isolate the process-global capture ring + attribution table."""
    q.get_capture().clear()
    q.get_attribution().clear()
    yield
    q.get_capture().clear()
    q.get_attribution().clear()


@pytest.fixture
def _restore_root_logging():
    """In-process ``pio`` invocations install a root handler bound to
    pytest's captured stderr (cli.main → setup_logging); drop it after
    the test so later tests don't log into a closed capture stream."""
    root = logging.getLogger()
    level = root.level
    before = list(root.handlers)
    yield
    for h in list(root.handlers):
        if h not in before:
            root.removeHandler(h)
    root.setLevel(level)


def _attributed(version, outcome) -> int:
    c = m.get_registry().counter(
        "pio_online_attributed_total",
        "Ingested events joined against recently served predictions, "
        "by model version and outcome (converted = the event's target "
        "item was in the served list)",
        labels=("version", "outcome"),
    )
    return int(c.labels(version=version, outcome=outcome).value)


# --- the comparison primitives ---


class TestCompare:
    def test_extract_items_reference_wire_format(self):
        items, scores = q.extract_items(
            {"itemScores": [
                {"item": "i1", "score": 2.5}, {"item": "i2", "score": 1.0},
            ]}
        )
        assert items == ("i1", "i2")
        assert scores == (2.5, 1.0)

    def test_extract_items_generic_result_digest(self):
        a, _ = q.extract_items({"qx": 5, "models": [[1, 8]]})
        b, _ = q.extract_items({"qx": 5, "models": [[1, 8]]})
        c, _ = q.extract_items({"qx": 6, "models": [[1, 8]]})
        assert a == b and a != c and len(a) == 1

    def test_extract_items_ignores_served_stamps(self):
        """A replayed result (no prId minted) must digest identically to
        the captured one — the stamps the serving tier injects are
        volatile."""
        raw, _ = q.extract_items({"qx": 5})
        stamped, _ = q.extract_items(
            {"qx": 5, "prId": "x" * 64, "modelVersion": "v1"}
        )
        assert raw == stamped

    def test_compare_topn_identical_and_disjoint(self):
        same = q.compare_topn(("a", "b"), (2.0, 1.0), ("a", "b"), (2.0, 1.0))
        assert same == {
            "jaccard": 1.0, "rank_displacement": 0.0, "score_delta": 0.0,
        }
        disjoint = q.compare_topn(("a",), (1.0,), ("b",), (1.0,))
        assert disjoint["jaccard"] == 0.0

    def test_compare_topn_rank_displacement_and_score_delta(self):
        cmp = q.compare_topn(
            ("a", "b", "c"), (3.0, 2.0, 1.0),
            ("c", "b", "a"), (3.5, 2.0, 1.0),
        )
        assert cmp["jaccard"] == 1.0
        assert cmp["rank_displacement"] == pytest.approx(4.0 / 3.0)
        assert cmp["score_delta"] > 0


# --- the capture ring + file round trip ---


class TestCapture:
    def test_ring_is_bounded_and_filterable(self):
        cap = q.PredictionCapture(capacity=4)
        for i in range(6):
            cap.record(
                version="v1" if i % 2 else "v2",
                query_json={"qx": i},
                result_json={"qx": i},
            )
        assert len(cap) == 4
        assert [r["query"]["qx"] for r in cap.dump()] == [2, 3, 4, 5]
        assert all(r["version"] == "v1" for r in cap.dump(version="v1"))
        assert [r["query"]["qx"] for r in cap.dump(limit=2)] == [4, 5]

    def test_save_load_round_trip_and_debug_dump_shape(self, tmp_path):
        cap = q.PredictionCapture()
        cap.record(version="v", query_json={"qx": 1}, result_json={"qx": 1})
        records = cap.dump()
        path = str(tmp_path / "cap.jsonl")
        assert q.save_capture(path, records) == 1
        assert q.load_capture(path) == records
        # a saved /debug/predictions.json response loads identically
        obj_path = str(tmp_path / "cap.json")
        with open(obj_path, "w") as f:
            json.dump({"predictions": records}, f)
        assert q.load_capture(obj_path) == records


# --- the attribution table ---


class TestAttributionTable:
    def _predict_event(self, pr_id, version="v1", items=("i1", "i2", "i3")):
        return Event(
            event="predict",
            entity_type="pio_pr",
            entity_id=pr_id,
            properties=DataMap({
                "engineInstanceId": version,
                "query": {"user": "u1"},
                "prediction": {
                    "itemScores": [
                        {"item": i, "score": 1.0} for i in items
                    ]
                },
            }),
        )

    def test_converted_observed_unknown_outcomes(self):
        table = q.AttributionTable()
        table.register_from_event(self._predict_event("p" * 64))
        conv0 = _attributed("v1", "converted")
        obs0 = _attributed("v1", "miss")
        unk0 = _attributed("unknown", "unknown")
        assert table.observe(Event(
            event="buy", entity_type="user", entity_id="u1",
            target_entity_type="item", target_entity_id="i2",
            pr_id="p" * 64,
        )) == "converted"
        assert table.observe(Event(
            event="view", entity_type="user", entity_id="u1",
            target_entity_type="item", target_entity_id="iX",
            pr_id="p" * 64,
        )) == "miss"
        assert table.observe(Event(
            event="buy", entity_type="user", entity_id="u1",
            target_entity_type="item", target_entity_id="i1",
            pr_id="z" * 64,
        )) == "unknown"
        assert table.observe(Event(
            event="buy", entity_type="user", entity_id="u1",
        )) is None  # no prId: not an attribution candidate
        assert _attributed("v1", "converted") == conv0 + 1
        assert _attributed("v1", "miss") == obs0 + 1
        assert _attributed("unknown", "unknown") == unk0 + 1
        stats = table.stats()
        v1 = stats["versions"]["v1"]
        assert v1["hitRate"] == pytest.approx(
            v1.get("converted", 0)
            / (v1.get("converted", 0) + v1.get("miss", 0))
        )

    def test_conversion_rank_is_one_based(self):
        table = q.AttributionTable()
        table.register_from_event(self._predict_event("r" * 64))
        h = m.get_registry().histogram(
            "pio_online_conversion_rank",
            "1-based rank of the converted item within its served list",
            labels=("version",),
            buckets=m.BATCH_SIZE_BUCKETS,
        ).labels(version="v1")
        base = h.snapshot()
        table.observe(Event(
            event="buy", entity_type="user", entity_id="u",
            target_entity_type="item", target_entity_id="i3",
            pr_id="r" * 64,
        ))
        delta = h.snapshot().delta(base)
        assert delta.count == 1 and delta.sum == pytest.approx(3.0)

    def test_ttl_expiry_and_bounded_size(self):
        table = q.AttributionTable(ttl_s=0.01, max_entries=2)
        table.register("a" * 64, "v1", ("i1",))
        time.sleep(0.05)
        assert table.observe(Event(
            event="buy", entity_type="user", entity_id="u",
            target_entity_type="item", target_entity_id="i1",
            pr_id="a" * 64,
        )) == "unknown"  # expired
        for c in "bcd":
            table.register(c * 64, "v1", ("i1",))
        assert len(table) == 2  # oldest evicted


# --- the ingest-path join via the event server's commit hook ---


@pytest.mark.parametrize("transport", ["async", "threaded"])
class TestIngestAttribution:
    def _post(self, port, path, payload):
        conn = http.client.HTTPConnection("localhost", port, timeout=10)
        try:
            conn.request(
                "POST", path, json.dumps(payload),
                {"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            return resp.status, json.loads(resp.read() or b"null")
        finally:
            conn.close()

    def test_attribution_join_over_http(self, mem_storage, transport):
        app_id = mem_storage.get_meta_data_apps().insert(
            App(id=0, name="qa")
        )
        mem_storage.get_meta_data_access_keys().insert(
            AccessKey(key="k", appid=app_id, events=())
        )
        mem_storage.get_l_events().init(app_id)
        server = EventServer(
            storage=mem_storage,
            config=EventServerConfig(port=0, transport=transport),
        ).start()
        try:
            pr_id = "q" * 64
            version = "inst-attr-" + transport
            conv0 = _attributed(version, "converted")
            # 1. the feedback predict event registers the served
            #    prediction (this is exactly what the engine server's
            #    feedback loop posts)
            status, body = self._post(
                server.port, f"/events.json?accessKey=k", {
                    "event": "predict",
                    "entityType": "pio_pr",
                    "entityId": pr_id,
                    "properties": {
                        "engineInstanceId": version,
                        "query": {"user": "u7"},
                        "prediction": {"itemScores": [
                            {"item": "i5", "score": 3.0},
                            {"item": "i9", "score": 1.0},
                        ]},
                    },
                },
            )
            assert status == 201, body
            # 2. a user event carrying the served prId converts (batch
            #    route: the hook covers both ingest paths)
            status, body = self._post(
                server.port, f"/batch/events.json?accessKey=k", [{
                    "event": "buy",
                    "entityType": "user",
                    "entityId": "u7",
                    "targetEntityType": "item",
                    "targetEntityId": "i9",
                    "prId": pr_id,
                }],
            )
            assert status == 200 and body[0]["status"] == 201
            assert _attributed(version, "converted") == conv0 + 1
            # the rendered exposition carries the family
            reg_text = m.get_registry().render()
            assert (
                f'pio_online_attributed_total{{version="{version}",'
                f'outcome="converted"}}' in reg_text
            )
            # status.json surfaces the registry-backed join summary
            _, sbody = EventAPI.handle(
                server.api, "GET", "/status.json", {"accessKey": "k"}
            )
            assert version in sbody["attribution"]["versions"]
        finally:
            server.shutdown()


# --- serving-side: version stamps, capture, gated dump ---


@pytest.fixture()
def query_api(mem_storage):
    fe.reset_counters()
    train_instance(mem_storage)
    dep = DeployedEngine.from_storage(make_engine(), mem_storage)
    api = QueryAPI(dep, ServerConfig())
    yield api
    api.close()


class TestServingAttribution:
    def test_response_stamped_with_model_version(self, query_api):
        _, body, _ = query_api.handle(
            "POST", "/queries.json", body=json.dumps({"qx": 4}).encode()
        )
        assert body["modelVersion"] == (
            query_api.deployed.engine_instance.id
        )

    def test_feedback_injects_pr_id_and_capture_records_it(
        self, mem_storage
    ):
        fe.reset_counters()
        train_instance(mem_storage)
        dep = DeployedEngine.from_storage(make_engine(), mem_storage)
        api = QueryAPI(
            dep,
            ServerConfig(
                feedback=True, access_key="fk",
                event_server_port=1,  # refused instantly; posts best-effort
            ),
        )
        try:
            _, body, _ = api.handle(
                "POST", "/queries.json", body=json.dumps({"qx": 1}).encode()
            )
            assert len(body["prId"]) == 64
            [record] = q.get_capture().dump()
            assert record["prId"] == body["prId"]
            assert record["version"] == dep.engine_instance.id
            # capture stores the RAW model output (replay-comparable)
            assert "prId" not in record["result"]
        finally:
            api.close()

    def test_capture_sampling_and_disable(self, mem_storage):
        fe.reset_counters()
        train_instance(mem_storage)
        dep = DeployedEngine.from_storage(make_engine(), mem_storage)
        api = QueryAPI(dep, ServerConfig(capture_sample=2))
        try:
            for i in range(4):
                api.handle(
                    "POST", "/queries.json",
                    body=json.dumps({"qx": i}).encode(),
                )
            assert len(q.get_capture()) == 2  # every 2nd query
        finally:
            api.close()
        q.get_capture().clear()
        api = QueryAPI(dep, ServerConfig(capture_sample=0))
        try:
            api.handle(
                "POST", "/queries.json", body=json.dumps({"qx": 9}).encode()
            )
            assert len(q.get_capture()) == 0
        finally:
            api.close()

    def test_predictions_dump_is_access_key_gated(self, mem_storage):
        fe.reset_counters()
        train_instance(mem_storage)
        dep = DeployedEngine.from_storage(make_engine(), mem_storage)
        api = QueryAPI(
            dep,
            ServerConfig(
                feedback=True, access_key="gk", event_server_port=1
            ),
        )
        try:
            api.handle(
                "POST", "/queries.json", body=json.dumps({"qx": 1}).encode()
            )
            status, _, _ = api.handle("GET", "/debug/predictions.json")
            assert status == 401
            status, payload, _ = api.handle(
                "GET", "/debug/predictions.json", {"accessKey": "gk"}
            )
            assert status == 200
            assert len(payload["predictions"]) == 1
            assert payload["predictions"][0]["query"] == {"qx": 1}
        finally:
            api.close()

    def test_predictions_dump_refused_without_configured_key(
        self, query_api
    ):
        """Capture records hold full query/result payloads — a keyless
        server must refuse the dump outright, not serve it open."""
        query_api.handle(
            "POST", "/queries.json", body=json.dumps({"qx": 1}).encode()
        )
        status, body, _ = query_api.handle(
            "GET", "/debug/predictions.json"
        )
        assert status == 403
        assert "access key" in body["message"]
        # the ring still captured (shadow scoring reads it in-process)
        assert len(q.get_capture()) == 1

    def test_capture_immune_to_inplace_mutating_plugin(self, mem_storage):
        """The capture snapshot is taken before the plugin stage and
        deep-copied: a blocker that mutates the response in place must
        not corrupt the recorded raw result (that would make an honest
        self-replay report false divergence)."""
        from predictionio_tpu.api.engine_plugins import (
            EngineServerPlugin,
            EngineServerPluginContext,
        )

        class InPlaceBlocker(EngineServerPlugin):
            plugin_name = "inplace"
            plugin_type = EngineServerPlugin.OUTPUT_BLOCKER

            def process(self, engine_instance, query_json, result_json, ctx):
                result_json["mutated"] = True
                return result_json

        fe.reset_counters()
        train_instance(mem_storage)
        dep = DeployedEngine.from_storage(make_engine(), mem_storage)
        api = QueryAPI(
            dep, ServerConfig(),
            plugin_context=EngineServerPluginContext([InPlaceBlocker()]),
        )
        try:
            _, body, _ = api.handle(
                "POST", "/queries.json", body=json.dumps({"qx": 5}).encode()
            )
            assert body["mutated"] is True
            records = q.get_capture().dump()
            assert len(records) == 1
            assert "mutated" not in records[0]["result"]
            report = q.replay_capture(records, dep)
            assert report["diverged"] == 0
            assert report["jaccard_mean"] == 1.0
        finally:
            api.close()

    def test_status_json_reports_version_and_capture(self, query_api):
        query_api.handle(
            "POST", "/queries.json", body=json.dumps({"qx": 0}).encode()
        )
        _, s, _ = query_api.handle("GET", "/status.json")
        assert s["modelVersion"] == query_api.deployed.engine_instance.id
        assert s["predictionCapture"]["records"] == 1


class TestReloadSwapAttribution:
    def test_swap_under_traffic_shows_both_versions_disjoint(
        self, mem_storage
    ):
        """Acceptance: a /reload swap under driven traffic shows BOTH
        version labels, with disjoint sample windows, in one /metrics
        scrape — and pio_model_info flips to the new version."""
        fe.reset_counters()
        train_instance(mem_storage)
        server = EngineServer(
            make_engine(), ServerConfig(port=0), storage=mem_storage
        ).start()
        try:
            base = f"http://localhost:{server.port}"
            v1 = server.api.deployed.engine_instance.id

            served = {"n": 0}
            lock = threading.Lock()

            def do_query(qx):
                req_body = json.dumps({"qx": qx}).encode()
                conn = http.client.HTTPConnection(
                    "localhost", server.port, timeout=10
                )
                try:
                    conn.request(
                        "POST", "/queries.json", req_body,
                        {"Content-Type": "application/json"},
                    )
                    resp = conn.getresponse()
                    resp.read()
                    if resp.status == 200:
                        with lock:
                            served["n"] += 1
                finally:
                    conn.close()

            for i in range(5):
                do_query(i)
            # train the new instance, then swap while traffic is live
            v2 = train_instance(mem_storage)
            threads = [
                threading.Thread(target=do_query, args=(100 + i,))
                for i in range(8)
            ]
            for t in threads:
                t.start()
            import urllib.request

            with urllib.request.urlopen(f"{base}/reload") as resp:
                resp.read()
            for t in threads:
                t.join()
            deadline = time.time() + 5
            while time.time() < deadline:
                if server.api.deployed.engine_instance.id == v2:
                    break
                time.sleep(0.05)
            assert server.api.deployed.engine_instance.id == v2
            for i in range(5):
                do_query(200 + i)

            with urllib.request.urlopen(f"{base}/metrics") as resp:
                text = resp.read().decode()
            samples = m.parse_exposition(text)
            n1 = samples.get(
                f'pio_serving_requests_total{{version="{v1}"}}', 0.0
            )
            n2 = samples.get(
                f'pio_serving_requests_total{{version="{v2}"}}', 0.0
            )
            # both windows present, disjoint: every served query counted
            # under exactly one version
            assert n1 >= 5 and n2 >= 5
            assert n1 + n2 == served["n"]
            assert samples.get(
                f'pio_model_info{{engine="fake",version="{v2}"}}'
            ) == 1.0
            assert samples.get(
                f'pio_model_info{{engine="fake",version="{v1}"}}'
            ) == 0.0
            # status.json totals span both versions
            with urllib.request.urlopen(f"{base}/status.json") as resp:
                status_json = json.loads(resp.read())
            assert status_json["requestCount"] == served["n"]
        finally:
            server.shutdown()


# --- replay: the deterministic divergence oracle ---


class TestReplay:
    def _capture_some(self, query_api, n=6):
        for i in range(n):
            status, _, _ = query_api.handle(
                "POST", "/queries.json", body=json.dumps({"qx": i}).encode()
            )
            assert status == 200
        return q.get_capture().dump()

    def test_self_replay_reports_zero_divergence(self, query_api):
        records = self._capture_some(query_api)
        report = q.replay_capture(records, query_api.deployed)
        assert report["queries"] == len(records)
        assert report["diverged"] == 0
        assert report["jaccard_mean"] == 1.0
        assert report["jaccard_min"] == 1.0
        assert report["rank_displacement_max"] == 0.0
        assert report["score_delta_mean"] == 0.0

    def test_replay_flags_a_diverging_model(self, query_api):
        records = self._capture_some(query_api, n=3)
        # corrupt the capture: a "different model" served other results
        records = [dict(r, items=["bogus"], scores=[0.0]) for r in records]
        report = q.replay_capture(records, query_api.deployed)
        assert report["diverged"] == 3
        assert report["jaccard_mean"] == 0.0
        assert "worst" in report

    def test_cli_replay_self_replay_smoke(
        self, mem_storage, tmp_path, capsys, _restore_root_logging
    ):
        from predictionio_tpu.tools.cli import main as cli_main

        fe.reset_counters()
        vpath = tmp_path / "engine.json"
        vpath.write_text(json.dumps({
            "id": "qreplay",
            "engineFactory": "tests.fake_engine.FakeEngineFactory",
            "datasource": {"params": {"id": 3}},
            "preparator": {"params": {"offset": 1}},
            "algorithms": [{"name": "a0", "params": {"id": 1}}],
        }))
        assert cli_main(["train", "-v", str(vpath)]) == 0
        engine = fe.FakeEngineFactory().apply()
        dep = DeployedEngine.from_storage(engine, mem_storage)
        api = QueryAPI(dep, ServerConfig())
        try:
            for i in range(4):
                api.handle(
                    "POST", "/queries.json",
                    body=json.dumps({"qx": i}).encode(),
                )
        finally:
            api.close()
        cap_path = str(tmp_path / "capture.jsonl")
        q.save_capture(cap_path, q.get_capture().dump())
        rc = cli_main([
            "replay", "--capture", cap_path, "-v", str(vpath),
            "--fail-on-divergence",
        ])
        out = capsys.readouterr().out
        assert rc == 0
        assert "diverged: 0/4" in out
        assert "jaccard mean 1.000000" in out


# --- shadow scoring in the continuous loop ---


class TestShadowScoring:
    def test_shadow_score_identical_instances_comparable(self, mem_storage):
        fe.reset_counters()
        iid1 = train_instance(mem_storage)
        iid2 = train_instance(mem_storage)
        records = [
            {"query": {"qx": i}, "items": [], "scores": []}
            for i in range(3)
        ]
        report = q.shadow_score(
            make_engine(), mem_storage, iid1, iid2, records,
            min_jaccard=0.5,
        )
        # the fake engine is deterministic: both instances serve the
        # same predictions, so the candidate is fully comparable
        assert report["verdict"] == "comparable"
        assert report["queries"] == 3
        assert report["jaccard_mean"] == 1.0
        assert report["liveVersion"] == iid1
        assert report["candidateVersion"] == iid2
        g = m.get_registry().gauge(
            "pio_shadow_last_jaccard",
            "Mean jaccard of the latest shadow-scored round "
            "(candidate vs live on the captured sample)",
        )
        assert g.value == 1.0

    def test_continuous_rounds_carry_shadow_verdict(self, mem_storage):
        import datetime as dt

        from predictionio_tpu.data.storage.base import EngineInstance
        from predictionio_tpu.workflow.continuous import continuous_train

        fe.reset_counters()
        # captured serving traffic the shadow pass scores against
        for i in range(4):
            q.get_capture().record(
                version="seed",
                query_json={"qx": i},
                result_json={"qx": i},
            )
        now = dt.datetime.now(dt.timezone.utc)
        template = EngineInstance(
            id="", status="", start_time=now, end_time=now,
            engine_id="fake", engine_version="1",
            engine_variant="engine.json",
            engine_factory="tests.fake_engine",
        )
        from tests.test_engine_server import make_params

        reports = []
        rounds = continuous_train(
            make_engine(), make_params(), template,
            storage=mem_storage,
            interval_s=0.01,
            max_rounds=2,
            on_round=reports.append,
            shadow_queries=4,
            shadow_min_jaccard=0.5,
        )
        assert rounds == 2
        # round 1 has no live reference yet; round 2 shadow-scores the
        # fresh candidate against round 1's instance
        assert reports[0].shadow is None
        shadow = reports[1].shadow
        assert shadow is not None
        assert shadow["verdict"] == "comparable"
        assert shadow["queries"] == 4
        assert shadow["liveVersion"] == reports[0].instance_id
        assert shadow["candidateVersion"] == reports[1].instance_id


# --- pio top: the VERSION / HIT% columns ---


class TestTopQualityColumns:
    def test_version_and_hit_rate_parsed_from_exposition(self):
        from predictionio_tpu.tools.top import (
            _row,
            active_model_version,
            attributed_hit_rate,
        )

        samples = {
            'pio_model_info{engine="e",version="v-new"}': 1.0,
            'pio_model_info{engine="e",version="v-old"}': 0.0,
            'pio_online_attributed_total{version="v-new",'
            'outcome="converted"}': 3.0,
            'pio_online_attributed_total{version="v-new",'
            'outcome="miss"}': 1.0,
            'pio_online_attributed_total{version="unknown",'
            'outcome="unknown"}': 7.0,
        }
        # the swapped-out version (gauge 0) is not "active"
        assert active_model_version(samples) == "v-new"
        # unknown outcomes are excluded from the hit-rate denominator
        assert attributed_hit_rate(samples) == pytest.approx(0.75)
        row = _row(
            {"url": "http://x", "up": True, "metrics": samples}, None, 0.0
        )
        assert row["version"] == "v-new"
        assert row["hit_rate"] == 75.0

    def test_no_quality_samples_yield_no_columns(self):
        from predictionio_tpu.tools.top import _row

        row = _row({"url": "http://x", "up": True, "metrics": {}}, None, 0.0)
        assert "version" not in row and "hit_rate" not in row


# --- end-to-end trace continuity (serving → feedback → ingest) ---


class TestTraceContinuity:
    def test_one_trace_spans_query_feedback_and_commit(self, tmp_path):
        """Satellite: one trace id asserted across http→batch→predict→
        feedback-post→committer-flush, dumped from BOTH servers'
        /debug/traces.json."""
        config = {
            "PIO_STORAGE_SOURCES_SQLITE_TYPE": "sqlite",
            "PIO_STORAGE_SOURCES_SQLITE_PATH": str(tmp_path / "q.db"),
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQLITE",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "SQLITE",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "SQLITE",
        }
        storage = Storage(config)
        app_id = storage.get_meta_data_apps().insert(App(id=0, name="tq"))
        storage.get_meta_data_access_keys().insert(
            AccessKey(key="tk", appid=app_id, events=())
        )
        storage.get_l_events().init(app_id)
        fe.reset_counters()
        train_instance(storage)
        tr.clear()
        es = EventServer(
            storage=storage, config=EventServerConfig(port=0, compact=False)
        ).start()
        eng = None
        try:
            eng = EngineServer(
                make_engine(),
                ServerConfig(
                    port=0, feedback=True, access_key="tk",
                    event_server_port=es.port,
                ),
                storage=storage,
            ).start()
            trace_id = "trace-quality-e2e"
            conn = http.client.HTTPConnection("localhost", eng.port)
            conn.request(
                "POST", "/queries.json", json.dumps({"qx": 3}),
                {
                    "Content-Type": "application/json",
                    "X-PIO-Trace-Id": trace_id,
                },
            )
            resp = conn.getresponse()
            assert resp.status == 200
            resp.read()
            conn.close()
            # the feedback post + committer flush land asynchronously
            want = {
                "http:/queries.json", "batch", "predict",
                "feedback-post", "http:POST /events.json", "insert",
                "group-commit-flush",
            }
            deadline = time.time() + 10
            names = set()
            while time.time() < deadline:
                names = {s["name"] for s in tr.dump(trace_id)}
                if want <= names:
                    break
                time.sleep(0.05)
            assert want <= names, names
            spans = tr.dump(trace_id)
            assert {s["traceId"] for s in spans} == {trace_id}
            by_name = {s["name"]: s for s in spans}
            # the chain: feedback-post parents on the serving http span,
            # the event server's http span parents on feedback-post
            assert (
                by_name["feedback-post"]["parentId"]
                == by_name["http:/queries.json"]["spanId"]
            )
            assert (
                by_name["http:POST /events.json"]["parentId"]
                == by_name["feedback-post"]["spanId"]
            )
            assert (
                by_name["insert"]["parentId"]
                == by_name["http:POST /events.json"]["spanId"]
            )

            # both servers dump the same trace over HTTP (gated)
            def dump_from(port, params):
                c = http.client.HTTPConnection("localhost", port, timeout=10)
                try:
                    c.request(
                        "GET", f"/debug/traces.json?{params}"
                    )
                    r = c.getresponse()
                    assert r.status == 200
                    return json.loads(r.read())["spans"]
                finally:
                    c.close()

            eng_spans = dump_from(
                eng.port, f"accessKey=tk&traceId={trace_id}"
            )
            es_spans = dump_from(
                es.port, f"accessKey=tk&traceId={trace_id}"
            )
            assert {s["name"] for s in eng_spans} >= {
                "http:/queries.json", "feedback-post",
            }
            assert {s["name"] for s in es_spans} >= {
                "insert", "group-commit-flush",
            }
        finally:
            if eng is not None:
                eng.shutdown()
            es.shutdown()
