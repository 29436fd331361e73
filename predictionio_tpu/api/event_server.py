"""The Event Server: REST event collection on :7070.

Capability parity with the reference EventServer
(data/src/main/scala/io/prediction/data/api/EventServer.scala:50-531):

  GET    /                      -> {"status": "alive"}
  GET    /plugins.json          -> registered plugin descriptions
  GET    /plugins/<type>/<name>/... -> plugin REST handler (auth)
  POST   /events.json           -> insert one event, 201 {"eventId"}
  POST   /batch/events.json     -> insert up to 50 events as ONE
                                   group-commit batch, 200 with a
                                   per-event status array (reference
                                   EventServer.scala:161-233)
  GET    /events.json           -> batch query (9 filters, default limit 20)
  GET    /events/<id>.json      -> one event or 404
  DELETE /events/<id>.json      -> {"message": "Found"} or 404
  GET    /stats.json            -> ingestion stats (requires stats=True)
  POST   /webhooks/<name>.json  -> JSON connector -> insert, 201
  GET    /webhooks/<name>.json  -> connector existence check
  POST   /webhooks/<name>       -> form connector -> insert, 201
  GET    /webhooks/<name>       -> connector existence check

Auth matches the reference (EventServer.scala:81-107): every data route
requires ?accessKey=...; an unknown key is 401, a missing key 401, an
invalid ?channel= name 400. The spray/akka actor stack is replaced by a
pure request core (`EventAPI.handle`) — unit-testable exactly like the
reference's spray-testkit route specs — plus a `ThreadingHTTPServer`
adapter (`EventServer`). Ingestion is purely host-side; the TPU only sees
event data later, as columnar batches from the store layer.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import json
import logging
import urllib.parse
import weakref
from typing import Any, Dict, Optional, Tuple

from predictionio_tpu.api.aio_http import TRANSPORTS, make_http_server

from predictionio_tpu.data.event import (
    Event,
    EventValidationError,
    parse_iso8601,
)
from predictionio_tpu.data.storage import Storage, get_storage
from predictionio_tpu.data.storage.base import (
    UNSET,
    PartialBatchError,
    StorageSaturatedError,
)
from predictionio_tpu.data.webhooks import (
    ConnectorException,
    to_event,
)
from predictionio_tpu.data.webhooks.example import (
    ExampleFormConnector,
    ExampleJsonConnector,
)
from predictionio_tpu.data.webhooks.mailchimp import MailChimpConnector
from predictionio_tpu.data.webhooks.segmentio import SegmentIOConnector
from predictionio_tpu.api.plugins import EventServerPlugin, EventServerPluginContext
from predictionio_tpu.api.stats import StatsTracker
from predictionio_tpu.utils import health as _health
from predictionio_tpu.utils import metrics as _metrics
from predictionio_tpu.utils import tracing as _tracing

logger = logging.getLogger(__name__)

# reference WebhooksConnectors.scala:26-34 (+ the example connectors the
# reference ships as copy-me templates, data/webhooks/example{json,form})
JSON_CONNECTORS = {
    "segmentio": SegmentIOConnector(),
    "examplejson": ExampleJsonConnector(),
}
FORM_CONNECTORS = {
    "mailchimp": MailChimpConnector(),
    "exampleform": ExampleFormConnector(),
}

DEFAULT_LIMIT = 20  # reference EventServer.scala:307


@dataclasses.dataclass
class EventServerConfig:
    """Reference EventServerConfig (EventServer.scala:496-500)."""

    ip: str = "localhost"
    port: int = 7070
    plugins: str = "plugins"
    stats: bool = False
    # bind with SO_REUSEPORT so several worker PROCESSES share the port
    # (kernel-balanced accepts) — the ingest scale-out past one
    # GIL-bound accept loop; requires multi-process-shared storage
    # (sqlite WAL file / gateway), NOT the in-memory backend
    reuse_port: bool = False
    # positive-result access-key cache TTL. Bounds how long a key
    # revoked by ANOTHER process keeps authenticating (same-process
    # deletes invalidate immediately via invalidate_access_key); 0
    # disables caching — every request reads the metadata store, the
    # reference's per-request behavior.
    auth_ttl_s: float = 5.0
    # REST transport: "async" = the event-loop frontend (api/aio_http.py)
    # — connections cost no OS threads; request handlers run on a
    # BOUNDED pool (handler_threads) because the insert path blocks
    # until its group-commit COMMIT acks. "threaded" = the stdlib
    # thread-per-connection fallback.
    transport: str = "async"
    # async-transport handler pool size: the ceiling on in-flight
    # (parked-on-COMMIT) requests. The group committer coalesces
    # everything queued within GROUP_COMMIT_MS, so a modest pool
    # saturates the write path; connections beyond it just queue.
    handler_threads: int = 16
    # background segment compaction (data/storage/segments.py): the
    # event server owns the write path, so it owns sealing cold row
    # ranges into mmap-scannable columnar segments too. A no-op on
    # backends without the tier (memory/http). False disables the
    # daemon (`pio eventserver --no-compact`); standalone compaction
    # stays available via `pio compact`.
    compact: bool = True
    compact_interval_s: float = 60.0
    # online feedback join (workflow/quality.py): committed feedback
    # `predict` events populate the prId→served-prediction table, and
    # committed events carrying a prId join against it, emitting
    # pio_online_attributed_total{version,outcome} + rank/time-to-
    # conversion histograms. Runs via the generic commit hook
    # (EventAPI.add_commit_observer); overhead is hard-gated <2% of
    # batch-ingest throughput by `bench.py --only quality`.
    attribution: bool = True

    def __post_init__(self):
        if self.transport not in TRANSPORTS:
            raise ValueError(
                f"unknown transport {self.transport!r} "
                f"(expected one of {TRANSPORTS})"
            )


def _saturated(e: StorageSaturatedError) -> Tuple[int, dict, str, dict]:
    """Deliberate backpressure: the storage write path refused admission
    (bounded group-commit queue full), so answer 503 + ``Retry-After``
    instead of parking the handler thread unboundedly. The transport
    layer counts it in ``pio_http_errors_total{status="503"}``."""
    retry_s = max(1, int(round(e.retry_after_s)))
    return (
        503,
        {"message": str(e)},
        "application/json",
        {"Retry-After": str(retry_s)},
    )


def _message(status: int, message: str) -> Tuple[int, dict]:
    return status, {"message": message}


# every live EventAPI, so the admin delete path can revoke a key from
# all in-process servers' auth caches immediately (the TTL alone left a
# same-process revocation authenticating for up to 5 s)
_LIVE_APIS: "weakref.WeakSet" = weakref.WeakSet()


def invalidate_access_key(key: Optional[str] = None) -> None:
    """Drop ``key`` (all keys when None) from every live in-process
    EventAPI's auth cache. Called by the access-key/app delete commands;
    cross-process servers still age revoked keys out at their TTL."""
    for api in list(_LIVE_APIS):
        api.invalidate_access_key(key)


class EventAPI:
    """Transport-independent request core for the event server."""

    def __init__(
        self,
        storage: Optional[Storage] = None,
        config: Optional[EventServerConfig] = None,
        plugin_context: Optional[EventServerPluginContext] = None,
    ):
        self.storage = storage or get_storage()
        self.config = config or EventServerConfig()
        self.plugin_context = plugin_context or EventServerPluginContext()
        self.stats = StatsTracker()
        self._events = self.storage.get_l_events()
        self._access_keys = self.storage.get_meta_data_access_keys()
        self._channels = self.storage.get_meta_data_channels()
        # access-key lookups hit the metadata store on EVERY request; on
        # a file-backed store that is a per-event SELECT contending with
        # the ingest writer (measured: most of the sqlite-vs-memory REST
        # throughput gap). Keys change rarely — a short TTL
        # (config.auth_ttl_s; 0 disables) bounds how long a key revoked
        # by another process keeps working (the reference re-reads per
        # request but against an in-JVM HBase client cache); same-process
        # deletes invalidate immediately (invalidate_access_key below).
        self._auth_cache: Dict[str, Tuple[float, Any]] = {}
        self._AUTH_TTL_S = float(self.config.auth_ttl_s)
        import time as _time

        self._started_monotonic = _time.monotonic()
        from predictionio_tpu.data.storage.segments import (
            CachedCompactionStatus,
        )

        self._compaction_status = CachedCompactionStatus(self.storage)
        # ingest bookkeeping in the process-global registry (the
        # /metrics exposition; per-route ingested-event counters beside
        # the storage tier's group-commit flush families)
        self._m_ingested = _metrics.get_registry().counter(
            "pio_events_ingested_total",
            "Events accepted by the event server, by route",
            labels=("route",),
        )
        # /readyz: the store must answer a cheap metadata read (TTL-
        # cached so an unauthenticated readiness poller cannot turn the
        # probe into a storage load); stalled-daemon checks are global
        self._ready_probes = (
            _health.TTLProbe("store", self._probe_store),
        )
        # the commit hook: observers run AFTER events commit, on the
        # ingest path, with the committed Event objects. The online
        # feedback join registers here; the per-user-cache tier's
        # change notifications (ROADMAP) will ride the same hook.
        self._commit_observers: list = []
        if self.config.attribution:
            from predictionio_tpu.workflow.quality import (
                attribution_observer,
            )

            self.add_commit_observer(attribution_observer())
        _LIVE_APIS.add(self)

    def add_commit_observer(self, fn) -> None:
        """Register ``fn(app_id, channel_id, events)`` to run after each
        successful insert/batch commit. Observers must be cheap (they
        sit on the ingest path) and must not raise — failures are
        logged and swallowed."""
        self._commit_observers.append(fn)

    def _notify_commit(self, app_id, channel_id, events) -> None:
        if not self._commit_observers or not events:
            return
        for obs in self._commit_observers:
            try:
                obs(app_id, channel_id, events)
            except Exception:
                logger.exception("commit observer failed")

    def _probe_store(self) -> None:
        self.storage.get_meta_data_apps().get_all()

    # --- auth (reference withAccessKey, EventServer.scala:81-107) ---

    def invalidate_access_key(self, key: Optional[str] = None) -> None:
        """Drop ``key`` (all keys when None) from the auth cache, so a
        just-revoked key stops authenticating NOW instead of at TTL
        expiry."""
        if key is None:
            self._auth_cache.clear()
        else:
            self._auth_cache.pop(key, None)

    def _lookup_access_key(self, key: str):
        import time as _time

        if self._AUTH_TTL_S <= 0:
            return self._access_keys.get(key)
        now = _time.monotonic()
        hit = self._auth_cache.get(key)
        if hit is not None and now - hit[0] < self._AUTH_TTL_S:
            return hit[1]
        access_key = self._access_keys.get(key)
        # only POSITIVE results cache: a just-created key must work
        # immediately, not 401 for a TTL (and unauthenticated floods of
        # random keys can't grow the cache — misses pay the store read,
        # exactly the pre-cache behavior)
        if access_key is not None:
            if len(self._auth_cache) > 10_000:
                self._auth_cache.clear()
            self._auth_cache[key] = (now, access_key)
        return access_key

    def _authenticate(
        self, query: Dict[str, str]
    ) -> Tuple[Optional[Tuple[int, Optional[int]]], Optional[Tuple[int, Any]]]:
        """Returns ((app_id, channel_id), None) or (None, error_response)."""
        key = query.get("accessKey")
        if not key:
            return None, _message(401, "Missing accessKey.")
        access_key = self._lookup_access_key(key)
        if access_key is None:
            return None, _message(401, "Invalid accessKey.")
        channel_name = query.get("channel")
        if channel_name is None:
            return (access_key.appid, None), None
        channels = self._channels.get_by_app_id(access_key.appid)
        for c in channels:
            if c.name == channel_name:
                return (access_key.appid, c.id), None
        return None, _message(400, f"Invalid channel '{channel_name}'.")

    # --- dispatch ---

    def handle(
        self,
        method: str,
        path: str,
        query: Optional[Dict[str, str]] = None,
        body: Optional[bytes] = None,
        form: Optional[Dict[str, str]] = None,
        headers: Optional[Dict[str, str]] = None,
    ) -> Tuple[int, Any]:
        """Route one request; returns (status, json-compatible payload)."""
        query = query or {}
        try:
            return self._route(method, path, query, body, form, headers)
        except Exception as e:  # reference Common.exceptionHandler
            logger.exception("internal error handling %s %s", method, path)
            return _message(500, str(e))

    def _route(
        self, method, path, query, body, form, headers=None
    ) -> Tuple[int, Any]:
        parts = [p for p in path.strip("/").split("/") if p]

        if not parts:
            if method == "GET":
                return 200, {"status": "alive"}
            return _message(405, "Method not allowed.")

        if path == "/plugins.json" and method == "GET":
            return 200, self.plugin_context.describe()

        if path == "/status.json" and method == "GET":
            return 200, self._status_json(query)

        if path == "/healthz" and method == "GET":
            # liveness: answers while the frontend runs handlers at all;
            # never consults storage or daemons (that's readiness)
            return 200, _health.liveness()

        if path == "/readyz" and method == "GET":
            # readiness: store reachable + no registered background
            # daemon (committers, compactor, continuous trainer) stalled
            # past its deadline — 503 tells the balancer to drain us
            ok, payload = _health.readiness(self._ready_probes)
            return (200 if ok else 503), payload

        if path == "/metrics" and method == "GET":
            # unauthenticated like status.json: process-level aggregates
            # only, the health-probe class of information
            return (
                200,
                _metrics.get_registry().render(),
                _metrics.render_content_type(),
            )

        if path == "/debug/traces.json" and method == "GET":
            # span dumps carry entity ids and timings — same class of
            # information the data routes gate behind access keys
            auth, err = self._authenticate(query)
            if err:
                return err
            from predictionio_tpu.api.http import traces_payload

            return traces_payload(query)

        if path == "/debug/profile":
            # on-demand profiler capture (utils/profiling.profile_route)
            # — device timelines expose workload structure, so it is
            # gated exactly like the data routes. A POST blocks for its
            # whole capture window, which is safe on BOTH transports:
            # async offloads every route to the bounded handler pool
            # (the capture parks one worker, same as a slow scan), and
            # threaded blocks its per-connection thread.
            auth, err = self._authenticate(query)
            if err:
                return err
            from predictionio_tpu.utils.profiling import profile_route

            return profile_route(method, query, True)

        if parts[0] == "plugins" and len(parts) >= 3 and method == "GET":
            auth, err = self._authenticate(query)
            if err:
                return err
            app_id, channel_id = auth
            plugin_type, plugin_name, args = parts[1], parts[2], parts[3:]
            table = (
                self.plugin_context.input_blockers
                if plugin_type == EventServerPlugin.INPUT_BLOCKER
                else self.plugin_context.input_sniffers
            )
            if plugin_name not in table:
                return _message(404, f"Plugin {plugin_name} not found.")
            return 200, table[plugin_name].handle_rest(app_id, channel_id, args)

        if path == "/events.json":
            auth, err = self._authenticate(query)
            if err:
                return err
            app_id, channel_id = auth
            if method == "POST":
                return self._post_event(app_id, channel_id, body, headers)
            if method == "GET":
                return self._find_events(app_id, channel_id, query)
            return _message(405, "Method not allowed.")

        if path == "/batch/events.json":
            auth, err = self._authenticate(query)
            if err:
                return err
            app_id, channel_id = auth
            if method != "POST":
                return _message(405, "Method not allowed.")
            return self._post_batch(app_id, channel_id, body, headers)

        if parts[0] == "events" and len(parts) == 2 and parts[1].endswith(".json"):
            auth, err = self._authenticate(query)
            if err:
                return err
            app_id, channel_id = auth
            event_id = urllib.parse.unquote(parts[1][: -len(".json")])
            if method == "GET":
                event = self._events.get(event_id, app_id, channel_id)
                if event is None:
                    return _message(404, "Not Found")
                return 200, event.to_json()
            if method == "DELETE":
                found = self._events.delete(event_id, app_id, channel_id)
                return (
                    (200, {"message": "Found"})
                    if found
                    else _message(404, "Not Found")
                )
            return _message(405, "Method not allowed.")

        if path == "/stats.json" and method == "GET":
            auth, err = self._authenticate(query)
            if err:
                return err
            app_id, _ = auth
            if not self.config.stats:
                return _message(
                    404,
                    "To see stats, launch Event Server with --stats argument.",
                )
            return 200, self.stats.get(app_id)

        if parts[0] == "webhooks" and len(parts) == 2:
            auth, err = self._authenticate(query)
            if err:
                return err
            app_id, channel_id = auth
            name = parts[1]
            if name.endswith(".json"):
                return self._webhook_json(
                    app_id, channel_id, name[: -len(".json")], method, body
                )
            return self._webhook_form(app_id, channel_id, name, method, form)

        return _message(404, "Not Found")

    def _status_json(self, query: Optional[Dict[str, str]] = None) -> dict:
        """Operational status (the engine server's status.json
        counterpart): uptime, transport, and segment-tier observability
        — segment count, compacted-event fraction, last-compaction
        timestamp (stats TTL-cached, ``CachedCompactionStatus``).

        The route itself stays unauthenticated (a health probe), but
        without a valid ``accessKey`` the compaction block is the
        cross-app AGGREGATE only — per-app names and counts are the
        same class of information the rest of the API gates behind
        keys. A valid key adds its own app's detail."""
        import time as _time

        per_app = self._compaction_status.get()
        # ingest totals are a read of the registry (same families the
        # /metrics route exposes), not a private tally
        ingested = {
            key[0]: int(child.value)
            for key, child in self._m_ingested.children()
        }
        out = {
            "status": "alive",
            "transport": self.config.transport,
            "uptimeSec": round(
                _time.monotonic() - self._started_monotonic, 3
            ),
            "eventsIngested": ingested,
            "compaction": {
                "apps": len(per_app),
                "segments": sum(s["segments"] for s in per_app.values()),
                "compactedEvents": sum(
                    s["segmentEvents"] for s in per_app.values()
                ),
                "lastCompactionMs": max(
                    (s["lastCompactionMs"] for s in per_app.values()),
                    default=0,
                ),
            },
        }
        if self.config.attribution:
            from predictionio_tpu.workflow.quality import get_attribution

            # the online feedback join (cross-app aggregate: version
            # labels are engine-instance ids, not app data)
            out["attribution"] = get_attribution().stats()
        key = (query or {}).get("accessKey")
        if key:
            access_key = self._lookup_access_key(key)
            if access_key is not None:
                app = self.storage.get_meta_data_apps().get(access_key.appid)
                s = per_app.get(app.name) if app else None
                if s is not None:
                    out["appCompaction"] = {
                        "app": app.name,
                        "segments": s["segments"],
                        "compactedEvents": s["segmentEvents"],
                        "compactedFraction": round(
                            s["compactedFraction"], 6
                        ),
                        "lastCompactionMs": s["lastCompactionMs"],
                    }
        return out

    # --- event handlers ---

    def _insert(
        self, app_id, channel_id, event: Event, route: str = "single"
    ) -> Tuple[int, Any]:
        event_id = self._events.insert(event, app_id, channel_id)
        self.plugin_context.notify_sniffers(app_id, channel_id, event)
        self._m_ingested.labels(route=route).inc()
        self._notify_commit(app_id, channel_id, (event,))
        result = (201, {"eventId": event_id})
        if self.config.stats:
            self.stats.bookkeeping(app_id, result[0], event)
        return result

    # reference EventServer.scala:161 ("Batch request must have less
    # than or equal to 50 events")
    MAX_BATCH_EVENTS = 50

    def _post_batch(
        self, app_id, channel_id, body, headers=None
    ) -> Tuple[int, Any]:
        """Reference batch route (EventServer.scala:161-233): a JSON
        array of up to 50 events, answered 200 with one status object
        per slot — 201 + eventId on success, 400/403 + message on a
        per-event failure (one bad event never fails its batchmates).
        All parseable, unblocked events of the request are handed to the
        store as ONE ``insert_batch`` — the storage tier's group-commit
        unit, so the whole slice is one transaction per shard instead of
        50 commits."""
        return self._traced_http(
            "http:POST /batch/events.json",
            headers,
            lambda: self._post_batch_inner(app_id, channel_id, body),
        )

    def _traced_http(self, name, headers, fn) -> Tuple[int, Any]:
        """Ingest-entry trace wrapper for CLIENT-SUPPLIED trace ids
        (``X-PIO-Trace-Id``): make the trace ambient under an
        ``insert`` span — the group-commit committer and the
        storage-gateway RPC client pick it up from there — and record
        the entry span when the handler returns. Untraced requests skip
        tracing entirely: per-event span recording would put the shared
        ring-buffer lock on the write hot path and flood the bounded
        ring, evicting the requests an operator deliberately traced
        (the storage gateway applies the same guard)."""
        import time as _time

        if not (headers and headers.get(_tracing.TRACE_HEADER.lower())):
            return fn()
        tctx, inbound = _tracing.from_headers(headers)
        t0 = _time.time()
        status = 500
        try:
            with _tracing.use(tctx), _tracing.span("insert"):
                result = fn()
            status = result[0]
            return result
        finally:
            _tracing.record_span(
                name, tctx.trace_id, span_id=tctx.span_id,
                parent_id=inbound, start_s=t0,
                duration_s=_time.time() - t0, attrs={"status": status},
            )

    def _post_batch_inner(self, app_id, channel_id, body) -> Tuple[int, Any]:
        try:
            payload = json.loads((body or b"").decode("utf-8"))
        except (json.JSONDecodeError, UnicodeDecodeError) as e:
            return _message(400, str(e))
        if not isinstance(payload, list):
            return _message(400, "Request body must be a JSON array.")
        if len(payload) > self.MAX_BATCH_EVENTS:
            return _message(
                400,
                "Batch request must have less than or equal to "
                f"{self.MAX_BATCH_EVENTS} events",
            )
        results: list = []
        pending: list = []  # (slot, event) surviving parse + blockers
        for item in payload:
            try:
                if not isinstance(item, dict):
                    raise EventValidationError(
                        "each batch entry must be a JSON object"
                    )
                event = Event.from_json(item)
            except EventValidationError as e:
                results.append({"status": 400, "message": str(e)})
                continue
            try:
                self.plugin_context.run_blockers(app_id, channel_id, event)
            except Exception as e:  # an input blocker rejected the event
                results.append({"status": 403, "message": str(e)})
                continue
            results.append(None)
            pending.append((len(results) - 1, event))
        if pending:
            try:
                event_ids = self._events.insert_batch(
                    [e for _, e in pending], app_id, channel_id
                )
                failed: frozenset = frozenset()
            except StorageSaturatedError as e:
                # NOTHING was admitted (the storage layer only raises
                # this when no slice was enqueued): the whole batch is
                # safe to retry after backoff (unlike PartialBatchError
                # below)
                return _saturated(e)
            except PartialBatchError as e:
                # some shard slices committed, others did not — report
                # per-event outcomes so the client retries ONLY the
                # failed slots (a blanket 500 would make it re-post the
                # committed slice under fresh ids). retry_after_s marks
                # the failures as capacity refusals: those slots answer
                # 503 (retry after backoff), not 500
                event_ids, failed = e.event_ids, e.failed_ids
                if e.retry_after_s is not None:
                    failed_result = {
                        "status": 503,
                        "message": (
                            "storage saturated; retry this event after "
                            f"~{max(1, int(round(e.retry_after_s)))}s"
                        ),
                    }
                else:
                    failed_result = {
                        "status": 500,
                        "message": "event failed to commit; retry this event",
                    }
            committed = []
            for (slot, event), event_id in zip(pending, event_ids):
                if event_id in failed:
                    results[slot] = dict(failed_result)
                    continue
                results[slot] = {"status": 201, "eventId": event_id}
                self._m_ingested.labels(route="batch").inc()
                committed.append(event)
                self.plugin_context.notify_sniffers(app_id, channel_id, event)
                if self.config.stats:
                    self.stats.bookkeeping(app_id, 201, event)
            self._notify_commit(app_id, channel_id, committed)
        return 200, results

    def _post_event(
        self, app_id, channel_id, body, headers=None
    ) -> Tuple[int, Any]:
        return self._traced_http(
            "http:POST /events.json",
            headers,
            lambda: self._post_event_inner(app_id, channel_id, body),
        )

    def _post_event_inner(self, app_id, channel_id, body) -> Tuple[int, Any]:
        try:
            payload = json.loads((body or b"").decode("utf-8"))
            event = Event.from_json(payload)
        except (json.JSONDecodeError, UnicodeDecodeError, EventValidationError) as e:
            return _message(400, str(e))
        try:
            self.plugin_context.run_blockers(app_id, channel_id, event)
        except Exception as e:  # an input blocker rejected the event
            return _message(403, str(e))
        try:
            return self._insert(app_id, channel_id, event)
        except StorageSaturatedError as e:
            return _saturated(e)

    def _find_events(self, app_id, channel_id, query) -> Tuple[int, Any]:
        try:
            start_time = (
                parse_iso8601(query["startTime"]) if "startTime" in query else None
            )
            until_time = (
                parse_iso8601(query["untilTime"]) if "untilTime" in query else None
            )
            limit = int(query.get("limit", DEFAULT_LIMIT))
            reversed_ = query.get("reversed", "false").lower() == "true"
        except (ValueError, TypeError) as e:
            return _message(400, str(e))
        event_name = query.get("event")
        events = list(
            self._events.find(
                app_id=app_id,
                channel_id=channel_id,
                start_time=start_time,
                until_time=until_time,
                entity_type=query.get("entityType"),
                entity_id=query.get("entityId"),
                event_names=[event_name] if event_name else None,
                target_entity_type=query.get("targetEntityType", UNSET),
                target_entity_id=query.get("targetEntityId", UNSET),
                limit=None if limit == -1 else limit,
                reversed=reversed_,
            )
        )
        if not events:
            return _message(404, "Not Found")
        return 200, [e.to_json() for e in events]

    # --- webhooks (reference api/Webhooks.scala:43-151) ---

    def _webhook_json(
        self, app_id, channel_id, web, method, body
    ) -> Tuple[int, Any]:
        connector = JSON_CONNECTORS.get(web)
        if connector is None:
            return _message(404, f"webhooks connection for {web} is not supported.")
        if method == "GET":
            return 200, {"message": "Ok"}
        if method != "POST":
            return _message(405, "Method not allowed.")
        try:
            payload = json.loads((body or b"").decode("utf-8"))
            event = to_event(connector, payload)
        except (
            json.JSONDecodeError,
            UnicodeDecodeError,
            ConnectorException,
            EventValidationError,
        ) as e:
            return _message(400, str(e))
        try:
            return self._insert(app_id, channel_id, event, route="webhook")
        except StorageSaturatedError as e:
            return _saturated(e)

    def _webhook_form(
        self, app_id, channel_id, web, method, form
    ) -> Tuple[int, Any]:
        connector = FORM_CONNECTORS.get(web)
        if connector is None:
            return _message(404, f"webhooks connection for {web} is not supported.")
        if method == "GET":
            return 200, {"message": "Ok"}
        if method != "POST":
            return _message(405, "Method not allowed.")
        try:
            event = to_event(connector, form or {})
        except (ConnectorException, EventValidationError) as e:
            return _message(400, str(e))
        try:
            return self._insert(app_id, channel_id, event, route="webhook")
        except StorageSaturatedError as e:
            return _saturated(e)


class EventServer:
    """HTTP wrapper (reference EventServerActor + Run, EventServer.scala:471-531).

    With the default async transport, every route is offloaded to a
    bounded handler pool and the event loop awaits the returned future:
    an idle keep-alive connection costs no thread, and the threads that
    do exist are parked exactly where the work is (the group-commit
    COMMIT wait), which is what the committer wants — many requests
    queued inside one flush window."""

    def __init__(
        self,
        storage: Optional[Storage] = None,
        config: Optional[EventServerConfig] = None,
        plugin_context: Optional[EventServerPluginContext] = None,
    ):
        self.config = config or EventServerConfig()
        self.api = EventAPI(storage, self.config, plugin_context)
        # background compactor: seals cold row ranges into columnar
        # segments while the server ingests (no-op for backends without
        # the tier). Owned here so shutdown stops it with the server.
        self.compactor = None
        if self.config.compact:
            from predictionio_tpu.data.storage.segments import (
                SegmentCompactor,
            )

            if SegmentCompactor.supported(self.api.storage):
                self.compactor = SegmentCompactor(
                    self.api.storage,
                    interval_s=self.config.compact_interval_s,
                )
        self._pool: Optional[concurrent.futures.ThreadPoolExecutor] = None
        if self.config.transport == "async":
            self._pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=max(1, self.config.handler_threads),
                thread_name_prefix="evhandler",
            )
            pool = self._pool

            def fn(method, path, query, body, form=None, headers=None):
                if path == "/healthz" and method == "GET":
                    # liveness answers INLINE on the loop (pure dict
                    # build, non-blocking): a handler pool saturated
                    # with parked COMMIT waits must not read as "dead"
                    return self.api.handle(
                        method, path, query, body, form, headers
                    )
                return pool.submit(
                    self.api.handle, method, path, query, body, form,
                    headers,
                )
        else:
            fn = self.api.handle
        self._http = make_http_server(
            fn, self.config.ip, self.config.port, "Event Server",
            reuse_port=self.config.reuse_port,
            transport=self.config.transport,
        )

    @property
    def port(self) -> int:
        return self._http.port

    def start(self) -> "EventServer":
        self._http.start()
        if self.compactor is not None:
            self.compactor.start()
        return self

    def serve_forever(self) -> None:
        if self.compactor is not None:
            self.compactor.start()
        self._http.serve_forever()

    def shutdown(self) -> None:
        if self.compactor is not None:
            self.compactor.close()
        self._http.shutdown()
        if self._pool is not None:
            # wait=False: a handler parked on a wedged COMMIT must not
            # hang undeploy (same contract as the batching executor)
            self._pool.shutdown(wait=False)


def create_event_server(
    config: Optional[EventServerConfig] = None,
    storage: Optional[Storage] = None,
) -> EventServer:
    """Reference EventServer.createEventServer (EventServer.scala:502-522).
    Plugins are auto-discovered at launch (the reference's ServiceLoader
    pass, EventServerPluginContext.scala:26-49)."""
    return EventServer(
        storage=storage,
        config=config,
        plugin_context=EventServerPluginContext.discover(),
    )
