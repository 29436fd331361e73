"""The engine (query) server: deployed-model REST serving.

Capability parity with the reference CreateServer
(core/src/main/scala/io/prediction/workflow/CreateServer.scala):

  GET  /               -> HTML status page           (:444-471)
  GET  /status.json    -> the same data as JSON (addition)
  POST /queries.json   -> the serving hot path        (:473-624)
  GET  /reload         -> hot-swap to latest trained instance (:626-632)
  GET  /stop           -> undeploy                    (:634-642)
  GET  /plugins.json   -> plugin descriptions         (:647-668)
  GET  /plugins/<type>/<name>/... -> plugin REST      (:670-691)

Deploy path parity: load the EngineInstance + its pickled models from
MODELDATA, ``engine.prepare_deploy`` (re-train sharded models / resolve
PersistentModel manifests), instantiate algorithms + serving via doer
(reference createServerActorWithEngine :197-250). The feedback loop posts
``predict`` events (entityType ``pio_pr``, fresh 64-char prId) back to the
Event Server (:509-579), and per-request bookkeeping tracks
requestCount / avg / last serving seconds (:586-593).

TPU-first divergence (deliberate): where the reference predicts per
request, sequentially per algorithm (:497-500, "TODO: Parallelize"),
queries here flow through a **micro-batching executor** — a request
that finds a serve slot free is dispatched at once, and the requests
that arrive while the slots are taken are served together as ONE
batched device predict (`BaseAlgorithm.batch_predict`, e.g. a single
[B, k] x [k, n_items] MXU matmul + top_k for the recommendation engine)
as soon as a slot frees, so the batch grows with the load by itself and
throughput scales with batch size instead of request count. No timer
and no window: an idle server adds no wait. What an algorithm declares
to depend on one query alone (``BaseAlgorithm.prepare_query``: the
e-commerce engine's read of the user's history) starts when the query
arrives, on a small pool beside the batch ahead, and not in the serial
path of the query's own batch.
"""

from __future__ import annotations

import collections
import concurrent.futures
import copy
import dataclasses
import datetime as _dt
import html
import itertools
import json
import logging
import queue
import secrets
import string
import threading
import time
import urllib.parse
import urllib.request
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

from predictionio_tpu.api.engine_plugins import (
    EngineServerPlugin,
    EngineServerPluginContext,
)
from predictionio_tpu.api.aio_http import TRANSPORTS, make_http_server
from predictionio_tpu.controller.engine import Engine, EngineParams
from predictionio_tpu.data.storage import Storage, get_storage
from predictionio_tpu.utils import compilation_cache as _cc
from predictionio_tpu.utils import device_ledger as _ledger
from predictionio_tpu.utils import health as _health
from predictionio_tpu.utils import metrics as _metrics
from predictionio_tpu.utils import tracing as _tracing
from predictionio_tpu.utils.serialize import loads_model
from predictionio_tpu.workflow import experiment as _experiment
from predictionio_tpu.workflow import quality as _quality
from predictionio_tpu.workflow.context import WorkflowContext
from predictionio_tpu.workflow.workflow_params import WorkflowParams

logger = logging.getLogger(__name__)


def _version_of(deployed) -> str:
    """The model-version label of a deployed engine: the persisted
    round's engine instance id (test doubles without one label as
    'unknown')."""
    inst = getattr(deployed, "engine_instance", None)
    return str(getattr(inst, "id", None) or "unknown")

_ALPHANUMERIC = string.ascii_letters + string.digits

# byte -> alphanumeric translation table: one 64-byte CSPRNG read per
# prId instead of 64 secrets.choice draws (each a fresh urandom-backed
# randbelow) on the feedback hot path. The %62 fold weights the first
# 256%62=8 characters 5/256 vs 4/256 — ~0.04 bit of entropy per char
# below uniform, irrelevant for a 64-char correlation id.
_PR_ID_TABLE = bytes(
    ord(_ALPHANUMERIC[b % len(_ALPHANUMERIC)]) for b in range(256)
)


def _gen_pr_id() -> str:
    """64-char alphanumeric prId (reference CreateServer.scala:525)."""
    return secrets.token_bytes(64).translate(_PR_ID_TABLE).decode("ascii")


@dataclasses.dataclass
class ServerConfig:
    """Reference ServerConfig (CreateServer.scala:80-96)."""

    ip: str = "localhost"
    port: int = 8000
    engine_instance_id: Optional[str] = None
    feedback: bool = False
    event_server_ip: str = "localhost"
    event_server_port: int = 7070
    access_key: Optional[str] = None
    batch: str = ""
    # micro-batching (TPU addition): the hard cap on one served batch.
    # When a batch closes is not configured: it closes the moment a
    # serve slot (pipeline_depth below) is free, over whatever queued
    # while the slots were taken (_BatchingExecutor).
    max_batch: int = 128
    # Daily self upgrade check (reference CreateServer.scala:253-260 runs
    # UpgradeCheckRunner every 1 day): best-effort, on a background
    # thread, never blocks serving; status.json reports the last result.
    # 0 disables. The first check waits initial_delay so short-lived
    # servers (tests, benches) never place the outbound call at all.
    upgrade_check_interval_s: float = 86400.0
    upgrade_check_initial_delay_s: float = 10.0
    # Batches allowed in flight at once: 2 = double-buffering, so batch
    # k+1's device dispatch overlaps batch k's result fetch. CONTRACT:
    # depth > 1 means serve_batch (supplement -> batch_predict -> serve)
    # runs CONCURRENTLY on the deployed engine, so controller code must
    # not mutate shared state without locking. The default is 1 — the
    # reference serves strictly serially (CreateServer.scala:473-624),
    # and a user engine with mutable predict-time state (a cache dict, a
    # lazily-built index) is legal under that API and would silently race
    # at depth 2. The packaged templates are pure: deploy them with
    # `--pipeline-depth 2` to overlap device dispatch with result fetch.
    # (An algorithm that defines `prepare_query` has THAT step, and the
    # serving's `supplement` before it, run beside serve_batch at any
    # depth: defining it is the opt-in, BaseAlgorithm's contract.)
    pipeline_depth: int = 1
    # REST transport: "async" = the event-loop frontend (api/aio_http.py,
    # in-flight queries are queue entries awaited as futures — the
    # collector can fill max_batch-sized device batches under load);
    # "threaded" = the stdlib thread-per-connection fallback.
    transport: str = "async"
    # feedback posts queue here when the event server lags; beyond this
    # the OLDEST pending post is dropped (and counted in status.json's
    # feedbackQueueDropped) — a down event server must not grow the
    # queue without bound
    feedback_queue_max: int = 4096
    # bind with SO_REUSEPORT so several engine-server PROCESSES share
    # one port (the `pio deploy --workers` fleet; the kernel balances
    # accepted connections across workers)
    reuse_port: bool = False
    # comma-separated jax device indices this server's prepared serving
    # state pins to (e.g. "0" for one chip per SO_REUSEPORT worker,
    # "0,1" for a 2-device mesh slice). None = the full default mesh.
    # The pinned mesh is what prepare_serving row-shards the resident
    # item factors over (ops/retrieval.py).
    serving_devices: Optional[str] = None
    # prediction capture (workflow/quality.py): every Nth served query
    # is recorded into the bounded process-global capture ring —
    # (query, result ids/scores, version, trace id) — dumped at the
    # gated GET /debug/predictions.json and replayable via `pio
    # replay`. 1 = every query, 0 disables capture entirely.
    capture_sample: int = 1
    # how many displaced DeployedEngines a /reload swap keeps prepared
    # (warm, factors resident) in the server's LRU — the reference's
    # multi-variant admin tier, and the promotion pipeline's instant-
    # rollback store. Evicted entries drain (last in-flight batch
    # resolves) and then release their device buffers. 0 = drain +
    # release immediately on swap.
    retained_states: int = 1

    def __post_init__(self):
        if self.feedback and not self.access_key:
            raise ValueError(
                "feedback loop requires access_key "
                "(reference CreateServer.scala:139-143)"
            )
        if self.transport not in TRANSPORTS:
            raise ValueError(
                f"unknown transport {self.transport!r} "
                f"(expected one of {TRANSPORTS})"
            )


def _mesh_from_device_spec(spec: str):
    """A 1-D data mesh over the named jax device indices ("0" or
    "0,2,3") of the devices THIS process can see: each `pio deploy
    --workers` worker pins its prepared serving state to its own device
    or mesh slice (on a TPU the supervisor narrows what a worker sees
    to its own chips before it starts, tools/cli.py)."""
    import jax

    from predictionio_tpu.parallel.mesh import make_mesh

    idxs = [int(p) for p in str(spec).split(",") if p.strip() != ""]
    devs = jax.devices()
    bad = [i for i in idxs if not 0 <= i < len(devs)]
    if not idxs or bad:
        raise ValueError(
            f"serving_devices {spec!r} names invalid device indices "
            f"{bad} (have {len(devs)} devices)"
        )
    return make_mesh({"data": len(idxs)}, [devs[i] for i in idxs])


class DeployedEngine:
    """Immutable serving state for one engine instance: instantiated
    algorithms + serving + deployable models."""

    def __init__(
        self,
        engine: Engine,
        engine_params: EngineParams,
        engine_instance,
        models: List[Any],
        ledger_scope: Optional["_ledger.LedgerScope"] = None,
    ):
        self.engine = engine
        self.engine_params = engine_params
        self.engine_instance = engine_instance
        _, _, self.algorithms, self.serving = engine.make_components(engine_params)
        self.models = models
        if len(self.models) != len(self.algorithms):
            raise ValueError(
                f"{len(self.models)} models for {len(self.algorithms)} algorithms"
            )
        # HBM residency ledger scope: device buffers registered during
        # this instance's prepare/warm are grouped under its engine-
        # instance id, so release() can assert THEY reached zero — even
        # with a same-version twin resident (the bare-/reload case).
        # from_storage hands in the scope that already covers
        # prepare_deploy; direct construction gets a fresh one.
        self._ledger_scope = ledger_scope or _ledger.get_ledger().scope(
            str(getattr(engine_instance, "id", None) or "unknown")
        )
        # compile serving executables before taking traffic (cold compiles
        # cost seconds and would land on the first unlucky requests);
        # persist them so the NEXT deploy of this engine skips the
        # compiles entirely
        from predictionio_tpu.utils.compilation_cache import (
            ensure_compilation_cache,
        )

        ensure_compilation_cache()
        with self._ledger_scope.activate():
            for algo, model in zip(self.algorithms, self.models):
                algo.warm(model)
        # in-flight batch accounting: the promotion pipeline's drain
        # stage waits on this before freeing the displaced instance's
        # device-resident serving state (release_serving). The condition
        # also serializes release() against new serve_batch entrants, so
        # a straggler that races past a swap either runs on the intact
        # device state or — after release — on the algorithms' host
        # fallback path, never on half-freed buffers.
        self._inflight = 0
        self._inflight_cond = threading.Condition()
        self._released = False
        # which algorithms define the per-query preparation step
        # (BaseAlgorithm.prepare_query): what the executor reads to
        # decide whether a query of this engine is prepared at arrival
        self._prepares = tuple(
            callable(getattr(algo, "prepare_query", None))
            for algo in self.algorithms
        )
        self.prepares_queries = any(self._prepares)

    @classmethod
    def from_storage(
        cls,
        engine: Engine,
        storage: Optional[Storage] = None,
        engine_instance_id: Optional[str] = None,
        engine_id: Optional[str] = None,
        engine_version: Optional[str] = None,
        engine_variant: Optional[str] = None,
        ctx: Optional[WorkflowContext] = None,
        workflow_params: Optional[WorkflowParams] = None,
    ) -> "DeployedEngine":
        """Reference createServerActorWithEngine (CreateServer.scala:197-250):
        resolve the instance (given id, or latest COMPLETED — scoped to
        (engine_id, engine_version, engine_variant) when given, as the
        reference Console.deploy does via getLatestCompleted), deserialize
        its models, prepare_deploy."""
        storage = storage or get_storage()
        ctx = ctx or WorkflowContext(mode="Serving", storage=storage)
        instances = storage.get_meta_data_engine_instances()
        if engine_instance_id is not None:
            instance = instances.get(engine_instance_id)
            if instance is None:
                raise ValueError(
                    f"engine instance {engine_instance_id!r} does not exist"
                )
        elif engine_id is not None:
            instance = instances.get_latest_completed(
                engine_id, engine_version or "", engine_variant or ""
            )
            if instance is None:
                raise ValueError(
                    f"no COMPLETED engine instance for engine {engine_id!r} "
                    f"version {engine_version!r} variant {engine_variant!r}; "
                    "run train first"
                )
        else:
            completed = [
                i for i in instances.get_all() if i.status == "COMPLETED"
            ]
            if not completed:
                raise ValueError(
                    "no COMPLETED engine instance found; run train first"
                )
            instance = max(completed, key=lambda i: i.start_time)
        engine_params = engine.engine_instance_to_engine_params(instance)
        blob = storage.get_model_data_models().get(instance.id)
        if blob is None:
            raise ValueError(
                f"no persisted models for engine instance {instance.id!r}"
            )
        persisted = loads_model(blob.models)
        # the ledger scope opens BEFORE prepare_deploy: prepare_serving
        # parks the resident factors/masks on device in there, and those
        # registrations must carry this instance's owner label
        scope = _ledger.get_ledger().scope(str(instance.id))
        with scope.activate():
            models = engine.prepare_deploy(
                ctx,
                engine_params,
                instance.id,
                persisted,
                workflow_params or WorkflowParams(),
            )
        return cls(
            engine, engine_params, instance, models, ledger_scope=scope
        )

    # --- the serving pipeline over one coalesced batch ---

    def prepare_query(self, query: Any) -> Tuple[Any, tuple]:
        """One query's preparation, for an engine with an algorithm that
        defines the step: ``(supplemented query, one prepared value an
        algorithm)``, None for an algorithm without the step. It depends
        on the query alone and touches no device state, so the executor
        runs it on its prepare pool when the query arrives, beside
        whatever batch is being served."""
        supplemented = self.serving.supplement(query)
        return supplemented, tuple(
            algo.prepare_query(model, supplemented) if prepares else None
            for algo, model, prepares in zip(
                self.algorithms, self.models, self._prepares
            )
        )

    def serve_batch(
        self, queries: Sequence[Any], prepared: Optional[Sequence] = None
    ) -> List[Any]:
        """supplement each -> ONE batch_predict per algorithm -> serve each
        with its original query (reference Engine.scala:769-810 eval path
        applies the same supplement/batch/serve order).

        ``prepared`` is what the executor hands an engine that prepares
        its queries at arrival: ``prepare_query``'s result for each
        query, in the batch's order. Those queries were supplemented
        there and are not supplemented again; each algorithm that
        defines the step gets its values as ``batch_predict``'s third
        argument. Without it (an engine with no such step, ``pio
        replay``, a test) the path is the one above, and an algorithm
        with the step prepares inline.

        May be called concurrently (up to ServerConfig.pipeline_depth
        batches in flight): algorithms/serving with mutable predict-time
        state must lock it or deploy with pipeline_depth=1."""
        with self._inflight_cond:
            self._inflight += 1
        try:
            with _tracing.stage(_tracing.HOST_PREP):
                if prepared is None:
                    supplemented = [
                        self.serving.supplement(q) for q in queries
                    ]
                else:
                    supplemented = [p[0] for p in prepared]
                indexed = list(enumerate(supplemented))
            per_algo: List[Dict[int, Any]] = [
                dict(
                    algo.batch_predict(
                        model, indexed, [p[1][k] for p in prepared]
                    )
                    if prepared is not None and self._prepares[k]
                    else algo.batch_predict(model, indexed)
                )
                for k, (algo, model) in enumerate(
                    zip(self.algorithms, self.models)
                )
            ]
            with _tracing.stage(_tracing.BUILD):
                return [
                    self.serving.serve(q, [pa[i] for pa in per_algo])
                    for i, q in enumerate(queries)
                ]
        finally:
            with self._inflight_cond:
                self._inflight -= 1
                self._inflight_cond.notify_all()

    # --- drain/release: the promotion pipeline's displaced-instance
    # lifecycle (free resident device factors only after the last
    # in-flight batch resolves) ---

    @property
    def inflight(self) -> int:
        with self._inflight_cond:
            return self._inflight

    @property
    def released(self) -> bool:
        return self._released

    def drain(self, timeout_s: float, on_progress=None) -> bool:
        """Wait (bounded) for every in-flight serve_batch to resolve.
        ``on_progress`` fires whenever the in-flight count moves — the
        promotion pipeline feeds it the watchdog heartbeat's ``beat``,
        so a drain that is MAKING progress never reads as stalled while
        a wedged one degrades /readyz once the deadline passes."""
        deadline = time.monotonic() + max(0.0, timeout_s)
        with self._inflight_cond:
            last = self._inflight
            while self._inflight > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._inflight_cond.wait(min(0.2, remaining))
                if self._inflight != last:
                    last = self._inflight
                    if on_progress is not None:
                        on_progress()
        return True

    def release(self, timeout_s: float = 0.0) -> bool:
        """Free the device-resident serving state (each algorithm's
        ``release_serving``) once nothing is in flight; returns whether
        it released. The hooks run UNDER the in-flight condition, so a
        serve_batch racing in behind the release observes the nulled
        device state (and takes the host fallback path) — never a
        half-freed buffer. A straggler that keeps the state wedged past
        ``timeout_s`` blocks the release: its buffers are freed by
        refcount when it finally resolves, never underneath it."""
        deadline = time.monotonic() + max(0.0, timeout_s)
        with self._inflight_cond:
            while self._inflight > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._inflight_cond.wait(min(0.2, remaining))
            if self._released:
                return True
            self._released = True
            for algo, model in zip(self.algorithms, self.models):
                try:
                    algo.release_serving(model)
                except Exception:
                    logger.exception(
                        "release_serving failed for %s", type(algo).__name__
                    )
        # the monitored release invariant (the PR 13 leak class): every
        # device buffer this instance registered during prepare/warm
        # must be back to zero now — nonzero counts in
        # pio_device_ledger_leaks_total and logs, instead of silently
        # pinning HBM until the process dies. (A straggler that raced
        # past the swap rebuilds serving state OUTSIDE this scope — the
        # transient shows up as component bytes and drift, never as a
        # false leak here.)
        self._ledger_scope.check_released()
        return True

    def ledger_bytes(self) -> int:
        """Device bytes currently registered under this instance's
        ledger scope (tests + status detail)."""
        return self._ledger_scope.bytes()


def _version_children(cache: Dict[str, Dict[str, Any]], families, version):
    """{name: the family's child for ``version``} out of ``cache``,
    resolved once a version: on the serve thread a ``labels()`` call at
    every observe is time that every queued request waits for."""
    children = cache.get(version)
    if children is None:
        children = cache[version] = {
            name: family.labels(version=version)
            for name, family in families.items()
        }
    return children


class _StageTimes:
    """One request's stage boundaries, all on ``time.perf_counter``: the
    queue entry carries it for every request, and the executor fills it
    in where the work happens. ``enqueued`` → ``closed`` is the queue
    wait (the collector closed the batch that holds the request, which
    it does once a serve slot is free: the wait for the in-flight
    semaphore is in here), → ``started`` the slot wait (the pool
    hand-off alone: a serve thread still finishing the batch before),
    → ``served`` the batch's predict, charged to each request in it;
    whoever finishes the request supplies the end of ``finish``.
    ``trace`` is the request's trace context when it sent
    ``X-PIO-Trace-Id``; ``attrs`` what the predict span says of the
    batch."""

    __slots__ = ("trace", "enqueued", "closed", "started", "served", "attrs")

    def __init__(self, trace: Optional["_tracing.TraceContext"] = None):
        self.trace = trace
        self.enqueued = self.closed = self.started = self.served = 0.0
        self.attrs: Optional[Dict[str, Any]] = None

    def stages(self, end: float) -> "tuple[tuple[str, float, float], ...]":
        """(name, start, seconds) of the four per-request stages, the
        last one ending at ``end``; they tile ``enqueued`` → ``end``."""
        return (
            ("queue_wait", self.enqueued, self.closed - self.enqueued),
            ("slot_wait", self.closed, self.started - self.closed),
            ("predict", self.started, self.served - self.started),
            ("finish", self.served, end - self.served),
        )

    def record_spans(self, end: float) -> None:
        """The executor's share of a traced request's chain: ``batch``
        under the http span, the four stages under ``batch``. Durations
        are perf_counter differences; only ``startMs`` is wall time."""
        if self.trace is None or not self.served:
            return
        wall_offset = time.time() - time.perf_counter()
        batch_id = _tracing.new_span_id()
        for name, start, seconds in self.stages(end):
            _tracing.record_span(
                name, self.trace.trace_id, parent_id=batch_id,
                start_s=start + wall_offset, duration_s=seconds,
                attrs=self.attrs if name == "predict" else None,
            )
        _tracing.record_span(
            "batch", self.trace.trace_id, span_id=batch_id,
            parent_id=self.trace.span_id,
            start_s=self.enqueued + wall_offset,
            duration_s=end - self.enqueued,
        )


class _Preparations:
    """Per-query preparation at arrival, for deployed engines whose
    algorithm defines ``prepare_query`` (BaseAlgorithm): the part of a
    query's work that depends on that query alone starts when the query
    is enqueued, on this pool, while the serve thread is still inside
    the batch ahead (mostly blocked on the device, the interpreter lock
    released), instead of in the serial path of the query's own batch,
    where every queued request waits for it too. An executor creates
    one with the first such query; engines without the step never do.

    The pool's size is fixed: a preparation is a store read of about a
    millisecond, arrivals are spread over the batch ahead, and whatever
    has not started when its batch does is taken over by the serve
    thread, so a small pool bounds the Python that competes with the
    serve thread for the interpreter lock and nothing waits on it."""

    WORKERS = 2

    def __init__(self):
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=self.WORKERS, thread_name_prefix="prepare"
        )
        registry = _metrics.get_registry()
        self._families = {
            "seconds": registry.histogram(
                "pio_serving_prepare_seconds",
                "One query's preparation (supplement and the "
                "algorithms' prepare_query), wherever it ran: on the "
                "prepare pool when the query arrived or on the serve "
                "thread inside its batch, by model version",
                labels=("version",),
                buckets=_metrics.LATENCY_BUCKETS_S,
            ),
            "late": registry.counter(
                "pio_serving_prepare_late_total",
                "Queries whose preparation was not finished when their "
                "micro-batch started: waited for, taken over by the "
                "serve thread or computed again after an error, by "
                "model version",
                labels=("version",),
            ),
        }
        self._children: Dict[str, Dict[str, Any]] = {}

    def start(
        self, deployed: DeployedEngine, query: Any
    ) -> "concurrent.futures.Future":
        return self._pool.submit(self._run, deployed, query)

    def _run(self, deployed: DeployedEngine, query: Any) -> Any:
        t0 = time.perf_counter()
        try:
            with _tracing.annotation("prepare"):
                return deployed.prepare_query(query)
        finally:
            _version_children(
                self._children, self._families, _version_of(deployed)
            )["seconds"].observe(time.perf_counter() - t0)

    def collect(
        self, dep: DeployedEngine, items, outcomes: List[tuple]
    ) -> Tuple[list, list]:
        """On the serve thread, as a batch starts: its prepared values
        in its order. A preparation still queued is cancelled and
        computed here; one that is running is waited for (charged to
        the batch's store-read stage: a preparation is a read before
        anything else); one that raised is logged and computed again
        here. A query whose preparation raises here too gets that error
        as its outcome and leaves the batch: returns the items that
        stay and their values."""
        kept, values, late = [], [], 0
        pendings = [item[4] for item in items]
        ready = [pending.done() for pending in pendings]
        # entered once a batch, so that the family holds one sample a
        # served batch whether or not the batch had to wait
        with _tracing.stage(_tracing.STORE_READ):
            running = [p for p in pendings if not (p.done() or p.cancel())]
            if running:
                concurrent.futures.wait(running)
        for item, pending, on_time in zip(items, pendings, ready):
            value = None
            if not pending.cancelled():
                try:
                    value = pending.result()
                except Exception:
                    logger.exception(
                        "preparing query %r failed; computing it again "
                        "inside its batch", item[1],
                    )
            late += not (on_time and value is not None)
            if value is None:
                try:
                    value = self._run(dep, item[1])
                except Exception as e:
                    outcomes.append((item[2], e, None))
                    continue
            kept.append(item)
            values.append(value)
        if late:
            _version_children(
                self._children, self._families, _version_of(dep)
            )["late"].inc(late)
        return kept, values

    def close(self) -> None:
        self._pool.shutdown(wait=False, cancel_futures=True)


class _BatchingExecutor:
    """Coalesces concurrent requests into device-sized batches.

    Requests enqueue (query, future); one collector thread waits for a
    request, then for one of the ``pipeline_depth`` in-flight slots,
    then takes whatever else is queued (up to ``max_batch``) and hands
    that batch to the serve pool. There is no timer: on an idle server
    a request is dispatched alone and at once, and on a busy one the
    batch is exactly what arrived while the slots were held, so it
    grows with the load and with the length of ``serve_batch`` by
    itself. ``submit_nowait`` returns the
    ``concurrent.futures.Future`` directly: the event-loop frontend
    awaits it, so an in-flight query is a queue entry, not a parked OS
    thread, and the collector can actually accumulate ``max_batch``-
    sized device batches under load. ``submit`` is the blocking wrapper
    the threaded transport (and in-process callers) use.

    Where a deployed engine's algorithm defines ``prepare_query``
    (``DeployedEngine.prepares_queries``), ``submit_nowait`` also starts
    that query's preparation at once on the ``prepare`` pool
    (``_Preparations``), the queue entry carries the pending result, and
    the serve thread hands ``serve_batch`` the batch's values in its
    order: what depends on one query alone runs while the query waits
    in the queue, not in its batch's serial path. A preparation belongs
    to the deployed engine it was started under (batches are grouped by
    deployed engine); a cancelled request's is dropped. An engine
    without the step is served by the code above alone: no pool, no
    future, ``serve_batch(queries)``.

    The default depth is 1: strictly serial serving, the reference's
    contract (CreateServer.scala:473-624), safe for engines with mutable
    predict-time state. Depth 2 (opt-in, see ServerConfig.pipeline_depth)
    double-buffers: while batch k's result fetch is crossing
    host<->device, batch k+1 already dispatched and batch k+2
    accumulates in the queue until a slot frees — the device never
    idles waiting on a fetch. Never more than ``pipeline_depth``
    ``serve_batch`` calls run at once.
    """

    _STOP = object()  # collector-thread shutdown sentinel

    def __init__(self, max_batch: int, pipeline_depth: int = 1):
        self.max_batch = max_batch
        self.pipeline_depth = max(1, pipeline_depth)
        self._queue: "queue.Queue" = queue.Queue()
        self._worker: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        self._closed = False
        self._inflight = threading.Semaphore(self.pipeline_depth)
        self._serve_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=self.pipeline_depth, thread_name_prefix="serve"
        )
        # created with the first query of an engine that prepares its
        # queries at arrival; None for ever with any other engine
        self._preparations: Optional[_Preparations] = None
        # collector batch-size accounting (served-group granularity, the
        # actual device batch): proves micro-batches coalesce under load.
        # The instrument is the process-global registry's mergeable
        # histogram (the /metrics family), labeled by the MODEL VERSION
        # the batch was served from — a /reload swap's fill profile is
        # diffable per version straight off /metrics. stats() reports
        # the all-versions delta since THIS executor was constructed.
        self._m_batch_fill = _metrics.get_registry().histogram(
            "pio_serving_batch_fill",
            "Queries per served micro-batch (the device batch size), "
            "by model version",
            labels=("version",),
            buckets=_metrics.BATCH_SIZE_BUCKETS,
        )
        # where a request's time goes inside the executor, and what one
        # batch's predict is made of for engines that bracket their
        # serving path with utils/tracing.stage. One family per stage:
        # a /metrics reader that sums a family over its label sets
        # cannot pick a stage out of a label. The executor observes
        # what it times itself, where that is cheapest: the queue wait
        # once a request on the collector thread, the slot wait and
        # predict once a BATCH for its n requests (they share both
        # durations), the batch stages once a batch; QueryAPI adds
        # ``finish``. The four request stages tile enqueue → response
        # built (_StageTimes.stages). ``immediate`` is no stage: it is
        # the counter of batches that found a slot free, kept here so
        # that its child too is resolved once a version.
        self._m_stages = {
            name: _metrics.get_registry().histogram(
                f"pio_serving_{name}_seconds",
                f"{help_}, by model version",
                labels=("version",),
                buckets=_metrics.LATENCY_BUCKETS_S,
            )
            for name, help_ in (
                ("queue_wait",
                 "Enqueue until the collector closed the micro-batch "
                 "that holds the request, which it does when a serve "
                 "slot is free"),
                ("slot_wait",
                 "Batch closed until a serve thread started it (the "
                 "pool hand-off), charged to each request in it"),
                ("predict",
                 "The micro-batch's serve_batch call, charged to each "
                 "request in it"),
                *((f"batch_{stage}",
                   f"Seconds of one served micro-batch spent in its "
                   f"{stage} stage")
                  for stage in _tracing.BATCH_STAGES),
                ("batch_unstaged",
                 "Seconds of one served micro-batch's predict that no "
                 "stage covers: predict less its stages entered at "
                 "depth 0 (a nested stage is not counted twice)"),
            )
        }
        self._m_stages["immediate"] = _metrics.get_registry().counter(
            "pio_serving_batch_immediate_total",
            "Served micro-batches whose serve slot was free when their "
            "first request was taken: the executor made them wait for "
            "nothing. Over pio_serving_batch_fill's count, the share of "
            "batches that started from an idle server, by model version",
            labels=("version",),
        )
        self._m_stage_children: Dict[str, Dict[str, Any]] = {}
        self._m_batch_bases = {
            key[0]: child.snapshot()
            for key, child in self._m_batch_fill.children()
        }
        # watchdog: a serve_batch wedged in a stuck device call
        # degrades /readyz once it overruns the deadline (executors of
        # one process share the heartbeat — either stalling is a
        # process-level routing signal); idle executors never stall
        self._hb = _health.heartbeat("serving-executor", deadline_s=120.0)

    def submit_nowait(
        self,
        deployed: DeployedEngine,
        query: Any,
        times: Optional[_StageTimes] = None,
    ) -> "concurrent.futures.Future":
        """Enqueue one query; the returned future resolves to its
        prediction (or raises its per-query error) once the micro-batch
        it rides is served. ``times`` rides the queue entry: the
        executor writes the request's stage boundaries into it, and the
        caller reads them when the future resolves. For an engine that
        prepares its queries at arrival the preparation starts here,
        and the entry carries its pending result."""
        fut: "concurrent.futures.Future" = concurrent.futures.Future()
        if times is None:
            times = _StageTimes()
        times.enqueued = time.perf_counter()
        # the closed-check and the enqueue share the lock with close()'s
        # sentinel post, so a request can never land behind _STOP in the
        # queue (its future would never resolve)
        with self._lock:
            if self._closed:
                raise RuntimeError("server is shutting down")
            if self._worker is None or not self._worker.is_alive():
                self._worker = threading.Thread(target=self._run, daemon=True)
                self._worker.start()
            pending = None
            if getattr(deployed, "prepares_queries", False):
                if self._preparations is None:
                    self._preparations = _Preparations()
                pending = self._preparations.start(deployed, query)
            self._queue.put((deployed, query, fut, times, pending))
        return fut

    def submit(self, deployed: DeployedEngine, query: Any) -> Any:
        return self.submit_nowait(deployed, query).result()

    def stats(self) -> Dict[str, Any]:
        """Served-batch accounting since this executor was constructed
        (merged across model versions): count, mean fill, bucketed size
        histogram (keys are the registry histogram's bucket upper
        bounds)."""
        snaps = []
        for key, child in self._m_batch_fill.children():
            snap = child.snapshot()
            base = self._m_batch_bases.get(key[0])
            if base is not None:
                snap = snap.delta(base)
            snaps.append(snap)
        if snaps:
            snap = _metrics.merge_snapshots(snaps)
        else:
            bounds = self._m_batch_fill.bounds
            snap = _metrics.HistogramSnapshot(
                bounds, (0,) * (len(bounds) + 1), 0.0, 0
            )
        # counts has one +Inf overflow slot beyond the finite bounds: a
        # batch larger than the last bound (max_batch is user-settable
        # past 1024) must not vanish from the histogram view
        hist = {
            int(bound): c
            for bound, c in zip(snap.bounds, snap.counts)
            if c
        }
        out = {
            "batches": snap.count,
            "queries": int(snap.sum),
            "batch_fill_mean": (snap.sum / snap.count) if snap.count else 0.0,
            "batch_size_histogram": hist,
        }
        if snap.counts[-1]:
            out["batch_size_overflow"] = snap.counts[-1]
        return out

    def close(self) -> None:
        """Stop the collector thread and release the serve-pool workers
        (a stopped/undeployed server must not leak threads for the
        process lifetime). In-flight batches finish; later submits fail."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            worker = self._worker
            self._queue.put(self._STOP)
        if worker is not None and worker.is_alive():
            worker.join(timeout=10.0)
        # wait=False so a wedged serve_batch (a stuck device call)
        # cannot hang THIS call forever, mirroring the bounded collector
        # join above. The guarantee is only that close() returns: a truly
        # wedged batch still blocks its request threads (their slots
        # never resolve) and, since pool workers are non-daemon, still
        # blocks interpreter exit — same as the reference's in-flight
        # Futures on undeploy.
        self._serve_pool.shutdown(wait=False)
        if self._preparations is not None:
            self._preparations.close()

    def _run(self) -> None:
        while True:
            with _tracing.annotation("wait_request"):
                first = self._queue.get()
            if first is self._STOP:
                return
            # the slot before the batch closes: whatever arrives while
            # pipeline_depth batches are in flight joins THIS batch. A
            # slot that is free already means the executor imposes no
            # wait at all on the request
            with _tracing.annotation("slot_wait"):
                immediate = self._inflight.acquire(blocking=False)
                if not immediate:
                    self._inflight.acquire()
            batch = [first]
            with _tracing.annotation("collect"):
                while len(batch) < self.max_batch:
                    try:
                        item = self._queue.get_nowait()
                    except queue.Empty:
                        break
                    if item is self._STOP:
                        self._queue.put(item)  # re-post for the outer loop
                        break
                    batch.append(item)
            closed = time.perf_counter()
            for item in batch:
                item[3].closed = closed
            # group by deployed engine (a reload may be in flight)
            groups: Dict[int, List[tuple]] = {}
            for item in batch:
                groups.setdefault(id(item[0]), []).append(item)
            slot_held = True
            for items in groups.values():
                # a future the transport cancelled (client gone before
                # its batch formed) is dropped here; marking the rest
                # RUNNING pins them against late cancellation
                live = []
                for it in items:
                    if it[2].set_running_or_notify_cancel():
                        live.append(it)
                    elif it[4] is not None:
                        it[4].cancel()  # nobody waits for its value
                items = live
                if not items:
                    continue
                if not slot_held:
                    # a batch that spans a reload: one slot a group
                    with _tracing.annotation("slot_wait"):
                        self._inflight.acquire()
                    immediate = False
                slot_held = False
                version = _version_of(items[0][0])
                self._m_batch_fill.labels(version=version).observe(
                    len(items)
                )
                # on this thread, not the serve thread: by the time a
                # response is out its queue wait is on /metrics, and the
                # serve thread's time is every queued request's
                children = _version_children(
                    self._m_stage_children, self._m_stages, version
                )
                if immediate:
                    children["immediate"].inc()
                observe_wait = children["queue_wait"].observe
                for it in items:
                    observe_wait(closed - it[3].enqueued)
                try:
                    self._serve_pool.submit(
                        self._serve_and_release, items[0][0], items
                    )
                except RuntimeError as e:
                    # pool shut down mid-close (a >join-timeout batch was
                    # in flight): fail these futures instead of leaving
                    # their waiters pending forever
                    self._inflight.release()
                    for it in items:
                        it[2].set_exception(
                            RuntimeError(f"server is shutting down: {e}")
                        )
            if slot_held:
                # every request of the batch was cancelled
                self._inflight.release()

    def _serve_and_release(self, dep: DeployedEngine, items) -> None:
        started = time.perf_counter()
        outcomes: List[tuple] = []
        # the batch runs under a serving compile_site (any executable
        # compile inside is a COLD compile: counted per site, span-
        # recorded, and drained below onto the predict span), under
        # the first traced item's ambient trace, so a compile span
        # chains into the request's trace tree, and under a fresh
        # accumulator of the engine's stage() durations
        batch_trace = next(
            (it[3].trace for it in items if it[3].trace is not None), None
        )
        compile_events: List[dict] = []
        stage_s = _tracing.StageTotals()
        served = started
        try:
            with self._hb.busy(), _cc.compile_site("serving"), \
                    _tracing.use(batch_trace), \
                    _tracing.stage_totals() as stage_s, \
                    _tracing.annotation("predict"):
                try:
                    prepared = None
                    serving = items
                    if items[0][4] is not None:
                        serving, prepared = self._preparations.collect(
                            dep, items, outcomes
                        )
                    if serving:
                        self._serve_isolating(
                            dep, serving, outcomes, prepared
                        )
                finally:
                    served = time.perf_counter()
                    compile_events = _cc.drain_compile_events()
        finally:
            self._inflight.release()
            observe = _version_children(
                self._m_stage_children, self._m_stages, _version_of(dep)
            )
            n = len(items)
            observe["slot_wait"].observe(started - items[0][3].closed, n)
            observe["predict"].observe(served - started, n)
            for name, seconds in stage_s.items():
                observe["batch_" + name].observe(seconds)
            observe["batch_unstaged"].observe(
                served - started - stage_s.staged
            )
            predict_attrs: Optional[Dict[str, Any]] = None
            if batch_trace is not None:
                # what a traced request's predict span says of the
                # batch: its size, the engine's stages, cold compiles
                predict_attrs = {"batch_size": n}
                if stage_s:
                    predict_attrs["stages_ms"] = {
                        name: round(seconds * 1000.0, 3)
                        for name, seconds in stage_s.items()
                    }
                if compile_events:
                    predict_attrs["cold_compiles"] = compile_events
            for it in items:
                times = it[3]
                times.started, times.served = started, served
                times.attrs = predict_attrs
            # the futures' callbacks (QueryAPI._finish_query: response
            # build, feedback, bookkeeping, the request's spans) run
            # here, one after another, before this thread can take the
            # next batch
            with _tracing.annotation("finish"):
                for f, exc, result in outcomes:
                    if exc is not None:
                        f.set_exception(exc)
                    else:
                        f.set_result(result)

    def _serve_isolating(
        self, dep: DeployedEngine, items, outcomes: List[tuple],
        prepared: Optional[list] = None,
    ) -> None:
        """Serve a batch; on failure bisect it so the poison query is
        located in O(log n) batched calls and its batchmates still get
        batched service (a serial per-query retry would multiply every
        innocent's latency by the batch size). Outcomes are collected as
        (future, exception, result) rather than resolved here so the
        caller controls when waiters wake. ``prepared`` is the items'
        prepared values where their engine prepares queries at arrival
        (halved with them), else None: ``serve_batch`` is then called
        with the queries alone."""
        try:
            queries = [it[1] for it in items]
            results = (
                dep.serve_batch(queries) if prepared is None
                else dep.serve_batch(queries, prepared)
            )
            for it, r in zip(items, results):
                outcomes.append((it[2], None, r))
        except Exception as e:
            if len(items) == 1:
                outcomes.append((items[0][2], e, None))
                return
            mid = len(items) // 2
            for half in (slice(None, mid), slice(mid, None)):
                self._serve_isolating(
                    dep, items[half], outcomes,
                    None if prepared is None else prepared[half],
                )


class QueryAPI:
    """Transport-independent request core for the engine server."""

    def __init__(
        self,
        deployed: DeployedEngine,
        config: Optional[ServerConfig] = None,
        plugin_context: Optional[EngineServerPluginContext] = None,
        reload_fn=None,
        stop_fn=None,
        experiment_start_fn=None,
        experiment_stop_fn=None,
    ):
        self.deployed = deployed
        self.config = config or ServerConfig()
        self.plugin_context = plugin_context or EngineServerPluginContext()
        self._reload_fn = reload_fn
        self._stop_fn = stop_fn
        self._experiment_start_fn = experiment_start_fn
        self._experiment_stop_fn = experiment_stop_fn
        # active experiment (sticky multi-variant serving). Reads on the
        # hot path take one reference snapshot — no lock: CPython
        # attribute assignment is atomic, and routing itself is a pure
        # hash of (salt, user_key), so workers need no shared state.
        self._experiment: Optional[_experiment.ActiveExperiment] = None
        self._executor = _BatchingExecutor(
            self.config.max_batch,
            self.config.pipeline_depth,
        )
        # non-query routes under the async transport run here, not on
        # the event loop: /plugins/... executes third-party handle_rest
        # code of unknown cost, and one blocking call inline on the
        # single-threaded loop would stall every connection
        self._route_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=2, thread_name_prefix="qroutes"
        )
        self.server_start_time = _dt.datetime.now(_dt.timezone.utc)
        # upgrade-check fields only; every serving stat lives in the
        # process-global metrics registry (per-child locks, no shared
        # hot-path lock)
        self._stats_lock = threading.Lock()
        # serving instruments: process-global families (the /metrics
        # exposition), read as deltas against construction-time
        # snapshots for this instance's status.json. The mergeable
        # log-bucket histogram replaces the old 512-sample reservoir —
        # a reservoir cannot aggregate across SO_REUSEPORT workers;
        # bucket vectors add.
        reg = _metrics.get_registry()
        # per-VERSION attribution: every serving family carries the
        # model version (the deployed engine instance id), so a /reload
        # swap's latency and quality are diffable per version off one
        # /metrics scrape. Requests record under the version of the
        # DeployedEngine snapshot that actually served them, so the two
        # versions' sample windows around a swap are disjoint.
        self._m_latency_fam = reg.histogram(
            "pio_serving_latency_seconds",
            "End-to-end /queries.json serving latency, by model version",
            labels=("version",),
            buckets=_metrics.LATENCY_BUCKETS_S,
        )
        # the last of a request's four stages (_StageTimes.stages; the
        # executor observes the three it times itself). Their sum plus
        # the parse before the enqueue is the request's
        # pio_serving_latency_seconds sample.
        self._m_finish_fam = reg.histogram(
            "pio_serving_finish_seconds",
            "serve_batch returned until this request's response was "
            "built (it waits for its batchmates' callbacks on the serve "
            "thread), by model version",
            labels=("version",),
            buckets=_metrics.LATENCY_BUCKETS_S,
        )
        # its child by version: this observe runs on the serve thread
        self._m_finish_children: Dict[str, Any] = {}
        self._m_requests_fam = reg.counter(
            "pio_serving_requests_total",
            "Completed /queries.json requests, by model version",
            labels=("version",),
        )
        self._m_last_fam = reg.gauge(
            "pio_serving_last_seconds",
            "Latency of the most recent served query, by model version",
            labels=("version",),
        )
        self._m_model_info = reg.gauge(
            "pio_model_info",
            "1 for the model version this server is actively serving, "
            "0 for versions it swapped out",
            labels=("engine", "version"),
        )
        self._m_feedback_dropped = reg.counter(
            "pio_feedback_queue_dropped_total",
            "Feedback posts dropped because the bounded queue was full",
        )
        # experimentation plane: per-arm allocation counts plus the
        # experiment's presence/split, federable off the same scrape as
        # every per-version family (the variant id IS the version label
        # on those)
        self._m_exp_requests = reg.counter(
            "pio_experiment_requests_total",
            "Queries served per experiment arm (variant = the arm's "
            "engine instance id)",
            labels=("experiment", "variant"),
        )
        self._m_exp_info = reg.gauge(
            "pio_experiment_info",
            "Traffic split fraction per experiment arm while the "
            "experiment runs; 0 once it stops",
            labels=("experiment", "variant"),
        )
        # per-instance "since this server deployed" views: snapshot every
        # pre-existing version child now (the families are process-global
        # and other servers may have populated them); versions this
        # server binds later enter the tables at bind time (zero for
        # fresh children)
        self._lat_bases: Dict[str, _metrics.HistogramSnapshot] = {
            vid: child.snapshot()
            for (vid,), child in self._m_latency_fam.children()
        }
        self._req_bases: Dict[str, float] = {
            vid: child.value
            for (vid,), child in self._m_requests_fam.children()
        }
        self._feedback_dropped_base = self._m_feedback_dropped.snapshot()
        self._capture_count = itertools.count(1)
        self._bind_version_metrics(deployed)
        # /readyz: a deployed model with its serving components is the
        # engine server's one hard readiness requirement; daemon-stall
        # checks (executor, feedback drainer, continuous trainer) are
        # global. ttl 0: the check is attribute reads, no caching needed.
        self._ready_probes = (
            _health.TTLProbe("model", self._probe_model, ttl_s=0.0),
        )
        # feedback posts drain on ONE daemon worker (not a thread per
        # request — that would throttle the micro-batched hot path). The
        # queue is BOUNDED (config.feedback_queue_max): a down event
        # server drops the oldest pending post instead of growing the
        # queue without limit; drops are counted for status.json.
        self._feedback_queue: "queue.Queue" = queue.Queue(
            maxsize=max(1, self.config.feedback_queue_max)
        )
        self._feedback_worker: Optional[threading.Thread] = None
        self._feedback_lock = threading.Lock()
        self._feedback_closed = False
        # daily upgrade self-check (reference CreateServer.scala:253-260)
        self._upgrade_status: Optional[str] = None
        self._upgrade_checked_at: Optional[str] = None
        self._upgrade_stop = threading.Event()
        if self.config.upgrade_check_interval_s > 0:
            threading.Thread(
                target=self._upgrade_check_loop, daemon=True
            ).start()

    def _bind_version_metrics(self, deployed) -> None:
        """Point the current-version instrument handles at ``deployed``'s
        model version and flip ``pio_model_info`` — called at
        construction and by :meth:`bind_deployed` on every /reload swap.
        """
        vid = _version_of(deployed)
        inst = getattr(deployed, "engine_instance", None)
        engine_label = str(
            getattr(inst, "engine_id", None)
            or getattr(inst, "engine_factory", None)
            or "unknown"
        )
        self._m_latency = self._m_latency_fam.labels(version=vid)
        self._m_requests = self._m_requests_fam.labels(version=vid)
        self._m_last = self._m_last_fam.labels(version=vid)
        if vid not in self._lat_bases:
            self._lat_bases[vid] = self._m_latency.snapshot()
        if vid not in self._req_bases:
            self._req_bases[vid] = self._m_requests.value
        # compat handles for the current version's "since deployed" view
        self._lat_base = self._lat_bases[vid]
        self._requests_base = self._req_bases[vid]
        self._m_model_info.labels(engine=engine_label, version=vid).set(1)
        self._active_model_label = (engine_label, vid)

    def bind_deployed(self, deployed) -> None:
        """Swap the serving snapshot (the /reload path): queries in
        flight keep the old DeployedEngine and keep recording under its
        version label; new queries record under the new one — the two
        versions' sample windows are disjoint by construction."""
        old_label = getattr(self, "_active_model_label", None)
        self.deployed = deployed
        self._bind_version_metrics(deployed)
        if old_label is not None and old_label != self._active_model_label:
            self._m_model_info.labels(
                engine=old_label[0], version=old_label[1]
            ).set(0)

    # --- experimentation plane (sticky multi-variant serving) ---

    def set_experiment(self, active: "_experiment.ActiveExperiment") -> None:
        """Bind an :class:`ActiveExperiment`: subsequent queries route
        by the sticky allocation hash to the arm's own DeployedEngine
        (so every per-version family is per-variant for free)."""
        for vid, frac in zip(active.spec.variants, active.spec.split):
            self._m_exp_info.labels(
                experiment=active.spec.name, variant=vid
            ).set(frac)
        self._experiment = active

    def clear_experiment(self) -> Optional["_experiment.ActiveExperiment"]:
        """Unbind the running experiment (allocation stops immediately;
        in-flight queries finish on the arm that served them). Returns
        the displaced ActiveExperiment so the server can retire its
        engines."""
        active = self._experiment
        self._experiment = None
        if active is not None:
            for vid in active.spec.variants:
                self._m_exp_info.labels(
                    experiment=active.spec.name, variant=vid
                ).set(0)
        return active

    def experiment_status(self) -> Optional[Dict[str, Any]]:
        active = self._experiment
        if active is None:
            return None
        status = active.status()
        requests = {}
        for (exp, vid), child in self._m_exp_requests.children():
            if exp == active.spec.name:
                requests[vid] = child.value
        status["requests"] = requests
        return status

    def _serving_totals(self) -> Tuple["_metrics.HistogramSnapshot", int]:
        """Latency histogram + request count summed across every model
        version this server served, as deltas against the construction/
        bind-time bases — the status.json 'since this server deployed'
        view over the labeled process-global families."""
        snaps = []
        for (vid,), child in self._m_latency_fam.children():
            snap = child.snapshot()
            base = self._lat_bases.get(vid)
            if base is not None:
                snap = snap.delta(base)
            snaps.append(snap)
        if snaps:
            lat = _metrics.merge_snapshots(snaps)
        else:
            bounds = self._m_latency_fam.bounds
            lat = _metrics.HistogramSnapshot(
                bounds, (0,) * (len(bounds) + 1), 0.0, 0
            )
        requests = 0
        for (vid,), child in self._m_requests_fam.children():
            requests += int(child.value - self._req_bases.get(vid, 0.0))
        return lat, requests

    def _upgrade_check_loop(self) -> None:
        from predictionio_tpu.tools.upgrade import check_for_upgrade

        if self._upgrade_stop.wait(self.config.upgrade_check_initial_delay_s):
            return
        while not self._upgrade_stop.is_set():
            status = check_for_upgrade()
            with self._stats_lock:
                self._upgrade_status = status
                self._upgrade_checked_at = _dt.datetime.now(
                    _dt.timezone.utc
                ).isoformat()
            logger.info("upgrade check: %s", status)
            self._upgrade_stop.wait(self.config.upgrade_check_interval_s)

    _FEEDBACK_STOP = object()

    def close(self) -> None:
        """Release serving resources (the batching executor's collector,
        serve-pool, feedback, and upgrade-check threads) when the server
        stops or undeploys."""
        self._upgrade_stop.set()
        self._executor.close()
        # wait=False: an in-flight route (e.g. /stop itself, whose timer
        # invoked this close) must not deadlock the teardown
        self._route_pool.shutdown(wait=False)
        with self._feedback_lock:
            self._feedback_closed = True
            worker = self._feedback_worker
            # the queue is bounded now: drain pending posts (they are
            # best-effort and the server is stopping) so the sentinel
            # put cannot hit a full queue. Producers hold
            # _feedback_lock too and check _feedback_closed first, so
            # nothing can refill the queue between the drain and the
            # sentinel put.
            try:
                while True:
                    self._feedback_queue.get_nowait()
            except queue.Empty:
                pass
            self._feedback_queue.put_nowait(self._FEEDBACK_STOP)
        if worker is not None and worker.is_alive():
            worker.join(timeout=10.0)

    def _enqueue_feedback(self, item) -> None:
        """Bounded, drop-oldest enqueue: when the event server lags or
        is down, the newest prediction wins a slot and the oldest
        pending post is counted dropped — memory stays bounded. Holds
        _feedback_lock so it serializes with close()'s drain+sentinel
        (an enqueue can neither land after the stop sentinel nor drop
        it)."""
        with self._feedback_lock:
            if self._feedback_closed:
                return  # feedback is best-effort; server is stopping
            while True:
                try:
                    self._feedback_queue.put_nowait(item)
                    return
                except queue.Full:
                    try:
                        self._feedback_queue.get_nowait()
                    except queue.Empty:
                        continue  # the worker drained it; retry the put
                    self._m_feedback_dropped.inc()

    def _ensure_feedback_worker(self) -> None:
        with self._feedback_lock:
            if self._feedback_closed:
                return  # feedback is best-effort; server is stopping
            if self._feedback_worker is None or not self._feedback_worker.is_alive():
                self._feedback_worker = threading.Thread(
                    target=self._drain_feedback, daemon=True
                )
                self._feedback_worker.start()

    def _drain_feedback(self) -> None:
        # watchdog (busy only around the post: an empty queue is idle,
        # not stalled); the urlopen timeout bounds each unit at 10 s
        hb = _health.heartbeat("feedback-drainer", deadline_s=60.0)
        while True:
            item = self._feedback_queue.get()
            if item is self._FEEDBACK_STOP:
                return
            url, data, tinfo = item if len(item) == 3 else (*item, None)
            with hb.busy():
                if tinfo is None:
                    self._post_feedback(url, data)
                    continue
                # propagate the serving trace onto the feedback POST and
                # record the hop: the event server's ingest spans parent
                # on this feedback-post span, which parents on the
                # request's http span
                trace_id, parent_span = tinfo
                span_id = _tracing.new_span_id()
                t0 = time.time()
                try:
                    self._post_feedback(
                        url, data,
                        headers={
                            _tracing.TRACE_HEADER: trace_id,
                            _tracing.PARENT_HEADER: span_id,
                        },
                    )
                finally:
                    _tracing.record_span(
                        "feedback-post", trace_id, span_id=span_id,
                        parent_id=parent_span, start_s=t0,
                        duration_s=time.time() - t0,
                    )

    def _post_feedback(self, url, data, headers=None) -> None:
        try:
            req = urllib.request.Request(
                url,
                data=json.dumps(data).encode("utf-8"),
                headers={
                    "Content-Type": "application/json",
                    **(headers or {}),
                },
                method="POST",
            )
            with urllib.request.urlopen(req, timeout=10) as resp:
                if resp.status != 201:
                    logger.error(
                        "Feedback event failed. Status code: %d. Data: %s",
                        resp.status, json.dumps(data),
                    )
        except Exception as e:
            logger.error("Feedback event failed: %s", e)

    # --- dispatch ---

    def handle(
        self,
        method: str,
        path: str,
        query: Optional[Dict[str, str]] = None,
        body: Optional[bytes] = None,
        headers: Optional[Dict[str, str]] = None,
    ) -> Tuple[int, Any, str]:
        """Returns (status, payload, content_type)."""
        try:
            return self._route(method, path, query or {}, body, headers)
        except Exception as e:
            logger.exception("internal error handling %s %s", method, path)
            return 500, {"message": str(e)}, "application/json"

    def handle_nowait(
        self,
        method: str,
        path: str,
        query: Optional[Dict[str, str]] = None,
        body: Optional[bytes] = None,
        form: Optional[Dict[str, str]] = None,
        headers: Optional[Dict[str, str]] = None,
    ) -> Union[Tuple[int, Any, str], "concurrent.futures.Future"]:
        """Transport-facing dispatch for the event-loop frontend
        (api/aio_http.py): the /queries.json hot path returns a
        ``concurrent.futures.Future`` resolving to a
        (status, payload, content_type) tuple, so an in-flight query is
        a micro-batch queue entry — not a parked OS thread; every other
        route is offloaded to a small pool (plugin handle_rest code has
        unknown cost and must not run inline on the loop) whose future
        the loop awaits the same way. Parse errors answer inline."""
        if path == "/queries.json" and method == "POST":
            try:
                return self._handle_query_nowait(body, headers)
            except Exception as e:
                logger.exception(
                    "internal error handling POST /queries.json"
                )
                return 500, {"message": str(e)}, "application/json"
        if path == "/healthz" and method == "GET":
            # liveness inline on the loop (non-blocking dict build): a
            # route pool wedged by third-party plugin code must not make
            # the orchestrator restart an otherwise-serving process
            return 200, _health.liveness(), "application/json"
        try:
            return self._route_pool.submit(
                self.handle, method, path, query, body, headers
            )
        except RuntimeError:  # pool shut down: server is stopping
            return (
                503, {"message": "server is shutting down"},
                "application/json",
            )

    def _probe_model(self) -> None:
        dep = self.deployed
        if dep is None or not dep.models or not dep.algorithms:
            raise RuntimeError("no model deployed")

    def _route(
        self, method, path, query, body, headers=None
    ) -> Tuple[int, Any, str]:
        parts = [p for p in path.strip("/").split("/") if p]
        if not parts and method == "GET":
            return 200, self._status_html(), "text/html"
        if path == "/healthz" and method == "GET":
            return 200, _health.liveness(), "application/json"
        if path == "/readyz" and method == "GET":
            ok, payload = _health.readiness(self._ready_probes)
            return (200 if ok else 503), payload, "application/json"
        if path == "/status.json" and method == "GET":
            return 200, self._status_json(), "application/json"
        if path == "/metrics" and method == "GET":
            # refresh the pull-style device gauges on the way out: the
            # ledger-vs-memory_stats drift and the persistent
            # executable-cache size are point-in-time reads (cheap; a
            # handful of stat calls), so scrape time is the right time
            try:
                _ledger.get_ledger().reconcile()
                _cc.persistent_cache_stats()
                _health.record_memory_gauges()
            except Exception:
                logger.debug(
                    "device-gauge refresh failed", exc_info=True
                )
            return (
                200,
                _metrics.get_registry().render(),
                _metrics.render_content_type(),
            )
        if path == "/debug/traces.json" and method == "GET":
            return self._debug_traces(query)
        if path == "/debug/profile":
            return self._debug_profile(method, query)
        if path == "/debug/predictions.json" and method == "GET":
            return self._debug_predictions(query)
        if path == "/queries.json" and method == "POST":
            return self._handle_query(body, headers)
        if path == "/experiment.json" and method in ("GET", "POST"):
            # like /reload this is an operator surface: under the async
            # transport it runs on the route pool, so a start (which may
            # read + warm variant states from storage) never blocks the
            # event loop. When an access key is configured it is
            # required, matching the other mutating surfaces.
            return self._experiment_route(method, query, body)
        if path == "/reload" and method in ("GET", "POST"):
            # synchronous: the promotion pipeline (and any fleet
            # orchestrator) needs the success/failure verdict in the
            # response, and under the async transport this runs on the
            # route pool, never the event loop. ``engineInstanceId``
            # pins the target version so an SO_REUSEPORT fleet converges
            # on ONE instance instead of racing "latest"; omitted, the
            # reference's latest-COMPLETED semantics apply.
            if self._reload_fn is None:
                return 200, "Reloading... (no reload hook)", "text/plain"
            target_id = query.get("engineInstanceId") or None
            try:
                new_id = self._reload_fn(target_id)
            except Exception as e:
                # the swap never happened: the old snapshot keeps
                # serving, and the 500 names the cause (store down,
                # corrupt/missing instance) instead of a silent log line
                logger.exception("reload failed; keeping current instance")
                return (
                    500,
                    {
                        "message": (
                            f"reload failed ({type(e).__name__}: {e}); "
                            "still serving engine instance "
                            f"{_version_of(self.deployed)}"
                        )
                    },
                    "application/json",
                )
            return (
                200,
                f"Reloading... now serving engine instance {new_id}",
                "text/plain",
            )
        if path == "/stop" and method == "GET":
            if self._stop_fn is not None:
                t = threading.Timer(1.0, self._stop_fn)
                t.daemon = True
                t.start()
            return 200, "Shutting down...", "text/plain"
        if path == "/plugins.json" and method == "GET":
            return 200, self.plugin_context.describe(), "application/json"
        if parts and parts[0] == "plugins" and len(parts) >= 3 and method == "GET":
            plugin_type, plugin_name, args = parts[1], parts[2], parts[3:]
            table = (
                self.plugin_context.output_blockers
                if plugin_type == EngineServerPlugin.OUTPUT_BLOCKER
                else self.plugin_context.output_sniffers
            )
            if plugin_name not in table:
                return 404, {"message": f"Plugin {plugin_name} not found."}, "application/json"
            return 200, table[plugin_name].handle_rest(args), "application/json"
        return 404, {"message": "Not Found"}, "application/json"

    # --- experimentation surface ---

    def _experiment_route(
        self, method: str, query: Dict[str, str], body: Optional[bytes]
    ) -> Tuple[int, Any, str]:
        if self.config.access_key and not secrets.compare_digest(
            query.get("accessKey", ""), self.config.access_key
        ):
            return (
                401, {"message": "Invalid accessKey."}, "application/json"
            )
        if method == "GET":
            return (
                200,
                {"experiment": self.experiment_status()},
                "application/json",
            )
        try:
            payload = json.loads((body or b"").decode("utf-8"))
            if not isinstance(payload, dict):
                raise ValueError("body must be a JSON object")
        except Exception as e:
            return 400, {"message": str(e)}, "application/json"
        if payload.get("stop"):
            if self._experiment_stop_fn is None:
                return (
                    501,
                    {"message": "no experiment hook on this server"},
                    "application/json",
                )
            winner = payload.get("winner")
            report = self._experiment_stop_fn(
                winner=str(winner) if winner else None
            )
            return 200, report, "application/json"
        if self._experiment_start_fn is None:
            return (
                501,
                {"message": "no experiment hook on this server"},
                "application/json",
            )
        try:
            spec = _experiment.ExperimentSpec.from_json(
                payload.get("spec") or payload
            )
            status = self._experiment_start_fn(spec)
        except ValueError as e:
            return 400, {"message": str(e)}, "application/json"
        except Exception as e:
            logger.exception("experiment start failed")
            return 500, {"message": str(e)}, "application/json"
        return 200, status, "application/json"

    # --- debug span dump (access-key gated when a key is configured) ---

    def _debug_traces(self, query: Dict[str, str]) -> Tuple[int, Any, str]:
        if self.config.access_key and not secrets.compare_digest(
            query.get("accessKey", ""), self.config.access_key
        ):
            return (
                401, {"message": "Invalid accessKey."}, "application/json"
            )
        from predictionio_tpu.api.http import traces_payload

        status, payload = traces_payload(query)
        return status, payload, "application/json"

    def _debug_profile(
        self, method: str, query: Dict[str, str]
    ) -> Tuple[int, Any, str]:
        """On-demand profiler capture (utils/profiling.profile_route):
        ``POST ?seconds=N`` runs one bounded jax.profiler capture and
        returns the zipped trace base64-encoded; ``GET`` is status.
        Device timelines expose workload structure, so the endpoint —
        like /debug/predictions.json — REQUIRES a configured access
        key. Under the async transport this runs on the route pool, so
        a capture never blocks the event loop or the serving hot path."""
        if not self.config.access_key:
            return (
                403,
                {
                    "message": "profile capture requires a configured "
                    "access key (deploy with --accesskey)."
                },
                "application/json",
            )
        from predictionio_tpu.utils.profiling import profile_route

        status, payload = profile_route(
            method,
            query,
            secrets.compare_digest(
                query.get("accessKey", ""), self.config.access_key
            ),
        )
        return status, payload, "application/json"

    def _debug_predictions(self, query: Dict[str, str]) -> Tuple[int, Any, str]:
        """The capture-ring dump. The payload is directly persistable as
        a capture file for ``pio replay`` (workflow/quality.py documents
        the record format). Unlike the span dump (opt-in trace ids, no
        bodies), these records hold full query/result payloads — so the
        endpoint REQUIRES a configured access key; a keyless deployment
        keeps capturing (shadow scoring reads the ring in-process) but
        refuses to serve it."""
        if not self.config.access_key:
            return (
                403,
                {
                    "message": "predictions dump requires a configured "
                    "access key (deploy with --accesskey)."
                },
                "application/json",
            )
        if not secrets.compare_digest(
            query.get("accessKey", ""), self.config.access_key
        ):
            return (
                401, {"message": "Invalid accessKey."}, "application/json"
            )
        limit = None
        if query.get("limit"):
            try:
                limit = int(query["limit"])
            except ValueError:
                return 400, {"message": "invalid limit"}, "application/json"
        return (
            200,
            {
                "predictions": _quality.get_capture().dump(
                    limit=limit,
                    version=query.get("version") or None,
                    variant=query.get("variant") or None,
                )
            },
            "application/json",
        )

    # --- the hot path (reference CreateServer.scala:473-624) ---

    def _handle_query(
        self, body: Optional[bytes], headers=None
    ) -> Tuple[int, Any, str]:
        result = self._handle_query_nowait(body, headers)
        if isinstance(result, concurrent.futures.Future):
            return result.result()
        return result

    def _handle_query_nowait(
        self, body: Optional[bytes], headers=None
    ) -> Union[Tuple[int, Any, str], "concurrent.futures.Future"]:
        """Parse + enqueue; the returned future completes (via the
        serve-pool thread that resolves the prediction, so feedback,
        plugins, and bookkeeping stay off the event loop) when the
        query's micro-batch is served. Parse errors answer inline."""
        serving_start = time.perf_counter()
        deployed = self.deployed  # snapshot against concurrent reload
        algorithms = deployed.algorithms
        query_time = _dt.datetime.now(_dt.timezone.utc)
        # spans are recorded only for CLIENT-SUPPLIED trace ids
        # (X-PIO-Trace-Id): minting + ring-buffer appends for every
        # request would add a shared-lock touch to the hot path (the
        # acceptance criterion forbids exactly that) and untraced
        # traffic would evict the deliberately-traced requests from the
        # bounded span ring — the same flood guard the storage gateway
        # applies. tctx.span_id is the http span, recorded at finish.
        if headers and headers.get(_tracing.TRACE_HEADER.lower()):
            tctx, inbound_parent = _tracing.from_headers(headers)
        else:
            tctx, inbound_parent = None, None
        active = self._experiment  # snapshot: stop mid-request is safe
        experiment = None
        try:
            query_json = json.loads((body or b"").decode("utf-8"))
            if active is not None:
                # sticky allocation: a pure hash of (salt, user_key) —
                # per-request, stateless, so every SO_REUSEPORT worker
                # and every restart assigns this user the same arm. The
                # chosen arm's DeployedEngine replaces the snapshot, so
                # batching, metrics, feedback, and capture all see the
                # variant as "the" deployed engine.
                _, deployed = active.route(query_json)
                algorithms = deployed.algorithms
                experiment = active.spec.name
            query = algorithms[0].query_from_json(query_json)
        except Exception as e:
            logger.error("query %r is invalid: %s", body, e)
            return 400, {"message": str(e)}, "application/json"

        times = _StageTimes(tctx)
        prediction_fut = self._executor.submit_nowait(
            deployed, query, times
        )
        out: "concurrent.futures.Future" = concurrent.futures.Future()

        def _finish(f: "concurrent.futures.Future") -> None:
            try:
                result = self._finish_query(
                    deployed, query, query_json, f.result(), query_time,
                    serving_start, times, tctx, inbound_parent,
                    experiment=experiment,
                )
            except concurrent.futures.CancelledError:
                return  # request was cancelled before its batch formed
            except Exception as e:
                logger.exception(
                    "internal error handling POST /queries.json"
                )
                result = (500, {"message": str(e)}, "application/json")
                # a failed request still shows where its time went
                times.record_spans(time.perf_counter())
            # when the answer left this thread: the transport's
            # pio_http_handoff_seconds runs from here to its writer
            out.resolved_at = time.perf_counter()
            try:
                out.set_result(result)
            except concurrent.futures.InvalidStateError:
                pass  # the transport cancelled the request (client gone)

        prediction_fut.add_done_callback(_finish)

        def _propagate_cancel(f: "concurrent.futures.Future") -> None:
            if f.cancelled():
                # client went away: if the query has not been picked up
                # into a batch yet, drop it from the collector entirely
                prediction_fut.cancel()

        out.add_done_callback(_propagate_cancel)
        return out

    def _finish_query(
        self, deployed, query, query_json, prediction, query_time,
        serving_start, times: _StageTimes, tctx=None, inbound_parent=None,
        experiment=None,
    ) -> Tuple[int, Any, str]:
        prediction_json = deployed.algorithms[0].result_to_json(prediction)
        # the capture baseline is the RAW model output (pre-stamp,
        # pre-plugin): `pio replay` re-runs exactly the model path, so a
        # self-replay against the same instance is byte-comparable. The
        # sampling draw is an atomic itertools counter (done callbacks
        # run on concurrent batch threads), and the snapshot is a deep
        # copy — a plugin blocker may mutate the response's nested
        # structures in place and must not corrupt the capture.
        do_capture = self.config.capture_sample > 0 and (
            next(self._capture_count) % self.config.capture_sample == 0
        )
        raw_json = copy.deepcopy(prediction_json) if do_capture else None
        version = _version_of(deployed)
        # per-version attribution: stamp the model version onto every
        # served prediction, so clients (and the feedback event) can
        # name the exact persisted round that produced it
        if isinstance(prediction_json, dict):
            prediction_json = dict(prediction_json, modelVersion=version)
            if experiment is not None:
                # stamp the arm onto the response BEFORE the feedback
                # post, so the prId attribution record carries it too
                prediction_json["experiment"] = experiment
                prediction_json["variant"] = version

        pr_id = None
        if self.config.feedback:
            prediction_json, pr_id = self._feedback(
                deployed, query, query_json, prediction, prediction_json,
                query_time, tctx,
            )

        prediction_json = self.plugin_context.run_blockers(
            deployed.engine_instance, query_json, prediction_json
        )
        self.plugin_context.notify_sniffers(
            deployed.engine_instance, query_json, prediction_json
        )

        now = time.perf_counter()
        elapsed = now - serving_start
        # registry bookkeeping: per-child locks only, no shared hot-path
        # lock. The children are the SERVING deployed's version — during
        # a /reload swap, in-flight queries still record under the old
        # version while new ones record under the new.
        self._m_latency_fam.labels(version=version).observe(elapsed)
        finish = self._m_finish_children.get(version)
        if finish is None:
            finish = self._m_finish_children[version] = (
                self._m_finish_fam.labels(version=version)
            )
        finish.observe(now - times.served)
        self._m_requests_fam.labels(version=version).inc()
        self._m_last_fam.labels(version=version).set(elapsed)
        if experiment is not None:
            self._m_exp_requests.labels(
                experiment=experiment, variant=version
            ).inc()
        if do_capture:
            _quality.get_capture().record(
                version=version,
                query_json=query_json,
                result_json=raw_json,
                pr_id=pr_id,
                trace_id=tctx.trace_id if tctx is not None else None,
                latency_s=elapsed,
                experiment=experiment,
                variant=version if experiment is not None else None,
            )
        if tctx is not None:
            times.record_spans(now)
            _tracing.record_span(
                "http:/queries.json", tctx.trace_id, span_id=tctx.span_id,
                parent_id=inbound_parent, duration_s=elapsed,
            )
        return 200, prediction_json, "application/json"

    # --- feedback loop (reference CreateServer.scala:509-579) ---

    def _feedback(
        self, deployed, query, query_json, prediction, prediction_json,
        query_time, tctx=None,
    ):
        org = getattr(prediction, "pr_id", None)
        new_pr_id = org if org else _gen_pr_id()
        data = {
            "event": "predict",
            "eventTime": query_time.isoformat().replace("+00:00", "Z"),
            "entityType": "pio_pr",
            "entityId": new_pr_id,
            "properties": {
                "engineInstanceId": deployed.engine_instance.id,
                "query": query_json,
                "prediction": prediction_json,
            },
        }
        query_pr_id = getattr(query, "pr_id", None)
        if query_pr_id is not None:
            data["prId"] = query_pr_id

        url = (
            f"http://{self.config.event_server_ip}:"
            f"{self.config.event_server_port}/events.json?"
            + urllib.parse.urlencode({"accessKey": self.config.access_key})
        )
        # traced requests carry (trace id, http span id) onto the queue
        # so the drainer's POST propagates X-PIO-Trace-Id — the ingest
        # span chain joins the serving trace instead of dead-ending here
        tinfo = (tctx.trace_id, tctx.span_id) if tctx is not None else None
        self._enqueue_feedback((url, data, tinfo))
        self._ensure_feedback_worker()

        # inject the fresh prId into the response: it is the attribution
        # join key the client must echo on subsequent events (reference
        # CreateServer.scala:525 returns it the same way)
        if isinstance(prediction_json, dict):
            prediction_json = dict(prediction_json, prId=new_pr_id)
        return prediction_json, new_pr_id

    # --- status page (reference CreateServer.scala:444-471 html.index) ---

    def _status_json(self) -> dict:
        """status.json is now a READ of the metrics registry (deltas
        against construction-time snapshots — 'since this server
        deployed'), not a walk of N private lock-guarded tallies. The
        p50/p99 keys survive, estimated by bucket interpolation from the
        mergeable log-bucket histogram that replaced the reservoir."""
        from predictionio_tpu.ops.streaming import pack_cache_stats
        from predictionio_tpu.workflow.continuous import (
            continuous_round_stats,
        )
        from predictionio_tpu.workflow.promotion import promotion_stats

        inst = self.deployed.engine_instance
        batch_stats = self._executor.stats()
        lat, requests = self._serving_totals()
        with self._stats_lock:
            upgrade_status = self._upgrade_status
            upgrade_checked = self._upgrade_checked_at
        return {
            "status": "alive",
            "engineInstanceId": inst.id,
            # the model-version label every serving metric carries
            # (pio_model_info flips on /reload)
            "modelVersion": _version_of(self.deployed),
            "predictionCapture": _quality.get_capture().stats(),
            "engineFactory": inst.engine_factory,
            "startTime": self.server_start_time.isoformat(),
            "algorithms": [type(a).__name__ for a in self.deployed.algorithms],
            "algorithmsParams": [
                repr(a.params) for a in self.deployed.algorithms
            ],
            # active residency precision per algorithm for THIS deployed
            # version (quantized retrieval tier, ops/retrieval.py);
            # None = no quantization-aware serving state
            "servingPrecision": [
                a.serving_precision(m)
                for a, m in zip(
                    self.deployed.algorithms, self.deployed.models
                )
            ],
            "serving": type(self.deployed.serving).__name__,
            "feedback": self.config.feedback,
            "eventServerIp": self.config.event_server_ip,
            "eventServerPort": self.config.event_server_port,
            "requestCount": requests,
            "avgServingSec": (lat.sum / lat.count) if lat.count else 0.0,
            "lastServingSec": self._m_last.value,
            # bucket-interpolated latency percentiles from the mergeable
            # log-bucket histogram (quantile_from_buckets)
            "p50ServingSec": lat.quantile(0.50),
            "p99ServingSec": lat.quantile(0.99),
            # collector batch accounting: does micro-batching engage?
            "batchFillMean": round(batch_stats["batch_fill_mean"], 3),
            "batchSizeHistogram": batch_stats["batch_size_histogram"],
            # bounded feedback queue (drop-oldest when the event
            # server lags; see ServerConfig.feedback_queue_max)
            "feedbackQueueDropped": int(
                self._m_feedback_dropped.value
                - self._feedback_dropped_base
            ),
            # training-side registry families surfaced for the serving
            # process (continuous retrain + hot-swap runs in-process)
            "packCache": pack_cache_stats(),
            "continuousRounds": continuous_round_stats(),
            # promotion-pipeline outcomes (workflow/promotion.py): the
            # in-process view of pio_promotion_total
            "promotion": promotion_stats(),
            # HBM residency ledger detail: per-device, per-component
            # registered bytes (the `pio top` detail view's source)
            "deviceLedger": {
                "totalBytes": _ledger.get_ledger().total_bytes(),
                "breakdown": _ledger.get_ledger().breakdown(),
            },
            # daily self-check (reference CreateServer.scala:253-260)
            "upgradeStatus": upgrade_status,
            "upgradeLastChecked": upgrade_checked,
        }

    def _status_html(self) -> str:
        s = self._status_json()
        rows = "".join(
            f"<tr><th>{html.escape(str(k))}</th>"
            f"<td>{html.escape(json.dumps(v))}</td></tr>"
            for k, v in s.items()
        )
        return (
            "<!DOCTYPE html><html><head><title>"
            f"Engine Server at {self.config.ip}:{self.config.port}"
            "</title></head><body><h1>PredictionIO-TPU Engine Server</h1>"
            f"<table>{rows}</table></body></html>"
        )


class EngineServer:
    """The MasterActor equivalent (reference CreateServer.scala:262-384):
    binds the HTTP frontend (event-loop by default, thread-per-connection
    via ``ServerConfig.transport='threaded'``), hot-swaps serving state
    on /reload, undeploys on /stop.

    A swap retires the displaced DeployedEngine into a small LRU of
    prepared serving states (``ServerConfig.retained_states`` — the
    reference's multi-variant admin tier): a rollback ``/reload`` back
    to a retained instance is one reference flip, no store read, no
    recompile. Evicted entries drain behind the in-flight batch
    boundary and then free their device-resident factors, on a
    background thread watched by the ``serving-drain`` heartbeat."""

    # bounded drain of evicted serving states; a drain wedged past the
    # heartbeat deadline degrades /readyz (utils/health.py semantics)
    DRAIN_TIMEOUT_S = 60.0
    DRAIN_DEADLINE_S = 120.0

    def __init__(
        self,
        engine: Engine,
        config: Optional[ServerConfig] = None,
        storage: Optional[Storage] = None,
        plugin_context: Optional[EngineServerPluginContext] = None,
        deployed: Optional[DeployedEngine] = None,
    ):
        self.engine = engine
        self.config = config or ServerConfig()
        self.storage = storage or get_storage()
        # deploy-time serving context: pins the prepared serving state
        # (resident sharded factors) to this worker's device slice, and
        # is REUSED by /reload so a hot model swap re-uploads onto the
        # same devices
        self._serving_ctx: Optional[WorkflowContext] = None
        if self.config.serving_devices:
            self._serving_ctx = WorkflowContext(
                mode="Serving",
                storage=self.storage,
                mesh=_mesh_from_device_spec(self.config.serving_devices),
            )
        if deployed is None:
            deployed = DeployedEngine.from_storage(
                engine,
                self.storage,
                self.config.engine_instance_id,
                ctx=self._serving_ctx,
            )
        # displaced-but-retained serving states, newest last (the
        # rollback store); guarded by its own lock — reload may be
        # driven concurrently from the route pool and a promotion loop
        self._retained: (
            "collections.OrderedDict[str, DeployedEngine]"
        ) = collections.OrderedDict()
        self._retained_lock = threading.Lock()
        # serializes the read-bind-retire sequence: reload may be driven
        # concurrently from the route pool and a promotion loop, and two
        # racing swaps reading the same api.deployed would displace one
        # fresh snapshot without ever retiring (draining/releasing) it
        self._swap_lock = threading.Lock()
        self.api = QueryAPI(
            deployed,
            self.config,
            plugin_context,
            reload_fn=self.reload,
            stop_fn=self.shutdown,
            experiment_start_fn=self.start_experiment,
            experiment_stop_fn=self.stop_experiment,
        )

        def handle(method, path, query, body, form=None, headers=None):
            return self.api.handle(method, path, query, body, headers)

        def handle_nowait(method, path, query, body, form=None, headers=None):
            return self.api.handle_nowait(
                method, path, query, body, form, headers
            )

        # the event loop awaits the query route's future; the threaded
        # frontend cannot await, so it gets the blocking dispatch
        fn = (
            handle_nowait if self.config.transport == "async" else handle
        )
        # the transport times the query route alone: a /metrics scrape
        # or a 2 s /debug/profile call must not sit in that mean
        self._http = make_http_server(
            fn, self.config.ip, self.config.port, "Engine Server",
            reuse_port=self.config.reuse_port,
            transport=self.config.transport,
            timed_routes=(("POST", "/queries.json"),),
        )
        _health.install_gc_pause_hook()

    @property
    def port(self) -> int:
        return self._http.port

    def start(self) -> "EngineServer":
        self._http.start()
        return self

    def serve_forever(self) -> None:
        self._http.serve_forever()

    def shutdown(self) -> None:
        self._http.shutdown()
        # a still-running experiment's non-live arms are owned by the
        # ActiveExperiment, not the retained LRU — retire them first so
        # their device buffers are released below, not leaked
        active = self.api.clear_experiment()
        if active is not None:
            with self._retained_lock:
                for vid, dep in active.engines.items():
                    if dep is not self.api.deployed:
                        self._retained.setdefault(vid, dep)
        self.api.close()
        # free the retained rollback states' device buffers AND the
        # actively deployed instance's — tests and operators cycle many
        # servers per process, and a down server keeping factors
        # resident is exactly the residency the device ledger flags.
        # The active release waits out in-flight batches (bounded);
        # release() itself asserts the ledger invariant.
        with self._retained_lock:
            retained = list(self._retained.values())
            self._retained.clear()
        for dep in retained:
            dep.release(timeout_s=1.0)
        self.api.deployed.release(timeout_s=1.0)

    def retained_versions(self) -> List[str]:
        """The engine-instance ids of the retained (instant-rollback)
        serving states, oldest first."""
        with self._retained_lock:
            return list(self._retained)

    def swap_deployed(self, fresh: DeployedEngine) -> DeployedEngine:
        """Atomically swap ``fresh`` in behind the in-flight batch
        boundary (bind_deployed re-points the per-version metrics +
        pio_model_info; queries in flight keep the old snapshot) and
        retire the displaced DeployedEngine into the retained LRU.
        Returns the displaced engine — the promotion pipeline drains it
        explicitly; LRU evictees drain + release in the background."""
        with self._swap_lock:
            old = self.api.deployed
            self.api.bind_deployed(fresh)
            self._retire(old)
        return old

    def _retire(self, old: DeployedEngine) -> None:
        evicted: List[DeployedEngine] = []
        with self._retained_lock:
            # a bare /reload re-deploys a fresh copy of the same instance
            # id: the previously retained copy it displaces must still
            # drain+release, not silently drop to GC with its resident
            # buffers unaccounted
            displaced_twin = self._retained.pop(old.engine_instance.id, None)
            if displaced_twin is not None and displaced_twin is not old:
                evicted.append(displaced_twin)
            self._retained[old.engine_instance.id] = old
            while len(self._retained) > max(0, self.config.retained_states):
                evicted.append(self._retained.popitem(last=False)[1])
        for dep in evicted:
            threading.Thread(
                target=self._drain_and_release, args=(dep,), daemon=True,
                name="serving-drain",
            ).start()

    def _drain_and_release(self, dep: DeployedEngine) -> None:
        """Background eviction: wait for the last in-flight batch, then
        free the device-resident serving state. Watched by the
        ``serving-drain`` heartbeat — a wedged drain degrades /readyz
        instead of silently leaking HBM."""
        hb = _health.heartbeat(
            "serving-drain", deadline_s=self.DRAIN_DEADLINE_S
        )
        with hb.busy():
            drained = dep.drain(self.DRAIN_TIMEOUT_S, on_progress=hb.beat)
            released = dep.release(timeout_s=1.0)
        if not (drained and released):
            logger.warning(
                "evicted serving state %s did not drain cleanly "
                "(drained=%s released=%s); buffers free by refcount when "
                "the straggler batch resolves",
                dep.engine_instance.id, drained, released,
            )

    def reload(self, engine_instance_id: Optional[str] = None) -> str:
        """Swap serving state (reference MasterActor ReloadServer,
        CreateServer.scala:322-343). With ``engine_instance_id`` the
        swap is pinned to that exact instance (the promotion / fleet-
        convergence contract; a retained LRU hit swaps without touching
        storage); without it, the latest COMPLETED instance of the same
        engine is resolved — the reference's semantics. Returns the now-
        serving instance id; raises on failure with the old snapshot
        still serving (the /reload route turns that into a 500)."""
        current = self.api.deployed
        current_id = current.engine_instance.id
        if engine_instance_id is not None and engine_instance_id == current_id:
            return current_id  # idempotent: fleet-converge nudges are free
        fresh: Optional[DeployedEngine] = None
        if engine_instance_id is not None:
            with self._retained_lock:
                fresh = self._retained.pop(engine_instance_id, None)
        if fresh is None:
            inst = current.engine_instance
            fresh = DeployedEngine.from_storage(
                self.engine,
                self.storage,
                engine_instance_id=engine_instance_id,
                engine_id=(
                    inst.engine_id if engine_instance_id is None else None
                ),
                engine_version=(
                    inst.engine_version
                    if engine_instance_id is None
                    else None
                ),
                engine_variant=(
                    inst.engine_variant
                    if engine_instance_id is None
                    else None
                ),
                ctx=self._serving_ctx,
            )
        # NOTE: a bare /reload (no pinned id) that resolves "latest" to
        # the instance already serving still swaps in the fresh copy —
        # the reference ReloadServer's unconditional re-deploy, and the
        # residency regression gate in tests/test_retrieval.py. Only
        # PINNED reloads short-circuit (above): that is what makes the
        # fleet-convergence nudges free.
        new_id = fresh.engine_instance.id
        self.swap_deployed(fresh)
        logger.info("reloaded engine instance %s", new_id)
        return new_id

    # --- experimentation plane ---

    def start_experiment(self, spec) -> Dict[str, Any]:
        """Deploy every arm of ``spec`` warm and bind the experiment
        into the QueryAPI. Arms resolve in order: the live instance is
        reused as-is; a retained-LRU hit is popped out warm (the PR 13
        machinery — no store read, no recompile); anything else builds
        from storage onto the serving device slice. Idempotent per spec:
        re-posting the same experiment (a fleet-converge nudge or a
        restart) is a no-op."""
        with self._swap_lock:
            current = self.api._experiment
            if current is not None:
                if current.spec == spec:
                    return self.api.experiment_status()
                raise ValueError(
                    f"experiment {current.spec.name!r} is already running"
                )
            live = self.api.deployed
            live_id = live.engine_instance.id
            engines: Dict[str, DeployedEngine] = {}
            created: List[DeployedEngine] = []
            try:
                for vid in spec.variants:
                    if vid == live_id:
                        engines[vid] = live
                        continue
                    with self._retained_lock:
                        dep = self._retained.pop(vid, None)
                    if dep is None:
                        dep = DeployedEngine.from_storage(
                            self.engine,
                            self.storage,
                            engine_instance_id=vid,
                            ctx=self._serving_ctx,
                        )
                    engines[vid] = dep
                    created.append(dep)
            except Exception:
                # partial deploy must not leak device state
                for dep in created:
                    dep.release(timeout_s=1.0)
                raise
            self.api.set_experiment(
                _experiment.ActiveExperiment(spec, engines)
            )
            logger.info(
                "experiment %s started: variants=%s split=%s",
                spec.name, spec.variants, spec.split,
            )
            return self.api.experiment_status()

    def stop_experiment(
        self, winner: Optional[str] = None
    ) -> Dict[str, Any]:
        """Unbind the experiment. The winner (and, on a plain stop, every
        non-live arm) retires into the retained LRU — warm for the
        promotion pipeline's pinned ``/reload``; losing arms skip the
        LRU and go straight onto the background drain+release path, so
        their device state lands at a ledger-zero release."""
        with self._swap_lock:
            active = self.api.clear_experiment()
            if active is None:
                return {"stopped": False, "experiment": None}
            live_id = self.api.deployed.engine_instance.id
            drained: List[str] = []
            retained: List[str] = []
            for vid, dep in active.engines.items():
                if dep is self.api.deployed:
                    continue
                if winner is not None and vid != winner:
                    drained.append(vid)
                    threading.Thread(
                        target=self._drain_and_release, args=(dep,),
                        daemon=True, name="serving-drain",
                    ).start()
                else:
                    retained.append(vid)
                    self._retire(dep)
            logger.info(
                "experiment %s stopped: winner=%s drained=%s retained=%s",
                active.spec.name, winner, drained, retained,
            )
            return {
                "stopped": True,
                "experiment": active.spec.name,
                "winner": winner,
                "live": live_id,
                "drained": drained,
                "retained": retained,
            }


def create_server(
    engine: Engine,
    config: Optional[ServerConfig] = None,
    storage: Optional[Storage] = None,
) -> EngineServer:
    """Reference CreateServer.main (CreateServer.scala:110-195). Plugins
    are auto-discovered at launch (the reference's ServiceLoader pass,
    EngineServerPluginContext.scala:42-74)."""
    return EngineServer(
        engine,
        config,
        storage,
        plugin_context=EngineServerPluginContext.discover(),
    )
