"""Lightweight end-to-end request tracing.

A trace id accepted via the ``X-PIO-Trace-Id`` header at an ingest or
serving entry point (the header is the opt-in: untraced hot-path
requests record nothing, so traced requests can't be evicted by bulk
traffic and the hot path never touches the span ring's lock) is
propagated — explicitly through the engine server's batching executor,
via a ``contextvars`` context through the group-commit committer and
the storage-gateway RPC client — so one request's path is a chain of
spans. Training runs mint their own trace per ``PhaseTimer``:

    serving:  http → batch → {queue_wait, slot_wait, predict, finish}
              → handoff → respond (the transport's histograms)
    ingest:   http → insert → group-commit-flush
    remote:   http → rpc:<dao>.<method> (gateway process) → flush

Spans land in a bounded process-global ring buffer (deque, oldest
evicted first) dumpable via ``GET /debug/traces.json`` on every server
(access-key gated) and ``pio trace``. This is deliberately NOT a
distributed-tracing stack: no sampling config, no exporters, no clock
sync — just enough to answer "where did this request's time go" across
the subsystems this repo actually has. For device-side timelines, wrap
the training call in ``utils.profiling.trace`` (jax.profiler).

:func:`stage` and :func:`annotation` put the program's own host phases
on the profiler's clock: while a capture of ``utils/profiling`` runs, a
``jax.profiler.TraceAnnotation`` named ``pio:<name>`` lands on the
capture's host planes, which share the device planes' clock, so a
device-idle gap can be put down to the host phase that was open during
it (``benchmarks/host_gaps.py``). The batch stages are ``BATCH_STAGES``
(``upload``, the operand's transfer, nests inside ``dispatch``); the
event loop's ``pio:respond`` is an annotation of its own. Stages nest: a
stage counts toward the batch's staged total only at depth 0, so what
``predict`` spends outside every stage is one number
(``pio_serving_batch_unstaged_seconds``).

Like utils/metrics.py, this module is a sanctioned home for
module-level observability state (tests/test_lint.py polices the rest
of the package).
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import secrets
import threading
import time
from typing import Dict, Iterator, List, NamedTuple, Optional

__all__ = [
    "TRACE_HEADER",
    "PARENT_HEADER",
    "TraceContext",
    "mint_trace_id",
    "new_span_id",
    "from_headers",
    "current",
    "use",
    "set_capturing",
    "annotation",
    "stage",
    "stage_totals",
    "StageTotals",
    "BATCH_STAGES",
    "record_span",
    "span",
    "dump",
    "dump_since",
    "high_water",
    "clear",
    "format_trace",
]

TRACE_HEADER = "X-PIO-Trace-Id"
PARENT_HEADER = "X-PIO-Parent-Span"

# completed spans kept for /debug/traces.json; oldest evicted first
MAX_SPANS = 4096

_ID_RE_MAX = 64  # accepted header ids are clamped to this many chars


class TraceContext(NamedTuple):
    """What propagates: the trace id plus the span id of the caller
    (the parent of whatever span the callee records)."""

    trace_id: str
    span_id: str


def mint_trace_id() -> str:
    return secrets.token_hex(8)


def new_span_id() -> str:
    return secrets.token_hex(4)


def _sanitize(raw: str) -> str:
    """Header-supplied ids go into JSON dumps and log lines verbatim —
    keep them printable and bounded."""
    cleaned = "".join(c for c in raw if c.isalnum() or c in "-_")
    return cleaned[:_ID_RE_MAX]


def from_headers(
    headers: Optional[Dict[str, str]],
) -> "tuple[TraceContext, Optional[str]]":
    """Trace context for one inbound request: the ``X-PIO-Trace-Id``
    header when present (client-chosen correlation id), a fresh mint
    otherwise. Returns ``(ctx, inbound_parent_span_id)``: ``ctx.span_id``
    is the id the entry-point span records under (children chain on it);
    the inbound parent — the remote caller's span on a cross-process hop
    — becomes the entry span's ``parentId``."""
    trace_id = ""
    parent = ""
    if headers:
        trace_id = _sanitize(headers.get(TRACE_HEADER.lower(), "") or "")
        parent = _sanitize(headers.get(PARENT_HEADER.lower(), "") or "")
    if not trace_id:
        trace_id = mint_trace_id()
    return TraceContext(trace_id, new_span_id()), (parent or None)


# the contextvar carries the trace across same-thread call stacks
# (event-server insert -> sqlite committer submit, storage client RPCs);
# cross-THREAD propagation (the batching executor, the committer's flush
# thread) is explicit — items carry their TraceContext.
_CURRENT: "contextvars.ContextVar[Optional[TraceContext]]" = (
    contextvars.ContextVar("pio_trace", default=None)
)


def current() -> Optional[TraceContext]:
    return _CURRENT.get()


@contextlib.contextmanager
def use(ctx: Optional[TraceContext]) -> Iterator[None]:
    """Bind ``ctx`` as the ambient trace for the block (no-op on None)."""
    token = _CURRENT.set(ctx)
    try:
        yield
    finally:
        _CURRENT.reset(token)


_NO_ANNOTATION = contextlib.nullcontext()
# True while a profiler session of utils/profiling runs (its
# _session_body flips it). An annotation made at any other time would
# land nowhere, and on the serving path even a no-op one at every stage
# is Python that the serve thread runs under the interpreter lock
# (PERF.md section 6, PR 26: it showed in the median)
_CAPTURING = [False]


def set_capturing(on: bool) -> None:
    _CAPTURING[0] = on


def annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` named ``pio:<name>`` for the
    block while a capture of ``utils/profiling`` runs: a host event on
    the capture's timeline. At any other time nothing (and no import of
    JAX)."""
    if not _CAPTURING[0]:
        return _NO_ANNOTATION
    import jax

    return jax.profiler.TraceAnnotation("pio:" + name)


# the host phases of one serving batch that stage() names: the engine
# server's executor has one ``pio_serving_batch_<stage>_seconds`` family
# for each, and engines bracket their serving path with these constants
HOST_PREP = "host_prep"
DISPATCH = "dispatch"
DEVICE_WAIT = "device_wait"
BUILD = "build"
# carved out of host prep by engines that read the event store and
# assemble candidacy lists (models/ecommerce): the store reads and the
# id lookups, list assembly and pad that the BATCH still spends serial
# time on. Where a query is prepared at its arrival
# (BaseAlgorithm.prepare_query), that is the residue: the serve
# thread's wait for a preparation still running (store read: a
# preparation is a read before anything else), a preparation it runs
# inline, and the batch's own stacking and padding
STORE_READ = "store_read"
MASK_PREP = "mask_prep"
# the host-to-device transfer of a batch's query operand
# (``jax.device_put``), nested inside ``dispatch`` so that dispatch keeps
# the upload and the program's launch together and this splits them
UPLOAD = "upload"
# the quantized retrieval tier's host refine (ops/retrieval.py
# ``_refine_exact``): the device's shortlist rescored against the
# original float32 rows, which may be a file mapped into memory
REFINE = "refine"
# a row-sharded retriever's cross-shard merge (ops/retrieval.py
# ``_merge_candidates``): the dispatch of the program that takes every
# shard's candidates across the sharded -> replicated hop to one top-n
MERGE = "merge"
BATCH_STAGES = (
    HOST_PREP, DISPATCH, DEVICE_WAIT, BUILD, STORE_READ, MASK_PREP, REFINE,
    MERGE, UPLOAD,
)

# the per-batch accumulator of stage() durations, bound by the engine
# server's executor for the length of one serve_batch
_STAGES: "contextvars.ContextVar[Optional[StageTotals]]" = (
    contextvars.ContextVar("pio_stages", default=None)
)


class StageTotals(dict):
    """``{stage name: seconds}`` of one batch, with ``staged``: the
    seconds of the stages entered at depth 0 (``depth``: stages open now
    on the thread that bound it), so a nested stage adds under its own
    name and not twice to what the batch spent inside stages."""

    __slots__ = ("staged", "depth")

    def __init__(self) -> None:
        super().__init__()
        self.staged = 0.0
        self.depth = 0


class stage_totals:
    """Bind a fresh :class:`StageTotals` for the block (``with
    stage_totals() as totals``); every :func:`stage` entered on this
    thread inside it adds its duration there."""

    __slots__ = ("_token",)

    def __enter__(self) -> StageTotals:
        totals = StageTotals()
        self._token = _STAGES.set(totals)
        return totals

    def __exit__(self, *exc) -> None:
        _STAGES.reset(self._token)


class stage:
    """One named host phase of a serving batch (one of
    ``BATCH_STAGES``): a ``pio:<name>`` annotation while a profiler
    capture runs, and, inside :func:`stage_totals`, its duration added
    under its name (a stage entered twice in a batch adds up) and, at
    depth 0 only, to the batch's ``staged`` seconds. Outside a serving
    batch (warm-up, eval, a single ``recommend``) it is the annotation
    alone."""

    __slots__ = ("name", "_annotation", "_t0", "_totals")

    def __init__(self, name: str):
        assert name in BATCH_STAGES, name
        self.name = name

    def __enter__(self) -> "stage":
        # a batch enters nine of these on the serve thread: with no
        # capture running not even the shared no-op is entered
        self._annotation = None
        if _CAPTURING[0]:
            self._annotation = annotation(self.name)
            self._annotation.__enter__()
        totals = self._totals = _STAGES.get()
        if totals is not None:
            totals.depth += 1
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        elapsed = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        totals = self._totals
        if totals is not None:
            totals[self.name] = totals.get(self.name, 0.0) + elapsed
            totals.depth -= 1
            if totals.depth == 0:
                totals.staged += elapsed


_SPANS: "collections.deque" = collections.deque(maxlen=MAX_SPANS)
_SPANS_LOCK = threading.Lock()
# monotonic per-process span sequence: every recorded span gets the next
# value as its ``seq`` field, so a remote consumer (the telemetry
# collector, utils/telemetry.py) can pull the ring INCREMENTALLY with
# ``?since=<seq>`` instead of re-downloading all 4096 spans per poll.
# The counter never resets within a process; a fresh process starts at 0
# (the collector treats a high-water mark BELOW its cursor as a restart
# and re-pulls from scratch).
_SEQ = [0]


def record_span(
    name: str,
    trace_id: str,
    span_id: Optional[str] = None,
    parent_id: Optional[str] = None,
    start_s: Optional[float] = None,
    duration_s: float = 0.0,
    attrs: Optional[dict] = None,
) -> str:
    """Append one completed span to the ring buffer. ``start_s`` is
    epoch seconds (wall clock; defaults to now - duration)."""
    sid = span_id or new_span_id()
    now = time.time()
    entry = {
        "traceId": trace_id,
        "spanId": sid,
        "parentId": parent_id,
        "name": name,
        "startMs": round(
            ((now - duration_s) if start_s is None else start_s) * 1000.0, 3
        ),
        "durationMs": round(duration_s * 1000.0, 3),
    }
    if attrs:
        entry["attrs"] = attrs
    with _SPANS_LOCK:
        _SEQ[0] += 1
        entry["seq"] = _SEQ[0]
        _SPANS.append(entry)
    return sid


@contextlib.contextmanager
def span(
    name: str,
    ctx: Optional[TraceContext] = None,
    attrs: Optional[dict] = None,
) -> Iterator[Optional[TraceContext]]:
    """Record a span around a block, parented on ``ctx`` (or the ambient
    context). The child context becomes the AMBIENT context for the
    block, so nested subsystems (committer submit, gateway RPC client)
    chain under it without explicit plumbing. No-op (yields None) when
    there is no trace."""
    parent = ctx if ctx is not None else current()
    if parent is None:
        yield None
        return
    child = TraceContext(parent.trace_id, new_span_id())
    token = _CURRENT.set(child)
    t0 = time.time()
    try:
        yield child
    finally:
        _CURRENT.reset(token)
        record_span(
            name,
            parent.trace_id,
            span_id=child.span_id,
            parent_id=parent.span_id,
            start_s=t0,
            duration_s=time.time() - t0,
            attrs=attrs,
        )


def dump(
    trace_id: Optional[str] = None, limit: int = MAX_SPANS
) -> List[dict]:
    """Spans (oldest first), optionally filtered to one trace. The
    filter is sanitized the same way inbound header ids are, so a
    client-chosen id with stripped characters still matches the id its
    spans were recorded under."""
    with _SPANS_LOCK:
        spans = list(_SPANS)
    if trace_id:
        trace_id = _sanitize(trace_id)
        spans = [s for s in spans if s["traceId"] == trace_id]
    return spans[-limit:]


def high_water() -> int:
    """The newest recorded span's sequence number (0 before any span)."""
    with _SPANS_LOCK:
        return _SEQ[0]


def dump_since(
    since: int,
    limit: int = MAX_SPANS,
    trace_id: Optional[str] = None,
) -> "tuple[List[dict], int]":
    """Incremental dump: ``(spans with seq > since, high-water mark)``.

    The cursor contract behind ``/debug/traces.json?since=<seq>``: a
    consumer feeds back the returned high-water mark on its next pull
    and only ever downloads new spans. ``since=0`` is the full ring
    (same content as :func:`dump`), and the high-water mark advances
    even when the matching spans were already evicted — the consumer's
    cursor never sticks behind a burst."""
    with _SPANS_LOCK:
        hwm = _SEQ[0]
        spans = [s for s in _SPANS if s["seq"] > since]
    if trace_id:
        trace_id = _sanitize(trace_id)
        spans = [s for s in spans if s["traceId"] == trace_id]
    return spans[-limit:], hwm


def clear() -> None:
    with _SPANS_LOCK:
        _SPANS.clear()
        _SEQ[0] = 0


def format_trace(spans: List[dict]) -> str:
    """Indent spans under their parents (the ``pio trace`` renderer).
    Orphans (parent evicted from the ring) print at the root."""
    by_parent: Dict[Optional[str], List[dict]] = {}
    ids = {s["spanId"] for s in spans}
    for s in sorted(spans, key=lambda x: x["startMs"]):
        parent = s.get("parentId")
        by_parent.setdefault(parent if parent in ids else None, []).append(s)

    lines: List[str] = []

    def walk(parent: Optional[str], depth: int) -> None:
        for s in by_parent.get(parent, []):
            attrs = s.get("attrs")
            lines.append(
                f"{'  ' * depth}{s['name']}: {s['durationMs']:.3f}ms"
                + (f"  {attrs}" if attrs else "")
            )
            walk(s["spanId"], depth + 1)

    walk(None, 0)
    return "\n".join(lines)
