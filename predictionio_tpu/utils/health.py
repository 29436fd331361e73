"""Runtime health: the heartbeat/watchdog registry behind /healthz+/readyz.

Every server in this package is a frontend over a set of background
daemons — sqlite group-commit committer threads, the segment compactor,
the continuous-training loop, the engine server's batching executor and
feedback drainer. A fleet operator (or the zero-downtime hot-swap loop
the ROADMAP plans) needs two different answers from each process:

- **liveness** (``GET /healthz``): is the process serving at all? Always
  200 while the frontend can run the handler — restart-worthy only when
  it stops answering.
- **readiness** (``GET /readyz``): should traffic be routed here NOW?
  503 when the model/store is unavailable or a background daemon is
  *stalled* — registered, mid-work, and silent past its deadline (a
  wedged COMMIT, a hung compaction round). Idle daemons are healthy by
  definition: a committer parked on an empty queue has nothing to prove.

The registry is process-global (one process = one fleet worker, exactly
like utils/metrics.py, and this module is a sanctioned home for that
module-level observability state — tests/test_lint.py polices the rest
of the package). Daemons register a :class:`Heartbeat` and wrap each
unit of work in ``with hb.busy():`` (or call ``hb.beat()`` inside long
rounds); ``readiness()`` folds every registered heartbeat plus
server-supplied probes into one verdict. Beats are lock-cheap (a float
store + a counter inc), far off any hot path's noise floor.
"""

from __future__ import annotations

import contextlib
import gc
import os
import threading
import time
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

from predictionio_tpu.utils import metrics as _metrics
from predictionio_tpu.utils import tracing as _tracing

__all__ = [
    "Heartbeat",
    "heartbeat",
    "unregister",
    "heartbeats",
    "liveness",
    "readiness",
    "TTLProbe",
    "record_memory_gauges",
]

_PROCESS_START_MONOTONIC = time.monotonic()


def _beats_counter() -> "_metrics.Counter":
    return _metrics.get_registry().counter(
        "pio_heartbeat_beats_total",
        "Heartbeats recorded by background daemons",
        labels=("daemon",),
    )


def _stalled_gauge() -> "_metrics.Gauge":
    return _metrics.get_registry().gauge(
        "pio_daemons_stalled",
        "Registered background daemons currently stalled past deadline",
    )


class Heartbeat:
    """One daemon's watchdog state.

    ``busy()`` brackets a unit of work; ``stalled()`` is True only when
    the daemon is INSIDE a unit and has not beaten for ``deadline_s`` —
    so an idle daemon never degrades readiness, and recovery (the unit
    finally completing, or beating mid-round) clears the stall without
    any explicit reset. ``deadline_s`` is mutable so tests (and
    operators via server config) can tighten it.
    """

    def __init__(self, name: str, deadline_s: float):
        self.name = name
        self.deadline_s = float(deadline_s)
        self._lock = threading.Lock()
        self._last = time.monotonic()
        self._busy = 0
        self._counter = _beats_counter().labels(daemon=name)

    def beat(self) -> None:
        with self._lock:
            self._last = time.monotonic()
        self._counter.inc()

    @contextlib.contextmanager
    def busy(self) -> Iterator[None]:
        """Mark one unit of work in flight; beats on entry and exit so
        back-to-back units never look stalled."""
        with self._lock:
            self._busy += 1
            self._last = time.monotonic()
        self._counter.inc()
        try:
            yield
        finally:
            with self._lock:
                self._busy -= 1
                self._last = time.monotonic()

    def stalled(self, now: Optional[float] = None) -> bool:
        now = time.monotonic() if now is None else now
        with self._lock:
            return self._busy > 0 and (now - self._last) > self.deadline_s

    def status(self, now: Optional[float] = None) -> dict:
        now = time.monotonic() if now is None else now
        with self._lock:
            busy, last = self._busy, self._last
        age = now - last
        return {
            "busy": busy,
            "sinceLastBeatSec": round(age, 3),
            "deadlineSec": self.deadline_s,
            "stalled": busy > 0 and age > self.deadline_s,
        }


_HEARTBEATS: Dict[str, Heartbeat] = {}
_HEARTBEATS_LOCK = threading.Lock()


def heartbeat(name: str, deadline_s: float = 60.0) -> Heartbeat:
    """Get-or-create the heartbeat named ``name``. Daemons that share a
    name (two executors of one process) share the heartbeat — either
    one stalling degrades readiness, which is the verdict an operator
    wants for the whole process. The first registration pins the
    deadline; adjust ``hb.deadline_s`` directly to change it."""
    hb = _HEARTBEATS.get(name)
    if hb is None:
        with _HEARTBEATS_LOCK:
            hb = _HEARTBEATS.get(name)
            if hb is None:
                hb = Heartbeat(name, deadline_s)
                _HEARTBEATS[name] = hb
    return hb


def unregister(name: str) -> None:
    """Drop a heartbeat (clean daemon shutdown). Optional for busy-mode
    daemons — an idle leftover is healthy — but polite in processes that
    cycle many servers (tests)."""
    with _HEARTBEATS_LOCK:
        _HEARTBEATS.pop(name, None)


def heartbeats() -> List[Heartbeat]:
    with _HEARTBEATS_LOCK:
        return [_HEARTBEATS[k] for k in sorted(_HEARTBEATS)]


def liveness() -> dict:
    """The /healthz payload: cheap, allocation-light, never consults
    storage or daemons — liveness must answer even when readiness is
    degraded, or the orchestrator restarts a process that only needed
    traffic drained."""
    return {
        "status": "ok",
        "uptimeSec": round(time.monotonic() - _PROCESS_START_MONOTONIC, 3),
    }


class TTLProbe:
    """A readiness probe with a small result cache, so an unauthenticated
    /readyz poller cannot turn the probe's storage read into a
    request-rate storage load (the same guard CachedCompactionStatus
    applies to the compaction stats)."""

    def __init__(self, name: str, fn: Callable[[], None], ttl_s: float = 1.0):
        self.name = name
        self._fn = fn
        self._ttl_s = float(ttl_s)
        self._lock = threading.Lock()
        self._cached: Optional[Tuple[float, bool, str]] = None

    def check(self) -> Tuple[bool, str]:
        now = time.monotonic()
        with self._lock:
            cached = self._cached
            if cached is not None and now - cached[0] < self._ttl_s:
                return cached[1], cached[2]
        try:
            self._fn()
            ok, detail = True, "ok"
        except Exception as e:
            ok, detail = False, f"{type(e).__name__}: {e}"
        with self._lock:
            self._cached = (now, ok, detail)
        return ok, detail


def readiness(
    probes: Sequence[TTLProbe] = (),
) -> Tuple[bool, dict]:
    """The /readyz verdict: every server-supplied probe passes AND no
    registered daemon is stalled past its deadline. Returns ``(ok,
    payload)``; the payload names each failing component so the 503 is
    actionable without log spelunking."""
    now = time.monotonic()
    components: Dict[str, dict] = {}
    ok = True
    stalled = 0
    for hb in heartbeats():
        s = hb.status(now)
        if s["stalled"]:
            ok = False
            stalled += 1
            components[hb.name] = s
    _stalled_gauge().set(stalled)
    probe_out: Dict[str, str] = {}
    for p in probes:
        p_ok, detail = p.check()
        probe_out[p.name] = detail
        if not p_ok:
            ok = False
    payload = {
        "status": "ok" if ok else "unavailable",
        "daemons": len(heartbeats()),
        "stalledDaemons": components,
        "probes": probe_out,
    }
    return ok, payload


# --- process/device memory gauges (training-round resource telemetry) ---


def record_memory_gauges() -> dict:
    """Set ``pio_device_memory_bytes{device,stat}`` from each addressable
    device's ``memory_stats()`` (backends without the API — the CPU
    client — report nothing) and ``pio_host_rss_bytes`` from
    /proc/self/status (RSS fallback; absent off-Linux). Called once per
    training round and at every engine-server ``/metrics`` scrape —
    cheap, but not a hot-path instrument. Returns what it recorded (the
    round report includes it). ``peak_bytes_in_use`` leaves a running
    program's temporaries out; the ``*_reserved`` stats, where the
    backend gives them, are what the allocator took from the device."""
    reg = _metrics.get_registry()
    out: dict = {}
    try:
        import jax

        g = reg.gauge(
            "pio_device_memory_bytes",
            "Device memory from device.memory_stats(), where the backend "
            "provides it",
            labels=("device", "stat"),
        )
        for d in jax.local_devices():
            try:
                ms = d.memory_stats()
            except Exception:
                ms = None
            if not ms:
                continue
            for stat in (
                "bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                "bytes_reserved", "peak_bytes_reserved",
            ):
                if stat in ms:
                    g.labels(device=str(d.id), stat=stat).set(float(ms[stat]))
                    out[f"device{d.id}.{stat}"] = int(ms[stat])
    except Exception:
        pass  # memory telemetry must never fail a training round
    rss = _read_rss_bytes()
    if rss is not None:
        reg.gauge(
            "pio_host_rss_bytes", "Resident set size of this process"
        ).set(float(rss))
        out["host_rss_bytes"] = rss
    return out


# --- interpreter pauses: the cyclic collector ---


class _GcPauses:
    """A ``gc.callbacks`` entry: every collection's start → stop goes
    to ``pio_gc_pause_seconds{generation}``, a full (generation 2) one
    to ``pio_gc_full_pause_seconds`` as well, and a ``pio:gc`` profiler
    annotation is open across generations 1 and 2 (generation 0 runs
    too often to annotate). A collection stops every thread of the
    process — it runs under the interpreter lock — so its pause is a
    pause of every request in flight, and of every request waiting to
    be read. The full ones are the long ones, ≈ 50 ms each on a serving
    cell's heap (PERF.md section 5), and they are one pause in a
    hundred: a quantile over all generations cannot see them, hence
    their own family.

    The hook never waits for a lock. A collection starts on whichever
    thread allocated last, and that may be a ``/metrics`` scrape inside
    the very child's ``_render``, holding the lock ``observe`` would
    wait for: the thread would wait for itself, and the interpreter
    would never collect again. A sample whose child is busy waits in
    ``_pending`` for the next collection instead (the interpreter runs
    one collection at a time, so the hook's own state has one writer)."""

    def __init__(self):
        reg = _metrics.get_registry()
        by_generation = reg.histogram(
            "pio_gc_pause_seconds",
            "Cyclic garbage collections of this process, start to stop, "
            "by generation",
            labels=("generation",),
            buckets=_metrics.LATENCY_BUCKETS_S,
        )
        full = reg.histogram(
            "pio_gc_full_pause_seconds",
            "Full (generation 2) garbage collections of this process, "
            "start to stop",
            buckets=_metrics.LATENCY_BUCKETS_S,
        )
        # the children a collection of each generation is observed in
        self._children = [
            (by_generation.labels(generation="0"),),
            (by_generation.labels(generation="1"),),
            (by_generation.labels(generation="2"), full.labels()),
        ]
        self._pending: list = []  # (child, seconds) a busy child missed
        self._t0 = 0.0
        self._annotation = None

    def __call__(self, phase: str, info: dict) -> None:
        generation = info["generation"]
        if phase == "start":
            if generation:
                self._annotation = _tracing.annotation("gc")
                self._annotation.__enter__()
            self._t0 = time.perf_counter()
        elif self._t0:
            seconds = time.perf_counter() - self._t0
            self._t0 = 0.0
            if self._annotation is not None:
                self._annotation.__exit__(None, None, None)
                self._annotation = None
            missed = self._pending
            missed.extend((c, seconds) for c in self._children[generation])
            self._pending = [
                (c, s) for c, s in missed if not c.try_observe(s)
            ]


def install_gc_pause_hook() -> None:
    """Time this process's garbage collections from now on (once a
    process, however many servers it starts)."""
    if not any(isinstance(cb, _GcPauses) for cb in gc.callbacks):
        gc.callbacks.append(_GcPauses())


def _read_rss_bytes() -> Optional[int]:
    try:
        with open("/proc/self/statm") as f:
            fields = f.read().split()
        return int(fields[1]) * os.sysconf("SC_PAGE_SIZE")
    except Exception:
        return None
