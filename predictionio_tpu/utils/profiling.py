"""Per-phase timers + jax.profiler integration.

The reference's only observability is coarse serving-time bookkeeping
(CreateServer.scala:399-404) plus the Spark web UI (SURVEY.md §5 —
"plan for jax.profiler traces + per-phase timers as first-class"). This
module provides both:

- ``PhaseTimer``: named wall-clock phases with nesting, collected per
  workflow run and queryable/printable for run summaries;
- ``trace(dir)``: context manager around ``jax.profiler.trace`` emitting
  a TensorBoard-loadable device trace when a profile dir is set;
- :class:`ProfileCapture` + :func:`profile_route`: the on-demand,
  secret-gated ``POST /debug/profile?seconds=N`` capture every server
  exposes (``pio profile`` drives it) — same session machinery as
  ``trace``, so CLI- and HTTP-triggered captures are layout-identical.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import os
import threading
import time
from typing import Dict, Iterator, List, Optional

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class PhaseRecord:
    name: str
    seconds: float
    depth: int
    start: float  # perf_counter at phase entry — orders the summary
    # True for phases that ran CONCURRENTLY under another recorded phase
    # (the streaming pipeline's scan/fold/compile run under the train
    # phase's wall clock): excluded from wall-clock totals so the
    # summary's arithmetic stays honest.
    overlapped: bool = False


class PhaseTimer:
    """Collects named wall-clock phases (nested phases indent).

    Every timer owns a trace id (``utils.tracing``) and emits each phase
    as a span into the process trace buffer, nested by the phase stack —
    so a continuous-training round is ONE coherent trace from store poll
    to checkpoint, dumpable via any server's /debug/traces.json or
    ``pio trace`` beside the text summary."""

    def __init__(self):
        from predictionio_tpu.utils import tracing as _tracing

        self.records: List[PhaseRecord] = []
        self.notes: Dict[str, object] = {}
        self._depth = 0
        self.trace_id = _tracing.mint_trace_id()
        self._span_stack: List[str] = []

    def note(self, key: str, value) -> None:
        """Attach a non-duration annotation (cache outcomes, delta
        sizes) shown in the summary — last write per key wins."""
        self.notes[key] = value

    @contextlib.contextmanager
    def phase(self, name: str) -> Iterator[None]:
        from predictionio_tpu.utils import tracing as _tracing

        start = time.perf_counter()
        start_wall = time.time()
        # the span id is minted at ENTRY so nested phases can parent on
        # it even though spans are recorded (as completed) at exit
        span_id = _tracing.new_span_id()
        parent_id = self._span_stack[-1] if self._span_stack else None
        self._span_stack.append(span_id)
        self._depth += 1
        try:
            yield
        finally:
            self._depth -= 1
            self._span_stack.pop()
            elapsed = time.perf_counter() - start
            self.records.append(
                PhaseRecord(name, elapsed, self._depth, start)
            )
            _tracing.record_span(
                f"phase:{name}", self.trace_id, span_id=span_id,
                parent_id=parent_id, start_s=start_wall,
                duration_s=elapsed,
            )
            logger.info("phase %s: %.3fs", name, elapsed)

    def add(
        self, name: str, seconds: float, overlapped: bool = False
    ) -> None:
        """Record an externally-measured phase. ``overlapped=True``
        marks busy time that was hidden under another phase (pipelined
        work) rather than serial wall clock."""
        from predictionio_tpu.utils import tracing as _tracing

        self.records.append(
            PhaseRecord(
                name, seconds, self._depth + 1, time.perf_counter(),
                overlapped=overlapped,
            )
        )
        _tracing.record_span(
            f"phase:{name}", self.trace_id,
            parent_id=self._span_stack[-1] if self._span_stack else None,
            duration_s=seconds,
            attrs={"overlapped": True} if overlapped else None,
        )

    def totals(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for r in self.records:
            out[r.name] = out.get(r.name, 0.0) + r.seconds
        return out

    def overlapped_total(self) -> float:
        """Busy seconds that were hidden under other phases — the work
        the pipeline took OFF the wall clock."""
        return sum(r.seconds for r in self.records if r.overlapped)

    def summary(self) -> str:
        # chronological, parents before their children (same start order,
        # shallower first)
        ordered = sorted(self.records, key=lambda r: (r.start, r.depth))
        lines = [
            f"{'  ' * r.depth}{r.name}: {r.seconds:.3f}s"
            + (" [overlapped]" if r.overlapped else "")
            for r in ordered
        ]
        hidden = self.overlapped_total()
        if hidden:
            lines.append(
                f"(pipelining hid {hidden:.3f}s of host/compile work "
                "under the phases above)"
            )
        if self.notes:
            lines.append(
                "notes: "
                + " ".join(f"{k}={v}" for k, v in self.notes.items())
            )
        return "\n".join(lines)


# --- on-demand profiler capture (the device-observability round) ---
#
# One capture machinery for BOTH entry points: `pio train --profile-dir`
# (the trace() context manager below, driven by workflow_params'
# profile_dir) and the secret-gated `POST /debug/profile?seconds=N`
# endpoint every server exposes. Both funnel through _profiler_session,
# so a CLI-launched capture and an HTTP-triggered one produce IDENTICAL
# trace layouts (jax's plugins/profile/<run>/ tree) — before this
# round, the HTTP path simply did not exist and the jax.profiler hook
# only fired when a train run was launched with --profile-dir.

# serializes jax.profiler sessions process-wide: jax refuses nested /
# concurrent traces, so a training --profile-dir capture and an HTTP
# capture must take turns
_SESSION_LOCK = threading.Lock()


@contextlib.contextmanager
def _session_body(profile_dir: str, python: bool = False) -> Iterator[None]:
    """The jax.profiler session itself — callers MUST hold
    :data:`_SESSION_LOCK` (``_profiler_session`` blocks for it; the
    HTTP capture acquires it non-blockingly so a busy profiler answers
    409 instead of parking a route-pool worker).

    The Python tracer is OFF unless ``python`` asks for it: hooking
    every Python call stalls the process it traces for seconds (a
    traced server stops answering, a traced train gains ≈ 6 s). The
    host tracer stays on, so the program's own ``pio:`` annotations
    (utils/tracing.annotation, made only while a session of this module
    runs) and the runtime's host events are kept, on the device planes'
    clock."""
    import jax

    from predictionio_tpu.utils import tracing as _tracing

    os.makedirs(profile_dir, exist_ok=True)
    logger.info("writing jax profiler trace to %s", profile_dir)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 1 if python else 0
    with jax.profiler.trace(profile_dir, profiler_options=options):
        _tracing.set_capturing(True)
        try:
            yield
        finally:
            _tracing.set_capturing(False)


@contextlib.contextmanager
def _profiler_session(profile_dir: str) -> Iterator[None]:
    """THE code path that touches jax.profiler: makedirs + trace,
    serialized on the process-wide session lock."""
    with _SESSION_LOCK:
        with _session_body(profile_dir):
            yield


@contextlib.contextmanager
def trace(profile_dir: Optional[str]) -> Iterator[None]:
    """jax.profiler.trace around a block when profile_dir is set; no-op
    otherwise. View with TensorBoard's profile plugin or Perfetto.
    (The context-manager API over the shared capture machinery — the
    HTTP ``/debug/profile`` endpoint rides the same session path.)"""
    if not profile_dir:
        yield
        return
    with _profiler_session(profile_dir):
        yield


def _m_captures() -> object:
    from predictionio_tpu.utils import metrics as _metrics

    return _metrics.get_registry().counter(
        "pio_profile_captures_total",
        "On-demand profiler captures by outcome (ok / busy = a capture "
        "or --profile-dir session was already running / error)",
        labels=("outcome",),
    )


class ProfileCapture:
    """Bounded on-demand capture driver behind ``POST /debug/profile``.

    One capture at a time (jax.profiler cannot nest); the capture runs
    INLINE in the calling route-pool thread for ``seconds`` (clamped to
    :attr:`MAX_SECONDS`), zips the produced trace tree, and returns the
    archive base64-encoded in the JSON response (the HTTP adapters
    render JSON/str payloads only — no binary framing needed). The
    spool directory is capped: only the newest :attr:`MAX_SPOOLED`
    capture trees are kept on disk."""

    MAX_SECONDS = 120.0
    MAX_SPOOLED = 4

    def __init__(self, spool_dir: Optional[str] = None):
        self._spool_dir = spool_dir
        self._lock = threading.Lock()
        self._busy = False
        self._last: Optional[dict] = None

    @property
    def spool_dir(self) -> str:
        if self._spool_dir is None:
            import tempfile

            self._spool_dir = os.path.join(
                tempfile.gettempdir(), "pio-profile-spool"
            )
        return self._spool_dir

    def status(self) -> dict:
        with self._lock:
            last = None
            if self._last is not None:
                last = {
                    k: v
                    for k, v in self._last.items()
                    if k != "archive_b64"
                }
            return {"running": self._busy, "last": last}

    def last(self) -> Optional[dict]:
        with self._lock:
            return self._last

    def capture(
        self, seconds: float, python: bool = False
    ) -> "tuple[int, dict]":
        """Run one bounded capture; returns ``(http_status, payload)``.
        409 while another capture (or a --profile-dir training session)
        holds the profiler; the payload carries the zipped trace tree
        base64-encoded plus its file listing. ``python`` turns the
        Python tracer on (frames of every call, at the price of
        stalling the server for the capture)."""
        seconds = max(0.1, min(float(seconds), self.MAX_SECONDS))
        with self._lock:
            if self._busy:
                _m_captures().labels(outcome="busy").inc()
                return 409, {"message": "a profile capture is already running"}
            self._busy = True
        try:
            # non-blocking probe AND hold: a --profile-dir training
            # session owning the lock answers 409 immediately, and the
            # lock stays held through the capture so a session starting
            # in between cannot park this route-pool worker
            if not _SESSION_LOCK.acquire(blocking=False):
                _m_captures().labels(outcome="busy").inc()
                return 409, {
                    "message": "a --profile-dir profiler session is active"
                }
            started = time.time()
            cap_dir = os.path.join(
                self.spool_dir, f"capture-{int(started * 1000)}"
            )
            try:
                with _session_body(cap_dir, python):
                    time.sleep(seconds)
                payload = self._archive(cap_dir, started, seconds)
            except Exception as e:
                logger.exception("profile capture failed")
                _m_captures().labels(outcome="error").inc()
                return 500, {"message": f"capture failed: {e}"}
            finally:
                _SESSION_LOCK.release()
            self._trim_spool()
            with self._lock:
                self._last = payload
            _m_captures().labels(outcome="ok").inc()
            return 200, payload
        finally:
            with self._lock:
                self._busy = False

    def _archive(self, cap_dir: str, started: float, seconds: float) -> dict:
        import base64
        import io
        import zipfile

        names: list = []
        buf = io.BytesIO()
        with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as zf:
            for root, _dirs, files in os.walk(cap_dir):
                for name in sorted(files):
                    full = os.path.join(root, name)
                    rel = os.path.relpath(full, cap_dir)
                    zf.write(full, rel)
                    names.append(rel)
        data = buf.getvalue()
        return {
            "startedAt": started,
            "seconds": seconds,
            "dir": cap_dir,
            "files": names,
            "archiveBytes": len(data),
            "archive_b64": base64.b64encode(data).decode("ascii"),
        }

    def _trim_spool(self) -> None:
        try:
            caps = sorted(
                d
                for d in os.listdir(self.spool_dir)
                if d.startswith("capture-")
            )
        except OSError:
            return
        import shutil

        for stale in caps[: -self.MAX_SPOOLED]:
            shutil.rmtree(
                os.path.join(self.spool_dir, stale), ignore_errors=True
            )


# THE process-global capture driver (all three servers' /debug/profile
# routes share it — one profiler, one spool).
_CAPTURE = ProfileCapture()


def get_capture() -> ProfileCapture:
    return _CAPTURE


def profile_route(
    method: str, query, authorized: bool
) -> "tuple[int, dict]":
    """The shared ``/debug/profile`` request core (all three servers
    route here after their own auth gate, like http.traces_payload):
    ``POST ?seconds=N`` runs one bounded capture and returns the
    archive (``&python=1`` with the Python tracer on); ``GET`` returns
    capture status (and the last archive with ``?archive=1``)."""
    if not authorized:
        return 401, {"message": "invalid or missing credentials"}
    cap = get_capture()
    if method == "POST":
        raw = (query or {}).get("seconds", "2")
        try:
            seconds = float(raw)
        except (TypeError, ValueError):
            return 400, {"message": f"invalid seconds {raw!r}"}
        return cap.capture(
            seconds, python=(query or {}).get("python") == "1"
        )
    if method == "GET":
        if (query or {}).get("archive"):
            last = cap.last()
            if last is None:
                return 404, {"message": "no capture taken yet"}
            return 200, last
        return 200, cap.status()
    return 405, {"message": "Method not allowed."}
