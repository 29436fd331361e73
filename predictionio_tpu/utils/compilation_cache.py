"""Persistent XLA compilation cache + executable-cache accounting.

No reference analog — the reference's JVM/Spark substrate has no
compilation step, while every first train/eval/serve here pays an XLA
compile. Persisting compiled executables across processes removes that
cost from every run after the first: `pio train` today, redeploys, repeated tuning sweeps,
and engine-server restarts all reuse yesterday's executables as long as
shapes (bucketed — ops/als.py pack_segments) and the jax/XLA version
match. JAX keys cache entries by program + compile options, so reuse is
always sound.

Layout — one rule: if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own
handling of it stands and this module sets no directory; otherwise the
cache is ``<checkout>/.jax_cache`` (beside the package, gitignored). The
directory is part of every cache key, so it never derives from a temp
name, a pid, the time, or per-run storage settings — a path that moves
never hits. ``JAX_ENABLE_COMPILATION_CACHE=false`` (JAX's own switch)
disables it.

**Executable-cache accounting (the device-observability round).** The
framework's in-memory executable caches — the ALS geometry-bucket
ladder, the retrieval pow2 top-k/width ladder, the serving top-k tiers
— were counted ad hoc (``pio_als_compile_total``) or not at all, and a
compile that happened INSIDE a serving batch (the p99 killer) was
indistinguishable from a deploy-time warm-up compile. Every cache now
reports through :func:`record_executable_compile`:

- ``pio_executable_cache_compiles_total{cache}`` /
  ``…_compile_seconds_total`` count compiles and their wall-clock per
  named cache; ``pio_executable_cache_entries`` /
  ``pio_executable_cache_bytes`` (cache=``persistent``) track the
  on-disk persistent cache (:func:`persistent_cache_stats`).
- Sites that must never compile — a live serving batch, an ingest
  flush — wrap their work in :func:`compile_site`; a compile recorded
  with an ambient site increments ``pio_cold_compiles_total{site}``,
  records a ``compile:<cache>`` span under the ambient trace
  (utils/tracing.py), and lands in the site's drainable event list so
  the serving executor can annotate the batch's ``predict`` span. A
  p99 spike is then attributable to "warm ladder missed width 128"
  straight from ``pio trace``.
"""

from __future__ import annotations

import contextlib
import contextvars
import logging
import os
import threading
from typing import Dict, Iterator, List, Optional

from predictionio_tpu.utils import metrics as _metrics

logger = logging.getLogger(__name__)

_configured = False


# --- executable-cache accounting ---


def _m_entries() -> "_metrics.Gauge":
    return _metrics.get_registry().gauge(
        "pio_executable_cache_entries",
        "Entries currently held by the persistent on-disk XLA cache "
        "(cache='persistent'; refreshed per scrape from a directory "
        "scan). In-memory ladders report compiles, not held entries — "
        "instance churn would make a held-entries gauge for them lie",
        labels=("cache",),
    )


def _m_compiles() -> "_metrics.Counter":
    return _metrics.get_registry().counter(
        "pio_executable_cache_compiles_total",
        "Executable compiles recorded per named cache (lifetime; "
        "per-instance ladders re-compile after /reload churn, so this "
        "counts work done, not entries held)",
        labels=("cache",),
    )


def _m_compile_seconds() -> "_metrics.Counter":
    return _metrics.get_registry().counter(
        "pio_executable_cache_compile_seconds_total",
        "Cumulative compile wall-clock per named executable cache",
        labels=("cache",),
    )


def _m_cache_bytes() -> "_metrics.Gauge":
    return _metrics.get_registry().gauge(
        "pio_executable_cache_bytes",
        "On-disk bytes of the persistent XLA compilation cache "
        "(cache='persistent'; in-memory caches report entries/seconds "
        "only)",
        labels=("cache",),
    )


def _m_cold() -> "_metrics.Counter":
    return _metrics.get_registry().counter(
        "pio_cold_compiles_total",
        "Compiles that happened inside a latency-critical site (a live "
        "serving batch, an ingest flush) instead of at warm-up — each "
        "one is tail latency a warm ladder should have absorbed",
        labels=("site",),
    )


# the ambient compile site + its per-site event list. The list is the
# hand-off to the serving executor: drain_compile_events() after the
# batch returns the compiles that hit THIS batch, for span annotation.
_SITE: "contextvars.ContextVar[Optional[str]]" = contextvars.ContextVar(
    "pio_compile_site", default=None
)
_SITE_EVENTS: "contextvars.ContextVar[Optional[list]]" = (
    contextvars.ContextVar("pio_compile_events", default=None)
)


@contextlib.contextmanager
def compile_site(site: str) -> Iterator[None]:
    """Declare the enclosed work a latency-critical site: any compile
    recorded inside is a COLD compile attributed to ``site``."""
    t_site = _SITE.set(site)
    t_events = _SITE_EVENTS.set([])
    try:
        yield
    finally:
        _SITE.reset(t_site)
        _SITE_EVENTS.reset(t_events)


def ambient_site() -> Optional[str]:
    return _SITE.get()


def drain_compile_events() -> List[dict]:
    """The cold-compile events recorded under the current
    :func:`compile_site` block so far (and clears them) — the serving
    executor attaches these to the batch's ``predict`` span."""
    events = _SITE_EVENTS.get()
    if not events:
        return []
    drained = list(events)
    del events[:]
    return drained


def record_executable_compile(
    cache: str, seconds: float, key=None
) -> None:
    """Account one freshly compiled executable in the named cache.

    Callers detect the compile themselves (a miss in their own key
    set / dict) and pass the wall-clock their first dispatch took —
    jit tracing+compile runs synchronously on that call, so the
    elapsed time is dominated by the compile. With an ambient
    :func:`compile_site`, the compile is additionally counted cold,
    recorded as a ``compile:<cache>`` span under the ambient trace,
    and appended to the site's drainable event list."""
    _m_compiles().labels(cache=cache).inc()
    _m_compile_seconds().labels(cache=cache).inc(max(0.0, seconds))
    site = _SITE.get()
    if site is None:
        return
    _m_cold().labels(site=site).inc()
    event = {"cache": cache, "seconds": round(seconds, 4), "site": site}
    if key is not None:
        event["key"] = str(key)
    events = _SITE_EVENTS.get()
    if events is not None:
        events.append(event)
    from predictionio_tpu.utils import tracing as _tracing

    ctx = _tracing.current()
    if ctx is not None:
        _tracing.record_span(
            f"compile:{cache}", ctx.trace_id, parent_id=ctx.span_id,
            duration_s=seconds, attrs=dict(event),
        )
    logger.warning(
        "cold compile inside %s: cache=%s key=%s %.3fs",
        site, cache, key, seconds,
    )


@contextlib.contextmanager
def track_compile(cache: str, seen: set, key) -> Iterator[bool]:
    """The one-liner for executable caches keyed by hashable statics:
    yields whether ``key`` is NEW in ``seen`` (a compile is about to
    happen on the enclosed first dispatch) and records it on success.
    A dispatch that RAISES un-marks the key and records nothing — the
    executable was never cached, and the retry that performs the real
    compile must still be attributable. ``seen`` mutates under the
    module lock, so concurrent first calls record the compile once."""
    import time as _time

    with _TRACK_LOCK:
        new = key not in seen
        if new:
            seen.add(key)
    t0 = _time.perf_counter()
    try:
        yield new
    except BaseException:
        if new:
            with _TRACK_LOCK:
                seen.discard(key)
        raise
    else:
        if new:
            record_executable_compile(
                cache, _time.perf_counter() - t0, key=key
            )


_TRACK_LOCK = threading.Lock()


def persistent_cache_stats() -> Dict[str, int]:
    """Entry count and on-disk bytes of the persistent XLA cache dir
    (zeros when disabled); sets the ``cache='persistent'`` gauges."""
    path = ensure_compilation_cache()
    entries = 0
    total = 0
    if path and os.path.isdir(path):
        try:
            for name in os.listdir(path):
                full = os.path.join(path, name)
                if os.path.isfile(full):
                    entries += 1
                    total += os.path.getsize(full)
        except OSError:
            logger.debug("persistent cache scan failed", exc_info=True)
    _m_entries().labels(cache="persistent").set(float(entries))
    _m_cache_bytes().labels(cache="persistent").set(float(total))
    return {"entries": entries, "bytes": total}


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache``: a fixed path derived from where the
    package lives, so every process of one checkout shares one cache."""
    package_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package_dir), ".jax_cache")


def ensure_compilation_cache() -> Optional[str]:
    """Turn on the persistent cache (idempotent) and return the directory
    in use, or None when JAX's cache is disabled. With
    ``JAX_COMPILATION_CACHE_DIR`` set the directory is JAX's to place;
    otherwise it is :func:`default_cache_dir`."""
    global _configured
    import jax

    if not _configured:
        _configured = True
        # What the environment sets (JAX reads JAX_<NAME> itself) stands;
        # the rest are this framework's defaults.
        defaults = {
            # Cache every program the framework compiles — the default
            # 1 s floor would skip the small serving/predict executables
            # whose cold compiles are exactly the deploy-time tail
            # latency the warm-up hook exists to hide.
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            # bound on-disk growth (LRU eviction): tuning sweeps and
            # jax/XLA version bumps would otherwise accumulate forever
            "jax_compilation_cache_max_size": 4 * 1024**3,
        }
        for name, value in defaults.items():
            if not os.environ.get(name.upper()):
                jax.config.update(name, value)
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", default_cache_dir())
        logger.info(
            "XLA compilation cache at %s", jax.config.jax_compilation_cache_dir
        )
    if not jax.config.jax_enable_compilation_cache:
        return None
    return jax.config.jax_compilation_cache_dir or None
