"""Unified metrics registry + Prometheus-text exposition.

The observability tentpole (SURVEY.md §5 plans "per-phase timers as
first-class"; the reference's only serving stats are the coarse
request-count bookkeeping in CreateServer.scala:399-404). Every server
in this package — event server, engine server, storage gateway — and
every background subsystem (group-commit committers, the segment
compactor, the pack cache, continuous training) records into ONE
process-global registry, exposed as Prometheus text at ``GET /metrics``
on each server. ``status.json`` keys that used to be N private
lock-guarded tallies are now reads of the same registry.

Three instrument kinds:

- :class:`Counter` — monotonically increasing float, with labels;
- :class:`Gauge` — settable float, with labels;
- :class:`Histogram` — **mergeable** fixed-bucket histogram. Bounds are
  fixed at family creation (log-spaced by default), so two workers of an
  SO_REUSEPORT fleet produce bucket vectors that ADD: the merged p99
  equals the p99 a single combined worker would have estimated. The
  512-sample reservoir this replaces structurally could not merge
  (concatenating reservoirs biases toward whichever worker sampled
  less traffic).

Hot-path cost: one dict lookup + one per-child ``threading.Lock``
acquire per record. There is no registry-global lock on the record
path (the registry lock only guards family/child CREATION), so serving
instrumentation adds no shared contention point beyond what each
instrument's own callers already serialize on — strictly less sharing
than the single ``_stats_lock`` the engine server used for everything.

Per-instance views over process-global instruments: a server that wants
"since I started" numbers (status.json) takes a :meth:`Counter.snapshot`
/ :meth:`Histogram.snapshot` at construction and reads deltas against
it; ``/metrics`` always reports process-lifetime values.
"""

from __future__ import annotations

import bisect
import math
import re
import threading
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "HistogramSnapshot",
    "MetricsRegistry",
    "get_registry",
    "log_buckets",
    "quantile_from_buckets",
    "merge_snapshots",
    "parse_exposition",
    "parse_exposition_families",
    "parse_labels",
    "sample_family_name",
    "sample_label_value",
    "counter_sum",
    "gauge_max",
    "histogram_quantile_from_samples",
    "render_content_type",
    "LATENCY_BUCKETS_S",
    "BATCH_SIZE_BUCKETS",
    "ROW_COUNT_BUCKETS",
    "CONVERGENCE_BUCKETS",
]

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")


def log_buckets(lo: float, hi: float, factor: float = 2.0) -> Tuple[float, ...]:
    """Fixed log-spaced bucket bounds from ``lo`` up to at least ``hi``.

    Fixed (config-independent) bounds are what makes histograms
    mergeable across processes: every worker slices the axis the same
    way, so bucket vectors add element-wise.
    """
    if lo <= 0 or hi <= lo or factor <= 1.0:
        raise ValueError("need 0 < lo < hi and factor > 1")
    out: List[float] = []
    v = float(lo)
    while True:
        out.append(v)
        if v >= hi * (1 - 1e-12):  # last finite bound covers hi
            break
        v *= factor
    return tuple(out)


# serving/RPC latency in seconds: 100 µs .. ~105 s, ×2 per bucket
LATENCY_BUCKETS_S = log_buckets(1e-4, 100.0)
# micro-batch fill / REST batch sizes: 1 .. 1024, ×2
BATCH_SIZE_BUCKETS = log_buckets(1.0, 1024.0)
# group-commit flush rows / sealed-row counts: 1 .. 65536, ×4
ROW_COUNT_BUCKETS = log_buckets(1.0, 65536.0, 4.0)
# per-sweep ALS factor-delta RMS (convergence telemetry): spans the
# warm-start tail (~1e-6) through a cold first sweep (~1), ×2
CONVERGENCE_BUCKETS = log_buckets(1e-6, 4.0)


def _escape_label_value(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _escape_help(v: str) -> str:
    return v.replace("\\", "\\\\").replace("\n", "\\n")


def _fmt(v: float) -> str:
    """Prometheus number formatting: integers bare, floats as repr."""
    if v == math.inf:
        return "+Inf"
    if isinstance(v, float) and v.is_integer():
        return str(int(v))
    return repr(float(v))


def _labels_key(
    label_names: Tuple[str, ...], kv: Dict[str, str]
) -> Tuple[str, ...]:
    if set(kv) != set(label_names):
        raise ValueError(
            f"labels {sorted(kv)} do not match declared {list(label_names)}"
        )
    return tuple(str(kv[name]) for name in label_names)


def _render_labels(
    label_names: Tuple[str, ...], values: Tuple[str, ...],
    extra: Optional[Tuple[str, str]] = None,
) -> str:
    pairs = [
        f'{n}="{_escape_label_value(v)}"'
        for n, v in zip(label_names, values)
    ]
    if extra is not None:
        pairs.append(f'{extra[0]}="{_escape_label_value(extra[1])}"')
    return "{" + ",".join(pairs) + "}" if pairs else ""


class _Family:
    """One metric family: a name, a type, declared label names, and the
    per-labelset children. Child creation is guarded by the registry
    lock; the record path touches only the child's own lock."""

    kind = "untyped"

    def __init__(self, name: str, help: str, label_names: Sequence[str]):
        if not _NAME_RE.match(name):
            raise ValueError(f"invalid metric name {name!r}")
        for ln in label_names:
            if not _LABEL_RE.match(ln):
                raise ValueError(f"invalid label name {ln!r}")
        self.name = name
        self.help = help
        self.label_names = tuple(label_names)
        self._children: Dict[Tuple[str, ...], object] = {}
        self._lock = threading.Lock()

    def _make_child(self):
        raise NotImplementedError

    def labels(self, **kv) -> object:
        key = _labels_key(self.label_names, kv)
        child = self._children.get(key)
        if child is None:
            with self._lock:
                child = self._children.get(key)
                if child is None:
                    child = self._make_child()
                    self._children[key] = child
        return child

    def _default(self):
        """The label-less child (for families declared without labels)."""
        if self.label_names:
            raise ValueError(
                f"{self.name} declares labels {self.label_names}; "
                "use .labels(...)"
            )
        return self.labels()

    def children(self) -> List[Tuple[Tuple[str, ...], object]]:
        with self._lock:
            return sorted(self._children.items())

    def reset(self) -> None:
        """Zero every child (tests / explicit cache-clear semantics)."""
        with self._lock:
            for child in self._children.values():
                child._reset()  # type: ignore[attr-defined]

    def render(self) -> List[str]:
        lines = [
            f"# HELP {self.name} {_escape_help(self.help)}",
            f"# TYPE {self.name} {self.kind}",
        ]
        for values, child in self.children():
            lines.extend(child._render(self, values))  # type: ignore
        return lines


class _CounterValue:
    __slots__ = ("_value", "_lock")

    def __init__(self):
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, n: float = 1.0) -> None:
        if n < 0:
            raise ValueError("counters only go up; use a gauge")
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        return self._value

    def snapshot(self) -> float:
        return self._value

    def _reset(self) -> None:
        with self._lock:
            self._value = 0.0

    def _render(self, family: "_Family", values: Tuple[str, ...]) -> List[str]:
        return [
            f"{family.name}"
            f"{_render_labels(family.label_names, values)} "
            f"{_fmt(self._value)}"
        ]


class _GaugeValue(_CounterValue):
    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)

    def inc(self, n: float = 1.0) -> None:  # gauges may go down
        with self._lock:
            self._value += n

    def dec(self, n: float = 1.0) -> None:
        self.inc(-n)


class Counter(_Family):
    kind = "counter"

    def _make_child(self) -> _CounterValue:
        return _CounterValue()

    # label-less convenience: family doubles as its single child
    def inc(self, n: float = 1.0) -> None:
        self._default().inc(n)

    @property
    def value(self) -> float:
        return self._default().value

    def snapshot(self) -> float:
        return self._default().snapshot()


class Gauge(_Family):
    kind = "gauge"

    def _make_child(self) -> _GaugeValue:
        return _GaugeValue()

    def set(self, v: float) -> None:
        self._default().set(v)

    def inc(self, n: float = 1.0) -> None:
        self._default().inc(n)

    def dec(self, n: float = 1.0) -> None:
        self._default().dec(n)

    @property
    def value(self) -> float:
        return self._default().value


class HistogramSnapshot:
    """An immutable (bounds, bucket counts, sum, count) capture —
    the unit of merging and of per-instance delta views."""

    __slots__ = ("bounds", "counts", "sum", "count")

    def __init__(
        self,
        bounds: Tuple[float, ...],
        counts: Tuple[int, ...],
        total: float,
        count: int,
    ):
        self.bounds = bounds
        self.counts = counts
        self.sum = total
        self.count = count

    def quantile(self, q: float) -> float:
        return quantile_from_buckets(self.bounds, self.counts, q)

    def merge(self, other: "HistogramSnapshot") -> "HistogramSnapshot":
        return merge_snapshots([self, other])

    def delta(self, base: "HistogramSnapshot") -> "HistogramSnapshot":
        """This snapshot minus an earlier one of the same family — the
        per-instance "since construction" view status.json uses."""
        if base.bounds != self.bounds:
            raise ValueError("snapshot bounds differ; cannot delta")
        return HistogramSnapshot(
            self.bounds,
            tuple(a - b for a, b in zip(self.counts, base.counts)),
            self.sum - base.sum,
            self.count - base.count,
        )


def merge_snapshots(snaps: Iterable[HistogramSnapshot]) -> HistogramSnapshot:
    """Merge same-bounds histograms by adding bucket vectors — the
    SO_REUSEPORT worker-fleet aggregation path. Because the bounds are
    fixed, the merged quantile estimate is IDENTICAL to what one worker
    observing the union of samples would report."""
    snaps = list(snaps)
    if not snaps:
        raise ValueError("nothing to merge")
    bounds = snaps[0].bounds
    for s in snaps[1:]:
        if s.bounds != bounds:
            raise ValueError("histogram bounds differ; cannot merge")
    counts = [0] * len(snaps[0].counts)  # finite buckets + the +Inf slot
    total = 0.0
    count = 0
    for s in snaps:
        for i, c in enumerate(s.counts):
            counts[i] += c
        total += s.sum
        count += s.count
    return HistogramSnapshot(bounds, tuple(counts), total, count)


def quantile_from_buckets(
    bounds: Sequence[float], counts: Sequence[int], q: float
) -> float:
    """Bucket-interpolated quantile: find the bucket holding rank
    ``q * count`` and linearly interpolate inside it (the standard
    Prometheus ``histogram_quantile`` estimator). The +Inf overflow
    bucket clamps to the highest finite bound."""
    total = sum(counts)
    if total <= 0:
        return 0.0
    rank = q * total
    cum = 0.0
    for i, c in enumerate(counts):
        if c <= 0:
            continue
        if cum + c >= rank:
            lo = bounds[i - 1] if i > 0 else 0.0
            hi = bounds[i] if i < len(bounds) else bounds[-1]
            if hi == math.inf or i >= len(bounds):
                return float(bounds[-1])
            frac = (rank - cum) / c
            return float(lo + (hi - lo) * max(0.0, min(1.0, frac)))
        cum += c
    return float(bounds[-1])


class _HistogramValue:
    __slots__ = ("_bounds", "_counts", "_sum", "_count", "_lock")

    def __init__(self, bounds: Tuple[float, ...]):
        self._bounds = bounds
        # one slot per finite bound + one +Inf overflow slot
        self._counts = [0] * (len(bounds) + 1)
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()

    def observe(self, v: float, n: int = 1) -> None:
        """Record ``v``, ``n`` times over: one lock take for the ``n``
        requests of a micro-batch that share a duration."""
        i = bisect.bisect_left(self._bounds, v)
        with self._lock:
            self._counts[i] += n
            self._sum += v * n
            self._count += n

    def try_observe(self, v: float) -> bool:
        """``observe`` that gives up (False) rather than wait for the
        lock. For a ``gc.callbacks`` hook: a collection starts on
        whichever thread allocated last, and that may be a scrape
        inside this child's ``_render``/``snapshot``, holding the lock
        the hook would wait for, for ever."""
        i = bisect.bisect_left(self._bounds, v)
        if not self._lock.acquire(blocking=False):
            return False
        try:
            self._counts[i] += 1
            self._sum += v
            self._count += 1
        finally:
            self._lock.release()
        return True

    @property
    def sum(self) -> float:
        return self._sum

    @property
    def count(self) -> int:
        return self._count

    def snapshot(self) -> HistogramSnapshot:
        with self._lock:
            return HistogramSnapshot(
                self._bounds, tuple(self._counts), self._sum, self._count
            )

    def quantile(
        self, q: float, since: Optional[HistogramSnapshot] = None
    ) -> float:
        snap = self.snapshot()
        if since is not None:
            snap = snap.delta(since)
        return snap.quantile(q)

    def _reset(self) -> None:
        with self._lock:
            self._counts = [0] * (len(self._bounds) + 1)
            self._sum = 0.0
            self._count = 0

    def _render(self, family: "_Family", values: Tuple[str, ...]) -> List[str]:
        with self._lock:
            counts = list(self._counts)
            total, count = self._sum, self._count
        lines = []
        cum = 0
        for bound, c in zip(self._bounds, counts):
            cum += c
            lines.append(
                f"{family.name}_bucket"
                f"{_render_labels(family.label_names, values, ('le', _fmt(bound)))} "
                f"{cum}"
            )
        cum += counts[-1]
        lines.append(
            f"{family.name}_bucket"
            f"{_render_labels(family.label_names, values, ('le', '+Inf'))} "
            f"{cum}"
        )
        labels = _render_labels(family.label_names, values)
        lines.append(f"{family.name}_sum{labels} {_fmt(total)}")
        lines.append(f"{family.name}_count{labels} {count}")
        return lines


class Histogram(_Family):
    kind = "histogram"

    def __init__(
        self,
        name: str,
        help: str,
        label_names: Sequence[str],
        buckets: Sequence[float],
    ):
        super().__init__(name, help, label_names)
        bounds = tuple(sorted(float(b) for b in buckets))
        if not bounds:
            raise ValueError("histogram needs at least one bucket bound")
        self.bounds = bounds

    def _make_child(self) -> _HistogramValue:
        return _HistogramValue(self.bounds)

    def observe(self, v: float, n: int = 1) -> None:
        self._default().observe(v, n)

    def snapshot(self) -> HistogramSnapshot:
        return self._default().snapshot()

    def quantile(
        self, q: float, since: Optional[HistogramSnapshot] = None
    ) -> float:
        return self._default().quantile(q, since)

    @property
    def sum(self) -> float:
        return self._default().sum

    @property
    def count(self) -> int:
        return self._default().count


class MetricsRegistry:
    """Thread-safe family registry. Families are get-or-create by name
    (two servers in one process share the family); re-registering a name
    with a different kind/labels/buckets raises — a silent mismatch
    would corrupt the exposition."""

    def __init__(self):
        self._families: Dict[str, _Family] = {}
        self._lock = threading.Lock()

    def _get_or_create(self, name: str, factory, kind: str, check) -> _Family:
        fam = self._families.get(name)
        if fam is None:
            with self._lock:
                fam = self._families.get(name)
                if fam is None:
                    fam = factory()
                    self._families[name] = fam
                    return fam
        if fam.kind != kind:
            raise ValueError(
                f"metric {name!r} already registered as {fam.kind}, "
                f"not {kind}"
            )
        check(fam)
        return fam

    def counter(
        self, name: str, help: str, labels: Sequence[str] = ()
    ) -> Counter:
        def check(fam):
            if fam.label_names != tuple(labels):
                raise ValueError(
                    f"metric {name!r} label mismatch: "
                    f"{fam.label_names} vs {tuple(labels)}"
                )

        return self._get_or_create(  # type: ignore[return-value]
            name, lambda: Counter(name, help, labels), "counter", check
        )

    def gauge(self, name: str, help: str, labels: Sequence[str] = ()) -> Gauge:
        def check(fam):
            if fam.label_names != tuple(labels):
                raise ValueError(
                    f"metric {name!r} label mismatch: "
                    f"{fam.label_names} vs {tuple(labels)}"
                )

        return self._get_or_create(  # type: ignore[return-value]
            name, lambda: Gauge(name, help, labels), "gauge", check
        )

    def histogram(
        self,
        name: str,
        help: str,
        labels: Sequence[str] = (),
        buckets: Sequence[float] = LATENCY_BUCKETS_S,
    ) -> Histogram:
        def check(fam):
            if fam.label_names != tuple(labels):
                raise ValueError(
                    f"metric {name!r} label mismatch: "
                    f"{fam.label_names} vs {tuple(labels)}"
                )
            if fam.bounds != tuple(sorted(float(b) for b in buckets)):
                raise ValueError(f"metric {name!r} bucket-bound mismatch")

        return self._get_or_create(  # type: ignore[return-value]
            name,
            lambda: Histogram(name, help, labels, buckets),
            "histogram",
            check,
        )

    def families(self) -> List[_Family]:
        with self._lock:
            return [self._families[k] for k in sorted(self._families)]

    def render(self) -> str:
        """Prometheus text exposition format 0.0.4: one ``# HELP`` and
        one ``# TYPE`` line per family, then the samples."""
        lines: List[str] = []
        for fam in self.families():
            lines.extend(fam.render())
        return "\n".join(lines) + "\n"

    def reset(self) -> None:
        """Zero every instrument (tests only; a live scrape target must
        never reset its counters)."""
        for fam in self.families():
            fam.reset()


def render_content_type() -> str:
    """The Prometheus text exposition content type, fully qualified —
    the transports send it verbatim (they only append a charset to
    types that lack one)."""
    return "text/plain; version=0.0.4; charset=utf-8"


def parse_exposition(text: str) -> Dict[str, float]:
    """Parse Prometheus text back into ``{'name{labels}': value}`` —
    shared by bench.py's before/after scrape deltas and the conformance
    tests. Escapes inside label values are preserved verbatim (the key
    is the raw sample name as rendered)."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        # the value is the last whitespace-separated token; the sample
        # name may contain spaces only inside a quoted label value
        idx = line.rfind(" ")
        if idx <= 0:
            continue
        name, value = line[:idx].strip(), line[idx + 1:]
        try:
            out[name] = float(value)
        except ValueError:
            continue
    return out


# --- exposition analysis helpers (fleet consumers) ---
#
# Shared by `pio top`, the telemetry collector (utils/telemetry.py), and
# bench.py: everything a scrape CONSUMER needs to turn raw exposition
# text back into typed samples, per-family sums, and reconstructed
# quantiles. Kept here (not in tools/) because the collector tier is
# library code.

_LABEL_PAIR_RE = re.compile(
    r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:\\.|[^"\\])*)"'
)

_UNESCAPE = {"\\\\": "\\", '\\"': '"', "\\n": "\n"}


def _unescape_label_value(v: str) -> str:
    out: List[str] = []
    i = 0
    while i < len(v):
        pair = v[i : i + 2]
        if pair in _UNESCAPE:
            out.append(_UNESCAPE[pair])
            i += 2
        else:
            out.append(v[i])
            i += 1
    return "".join(out)


def sample_family_name(sample_key: str) -> str:
    """``pio_foo_total{a="b"}`` → ``pio_foo_total``."""
    return sample_key.split("{", 1)[0]


def sample_label_value(sample_key: str, label: str) -> Optional[str]:
    """One label's (still-escaped) value from a rendered sample key."""
    m = re.search(rf'{label}="((?:\\.|[^"\\])*)"', sample_key)
    return m.group(1) if m else None


def parse_labels(sample_key: str) -> Tuple[Tuple[str, str], ...]:
    """The label set of a rendered sample key as ordered (name, value)
    pairs, with exposition escapes undone — the representation the
    federation layer merges and re-renders on."""
    if "{" not in sample_key:
        return ()
    body = sample_key.split("{", 1)[1].rsplit("}", 1)[0]
    return tuple(
        (name, _unescape_label_value(value))
        for name, value in _LABEL_PAIR_RE.findall(body)
    )


def parse_exposition_families(text: str) -> "Dict[str, dict]":
    """Parse Prometheus text into typed families::

        {family: {"kind": "counter"|"gauge"|"histogram"|"untyped",
                  "help": str,
                  "samples": [(sample_name, labels, value), ...]}}

    ``sample_name`` keeps histogram suffixes (``_bucket``/``_sum``/
    ``_count``) and ``labels`` is the ordered, unescaped pair tuple from
    :func:`parse_labels`. This is the typed complement of
    :func:`parse_exposition` — the federation layer needs the TYPE line
    to know whether samples sum (counters, histogram buckets) or keep
    per-instance identity (gauges)."""
    families: Dict[str, dict] = {}

    def family_for(sample_name: str) -> dict:
        # histogram samples carry suffixes; map them onto their family
        base = sample_name
        for suffix in ("_bucket", "_sum", "_count"):
            if sample_name.endswith(suffix):
                candidate = sample_name[: -len(suffix)]
                if candidate in families:
                    base = candidate
                    break
        fam = families.get(base)
        if fam is None:
            fam = families[base] = {
                "kind": "untyped", "help": "", "samples": [],
            }
        return fam

    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        if line.startswith("# HELP "):
            parts = line.split(" ", 3)
            if len(parts) >= 3:
                fam = families.setdefault(
                    parts[2], {"kind": "untyped", "help": "", "samples": []}
                )
                fam["help"] = parts[3] if len(parts) > 3 else ""
            continue
        if line.startswith("# TYPE "):
            parts = line.split(" ", 3)
            if len(parts) >= 4:
                fam = families.setdefault(
                    parts[2], {"kind": "untyped", "help": "", "samples": []}
                )
                fam["kind"] = parts[3]
            continue
        if line.startswith("#"):
            continue
        idx = line.rfind(" ")
        if idx <= 0:
            continue
        key, raw_value = line[:idx].strip(), line[idx + 1 :]
        try:
            value = float(raw_value)
        except ValueError:
            continue
        sample_name = sample_family_name(key)
        family_for(sample_name)["samples"].append(
            (sample_name, parse_labels(key), value)
        )
    return families


def counter_sum(samples: Dict[str, float], family: str) -> float:
    """Sum a counter family across its label sets (flat
    :func:`parse_exposition` samples)."""
    total = 0.0
    for key, value in samples.items():
        if sample_family_name(key) == family:
            total += value
    return total


def gauge_max(samples: Dict[str, float], family: str) -> Optional[float]:
    vals = [
        v for k, v in samples.items() if sample_family_name(k) == family
    ]
    return max(vals) if vals else None


_LE_RE = re.compile(r'le="([^"]+)"')


def histogram_quantile_from_samples(
    samples: Dict[str, float], family: str, q: float
) -> Optional[float]:
    """Quantile from the exposition's cumulative ``_bucket`` samples,
    summed across label sets (bounds are fixed per family, so cumulative
    vectors add — the SO_REUSEPORT merge property)."""
    by_le: Dict[float, float] = {}
    for key, value in samples.items():
        if sample_family_name(key) != f"{family}_bucket":
            continue
        m = _LE_RE.search(key)
        if not m:
            continue
        le = m.group(1)
        bound = float("inf") if le == "+Inf" else float(le)
        by_le[bound] = by_le.get(bound, 0.0) + value
    if not by_le:
        return None
    bounds = sorted(b for b in by_le if b != float("inf"))
    cum = [by_le[b] for b in bounds] + [by_le.get(float("inf"), 0.0)]
    counts = [int(c - (cum[i - 1] if i else 0.0)) for i, c in enumerate(cum)]
    if sum(counts) <= 0:
        return None
    return quantile_from_buckets(bounds, counts, q)


# THE process-global registry (one per worker process; an SO_REUSEPORT
# fleet aggregates by scraping every worker and merging, see
# merge_snapshots). utils/metrics.py is the one sanctioned home for
# module-level metric state — tests/test_lint.py polices the rest of
# the package.
REGISTRY = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    return REGISTRY
