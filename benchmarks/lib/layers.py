"""Per-layer metrics: one small vocabulary of sources, implemented once.

A metric is a file ``layer_metrics/<name>.json`` with a ``read`` string
and an optional ``scale``. A later metric over a source kind that exists
is a new file and no code:

    phase:<PhaseTimer name>[+<name>...]   seconds, from `pio train`'s log
    log:<field>                           what the harness derives from a
                                          child's log and clock
    prom:<family>:delta                   a counter's rise over the window
    prom:<family>:mean                    a histogram's sum / count rise
    prom:<family>:quantile:<q>            from the histogram's bucket rises
    loadgen:<field>                       the generator's own account
    trace:busy_s | trace:window_s | trace:idle_share
    trace:ops_matching:<regex>            union of matching device events
    share:<counts fn>:<mfu|roofline>/<time source>

A reader that finds nothing to read returns None and the metric is left
out of the line; a share is never reported as 0.
"""

from __future__ import annotations

from . import counts as counts_
from .children import metric_samples


def trace_patterns(reads):
    """The regexes that the trace reduction has to match, from ``read``
    strings."""
    out = []
    for read in reads:
        for part in read.split("/"):
            if part.startswith("trace:ops_matching:"):
                out.append(part[len("trace:ops_matching:"):])
    return sorted(set(out))


def _family_delta(scrapes, family, suffix=""):
    if scrapes is None:
        return None
    before = metric_samples(scrapes[0], family + suffix)
    after = metric_samples(scrapes[1], family + suffix)
    if not after:
        return None
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def _prom(scrapes, spec):
    family, _, how = spec.partition(":")
    if how == "delta":
        d = _family_delta(scrapes, family)
        return None if d is None else sum(d.values())
    total = _family_delta(scrapes, family, "_sum")
    count = _family_delta(scrapes, family, "_count")
    if not count or sum(count.values()) <= 0:
        return None
    if how == "mean":
        return sum(total.values()) / sum(count.values())
    if how == "count":
        return sum(count.values())
    if how.startswith("quantile:"):
        q = float(how.split(":", 1)[1])
        buckets = {}
        for labels, v in _family_delta(scrapes, family, "_bucket").items():
            le = labels.split('le="', 1)[1].split('"', 1)[0]
            edge = float("inf") if le == "+Inf" else float(le)
            buckets[edge] = buckets.get(edge, 0.0) + v
        edges = sorted(buckets)
        target, lower = q * buckets[edges[-1]], 0.0
        for n, edge in enumerate(edges):
            if buckets[edge] >= target:
                below = buckets[edges[n - 1]] if n else 0.0
                if edge == float("inf"):
                    return lower
                inside = buckets[edge] - below
                frac = (target - below) / inside if inside > 0 else 1.0
                return lower + (edge - lower) * frac
            lower = edge
    raise ValueError(f"no such prom reading: {spec}")


def _trace(ctx, spec):
    device = (ctx.get("trace") or {}).get("device")
    if not device or not device.get("busy_s"):
        return None
    window = ctx.get("trace_window_s")
    if spec == "busy_s":
        return device["busy_s"]
    if spec == "window_s":
        return window
    if spec == "idle_share":
        return None if not window else 1.0 - device["busy_s"] / window
    if spec.startswith("ops_matching:"):
        found = device["matching"].get(spec[len("ops_matching:"):])
        return found["seconds"] if found and found["seconds"] > 0 else None
    raise ValueError(f"no such trace reading: {spec}")


def read(ctx, source):
    """The value of one ``read`` string in this run's context, or None."""
    kind, _, spec = source.partition(":")
    if kind == "phase":
        names = spec.split("+")
        phases = ctx.get("phases") or {}
        if any(n not in phases for n in names):
            return None
        return sum(phases[n] for n in names)
    if kind == "log":
        return (ctx.get("log") or {}).get(spec)
    if kind == "loadgen":
        return (ctx.get("loadgen") or {}).get(spec)
    if kind == "prom":
        return _prom(ctx.get("prom"), spec)
    if kind == "trace":
        return _trace(ctx, spec)
    if kind == "share":
        what, _, time_source = spec.partition("/")
        fn, _, against = what.partition(":")
        seconds = read(ctx, time_source)
        seen = ctx.get("seen")
        if not seconds or seconds <= 0 or not seen:
            return None
        work = counts_.COUNTS[fn](ctx["shape"], seen)
        if against == "mfu":
            least = work["flops"] / ctx["peaks"]["bf16_flops_per_s"]
        elif against == "roofline":
            least, _ = counts_.roofline_seconds(work, ctx["peaks"])
        else:
            raise ValueError(f"a share is of mfu or roofline: {source}")
        return (least / seconds) or None
    raise ValueError(f"no such source kind: {source}")


def evaluate(ctx, definitions):
    """{name: {"value", "unit"}} for every definition that finds its
    source; ``definitions`` are the metric files' contents."""
    out = {}
    for d in definitions:
        value = read(ctx, d["read"])
        if value is None:
            continue
        out[d["name"]] = {
            "value": float(value) * d.get("scale", 1.0), "unit": d["unit"],
        }
    return out
