#!/usr/bin/env python3
"""Puts each of the device's longest idle gaps down to a host phase.

    python3 benchmarks/host_gaps.py <file.xplane.pb | directory holding one>

The program names its host phases with ``pio:`` annotations
(``predictionio_tpu/utils/tracing.py``: ``pio:collect``, ``pio:predict``,
``pio:dispatch``, ``pio:finish``, ``pio:gc``, ...). A profiler capture
holds them on its host planes, one line a thread, on the clock of the
device planes. For the ten longest gaps of the device's ``XLA Ops`` line
this prints which annotations were open during the gap, by thread, with
the seconds of overlap, and ``unannotated`` for what no annotation on any
thread covers.

An annotation that encloses others (``pio:predict`` around
``pio:dispatch``) is charged its own time only: the part of the gap that
none of its children on that thread covers.

Stand-alone: it needs JAX to read the file (``lib/trace.read_planes``)
and so runs in a process of its own, off the chip (``JAX_PLATFORMS=cpu``).
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib import trace  # noqa: E402

PREFIX = "pio:"
TOP = 10


def device_gaps(planes, top=TOP):
    """(first op's start ns, [(start ns, length ns)]): the longest gaps of
    the ``XLA Ops`` line, on the device plane that ``reduce_planes`` lists
    gaps for (the last)."""
    spans = None
    for name, lines in planes:
        if name.startswith(trace.DEVICE_PLANE):
            events = dict(lines).get(trace.OPS_LINE)
            if events:
                spans = [(s, e) for _, s, e in events]
    if not spans:
        return 0, []
    first, last = min(s for s, _ in spans), max(e for _, e in spans)
    return first, trace.gaps(spans, first, last)[:top]


def host_threads(planes, prefix=PREFIX):
    """{thread label: [(annotation, start ns, end ns)]} over the planes
    that are no device's. Lines often share a name (every Python thread
    is ``python``), so a label is the line's name and its place in its
    plane."""
    out = {}
    for plane, lines in planes:
        if plane.startswith(trace.DEVICE_PLANE):
            continue
        for n, (line, events) in enumerate(lines):
            named = [e for e in events if e[0].startswith(prefix)]
            if named:
                out[f"{line or 'thread'}#{n}"] = named
    return out


def attribute(gap, threads, first=0):
    """One gap against the annotations: {"at_s" (after the device's first
    op), "seconds", "covered" (share), "unannotated_s", "by": [[thread,
    annotation, seconds]] longest first}."""
    g0, length = gap
    g1 = g0 + length
    by, covered = [], []
    for thread, events in threads.items():
        inside = [
            (name, max(s, g0), min(e, g1))
            for name, s, e in events if s < g1 and e > g0
        ]
        covered += [(s, e) for _, s, e in inside]
        for name, (own_ns, _) in trace.own_times(inside).items():
            if own_ns > 0:
                by.append([thread, name, own_ns / 1e9])
    under = trace.union_seconds(covered)
    return {
        "at_s": (g0 - first) / 1e9, "seconds": length / 1e9,
        "covered": under / length if length else 0.0,
        "unannotated_s": (length - under) / 1e9,
        "by": sorted(by, key=lambda row: -row[2]),
    }


def host_gaps(planes, top=TOP):
    """The ``top`` longest device-idle gaps, each attributed."""
    threads = host_threads(planes)
    first, found = device_gaps(planes, top)
    return [attribute(gap, threads, first) for gap in found]


def render(rows):
    """One line a gap: its length, the share that annotations cover, then
    ``thread annotation seconds`` longest first, ``unannotated`` last."""
    if not rows:
        return ["no device plane with an 'XLA Ops' line: nothing to attribute"]
    out = []
    for n, row in enumerate(rows, 1):
        parts = [f"{thread} {name} {s:.6f}" for thread, name, s in row["by"]]
        parts.append(f"unannotated {row['unannotated_s']:.6f}")
        out.append(
            f"gap {n}: {row['seconds']:.6f} s at +{row['at_s']:.6f} s, "
            f"{100 * row['covered']:.1f} % annotated: " + "; ".join(parts)
        )
    return out


def main(argv) -> int:
    if len(argv) != 2:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    path = argv[1]
    if os.path.isdir(path):
        path = trace.newest_xplane(path)
    if not path or not os.path.isfile(path):
        print(f"no *.xplane.pb at {argv[1]}", file=sys.stderr)
        return 2
    for line in render(host_gaps(trace.read_planes(path))):
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
