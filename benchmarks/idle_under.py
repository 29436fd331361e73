#!/usr/bin/env python3
"""Seconds of a capture in which the device idled while a host phase was open.

    python3 benchmarks/idle_under.py <file.xplane.pb | directory> <regex>...

The device is idle where no event of its ``XLA Ops`` line runs, between
its first and its last op. The host's phases are the program's ``pio:``
annotations (``predictionio_tpu/utils/tracing.py``) on the capture's
host planes, which share the device planes' clock. For each regex this
prints the seconds in which the device was idle and some host event
whose name matches the regex was open, on any thread: computed for each
device plane and averaged over them, as ``lib/trace.reduce_planes``
averages its busy time. ``^pio:(upload|dispatch|merge|device_wait)$``
reads the idle time under the runtime's round trip: a serve thread
launching a program or waiting for its answer.

Stand-alone, like ``host_gaps.py``: it needs JAX to read the file
(``lib/trace.read_planes``) and so runs in a process of its own, off the
chip (``JAX_PLATFORMS=cpu``).
"""

from __future__ import annotations

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib import trace  # noqa: E402


def merged(intervals):
    """The union of (start, end) intervals as sorted disjoint ones."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([start, end])
    return out


def overlap_ns(a, b):
    """Length of the intersection of two sorted lists of disjoint
    (start, end) intervals."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_under(planes, patterns):
    """{pattern: seconds} of device-idle time under an open host event
    matching the pattern, the mean over the device planes with an ``XLA
    Ops`` line; None where there is no such plane. ``planes`` as
    ``lib/trace.reduce_planes`` takes them."""
    devices, host = [], []
    for plane, lines in planes:
        if plane.startswith(trace.DEVICE_PLANE):
            events = dict(lines).get(trace.OPS_LINE)
            if events:
                devices.append([(s, e) for _, s, e in events])
        else:
            host += [ev for _, events in lines for ev in events]
    if not devices:
        return None
    out = {}
    for pattern in patterns:
        rx = re.compile(pattern)
        under = merged((s, e) for name, s, e in host if rx.search(name))
        total = 0
        for spans in devices:
            first = min(s for s, _ in spans)
            last = max(e for _, e in spans)
            idle = sorted(
                (s, s + n) for s, n in trace.gaps(spans, first, last)
            )
            total += overlap_ns(idle, under)
        out[pattern] = total / 1e9 / len(devices)
    return out


def main(argv) -> int:
    if len(argv) < 3:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    path = argv[1]
    if os.path.isdir(path):
        path = trace.newest_xplane(path)
    if not path or not os.path.isfile(path):
        print(f"no *.xplane.pb at {argv[1]}", file=sys.stderr)
        return 2
    print(json.dumps(idle_under(trace.read_planes(path), argv[2:])))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
