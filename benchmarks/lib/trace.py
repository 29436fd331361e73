"""From a profiler trace to numbers. Runs in a host-only child (it needs
``jax.profiler.ProfileData``, and the harness's parent never imports JAX).

A device plane is one whose name starts with ``/device:TPU``. On it the
line ``XLA Ops`` carries one event per executed operation, containers
(``while``, fusions' parents) enclosing their children. So:

* busy time is the *union* of the ops' intervals, never their sum;
* an op's own time is its duration less what its children cover;
* time matching a pattern is the union of the matching intervals on any
  line of the plane (``XLA Modules`` has one event per program run), so
  a container and its children are not counted twice.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = "/device:TPU"
OPS_LINE = "XLA Ops"
UNIX_NS = 10**18  # a start above this is a unix time, not an offset


def newest_xplane(trace_dir):
    found = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    return found[-1] if found else None


def union_seconds(intervals):
    """Total length of the union of (start, end) intervals."""
    total, reach = 0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def gaps(intervals, first, last):
    """(start, length) of every stretch of [first, last] that no interval
    covers, longest first."""
    out, reach = [], first
    for start, end in sorted(intervals):
        if start > reach:
            out.append((reach, start - reach))
        reach = max(reach, end)
    if last > reach:
        out.append((reach, last - reach))
    return sorted(out, key=lambda g: -g[1])


def own_times(events):
    """{name: [own ns, count]} for (name, start, end) events of one line,
    where an event that lies inside another is its child."""
    out, stack = {}, []

    def close(ev):
        name, start, end, covered = ev
        slot = out.setdefault(name, [0, 0])
        slot[0] += (end - start) - covered
        slot[1] += 1

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= start:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(end, stack[-1][2]) - start
        stack.append([name, start, end, 0])
    while stack:
        close(stack.pop())
    return out


def reduce_planes(planes, patterns=()):
    """``planes``: [(plane name, [(line name, [(event name, start ns,
    end ns)])])]. Returns the reduction as plain JSON-able data; device
    numbers are averaged over the device planes found, and absent (None)
    where there is none: a CPU trace has no device plane. Each plane's
    own busy time is kept beside the average (``busy_by_plane``)."""
    layout = [
        {"plane": p, "lines": [{"line": ln, "events": len(ev)} for ln, ev in lines]}
        for p, lines in planes
    ]
    devices = []
    for p, lines in planes:
        if not p.startswith(DEVICE_PLANE):
            continue
        by_line = dict(lines)
        events = by_line.get(OPS_LINE)
        if events is None:  # an unknown layout: take every line but steps
            events = [e for ln, ev in lines if ln != "Steps" for e in ev]
        if events:
            devices.append((p, (events, [e for _, ev in lines for e in ev])))
    out = {"layout": layout, "device_planes": len(devices), "device": None}
    if not devices:
        return out
    n = len(devices)
    busy, busy_by_plane = 0.0, {}
    matching = {p: {"seconds": 0.0, "events": 0.0} for p in patterns}
    merged, spans, first, last = {}, [], None, None
    for plane, (events, every_line) in devices:
        spans_d = [(s, e) for _, s, e in events]
        busy_by_plane[plane] = union_seconds(spans_d) / 1e9
        busy += busy_by_plane[plane]
        for p in patterns:
            rx = re.compile(p)
            hit = [(s, e) for nm, s, e in every_line if rx.search(nm)]
            matching[p]["seconds"] += union_seconds(hit) / 1e9 / n
            matching[p]["events"] += len(hit) / n
        for name, (ns, count) in own_times(events).items():
            slot = merged.setdefault(name, [0, 0])
            slot[0] += ns
            slot[1] += count
        lo, hi = min(s for s, _ in spans_d), max(e for _, e in spans_d)
        first = lo if first is None else min(first, lo)
        last = hi if last is None else max(last, hi)
        spans = spans_d  # gaps are listed for the last device plane
    ops = sorted(merged.items(), key=lambda kv: -kv[1][0])
    out["device"] = {
        "busy_s": busy / n,
        "busy_by_plane": busy_by_plane,  # an idle or straggling chip shows
        "matching": matching,
        "first_ns": first, "last_ns": last,
        "absolute_clock": bool(first > UNIX_NS),
        "ops": [[name, ns / 1e9 / n, count] for name, (ns, count) in ops[:40]],
        "gaps": [[s, ln / 1e9] for s, ln in gaps(spans, first, last)[:10]],
    }
    return out


def read_planes(path):
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [
                (ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                for ev in line.events
            ]))
        planes.append((plane.name, lines))
    return planes


def reduce_dir(trace_dir, patterns=()):
    path = newest_xplane(trace_dir)
    if path is None:
        return {"layout": [], "device_planes": 0, "device": None,
                "error": f"no *.xplane.pb under {trace_dir}"}
    out = reduce_planes(read_planes(path), patterns)
    out["file_bytes"] = os.path.getsize(path)
    return out
