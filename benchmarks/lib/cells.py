"""What every kind of cell shares: the ``Run`` (the cell's manifest entry,
its configuration and traffic files, the seed, the window's length), the
result line, the trace's reduction and the breakdown.

A kind of cell is a module ``lib/kinds/<kind>.py`` (the traffic file's
``kind``, ``-`` written ``_``) with one function ``run_cell(run)`` that
returns the result line's dictionary; ``run.py`` finds it by that name.
Set-up is everything before the window; the reference runs after the
window has closed, the memory has been read and the chip's owner has
exited.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import time

from . import layers
from .children import CellFailed, child_env, run_child, say, stage


@dataclasses.dataclass
class Run:
    name: str
    config: dict
    traffic: dict
    layer_defs: list  # the metric files of this cell's per-layer metrics
    end_to_end: list  # names of the end-to-end metrics this cell reports
    peaks_table: dict
    seed: int
    seconds: float
    trace: bool
    work: str
    chips: int = 1
    require_tpu: bool = True  # the tests' own switch; the command has none

    def peaks(self, device):
        """The peaks of the chips the cell runs on: the table's, which is
        per chip, times the cell's chips. A device not in the table is an
        error, and anything but a TPU is refused as a measurement.
        Another number of devices than the cell's chips is refused in a
        rehearsal too: its children are given the cell's count."""
        if device is None:
            raise CellFailed("the child's log names no device")
        if self.require_tpu and device["platform"] != "tpu":
            raise CellFailed(
                f"JAX found platform {device['platform']!r}, not a TPU: "
                "refusing to time a CPU"
            )
        if device["count"] != self.chips:
            raise CellFailed(
                f"the cell asks for {self.chips} chip(s), JAX found {device}"
            )
        if device["kind"] not in self.peaks_table:
            if self.require_tpu:
                raise CellFailed(f"no peaks for device_kind {device['kind']!r}")
            return None
        return {
            k: v * self.chips if isinstance(v, (int, float)) else v
            for k, v in self.peaks_table[device["kind"]].items()
        }


def kind_of(run: Run):
    """The module of the cell's kind, found by the traffic file's name
    for it: a new kind is a new file."""
    name = run.traffic["kind"].replace("-", "_")
    try:
        return importlib.import_module(f"lib.kinds.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"lib.kinds.{name}":
            raise
        raise CellFailed(f"no kind of cell {run.traffic['kind']!r}") from e


def settle_disk() -> None:
    """Set-up writes a store or a model blob of gigabytes; what of it is
    still on its way to the disk when the window opens stalls whoever
    writes next (a train's commit at exit, a server's log line). Wait for
    it here, as set-up."""
    t0 = time.time()
    os.sync()
    say(phase="settle_disk", seconds=time.time() - t0)


def write_variant(run):
    """engine.json: the configuration's engine block, the seed as the
    algorithm's ``seed``."""
    engine = json.loads(json.dumps(run.config["engine"]))
    engine["datasource"] = {"params": {"app_name": "bench"}}
    for algo in engine["algorithms"]:
        algo["params"]["seed"] = run.seed
    path = os.path.join(run.work, "engine.json")
    with open(path, "w") as f:
        json.dump(engine, f)
    return path


def result_line(run, *, numbers, attempted, failed, metrics, device, extra):
    """The contract's object. ``numbers`` is the comparison, each beside
    its limit; it decides ``correct`` and comes last in the line."""
    correct = bool(numbers) and all(n["ok"] for n in numbers.values())
    keep = [d["name"] for d in run.layer_defs] if run.trace else run.end_to_end
    line = {
        "correct": correct, "attempted": attempted, "failed": failed,
        "metrics": {k: metrics[k] for k in keep if k in metrics},
        "device": device,
    }
    line.update(extra)
    line["compared"] = {
        k: {"value": n["value"], "limit": n["limit"]}
        for k, n in numbers.items()
    }
    return line


def reduce_trace(run, trace_dir):
    """The trace's reduction, by a host-only child (the parent never
    imports JAX; the chip's owner has exited or is another process)."""
    out_path = os.path.join(run.work, "trace.json")
    patterns = layers.trace_patterns(d["read"] for d in run.layer_defs)
    run_child(
        "reduce_trace", stage("reduce_trace", trace_dir, out_path, *patterns),
        child_env(run.work, host_only=True), run.work, timeout=200,
    )
    with open(out_path) as f:
        return json.load(f)


def batches_seen(reduced):
    """Batches counted in the trace itself: each pattern that a cell's
    metric files match is a program that runs once a batch (on one chip
    the fused program; on a mesh the sharded one, and the merge after
    it), so the batches are the most runs any of them made. The scrapes
    around a capture span its start-up and its archiving too, so they
    cannot count what the trace saw."""
    device = reduced.get("device") or {}
    runs = [m["events"] for m in (device.get("matching") or {}).values()]
    return max(runs, default=0)


def breakdown(reduced, t_trace_start, label_at):
    """The device ops that took most time, and the longest idle gaps, each
    labelled by ``label_at(unix time of the gap's start)``: what the host
    was doing then, from its log or from the generator's own account."""
    device = reduced.get("device")
    if not device:
        return None
    base = 0.0 if device["absolute_clock"] else t_trace_start * 1e9
    gaps = [
        [label_at((base + start_ns) / 1e9)[:80], seconds]
        for start_ns, seconds in device["gaps"]
    ]
    return {
        "device_ops": [[name[:120], s] for name, s, _ in device["ops"][:10]],
        "idle_gaps": gaps,
    }
