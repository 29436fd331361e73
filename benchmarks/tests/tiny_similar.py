"""The Similar Product cell at a size a test run can hold, on the CPU: the
real manifest, metric files, kind and generator, with the configuration
and the traffic cut down (as ``tiny_ecom.py`` does for its cell). A tiny
run is never a measurement."""

from __future__ import annotations

import json
import os
import sys
import tempfile

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as harness  # noqa: E402
import tiny  # noqa: E402

CELL = "simprod-amazon-d512.query-detail-page"
SHAPE = {"n_items": 20000, "rank": 32, "n_categories": 24}


def make_bench(tmp):
    """``tiny.make_bench`` with this cell's configuration and traffic cut
    to the tiny size too. Returns (bench dir, manifest)."""
    bench, manifest = tiny.make_bench(tmp)
    path = os.path.join(bench, "configs", "simprod-amazon-d512.json")
    with open(path) as f:
        config = json.load(f)
    config["shape"] = SHAPE
    config["verify"] = {"answers": 60, "per_shape": 8}
    config["engine"]["algorithms"][0]["params"].update(
        rank=SHAPE["rank"], warm_max_batch=16)
    with open(path, "w") as f:
        json.dump(config, f)
    path = os.path.join(bench, "traffic", "similar-detail-page.json")
    with open(path) as f:
        traffic = json.load(f)
    traffic.update(rate_per_s=30, connections=4, trace_seconds=1,
                   warmup_seconds=2)
    with open(path, "w") as f:
        json.dump(traffic, f)
    return bench, manifest


def tiny_run(tmp, *, seed=5, seconds=8.0, trace=False, bench=None,
             manifest=None):
    if bench is None:
        bench, manifest = make_bench(tmp)
    return tiny.tiny_run(tmp, CELL, seed=seed, seconds=seconds, trace=trace,
                         bench=bench, manifest=manifest)


if __name__ == "__main__":  # a rehearsal by hand
    with tempfile.TemporaryDirectory() as tmp:
        line = tiny_run(tmp, trace=len(sys.argv) > 1)
        harness.print_result(line)
