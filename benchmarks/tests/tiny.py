"""A tiny copy of the benchmark for the CPU: the real manifest, metric
files and code, with configurations and traffic cut to a size a test run
can hold. A tiny run is never a measurement: ``require_tpu=False`` is the
tests' own switch and the command line has none."""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run as harness  # noqa: E402

TRAIN, SERVE = "reco-ml20m-r32.train", "reco-msd-d2048.query-steady"
SHAPES = {
    "reco-ml20m-r32": {"n_users": 3000, "n_items": 800, "n_events": 120000,
                       "rank": 8, "iterations": 5},
    "reco-msd-d2048": {"n_users": 5000, "n_items": 700, "rank": 64},
}


def make_bench(tmp):
    """A fresh directory under ``tmp``: configs, traffic, metric files and peaks as a later
    PR would find them, at the tiny size. Returns (bench dir, manifest)."""
    os.makedirs(tmp, exist_ok=True)
    bench = tempfile.mkdtemp(prefix="bench_", dir=tmp)
    for name in ("layer_metrics", "traffic", "configs"):
        shutil.copytree(os.path.join(BENCH, name), os.path.join(bench, name))
    shutil.copy(os.path.join(BENCH, "peaks.json"), bench)
    for name, shape in SHAPES.items():
        path = os.path.join(bench, "configs", name + ".json")
        with open(path) as f:
            config = json.load(f)
        config["shape"] = shape
        params = config["engine"]["algorithms"][0]["params"]
        params["rank"] = shape["rank"]
        if "iterations" in shape:
            params["num_iterations"] = shape["iterations"]
        else:
            config["verify"]["answers"] = 40
        with open(path, "w") as f:
            json.dump(config, f)
    path = os.path.join(bench, "traffic", "open-loop-steady.json")
    with open(path) as f:
        traffic = json.load(f)
    traffic.update(rate_per_s=40, connections=4, trace_seconds=1,
                   warmup_seconds=1)
    traffic["users"]["n"] = SHAPES["reco-msd-d2048"]["n_users"]
    with open(path, "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    # the cells that are built and not admitted yet are tested all the same
    with open(os.path.join(BENCH, "not-admitted.json")) as f:
        waiting = json.load(f)
    for key in ("configs", "workloads", "per_layer"):
        manifest[key] += waiting[key]
    names = {m["name"] for m in manifest["end_to_end"]}
    manifest["end_to_end"] += [m for m in waiting["end_to_end"]
                               if m["name"] not in names]
    return bench, manifest


def sqlite_path(env, repository):
    """The sqlite file behind one repository (METADATA, EVENTDATA) of a
    child's environment (``children.child_env``)."""
    source = env[f"PIO_STORAGE_REPOSITORIES_{repository}_SOURCE"]
    assert env[f"PIO_STORAGE_SOURCES_{source}_TYPE"] == "sqlite"
    return env[f"PIO_STORAGE_SOURCES_{source}_PATH"]


def tiny_run(tmp, workload, *, seed=5, seconds=2.0, trace=False, bench=None,
             manifest=None):
    """Drive one run at the tiny size on the CPU; returns the result
    line's dictionary."""
    if bench is None:
        bench, manifest = make_bench(tmp)
    work = tempfile.mkdtemp(prefix="work_", dir=tmp)
    os.environ["JAX_PLATFORMS"] = "cpu"
    run = harness.build_run(
        manifest, workload, seed, seconds, trace, work, bench=bench,
        require_tpu=False,
    )
    try:
        return harness.run_cell(run)
    finally:
        harness.children.stop_all()


if __name__ == "__main__":  # a rehearsal by hand
    with tempfile.TemporaryDirectory() as tmp:
        line = tiny_run(tmp, sys.argv[1], trace=len(sys.argv) > 2)
        harness.print_result(line)
