#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, in a new process.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Progress goes out as earlier lines; the last line of standard output is the
contract's one JSON object. The numbers that decided ``correct`` are in
it under ``compared`` (last), and are the last lines on standard error.

This parent never imports JAX: the chip is held by one child at a time
(`pio train`, or `pio deploy`). A run that finds no TPU, or another number
of chips than the cell asks for, exits with a code other than 0 and
prints no result.

Driven by data: a cell names its configuration and its traffic, and this
file finds ``configs/<config>.json``, ``traffic/<traffic>.json``,
``layer_metrics/<metric>.json`` and the traffic's kind,
``lib/kinds/<kind>.py``, by those names. A new cell, configuration, mix,
per-layer metric over a source kind that exists, or kind of cell is new
files and new entries, and no edit here.

``--manifest`` (a builder's option; the driver never gives it) names
another file of BENCHMARK.json's shape under ``benchmarks/``:
``not-admitted.json`` holds the cells that are built and not yet in the
benchmark, with the reason in PERF.md.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import sys
import tempfile

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from lib import cells, children  # noqa: E402


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def reports(metric, cell_name):
    return cell_name in metric.get("workloads", [cell_name])


def build_run(manifest, workload, seed, seconds, trace, work, bench=BENCH,
              **more):
    """The Run of one cell, from the manifest and the files it names."""
    found = [w for w in manifest["workloads"] if w["name"] == workload]
    if not found:
        raise children.CellFailed(f"BENCHMARK.json has no workload {workload!r}")
    cell = found[0]
    config = load_json(bench, "configs", cell["config"] + ".json")
    traffic = load_json(bench, "traffic", cell["traffic"] + ".json")
    return cells.Run(
        name=workload, config=config, traffic=traffic,
        layer_defs=[
            dict(load_json(bench, "layer_metrics", m["name"] + ".json"),
                 name=m["name"], unit=m["unit"])
            for m in manifest["per_layer"] if reports(m, workload)
        ],
        end_to_end=[m["name"] for m in manifest["end_to_end"]
                    if reports(m, workload)],
        peaks_table=load_json(bench, "peaks.json"),
        seed=seed, seconds=seconds, trace=trace, work=work,
        chips=cell["chips"], **more,
    )


def run_cell(run):
    """The result line's dictionary, by the cell's kind of traffic."""
    return cells.kind_of(run).run_cell(run)


def print_result(line) -> None:
    for name, n in line["compared"].items():
        print(f"compared {name}: {n['value']!r} limit {n['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=None,
                    help="a file under benchmarks/ in BENCHMARK.json's shape")
    args = ap.parse_args(argv)

    def on_sigterm(signum, frame):  # leave nothing running
        raise SystemExit(143)

    signal.signal(signal.SIGTERM, on_sigterm)
    work = None
    try:
        if not os.path.isdir(os.path.join(ROOT, "predictionio_tpu")):
            raise children.CellFailed(
                "the program (predictionio_tpu/) is not beside benchmarks/"
            )
        held_to = os.environ.get("JAX_PLATFORMS", "")
        if held_to and "tpu" not in held_to.split(","):
            # what every child would inherit; a child's own log is what
            # decides otherwise, once it has reached its device
            raise children.CellFailed(
                f"JAX_PLATFORMS={held_to}: refusing to time a CPU"
            )
        manifest = (load_json(BENCH, args.manifest) if args.manifest
                    else load_json(ROOT, "BENCHMARK.json"))
        scratch = os.path.join(ROOT, ".bench_work")
        os.makedirs(scratch, exist_ok=True)
        work = tempfile.mkdtemp(prefix="run_", dir=scratch)
        run = build_run(manifest, args.workload, args.seed, args.seconds,
                        bool(args.trace), work)
        children.say(phase="start", workload=args.workload, seed=args.seed,
                     seconds=args.seconds, trace=args.trace,
                     cache_dir=children.cache_dir())
        line = run_cell(run)
    except children.CellFailed as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    finally:
        children.stop_all()
        if work:
            keep_logs(work, args)
            shutil.rmtree(work, ignore_errors=True)
    print_result(line)
    return 0


def keep_logs(work, args) -> None:
    """The ends of the children's logs outlive the scratch directory, in
    ``chiprun_out/`` (gitignored): small, and only what a builder reads."""
    dest = os.path.join(ROOT, "chiprun_out", "bench",
                        f"{args.workload}-{args.seed}-{args.trace}")
    try:
        os.makedirs(dest, exist_ok=True)
        for name in os.listdir(work):
            if name.endswith(".log") or name == "trace.json":
                with open(os.path.join(work, name), "rb") as src:
                    src.seek(0, os.SEEK_END)
                    src.seek(max(0, src.tell() - 2**18))
                    tail = src.read()
                with open(os.path.join(dest, name), "wb") as out:
                    out.write(tail)
    except OSError as e:
        children.say(phase="keep_logs", failed=True, error=repr(e))


if __name__ == "__main__":
    sys.exit(main())
