#!/usr/bin/env python3
"""Find the knee of a serving cell once, on the chip: one deployment, one
short open-loop window at each of a few fixed rates. A builder's tool; the
benchmark's runs never call it, and a cell's rate is a number in its
traffic file.

    python3 benchmarks/sweep.py --workload <name> --rates 100,200,400 --seconds 10
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run as harness  # noqa: E402
from lib import children, compare, layers, loadgen  # noqa: E402
from lib.kinds import open_loop_queries as serving  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--same-order", type=int, default=0,
                    help="1: every window offers the same order")
    args = ap.parse_args(argv)
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    scratch = os.path.join(harness.ROOT, ".bench_work")
    os.makedirs(scratch, exist_ok=True)
    work = tempfile.mkdtemp(prefix="sweep_", dir=scratch)
    server = None
    try:
        run = harness.build_run(manifest, args.workload, args.seed,
                                args.seconds, False, work)
        server, *_ = serving.start_server(run)
        for n, rate in enumerate(float(r) for r in args.rates.split(",")):
            run.seed = args.seed + (0 if args.same_order else n)
            traffic = dict(run.traffic, rate_per_s=rate)
            got = serving.offer(run, server, traffic, args.seconds)
            _, shaped = compare.parse_answers(got["out"], got["nums"])
            latency = (got["answered"] - got["due"]) * 1e3
            fill = layers.read(
                {"prom": got["scrapes"]}, "prom:pio_serving_batch_fill:mean")
            print(json.dumps({
                "rate_per_s": rate, "offered": len(latency),
                "well_formed": int(shaped.sum()),
                "completed_per_s": float(shaped.sum() / max(
                    args.seconds, np.nanmax(got["answered"]))),
                "drain_s": float(np.nanmax(got["answered"]) - got["due"][-1]),
                "p50_ms": loadgen.percentile(latency, 50),
                "p95_ms": loadgen.percentile(latency, 95),
                "p99_ms": loadgen.percentile(latency, 99),
                "p95_first_half_ms": loadgen.percentile(latency[:len(latency) // 2], 95),
                "p95_second_half_ms": loadgen.percentile(latency[len(latency) // 2:], 95),
                "late_p95_ms": loadgen.percentile(
                    (got["sent"] - got["due"]) * 1e3, 95),
                "batch_fill": fill,
                "loadgen_lag_max_ms": max(got["lag"]["worst_ms_by_second"]),
                "parent_tick_max_ms": got["parent_tick"][0],
            }), flush=True)
    except children.CellFailed as e:
        print(f"no sweep: {e}", file=sys.stderr)
        return 2
    finally:
        if server is not None:
            server.stop()
        children.stop_all()
        shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
