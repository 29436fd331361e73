#!/usr/bin/env python3
"""Find the knee of the Similar Product cell once, on the chip: one
deployment, one open-loop window at each of a few fixed rates
(``sweep_ecom.py``'s rule for the cell whose kind is ``similar-queries``).
A builder's tool; the benchmark's runs never call it, and the cell's rate
is a number in its traffic file.

    python3 benchmarks/sweep_similar.py --rates 100,200,300 --seconds 40
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
from lib import children, layers, loadgen, reference_similar, similar  # noqa: E402
from lib.kinds import similar_queries as serving  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload",
                    default="simprod-amazon-d512.query-detail-page")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    scratch = os.path.join(harness.ROOT, ".bench_work")
    os.makedirs(scratch, exist_ok=True)
    work = tempfile.mkdtemp(prefix="sweep_", dir=scratch)
    server = None
    try:
        run = harness.build_run(manifest, args.workload, args.seed,
                                args.seconds, False, work)
        server, ctx, *_ = serving.start_server(run)
        for rate in (float(r) for r in args.rates.split(",")):
            traffic = dict(run.traffic, rate_per_s=rate)
            got = serving.offer(run, server, ctx, traffic, args.seconds)
            sched = similar.make_schedule(traffic, run.config, args.seconds,
                                          run.seed)
            ok = np.array([
                status == 200 and reference_similar.parse_answer(
                    body, int(num)) is not None
                for (_, _, status, body), num in zip(got["out"], sched["nums"])
            ])
            latency = (got["answered"] - sched["due"]) * 1e3
            prom = {"prom": got["scrapes"]}
            half = len(latency) // 2

            def ms(family):
                return 1e3 * (layers.read(prom, f"prom:{family}:mean") or 0.0)

            print(json.dumps({
                "rate_per_s": rate, "offered": len(latency),
                "well_formed": int(ok.sum()),
                "completed_per_s": float(ok.sum() / max(
                    args.seconds, np.nanmax(got["answered"]))),
                "drain_s": float(np.nanmax(got["answered"]) - sched["due"][-1]),
                "p50_ms": loadgen.percentile(latency, 50),
                "p95_ms": loadgen.percentile(latency, 95),
                "p99_ms": loadgen.percentile(latency, 99),
                "p95_first_half_ms": loadgen.percentile(latency[:half], 95),
                "p95_second_half_ms": loadgen.percentile(latency[half:], 95),
                "late_p95_ms": loadgen.percentile(
                    (got["sent"] - sched["due"]) * 1e3, 95),
                "batch_fill": layers.read(
                    prom, "prom:pio_serving_batch_fill:mean"),
                "predict_ms": ms("pio_serving_predict_seconds"),
                "device_wait_ms": ms("pio_serving_batch_device_wait_seconds"),
                "refine_ms": ms("pio_serving_batch_refine_seconds"),
                "cold_compiles": layers.read(
                    prom, "prom:pio_cold_compiles_total:delta"),
                "host_fallbacks": layers.read(
                    prom, "prom:pio_similar_host_fallback_total:delta"),
                "memory": serving.memory_of(server.proc.pid),
                "loadgen_lag_max_ms": max(got["lag"]["worst_ms_by_second"]),
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
