#!/usr/bin/env python3
"""The control of the e-commerce cell's comparison, at the cell's own
width, with numpy alone (no chip, no program): the plain reference put in
the program's place with both operands of the product rounded to the
configuration's ``control_precision``. It has to come out as not correct
by the run's own comparison and limits. One JSON line a seed.

    python3 benchmarks/control_ecom.py --seeds 1,2,3 [--queries 200]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

from lib import compare, data, ecom, reference_ecom  # noqa: E402
from run import load_json  # noqa: E402


def control(config, traffic, seed, seconds, n_queries):
    shape = config["shape"]
    sched = ecom.make_schedule(traffic, config, seconds, seed)
    history = ecom.History(config, seed)
    cats = ecom.item_categories(shape, config)
    gone = np.zeros(shape["n_items"], bool)
    gone[ecom.unavailable_items(shape, config, seed)] = True
    Y = data.seeded_factors(shape["n_items"], shape["rank"], seed, 1)
    pick = np.random.default_rng(seed).choice(
        len(sched["due"]), size=min(n_queries, len(sched["due"])),
        replace=False)
    known = [k for k in pick if sched["users"][k] < sched["n_held"]]
    X = data.Rows(
        data.seeded_factors(shape["n_users"], shape["rank"], seed, 0),
        sched["users"][known])
    queries = []
    for k in pick:
        code, shape_k = int(sched["users"][k]), int(sched["shapes"][k])
        cosine = code >= sched["n_held"]
        recent = [i for _, i in sorted(history.views(code), reverse=True)]
        queries.append({
            "row": (reference_ecom.recent_vector(Y, recent) if cosine
                    else X[np.array([code])][0].astype(np.float64)),
            "cosine": cosine, "exclude": np.unique(history.seen(code)),
            "white": (sched["white"].get(int(k))
                      if shape_k == ecom.WHITE_LIST else None),
            "category": (int(sched["category"][k])
                         if shape_k == ecom.CATEGORY else None),
            "version": 0, "num": int(sched["nums"][k]),
        })
    reference_ecom.control_answers(
        queries, Y, cats, [gone], config["control_precision"])
    numbers = compare.Numbers(config["limits"])
    reference_ecom.serve_numbers(numbers, queries, reference_ecom.reference_topn(
        queries, Y, cats, [gone]))
    return numbers.out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="ecom-taobao-d512.query-filtered")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--queries", type=int, default=200)
    args = ap.parse_args(argv)
    manifest = load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    (cell,) = [w for w in manifest["workloads"] if w["name"] == args.workload]
    config = load_json(BENCH, "configs", cell["config"] + ".json")
    traffic = load_json(BENCH, "traffic", cell["traffic"] + ".json")
    all_failed = True
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        got = control(config, traffic, seed, manifest["run_seconds"],
                      args.queries)
        ok = all(n["ok"] for n in got.values())
        all_failed &= not ok
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "seconds": time.time() - t0,
            "correct": {"control_" + config["control_precision"]: ok},
            "compared": {k: v["value"] for k, v in got.items()},
        }), flush=True)
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
