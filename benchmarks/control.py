#!/usr/bin/env python3
"""The control of each cell's comparison, and the readings of its faults.

    python3 benchmarks/control.py --workload <name> --seeds 1,2,3 [--faults 1]

The control is the plain reference put in the program's place and
computed in the nearest precision below the one the configuration states
(its ``control_precision``). It has to come out as not correct by the
same comparison and the same limits as a run's. This script needs no
chip and no program: numpy alone, at the cell's own size. The benchmark's
own runs never call it; ``tests/test_control.py`` keeps it at a small
size. One JSON line a seed.
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

from lib import compare, data, loadgen, reference  # noqa: E402
from run import load_json  # noqa: E402


def train_control(config, seed, faults=False):
    """{name of what stood in for the program: its numbers}."""
    shape = config["shape"]
    algo = config["engine"]["algorithms"][0]["params"]
    u, i, r = data.synth_ratings(
        shape["n_users"], shape["n_items"], shape["n_events"],
        config["data"]["structure_seed"], seed,
    )
    present_u, du = data.dense_codes(u, shape["n_users"])
    present_i, di = data.dense_codes(i, shape["n_items"])
    def als(precision="float64", sweeps=algo["num_iterations"], events=None):
        e = slice(None, events)
        return reference.als_reference(
            du[e], di[e], r[e], len(present_u), len(present_i),
            rank=algo["rank"], iterations=sweeps, reg=algo["lambda_"],
            seed=seed, precision=precision,
        )

    X_ref, Y_ref = als()
    stand_ins = {"control_" + config["control_precision"]:
                 lambda: als(config["control_precision"])}
    if faults:
        altered = X_ref.copy()
        altered[len(altered) // 2] *= 1.5
        stand_ins.update(
            a_sweep_left_out=lambda: als(sweeps=algo["num_iterations"] - 1),
            half_the_events=lambda: als(events=len(r) // 2),
            a_row_altered=lambda: (altered, Y_ref),
        )
    out = {}
    for name, make in stand_ins.items():
        numbers = compare.Numbers(config["limits"])
        X, Y = make()
        compare.train_numbers(
            numbers, X, Y, present_u, present_i, X_ref, Y_ref, present_u,
            present_i,
        )
        out[name] = numbers.out
    return out


def serve_control(config, traffic, seed, seconds):
    shape = config["shape"]
    X = data.seeded_factors(shape["n_users"], shape["rank"], seed, 0)
    Y = data.seeded_factors(shape["n_items"], shape["rank"], seed, 1)
    _, users, nums = loadgen.make_schedule(traffic, seconds, seed)
    pick = compare.sample_answers(
        nums, np.ones(len(nums), bool), config["verify"]["answers"], seed
    )
    numbers = compare.Numbers(config["limits"])
    compare.serve_numbers(
        numbers, None, pick, users, nums, X, Y,
        control=config["control_precision"],
    )
    return {"control_" + config["control_precision"]: numbers.out}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--faults", type=int, default=0)
    ap.add_argument("--manifest", default=None,
                    help="a file under benchmarks/ in BENCHMARK.json's shape")
    args = ap.parse_args(argv)
    run_seconds = load_json(os.path.dirname(BENCH), "BENCHMARK.json")["run_seconds"]
    manifest = (load_json(BENCH, args.manifest) if args.manifest
                else load_json(os.path.dirname(BENCH), "BENCHMARK.json"))
    (cell,) = [w for w in manifest["workloads"] if w["name"] == args.workload]
    config = load_json(BENCH, "configs", cell["config"] + ".json")
    traffic = load_json(BENCH, "traffic", cell["traffic"] + ".json")
    all_failed = True
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        if traffic["kind"] == "train-loop":
            got = train_control(config, seed, bool(args.faults))
        else:
            got = serve_control(config, traffic, seed,
                                args.seconds or run_seconds)
        verdicts = {
            k: all(n["ok"] for n in v.values()) for k, v in got.items()
        }
        all_failed &= not any(verdicts.values())
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "seconds": time.time() - t0, "correct": verdicts,
            "compared": {k: {n: v[n]["value"] for n in v} for k, v in got.items()},
        }), flush=True)
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
