#!/usr/bin/env python3
"""The control of the Similar Product cell's comparison, at the cell's own
width, with numpy alone (no chip, no program, no 19 GB file: the table is
drawn from the seed a stream at a time). Stand-ins are put in the
program's place and held to the run's own comparison and limits:

* ``program``: the exact shortlist of 64 refined in float32, which is the
  program's own arithmetic, and has to come out correct;
* ``bfloat16_refine``: the same shortlist refined with both operands of
  the product rounded to the configuration's ``control_precision``;
* ``int8_scores_served``: the device's stage-1 scores served as they are
  (the best ``num`` by the int8 cosines, with those scores);
* ``shortlist_one_wide``: the device's best candidate alone.

Each of the last three has to come out as not correct. One JSON line a
seed.

    python3 benchmarks/control_similar.py --seeds 1,2 [--queries 30]
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

from lib import compare, data, reference_similar, similar  # noqa: E402
from run import load_json  # noqa: E402

SHORTLIST = 64  # what the device hands the host refine a query
STAND_INS = ("program", "bfloat16_refine", "int8_scores_served",
             "shortlist_one_wide")


def stand_ins(config, queries, rows_of, blocks, cats, names=STAND_INS):
    """{stand-in: the comparison's numbers}. ``rows_of(ids)`` gives
    float32 rows of the table, ``blocks()`` a fresh pass over it."""
    queries = [dict(q, served=np.zeros(0, np.int64)) for q in queries]
    Q = reference_similar.query_vectors(rows_of, queries)
    wide = reference_similar.reference_topn(
        [dict(q, num=SHORTLIST) for q in queries], Q, blocks(), cats)
    low = None
    if {"int8_scores_served", "shortlist_one_wide"} & set(names):
        low = reference_similar.reference_topn(
            queries, Q, blocks(), cats, "int8")
    ids = np.unique(np.concatenate(
        [g["best_items"] for g in wide + (low or [])]))
    rows = rows_of(ids)

    def rows_at(items):
        return rows[np.searchsorted(ids, items)]

    out = {}
    for name in names:
        served, got = [], []
        for r, (q, g) in enumerate(zip(queries, wide)):
            if name in ("program", "bfloat16_refine"):
                s = reference_similar.rescore(
                    Q[r], rows_at(g["best_items"]),
                    "float32" if name == "program"
                    else config["control_precision"])
                order = np.lexsort((g["best_items"], -s))[:q["num"]]
                order = order[s[order] > 0]
                items, scores = g["best_items"][order], s[order]
            else:
                take = 1 if name == "shortlist_one_wide" else q["num"]
                items = low[r]["best_items"][:take]
                scores = low[r]["best_scores"][:take]
            served.append(dict(q, served=items, served_scores=scores))
            got.append({
                "best_items": g["best_items"][:q["num"]],
                "best_scores": g["best_scores"][:q["num"]],
                "served_scores": reference_similar.normalize(
                    rows_at(items)) @ Q[r]})
        numbers = compare.Numbers(config["limits"])
        reference_similar.serve_numbers(numbers, served, got)
        out[name] = numbers.out
    return out


class Rows:
    """Rows of the seeded table by item id, drawn from the seed's
    streams: one pass for the ids not held yet."""

    def __init__(self, blocks):
        self.blocks, self.held = blocks, {}

    def __call__(self, ids):
        ids = np.asarray(ids, np.int64)
        missing = np.unique([i for i in ids.tolist() if i not in self.held])
        if len(missing):
            for a, block in self.blocks():
                for i in missing[np.searchsorted(missing, a):
                                 np.searchsorted(missing, a + len(block))]:
                    self.held[int(i)] = block[i - a].copy()
        return np.stack([self.held[int(i)] for i in ids])


def control(config, traffic, seed, seconds, n_queries):
    shape = config["shape"]
    sched = similar.make_schedule(traffic, config, seconds, seed)
    cats = similar.item_categories(shape, config)
    rng = np.random.default_rng(seed)
    per_shape = max(1, n_queries // len(similar.SHAPES))
    pick = np.concatenate([
        rng.permutation(np.flatnonzero(sched["shapes"] == s))[:per_shape]
        for s in range(len(similar.SHAPES))])
    queries = [{
        "items": sched["items"][k],
        "exclude": np.union1d(sched["items"][k],
                              sched["black"].get(k, np.zeros(0, np.int64))),
        "white": sched["white"].get(k),
        "category": (int(sched["category"][k])
                     if sched["category"][k] >= 0 else None),
        "num": int(sched["nums"][k]),
    } for k in pick.tolist()]

    def blocks():
        for stream in range(data.FACTOR_BLOCKS):
            yield from similar.stream_pieces(
                shape["n_items"], shape["rank"], seed, stream)

    rows_of = Rows(blocks)
    rows_of(np.concatenate([q["items"] for q in queries]))
    return stand_ins(config, queries, rows_of, blocks, cats)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload",
                    default="simprod-amazon-d512.query-detail-page")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--queries", type=int, default=30)
    args = ap.parse_args(argv)
    manifest = load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    (cell,) = [w for w in manifest["workloads"] if w["name"] == args.workload]
    config = load_json(BENCH, "configs", cell["config"] + ".json")
    traffic = load_json(BENCH, "traffic", cell["traffic"] + ".json")
    as_expected = True
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        got = control(config, traffic, seed, manifest["run_seconds"],
                      args.queries)
        ok = {name: all(n["ok"] for n in out.values())
              for name, out in got.items()}
        as_expected &= ok == {name: name == "program" for name in got}
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "seconds": time.time() - t0, "correct": ok,
            "compared": {name: {k: v["value"] for k, v in out.items()}
                         for name, out in got.items()},
        }), flush=True)
    return 0 if as_expected else 1


if __name__ == "__main__":
    sys.exit(main())
