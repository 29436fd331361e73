"""The plain reference of the e-commerce cell, and its comparison.

A copy of ``predictionio_tpu/models/ecommerce/reference.py`` (upstream's
``predict`` in float64; its departures from upstream are listed there) in
numpy alone, importing nothing of the program, blocked over the items so
that 4.16 M x 512 fit: scores = Y·x for a user with factors, else the sum
of the cosines between the ten most recent views and every item;
candidates = all - unavailable - seen - blackList, ∩ whiteList,
∩ categories, score > 0; top ``num``, ties to the lowest index. It runs
after the server has stopped, over a sample of the window's answers with
the masks each answer was served under.

With ``precision="bfloat16"`` it is the *control*: both operands of the
big product rounded to bfloat16 and accumulated in float32, which is what
a TPU's default matmul precision would do to it.
"""

from __future__ import annotations

import json

import numpy as np

from .reference import round_bfloat16

BLOCK = 1 << 18


def item_id(name) -> int:
    """Item names are i%05d: the row is in the name."""
    return int(name[1:])


def parse_answer(body, num):
    """(item rows, scores) of a 200 answer with at most ``num`` distinct
    well-formed items, else None."""
    try:
        scored = json.loads(body)["itemScores"]
        items = np.array([item_id(s["item"]) for s in scored], np.int64)
        scores = np.array([float(s["score"]) for s in scored], np.float64)
    except (ValueError, KeyError, TypeError, IndexError):
        return None
    if len(items) > num or len(set(items.tolist())) != len(items):
        return None
    return items, scores


def normalize(rows):
    rows = np.asarray(rows, np.float64)
    norms = np.linalg.norm(rows, axis=-1, keepdims=True)
    return np.divide(rows, norms, out=np.zeros_like(rows), where=norms > 0)


def recent_vector(Y, recent):
    """Σ of the normalized factor rows of the (at most ten, newest first)
    recently viewed items, float64."""
    return normalize(Y[np.asarray(recent[:10], np.int64)]).sum(axis=0)


def reference_topn(queries, Y, item_cats, unavailable, precision="float64",
                   block=BLOCK):
    """For each query the reference's own best ``num`` (scores and items)
    and its scores of the items that were served.

    A query is a dict: ``row`` (the float64 query vector), ``cosine``
    (scored against normalized items), ``exclude`` (sorted item ids:
    seen ∪ blackList), ``white`` (sorted ids or None), ``category`` (code
    or None), ``version`` (index into ``unavailable``, the [n_items] bool
    masks in force), ``num``, ``served`` (item ids). ``Y`` is the float32
    item table."""
    n_items, nq = Y.shape[0], len(queries)
    if precision not in ("float64", "bfloat16"):
        raise ValueError(f"no such reference precision: {precision}")
    Q = np.stack([q["row"] for q in queries]).astype(np.float64)
    if precision == "bfloat16":
        Q_low = round_bfloat16(Q.astype(np.float32))
    cosine = np.array([q["cosine"] for q in queries], bool)
    top = max(q["num"] for q in queries)
    found = [[] for _ in queries]  # per query: (scores, ids) of each block
    kept = [np.zeros(0) for _ in queries]  # its best num scores so far
    floor = np.zeros(nq)  # only positive scores are served
    served = [np.full(len(q["served"]), np.nan) for q in queries]
    for a in range(0, n_items, block):
        b = min(a + block, n_items)
        if precision == "float64":
            Yb = Y[a:b].astype(np.float64)
            S = Q @ Yb.T
        else:
            Yb = Y[a:b]
            S = (Q_low @ round_bfloat16(Yb).T).astype(np.float64)
        if cosine.any():
            norms = np.linalg.norm(Y[a:b].astype(np.float64), axis=1)
            S[cosine] *= np.divide(
                1.0, norms, out=np.zeros_like(norms), where=norms > 0)
        cats_b = item_cats[a:b]
        for r, q in enumerate(queries):
            ok = ~unavailable[q["version"]][a:b]
            ex = q["exclude"]
            ok[ex[np.searchsorted(ex, a):np.searchsorted(ex, b)] - a] = False
            if q["white"] is not None:
                w = q["white"]
                inside = np.zeros(b - a, bool)
                inside[w[np.searchsorted(w, a):np.searchsorted(w, b)] - a] = True
                ok &= inside
            if q["category"] is not None:
                ok &= cats_b == q["category"]
            # only what beats the query's num-th best so far can still be
            # among its best: after the first block that is a handful
            live = np.flatnonzero(ok & (S[r] > floor[r]))
            s = S[r][live]
            if len(live) > top:
                part = np.argpartition(-s, top - 1)[:top]
                live, s = live[part], s[part]
            found[r].append((s, live + a))
            kept[r] = np.sort(np.concatenate([kept[r], s]))[-q["num"]:]
            if len(kept[r]) == q["num"]:
                # ties to the lowest index: an equal score in a later
                # block never displaces one already kept
                floor[r] = max(0.0, kept[r][0])
            mine = np.flatnonzero((q["served"] >= a) & (q["served"] < b))
            served[r][mine] = S[r][q["served"][mine] - a]
    out = []
    for r, q in enumerate(queries):
        s = np.concatenate([f[0] for f in found[r]])
        i = np.concatenate([f[1] for f in found[r]])
        order = np.lexsort((i, -s))[:q["num"]]
        out.append({"best_scores": s[order], "best_items": i[order],
                    "served_scores": served[r]})
    return out


def serve_numbers(numbers, queries, got):
    """``rank_gap``: how far a served item's reference score lies below
    the reference's own item at that rank, at worst; an answer shorter or
    longer than the reference's is infinitely far. ``score_err``: how far
    a served score lies from the reference's score of the same item.
    ``queries[r]["served"]`` / ``["served_scores"]`` are the answer under
    test: the program's, or (the control) the low-precision reference's
    own best list put in its place."""
    for q, g in zip(queries, got):
        best, ref = g["best_scores"], g["served_scores"]
        if len(q["served"]) != len(best):
            numbers.add("rank_gap", np.inf)
            numbers.wrong += 1
            continue
        if len(best) == 0:
            numbers.add("rank_gap", 0.0)
            numbers.add("score_err", 0.0)
            continue
        oks = [
            numbers.add("rank_gap", max(0.0, float(np.max(best - ref)))),
            numbers.add("score_err",
                        float(np.max(np.abs(q["served_scores"] - ref)))),
        ]
        numbers.wrong += not all(oks)


def control_answers(queries, Y, item_cats, unavailable, precision):
    """Put the reference at ``precision`` in the program's place: each
    query's ``served`` and ``served_scores`` become its own best list."""
    for q in queries:
        q["served"] = np.zeros(0, np.int64)
    for q, g in zip(queries, reference_topn(
            queries, Y, item_cats, unavailable, precision)):
        q["served"], q["served_scores"] = g["best_items"], g["best_scores"]
