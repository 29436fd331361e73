"""The plain reference of the Similar Product cell, and its comparison.

A copy of ``predictionio_tpu/models/similarproduct/reference.py``
(upstream's ``predict`` in float64; its departures from upstream are
listed there) in numpy alone, importing nothing of the program, over the
item table in row blocks so that 9.4 M x 512 need never be in memory:
the blocks come from the float32 file the set-up wrote (mapped), or, for
the control in a sandbox that cannot hold that file, from the seed's own
streams. Score = the sum over the query items of the cosine to the item;
candidates = all - the query items - blackList, ∩ whiteList,
∩ category, score > 0; top ``num``, ties to the lowest index. It runs
after the server has stopped, over a sample of the window's answers.

With ``precision="int8"`` the scores are a *stand-in* for the control:
the device's stage-1 scores served as they are (rows and query quantized
to int8 a row, the int32 product rescaled, the cosine taken with the
dequantized rows' norms). ``rescore`` with ``precision="bfloat16"`` is
the other: the shortlist's refine done in bfloat16.
"""

from __future__ import annotations

import numpy as np

from .reference import round_bfloat16
from .reference_ecom import normalize, parse_answer  # noqa: F401

BLOCK = 1 << 17


def file_blocks(Y, block=BLOCK):
    """(first row, float32 rows) over a table that is indexable by rows
    (an array, or a file mapped into memory)."""
    for a in range(0, Y.shape[0], block):
        yield a, np.asarray(Y[a:a + block], np.float32)


def query_vectors(rows_of, queries):
    """[Q, k] float64: for each query the sum of the normalized factor
    rows of its items. ``rows_of(ids)`` gives their float32 rows."""
    return np.stack([
        normalize(rows_of(np.asarray(q["items"], np.int64))).sum(axis=0)
        for q in queries])


def _quantized_scores(Q, Yb):
    """The int8 tier's stage-1 cosines of one block, as
    ``ops/retrieval.py::_approx_scores`` and ``_scale_cosine`` make them:
    float32 arithmetic around an exact integer product."""
    def quantize(rows):
        scale = np.abs(rows).max(axis=1) / np.float32(127.0)
        scale = np.where(scale > 0, scale, np.float32(1.0)).astype(np.float32)
        return np.clip(np.rint(rows / scale[:, None]), -127, 127), scale

    qi, qs = quantize(Q.astype(np.float32))
    yi, ys = quantize(Yb)
    deq = (yi * ys[:, None]).astype(np.float32)
    norms = np.sqrt(np.einsum("ij,ij->i", deq, deq))
    rn = np.divide(np.float32(1.0), norms, out=np.zeros_like(norms),
                   where=norms > 0)
    acc = qi.astype(np.float64) @ yi.astype(np.float64).T  # exact integers
    return (acc.astype(np.float32) * qs[:, None] * ys[None, :]
            * rn[None, :]).astype(np.float64)


def reference_topn(queries, Q, blocks, item_cats, precision="float64"):
    """For each query the reference's own best ``num`` (scores and items)
    and its scores of the items that were served.

    A query is a dict: ``exclude`` (sorted item ids: the query items ∪
    blackList), ``white`` (sorted ids or None), ``category`` (code or
    None), ``num``, ``served`` (item ids). ``Q`` are the query vectors
    (``query_vectors``), ``blocks`` yields (first row, float32 rows) over
    the whole table in ascending order, ``item_cats`` is [n_items]."""
    if precision not in ("float64", "int8"):
        raise ValueError(f"no such reference precision: {precision}")
    nq = len(queries)
    top = max(q["num"] for q in queries)
    found = [[] for _ in queries]  # per query: (scores, ids) of each block
    kept = [np.zeros(0) for _ in queries]  # its best num scores so far
    floor = np.zeros(nq)  # only positive scores are served
    served = [np.full(len(q["served"]), np.nan) for q in queries]
    for a, Yb in blocks:
        b = a + len(Yb)
        if precision == "float64":
            # cosine = dot over norm: the product of a block, then one
            # scale a column (a normalized copy of the block costs five
            # times the product)
            Y64 = Yb.astype(np.float64)
            norms = np.sqrt(np.einsum("ij,ij->i", Y64, Y64))
            S = Q @ Y64.T
            S *= np.divide(1.0, norms, out=np.zeros_like(norms),
                           where=norms > 0)
        else:
            S = _quantized_scores(Q, Yb)
        cats_b = item_cats[a:b]
        for r, q in enumerate(queries):
            ok = np.ones(b - a, bool)
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


def rescore(Q, rows, precision):
    """[len(rows)] scores of one query vector against a few float32
    rows, as the host refine makes them: at ``float32`` the program's
    own arithmetic; at ``bfloat16`` the stand-in, both operands of the
    product rounded (the norms stay float32)."""
    q, rows = Q.astype(np.float32), np.asarray(rows, np.float32)
    norms = np.sqrt(np.einsum("ij,ij->i", rows, rows))
    rn = np.divide(np.float32(1.0), norms, out=np.zeros_like(norms),
                   where=norms > 0)
    if precision == "bfloat16":
        q, rows = round_bfloat16(q), round_bfloat16(rows)
    elif precision != "float32":
        raise ValueError(f"no such refine precision: {precision}")
    return ((rows @ q) * rn).astype(np.float64)


def serve_numbers(numbers, queries, got):
    """``recall_at_num``: of all the items of the reference's best lists,
    the share that was served (one number over all the queries).
    ``score_err``: how far a served score lies from the reference's score
    of the same item. ``order_err``: by how much the reference's scores
    of a served list rise from one place to the next, at worst (a list
    in the reference's order reads 0). An answer shorter or longer than
    the reference's is wrong. ``queries[r]["served"]`` /
    ``["served_scores"]`` are the answer under test: the program's, or a
    stand-in's put in its place."""
    hit = total = 0
    for q, g in zip(queries, got):
        best, ref = g["best_items"], g["served_scores"]
        hit += len(np.intersect1d(q["served"], best))
        total += len(best)
        if len(q["served"]) != len(best):
            numbers.wrong += 1
            continue
        if len(best) == 0:
            continue
        oks = [
            numbers.add("score_err",
                        float(np.max(np.abs(q["served_scores"] - ref)))),
            numbers.add("order_err",
                        max(0.0, float(np.max(np.diff(ref), initial=0.0)))),
        ]
        numbers.wrong += not all(oks)
    for name in ("score_err", "order_err"):
        if name not in numbers.out:
            numbers.add(name, 0.0)
    recall = hit / total if total else 0.0
    limit = numbers.limits["recall_at_num"]
    numbers.out["recall_at_num"] = {
        "value": recall, "limit": limit, "ok": bool(recall >= limit)}
    return hit, total
