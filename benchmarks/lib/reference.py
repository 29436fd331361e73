"""The plain references. They import nothing of the program and take
nothing that the program has made: data and seeds in, answers out.

* ``als_reference``: explicit ALS as the recommendation template runs it
  (MLlib 1.3 semantics: item factors start as |N(0,1)|/sqrt(k) from
  ``numpy.random.default_rng(seed)``, the user side is solved first, the
  regulariser is lambda x the row's observation count), in float64.
  With ``precision="bfloat16"`` it is the *control*: the gathered factor
  rows are rounded to bfloat16 before every product, which is what
  ``compute_dtype="bfloat16"`` would do to the normal equations.
* ``topn_reference``: the scores of a user row against every item, in
  float64 (or with both tables rounded to bfloat16, as the control).
"""

from __future__ import annotations

import concurrent.futures
import math

import numpy as np

CHUNK_BYTES = 8 * 2**20  # gathered rows a worker holds: small, so they stay in cache


def round_bfloat16(x):
    """Round float32 values to the nearest bfloat16 (ties to even)."""
    bits = np.ascontiguousarray(x, np.float32).view(np.uint32)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & np.uint32(0xFFFF0000)
    return bits.view(np.float32)


def _ladder(counts):
    """Pad each count up a ladder 1, 2, 3, 4, 6, 8, 12, 16, ... (at most
    a third wasted), so rows of equal padded length solve in one batch."""
    c = np.maximum(counts, 1).astype(np.int64)
    p2 = 1 << np.ceil(np.log2(c)).astype(np.int64)
    three_quarters = (p2 // 4) * 3
    return np.where((three_quarters >= c) & (p2 >= 4), three_quarters, p2)


def plan_side(rows, cols, vals, n_rows, n_cols):
    """Sort one side's observations by row and group rows of like length.
    Returns (counts, [(row ids, column of each slot [R, L], value of each
    slot [R, L])]); a padded slot points at column ``n_cols``, a zero row
    that the solver appends, and carries the value 0."""
    order = np.argsort(rows, kind="stable")
    cols_s = np.append(cols[order].astype(np.int64), n_cols)
    vals_s = np.append(vals[order].astype(np.float64), 0.0)
    counts = np.bincount(rows, minlength=n_rows).astype(np.int64)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    padded = _ladder(counts)
    groups = []
    present = np.flatnonzero(counts > 0)
    for length in np.unique(padded[present]):
        ids = present[padded[present] == length]
        lane = np.arange(length, dtype=np.int64)[None, :]
        pos = np.where(lane < counts[ids, None], starts[ids, None] + lane, -1)
        groups.append((ids, cols_s[pos], vals_s[pos]))
    return counts, groups


def _solve_chunk(ids, cols, vals, counts, other, reg, low):
    k = other.shape[1]
    G = other[cols]
    if low:
        G = round_bfloat16(G).astype(np.float64)
    Gt = G.transpose(0, 2, 1)
    A = np.matmul(Gt, G)
    b = np.matmul(Gt, vals[:, :, None])
    A += (reg * counts[ids])[:, None, None] * np.eye(k)[None]
    return ids, np.linalg.solve(A, b)[:, :, 0]


def solve_side(plan, prev, other, reg, low, pool):
    """One half-sweep: rows with observations get the ridge solution
    against ``other``; rows with none keep their previous value."""
    counts, groups = plan
    k = other.shape[1]
    out = prev.copy()
    other = np.vstack([other, np.zeros((1, k))])  # the padded slots' row
    jobs = []
    for ids, cols, vals in groups:
        step = max(1, CHUNK_BYTES // (cols.shape[1] * k * 8))
        for s in range(0, len(ids), step):
            jobs.append(pool.submit(
                _solve_chunk, ids[s:s + step], cols[s:s + step],
                vals[s:s + step], counts, other, reg, low,
            ))
    for job in jobs:
        ids, x = job.result()
        out[ids] = x
    return out


def als_reference(u, i, r, n_users, n_items, *, rank, iterations, reg, seed,
                  precision="float64", threads=12):
    """(X, Y) float64 after ``iterations`` sweeps from the program's
    initial state. ``u`` and ``i`` are dense row numbers."""
    if precision not in ("float64", "bfloat16"):
        raise ValueError(f"no such reference precision: {precision}")
    low = precision == "bfloat16"
    rng = np.random.default_rng(seed)
    Y = (np.abs(rng.standard_normal((n_items, rank))) / math.sqrt(rank))
    Y = Y.astype(np.float32).astype(np.float64)  # the program starts in float32
    X = np.zeros((n_users, rank), np.float64)
    plan_u = plan_side(u, i, r, n_users, n_items)
    plan_i = plan_side(i, u, r, n_items, n_users)
    with concurrent.futures.ThreadPoolExecutor(threads) as pool:
        for _ in range(iterations):
            X = solve_side(plan_u, X, Y, reg, low, pool)
            Y = solve_side(plan_i, Y, X, reg, low, pool)
    return X, Y


def topn_reference(X_rows, Y, precision="float64"):
    """Scores of each given user row against every item: [B, n_items]
    float64. The control rounds both tables to bfloat16 and accumulates
    the products in float32, as one pass of the chip's matrix unit does."""
    if precision == "float64":
        return X_rows.astype(np.float64) @ Y.astype(np.float64).T
    if precision == "bfloat16":
        return (round_bfloat16(X_rows) @ round_bfloat16(Y).T).astype(
            np.float64
        )
    raise ValueError(f"no such reference precision: {precision}")
