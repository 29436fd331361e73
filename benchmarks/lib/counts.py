"""Operations and bytes that the algorithms *need*, from shapes alone.

These are the numerators of the roofline and utilization shares; they
count the algorithm's work, never the implementation's (no multiplier for
multi-pass float32 products, no padding, no recomputation). Each function
takes the configuration's ``shape`` mapping and a mapping of observed
counts (events, answers, batches) and returns {"flops": ..., "bytes": ...}.
"""

from __future__ import annotations


def als_loop(shape, seen):
    """The ten sweeps of explicit ALS. Per side per sweep: the normal
    equations take 2 nnz k^2 (A) + 2 nnz k (b) operations and each of the R
    rows a k x k Cholesky solve, k^3/3. Bytes that must move: the wire
    (two int32 ids and a float32 rating per observation) read once, one
    gathered factor row of k float32 per observation, and the side's
    factor table written once."""
    k, nnz = shape["rank"], seen["events"]
    sweeps = shape["iterations"]
    flops = bytes_ = 0.0
    for rows in (seen["users"], seen["items"]):
        flops += 2.0 * nnz * k * k + 2.0 * nnz * k + rows * k**3 / 3.0
        bytes_ += nnz * 12.0 + nnz * k * 4.0 + rows * k * 4.0
    return {"flops": flops * sweeps, "bytes": bytes_ * sweeps}


def topn_batches(shape, seen):
    """Top-N over the whole catalog, as served: each *batch* reads the
    item table once (N k float32) and one user row per query; each query
    costs 2 N k operations, counted once."""
    n, k = shape["n_items"], shape["rank"]
    q, b = seen["queries"], seen["batches"]
    return {"flops": 2.0 * q * n * k, "bytes": b * n * k * 4.0 + q * k * 4.0}


def topn_queries(shape, seen):
    """The serving step end to end: 2 N k operations an answer."""
    return {"flops": 2.0 * seen["queries"] * shape["n_items"] * shape["rank"],
            "bytes": 0.0}


COUNTS = {
    "als_loop": als_loop, "topn_batches": topn_batches,
    "topn_queries": topn_queries,
}


def roofline_seconds(work, peaks):
    """(least seconds the chip could take, which bound sets it)."""
    by_flops = work["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "bytes")
