"""Operations and bytes that the quantized retrieval tier *needs*, from
shapes alone, beside ``counts.py`` (whose rules hold here: the
algorithm's work, never the implementation's). Registered into
``counts.COUNTS`` by the kind that reads it (``kinds/similar_queries.py``).
"""

from __future__ import annotations


def topn_batches_quantized(shape, seen):
    """Top-N over the whole catalog held in int8, as the two-stage
    program serves it: each *batch* reads the item table once at ONE
    byte an element (N k), one float32 scale and one float32 reciprocal
    norm an item (8 N), and one float32 query row a query; each query
    costs 2 N k operations, counted once and set against the bf16 peak
    by ``roofline_seconds`` (at the widest batch of the ladder that is
    under the bytes' time, so bytes bind and the int8 peak is not
    needed). The shortlist's gather and rescore, the mask and the
    category codes are under 1 % of the bytes and left out."""
    n, k = shape["n_items"], shape["rank"]
    q, b = seen["queries"], seen["batches"]
    return {"flops": 2.0 * q * n * k,
            "bytes": b * (n * k * 1.0 + 8.0 * n) + q * k * 4.0}
