"""Upstream's Similar Product ``predict`` as plain numpy float64: one query
at a time, no batching, no device, no quantisation. The engine's serving
path (``engine.py``) is held to it by the tests, and
``benchmarks/lib/reference_similar.py`` is its copy for the benchmark.

Upstream is ``ALSAlgorithm.predict`` of Apache PredictionIO's Similar
Product template (query ``items``, ``num``, ``categories``, ``whiteList``,
``blackList``): the score of a candidate is the sum over the query items
of the cosine between the query item's factors and the candidate's; the
query items themselves, the blackList, everything outside the whiteList
or the categories and every score <= 0 are dropped; the top ``num`` are
served. Departures from it, each on purpose:

* ties go to the lowest item index (upstream's priority queue leaves
  their order open);
* an item without factors (an all-zero row) has cosine 0 to everything
  and is never served (upstream skips it; the same answer, since only
  positive scores are served);
* a query item the model does not know is skipped, and a query none of
  whose items is known is answered with nothing (upstream's
  ``flatMap`` over ``Option`` and its empty result);
* categories are codes in ``item_categories`` ([n_items, C] int32, -1
  where an item has fewer than C), not a set of strings an item.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


def normalize(rows: np.ndarray) -> np.ndarray:
    """Rows over their L2 norms in float64; zero rows stay zero."""
    rows = np.asarray(rows, np.float64)
    norms = np.linalg.norm(rows, axis=-1, keepdims=True)
    return np.divide(rows, norms, out=np.zeros_like(rows), where=norms > 0)


def cosine_sums(item_factors: np.ndarray, query_rows: Sequence[int]) -> np.ndarray:
    """Float64 scores of every item: the sum over the query items of the
    cosine to the item."""
    Yn = normalize(item_factors)
    return Yn @ Yn[np.asarray(list(query_rows), np.int64)].sum(axis=0)


def candidates(
    n_items: int,
    query_rows: Sequence[int],
    *,
    black_list: Sequence[int] = (),
    white_list: Optional[Sequence[int]] = None,
    categories: Optional[Sequence[int]] = None,
    item_categories: Optional[np.ndarray] = None,
) -> np.ndarray:
    """[n_items] bool: all - the query items - blackList, ∩ whiteList,
    ∩ categories (``categories`` are the query's codes)."""
    ok = np.ones(n_items, bool)
    ok[np.asarray(list(query_rows), np.int64)] = False
    ok[np.asarray(list(black_list), np.int64)] = False
    if white_list is not None:
        white = np.zeros(n_items, bool)
        white[np.asarray(list(white_list), np.int64)] = True
        ok &= white
    if categories is not None:
        ok &= np.isin(item_categories, np.asarray(list(categories))).any(axis=1)
    return ok


def top(scores: np.ndarray, ok: np.ndarray, num: int) -> List[Tuple[int, float]]:
    """The ``num`` best candidates of positive score, best first, ties to
    the lowest index."""
    live = np.flatnonzero(ok & (scores > 0))
    order = live[np.lexsort((live, -scores[live]))][:num]
    return [(int(i), float(scores[i])) for i in order]


def predict(
    item_factors: np.ndarray,
    item_index: Dict[str, int],
    query: dict,
    *,
    item_categories: Optional[np.ndarray] = None,
    category_names: Sequence[str] = (),
) -> List[Tuple[str, float]]:
    """One query (upstream's JSON field names) -> [(item, score)]."""
    def rows(names):
        return [item_index[i] for i in names if i in item_index]

    query_rows = rows(query["items"])
    if not query_rows:
        return []
    code = {c: j for j, c in enumerate(category_names)}
    ok = candidates(
        len(item_factors), query_rows,
        black_list=rows(query.get("blackList") or ()),
        white_list=(None if query.get("whiteList") is None
                    else rows(query["whiteList"])),
        categories=(None if query.get("categories") is None else
                    [code[c] for c in query["categories"] if c in code]),
        item_categories=item_categories,
    )
    names = {v: k for k, v in item_index.items()}
    scores = cosine_sums(item_factors, query_rows)
    return [(names[i], s) for i, s in top(scores, ok, int(query.get("num", 10)))]
