"""Upstream's E-Commerce Recommendation ``predict`` as plain numpy float64:
one query at a time, no batching, no device, no cache. The engine's
serving path (``engine.py``) is held to it by the tests, and
``benchmarks/lib/reference_ecom.py`` is its copy for the benchmark.

Upstream is ``ALSAlgorithm.predict`` / ``predictNewUser`` of Apache
PredictionIO's E-Commerce Recommendation template (``engine.json`` with
``unseenOnly``, ``seenEvents``, ``similarEvents``; query ``user``, ``num``,
``categories``, ``whiteList``, ``blackList``; the ``unavailableItems``
constraint entity). Departures from it, each on purpose:

* ties go to the lowest item index (upstream's priority queue leaves
  their order open);
* an item without factors scores 0 and is never served (upstream skips
  it; the same answer, since only positive scores are served);
* the seen set, the recent views and the unavailable set are arguments:
  upstream reads them from the event store inside ``predict``; the
  caller reads them here, so that the reference holds no store;
* a user whose factor row is all zeros counts as having none (a row
  that no rating ever touched), as the engine has it.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np


def normalize(rows: np.ndarray) -> np.ndarray:
    """Rows over their L2 norms in float64; zero rows stay zero."""
    rows = np.asarray(rows, np.float64)
    norms = np.linalg.norm(rows, axis=-1, keepdims=True)
    return np.divide(rows, norms, out=np.zeros_like(rows), where=norms > 0)


def scores_for(
    user_factors: np.ndarray,
    item_factors: np.ndarray,
    user_row: Optional[int],
    recent_items: Sequence[int],
) -> Optional[np.ndarray]:
    """Float64 scores of every item: Y·x for a user with factors, else the
    sum over the (at most ten, newest first) recently viewed items of the
    cosine to every item; None where the user has neither."""
    Y = np.asarray(item_factors, np.float64)
    if user_row is not None and np.any(user_factors[user_row]):
        return Y @ np.asarray(user_factors[user_row], np.float64)
    recent = list(recent_items)[:10]
    if not recent:
        return None
    return normalize(Y) @ normalize(Y[recent]).sum(axis=0)


def candidates(
    n_items: int,
    *,
    unavailable: Iterable[int] = (),
    seen: Iterable[int] = (),
    black_list: Iterable[int] = (),
    white_list: Optional[Iterable[int]] = None,
    categories: Optional[Iterable[int]] = None,
    item_categories: Optional[np.ndarray] = None,
) -> np.ndarray:
    """[n_items] bool: all - unavailable - seen - blackList, ∩ whiteList,
    ∩ categories. ``item_categories`` is [n_items, C] category codes (-1
    where an item has fewer); ``categories`` the query's codes."""
    ok = np.ones(n_items, bool)
    for gone in (unavailable, seen, black_list):
        ok[np.asarray(list(gone), np.int64)] = False
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
    user_factors: np.ndarray,
    item_factors: np.ndarray,
    user_index: Dict[str, int],
    item_index: Dict[str, int],
    query: dict,
    *,
    seen: Iterable[str] = (),
    recent: Sequence[str] = (),
    unavailable: Iterable[str] = (),
    item_categories: Optional[np.ndarray] = None,
    category_names: Sequence[str] = (),
) -> List[Tuple[str, float]]:
    """One query (upstream's JSON field names) -> [(item, score)].
    ``seen`` are the items of the user's seen events (empty where
    ``unseenOnly`` is off), ``recent`` the items of their similar events,
    newest first."""
    def rows(names):
        return [item_index[i] for i in names if i in item_index]

    scores = scores_for(
        user_factors, item_factors, user_index.get(query["user"]),
        rows(list(recent)[:10]),
    )
    if scores is None:
        return []
    code = {c: j for j, c in enumerate(category_names)}
    ok = candidates(
        len(item_factors), unavailable=rows(unavailable), seen=rows(seen),
        black_list=rows(query.get("blackList") or ()),
        white_list=(None if query.get("whiteList") is None
                    else rows(query["whiteList"])),
        categories=(None if query.get("categories") is None else
                    [code[c] for c in query["categories"] if c in code]),
        item_categories=item_categories,
    )
    names = {v: k for k, v in item_index.items()}
    return [(names[i], s) for i, s in top(scores, ok, int(query.get("num", 10)))]
