"""The comparison that decides ``correct``: what the timed path produced
against the plain reference, each number beside a limit of its own.
The limits live in the configuration's file; PERF.md gives the readings
each was set from.
"""

from __future__ import annotations

import json

import numpy as np

from . import reference


class Numbers:
    """Name -> worst value seen, its limit, and whether it holds. A value
    that is not finite never holds."""

    def __init__(self, limits):
        self.limits, self.out, self.wrong = limits, {}, 0

    def add(self, name, value):
        value = float(value)
        if not np.isfinite(value):
            value = float("inf")
        if name in self.out and not value > self.out[name]["value"]:
            return self.out[name]["ok"]
        limit = self.limits[name]
        ok = bool(np.isfinite(value) and value <= limit)
        self.out[name] = {"value": value, "limit": limit, "ok": ok}
        return ok


# --- a persisted ALS model against the reference's ---


def _row_gap(A, A_ref):
    """Worst row: the norm of its difference over the reference row's norm
    or the median row's, whichever is larger (some rows are all but 0)."""
    norms = np.linalg.norm(A_ref, axis=1)
    floor = np.maximum(norms, np.median(norms))
    return float(np.max(np.linalg.norm(A - A_ref, axis=1) / floor))


def train_numbers(numbers, X, Y, rows_u, rows_i, X_ref, Y_ref, present_u,
                  present_i):
    """One model's numbers. ``rows_*`` are the raw ids of the model's
    factor rows, ``present_*`` those of the reference's."""
    if (X.shape != X_ref.shape or Y.shape != Y_ref.shape
            or not np.array_equal(rows_u, present_u)
            or not np.array_equal(rows_i, present_i)):
        numbers.add("rows_not_as_the_data", 1)
        numbers.wrong += 1
        return
    numbers.add("rows_not_as_the_data", 0)
    X, Y = X.astype(np.float64), Y.astype(np.float64)
    oks = [
        numbers.add("user_factor_gap",
                    np.linalg.norm(X - X_ref) / np.linalg.norm(X_ref)),
        numbers.add("item_factor_gap",
                    np.linalg.norm(Y - Y_ref) / np.linalg.norm(Y_ref)),
        numbers.add("worst_row_gap",
                    max(_row_gap(X, X_ref), _row_gap(Y, Y_ref))),
    ]
    numbers.wrong += not all(oks)


# --- served answers against the reference's scores ---


def parse_answers(out, nums):
    """(answers, shaped): per request the served (item rows, scores), or
    None; and whether it came at all, with status 200 and ``num`` distinct
    well-formed items. Item names are i%05d: the row is in the name."""
    answers, shaped = [], np.zeros(len(out), bool)
    for k, (_, _, status, body) in enumerate(out):
        answers.append(None)
        if status != 200:
            continue
        try:
            scored = json.loads(body)["itemScores"]
            items = np.array([int(s["item"][1:]) for s in scored], np.int64)
            scores = np.array([float(s["score"]) for s in scored])
        except (ValueError, KeyError, TypeError, IndexError):
            continue
        if len(items) == nums[k] and len(set(items.tolist())) == len(items):
            answers[k] = (items, scores)
            shaped[k] = True
    return answers, shaped


def sample_answers(nums, shaped, n, seed):
    """Indices of ``n`` well-formed answers drawn from the seed, one of
    the longest among them."""
    ok = np.flatnonzero(shaped)
    if len(ok) == 0:
        return ok
    pick = np.random.default_rng(seed).choice(ok, size=min(n, len(ok)),
                                              replace=False)
    longest = ok[np.argmax(nums[ok])]
    return np.unique(np.append(pick, longest))


def serve_numbers(numbers, answers, pick, users, nums, X, Y, control=None,
                  block=256):
    """``rank_gap``: how far a served item's reference score lies below
    the reference's own item at that rank, at worst. ``score_err``: how far
    a served score lies from the reference's score of the same item.
    With ``control`` set, the answers are not the program's but the
    reference's own at that lower precision."""
    n_items = Y.shape[0]
    for s in range(0, len(pick), block):
        part = pick[s:s + block]
        ref = reference.topn_reference(X[users[part]], Y)
        low = (reference.topn_reference(X[users[part]], Y, control)
               if control else None)
        for row, k in enumerate(part):
            num = int(nums[k])
            if control:
                items = np.argsort(-low[row], kind="stable")[:num]
                scores = low[row][items]
            else:
                items, scores = answers[k]
            if items.min() < 0 or items.max() >= n_items:
                numbers.add("rank_gap", np.inf)
                numbers.wrong += 1
                continue
            best = -np.sort(-ref[row])[:num]
            oks = [
                numbers.add("rank_gap",
                            max(0.0, float(np.max(best - ref[row][items])))),
                numbers.add("score_err",
                            float(np.max(np.abs(scores - ref[row][items])))),
            ]
            numbers.wrong += not all(oks)
