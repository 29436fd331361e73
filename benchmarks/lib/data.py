"""Inputs, made from the seed with numpy alone.

Every seed does the same work in another order: the *structure* (which
pairs are rated, how many requests, which gaps between them) comes from a
seed fixed in the configuration or traffic file, and ``--seed`` relabels
it and draws the values. The compiled shapes therefore never depend on
``--seed``, and runs with different seeds differ no more than runs of one.
"""

from __future__ import annotations

import concurrent.futures

import numpy as np


def user_name(v) -> str:
    return f"u{int(v):06d}"


def item_name(v) -> str:
    return f"i{int(v):05d}"


def rating_structure(n_users, n_items, n_events, structure_seed):
    """Which (user, item) pairs are rated: MovieLens-20M's margins
    (lognormal user activity, zipf item popularity), as `chip_smoke.py`
    draws them. Popularity-ordered ids, int32."""
    rng = np.random.default_rng(structure_seed)
    u_p = rng.lognormal(0, 1.1, n_users)
    u_p /= u_p.sum()
    i_p = 1.0 / np.arange(1, n_items + 1) ** 0.9
    i_p /= i_p.sum()
    u = rng.choice(n_users, size=n_events, p=u_p).astype(np.int32)
    i = rng.choice(n_items, size=n_events, p=i_p).astype(np.int32)
    return u, i


def synth_ratings(n_users, n_items, n_events, structure_seed, seed):
    """MovieLens-20M-shaped synthetic ratings (the dataset is not in the
    image and the chip machine has no network): the fixed structure with
    its ids relabelled by the seed, and low-rank-plus-noise scores from
    the seed snapped to ML-20M's 0.5-step 0.5..5.0 scale."""
    u0, i0 = rating_structure(n_users, n_items, n_events, structure_seed)
    rng = np.random.default_rng(seed)
    u = rng.permutation(n_users).astype(np.int32)[u0]
    i = rng.permutation(n_items).astype(np.int32)[i0]
    k0 = 12
    U = (rng.standard_normal((n_users, k0)) / np.sqrt(k0)).astype(np.float32)
    V = (rng.standard_normal((n_items, k0)) / np.sqrt(k0)).astype(np.float32)
    raw = np.empty(n_events, np.float32)
    for s in range(0, n_events, 4_000_000):
        e = min(s + 4_000_000, n_events)
        raw[s:e] = np.einsum("nk,nk->n", U[u[s:e]], V[i[s:e]])
    scores = 3.0 + 1.3 * raw + 0.5 * rng.standard_normal(n_events)
    r = np.clip(np.round(scores * 2.0) / 2.0, 0.5, 5.0).astype(np.float32)
    return u, i, r


FACTOR_BLOCKS = 16  # fixed: the factors may not depend on the core count


def seeded_factors(n_rows, rank, seed, side):
    """Float32 normal rows scaled by rank**-0.25, so that a user row dot
    an item row has variance 1. Filled in FACTOR_BLOCKS independent
    streams, a thread each (numpy's generators release the lock)."""
    out = np.empty((n_rows, rank), np.float32)
    streams = np.random.SeedSequence([int(seed), side]).spawn(FACTOR_BLOCKS)
    edges = np.linspace(0, n_rows, FACTOR_BLOCKS + 1).astype(np.int64)
    scale = np.float32(rank ** -0.25)

    def fill(b):
        block = out[edges[b]:edges[b + 1]]
        np.random.default_rng(streams[b]).standard_normal(
            block.shape, dtype=np.float32, out=block
        )
        block *= scale

    with concurrent.futures.ThreadPoolExecutor(8) as pool:
        list(pool.map(fill, range(FACTOR_BLOCKS)))
    return out


class Rows:
    """The factor rows of the users a window asks about and no others,
    indexed by user id as the whole table would be: the reference needs
    no more, and 4.7 GB less sits in the parent while the window runs."""

    def __init__(self, table, users):
        self.ids = np.unique(users)
        self.rows = table[self.ids]

    def __getitem__(self, users):
        return self.rows[np.searchsorted(self.ids, users)]


def zipf_ids(n, s, size, rng):
    """``size`` ids in [0, n) with P(id) proportional to (id+1)**-s."""
    p = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    cdf = np.cumsum(p)
    return np.searchsorted(cdf, rng.random(size) * cdf[-1]).astype(np.int64)


def dense_codes(ids, n):
    """(ids present in ascending order, each id's rank among them): what
    ``numpy.unique(ids, return_inverse=True)`` gives, without the sort."""
    present = np.bincount(ids, minlength=n) > 0
    rank = np.cumsum(present) - 1
    return np.flatnonzero(present), rank[ids]
