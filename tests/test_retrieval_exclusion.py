"""Exclusion lists applied after an over-fetched top-k
(ops/retrieval.py ``_masked_top_k``): where a list is no wider than the
top-k it feeds, each of the four retrieval kernels takes the top
``n + W`` without it and drops the listed candidates afterwards. These
hold that path to the membership grid it replaces, bit for bit on the
live slots, on one device and row-sharded over four; they also hold the
category compare to a numpy oracle and the counter to its runs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import retrieval
from predictionio_tpu.ops.retrieval import ItemRetriever
from tests.test_retrieval import _family_value, _mesh_or_none

# wide enough that the one-device top-k leaves its narrow shortcut (293
# blocks of 1,024) and a shard's too at a top-32 (74 blocks a shard)
N_ITEMS, RANK, BATCH, N_CATS = 300_000, 8, 8, 5
# kernel -> (shards, precision, the width of the top-k the list feeds)
KERNELS = {
    "fused_topn_single": (1, "float32", 16),
    "fused_topn_single_2s": (1, "int8", 64),
    "shard_topk_kernel": (4, "float32", 16),
    "shard_topk_kernel_2s": (4, "int8", 64),
}


def _table(kind):
    """Rows, queries and two category codes an item (-1 where it has
    fewer). ``ties``: every row is one of seven, so every score is one
    of seven a query and every rank a tie."""
    rng = np.random.default_rng(7)
    Y = rng.standard_normal((N_ITEMS, RANK)).astype(np.float32)
    if kind == "ties":
        Y = Y[rng.integers(0, 7, N_ITEMS)]
    q = rng.standard_normal((BATCH, RANK)).astype(np.float32)
    codes = rng.integers(0, N_CATS, (N_ITEMS, 2)).astype(np.int32)
    codes[rng.random(N_ITEMS) < 0.3, 1] = -1
    codes[rng.random(N_ITEMS) < 0.1] = -1
    return Y, q, codes


def _lists(Y, q, codes, w, n_pad, shards):
    """Per-query lists that bite: query 0 excludes its own top ``w``
    (every winner), 1 its top ``w // 2`` each written twice, 2 none, 3
    every other id of its top tie group (ties split), 4 ids of the last
    shard's rows and of the pad, 5 a category of one code and its top
    ids, 6 a whitelist of 40 and its best ``w // 2`` excluded, 7 a short list
    (sentinel-padded)."""
    top = np.argsort(-(q @ Y.T), axis=1, kind="stable")
    exclude = [None] * BATCH
    exclude[0] = top[0, :w]
    exclude[1] = np.repeat(top[1, : w // 2], 2)
    exclude[3] = top[3, : 2 * w : 2]
    last = n_pad - n_pad // shards
    exclude[4] = np.concatenate([
        top[4][top[4] >= last][: w - 2], [n_pad - 1, N_ITEMS],
    ])
    exclude[5] = top[5, :w]
    exclude[6] = top[6, : w // 2]
    exclude[7] = top[7, :3]
    include = [None] * BATCH
    include[6] = top[6, :40]
    categories = [None] * BATCH
    categories[5] = np.array([codes[top[5, 0], 0]], np.int32)
    return exclude, include, categories


def _run(r, kernel, operand, n, widths):
    """One run of ``kernel`` over ``r``'s resident state, traced anew
    (a new function for one device, a new ``_stage1`` on a mesh)."""
    shards, precision, _ = KERNELS[kernel]
    if shards == 1:
        body = getattr(retrieval, "_" + kernel).__wrapped__
        if precision == "float32":
            return jax.jit(lambda *a: body(*a), static_argnums=range(5, 9))(
                operand, r._y_dev, r._rn_dev, r._allow_dev, r._codes_dev,
                n, True, True, widths)
        return jax.jit(lambda *a: body(*a), static_argnums=range(6, 12))(
            operand, r._y_dev, r._scale_operand, r._rn_dev, r._allow_dev,
            r._codes_dev, 16, n, True, True, precision, widths)
    r._stage1_cache = {}
    if precision == "float32":
        return r._stage1(n, True, True, widths)(
            operand, r._y_dev, r._rn_dev, r._allow_dev, r._codes_dev)
    return r._stage1(16, True, True, widths, n)(
        operand, r._y_dev, r._scale_operand, r._rn_dev, r._allow_dev,
        r._codes_dev)


def _candidates(packed, shards):
    """(scores, ids) of a kernel's packed output, a shard's block at a
    time on a mesh."""
    a = np.asarray(packed)
    a = a.reshape(a.shape[0], shards, 2, -1)
    return np.ascontiguousarray(a[:, :, 0]).view(np.float32), a[:, :, 1]


@pytest.mark.parametrize("extra", [0, 1], ids=["W=n", "W=n+1"])
@pytest.mark.parametrize("kind", ["random", "ties"])
@pytest.mark.parametrize("kernel", list(KERNELS))
def test_after_topk_is_the_grid_bit_for_bit(kernel, kind, extra, monkeypatch):
    """Each kernel with lists ``W`` = n wide (applied after the top-k)
    and n + 1 wide (the grid, as before), against the same kernel with
    the grid forced: the same live scores and ids in the same order,
    dead slots -inf alone. The lists plant winners, duplicates, ties,
    ids of another shard and of the pad, and sentinel padding; a
    category filter and a whitelist ride beside them."""
    shards, precision, n = KERNELS[kernel]
    w = n + extra
    Y, q, codes = _table(kind)
    r = ItemRetriever(
        Y, mesh=_mesh_or_none(shards), precision=precision,
        category_codes=codes, category_width=3,
        component=f"excl-{kernel}",
    )
    try:
        rows = r._n_pad // shards
        assert retrieval._excl_after_topk(w, n, rows) == (extra == 0)
        lists = _lists(Y, q, codes, w, r._n_pad, shards)
        widths = (w, 64, 3)
        operand = jax.device_put(
            retrieval._pack_operand(q, BATCH, widths, r._n_pad, *lists,
                                    np.zeros(BATCH, bool)),
            r._operand_at,
        )
        got_s, got_i = _candidates(_run(r, kernel, operand, n, widths), shards)
        monkeypatch.setattr(retrieval, "_excl_after_topk", lambda *a: False)
        want_s, want_i = _candidates(
            _run(r, kernel, operand, n, widths), shards)
    finally:
        r.free()
    live = want_s > -np.inf
    np.testing.assert_array_equal(got_s > -np.inf, live)
    np.testing.assert_array_equal(got_s[live], want_s[live])
    np.testing.assert_array_equal(got_i[live], want_i[live])
    assert live[[0, 2, 5]].any(axis=(1, 2)).all()  # lists that left winners
    for b, ids in enumerate(lists[0]):
        if ids is not None:  # no listed id is live
            assert not np.isin(got_i[b][got_s[b] > -np.inf], ids).any()
    cat = lists[2][5][0]
    assert (codes[got_i[5][got_s[5] > -np.inf]] == cat).any(axis=1).all()


def test_category_compare_against_numpy():
    """The unrolled category compare: an item matches where any of its
    codes is any of the query's; an item's -1 and a query's -2 padding
    match nothing, and a query without a filter keeps every row."""
    rows = 2 * retrieval._LO
    rng = np.random.default_rng(3)
    codes = rng.integers(0, 4, (rows, 2)).astype(np.int32)
    codes[rng.random(rows) < 0.4, 1] = -1
    codes[:50] = -1
    cats = np.array([[1, -2, -2], [0, 3, -2], [-2, -2, -2], [2, 2, 1]],
                    np.int32)
    has_cat = np.array([True, True, False, True])
    scores = jnp.asarray(rng.standard_normal((4, rows)), jnp.float32)
    none = jnp.full((4, 1), rows, jnp.int32)
    got = np.asarray(retrieval._mask_scores(
        scores, jnp.ones(rows, bool), none, none, jnp.zeros(4, bool), False,
        (jnp.asarray(codes), jnp.asarray(cats), jnp.asarray(has_cat)),
    )) > -np.inf
    want = (codes[None, :, :, None] == cats[:, None, None, :]).any(axis=(2, 3))
    want |= ~has_cat[:, None]
    np.testing.assert_array_equal(got, want)
    assert not got[[0, 1, 3], :50].any() and got[2].all()


@pytest.mark.parametrize("precision", ["float32", "int8"])
@pytest.mark.parametrize("shards", [1, 4])
def test_the_counter_counts_runs_after_the_top_k(shards, precision):
    """``pio_retrieval_exclusion_after_topk_total`` counts one a
    ``topn`` whose lists went after the top-k (a list of 16 against a
    top-16, or a shortlist of 256), none where the grid was built (a
    list of 512 over either) or where no query had a list; the answers
    are the reference's either way."""
    mesh = _mesh_or_none(shards)
    rng = np.random.default_rng(11)
    Y = rng.standard_normal((5_000, 8)).astype(np.float32)
    q = Y[:3]
    component = f"excl-count-{precision}x{shards}"
    family = "pio_retrieval_exclusion_after_topk_total"
    r = ItemRetriever(Y, mesh=mesh, precision=precision, component=component,
                      exclude_ladder=(1, 16, 512))
    try:
        for width, counted in ((16, 1), (512, 0), (1, 0)):
            exclude = [np.arange(i, i + width) for i in range(3)]
            if width == 1:
                exclude = None
            before = _family_value(family, component=component)
            s, i = r.topn(q, 16, exclude=exclude)
            assert _family_value(family, component=component) == (
                before + counted), width
            _, ref_i = retrieval.naive_topn_reference(Y, q, 16,
                                                      exclude=exclude)
            np.testing.assert_array_equal(i, ref_i)
    finally:
        r.free()
