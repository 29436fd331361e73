"""The cases of the whole-block parity tests (tests/test_retrieval.py:
float32; tests/test_retrieval_quantized.py: bf16 and int8).

``ItemRetriever`` pads its resident rows to whole blocks once, at build,
and its programs then neither pad nor slice. These cases hold what that
must not change: item counts on both sides of a block edge, every kind
of mask, the same ids in the same order as ``naive_topn_reference``,
ties to the lowest index, dead slots that stay under ``n_items``, an id
in the last real block that bites and one in the pad's range that is
dropped.

Below them the cases of ``_top_k`` itself against ``lax.top_k`` over
the whole row, at the widths where it takes its second level (the
quantized shortlists, 64 and 256), and ``one_level_top_k``: the top-k
as it was before that level, the yardstick of "the same answer"."""

import math

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.ops import retrieval
from predictionio_tpu.ops.retrieval import ItemRetriever, naive_topn_reference

# both sides of a block edge (2,048), two blocks and one row, a count of
# no shape at all, and one wide enough to leave ``_top_k``'s shortcut at
# a shortlist of 128 (293 blocks of 1,024 against 2 x 128)
ITEM_COUNTS = (2_047, 2_049, 4_097, 5_000, 300_000)
MASKS = ("none", "exclusion", "whitelist", "category", "positive_only")
RANK, N_CATS, NUM, BATCH = 8, 5, 8, 4


def table(n_items):
    """Factors whose last real row is query 0's best item by far, whose
    rows 7 and 11 are equal (a tie at every query) and whose last two
    rows are equal too; the queries; one category code an item."""
    rng = np.random.default_rng(n_items)
    Y = rng.standard_normal((n_items, RANK)).astype(np.float32)
    q = rng.standard_normal((BATCH, RANK)).astype(np.float32)
    Y[11] = Y[7] = 3.0 * q[1]  # query 1's two best, tied
    Y[n_items - 1] = Y[n_items - 2] = 4.0 * q[0]  # query 0's, tied
    codes = rng.integers(0, N_CATS, (n_items, 1)).astype(np.int32)
    return Y, q, codes


def check(n_items, precision, mask, mesh=None):
    Y, q, codes = table(n_items)
    r = ItemRetriever(
        Y, mesh=mesh, precision=precision, category_codes=codes,
        component=f"blocks-{precision}",
    )
    try:
        _check(r, Y, q, codes, mask, mesh)
    finally:  # a live sharded handle reads as drift in the ledger's tests
        r.free()


def _check(r, Y, q, codes, mask, mesh):
    n_items = r.n_items
    shards = 1 if mesh is None else mesh.shape["data"]
    assert r._n_pad % (shards * retrieval._ROW_BLOCK) == 0
    assert n_items <= r._n_pad
    assert r._n_pad < n_items + shards * retrieval._ROW_BLOCK
    last, pad_id = n_items - 1, r._n_pad - 1
    kw, ref_kw = {}, {}
    if mask == "exclusion":
        # the last real row goes (its twin takes the place); an id of
        # the pad and the sentinel's neighbour change nothing
        kw["exclude"] = [
            np.array([last, pad_id, n_items]), None, np.array([7]), None,
        ]
        ref_kw["exclude"] = [np.array([last]), None, np.array([7]), None]
    elif mask == "whitelist":
        # three live items for query 0 (fewer than NUM: dead slots), the
        # pad's id among them; query 2 asks for nothing at all
        kw["include"] = [
            np.array([3, last, 40, pad_id]), None, np.array([], np.int64),
            np.arange(0, 900, 3),
        ]
        ref_kw["include"] = [
            np.array([3, last, 40]), None, np.array([], np.int64),
            np.arange(0, 900, 3),
        ]
    elif mask == "category":
        cats = [np.array([2], np.int32), None, np.array([0], np.int32), None]
        kw["categories"] = cats
        ref_kw["include"] = [
            None if c is None else np.flatnonzero(codes[:, 0] == c[0])
            for c in cats
        ]
    elif mask == "positive_only":
        kw["positive_only"] = ref_kw["positive_only"] = True
    s, i = r.topn(q, NUM, **kw)
    ref_s, ref_i = naive_topn_reference(Y, q, NUM, **ref_kw)
    live = ref_s > -np.inf
    np.testing.assert_array_equal(s > -np.inf, live)
    np.testing.assert_array_equal(i[live], ref_i[live])
    np.testing.assert_allclose(s[live], ref_s[live], rtol=1e-5, atol=1e-5)
    assert i.min() >= 0 and i.max() < n_items  # the dead slots too
    if mask == "none":  # ties go to the lowest index
        assert list(i[0, :2]) == [n_items - 2, n_items - 1]
        assert list(i[1, :2]) == [7, 11]
    if mask == "exclusion":
        assert last not in i[0] and i[0, 0] == n_items - 2
        assert 7 not in i[2]
    if mask == "whitelist":
        assert list(i[0, :3]) == list(ref_i[0, :3]) and i[0, 0] == last
        assert (s[0, 3:] == -np.inf).all() and (s[2] == -np.inf).all()


# _top_k's second level: n from _SUB_FROM up, the batches of the ladder,
# and rows of whole blocks, more than 2·n of them for the widest n
TOP_K_WIDTHS = (64, 256)
TOP_K_BATCHES = (8, 16, 32)
TOP_K_ROWS = 600 * 1024
TOP_K_KINDS = ("ties", "dead", "one_block")


def top_k_scores(n, batch, kind):
    """[batch, TOP_K_ROWS] float32 scores of one kind of case.

    ``ties``: few distinct values, so every rank is a tie; row 0 holds
    one value throughout, and row 1 a run of 1.5·n equal best scores
    that starts inside a sub-block just before a block boundary and
    crosses it and several sub-block boundaries: the lowest indices win.
    ``dead``: row 0 has 7 live scores (fewer than ``n``), row 1 none,
    row 2 exactly ``n``. ``one_block``: row 0's best 1,024 lie in one
    block, row 1's best ``n`` in one sub-block where ``n`` fits one."""
    rng = np.random.default_rng(n * 1000 + batch)
    s = rng.standard_normal((batch, TOP_K_ROWS)).astype(np.float32)
    if kind == "ties":
        s = np.round(s * 2).astype(np.float32)
        s[0] = 1.0
        start = 3 * 1024 - n // 2 - 5
        s[1, start:start + n + n // 2] = 100.0
    elif kind == "dead":
        s[0, : TOP_K_ROWS - 7] = -np.inf
        s[1] = -np.inf
        s[2, rng.permutation(TOP_K_ROWS)[: TOP_K_ROWS - n]] = -np.inf
    else:
        s[0, 7 * 1024: 8 * 1024] += 10.0
        s[1, 9 * 1024 + 256: 9 * 1024 + 384] += 10.0
    return s


def one_level_top_k(scores, n: int):
    """``ops/retrieval.py::_top_k`` with one level of blocks alone:
    the top ``n`` of the ``n`` best blocks' ``n·_BLOCK`` scores."""
    b, rows = scores.shape
    block = retrieval._BLOCK
    if -(-rows // block) <= 2 * n:
        return jax.lax.top_k(scores, n)
    g = math.gcd(b, 8)
    blocks = scores.reshape(b // g, g, rows // block, block)
    _, best = jax.lax.top_k(blocks.max(axis=3).reshape(b, -1), n)
    best = jnp.sort(best, axis=1)
    cand = jnp.take_along_axis(
        blocks, best.reshape(b // g, g, n, 1), axis=2
    )
    s, j = jax.lax.top_k(cand.reshape(b, n * block), n)
    return s, (
        jnp.take_along_axis(best, j // block, axis=1) * block + j % block
    )
