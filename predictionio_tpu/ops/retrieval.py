"""Sharded on-device top-N retrieval: mesh-resident item factors, fused
score+top-k per shard, cross-shard merge, and on-device candidacy masks.

This is the ALX serving recipe (PAPERS.md, arXiv:2112.02194) applied to
the query path: where ``ServingFactors`` (ops/als.py) REPLICATES the
catalog on every device and data-parallelizes over query rows, this
module ROW-SHARDS the item-factor matrix over the mesh — the layout that
keeps scaling once the catalog outgrows a single device's HBM — and
never materializes the full [B, N] score matrix anywhere:

1. **Per-shard fused score+top-k** (``shard_map``): every device holds
   its factor rows resident between queries, scores the whole query
   batch against its slice with one [B, k] x [k, N/S] matmul, applies
   the candidacy masks as ``-inf`` IN the same program, and runs
   ``lax.top_k`` over its slice. No collective in this stage.
2. **Cross-shard merge**: each shard contributes its top
   ``min(n, rows_per_shard)`` candidates (score + global-id bits packed
   in one buffer); only those B x S x n_local rows cross the
   interconnect (sharded→replicated constraint), and one final
   ``top_k`` over the concatenated candidates yields the EXACT global
   top-N — every global top-n element is by construction within its own
   shard's top-n, so the merge loses nothing. Tie-breaking matches a
   full-matrix ``top_k`` (lowest index wins): within a shard ``top_k``
   orders ties by local index, and the merge concatenates shards in
   ascending-offset order.
3. **Candidacy as on-device masks**: business rules (ecommerce's
   unavailable/blacklist/seen sets, similarproduct's query-item
   exclusion) stop being a host post-filter over the full score row.
   A RESIDENT global mask (refreshed out-of-band on constraint-entity
   change, see data/constraints.py) plus small per-query
   inclusion/exclusion id lists travel as indices and scatter into the
   mask on device; masked scores become ``-inf`` before ``top_k``. An
   exclusion list no wider than the top-k it feeds is applied to the
   candidates of a top-k over-fetched by its width instead
   (``_masked_top_k``): no ``[B, N]`` mask is built for it.

The single-device fallback is the SAME kernel fused into one jit
(score + mask + top_k, one dispatch) — 1-device serving no longer
materializes the full score row per query on host, and the parity tests
cover both shapes. The final packed buffer rides the
``_topn_packed`` layout (``ops/als.py _pack_topn``: score bits beside
int32 ids in one int32 buffer — and the row-sharded output pinning
lesson of ``_topn_packed_sharded``): one fetch per batch, ids never
pass through a float.

Metrics (utils/metrics.py conventions, visible in ``pio top``):
``pio_retrieval_merge_rows_total{component}`` (candidate
rows the merge takes across the sharded→replicated hop, every batch;
the merge's dispatch is the batch stage ``merge``),
``pio_retrieval_mask_refresh_total{component,outcome}``,
``pio_retrieval_mask_age_seconds{component}``,
``pio_retrieval_resident_bytes{component}``,
``pio_retrieval_operand_transfers_total{component}`` (host-to-device
transfers ``topn`` made: one a call),
``pio_retrieval_topk_two_level_total{component}`` (runs whose top-k took
its second level, ``_two_level``),
``pio_retrieval_exclusion_after_topk_total{component}`` (runs whose
exclusion lists were applied after the top-k, ``_excl_after_topk``),
and for the quantized tiers' host
refine ``pio_retrieval_shortlist_rows_total{component}`` (candidate
rows gathered and rescored) and
``pio_retrieval_refine_changed_total{component}`` (answers it changed).

Device-observability round: the resident factors/norms and the
candidacy mask register in the HBM residency ledger
(``pio_device_ledger_bytes{device,component,owner}``,
utils/device_ledger.py) — component ``<component>`` for factors+norms,
``<component>-mask`` for the constraint-fed mask; executable compiles
(the fused single-device program and the per-shard stage-1 ladder)
report through utils/compilation_cache.py's executable-cache
accounting, so one compiling inside a live serving batch is counted in
``pio_cold_compiles_total{site="serving"}`` and annotated on the
serving trace. Batches record padding waste
(``pio_padding_waste_ratio{site}``), and on a mesh one batch in
``_SKEW_SAMPLE_EVERY`` records cross-shard skew
(``pio_retrieval_shard_skew{kind}`` — candidate-count and final-result
imbalance over the mesh, the stage-1 load-imbalance proxy: per-shard
scoring work is shape-uniform, so imbalance shows up in candidate
survival, not FLOPs), from the shards' candidates fetched after the
answer, with no barrier between the two programs. How long the shards'
program and the merge ran on the device is the profiler trace's to say.

Quantized residency (the approximate-computing MF / ALX recipe for
10M+-item catalogs, arXiv:1808.03843 + arXiv:2112.02194): with
``precision="int8"`` the resident rows store as int8 with one float32
scale per row (symmetric per-row quantization, ``scale =
max|row|/127``); ``"bf16"`` is the middle tier. Retrieval becomes
two stages fused into the SAME per-shard program: stage 1 quantizes
the query block the same way and contracts in the quantized domain
(int8 x int8 -> int32 accumulate — the MXU-native form) with the
dequant-rescale epilogue (``* q_scale * row_scale``) fused onto the
accumulator, masks exactly as the float32 path does, and shortlists
the top-(c·n) candidates; stage 2 gathers ONLY those c·n rows,
dequantizes them to float32, and rescores against the full-precision
query BEFORE the (unchanged) cross-shard merge, so the per-shard
truncation keeps the right candidates. The merge returns the full
c·n-wide candidate list, and a final host refinement rescores those
c·n rows per query against the ORIGINAL float32 factors — which stay
on the host, where every engine keeps them (in RAM, or as the mapped
file of a ``PersistentModel``; the quantized staging copy is made in
row blocks and dropped once uploaded); HBM holds only the quantized
rows. B·c·n·k host FLOPs per batch is noise
next to the device matmul, and it buys id parity with the exact path:
returned scores are exact over the original matrix, and recall can
only be lost when a true top-n item misses the entire merged c·n
shortlist (int8 round-trip error at the top-n boundary alone costs
~0.5% recall; the wide-shortlist + original-rows refine is what gets
the gate to ≥ 0.999). ``float32`` keeps the single-stage exact path
byte-for-byte. Capacity shows up in the ledger (component
``<component>/<precision>`` for quantized deployments) and in
``pio_retrieval_bytes_per_item{component,precision}``.
"""

from __future__ import annotations

import concurrent.futures
import functools
import logging
import math
import operator
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from predictionio_tpu.ops.als import _pack_topn, unpack_topn
from predictionio_tpu.ops.similarity import pow2_at_least
from predictionio_tpu.parallel.mesh import pad_to_multiple
from predictionio_tpu.utils import compilation_cache as _cc
from predictionio_tpu.utils import device_ledger as _ledger
from predictionio_tpu.utils import metrics as _metrics
from predictionio_tpu.utils import tracing as _tracing

logger = logging.getLogger(__name__)

# one batch in this many on a mesh also fetches the shards' candidates,
# after its answer, for pio_retrieval_shard_skew (ItemRetriever.topn)
_SKEW_SAMPLE_EVERY = 16

# executable keys this process already compiled on the SHARED
# single-device fused-program jit cache (executable-cache accounting:
# the cache is process-global, so the seen-set must be too — a second
# retriever with identical shapes hits jit's cache, not a compile)
_FUSED_SEEN: set = set()


# serving-time residency precisions for the resident item matrix
# (ItemRetriever ``precision=``, plumbed from the engines' params)
PRECISIONS = ("float32", "bf16", "int8")


def quantize_rows_int8(
    factors: np.ndarray,
) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric per-row int8 quantization: ``scale = max|row|/127``,
    ``row_q = round(row/scale)``. Zero rows get scale 1.0 (their
    quantized form is all-zero either way), so dequantization never
    divides by zero and padding rows stay exactly zero."""
    f = np.asarray(factors, np.float32)
    scale = np.abs(f).max(axis=1) / 127.0
    scale = np.where(scale > 0, scale, 1.0).astype(np.float32)
    q = np.clip(np.rint(f / scale[:, None]), -127, 127).astype(np.int8)
    return q, scale


def dequantize_rows_int8(
    rows_q: np.ndarray, scale: np.ndarray
) -> np.ndarray:
    """f32 rows the int8 storage round-trips to — the matrix the exact
    stage-2 rescore (and therefore the parity oracle) scores against."""
    return rows_q.astype(np.float32) * np.asarray(scale, np.float32)[:, None]


def _reciprocal_norms(factors: np.ndarray) -> np.ndarray:
    """1/||y|| per row, 0 for zero rows — multiplying raw dot scores by
    this yields cosine-against-normalized-candidates, so ONE resident
    factor matrix serves both raw-dot (known-user) and cosine
    (similar-items) scoring instead of two catalog-sized copies."""
    f = np.asarray(factors, np.float32)
    # einsum, not linalg.norm: the latter squares into a temporary as
    # large as the table (8.5 GB at 4.16 M x 512)
    norms = np.sqrt(np.einsum("ij,ij->i", f, f))
    return np.where(norms > 0, 1.0 / np.where(norms == 0, 1.0, norms), 0.0).astype(
        np.float32
    )


# rows a worker quantizes at a time (4 MB of float32 at rank 512), and
# how many workers: the temporaries of a block stay in cache and their
# sum stays far under one int8 table
_QUANT_BLOCK_ROWS = 2048
_QUANT_THREADS = 4


def _quantize_resident(factors: np.ndarray, n_pad: int, precision: str):
    """The quantized tier's host arrays, made in row blocks straight
    from the caller's table (which may be a file mapped into memory, 19
    GB at 9.4 M x 512): ``(rows [n_pad, k] int8 or bfloat16, per-row
    scales [n_pad] or None, reciprocal norms of the DEQUANTIZED rows
    [n_pad], reciprocal norms of the original rows [n_pad])``. Padding
    rows stay zero. Never a float32 copy of the table, never its
    dequantized form: a block's temporaries are a block's."""
    n, k = factors.shape
    rows = np.zeros(
        (n_pad, k), np.int8 if precision == "int8" else jnp.bfloat16
    )
    scale = np.ones(n_pad, np.float32) if precision == "int8" else None
    rn, rn_exact = np.zeros(n_pad, np.float32), np.zeros(n_pad, np.float32)

    def block(a: int) -> None:
        b = min(a + _QUANT_BLOCK_ROWS, n)
        f = np.asarray(factors[a:b], np.float32)
        rn_exact[a:b] = _reciprocal_norms(f)
        if precision == "int8":
            sc = np.maximum(f.max(axis=1), -f.min(axis=1)) / 127.0
            sc = np.where(sc > 0, sc, 1.0).astype(np.float32)
            t = f / sc[:, None]
            np.rint(t, out=t)
            np.clip(t, -127, 127, out=t)
            rows[a:b] = t  # the cast of quantize_rows_int8
            scale[a:b] = sc
            np.multiply(rows[a:b], sc[:, None], out=t)
        else:
            rows[a:b] = f
            t = rows[a:b].astype(np.float32)
        rn[a:b] = _reciprocal_norms(t)

    # numpy releases the lock inside each of a block's passes
    with concurrent.futures.ThreadPoolExecutor(_QUANT_THREADS) as pool:
        list(pool.map(block, range(0, n, _QUANT_BLOCK_ROWS)))
    return rows, scale, rn, rn_exact


# rows of a float32 table that go up at a time (256 MB at rank 512)
_UPLOAD_ROWS = 1 << 17


@functools.partial(jax.jit, donate_argnums=0)
def _write_rows(table, block, at):
    """``table`` with ``block`` at row ``at``, in ``table``'s own buffer."""
    return jax.lax.dynamic_update_slice(table, block, (at, 0))


def _upload_padded(rows: np.ndarray, n_pad: int, device):
    """``[n_pad, k]`` on ``device`` (None: the default device): the
    caller's ``[n, k]`` rows with zero rows after them, written in row
    blocks into one donated buffer. The padding rows are made on the
    device: a padded copy of an 8.5 GB table fits neither the host's
    memory beside the table nor the chip's beside the result. One block
    is in flight at a time."""
    table = jnp.zeros((n_pad, rows.shape[1]), rows.dtype, device=device)
    for a in range(0, len(rows), _UPLOAD_ROWS):
        block = jax.device_put(rows[a:a + _UPLOAD_ROWS], device)
        table = jax.block_until_ready(_write_rows(table, block, a))
    return table


def _upload_row_sharded(rows: np.ndarray, n_pad: int, sharding):
    """``[n_pad, k]`` laid out by ``sharding`` (rows split over a mesh
    axis): each device's shard is ``_upload_padded`` from a view of the
    caller's rows, in the rows that device owns, and the shards are
    assembled as one array. Only a shard that runs past the caller's
    rows gets padding rows, and those are made on its device: no host
    copy of the table, padded or not (19 GB at 9.4 M x 512)."""
    shape = (n_pad, rows.shape[1])
    shards = []
    for device, (at, _) in sharding.addressable_devices_indices_map(
        shape
    ).items():
        start, stop, _ = at.indices(n_pad)
        shards.append(_upload_padded(
            rows[start:min(stop, len(rows))], stop - start, device
        ))
    return jax.make_array_from_single_device_arrays(shape, sharding, shards)


def _batch_sizes(max_batch: int) -> Tuple[int, ...]:
    """The padded batch sizes: 8 doubling until ``max_batch`` is held."""
    sizes = [8]
    while sizes[-1] < max_batch:
        sizes.append(sizes[-1] * 2)
    return tuple(sizes)


def _ladder(widths) -> Optional[Tuple[int, ...]]:
    """A closed ladder of widths, ascending and at least 1, or None."""
    if widths is None:
        return None
    out = tuple(sorted({max(1, int(w)) for w in widths}))
    if not out:
        raise ValueError("a ladder needs at least one width")
    return out


def _longest(lists) -> int:
    """The length of the longest list (``None`` entries carry none)."""
    return max((len(a) for a in lists if a is not None), default=0)


# an item id splits into a high and a low digit, id = hi * _LO + lo
_LO = 2048
# the width of one block of the block-wise top-k
_BLOCK = 1024
# the width of one sub-block of its second level: one lane row
_SUB = 128
# the second level runs from this many winners up. The last sort takes
# n·_BLOCK scores a query: at the quantized tiers' shortlists (64, 256)
# that is 65,536 / 262,144, and a top-256 of 262,144 costs 1.5 ms of a
# 10 ms program on a v5e at a batch of 8 (PERF.md §5), where n·_SUB =
# 32,768 is an eighth of it. At n <= 32 (the float32 programs' top-16,
# each shard's) the sort is small and the program stays as it is
_SUB_FROM = 64
# resident rows come in whole blocks of both (``ItemRetriever`` pads its
# table once, at build), so a score block, its masks and its block
# maxima are reshapes of one another and no program pads or slices
_ROW_BLOCK = math.lcm(_LO, _BLOCK)
# lists wider than this are folded in pieces, so that the one-hots of a
# [128, 8192] block never stand in memory at once
_LIST_CHUNK = 1024


def _membership(ids, rows: int):
    """[B, rows] bool: does row j appear in ``ids[b]``? ``rows`` is whole
    blocks of ``_LO`` (a resident table's always are). Computed as the
    product of two one-hot encodings (the id's high digit, its low
    digit) on the matrix unit: ``grid[b, hi, lo] = sum_w [hi_w == hi] *
    [lo_w == lo]``, handed back as it is made: its ``rows / _LO`` x
    ``_LO`` cells ARE the rows, nothing is sliced off. A scatter of
    ``[B, W]`` ids into a ``[B, rows]``
    mask lowers on the TPU to a loop over the batch's rows that rewrites
    each whole row (0.7 ms a row at 4.16 M items: over half of the fused
    program at a batch of 32, PERF.md PR 28); this costs 2·B·W·rows
    operations in one bfloat16 pass, exact because its operands are 0
    and 1. Ids outside ``[0, rows)`` (the sentinel of padded slots, ids
    owned by another shard) match no high digit and are dropped; the id
    of a padding row marks that row, which the resident mask has
    already taken out. A width of 1 (what a batch without a list of
    this kind ships) is one compare an element inside the scoring pass:
    no grid is made, and none has to be laid out as the scores are."""
    b, w = ids.shape
    if w == 1:  # "no list", or a list of one: a compare, not a grid
        return ids == jnp.arange(rows, dtype=jnp.int32)[None, :]
    n_hi = rows // _LO
    hi_of = jnp.arange(n_hi, dtype=jnp.int32)[None, None, :]
    lo_of = jnp.arange(_LO, dtype=jnp.int32)[None, None, :]

    def fold(part):
        hi = (part // _LO)[:, :, None] == hi_of
        lo = (part % _LO)[:, :, None] == lo_of
        return jnp.einsum(
            "bwh,bwl->bhl", hi.astype(jnp.bfloat16),
            lo.astype(jnp.bfloat16), preferred_element_type=jnp.float32,
        )

    if w <= _LIST_CHUNK:
        grid = fold(ids)
    else:
        ids = jnp.pad(  # -1 has no high digit: it matches nothing
            ids, ((0, 0), (0, -w % _LIST_CHUNK)), constant_values=-1
        )
        pieces = ids.reshape(b, -1, _LIST_CHUNK)
        grid, _ = jax.lax.scan(
            lambda acc, part: (acc + fold(part), None),
            jnp.zeros((b, n_hi, _LO), jnp.float32),
            jnp.moveaxis(pieces, 1, 0),
        )
    return (grid > 0).reshape(b, rows)


def _top_k(scores, n: int):
    """``lax.top_k(scores, n)`` over a wide score block, in two steps:
    the maximum of each block of ``_BLOCK`` scores, the ``n`` blocks
    with the largest maxima, then the top ``n`` of those blocks' scores.
    Exact, ties included: an element of the true top ``n`` lies in one
    of the ``n`` best blocks (each better block holds an element that
    beats it), the blocks are taken up in index order, so that equal
    scores still go to the lowest index. ``lax.top_k`` over [B, 4.16 M]
    is 8 ms at a batch of 32 and 36 ms at 128 (PERF.md PR 28); the
    block maxima are one pass over the scores. A wide score block is
    whole blocks (a resident table's rows are), and it is read in the
    form it was written in: ``[B, rows]`` tiled eight rows by 128
    columns is ``[B/8, 8, rows/_BLOCK, _BLOCK]`` in the same order, so
    nothing is padded or copied at any batch size. A narrow one, of
    any width, goes to ``lax.top_k`` itself. A dead slot (fewer than
    ``n`` live candidates) holds -inf and the index of any row, a
    padding row's too: ``ItemRetriever._unpack`` brings those under
    ``n_items`` on the host.

    From ``_SUB_FROM`` winners up (``_two_level``) the same argument
    runs once more inside the ``n`` blocks: the maxima of their
    sub-blocks of ``_SUB`` in index order, the ``n`` best of those, and
    the last sort over ``n·_SUB`` scores instead of ``n·_BLOCK``."""
    b, rows = scores.shape
    if -(-rows // _BLOCK) <= 2 * n:  # a narrow block: nothing to gain
        return jax.lax.top_k(scores, n)
    g = math.gcd(b, 8)
    blocks = scores.reshape(b // g, g, rows // _BLOCK, _BLOCK)
    _, best = jax.lax.top_k(blocks.max(axis=3).reshape(b, -1), n)
    best = jnp.sort(best, axis=1)
    cand = jnp.take_along_axis(
        blocks, best.reshape(b // g, g, n, 1), axis=2
    )
    if not _two_level(rows, n):
        s, j = jax.lax.top_k(cand.reshape(b, n * _BLOCK), n)
        return s, (
            jnp.take_along_axis(best, j // _BLOCK, axis=1) * _BLOCK
            + j % _BLOCK
        )
    per = _BLOCK // _SUB
    subs = cand.reshape(b // g, g, n * per, _SUB)
    _, sub = jax.lax.top_k(subs.max(axis=3).reshape(b, n * per), n)
    sub = jnp.sort(sub, axis=1)
    cand = jnp.take_along_axis(
        subs, sub.reshape(b // g, g, n, 1), axis=2
    ).reshape(b, n * _SUB)
    # a stable sort, not lax.top_k: a TPU splits a top-256 of 32,768
    # into sorts by value alone, which leave equal scores in any order
    # (PERF.md §6); sorted stably, ties go to the lowest index
    s, j = jax.lax.sort(
        (-cand, jax.lax.broadcasted_iota(jnp.int32, cand.shape, 1)),
        dimension=1, is_stable=True,
    )
    s, j = -s[:, :n], j[:, :n]
    # winner -> its sub-block among the candidates -> its block's row
    sub = jnp.take_along_axis(sub, j // _SUB, axis=1)
    return s, (
        jnp.take_along_axis(best, sub // per, axis=1) * _BLOCK
        + sub % per * _SUB + j % _SUB
    )


def _two_level(rows: int, n: int) -> bool:
    """Does ``_top_k`` over ``rows`` scores a query take its second
    level for the top ``n``? Static on the shapes, so one executable
    always does or never does."""
    return n >= _SUB_FROM and -(-rows // _BLOCK) > 2 * n


def _mask_scores(
    scores, allow0, excl, incl, has_incl, positive_only, cat=None
):
    """Shared mask application, all of it in the scores' own
    ``[B, rows]`` form (whole blocks: nothing is padded or sliced):
    ``allow0`` is the resident [rows] mask, False on the padding rows;
    ``excl``/``incl`` are per-query id lists already mapped into THIS
    score block's index space with out-of-range values pointing past the
    last row (``_membership`` drops them — sentinel-padded slots and,
    on a shard, ids owned by other shards); ``excl`` None leaves the
    exclusion lists to ``_masked_top_k``. ``has_incl`` flags queries
    with a whitelist: only their rows intersect with the scattered
    inclusion mask. ``cat`` = (resident per-item category codes
    [rows, C], the queries' category codes [B, Wc], which queries
    carry any): membership is a compare in this program, so a category
    filter ships its few codes and never a list as wide as the
    category. Its C x Wc compares are unrolled element-wise ``==`` and
    ``|`` (both counts static and small), which the scoring pass
    absorbs: an ``any`` over a ``[B, rows, Wc]`` compare is a fusion of
    its own that writes a ``[B, rows]`` predicate for the scoring pass
    to read back. Item slots without a category hold -1, query slots
    -2: padding never matches."""
    rows = scores.shape[1]
    allow = allow0[None, :]
    if excl is not None:
        allow = allow & ~_membership(excl, rows)
    allow = allow & (_membership(incl, rows) | ~has_incl[:, None])
    if cat is not None:
        codes, cats, has_cat = cat
        member = functools.reduce(operator.or_, (
            codes[None, :, c] == cats[:, j, None]
            for c in range(codes.shape[1]) for j in range(cats.shape[1])
        ))
        allow = allow & (member | ~has_cat[:, None])
    if positive_only:
        allow = allow & (scores > 0)
    return jnp.where(allow, scores, -jnp.inf)


def _excl_after_topk(w: int, n: int, rows: int) -> bool:
    """Are exclusion lists ``w`` wide applied to a top-``(n + w)`` of
    ``rows`` scores a query rather than as a ``[B, rows]`` membership
    grid? Where a list is no wider than the top-k it feeds (and is a
    list: a width of 1 is already one compare inside the scoring pass).
    Static on the shapes, so one executable always does or never
    does."""
    return 1 < w <= n and n + w <= rows


def _masked_top_k(
    scores, n: int, allow0, excl, incl, has_incl, positive_only, cat
):
    """``_top_k(_mask_scores(...), n)``, the exclusion lists applied
    after the top-k where ``_excl_after_topk`` says so: the top
    ``n + W`` of the scores under every other mask, the candidates
    whose index is in the query's list marked -inf, the first ``n``
    kept by a stable sort on that mark. Exact, ties included: at most
    W listed rows rank above any of the true top ``n``, so each of them
    is in the top ``n + W``, and the stable sort keeps the survivors in
    their (score desc, index asc) order. What a ``[B, W]`` list costs
    is then a ``[B, n + W, W]`` compare, where the grid was a product
    over every row and a layout copy of its ``pred`` form."""
    w = excl.shape[1]
    after = _excl_after_topk(w, n, scores.shape[1])
    s, i = _top_k(
        _mask_scores(
            scores, allow0, None if after else excl, incl, has_incl,
            positive_only, cat,
        ),
        n + w if after else n,
    )
    if not after:
        return s, i
    hit = jnp.any(i[:, :, None] == excl[:, None, :], axis=2)
    _, s, i = jax.lax.sort(
        (hit.astype(jnp.int32), jnp.where(hit, -jnp.inf, s), i),
        dimension=1, is_stable=True, num_keys=1,
    )
    return s[:, :n], i[:, :n]


def pow2_topk_width(
    max_num: int, n_items: int, site: str = "retrieval_topk"
) -> int:
    """The top-k width to request for a batch whose largest query wants
    ``max_num`` results: a power of two (min 16) so varying ``num``s
    share O(log) compiled executables, clamped to the catalog. EVERY
    top-k / shortlist width the serving tier requests routes through
    here (tests/test_lint.py enforces it) — a raw width is one
    executable per distinct ``num``. Records the ladder's padding waste
    (requested vs padded width) in ``pio_padding_waste_ratio{site}``."""
    w = min(max(16, pow2_at_least(max_num)), n_items)
    if w > 0:
        _m_padding_waste().labels(site=site).set(
            (w - min(max_num, w)) / w
        )
    return w


def trimmed_results(
    scores: np.ndarray, idx: np.ndarray, nums: Sequence[int]
) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Per-query ``(item idx, scores)`` pairs from a ``topn`` result,
    trimmed to each query's ``num`` and to its live candidates (masked
    slots carry ``-inf`` and sort to the tail, so the live rows are a
    prefix — this is the k > live-candidate-count edge)."""
    out = []
    for r, num in enumerate(nums):
        row_s, row_i = scores[r], idx[r]
        take = min(int(num), int((row_s > -np.inf).sum()))
        out.append((row_i[:take], row_s[:take]))
    return out


def category_arrays(
    items: Dict[int, object], n_items: int
) -> Tuple[Tuple[str, ...], np.ndarray]:
    """(category names, per-item category codes [n_items, C] int32, -1
    where an item has fewer than C) from the ``{dense index: item}``
    mapping a train produces (an item is anything with
    ``.categories``): what an engine's model persists and hands
    ``ItemRetriever`` as its resident ``category_codes``."""
    names = sorted({c for it in items.values() for c in it.categories})
    code = {c: j for j, c in enumerate(names)}
    width = max([len(set(it.categories)) for it in items.values()] + [1])
    codes = np.full((n_items, width), -1, np.int32)
    for idx, it in items.items():
        cs = sorted({code[c] for c in it.categories})
        codes[idx, : len(cs)] = cs
    return tuple(names), codes


def category_codes(code_of: Dict[str, int], categories) -> np.ndarray:
    """The sorted codes of a query's category names (``code_of`` maps a
    model's category names to their codes; a name no item carries has
    none: an empty array means NO candidates)."""
    return np.asarray(
        sorted({code_of[c] for c in categories if c in code_of}), np.int32
    )


def names_by_index(item_index) -> np.ndarray:
    """Item names by dense index, as an object array (not a second pair
    of dicts, and nothing the collector walks)."""
    names = np.empty(len(item_index), object)
    for name, idx in item_index.items():
        names[idx] = name
    return names


def include_candidates(item_index, white_list) -> Optional[np.ndarray]:
    """A query's ``whiteList`` mapped through the item index: its
    inclusion list. ``None`` = unrestricted; an EMPTY array = NO
    candidates, matching the host paths' all-False whitelist mask.
    (Categories never travel as lists: they are resident codes,
    ``topn(categories=...)``.)"""
    if white_list is None:
        return None
    return np.asarray(
        [item_index[i] for i in white_list if i in item_index], np.int64
    )


def _operand_slices(k: int, widths) -> List[slice]:
    """Where each part of a batch's packed operand lies among its
    columns: the query rows' ``k`` float32 bit patterns, the exclusion
    block, the inclusion block, the category codes (``widths`` = their
    three widths) and the flags has_incl, has_cat, row_norm. The wide
    parts come first, so that at the serving widths (k 512, lists of
    1,024 and 8,192) each starts on a multiple of 128 lanes."""
    edges = np.cumsum((0, k) + tuple(widths) + (3,)).tolist()
    return [slice(a, b) for a, b in zip(edges, edges[1:])]


def _assemble_idx(lists, block, has=None) -> None:
    """Per-query id lists into ``block``, a [b_pad, W] view of the
    packed operand already filled with its padding value (W wide enough:
    ``ItemRetriever._width``). ``has`` is the flag column of the
    queries that carry a list at all (``None`` entries carry none; an
    empty list is a list)."""
    for r, a in enumerate(lists):
        if a is None:
            continue
        if has is not None:
            has[r] = 1
        if len(a):
            block[r, : len(a)] = a


def _pack_operand(
    q, b_pad: int, widths, sentinel: int,
    exclude=(), include=(), categories=(), row_norm=(),
) -> np.ndarray:
    """Everything a batch sends to the device as ONE [b_pad, W] int32
    buffer (``_operand_slices``), written in place into one allocation:
    the float32 query rows as their bits, the id lists padded with
    ``sentinel`` (n_pad: out of range on every shard and on the single
    device, so the masks drop it), the category codes padded with -2
    (an item without a category holds -1: padding never matches), the
    three flags as 0/1. Floats travel as integer bits, never integers
    as float bits (``_pack_topn`` says what the chip does to those).
    One buffer is one ``device_put`` a batch where there were seven."""
    b, k = q.shape
    rows, excl, incl, cats, flags = _operand_slices(k, widths)
    buf = np.empty((b_pad, flags.stop), np.int32)
    bits = buf[:, rows].view(np.float32)
    bits[:b] = q
    bits[b:] = 0.0
    buf[:, excl.start:incl.stop] = sentinel
    buf[:, cats] = -2
    buf[:, flags] = 0
    _assemble_idx(exclude, buf[:, excl])
    _assemble_idx(include, buf[:, incl], buf[:, flags.start])
    _assemble_idx(categories, buf[:, cats], buf[:, flags.start + 1])
    buf[: len(row_norm), flags.start + 2] = row_norm
    return buf


def _unpack_operand(packed, k: int, widths):
    """The traced inverse of ``_pack_operand``: static slices, a bitcast
    for the rows, ``!= 0`` for the flags. Returns q, excl, incl,
    has_incl, cats, has_cat, row_norm."""
    rows, excl, incl, cats, flags = _operand_slices(k, widths)
    on = packed[:, flags] != 0
    return (
        jax.lax.bitcast_convert_type(packed[:, rows], jnp.float32),
        packed[:, excl], packed[:, incl], on[:, 0],
        packed[:, cats], on[:, 1], on[:, 2],
    )


@functools.partial(
    jax.jit, static_argnames=("n", "positive_only", "normalize", "widths")
)
def _fused_topn_single(
    packed, Y, rn, allow0, codes, n, positive_only, normalize, widths
):
    """The single-device path as ONE program over ONE operand a batch
    (``_pack_operand``): matmul + optional cosine scaling + masks +
    top_k, no [B, N] score materialization on host and no host
    post-filter (the pre-round-12 ecommerce predict computed the full
    score row in numpy and masked it in Python)."""
    q, excl, incl, has_incl, cats, has_cat, row_norm = _unpack_operand(
        packed, Y.shape[1], widths
    )
    scores = _scale_cosine(_exact_scores(q, Y), rn, row_norm, normalize)
    s, i = _masked_top_k(
        scores, n, allow0, excl, incl, has_incl, positive_only,
        (codes, cats, has_cat),
    )
    return _pack_topn(s, i)


def _scale_cosine(scores, rn, row_norm, normalize):
    """Cosine scaling of a score block by the resident reciprocal
    norms: every row (``normalize`` True), none (False), or the rows
    flagged in ``row_norm`` (``"rows"``: known users' raw dots and
    recent-view cosine queries ride one program run)."""
    if normalize == "rows":
        return scores * jnp.where(row_norm[:, None], rn[None, :], 1.0)
    return scores * rn[None, :] if normalize else scores


def _exact_scores(q, Y):
    """The float32 tier's score block. ``precision="highest"`` because a
    TPU's default matmul precision rounds float32 operands to bfloat16:
    the exact tier must order items as a float32 host reference does."""
    return jnp.dot(
        q, Y.T, preferred_element_type=jnp.float32, precision="highest"
    )


def _approx_scores(q, Yq, scale, precision):
    """Stage-1 score block in the RESIDENT precision. ``int8`` runs the
    contraction in the quantized domain — the query block quantizes
    per-row the same way the resident rows did, the matmul accumulates
    int8 x int8 -> int32 (the MXU-native form), and the dequant-rescale
    epilogue ``* q_scale * row_scale`` is fused onto the accumulator in
    the same program. ``bf16`` contracts in bf16 with an f32
    accumulator; ``scale`` is unread there (and DCE'd)."""
    if precision == "int8":
        qs = jnp.max(jnp.abs(q), axis=1) / 127.0
        qs = jnp.where(qs > 0, qs, 1.0)
        qi = jnp.clip(
            jnp.round(q / qs[:, None]), -127, 127
        ).astype(jnp.int8)
        acc = jnp.dot(qi, Yq.T, preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) * qs[:, None] * scale[None, :]
    return jnp.dot(
        q.astype(jnp.bfloat16), Yq.T, preferred_element_type=jnp.float32
    )


def _rescore_exact(
    q, Yq, scale, s1, i1, rn, positive_only, normalize, precision,
    row_norm=None,
):
    """Stage 2: gather ONLY the shortlisted rows, dequantize to f32,
    and rescore against the full-precision query — a returned score is
    exact over the dequantized matrix, so quantization can only cost
    stage-1 shortlist misses, never wrong scores. ``positive_only``
    re-applies on the EXACT score (a borderline approx-positive item
    must not leak through), and stage-1 ``-inf`` (masked/dead) slots
    stay ``-inf``."""
    rows = jnp.take(Yq, i1, axis=0).astype(jnp.float32)
    if precision == "int8":
        rows = rows * jnp.take(scale, i1)[:, :, None]
    rescored = jnp.einsum("bk,bck->bc", q, rows, precision="highest")
    if normalize == "rows":
        rescored = rescored * jnp.where(
            row_norm[:, None], jnp.take(rn, i1), 1.0
        )
    elif normalize:
        rescored = rescored * jnp.take(rn, i1)
    if positive_only:
        rescored = jnp.where(rescored > 0, rescored, -jnp.inf)
    return jnp.where(s1 == -jnp.inf, -jnp.inf, rescored)


@functools.partial(
    jax.jit,
    static_argnames=(
        "n", "shortlist", "positive_only", "normalize", "precision",
        "widths",
    ),
)
def _fused_topn_single_2s(
    packed, Yq, scale, rn, allow0, codes,
    n, shortlist, positive_only, normalize, precision, widths,
):
    """Quantized single-device path: BOTH stages in one program —
    approx score with the fused dequant-rescale epilogue + the same
    packed operand and masks as the exact path + top-(c·n) shortlist,
    then the exact-f32 rescore of just the shortlist rows and the final
    top_k."""
    q, excl, incl, has_incl, cats, has_cat, row_norm = _unpack_operand(
        packed, Yq.shape[1], widths
    )
    approx = _scale_cosine(
        _approx_scores(q, Yq, scale, precision), rn, row_norm, normalize
    )
    s1, i1 = _masked_top_k(
        approx, shortlist, allow0, excl, incl, has_incl, positive_only,
        (codes, cats, has_cat),
    )
    rescored = _rescore_exact(
        q, Yq, scale, s1, i1, rn, positive_only, normalize, precision,
        row_norm,
    )
    s, j = jax.lax.top_k(rescored, n)
    return _pack_topn(s, jnp.take_along_axis(i1, j, axis=1))


def _shard_topk_kernel_2s(
    packed, Yq, scale, rn, allow0, codes,
    *, axis, n_local, shortlist, positive_only, normalize, precision,
    widths,
):
    """Per-shard two-stage body (runs under shard_map): the quantized
    counterpart of ``_shard_topk_kernel`` — candidacy masks and the
    id-list localize/scatter are IDENTICAL; only the score producer
    (quantized stage 1 + exact rescore of the top-(c·n_local)
    shortlist) differs. Emits packed top-n_local EXACT candidates with
    global ids, so the cross-shard merge is unchanged."""
    q, excl, incl, has_incl, cats, has_cat, row_norm = _unpack_operand(
        packed, Yq.shape[1], widths
    )
    rows_l = Yq.shape[0]
    off = jax.lax.axis_index(axis).astype(jnp.int32) * rows_l

    def localize(g):
        return jnp.where((g >= off) & (g < off + rows_l), g - off, rows_l)

    approx = _scale_cosine(
        _approx_scores(q, Yq, scale, precision), rn, row_norm, normalize
    )
    s1, i1 = _masked_top_k(
        approx, shortlist, allow0, localize(excl), localize(incl),
        has_incl, positive_only, (codes, cats, has_cat),
    )
    rescored = _rescore_exact(
        q, Yq, scale, s1, i1, rn, positive_only, normalize, precision,
        row_norm,
    )
    s, j = jax.lax.top_k(rescored, n_local)
    return _pack_topn(s, jnp.take_along_axis(i1, j, axis=1) + off)


def _shard_topk_kernel(
    packed, Y, rn, allow0, codes,
    *, axis, n_local, positive_only, normalize, widths,
):
    """Per-shard body (runs under shard_map): local slice views of the
    resident arrays, the batch's packed operand replicated, NO
    collective — each shard emits its own packed top-n_local candidates
    with GLOBAL ids."""
    q, excl, incl, has_incl, cats, has_cat, row_norm = _unpack_operand(
        packed, Y.shape[1], widths
    )
    rows_l = Y.shape[0]
    off = jax.lax.axis_index(axis).astype(jnp.int32) * rows_l

    def localize(g):
        # ids owned by other shards map to rows_l (out of range, dropped
        # by the scatter) rather than subtracting into negative values,
        # which .at[] would WRAP NumPy-style back into this shard
        return jnp.where((g >= off) & (g < off + rows_l), g - off, rows_l)

    scores = _scale_cosine(_exact_scores(q, Y), rn, row_norm, normalize)
    s, i = _masked_top_k(
        scores, n_local, allow0, localize(excl), localize(incl), has_incl,
        positive_only, (codes, cats, has_cat),
    )
    return _pack_topn(s, i + off)


@functools.partial(jax.jit, static_argnames=("n", "n_local", "rep_s"))
def _merge_candidates(packed, n, n_local, rep_s):
    """Cross-shard merge: the ONLY sharded→replicated hop, and it moves
    just the B x S x n_local candidate rows (scores + id bits), never
    the score matrix. One final top_k over the concatenation is exact
    (each shard already surfaced every global-top-n element it owns).
    ``rep_s`` pins the output replicated the same way
    ``_topn_packed_sharded`` pins its output row-sharded: as a hashable
    static, so XLA's propagation cannot choose a different layout on
    some backend/core-count combination."""
    x = jax.lax.with_sharding_constraint(packed, rep_s)
    B = x.shape[0]
    S = x.shape[1] // (2 * n_local)
    x = x.reshape(B, S, 2, n_local)
    s_cand = jax.lax.bitcast_convert_type(
        x[:, :, 0, :], jnp.float32
    ).reshape(B, S * n_local)
    i_cand = x[:, :, 1, :].reshape(B, S * n_local)
    s, j = jax.lax.top_k(s_cand, n)
    return _pack_topn(s, jnp.take_along_axis(i_cand, j, axis=1))


# --- metric families (get-or-create per call: dict lookups at batch
# granularity, following the utils/metrics conventions) ---


def _m_merge_rows():
    return _metrics.get_registry().counter(
        "pio_retrieval_merge_rows_total",
        "Candidate rows a row-sharded retriever's merge took across the "
        "sharded->replicated hop (padded batch rows x shards x each "
        "shard's top-n, every batch)",
        labels=("component",),
    )


def _m_mask_refresh():
    return _metrics.get_registry().counter(
        "pio_retrieval_mask_refresh_total",
        "Resident candidacy-mask refreshes by outcome "
        "(refreshed=rebuilt+uploaded, unchanged=skipped)",
        labels=("component", "outcome"),
    )


def _m_operand_transfers():
    return _metrics.get_registry().counter(
        "pio_retrieval_operand_transfers_total",
        "Host-to-device transfers ItemRetriever.topn made for its "
        "batches' operands (query rows, id lists, category codes and "
        "flags travel as one packed buffer: one a call)",
        labels=("component",),
    )


def _m_topk_two_level():
    return _metrics.get_registry().counter(
        "pio_retrieval_topk_two_level_total",
        "Runs of a retrieval program whose block-wise top-k took its "
        "second level (the best sub-blocks of 128 inside the best blocks "
        "of 1,024, for a top-k of 64 or more: the quantized shortlists)",
        labels=("component",),
    )


def _m_excl_after_topk():
    return _metrics.get_registry().counter(
        "pio_retrieval_exclusion_after_topk_total",
        "Runs of a retrieval program that applied its exclusion lists to "
        "an over-fetched top-k (lists no wider than the top-k they feed) "
        "instead of a [B, rows] membership grid",
        labels=("component",),
    )


def _m_shortlist_rows():
    return _metrics.get_registry().counter(
        "pio_retrieval_shortlist_rows_total",
        "Candidate rows of the device's shortlists that the quantized "
        "tier's host refine gathered from the original float32 table "
        "and rescored",
        labels=("component",),
    )


def _m_refine_changed():
    return _metrics.get_registry().counter(
        "pio_retrieval_refine_changed_total",
        "Answers whose ids or order the host refine changed from the "
        "device's own best n (over the dequantized rows): whether the "
        "float32 originals matter at all",
        labels=("component",),
    )


def _m_mask_age():
    return _metrics.get_registry().gauge(
        "pio_retrieval_mask_age_seconds",
        "Seconds since the resident candidacy mask was last refreshed",
        labels=("component",),
    )


def _m_resident_bytes():
    return _metrics.get_registry().gauge(
        "pio_retrieval_resident_bytes",
        "Bytes of retrieval state resident on device (factors + norms "
        "+ mask)",
        labels=("component",),
    )


def _m_bytes_per_item():
    # the name is bytes PER ITEM — a per-row ratio, deliberately not
    # suffixed `_bytes` (that reads as a footprint total, which is
    # pio_retrieval_resident_bytes); tests/test_lint.py's
    # METRIC_NAME_ALLOWED carries the reviewed deviation
    return _metrics.get_registry().gauge(
        "pio_retrieval_bytes_per_item",
        "Device bytes of resident retrieval factor state per catalog "
        "item (rows + per-row scale + folded norms) by serving "
        "precision — the capacity-planning number behind the "
        "float32/bf16/int8 residency ladder",
        labels=("component", "precision"),
    )


def _m_padding_waste():
    return _metrics.get_registry().gauge(
        "pio_padding_waste_ratio",
        "Fraction of a padded dimension that is padding (0 = no waste): "
        "serving batch rows, top-k ladder width, ALS geometry-bucket "
        "slots — the compile-sharing cost the capacity planning reads",
        labels=("site",),
    )


def _m_shard_skew():
    return _metrics.get_registry().gauge(
        "pio_retrieval_shard_skew",
        "Cross-shard retrieval imbalance on sampled batches: "
        "max-shard / mean-shard of live stage-1 candidates "
        "(kind=candidates) and of final top-n contributions "
        "(kind=results); 1.0 = perfectly even",
        labels=("kind",),
    )


def _m_shard_candidates():
    return _metrics.get_registry().gauge(
        "pio_retrieval_shard_candidates",
        "Live stage-1 candidates contributed per shard on the most "
        "recent sampled batch",
        labels=("shard",),
    )


class ItemRetriever:
    """Device-resident top-N retrieval over one item-factor matrix.

    Upload-once semantics: construct at ``prepare_serving`` (the engine
    server's prepared-serving state owns the instance), after which each
    query batch ships ONE packed buffer up (``_pack_operand``: its
    [B, k] query rows, the per-query id lists, category codes and
    flags) and one packed [B, 2n] buffer down.

    With a ``mesh`` the factor rows (and the norm/mask vectors) shard
    over ``axis`` and stay resident between queries; without one (or on
    a 1-device mesh) everything lives on ``device`` (default backend
    device) and retrieval is the fused single-program path. The
    resident rows are zero-padded ONCE, here, to whole blocks
    (``_ROW_BLOCK``; on a mesh to whole blocks a shard), so that no
    program pads, slices or re-tiles a ``[B, rows]`` array on a run.
    Padding rows exist on the device alone (the caller's table is kept
    as it is, never copied), are permanently masked out, and no index
    of one is ever returned
    (``pio_padding_waste_ratio{site="retrieval_rows"}`` says how many).

    ``precision`` selects the residency tier: ``"float32"`` (exact,
    single-stage — the historical path, byte-for-byte), ``"bf16"``, or
    ``"int8"`` (rows + one f32 scale per row). Quantized tiers serve
    through the fused two-stage kernels — stage 1 shortlists the
    top-(``shortlist_mult``·n) candidates from the quantized scores,
    stage 2 rescores the shortlist in exact f32 over the dequantized
    rows before the merge — plus a final host refinement of the merged
    c·n candidates against the ORIGINAL f32 rows (host RAM, zero HBM):
    returned scores are exact over the original matrix, ids match the
    exact path except for whole-shortlist misses, and recall is gated
    (≥ 0.999 in tests/bench).
    """

    def __init__(
        self,
        item_factors: np.ndarray,
        mesh: Optional[Mesh] = None,
        axis: str = "data",
        component: str = "retrieval",
        device=None,
        precision: str = "float32",
        shortlist_mult: int = 4,
        category_codes: Optional[np.ndarray] = None,
        category_width: int = 1,
        exclude_ladder: Optional[Sequence[int]] = None,
        include_ladder: Optional[Sequence[int]] = None,
        max_batch: Optional[int] = None,
    ):
        """``category_codes`` ([n_items, C] int32, -1 where an item has
        fewer than C categories) makes the items' categories resident
        beside the mask; a query then ships up to ``category_width``
        category codes (``topn(categories=...)``). The two ladders and
        ``max_batch`` close the executable space: with them set, id
        lists pad to the smallest listed width that holds the batch's
        longest list (a width of 1 stands for "no list"), the batch to
        8 doubling up to ``max_batch``, and ``warm()`` compiles every
        combination; a list or batch over the top is refused
        (``fits()`` says so beforehand) instead of compiled on a live
        batch. Without them widths are powers of two, as traffic brings
        them, and ``warm()`` covers the widths it is told."""
        if precision not in PRECISIONS:
            raise ValueError(
                f"precision must be one of {PRECISIONS}, got {precision!r}"
            )
        if shortlist_mult < 1:
            raise ValueError(
                f"shortlist_mult must be >= 1, got {shortlist_mult}"
            )
        if mesh is not None and mesh.shape[axis] == 1:
            # collapse to the fused single-device path, but KEEP the
            # mesh's device: a `pio deploy --workers` worker pinned to
            # one device arrives here as a 1-device mesh, and dropping
            # it would land every worker's resident factors on the
            # process-default device 0
            if device is None:
                device = mesh.devices.flat[0]
            mesh = None
        self.mesh = mesh
        self._axis = axis
        self.component = component
        self.precision = precision
        self.shortlist_mult = int(shortlist_mult)
        factors = np.asarray(item_factors, np.float32)
        self.n_items, self.rank = factors.shape
        n_shards = mesh.shape[axis] if mesh is not None else 1
        self._n_shards = n_shards
        # whole blocks, and on a mesh whole blocks a shard: the programs
        # then never pad, slice or re-tile a [B, rows] array (_ROW_BLOCK)
        n_pad = pad_to_multiple(max(self.n_items, 1), n_shards * _ROW_BLOCK)
        self._n_pad = n_pad
        _m_padding_waste().labels(site="retrieval_rows").set(
            (n_pad - self.n_items) / n_pad
        )
        # the caller's array, never a second copy of it: the float32
        # table that is uploaded (or that the refine reads) IS the
        # caller's, which may be a file mapped into memory (8.5 GB at
        # 4.16 M x 512 would otherwise sit twice in host RAM for the
        # life of the server, 19 GB at 9.4 M). The padding rows exist on
        # the device alone, and no index of one leaves the retriever
        # (``_unpack``)
        self._factors = factors
        scale_host: Optional[np.ndarray] = None
        if precision == "float32":
            y_host = factors
            rn = np.zeros(n_pad, np.float32)
            rn[: self.n_items] = _reciprocal_norms(factors)
            rn_exact = rn
            # float32 keeps no second table: dequantized_factors() hands
            # back the caller's array, and nothing refines
            self._y_f32_host: Optional[np.ndarray] = None
        else:
            # residency tier: the resident row storage, made in row
            # blocks. Norms fold from the DEQUANTIZED rows, so the
            # cosine path is self-consistent with stage 2's exact
            # rescore; the ORIGINAL rows' norms stay on the host (4
            # bytes an item) for the refine and an engine's host path
            y_host, scale_host, rn, rn_exact = _quantize_resident(
                factors, n_pad, precision
            )
            # the final exact-rescore stage reads the ORIGINAL f32 rows
            # on the host, where every engine keeps them (in RAM, or as
            # the mapped file of a PersistentModel): only the quantized
            # rows occupy HBM
            self._y_f32_host = factors
        self._rn_f32_host: Optional[np.ndarray] = rn_exact
        self._valid = np.zeros(n_pad, bool)
        self._valid[: self.n_items] = True
        self._excluded_ids: Optional[np.ndarray] = None
        self.has_categories = category_codes is not None
        codes = np.full((n_pad, 1), -1, np.int32)
        if category_codes is not None:
            cc = np.asarray(category_codes, np.int32).reshape(
                self.n_items, -1
            )
            codes = np.full((n_pad, max(1, cc.shape[1])), -1, np.int32)
            codes[: self.n_items, : cc.shape[1]] = cc
        self.category_width = max(1, int(category_width))
        self._exclude_ladder = _ladder(exclude_ladder)
        self._include_ladder = _ladder(include_ladder)
        self._batch_ladder = (
            None if max_batch is None else _batch_sizes(max_batch)
        )
        if mesh is None:
            self._device = device
            put = lambda a: (
                jax.device_put(a, device) if device is not None
                else jax.device_put(a)
            )
            self._y_dev = (
                _upload_padded(y_host, n_pad, device)
                if precision == "float32" else put(y_host)
            )
            self._scale_dev = (
                put(scale_host) if scale_host is not None else None
            )
            self._rn_dev = put(rn)
            self._allow_dev = put(self._valid)
            self._codes_dev = put(codes)
            # where a batch's packed operand goes (None: the default
            # device)
            self._operand_at = device
        else:
            self._device = None
            rows_at = NamedSharding(mesh, P(axis, None))
            self._y_dev = (
                _upload_row_sharded(y_host, n_pad, rows_at)
                if precision == "float32" else jax.device_put(y_host, rows_at)
            )
            self._scale_dev = (
                jax.device_put(scale_host, NamedSharding(mesh, P(axis)))
                if scale_host is not None else None
            )
            self._rn_dev = jax.device_put(rn, NamedSharding(mesh, P(axis)))
            self._allow_dev = jax.device_put(
                self._valid, NamedSharding(mesh, P(axis))
            )
            self._codes_dev = jax.device_put(
                codes, NamedSharding(mesh, P(axis, None))
            )
            self._rep_out = NamedSharding(mesh, P(None, None))
            self._operand_at = self._rep_out
            # per-(n_local, flags, widths, shortlist) jitted shard_map
            # stage-1 executables
            self._stage1_cache: Dict[tuple, object] = {}
        # the quantized staging copy goes once it is on the device (an
        # int8 table is 4.8 GB at 9.4 M x 512): dequantized_factors()
        # reads the device. (What the TPU runtime itself keeps on the
        # host after an upload, 14.2 GB for these 4.8, does not depend
        # on the transfer's size: uploading in blocks was tried and
        # changed nothing, PERF.md PR 33.)
        jax.block_until_ready(self._y_dev)
        del y_host
        self._batches = 0
        self._freed = False
        # per-(n_local, flags, shapes) executables this instance already
        # compiled (executable-cache accounting for the stage-1 ladder;
        # the jit cache behind it is per-instance via self._stage1_cache)
        self._exec_seen: set = set()
        self._mask_stamp = time.monotonic()
        _m_mask_age().labels(component=component).set(0.0)
        # the gauge reads the ACTUAL device arrays, not the f32 host
        # staging copy — on a quantized deployment those differ by the
        # whole point of this mode
        _m_resident_bytes().labels(component=component).set(
            self.resident_bytes
        )
        # HBM residency ledger: factors+norms (+ per-row scales) under
        # the component name — suffixed /<precision> for quantized
        # deployments so pio_device_ledger_bytes attributes capacity
        # per precision tier — and the constraint-fed candidacy mask
        # under <component>-mask (its lifecycle differs — re-uploaded
        # on constraint change). The per-device footprint maps
        # attribute each shard's bytes to its own device for drift
        # reconciliation; the anchor finalizers are the refcount
        # backstop and free() closes explicitly on the drain/release
        # path.
        factor_arrays = [self._y_dev, self._rn_dev]
        if self._scale_dev is not None:
            factor_arrays.append(self._scale_dev)
        f_label, f_bytes, f_members = _ledger.device_footprint(
            *factor_arrays
        )
        self._ledger_component = (
            component if precision == "float32"
            else f"{component}/{precision}"
        )
        self._ledger_factors = _ledger.get_ledger().register(
            component=self._ledger_component,
            nbytes=f_bytes,
            device=f_label,
            anchor=self,
            members=f_members,
        )
        _m_bytes_per_item().labels(
            component=component, precision=precision
        ).set(f_bytes / max(1, self.n_items))
        m_label, m_bytes, m_members = _ledger.device_footprint(
            self._allow_dev, self._codes_dev
        )
        self._ledger_mask = _ledger.get_ledger().register(
            component=f"{component}-mask",
            nbytes=m_bytes,
            device=m_label,
            anchor=self,
            members=m_members,
        )
        logger.info(
            "ItemRetriever[%s]: %d items (rank %d, %s) resident %s",
            component, self.n_items, self.rank, precision,
            f"row-sharded over {n_shards} devices" if mesh is not None
            else "on one device",
        )

    # --- resident global mask (the out-of-band-refreshed constraint set) ---

    def set_excluded_ids(self, idx) -> bool:
        """Replace the resident exclusion set (dense item indices, e.g.
        the ecommerce ``unavailableItems`` constraint mapped through the
        item index). Rebuilds and re-uploads the sharded mask only when
        the set actually changed; returns whether it did. Called from
        the constraint cache's background refresh thread — the swap is a
        single reference assignment, so in-flight batches keep the mask
        they started with."""
        idx = np.unique(np.asarray(idx, np.int64)) if len(idx) else np.zeros(
            0, np.int64
        )
        idx = idx[(idx >= 0) & (idx < self.n_items)]
        if self._excluded_ids is not None and np.array_equal(
            idx, self._excluded_ids
        ):
            _m_mask_refresh().labels(
                component=self.component, outcome="unchanged"
            ).inc()
            self._touch_mask()
            return False
        allow = self._valid.copy()
        allow[idx] = False
        if self.mesh is None:
            dev = self._device
            self._allow_dev = (
                jax.device_put(allow, dev) if dev is not None
                else jax.device_put(allow)
            )
        else:
            self._allow_dev = jax.device_put(
                allow, NamedSharding(self.mesh, P(self._axis))
            )
        self._excluded_ids = idx
        # re-`set` from the FRESH device footprint (never the size
        # captured at prepare): on a quantized deployment the prepare-
        # time f32 staging sizes are 2-4x the resident truth, and a
        # stale number here is exactly the reconcile() drift the ledger
        # exists to catch. The resident-bytes gauge re-reads the actual
        # arrays for the same reason.
        _, m_bytes, m_members = _ledger.device_footprint(
            self._allow_dev, self._codes_dev
        )
        self._ledger_mask.set(m_bytes, members=m_members)
        _m_resident_bytes().labels(component=self.component).set(
            self.resident_bytes
        )
        _m_mask_refresh().labels(
            component=self.component, outcome="refreshed"
        ).inc()
        self._touch_mask()
        return True

    def _touch_mask(self) -> None:
        self._mask_stamp = time.monotonic()
        _m_mask_age().labels(component=self.component).set(0.0)

    @property
    def mask_age_s(self) -> float:
        return time.monotonic() - self._mask_stamp

    @property
    def resident_bytes(self) -> int:
        arrays = [
            self._y_dev, self._rn_dev, self._allow_dev, self._codes_dev
        ]
        if self._scale_dev is not None:
            arrays.append(self._scale_dev)
        return int(sum(a.nbytes for a in arrays))

    @property
    def reciprocal_norms(self) -> np.ndarray:
        """1/||y|| of the original float32 rows, [n_items], on the host."""
        return self._rn_f32_host[: self.n_items]

    @property
    def max_batch(self) -> Optional[int]:
        """The closed ladder's widest batch (None: no ladder)."""
        return self._batch_ladder[-1] if self._batch_ladder else None

    def dequantized_factors(self) -> np.ndarray:
        """Host f32 matrix the device path actually scores against —
        the original factors for float32, the dequantized resident rows
        otherwise, READ BACK FROM THE DEVICE (no quantized copy stays on
        the host). This is the reference the exact-rescore parity
        oracle (tests/bench) feeds to ``naive_topn_reference``."""
        if self.precision == "float32":
            return self._factors
        rows = np.asarray(self._y_dev)[: self.n_items]
        if self.precision == "int8":
            return dequantize_rows_int8(
                rows, np.asarray(self._scale_dev)[: self.n_items]
            )
        return rows.astype(np.float32)

    # --- the hot path ---

    def fits(self, *, exclude=0, include=0, categories=0) -> bool:
        """Whether a query whose exclusion list, inclusion list and
        category list are that long is served by an executable of the
        closed ladder (always, where no ladder is set). The engine asks
        before it calls ``topn``: what does not fit is answered on the
        host, never compiled on a live batch."""
        for ladder, n in (
            (self._exclude_ladder, exclude), (self._include_ladder, include),
        ):
            if ladder is not None and n > ladder[-1]:
                return False
        return categories <= self.category_width

    @staticmethod
    def _width(ladder, width: int, what: str) -> int:
        if ladder is None:
            return pow2_at_least(width)
        for w in ladder:
            if w >= width:
                return w
        raise ValueError(
            f"{what} of {width} is over the ladder's top {ladder[-1]}"
        )

    def _count_top_k(self, rows: int, n: int, w: int) -> None:
        """A run of a program whose top-``n`` over ``rows`` scores a
        query, with exclusion lists ``w`` wide, applies the lists after
        the top-k (``_excl_after_topk``) or takes the top-k's second
        level, counted (both static per executable)."""
        if _excl_after_topk(w, n, rows):
            _m_excl_after_topk().labels(component=self.component).inc()
            n += w
        if _two_level(rows, n):
            _m_topk_two_level().labels(component=self.component).inc()

    def _upload(self, operand: np.ndarray):
        """A batch's packed operand onto the device(s): the one
        host-to-device transfer of a ``topn`` call, counted."""
        _m_operand_transfers().labels(component=self.component).inc()
        with _tracing.stage(_tracing.UPLOAD):
            return jax.device_put(operand, self._operand_at)

    def topn(
        self,
        query_rows: np.ndarray,
        n: int,
        *,
        exclude: Optional[Sequence] = None,
        include: Optional[Sequence] = None,
        categories: Optional[Sequence] = None,
        positive_only: bool = False,
        normalize=False,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Exact masked top-``n`` for a query batch.

        ``categories`` are per-query arrays of category codes (``None``
        = no category filter; an empty array = NO candidates), tested
        against the resident per-item codes inside the program.
        ``normalize`` may be a per-row boolean sequence: the flagged
        rows score as cosine, the others as raw dots, in one run.

        ``exclude``/``include`` are per-query dense item-index arrays
        (``None`` entries mean no list for that query; an ``include``
        entry restricts the query's candidates to exactly that set —
        an empty array means NO candidates, matching whitelist
        semantics). ``positive_only`` drops non-positive scores (the
        templates' ``scores > 0`` rule); ``normalize`` scores against
        L2-normalized candidates (the cosine/similar-items path).
        Returns (scores [B, n], item idx [B, n]); slots past a query's
        live-candidate count carry ``-inf`` — the k > live-candidates
        edge is the caller filtering those out.
        """
        if self._freed:
            raise RuntimeError(
                "ItemRetriever was freed (release_serving); the owner "
                "must null its reference before freeing"
            )
        q = np.atleast_2d(np.asarray(query_rows, np.float32))
        b = q.shape[0]
        if not (0 < n <= self.n_items):
            raise ValueError(
                f"n must be in [1, {self.n_items}], got {n}"
            )
        # quantized precisions: the DEVICE pipeline returns the full
        # c·n-wide merged candidate list (not just n) and a final host
        # refinement rescores it against the ORIGINAL f32 rows — the
        # dequantized matrix reorders items at the top-n boundary, so
        # taking n on-device would cap recall below the 0.999 gate no
        # matter how wide the shard shortlist is
        n_dev = (
            n if self.precision == "float32"
            else self._shortlist_width(n, self.n_items)
        )
        with _tracing.stage(_tracing.MASK_PREP):
            b_pad = max(8, self._width(self._batch_ladder, b, "a batch"))
            exclude, include, categories = (
                list(lists or ()) for lists in (exclude, include, categories)
            )
            n_cats = _longest(categories)
            if n_cats > self.category_width:
                raise ValueError(
                    f"{n_cats} categories in one query, the retriever "
                    f"holds {self.category_width}"
                )
            # the widths an executable is compiled for: the smallest of
            # the ladder (or the next power of two) that holds the
            # batch's longest list; 1 stands for "no list"
            widths = (
                self._width(
                    self._exclude_ladder, max(1, _longest(exclude)),
                    "an exclusion list",
                ),
                self._width(
                    self._include_ladder, max(1, _longest(include)),
                    "an inclusion list",
                ),
                self.category_width,
            )
            row_norm = np.zeros(b, bool)
            if not isinstance(normalize, (bool, np.bool_)):
                row_norm[:] = np.asarray(normalize, bool)
                normalize = "rows"
            else:
                normalize = bool(normalize)
            operand = _pack_operand(
                q, b_pad, widths, self._n_pad,
                exclude, include, categories, row_norm,
            )
        self.last_padded = (b_pad, widths[0], widths[1])
        _m_mask_age().labels(component=self.component).set(self.mask_age_s)
        _m_padding_waste().labels(site="retrieval_batch").set(
            (b_pad - b) / b_pad
        )
        if self.mesh is None:
            # executable-cache accounting: the fused program's jit cache
            # is keyed by shapes + statics; a NEW key here is a compile
            # (cold if it happens under a serving compile_site)
            if self.precision == "float32":
                exec_key = (
                    self._n_pad, self.rank, b_pad, *widths,
                    self._codes_dev.shape[1],
                    n, positive_only, normalize,
                )
                self._count_top_k(self._n_pad, n, widths[0])
                with _tracing.stage(_tracing.DISPATCH), _cc.track_compile(
                    "retrieval-fused", _FUSED_SEEN, exec_key
                ):
                    packed = _fused_topn_single(
                        self._upload(operand), self._y_dev, self._rn_dev,
                        self._allow_dev, self._codes_dev,
                        n, positive_only, normalize, widths,
                    )
            else:
                shortlist = self._shortlist_width(n_dev, self._n_pad)
                exec_key = (
                    self._n_pad, self.rank, b_pad, *widths,
                    self._codes_dev.shape[1],
                    n_dev, shortlist, positive_only, normalize,
                    self.precision,
                )
                self._count_top_k(self._n_pad, shortlist, widths[0])
                with _tracing.stage(_tracing.DISPATCH), _cc.track_compile(
                    "retrieval-fused", _FUSED_SEEN, exec_key
                ):
                    packed = _fused_topn_single_2s(
                        self._upload(operand), self._y_dev,
                        self._scale_operand, self._rn_dev, self._allow_dev,
                        self._codes_dev,
                        n_dev, shortlist, positive_only, normalize,
                        self.precision, widths,
                    )
            with _tracing.stage(_tracing.DEVICE_WAIT):
                host = np.asarray(packed)[:b]
            if self.precision != "float32":
                return self._refine_exact(
                    q, host, n_dev, n, positive_only,
                    row_norm if normalize == "rows" else normalize,
                )
            with _tracing.stage(_tracing.BUILD):
                return self._unpack(host, n)

        n_local = min(n_dev, self._n_pad // self._n_shards)
        shortlist = (
            None if self.precision == "float32"
            else self._shortlist_width(
                n_local, self._n_pad // self._n_shards
            )
        )
        stage1 = self._stage1(
            n_local, positive_only, normalize, widths, shortlist
        )
        self._batches += 1
        exec_key = (
            n_local, positive_only, normalize, b_pad, *widths, shortlist,
            self.precision,
        )
        resident = (
            (self._y_dev, self._rn_dev) if shortlist is None
            else (self._y_dev, self._scale_operand, self._rn_dev)
        )
        self._count_top_k(
            self._n_pad // self._n_shards,
            n_local if shortlist is None else shortlist,
            widths[0],
        )
        with _tracing.stage(_tracing.DISPATCH), _cc.track_compile(
            "retrieval-stage1", self._exec_seen, exec_key
        ):
            cand = stage1(
                self._upload(operand), *resident, self._allow_dev,
                self._codes_dev,
            )
        with _tracing.stage(_tracing.MERGE):
            packed = _merge_candidates(cand, n_dev, n_local, self._rep_out)
        _m_merge_rows().labels(component=self.component).inc(
            b_pad * self._n_shards * n_local
        )
        with _tracing.stage(_tracing.DEVICE_WAIT):
            host = np.asarray(packed)[:b]
        if self._batches % _SKEW_SAMPLE_EVERY == 1:
            # the merge has read the candidates by now, so this fetch
            # waits on nothing: one host copy, on sampled batches only
            self._record_skew(np.asarray(cand)[:b], host, n_dev, n_local)
        if self.precision != "float32":
            return self._refine_exact(
                q, host, n_dev, n, positive_only,
                row_norm if normalize == "rows" else normalize,
            )
        return self._unpack(host, n)

    def _unpack(
        self, packed: np.ndarray, n: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        """``unpack_topn`` with every index under ``n_items``: a dead
        slot (-inf) may point at a padding row, which the caller's
        table and an engine's item names do not have."""
        s, i = unpack_topn(packed, n)
        return s, np.minimum(i, self.n_items - 1)

    def _refine_exact(
        self,
        q: np.ndarray,
        packed: np.ndarray,
        n_dev: int,
        n: int,
        positive_only: bool,
        normalize,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Final exact rescore of the device's merged c·n candidates
        against the ORIGINAL float32 rows (host RAM — the engines keep
        ``item_factors`` host-resident anyway, so this costs zero HBM).
        B·c·n·k host FLOPs per batch, negligible next to the B·N·k the
        device just did; recall@n is then limited only by whole-shortlist
        misses and id parity vs the exact path holds by construction."""
        with _tracing.stage(_tracing.REFINE):
            s_d, i_d = self._unpack(packed, n_dev)
            # [B, n_dev, k] gather from host RAM, or from the pages of
            # a mapped file (the page cache's, or the disk's)
            rows = self._y_f32_host[i_d]
            sc = np.einsum(
                "bk,bnk->bn", q, rows, optimize=True
            ).astype(np.float32)
            if isinstance(normalize, np.ndarray):  # per-row cosine flags
                sc = sc * np.where(
                    normalize[:, None], self._rn_f32_host[i_d],
                    np.float32(1.0),
                )
            elif normalize:
                sc = sc * self._rn_f32_host[i_d]
            if positive_only:
                sc = np.where(sc > 0, sc, -np.inf)
            # dead device slots (masked / past live-candidate count)
            # stay dead regardless of what their placeholder id
            # rescores to
            sc = np.where(s_d == -np.inf, -np.inf, sc)
            # descending exact score, ties broken by LOWEST global id —
            # the same order naive_topn_reference's stable sort produces
            order = np.lexsort((i_d, -sc), axis=1)[:, :n]
            scores = np.take_along_axis(sc, order, axis=1)
            idx = np.take_along_axis(i_d, order, axis=1)
            # what the float32 originals bought: the answers whose live
            # ids or their order differ from the device's own best n
            live = scores > -np.inf
            changed = ((idx != i_d[:, :n]) & live).any(axis=1)
            _m_shortlist_rows().labels(component=self.component).inc(
                i_d.size
            )
            _m_refine_changed().labels(component=self.component).inc(
                int(changed.sum())
            )
            return scores, idx

    def _record_skew(
        self, cand: np.ndarray, host: np.ndarray, n: int, n_local: int
    ) -> None:
        """Cross-shard imbalance from one sampled batch: live stage-1
        candidates per shard, and which shard each final top-n row came
        from. Uniform shapes make per-shard FLOPs equal, so imbalance —
        the thing that stretches the merge's critical path — shows up
        here, not in timers."""
        S = self._n_shards
        if S <= 1 or not len(cand):
            return
        arr = cand.reshape(cand.shape[0], S, 2, n_local)
        cand_scores = np.ascontiguousarray(arr[:, :, 0, :]).view(np.float32)
        live = (cand_scores > -np.inf).sum(axis=(0, 2)).astype(float)
        g = _m_shard_candidates()
        for s in range(S):
            g.labels(shard=str(s)).set(float(live[s]))
        if live.mean() > 0:
            _m_shard_skew().labels(kind="candidates").set(
                float(live.max() / live.mean())
            )
        scores, idx = unpack_topn(host, n)
        owners = idx[scores > -np.inf] // (self._n_pad // S)
        counts = np.bincount(owners, minlength=S).astype(float)
        if counts.mean() > 0:
            _m_shard_skew().labels(kind="results").set(
                float(counts.max() / counts.mean())
            )

    @property
    def _scale_operand(self):
        """The per-row scale operand of the two-stage kernels. bf16 has
        no scales; the norm vector rides in the slot (same shape and
        sharding spec) and the kernel — static on precision — never
        reads it, so XLA DCEs the input instead of us shipping a dummy
        catalog-length buffer."""
        return (
            self._scale_dev if self._scale_dev is not None
            else self._rn_dev
        )

    def _shortlist_width(self, n: int, rows: int) -> int:
        """Stage-1 shortlist width for a final top-``n`` over ``rows``
        candidate rows: ``shortlist_mult``·n, pow2-bucketed through the
        shared ladder (O(log) compiled widths) and clamped to the row
        count — never below ``n``, so the stage-2 top_k is always
        satisfiable."""
        return pow2_topk_width(
            min(self.shortlist_mult * n, rows), rows,
            site="retrieval_shortlist",
        )

    def _stage1(
        self,
        n_local: int,
        positive_only: bool,
        normalize,
        widths: Tuple[int, int, int],
        shortlist: Optional[int] = None,
    ):
        key = (n_local, positive_only, normalize, widths, shortlist)
        fn = self._stage1_cache.get(key)
        if fn is None:
            axis = self._axis
            if shortlist is None:
                kernel = functools.partial(
                    _shard_topk_kernel,
                    axis=axis, n_local=n_local,
                    positive_only=positive_only, normalize=normalize,
                    widths=widths,
                )
                in_specs = (
                    P(None, None),  # the batch's packed operand: replicated
                    P(axis, None),  # Y: row-sharded
                    P(axis),        # rn
                    P(axis),        # allow
                    P(axis, None),  # per-item category codes
                )
            else:
                kernel = functools.partial(
                    _shard_topk_kernel_2s,
                    axis=axis, n_local=n_local, shortlist=shortlist,
                    positive_only=positive_only, normalize=normalize,
                    precision=self.precision, widths=widths,
                )
                in_specs = (
                    P(None, None),  # the batch's packed operand: replicated
                    P(axis, None),  # Yq: row-sharded quantized rows
                    P(axis),        # per-row scales
                    P(axis),        # rn
                    P(axis),        # allow
                    P(axis, None),  # per-item category codes
                )
            fn = jax.jit(
                jax.shard_map(
                    kernel,
                    mesh=self.mesh,
                    in_specs=in_specs,
                    # per-shard candidate blocks concatenate along the
                    # candidate dim: the stage-1 output STAYS sharded
                    out_specs=P(None, axis),
                    check_vma=False,
                )
            )
            self._stage1_cache[key] = fn
        return fn

    def warm_tiers(self, n: int) -> List[int]:
        """The top-k widths ``warm(n=n)`` compiles: 16 doubling to ``n``,
        clamped to the catalog."""
        tiers, w = [], 16
        while True:
            tiers.append(min(w, self.n_items))
            if w >= min(n, self.n_items):
                return sorted(set(tiers))
            w *= 2

    def ladder_size(self, tiers: int = 1, flags: int = 1) -> int:
        """How many executables the closed ladder holds."""
        return (
            len(self._batch_ladder or ()) * len(self._exclude_ladder or (1,))
            * len(self._include_ladder or (1,)) * tiers * flags
        )

    def free(self) -> None:
        """Drop the device-resident buffers (factors, norms, mask) and
        the compiled stage cache. Owner contract (the engines'
        ``release_serving``): null the model's retriever reference FIRST
        and only call this after the last in-flight batch drained — a
        subsequent ``topn`` raises rather than computing on half state.
        The buffers' device memory is freed by refcount: a wedged
        straggler still holding them keeps them alive until it resolves,
        so nothing is ever freed underneath a running batch."""
        self._freed = True
        self._y_dev = None
        self._scale_dev = None
        self._rn_dev = None
        self._allow_dev = None
        self._codes_dev = None
        self._factors = None
        self._y_f32_host = None
        self._rn_f32_host = None
        if self.mesh is not None:
            self._stage1_cache = {}
        _m_resident_bytes().labels(component=self.component).set(0.0)
        _m_bytes_per_item().labels(
            component=self.component, precision=self.precision
        ).set(0.0)
        self._ledger_factors.close()
        self._ledger_mask.close()

    def warm(
        self,
        n: int = 16,
        max_batch: int = 128,
        flag_combos: Sequence[Tuple[bool, bool]] = ((True, False),),
        exclude_widths: Sequence[int] = (1, 16, 64),
    ) -> None:
        """Deploy-time compile of the padded-batch executables the
        serving path can hit (O(log) per flag combo x exclude width;
        see BaseAlgorithm.warm). ``flag_combos`` lists the
        (positive_only, normalize) pairs the engine serves with;
        ``exclude_widths`` the per-query exclusion-list widths to
        pre-trace — the id-list block pads to a power of two, so a
        query arriving with a blacklist/seen set is a DIFFERENT traced
        shape than a bare query, and without warming it the first such
        query would pay an XLA compile inside a live batch. 1/16/64
        cover bare queries and the common seen/blacklist sizes; rarer
        widths (and whitelists) still compile on first use.

        The top-k width itself LADDERS (16 doubling to ``n``): each
        pow2 tier the pow2_topk_width router can request is a distinct
        executable, and on a quantized retriever each tier also pins
        its derived stage-1 shortlist width — so the whole
        precision x shortlist combination space this instance can
        serve compiles here, never inside the first live batch that
        asks for a wider ``num``.

        A retriever built with ladders (``max_batch``,
        ``exclude_ladder``, ``include_ladder``) compiles their whole
        product instead, for every tier and flag combination: the
        executable space is then closed, and ``max_batch`` and
        ``exclude_widths`` are not read. A flag pair's ``normalize``
        may be ``"rows"`` (per-row cosine flags)."""
        k = self.rank
        batches = self._batch_ladder or _batch_sizes(max_batch)
        for nn in self.warm_tiers(n):
            for positive_only, normalize in flag_combos:
                for b in batches:
                    rows = (
                        np.zeros(b, bool) if normalize == "rows"
                        else normalize
                    )
                    for ew in self._exclude_ladder or exclude_widths:
                        for iw in self._include_ladder or (1,):
                            self.topn(
                                np.zeros((b, k), np.float32), nn,
                                exclude=(
                                    [np.zeros(ew, np.int64)] * b
                                    if ew > 1 else None
                                ),
                                include=(
                                    [np.zeros(iw, np.int64)] * b
                                    if iw > 1 else None
                                ),
                                positive_only=positive_only,
                                normalize=rows,
                            )


def naive_topn_reference(
    item_factors: np.ndarray,
    query_rows: np.ndarray,
    n: int,
    *,
    exclude: Optional[Sequence] = None,
    include: Optional[Sequence] = None,
    positive_only: bool = False,
    normalize: bool = False,
) -> Tuple[np.ndarray, np.ndarray]:
    """The naive path the sharded retriever must match id-for-id: ONE
    full [B, N] score matrix (device matmul — the same contraction the
    sharded kernel runs per slice), then a HOST post-filter and sort per
    query. This is both the parity oracle for tests and the
    ``retrieval_vs_naive_speedup`` denominator in the saturation bench —
    it is what serving did before round 12."""
    Y = np.asarray(item_factors, np.float32)
    q = np.atleast_2d(np.asarray(query_rows, np.float32))
    scores = np.asarray(_exact_scores(jnp.asarray(q), jnp.asarray(Y))).copy()
    if normalize:
        scores *= _reciprocal_norms(Y)[None, :]
    b, N = scores.shape
    out_s = np.full((b, n), -np.inf, np.float32)
    out_i = np.zeros((b, n), np.int32)
    for r in range(b):
        row = scores[r]
        allow = np.ones(N, bool)
        inc_list = include[r] if include is not None else None
        if inc_list is not None:
            wl = np.zeros(N, bool)
            wl[np.asarray(inc_list, np.int64)] = True
            allow &= wl
        exc_list = exclude[r] if exclude is not None else None
        if exc_list is not None and len(exc_list):
            allow[np.asarray(exc_list, np.int64)] = False
        if positive_only:
            allow &= row > 0
        masked = np.where(allow, row, -np.inf)
        order = np.argsort(-masked, kind="stable")[:n]
        out_s[r, : len(order)] = masked[order]
        out_i[r, : len(order)] = order
    return out_s, out_i
