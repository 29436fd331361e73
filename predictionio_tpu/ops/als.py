"""Alternating Least Squares on a TPU mesh — explicit and implicit feedback.

This is the TPU-native replacement for MLlib ALS
(`ALS.train` / `ALS.trainImplicit`), which the reference's recommendation
templates delegate to (examples/scala-parallel-recommendation/custom-query/
src/main/scala/ALSAlgorithm.scala:66-73). MLlib's implementation exchanges
rating blocks over Spark shuffles each half-iteration; here the ragged
rating matrix is repacked host-side into a **fixed-width segment layout**
(ELL-style, in the spirit of the ALX paper's static-shape recipe,
PAPERS.md — arXiv:2112.02194), chosen over per-density bucketing after
profiling: a bucket ladder turns each half-iteration into ~40 small
sequential device ops, each at ~1% utilization, while one packed layout
runs the whole side as a handful of large ops.

- **Segment packing (host, vectorized):** each row's observation list is
  split into segments of exactly ``L`` slots (short rows pad their single
  segment; long rows span several segments). All device shapes are
  static; the ragged CSR never reaches the accelerator, and padding waste
  is bounded by L per nonempty row.
- **Gather + einsum normal equations (device):** gather the counter-side
  factors ``Yg = Y[cols]`` ([S, L, k]) chunk-by-chunk, form per-segment
  Gramian corrections with one einsum ([S, k, k] — MXU work), and
  scatter-add segments into per-row systems ``A`` [R, k, k], ``b`` [R, k]
  (most rows are a single segment). Add the shared Gramian (implicit
  mode) and regularization, then solve ALL rows with one batched
  Cholesky. Rows with no observations keep their previous factors.
- **Sharding:** segments are sharded over the mesh's ``data`` axis;
  factor/system rows are row-sharded and the counter-side factors
  replicated for the gather. The shared Gramian ``YᵀY`` of a row-sharded
  factor matrix is a sharded matmul whose partial products XLA
  all-reduces over ICI — the explicit Gramian all-reduce of the
  ALX/MLlib designs falls out of the sharding annotations.

Solves run in float32 (k×k, numerically delicate); gathers/einsums can run
in bfloat16 with float32 accumulation via ``compute_dtype``.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import logging
import math
import threading
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from predictionio_tpu.parallel.mesh import pad_to_multiple
from predictionio_tpu.utils import compilation_cache as _cc
from predictionio_tpu.utils import device_ledger as _dl
from predictionio_tpu.utils import metrics as _metrics
from predictionio_tpu.utils import tracing as _tracing

logger = logging.getLogger(__name__)

# Concurrent fused-loop executions from several host threads (a grid
# evaluation's thread-parallel variants) deterministically deadlock the
# XLA CPU client on small-core boxes: threads park forever inside
# run_iters/device_get (tier-1's test_grid_evaluation_picks_best hang).
# On the CPU backend the device work serializes on the cores anyway, so
# a process-wide lock around the device loop + factor fetch costs
# nothing and removes the deadlock; accelerator backends never take it.
_CPU_DEVICE_LOOP_LOCK = threading.Lock()


def _device_loop_guard():
    import contextlib

    if jax.default_backend() == "cpu":
        return _CPU_DEVICE_LOOP_LOCK
    return contextlib.nullcontext()


@dataclasses.dataclass(frozen=True)
class ALSConfig:
    rank: int = 10
    iterations: int = 10
    reg: float = 0.01
    alpha: float = 1.0  # implicit-feedback confidence scale
    implicit_prefs: bool = False
    # MLlib<=1.3 scales reg by per-row observation count (ALS-WR); "plain"
    # uses unscaled reg.
    reg_mode: str = "weighted"
    seed: int = 0
    compute_dtype: str = "float32"  # or "bfloat16" for MXU-rate einsums
    # MAX slot width of the packed segment layout. Each solve side uses
    # the smallest power of two >= its mean observation count (min 8,
    # capped here): sparse sides would otherwise pad every row out to the
    # full width (e.g. 3 obs/user -> 40x waste at width 128), while dense
    # sides want wide segments for big einsum chunks.
    segment_length: int = 128
    # max gathered slots per device chunk (bounds the [chunk, L, k]
    # gather buffer; ~4M slots * rank 32 * bf16 = 256 MB)
    chunk_slots: int = 4_194_304
    # per-sweep convergence telemetry from the fused loop (factor-delta
    # RMS per side, written into a fixed [TELEMETRY_SLOTS, 4] output —
    # no host callback inside the jit). Two elementwise reductions over
    # the factor matrices per sweep: noise against the gather/einsum/
    # Cholesky work (bench.py gates the overhead at <2% of sweep time).
    # Off = a separate executable (the flag is a static jit arg).
    sweep_telemetry: bool = True
    # per-row solver. "exact" solves the full k x k normal equations with
    # one batched Cholesky per half-sweep; "subspace" runs the iALS++
    # blocked Gauss-Seidel update (arXiv:2110.14044): one pass over
    # rank/block_size column blocks per half-sweep, each block a batched
    # block_size x block_size solve against the residual — the [R, k, k]
    # system tensor is never materialized, so solve FLOPs and HBM traffic
    # drop by ~rank/block_size at equal per-sweep quality. Both solvers
    # target the same normal equations (same fixed point); block_size
    # must divide rank.
    solver: str = "exact"
    block_size: int = 0

    def __post_init__(self):
        if self.reg_mode not in ("weighted", "plain"):
            raise ValueError(f"reg_mode must be weighted|plain, got {self.reg_mode}")
        validate_solver(self.solver, self.block_size, self.rank)

    @property
    def telemetry_rows_per_sweep(self) -> int:
        """Telemetry rows the fused loop records per sweep: one for the
        exact solver, one PER BLOCK for the subspace solver (the
        per-block convergence curve of satellite telemetry)."""
        if self.solver == "subspace" and self.block_size:
            return self.rank // self.block_size
        return 1


def validate_solver(solver: str, block_size: int, rank: int) -> None:
    """Shared solver-param coherence check: ALSConfig and every engine's
    algorithm params call this at construction, so an incoherent
    solver/block_size pair fails at PARAM PARSE time with a clear error
    instead of surfacing as a shape error inside the jit."""
    if solver not in ("exact", "subspace"):
        raise ValueError(
            f"solver must be 'exact' or 'subspace', got {solver!r}"
        )
    if solver == "subspace":
        if not isinstance(block_size, int) or block_size <= 0:
            raise ValueError(
                "solver='subspace' requires block_size > 0 (a divisor of "
                f"rank={rank}); got block_size={block_size!r}"
            )
        if rank % block_size != 0:
            raise ValueError(
                f"block_size={block_size} must divide rank={rank} for "
                "the iALS++ blocked subspace solver (the rank splits "
                "into rank/block_size equal column blocks)"
            )


def config_train_key(config: "ALSConfig") -> tuple:
    """The training-semantics identity of a config — everything that
    changes what the fused loop COMPUTES for fixed data. The resident
    pack (ops/streaming.py) keys its device-held factor/regularizer
    state on this: a mismatch on any component (reg sweep, implicit
    flip, alpha retune, solver or block-size change) demotes the round
    to the host wire instead of warm-starting from factors trained
    under different semantics."""
    return (
        config.rank, config.reg, config.reg_mode,
        config.implicit_prefs, config.alpha,
        config.solver, config.block_size,
    )


@dataclasses.dataclass
class PackedSide:
    """Host-side fixed-width segment view of one solve side, pre-shaped
    for the chunked device loop: segment arrays are [C, Sc, L] where
    C·Sc ≥ #segments and Sc·L ≤ chunk_slots.

    There is NO per-slot validity mask: each segment's valid slots are a
    prefix, so one count per segment (``rem``) reconstructs the mask
    on-device as ``iota(L) < rem`` — L bytes/segment less host->HBM
    transfer than the uint8 mask plane rounds 1-3 shipped (≈50 MB at
    ML-20M scale), and one less [C, Sc, L] stream in the accumulation
    loop."""

    n_rows: int  # real (unpadded) row count
    seg_rows: np.ndarray  # [C, Sc] row id of each segment (padding -> n_rows)
    cols: np.ndarray  # [C, Sc, L] column ids (padding = 0, masked)
    vals: np.ndarray  # [C, Sc, L] ratings
    rem: np.ndarray  # [C, Sc] int32 valid slots per segment (prefix)
    counts: np.ndarray  # [n_rows] observation counts

    @property
    def n_segments(self) -> int:
        return self.seg_rows.shape[0] * self.seg_rows.shape[1]


def pack_segments(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n_rows: int,
    segment_length: int = 128,
    pad_segments_to: int = 1,
    chunk_slots: int = 4_194_304,
) -> PackedSide:
    """Pack COO observations into fixed-width row segments (vectorized).

    Each nonempty row occupies ``ceil(count / L)`` consecutive segments of
    exactly ``L`` slots; the last segment of a row is zero-padded and
    masked. Padding segments (to fill the [C, Sc] grid and make the
    segment dim divide ``pad_segments_to``, the mesh axis size) carry the
    sentinel row id ``n_rows`` so their scatter-add lands in a discarded
    system row.
    """
    L = int(segment_length)
    rows = np.asarray(rows, dtype=np.int32)
    cols = np.asarray(cols, dtype=np.int32)
    vals = np.asarray(vals, dtype=np.float32)
    order = np.argsort(rows, kind="stable")
    rows_s, cols_s, vals_s = rows[order], cols[order], vals[order]
    counts = np.bincount(rows_s, minlength=n_rows).astype(np.int32)
    g = _segment_geometry(counts, n_rows, L, pad_segments_to, chunk_slots)

    p_cols = np.zeros((g.total, L), dtype=np.int32)
    p_vals = np.zeros((g.total, L), dtype=np.float32)
    if len(rows_s):
        offset = np.arange(len(rows_s), dtype=np.int64) - g.starts[rows_s]
        flat = (g.seg_base[rows_s] + offset // L) * L + offset % L
        p_cols.reshape(-1)[flat] = cols_s
        p_vals.reshape(-1)[flat] = vals_s
    return PackedSide(
        n_rows=n_rows,
        seg_rows=g.seg_rows.reshape(g.n_chunks, g.sc),
        cols=p_cols.reshape(g.n_chunks, g.sc, L),
        vals=p_vals.reshape(g.n_chunks, g.sc, L),
        rem=g.rem.reshape(g.n_chunks, g.sc),
        counts=counts,
    )


@dataclasses.dataclass
class _SegGeometry:
    """Segment-grid geometry of one solve side, computed from per-row
    counts alone (no pass over the observations)."""

    n_rows: int
    L: int
    counts: np.ndarray  # [n_rows] int32
    starts: np.ndarray  # [n_rows + 1] int64 CSR offsets of the sorted COO
    seg_base: np.ndarray  # [n_rows + 1] int64 first segment of each row
    n_segs: int
    sc: int
    n_chunks: int
    total: int  # n_chunks * sc >= n_segs
    seg_rows: np.ndarray  # [total] row of each segment (padding -> n_rows)
    rem: np.ndarray  # [total] valid slots per segment


def _segment_geometry(
    counts: np.ndarray,
    n_rows: int,
    L: int,
    pad_segments_to: int,
    chunk_slots: int,
) -> _SegGeometry:
    starts = np.zeros(n_rows + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    segs_per_row = -(-counts // L)  # ceil; 0 for empty rows
    seg_base = np.zeros(n_rows + 1, dtype=np.int64)
    np.cumsum(segs_per_row, out=seg_base[1:])
    n_segs = int(seg_base[-1])

    # chunk grid: Sc segments per chunk, Sc*L <= chunk_slots, Sc a
    # multiple of the shard count so each chunk's segment dim shards
    # evenly — and no larger than the data needs, so small inputs don't
    # pad out to the full chunk budget
    sc = max(1, int(chunk_slots) // L)
    sc = max(pad_segments_to, sc - sc % pad_segments_to)
    # Bucket the needed segment count (to a multiple of the shard pad):
    # the packed arrays' shapes feed straight into jit, and k-fold/grid
    # evaluation produces near-identical segment counts (e.g. 402/403/
    # 408) that would otherwise each pay a full XLA compile. Rounding up
    # at 4-significant-bit granularity (the granule is 2^(bitlength-4))
    # collapses them onto one executable with ≤12.5% padding — round 3
    # bucketed to full powers of two, which cost up to 2x padded slots
    # and measurably slowed the single-train benchmarks. The extra
    # segments carry the sentinel row id and are masked out. Bucketing
    # only changes sc in the single-chunk regime (sc_needed below the
    # chunk budget, min() below); budget-capped large trains (ML-20M)
    # get the same sc as before and pad at most one trailing chunk.
    per_pad = -(-max(n_segs, 1) // pad_segments_to)
    sc_needed = pad_segments_to * _bucket_count(per_pad)
    sc = min(sc, sc_needed)
    n_chunks = max(1, -(-max(n_segs, 1) // sc))
    total = n_chunks * sc

    seg_rows = np.full(total, n_rows, dtype=np.int32)
    rem = np.zeros(total, dtype=np.int32)
    if n_segs:
        seg_rows[:n_segs] = np.repeat(
            np.arange(n_rows, dtype=np.int32), segs_per_row
        )
        # valid slots per segment: full L except each row's last segment
        seg_ord = np.arange(n_segs, dtype=np.int64) - seg_base[seg_rows[:n_segs]]
        rem[:n_segs] = np.minimum(
            counts[seg_rows[:n_segs]].astype(np.int64) - seg_ord * L, L
        )
    return _SegGeometry(
        n_rows=n_rows, L=L, counts=counts, starts=starts,
        seg_base=seg_base, n_segs=n_segs, sc=sc, n_chunks=n_chunks,
        total=total, seg_rows=seg_rows, rem=rem,
    )


# --- device-side packing (single-device fast path) ---
#
# The padded segment arrays are up to ~3x the COO bytes; building them on
# HOST means packing them there and shipping that inflation over the
# host->device link. Instead the COO crosses the link ONCE, losslessly
# narrowed (item ids to uint16 when they fit, half-step ratings to int8)
# and — since round 5 — WITHOUT its row-id plane: the host stable-sorts
# by user, the CSR offsets (already needed for the scatter) encode the
# row ids, and _device_pack_presorted rebuilds them in HBM with one
# cumsum pass — and half-step ratings nibble-pack two per byte.
# ML-20M wire: ~51 MB vs ~140 MB with the int32 row plane.
# This replaces the role of the reference's region-parallel HBase scan
# feeding Spark block shuffles (data/storage/hbase/HBPEvents.scala:84-90):
# the wire carries the minimal representation, the accelerator does the
# layout.


def _narrow_ids(idx: np.ndarray) -> np.ndarray:
    """Ids as the narrowest lossless wire dtype (uint16 covers catalogs
    under 64k — the item axis of every MovieLens-class dataset)."""
    return idx.astype(np.uint16) if idx.size and idx.max() < 65536 else idx


def _narrow_vals(vals: np.ndarray) -> Tuple[np.ndarray, float]:
    """(wire_array, scale): ratings on half-step scales (MovieLens 1..5
    or 0.5..5.0) travel as int8 exactly; anything else stays float32."""
    if vals.size == 0:
        return vals, 1.0
    doubled = vals * 2.0
    rounded = np.rint(doubled)
    if (
        np.abs(doubled - rounded).max() == 0.0
        and np.abs(rounded).max() <= 127
    ):
        return rounded.astype(np.int8), 0.5
    return vals, 1.0


def _nibble_packable(vw: np.ndarray) -> bool:
    """Half-step ratings in [0, 7.5] (doubled: 0..15) fit a NIBBLE each —
    two per wire byte, halving the value plane (20 MB -> 10 MB at
    ML-20M). Requires an even element count (pairing; the 4-bit COO
    length bucketing makes any non-tiny wire even) and no negatives
    (implicit-feedback dislikes keep the plain int8 tier)."""
    return (
        vw.dtype == np.int8
        and vw.size > 0
        and vw.size % 2 == 0
        and vw.min() >= 0
        and vw.max() <= 15
    )


def _pack_nibbles_host(vw: np.ndarray) -> np.ndarray:
    return (
        (vw[0::2].astype(np.uint8) & 0xF)
        | (vw[1::2].astype(np.uint8) << 4)
    )


def _unpack_nibbles_host(packed: np.ndarray) -> np.ndarray:
    """Host inverse of _pack_nibbles_host (the delta-fold path recovers
    the cached wire's exact COO instead of rescanning the store)."""
    out = np.empty(packed.size * 2, np.int8)
    out[0::2] = (packed & np.uint8(0xF)).astype(np.int8)
    out[1::2] = (packed >> np.uint8(4)).astype(np.int8)
    return out


def wire_coo(wire: "HostWire") -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Recover the exact user-major (user, item, value) COO a HostWire
    was finished from — every narrowing tier is lossless, so feeding
    this back through :func:`finish_wire` (after a dense-id relabel)
    reproduces the wire byte-for-byte. This is what lets the delta-fold
    path re-finish a grown store from the CACHED wire without touching
    the old rows in storage."""
    n = int(wire.counts_u.sum())
    u = np.repeat(
        np.arange(wire.n_users, dtype=np.int32), wire.counts_u
    )
    i = np.asarray(wire.iw[:n], dtype=np.int32)
    if wire.nibble:
        v = _unpack_nibbles_host(wire.vw)[:n].astype(np.float32)
        v *= np.float32(wire.v_scale)
    elif wire.vw.dtype == np.int8:
        v = wire.vw[:n].astype(np.float32) * np.float32(wire.v_scale)
    else:
        v = np.asarray(wire.vw[:n], dtype=np.float32)
    return u, i, v


@jax.jit
def _unpack_nibbles(packed):
    """uint8 [n/2] -> int8 [n], inverse of _pack_nibbles_host (one cheap
    elementwise pass in HBM; the wire stays half-size)."""
    lo = (packed & jnp.uint8(0xF)).astype(jnp.int8)
    hi = ((packed >> jnp.uint8(4)) & jnp.uint8(0xF)).astype(jnp.int8)
    return jnp.stack([lo, hi], axis=1).reshape(-1)


@functools.partial(jax.jit, static_argnames=("total", "L", "scale"))
def _device_pack_presorted(cols, vals, starts, seg_base, total, L, scale):
    """Pack a HOST-presorted (by row id) COO side WITHOUT the row-id
    plane on the wire: row ids rebuild on device from the CSR offsets by
    an indicator-cumsum (one memory-bound pass over [n]), then the
    scatter layout is identical to _device_scatter_pack's post-sort
    layout — but with no 20M-row device sort and, at ML-20M, ~80 MB less
    host->device traffic (the int32 row plane compresses to the CSR
    offsets already shipped for the scatter). Sentinel-padded tail
    elements get row ids past the last real row; their gathers clamp to
    the CSR edge values, so they land in masked padding segments or drop
    (mode="drop"), exactly like the sorted path. Returns the rebuilt row
    ids — the counter side's pack consumes them as its column values."""
    n = cols.shape[0]
    j = jnp.arange(n, dtype=jnp.int32)
    marks = (
        jnp.zeros((n + 1,), jnp.int32).at[starts[1:]].add(1, mode="drop")
    )
    keys = jnp.cumsum(marks[:n], dtype=jnp.int32)
    offset = j - starts[keys]
    flat = (seg_base[keys] + offset // L) * L + offset % L
    opts = dict(unique_indices=True, indices_are_sorted=True, mode="drop")
    p_cols = (
        jnp.zeros((total * L,), jnp.int32)
        .at[flat].set(cols.astype(jnp.int32), **opts)
    )
    p_vals = (
        jnp.zeros((total * L,), jnp.float32)
        .at[flat].set(vals.astype(jnp.float32) * scale, **opts)
    )
    return keys, p_cols, p_vals


@functools.partial(jax.jit, static_argnames=("total", "L", "scale"))
def _device_scatter_pack(keys, cols, vals, starts, seg_base, total, L, scale):
    """Sort the COO by ``keys`` and scatter values/cols into the padded
    [total, L] segment layout — all on device. The flat slot index of the
    j-th sorted element is derivable from the CSR offsets alone, and is
    strictly increasing, so the scatters are sorted unique-index writes.
    The stable sort makes slot assignment deterministic for a given input
    order (since round 5 the input arrives user-sorted, so within-row
    slot order differs from the host packer's insertion order by a
    permutation — same masked sums, float-rounding-level differences
    only). Sentinel-padded COO elements (row id == n_rows) sort last and
    either land in masked padding segments or drop out of bounds
    (mode="drop")."""
    ks, cs, vs = jax.lax.sort(
        (keys.astype(jnp.int32), cols.astype(jnp.int32), vals),
        num_keys=1, is_stable=True,
    )
    n = keys.shape[0]
    j = jnp.arange(n, dtype=jnp.int32)
    offset = j - starts[ks]
    flat = (seg_base[ks] + offset // L) * L + offset % L
    opts = dict(unique_indices=True, indices_are_sorted=True, mode="drop")
    p_cols = jnp.zeros((total * L,), jnp.int32).at[flat].set(cs, **opts)
    p_vals = (
        jnp.zeros((total * L,), jnp.float32)
        .at[flat].set(vs.astype(jnp.float32) * scale, **opts)
    )
    return p_cols, p_vals


# --- device kernels ---


def _accumulate_systems(
    Y: jax.Array,  # [n_cols(+pad), k] counter-side factors (replicated)
    seg_rows: jax.Array,  # [C, Sc]
    cols: jax.Array,  # [C, Sc, L]
    vals: jax.Array,  # [C, Sc, L]
    rem: jax.Array,  # [C, Sc] valid slots per segment
    alpha,
    n_sys_rows: int,
    *,
    implicit: bool,
    compute_dtype: str,
) -> Tuple[jax.Array, jax.Array]:
    """Per-row normal-equation systems A [R, k, k], b [R, k] from the
    packed segments: a fori_loop over chunks, each chunk ONE gather + two
    einsums + a scatter-add. The chunk loop bounds the [Sc, L, k] gather
    buffer; the einsums are the MXU work."""
    k = Y.shape[-1]
    L = cols.shape[-1]
    cdt = jnp.dtype(compute_dtype)
    # float32 inputs ask for full-precision MXU passes; bfloat16 trades
    # precision for MXU rate explicitly via compute_dtype
    prec = "highest" if cdt == jnp.float32 else "default"
    # The gather is ROW-RATE bound on TPU (measured ~420M rows/s either
    # dtype), so gathering pre-cast rows also skips a cast pass over the
    # [Sc, L, k] buffer; the cast of Y itself is one cheap pass.
    Yc = Y.astype(cdt)
    iota_l = jnp.arange(L, dtype=jnp.int32)
    A0 = jnp.zeros((n_sys_rows, k, k), jnp.float32)
    b0 = jnp.zeros((n_sys_rows, k), jnp.float32)

    def body(c, carry):
        A, b = carry
        rows_c = jax.lax.dynamic_index_in_dim(seg_rows, c, keepdims=False)
        cols_c = jax.lax.dynamic_index_in_dim(cols, c, keepdims=False)
        vals_c = jax.lax.dynamic_index_in_dim(vals, c, keepdims=False)
        rem_c = jax.lax.dynamic_index_in_dim(rem, c, keepdims=False)
        # per-slot validity, reconstructed from the per-segment prefix
        # count (valid slots always lead) — no [C, Sc, L] mask stream
        mask_c = (iota_l[None, :] < rem_c[:, None]).astype(jnp.float32)
        Yg = Yc[cols_c]  # [Sc, L, k] gather from HBM
        if implicit:
            # MLlib trainImplicit semantics (Hu-Koren-Volinsky):
            # confidence c = alpha·|r| (non-negative — keeps A
            # positive-definite even for dislike ratings r<0, e.g.
            # similarproduct LikeAlgorithm's -1); preference p = 1(r>0).
            # A = G + Σ c·y yᵀ ; b = Σ p·(1+c)·y, so a dislike contributes
            # confidence to A but nothing to b.
            aw = (alpha * jnp.abs(vals_c) * mask_c).astype(cdt)
            pref = (vals_c > 0).astype(jnp.float32) * mask_c
            bw = (pref * (1.0 + alpha * jnp.abs(vals_c))).astype(cdt)
        else:
            # A = Σ y yᵀ over observed ; b = Σ r·y
            aw = mask_c.astype(cdt)
            bw = (vals_c * mask_c).astype(cdt)
        A_seg = jnp.einsum(
            "slk,sl,slj->skj", Yg, aw, Yg,
            preferred_element_type=jnp.float32, precision=prec,
        )
        b_seg = jnp.einsum(
            "slk,sl->sk", Yg, bw,
            preferred_element_type=jnp.float32, precision=prec,
        )
        # most rows are one segment; multi-segment rows combine here
        return A.at[rows_c].add(A_seg), b.at[rows_c].add(b_seg)

    return jax.lax.fori_loop(0, seg_rows.shape[0], body, (A0, b0))


def _spd_solve(A: jax.Array, b: jax.Array) -> jax.Array:
    """Batched SPD solve: in-place vectorized Cholesky with the forward
    substitution fused into the factorization sweep.

    XLA's native cho_factor/cho_solve on TPU streams the [R, k, k] batch
    through HBM dozens of times — measured 502 ms per solve at
    R=138k, k=32 (v5e), which was HALF the ML-20M device loop. This
    formulation is k fused steps, each one column rescale + rank-1
    update over the whole batch (~4.5x faster measured, max rel err
    ~6e-7 vs cho_solve on the same systems). Entries outside the lower
    triangle are left stale rather than masked — each step's column
    read masks them off, saving a full [R, k, k] pass per step.

    Supports leading batch dims via vmap (the grid path vmaps it).
    """
    n = A.shape[-1]
    idx = jnp.arange(n)

    def fac_body(j, carry):
        A, y, r, dinv = carry
        col = jax.lax.dynamic_index_in_dim(A, j, axis=2, keepdims=False)
        d = jax.lax.rsqrt(
            jax.lax.dynamic_index_in_dim(col, j, axis=1, keepdims=False)
        )
        col = jnp.where(idx[None, :] >= j, col * d[:, None], 0.0)
        # forward substitution, fused: y_j = r_j / L_jj, r -= L[:, j] y_j
        yj = jax.lax.dynamic_index_in_dim(r, j, axis=1, keepdims=False) * d
        r = r - col * yj[:, None]
        y = jax.lax.dynamic_update_index_in_dim(y, yj, j, axis=1)
        dinv = jax.lax.dynamic_update_index_in_dim(dinv, d, j, axis=1)
        # rank-1 Schur update; col is zero above j, so rows/cols < j are
        # untouched and the (never-read) upper triangle absorbs the rest.
        # The scaled column lands in A[:, :, j] via the SAME fused pass (a
        # select on the column index) — a separate dynamic_update_slice
        # here materialized a full [R, k, k] data-formatting copy per
        # pass, doubling solve HBM traffic (trace: copy.80/copy.110 ~
        # equal bytes to the multiply-subtract itself).
        A = jnp.where(
            idx[None, None, :] == j,
            col[:, :, None],
            A - col[:, :, None] * col[:, None, :],
        )
        return (A, y, r, dinv)

    zeros = jnp.zeros_like(b)
    L, y, _, dinv = jax.lax.fori_loop(
        0, n, fac_body, (A, zeros, b, zeros)
    )

    def back_body(jj, x):
        j = n - 1 - jj
        lcol = jax.lax.dynamic_index_in_dim(L, j, axis=2, keepdims=False)
        # x_j = (y_j - sum_{i>j} L_ij x_i) / L_jj ; x_i is still zero for
        # i <= j and L_ij zero for i < j, so the full dot is the tail sum
        s = jnp.sum(lcol * x, axis=-1)
        xj = (
            jax.lax.dynamic_index_in_dim(y, j, axis=1, keepdims=False) - s
        ) * jax.lax.dynamic_index_in_dim(dinv, j, axis=1, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(x, xj, j, axis=1)

    return jax.lax.fori_loop(0, n, back_body, zeros)


def _solve_side(
    X_prev: jax.Array,  # [R, k] previous factors (kept for zero-obs rows)
    Y: jax.Array,  # [n_cols(+pad), k] counter-side factors
    G: jax.Array,  # [k, k] shared Gramian YᵀY (implicit) or zeros
    pack,  # (seg_rows, cols, vals, rem) pre-shaped [C, Sc(, L)]
    lam: jax.Array,  # [R] per-row regularizer (precomputed, guarded > 0)
    has_obs: jax.Array,  # [R] bool — rows with at least one observation
    alpha,
    *,
    implicit: bool,
    compute_dtype: str,
) -> jax.Array:
    k = Y.shape[-1]
    seg_rows, cols, vals, rem = pack
    A, b = _accumulate_systems(
        Y, seg_rows, cols, vals, rem, alpha, X_prev.shape[0],
        implicit=implicit, compute_dtype=compute_dtype,
    )
    if implicit:
        A = A + G[None]
    A = A + lam[:, None, None] * jnp.eye(k, dtype=jnp.float32)
    # ONE batched Cholesky over every row's k x k system
    x = _spd_solve(A, b)
    # rows with no observations keep their previous factors (MLlib only
    # materializes factors for observed ids; init survives here)
    return jnp.where(has_obs[:, None], x.astype(X_prev.dtype), X_prev)


def _solve_side_subspace(
    X_prev: jax.Array,  # [R, k] previous factors (updated in place per block)
    Y: jax.Array,  # [n_cols(+pad), k] counter-side factors
    G: jax.Array,  # [k, k] shared Gramian YᵀY (implicit) or zeros
    pack,  # (seg_rows, cols, vals, rem) pre-shaped [C, Sc(, L)]
    lam: jax.Array,  # [R] per-row regularizer
    has_obs: jax.Array,  # [R] bool
    alpha,
    *,
    implicit: bool,
    compute_dtype: str,
    block_size: int,
) -> Tuple[jax.Array, jax.Array]:
    """One iALS++ block-Gauss-Seidel pass over the side's normal
    equations (arXiv:2110.14044): for each of the rank/block_size column
    blocks B, accumulate only the [R, b, b] block system and the [R, b]
    residual right-hand side ``b_B - (M x)_B`` (M = A + G + lam·I), solve
    the batched b x b systems, and update the block columns in place —
    later blocks see earlier blocks' updates (Gauss-Seidel), which is
    what buys the faster per-sweep convergence the paper measures.

    Versus the exact solver this never materializes the [R, k, k]
    systems: per slot the einsum work drops from k² to k²/b + k·b
    (score recompute + block outer products) and the batched solve from
    k³ to k·b² — ~4x fewer solve-phase FLOPs at rank 64 / block 8, and
    [R, k, b]-not-[R, k, k] of HBM behind the Cholesky. The (A x)_B
    residual term reuses the per-slot score d = y·x, so dislikes /
    confidence weights flow through exactly as in the exact accumulator.

    Returns ``(X_new, block_deltas)`` with ``block_deltas`` the [n_blocks]
    per-block update RMS — the subspace convergence telemetry. Rows with
    no observations keep their previous factors (their block deltas are
    forced to zero before the update lands)."""
    k = Y.shape[-1]
    b = block_size
    n_blocks = k // b
    seg_rows, cols, vals, rem = pack
    L = cols.shape[-1]
    cdt = jnp.dtype(compute_dtype)
    prec = "highest" if cdt == jnp.float32 else "default"
    Yc = Y.astype(cdt)
    iota_l = jnp.arange(L, dtype=jnp.int32)
    R = X_prev.shape[0]
    eye_b = jnp.eye(b, dtype=jnp.float32)

    x = X_prev.astype(jnp.float32)
    deltas = []
    for bi in range(n_blocks):  # static unroll: block slices stay static
        s0 = bi * b
        A0 = jnp.zeros((R, b, b), jnp.float32)
        r0 = jnp.zeros((R, b), jnp.float32)

        def body(c, carry, s0=s0, x=x):
            A, rs = carry
            rows_c = jax.lax.dynamic_index_in_dim(seg_rows, c, keepdims=False)
            cols_c = jax.lax.dynamic_index_in_dim(cols, c, keepdims=False)
            vals_c = jax.lax.dynamic_index_in_dim(vals, c, keepdims=False)
            rem_c = jax.lax.dynamic_index_in_dim(rem, c, keepdims=False)
            mask_c = (iota_l[None, :] < rem_c[:, None]).astype(jnp.float32)
            Yg = Yc[cols_c]  # [Sc, L, k]
            Yb = jax.lax.slice_in_dim(Yg, s0, s0 + b, axis=2)  # [Sc, L, b]
            xg = x[rows_c].astype(cdt)  # [Sc, k] CURRENT factors
            # per-slot score d = y·x against the current (partially
            # updated) factors — the Gauss-Seidel residual ingredient
            d = jnp.einsum(
                "slk,sk->sl", Yg, xg,
                preferred_element_type=jnp.float32, precision=prec,
            )
            if implicit:
                aw = alpha * jnp.abs(vals_c) * mask_c
                pref = (vals_c > 0).astype(jnp.float32) * mask_c
                bw = pref * (1.0 + alpha * jnp.abs(vals_c))
            else:
                aw = mask_c
                bw = vals_c * mask_c
            A_seg = jnp.einsum(
                "slb,sl,slc->sbc", Yb, aw.astype(cdt), Yb,
                preferred_element_type=jnp.float32, precision=prec,
            )
            # b_B - (A x)_B in one weighted reduction: Σ (bw - aw·d)·y_B
            r_seg = jnp.einsum(
                "sl,slb->sb", (bw - aw * d).astype(cdt), Yb,
                preferred_element_type=jnp.float32, precision=prec,
            )
            return A.at[rows_c].add(A_seg), rs.at[rows_c].add(r_seg)

        A, rs = jax.lax.fori_loop(0, seg_rows.shape[0], body, (A0, r0))
        xB = jax.lax.slice_in_dim(x, s0, s0 + b, axis=1)  # [R, b]
        if implicit:
            GB = jax.lax.slice_in_dim(G, s0, s0 + b, axis=0)  # [b, k]
            A = A + jax.lax.slice_in_dim(GB, s0, s0 + b, axis=1)[None]
            rs = rs - x @ GB.T  # (G x)_B — G is symmetric
        A = A + lam[:, None, None] * eye_b
        rs = rs - lam[:, None] * xB
        delta = _spd_solve(A, rs)
        delta = jnp.where(has_obs[:, None], delta, 0.0)
        x = jax.lax.dynamic_update_slice_in_dim(x, xB + delta, s0, axis=1)
        deltas.append(jnp.sqrt(jnp.mean(jnp.square(delta))))
    return x.astype(X_prev.dtype), jnp.stack(deltas)


@jax.jit
def _gramian(Y: jax.Array) -> jax.Array:
    """YᵀY in float32. With Y row-sharded this is a reduce over the data
    axis that XLA lowers to psum over ICI."""
    Yf = Y.astype(jnp.float32)
    return jnp.einsum(
        "nk,nj->kj", Yf, Yf,
        preferred_element_type=jnp.float32, precision="highest",
    )


def _implicit_objective(
    X: jax.Array,
    Y: jax.Array,
    user_pack,
    user_lam: jax.Array,
    item_lam: jax.Array,
    alpha,
    *,
    compute_dtype: str,
) -> jax.Array:
    """The Hu-Koren-Volinsky implicit objective at the current factors:
    ``Σ_all s² + Σ_obs [c·s² − 2(1+c)·p·s + (1+c)·p²] + Σ lam·‖·‖²``
    (c = α·|r|, p = 1(r>0), s = x·y). The full-matrix term collapses via
    the Gramian trick — ⟨XᵀX, YᵀY⟩, two k×k matmuls — and the observed
    correction is one extra gather+score pass over the user-side pack
    (k·L per slot, ~1/k of a solve sweep's einsum work). Padding rows
    are zero on both sides, so the Gramians are exact over the padded
    matrices. The pack is event-level (duplicate (u,i) events are not
    merged — delta folds depend on that), so each repeat subtracts its
    cell's s² again while the all-pairs term counts it once: stores
    with repeated interactions can report negative values. The
    per-sweep trend is the convergence signal, not the absolute
    level."""
    seg_rows, cols, vals, rem = user_pack
    L = cols.shape[-1]
    cdt = jnp.dtype(compute_dtype)
    prec = "highest" if cdt == jnp.float32 else "default"
    Xc = X.astype(cdt)
    Yc = Y.astype(cdt)
    iota_l = jnp.arange(L, dtype=jnp.int32)

    def body(c, acc):
        rows_c = jax.lax.dynamic_index_in_dim(seg_rows, c, keepdims=False)
        cols_c = jax.lax.dynamic_index_in_dim(cols, c, keepdims=False)
        vals_c = jax.lax.dynamic_index_in_dim(vals, c, keepdims=False)
        rem_c = jax.lax.dynamic_index_in_dim(rem, c, keepdims=False)
        mask_c = (iota_l[None, :] < rem_c[:, None]).astype(jnp.float32)
        s = jnp.einsum(
            "slk,sk->sl", Yc[cols_c], Xc[rows_c],
            preferred_element_type=jnp.float32, precision=prec,
        )
        cw = alpha * jnp.abs(vals_c) * mask_c
        p = (vals_c > 0).astype(jnp.float32) * mask_c
        term = cw * s * s - 2.0 * (1.0 + cw) * p * s + (1.0 + cw) * p * p
        return acc + jnp.sum(term)

    obs = jax.lax.fori_loop(
        0, seg_rows.shape[0], body, jnp.float32(0.0)
    )
    all_sq = jnp.sum(_gramian(X) * _gramian(Y))
    Xf = X.astype(jnp.float32)
    Yf = Y.astype(jnp.float32)
    reg = jnp.sum(user_lam * jnp.sum(Xf * Xf, axis=-1)) + jnp.sum(
        item_lam * jnp.sum(Yf * Yf, axis=-1)
    )
    return all_sq + obs + reg


def _constrain(a: jax.Array, sharding) -> jax.Array:
    return (
        jax.lax.with_sharding_constraint(a, sharding)
        if sharding is not None
        else a
    )


# per-sweep telemetry rows the fused loop can record before the ring
# wraps (sweeps past this many stop recording — mode="drop" scatter);
# each row is [dx_rms, dy_rms, x_rms, y_rms, objective] float32. The
# subspace solver records ONE ROW PER BLOCK per sweep, so its buffer is
# allocated at TELEMETRY_SLOTS x rows_per_sweep rows (the block count is
# a jit static) — the same TELEMETRY_SLOTS sweeps fit either way, and
# sweeps x blocks rows never silently truncate into the sweep budget.
TELEMETRY_SLOTS = 64
TELEMETRY_COLS = 5


@functools.partial(
    jax.jit,
    static_argnames=(
        "implicit", "compute_dtype", "rep_sharding", "row_sharding",
        "telemetry", "solver", "block_size",
    ),
    donate_argnums=(0, 1),
)
def _run_iterations(
    X: jax.Array,
    Y: jax.Array,
    user_pack,  # (seg_rows, cols, vals, rem) each [C, Sc(, L)]
    item_pack,
    user_lam: jax.Array,  # [R_u] per-row regularizer
    item_lam: jax.Array,  # [R_i]
    user_has_obs: jax.Array,  # [R_u] bool
    item_has_obs: jax.Array,  # [R_i]
    alpha,
    n_iters: jax.Array,  # dynamic: one compile serves every chunk size
    *,
    implicit: bool,
    compute_dtype: str,
    rep_sharding,  # NamedSharding(P()) or None — replicate for gathers
    row_sharding,  # NamedSharding(P(axis)) or None
    telemetry: bool = True,
    solver: str = "exact",
    block_size: int = 0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The whole training loop as ONE XLA program: lax.fori_loop over
    iterations, each half-iteration a chunked gather/einsum accumulation
    plus one batched solve (``solver="exact"``) or an iALS++ block
    Gauss-Seidel pass (``solver="subspace"``, see _solve_side_subspace).
    One dispatch covers all iterations — no host round trip per
    half-step, factors never leave HBM, and the replicate/shard handoffs
    become compiled all-gathers instead of per-step device_puts. The
    trip count is a runtime value so warm-up, checkpoint chunks, and
    resumes all reuse the same executable. The regularizer (with reg
    and, in weighted mode, per-row counts baked in) arrives as data, so
    sweeping reg reuses the executable too.

    With ``telemetry`` (the convergence tentpole), sweep ``i`` also
    writes [RMS(X_i - X_{i-1}), RMS(Y_i - Y_{i-1}), RMS(X_i), RMS(Y_i),
    objective] rows into a fixed [TELEMETRY_SLOTS x rows_per_sweep, 5]
    output — one row per sweep (exact) or per sweep x block (subspace,
    with per-block update RMS in the delta columns). The objective
    column carries the Hu-Koren-Volinsky implicit loss via the Gramian
    trick in implicit mode and 0 otherwise. All of it is computed IN the
    loop and fetched alongside the factors, never via a host callback
    inside the jit."""
    k = X.shape[-1]
    zeros_g = jnp.zeros((k, k), jnp.float32)
    subspace = solver == "subspace"
    nb = (k // block_size) if (subspace and block_size) else 1

    def half(X, Y, pack, lam, has_obs):
        G = _gramian(Y) if implicit else zeros_g
        Y_rep = _constrain(Y, rep_sharding)
        if subspace:
            X, block_d = _solve_side_subspace(
                X, Y_rep, G, pack, lam, has_obs, alpha,
                implicit=implicit, compute_dtype=compute_dtype,
                block_size=block_size,
            )
        else:
            X = _solve_side(
                X, Y_rep, G, pack, lam, has_obs, alpha,
                implicit=implicit, compute_dtype=compute_dtype,
            )
            block_d = None
        return _constrain(X, row_sharding), block_d

    def _rms(a):
        return jnp.sqrt(jnp.mean(jnp.square(a.astype(jnp.float32))))

    def body(i, carry):
        X, Y, tel = carry
        Xn, dxb = half(X, Y, user_pack, user_lam, user_has_obs)
        Yn, dyb = half(Y, Xn, item_pack, item_lam, item_has_obs)
        if telemetry:
            obj = (
                _implicit_objective(
                    Xn, Yn, user_pack, user_lam, item_lam, alpha,
                    compute_dtype=compute_dtype,
                )
                if implicit
                else jnp.float32(0.0)
            )
            x_rms, y_rms = _rms(Xn), _rms(Yn)
            if subspace:
                # one row per block; sweep-level deltas reassemble on
                # host as sqrt(mean(block_delta²)) — blocks are disjoint
                # column sets, so the identity is exact
                for j in range(nb):
                    row = jnp.stack([dxb[j], dyb[j], x_rms, y_rms, obj])
                    tel = tel.at[i * nb + j].set(row, mode="drop")
            else:
                row = jnp.stack(
                    [_rms(Xn - X), _rms(Yn - Y), x_rms, y_rms, obj]
                )
                tel = tel.at[i].set(row, mode="drop")
        return (Xn, Yn, tel)

    tel0 = jnp.zeros((TELEMETRY_SLOTS * nb, TELEMETRY_COLS), jnp.float32)
    return jax.lax.fori_loop(0, n_iters, body, (X, Y, tel0))


@functools.partial(
    jax.jit,
    static_argnames=(
        "implicit", "compute_dtype", "rep_sharding", "row_sharding",
    ),
    donate_argnums=(0, 1),
)
def _run_iterations_grid(
    X: jax.Array,  # [V, R_u, k] per-variant factors
    Y: jax.Array,  # [V, R_i, k]
    user_pack,  # shared across variants — only the regularizer differs
    item_pack,
    user_lam: jax.Array,  # [V, R_u]
    item_lam: jax.Array,  # [V, R_i]
    user_has_obs: jax.Array,  # [R_u]
    item_has_obs: jax.Array,  # [R_i]
    alpha,
    n_iters: jax.Array,
    *,
    implicit: bool,
    compute_dtype: str,
    rep_sharding=None,  # NamedSharding(P(None, None, None)) or None
    row_sharding=None,  # NamedSharding(P(None, axis, None)) or None
) -> Tuple[jax.Array, jax.Array]:
    """The reg-grid training loop as ONE vmapped XLA program: V variants
    that share data/rank/iterations and differ only in the regularizer
    train together, so one dispatch covers the whole grid axis and the
    per-variant einsums batch onto the MXU instead of running as V
    serial programs (the reference's grid is host-thread `.par`,
    MetricEvaluator.scala:221-230 — there is no device-side analog).

    On a mesh, rows/segments shard over the mesh axis exactly as the
    single-variant program does (the variant axis is unsharded — every
    device trains all variants over its row shard); there the fori loop
    sits OUTSIDE the vmap so the replicate/row-shard constraints apply
    to the whole [V, R, k] batch each half-iteration. Single-device
    grids keep the r3 vmap-outside structure, which tracks serial
    train_als runs most closely — equivalence is float-level (~1e-5
    factor noise from differing XLA fusion), not bit-exact, the same
    nondeterminism class as the reference's `.par` thread-pool grid."""

    if rep_sharding is None and row_sharding is None:

        def single(X1, Y1, ul, il):
            k = X1.shape[-1]
            zeros_g = jnp.zeros((k, k), jnp.float32)

            def half1(Xs, Ys, pack, lam, has_obs):
                G = _gramian(Ys) if implicit else zeros_g
                return _solve_side(
                    Xs, Ys, G, pack, lam, has_obs, alpha,
                    implicit=implicit, compute_dtype=compute_dtype,
                )

            def body1(_, carry):
                Xc, Yc = carry
                Xc = half1(Xc, Yc, user_pack, ul, user_has_obs)
                Yc = half1(Yc, Xc, item_pack, il, item_has_obs)
                return (Xc, Yc)

            return jax.lax.fori_loop(0, n_iters, body1, (X1, Y1))

        return jax.vmap(single)(X, Y, user_lam, item_lam)

    def half(X, Y, pack, lam, has_obs):
        if implicit:
            G = jax.vmap(_gramian)(Y)
        else:
            k = X.shape[-1]
            G = jnp.zeros((X.shape[0], k, k), jnp.float32)
        Y_rep = _constrain(Y, rep_sharding)
        X = jax.vmap(
            lambda Xv, Yv, Gv, lamv: _solve_side(
                Xv, Yv, Gv, pack, lamv, has_obs, alpha,
                implicit=implicit, compute_dtype=compute_dtype,
            )
        )(X, Y_rep, G, lam)
        return _constrain(X, row_sharding)

    def body(_, carry):
        Xc, Yc = carry
        Xc = half(Xc, Yc, user_pack, user_lam, user_has_obs)
        Yc = half(Yc, Xc, item_pack, item_lam, item_has_obs)
        return (Xc, Yc)

    return jax.lax.fori_loop(0, n_iters, body, (X, Y))


def train_als_grid(
    user_idx: np.ndarray,
    item_idx: np.ndarray,
    ratings: np.ndarray,
    n_users: int,
    n_items: int,
    config: "ALSConfig",
    regs: Sequence[float],
    mesh: Optional[Mesh] = None,
    axis: str = "data",
) -> List["ALSModelArrays"]:
    """Train ``len(regs)`` regularizer variants of one ALS configuration
    in a single batched device program (everything but ``config.reg`` is
    shared: data is packed once, initial factors are identical, and the
    iteration loop is vmapped over the reg axis).

    Returns one ALSModelArrays per reg, in order — numerically matching
    ``train_als`` with ``config.reg = regs[i]`` run one at a time. On a
    multi-device mesh (round-4 upgrade; rounds 1-3 fell back to serial
    per-variant training there) rows/segments shard over ``axis`` with
    the variant axis unsharded, so the whole grid still runs as ONE
    device program with the same collective pattern as train_als.
    """
    if config.solver != "exact":
        raise ValueError(
            "train_als_grid supports solver='exact' only (the vmapped "
            "grid program has no subspace variant); train subspace "
            "configs one at a time via train_als"
        )
    if mesh is not None and mesh.size == 1:
        mesh = None
    k = config.rank
    n_variants = len(regs)
    if n_variants == 0:
        return []
    n_shards = mesh.shape[axis] if mesh is not None else 1

    user_side = pack_segments(
        user_idx, item_idx, ratings, n_users,
        auto_segment_length(user_idx, n_users, config.segment_length),
        n_shards, config.chunk_slots,
    )
    item_side = pack_segments(
        item_idx, user_idx, ratings, n_items,
        auto_segment_length(item_idx, n_items, config.segment_length),
        n_shards, config.chunk_slots,
    )
    logger.info(
        "ALS grid: %d reg variants x (%d users, %d items, %d ratings, "
        "rank %d) in one vmapped program%s",
        n_variants, n_users, n_items, len(ratings), k,
        f" over a {n_shards}-way mesh" if mesh is not None else "",
    )

    rng = np.random.default_rng(config.seed)
    # +1 sentinel row, bucketed (_bucket_count) so near-identical
    # cardinalities share one executable, padded so the row dim shards
    # evenly over the mesh
    r_u = pad_to_multiple(_bucket_count(n_users + 1), n_shards)
    r_i = pad_to_multiple(_bucket_count(n_items + 1), n_shards)
    Y0 = np.zeros((r_i, k), np.float32)
    Y0[:n_items] = np.abs(rng.standard_normal((n_items, k))) / math.sqrt(k)

    weighted = config.reg_mode == "weighted"

    def lam_grid(side: PackedSide, n_sys_rows: int) -> np.ndarray:
        counts = np.zeros(n_sys_rows, np.float32)
        counts[: side.n_rows] = side.counts
        out = np.empty((n_variants, n_sys_rows), np.float32)
        for v, reg in enumerate(regs):
            lam = reg * counts if weighted else np.full_like(counts, reg)
            out[v] = np.maximum(lam, 1e-8)
        return out

    def obs(side: PackedSide, n_sys_rows: int) -> np.ndarray:
        counts = np.zeros(n_sys_rows, np.float32)
        counts[: side.n_rows] = side.counts
        return counts > 0

    vrow = P(None, axis, None) if mesh is not None else P()
    vlam = P(None, axis) if mesh is not None else P()
    seg2 = P(None, axis) if mesh is not None else P()
    seg3 = P(None, axis, None) if mesh is not None else P()
    row1 = P(axis) if mesh is not None else P()
    pack = lambda side: (
        _place(mesh, side.seg_rows, seg2),
        _place(mesh, side.cols, seg3),
        _place(mesh, side.vals, seg3),
        _place(mesh, side.rem, seg2),
    )
    X = _place(mesh, np.zeros((n_variants, r_u, k), np.float32), vrow)
    Y = _place(
        mesh, np.broadcast_to(Y0, (n_variants, r_i, k)).copy(), vrow
    )
    X, Y = _run_iterations_grid(
        X, Y, pack(user_side), pack(item_side),
        _place(mesh, lam_grid(user_side, r_u), vlam),
        _place(mesh, lam_grid(item_side, r_i), vlam),
        _place(mesh, obs(user_side, r_u), row1),
        _place(mesh, obs(item_side, r_i), row1),
        config.alpha, jnp.int32(config.iterations),
        implicit=config.implicit_prefs,
        compute_dtype=config.compute_dtype,
        rep_sharding=(
            NamedSharding(mesh, P(None, None, None))
            if mesh is not None else None
        ),
        row_sharding=(
            NamedSharding(mesh, vrow) if mesh is not None else None
        ),
    )
    if getattr(X, "is_fully_addressable", True) and getattr(
        Y, "is_fully_addressable", True
    ):
        # one device_get for both factor stacks
        X_host, Y_host = (np.asarray(a) for a in jax.device_get((X, Y)))
    else:
        X_host, Y_host = _fetch_global(X), _fetch_global(Y)
    return [
        ALSModelArrays(X_host[v, :n_users], Y_host[v, :n_items])
        for v in range(n_variants)
    ]


def _place(mesh: Optional[Mesh], arr, spec):
    if mesh is None:
        return jnp.asarray(arr)
    return jax.device_put(arr, NamedSharding(mesh, spec))


def _bucket_count(n: int) -> int:
    """Round a count up at 4-significant-bit granularity (≤12.5% padding
    worst-case, just above a power of two; ~6% typical).

    Every jit-visible dimension derived from data cardinalities buckets
    through this so near-identical inputs share one compiled executable:
    segment grids already did (see _segment_geometry); round 5 extends it
    to the system-ROW dimension, because a retrain after new users arrive
    — or the store-scan path seeing 138,432 distinct users where the
    direct path passed 138,493 — otherwise recompiles the whole iteration
    program over a 0.04% shape change (a multi-second XLA pause that
    showed up as the round-4 store→train seam)."""
    n = int(n)
    granule = 1 << max(0, n.bit_length() - 4)
    return -(-n // granule) * granule


def auto_segment_length(
    idx: Optional[np.ndarray], n_rows: int, cap: int,
    counts: Optional[np.ndarray] = None,
) -> int:
    """Smallest power of two >= the side's mean observation count, within
    [min(8, cap), cap] — shared by train_als and train_als_grid so the
    two paths always pack identically (see ALSConfig.segment_length).
    Pass precomputed per-row ``counts`` to skip the bincount pass;
    ``idx`` may then be None (the streaming packer never materializes a
    row-id plane)."""
    floor = min(8, cap)  # honor caps below 8
    if counts is None:
        counts = np.bincount(idx, minlength=n_rows)
    nonempty = int((counts > 0).sum())
    if nonempty == 0:
        return floor
    mean = (
        len(idx) if idx is not None else int(counts.sum())
    ) / nonempty
    L = floor
    while L < cap and L < mean:
        L *= 2
    return L


@dataclasses.dataclass
class ALSModelArrays:
    """Trained factors (host-resident numpy for persistence; see
    models/recommendation for the serving wrapper)."""

    user_factors: np.ndarray  # [n_users, k]
    item_factors: np.ndarray  # [n_items, k]


# --- host wire: the presorted, narrowed COO + geometry ---
#
# Everything the single-device pack path ships to the accelerator, as one
# value: the streaming ingest pipeline (ops/streaming.py) builds it
# incrementally while the store scan is still running, the pack-artifact
# cache stores it so a repeat train skips scan+pack entirely, and
# train_als builds it monolithically. All three enter training through
# train_from_wire, so the device program is identical regardless of how
# the wire was produced.


def aux_pad(arr: np.ndarray) -> np.ndarray:
    """Bucket a CSR-offset array's length (indexed only by row ids
    <= n_rows, so edge-padding is inert) — keeps the pack executable
    shared across near-identical cardinalities, matching the row-dim
    bucketing of the iteration program."""
    out = np.full(_bucket_count(len(arr)), arr[-1], np.int32)
    out[: len(arr)] = arr
    return out


@dataclasses.dataclass
class HostWire:
    """Presorted (by user), narrowed COO wire plus segment geometry —
    the minimal host representation of one training input."""

    n_users: int
    n_items: int
    L_u: int
    L_i: int
    geo_u: _SegGeometry
    geo_i: _SegGeometry
    iw: np.ndarray  # item ids, user-sorted, sentinel-padded, narrowed
    vw: np.ndarray  # values (nibble-packed uint8, int8, or float32)
    nibble: bool
    v_scale: float
    aux: dict  # su/bu/si/bi int32 CSR offsets + segment bases (aux_pad'd)
    counts_u: np.ndarray  # [n_users] int32 observation counts
    counts_i: np.ndarray  # [n_items]
    # a STRIPPED wire kept only its geometry/metadata: the COO planes
    # (iw/vw) and aux offsets live on device under a ResidentPack
    # (ops/streaming.py) and must be restored before any host use
    stripped: bool = False

    @property
    def wire_mb(self) -> float:
        return round(
            (
                self.iw.nbytes
                + self.vw.nbytes
                + sum(int(a.nbytes) for a in self.aux.values())
            )
            / 2**20,
            1,
        )

    @property
    def padded_slots(self) -> int:
        return self.geo_u.total * self.L_u + self.geo_i.total * self.L_i

    def identity_bytes(self) -> bytes:
        """Data-identity material for the checkpoint fingerprint."""
        return self.iw.tobytes() + self.vw.tobytes()


def build_host_wire(
    user_idx: np.ndarray,
    item_idx: np.ndarray,
    ratings: np.ndarray,
    n_users: int,
    n_items: int,
    config: ALSConfig,
    counts_u: Optional[np.ndarray] = None,
    counts_i: Optional[np.ndarray] = None,
) -> HostWire:
    """Monolithic wire build from a COO batch: the host stable-sorts by
    user (the CSR offsets then encode row ids on device), narrows item
    ids and ratings to their minimal lossless wire dtypes, and
    nibble-packs half-step ratings two per byte."""
    user_idx = np.asarray(user_idx, np.int32)
    item_idx = np.asarray(item_idx, np.int32)
    ratings_f = np.asarray(ratings, np.float32)
    if counts_u is None:
        counts_u = np.bincount(user_idx, minlength=n_users).astype(np.int32)
    if counts_i is None:
        counts_i = np.bincount(item_idx, minlength=n_items).astype(np.int32)
    L_u = auto_segment_length(
        user_idx, n_users, config.segment_length, counts=counts_u
    )
    L_i = auto_segment_length(
        item_idx, n_items, config.segment_length, counts=counts_i
    )
    geo_u = _segment_geometry(counts_u, n_users, L_u, 1, config.chunk_slots)
    geo_i = _segment_geometry(counts_i, n_items, L_i, 1, config.chunk_slots)
    n = len(ratings_f)
    order = np.argsort(user_idx, kind="stable")
    # bucket the COO length (4 significant bits) so k-fold/grid runs
    # with near-identical rating counts share one pack executable;
    # padding elements carry the sentinel row id on BOTH sides and
    # either land in masked padding segments or drop out of bounds
    pad = (_bucket_count(n) - n) if n else 1
    iw = np.concatenate([item_idx[order], np.full(pad, n_items, np.int32)])
    vw = np.concatenate([ratings_f[order], np.zeros(pad, np.float32)])
    return finish_wire(
        iw, vw, n_users, n_items, L_u, L_i, geo_u, geo_i,
        counts_u, counts_i,
    )


def finish_wire(
    iw: np.ndarray,
    vw: np.ndarray,
    n_users: int,
    n_items: int,
    L_u: int,
    L_i: int,
    geo_u: _SegGeometry,
    geo_i: _SegGeometry,
    counts_u: np.ndarray,
    counts_i: np.ndarray,
) -> HostWire:
    """Shared tail of the monolithic and streaming packers: narrow a
    user-sorted, sentinel-padded (to the bucketed COO length) item/value
    COO to its minimal wire dtypes and assemble the :class:`HostWire` —
    both producers hand identical inputs here, so the wires (and the
    device programs consuming them) are byte-identical."""
    iw = _narrow_ids(iw)
    vw, v_scale = _narrow_vals(vw)
    nibble = _nibble_packable(vw)
    if nibble:
        vw = _pack_nibbles_host(vw)
    aux = {
        "su": aux_pad(geo_u.starts.astype(np.int32)),
        "bu": aux_pad(geo_u.seg_base.astype(np.int32)),
        "si": aux_pad(geo_i.starts.astype(np.int32)),
        "bi": aux_pad(geo_i.seg_base.astype(np.int32)),
    }
    return HostWire(
        n_users=n_users, n_items=n_items, L_u=L_u, L_i=L_i,
        geo_u=geo_u, geo_i=geo_i, iw=iw, vw=vw, nibble=nibble,
        v_scale=v_scale, aux=aux, counts_u=counts_u, counts_i=counts_i,
    )


def _padded_rows(n: int, n_shards: int) -> int:
    # +1 sentinel row for segment padding, bucketed so near-identical
    # cardinalities share one executable (see _bucket_count), rounded
    # up so the row dim shards evenly over the mesh
    return pad_to_multiple(_bucket_count(n + 1), n_shards)


def _factor_init_host(
    n_users: int, n_items: int, config: ALSConfig, n_shards: int
) -> Tuple[np.ndarray, np.ndarray]:
    """MLlib-style init: nonnegative scaled normals on the item side;
    sentinel/padding rows zero."""
    k = config.rank
    rng = np.random.default_rng(config.seed)
    X0 = np.zeros((_padded_rows(n_users, n_shards), k), np.float32)
    Y0 = np.zeros((_padded_rows(n_items, n_shards), k), np.float32)
    Y0[:n_items] = np.abs(rng.standard_normal((n_items, k))) / math.sqrt(k)
    return X0, Y0


def _lam_obs_host(
    counts: np.ndarray, n_real: int, n_sys_rows: int, config: ALSConfig
) -> Tuple[np.ndarray, np.ndarray]:
    padded = np.zeros(n_sys_rows, np.float32)
    padded[:n_real] = counts
    weighted = config.reg_mode == "weighted"
    lam = config.reg * padded if weighted else np.full_like(padded, config.reg)
    # guard zero-count/padding rows against singular systems (their
    # solutions are discarded by the has_obs select anyway)
    return np.maximum(lam, 1e-8).astype(np.float32), padded > 0


# geometries whose iteration executable this process already warmed up
# (under _CPU_DEVICE_LOOP_LOCK's module; guarded by its own lock). The
# continuous-training loop re-enters start_compile_async every round
# with bucket-stable shapes — re-running the zero-filled warm-up
# execution would serialize behind the device-loop guard and burn a
# core for nothing. If the jit cache was dropped anyway, training just
# compiles inline (timing-accounted, never wrong).
_WARMED_GEOMETRIES: set = set()
_WARMED_LOCK = threading.Lock()


def start_compile_async(
    n_users: int,
    n_items: int,
    geo_u: _SegGeometry,
    geo_i: _SegGeometry,
    L_u: int,
    L_i: int,
    config: ALSConfig,
):
    """Compile the single-device iteration executable for these shapes on
    a BACKGROUND thread, so XLA compile hides under scan/pack/transfer
    (the streaming pipeline calls this the moment bucket geometry is
    known). The warm-up is a zero-iteration run on zero-filled arrays of
    the exact shapes/dtypes the real call uses, so the jit cache (and the
    persistent compilation cache) is hot when training dispatches; a
    geometry this process already warmed skips the whole thing.

    Returns ``wait() -> dict`` with ``busy_s`` (and ``error`` if the
    warm-up failed — logged at error level; training then compiles
    inline, where a compile the device refuses raises)."""
    import threading
    import time as _time

    geo_key = (
        _padded_rows(n_users, 1), _padded_rows(n_items, 1),
        geo_u.n_chunks, geo_u.sc, L_u, geo_i.n_chunks, geo_i.sc, L_i,
        config.rank, config.implicit_prefs, config.compute_dtype,
        config.sweep_telemetry, config.solver, config.block_size,
    )
    with _WARMED_LOCK:
        warmed = geo_key in _WARMED_GEOMETRIES
    if warmed:
        # geometry-bucket hit: the warm-up skip the continuous loop
        # relies on every round (accounted so /metrics can show the
        # AOT cache doing its job)
        _record_compile("cached")
        return lambda: {"busy_s": 0.0}

    rec: dict = {}

    def work() -> None:
        t0 = _time.perf_counter()
        try:
            k = config.rank
            r_u = _padded_rows(n_users, 1)
            r_i = _padded_rows(n_items, 1)

            def zpack(geo: _SegGeometry, L: int):
                return (
                    jnp.zeros((geo.n_chunks, geo.sc), jnp.int32),
                    jnp.zeros((geo.n_chunks, geo.sc, L), jnp.int32),
                    jnp.zeros((geo.n_chunks, geo.sc, L), jnp.float32),
                    jnp.zeros((geo.n_chunks, geo.sc), jnp.int32),
                )

            # the warm-up EXECUTES (zero iterations) — on the CPU
            # backend it must serialize with any in-flight device loop,
            # or the concurrent-execution deadlock the guard exists for
            # can recur through this background thread
            with _device_loop_guard():
                out = _run_iterations(
                    jnp.zeros((r_u, k), jnp.float32),
                    jnp.zeros((r_i, k), jnp.float32),
                    zpack(geo_u, L_u), zpack(geo_i, L_i),
                    jnp.zeros((r_u,), jnp.float32),
                    jnp.zeros((r_i,), jnp.float32),
                    jnp.zeros((r_u,), bool), jnp.zeros((r_i,), bool),
                    config.alpha, jnp.int32(0),
                    implicit=config.implicit_prefs,
                    compute_dtype=config.compute_dtype,
                    rep_sharding=None, row_sharding=None,
                    telemetry=config.sweep_telemetry,
                    solver=config.solver, block_size=config.block_size,
                )
                jax.block_until_ready(out)
            with _WARMED_LOCK:
                _WARMED_GEOMETRIES.add(geo_key)
        except Exception as e:
            # a compile (or warm-up execution) the device refuses must
            # surface: the `error` outcome of pio_als_compile_total lives
            # in this process's registry and dies with a one-shot train
            logger.error(
                "ALS warm-up compile failed for geometry %s", geo_key,
                exc_info=True,
            )
            rec["error"] = repr(e)
        rec["busy_s"] = _time.perf_counter() - t0
        _record_compile(
            "error" if "error" in rec else "warmed", rec["busy_s"]
        )

    th = threading.Thread(target=work, daemon=True, name="als-warm-compile")
    th.start()

    def wait() -> dict:
        th.join()
        return rec

    return wait


def init_factor_state_single(
    counts_u: np.ndarray,
    counts_i: np.ndarray,
    n_users: int,
    n_items: int,
    config: ALSConfig,
    warm: Optional[Tuple[np.ndarray, np.ndarray]] = None,
) -> tuple:
    """Place the single-device factor/regularizer state: X as DEVICE
    zeros (its [r_u, k] buffer never crosses the host→device link — at
    ML-20M that is ~17 MB of zeros the wire no longer carries), Y0 and
    the small lam/has_obs vectors shipped from host.

    ``warm`` — ``([n_users, k], [n_items, k])`` host factor seeds (the
    delta-training warm start: previous model rows carried over, new
    rows already given a fresh init by the caller). A few ALS sweeps
    from a warm seed recover full quality after small data changes (the
    ALX / GPU-MF warm-start observation, PAPERS.md), which is what makes
    a reduced sweep budget safe."""
    k = config.rank
    if warm is not None:
        Xw, Yw = warm
        if Xw.shape != (n_users, k) or Yw.shape != (n_items, k):
            raise ValueError(
                f"warm factor shapes {Xw.shape}/{Yw.shape} do not match "
                f"({n_users}, {k})/({n_items}, {k})"
            )
        X0 = np.zeros((_padded_rows(n_users, 1), k), np.float32)
        X0[:n_users] = Xw
        Y0 = np.zeros((_padded_rows(n_items, 1), k), np.float32)
        Y0[:n_items] = Yw
        # these device arrays enter the DONATED X/Y slots of the fused
        # loop; place them as device-owned copies (jnp.array copies,
        # jnp.asarray may zero-copy alias page-aligned host memory on
        # the CPU backend — donating an alias hands XLA a buffer the
        # caller's numpy still points into)
        X = jnp.array(X0)
        Y = jnp.array(Y0)
        user_lam_h, user_obs_h = _lam_obs_host(
            counts_u, n_users, X.shape[0], config
        )
        item_lam_h, item_obs_h = _lam_obs_host(
            counts_i, n_items, Y.shape[0], config
        )
        return (
            X, Y,
            jnp.asarray(user_lam_h), jnp.asarray(item_lam_h),
            jnp.asarray(user_obs_h), jnp.asarray(item_obs_h),
        )
    _, Y0 = _factor_init_host(n_users, n_items, config, 1)
    X = jnp.zeros((_padded_rows(n_users, 1), k), jnp.float32)
    Y = jnp.array(Y0)  # device-owned copy: Y is DONATED (see warm note)
    user_lam_h, user_obs_h = _lam_obs_host(counts_u, n_users, X.shape[0], config)
    item_lam_h, item_obs_h = _lam_obs_host(counts_i, n_items, Y.shape[0], config)
    return (
        X, Y,
        jnp.asarray(user_lam_h), jnp.asarray(item_lam_h),
        jnp.asarray(user_obs_h), jnp.asarray(item_obs_h),
    )


def device_pack_from_wire(
    wire: HostWire,
    device_wire: Optional[tuple] = None,  # (i_dev, v_dev, aux_dev) pre-shipped
    timings: Optional[dict] = None,
    geo_dev: Optional[tuple] = None,  # resident (sr_u, rem_u, sr_i, rem_i)
) -> Tuple[tuple, tuple]:
    """Transfer the wire (unless pre-shipped) and build the padded
    segment layout in HBM. Returns (user_pack, item_pack) ready for
    :func:`_train_packed`.

    ``geo_dev`` — device-resident ``(seg_rows_u, rem_u, seg_rows_i,
    rem_i)`` flat int32 arrays (the ResidentPack's copies): when given,
    the per-call ``jnp.asarray`` upload of the host geometry arrays is
    skipped — on a resident scatter round nothing store-sized crosses
    the link."""
    import time as _time

    t_phase = _time.perf_counter()
    if device_wire is None:
        i_dev = jax.device_put(wire.iw)
        v_wire_dev = jax.device_put(wire.vw)
        v_dev = _unpack_nibbles(v_wire_dev) if wire.nibble else v_wire_dev
        aux = jax.device_put(wire.aux)
        if timings is not None:
            jax.block_until_ready((i_dev, v_dev, aux))
            timings["device_put_s"] = _time.perf_counter() - t_phase
    else:
        i_dev, v_dev, aux = device_wire
    if timings is not None:
        timings["wire_mb"] = wire.wire_mb
    t_phase = _time.perf_counter()
    u_keys, pcu, pvu = _device_pack_presorted(
        i_dev, v_dev, aux["su"], aux["bu"],
        total=wire.geo_u.total, L=wire.L_u, scale=wire.v_scale,
    )
    pci, pvi = _device_scatter_pack(
        i_dev, u_keys, v_dev, aux["si"], aux["bi"],
        total=wire.geo_i.total, L=wire.L_i, scale=wire.v_scale,
    )
    if timings is not None:
        # dispatch is async; this records the (cached-after-first)
        # pack-executable compile time, not the scatter itself
        timings["device_pack_dispatch_s"] = _time.perf_counter() - t_phase

    def geo_pack(geo: _SegGeometry, pc, pv, sr_dev=None, rem_dev=None):
        return (
            (
                sr_dev.reshape(geo.n_chunks, geo.sc)
                if sr_dev is not None
                else jnp.asarray(geo.seg_rows.reshape(geo.n_chunks, geo.sc))
            ),
            pc.reshape(geo.n_chunks, geo.sc, geo.L),
            pv.reshape(geo.n_chunks, geo.sc, geo.L),
            (
                rem_dev.reshape(geo.n_chunks, geo.sc)
                if rem_dev is not None
                else jnp.asarray(geo.rem.reshape(geo.n_chunks, geo.sc))
            ),
        )

    sr_u = rem_u = sr_i = rem_i = None
    if geo_dev is not None:
        sr_u, rem_u, sr_i, rem_i = geo_dev
    return (
        geo_pack(wire.geo_u, pcu, pvu, sr_u, rem_u),
        geo_pack(wire.geo_i, pci, pvi, sr_i, rem_i),
    )


def train_from_wire(
    wire: HostWire,
    config: ALSConfig,
    *,
    device_wire: Optional[tuple] = None,  # (i_dev, v_dev, aux_dev) pre-shipped
    timings: Optional[dict] = None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 5,
    profile_dir: Optional[str] = None,
    compile_wait=None,  # callable from start_compile_async, or None
    factor_state: Optional[tuple] = None,  # pre-placed (X, Y, lam/obs x4)
    warm_start: Optional[ALSModelArrays] = None,
    _fp_material=None,
    geo_dev: Optional[tuple] = None,  # resident geometry device arrays
    factor_slots_out: Optional[dict] = None,  # receives final device X/Y
) -> ALSModelArrays:
    """Train from a :class:`HostWire` (single-device device-pack path).

    ``device_wire``/``factor_state``/``compile_wait`` let the streaming
    pipeline hand in work it already overlapped with the store scan;
    left as None, this performs the same transfer → device-pack →
    compile → loop sequence train_als always did. ``geo_dev`` passes
    resident segment-geometry device arrays straight through to
    :func:`device_pack_from_wire`; ``factor_slots_out`` (a dict)
    receives the fused loop's FINAL device-resident factor arrays under
    ``"X"``/``"Y"`` — the donated slots round-trip back to the caller
    (the ResidentPack keeps them for the next round) instead of being
    dropped after the host fetch.

    ``warm_start`` seeds the factor state from a previous model whose
    rows are ALREADY aligned to this wire's dense id spaces (shapes must
    be exactly [n_users, k]/[n_items, k] — callers relabel old rows and
    fresh-init new ones; see ops/streaming's delta fold). Combined with
    a reduced ``config.iterations`` this is the delta-retrain budget:
    cost proportional to the data change, not the store size."""
    if factor_state is None:
        # factor/lam/obs placement first: their (small) transfers enqueue
        # ahead of the wire, so the device_put fence attributes them too
        factor_state = init_factor_state_single(
            wire.counts_u, wire.counts_i, wire.n_users, wire.n_items,
            config,
            warm=(
                None
                if warm_start is None
                else (
                    np.asarray(warm_start.user_factors, np.float32),
                    np.asarray(warm_start.item_factors, np.float32),
                )
            ),
        )
    user_pack, item_pack = device_pack_from_wire(
        wire, device_wire=device_wire, timings=timings, geo_dev=geo_dev
    )
    if timings is not None:
        timings["padded_slots"] = wire.padded_slots
    # geometry-bucket padding waste: each rating occupies one slot on
    # each side's segment grid; everything else is padding the bucketed
    # executables bought (pio_padding_waste_ratio{site="als_pack"})
    slots = wire.padded_slots
    if slots:
        nnz = int(wire.counts_u.sum())
        _metrics.get_registry().gauge(
            "pio_padding_waste_ratio",
            "Fraction of a padded dimension that is padding (0 = no "
            "waste): serving batch rows, top-k ladder width, ALS "
            "geometry-bucket slots — the compile-sharing cost the "
            "capacity planning reads",
            labels=("site",),
        ).labels(site="als_pack").set(
            max(0.0, (slots - 2 * nnz) / slots)
        )
    return _train_packed(
        user_pack, item_pack, *factor_state,
        config=config, mesh=None, axis="data",
        n_users=wire.n_users, n_items=wire.n_items,
        checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
        timings=timings, profile_dir=profile_dir,
        fp_material=(
            _fp_material if _fp_material is not None else wire.identity_bytes
        ),
        compile_wait=compile_wait,
        factor_slots_out=factor_slots_out,
    )


def train_als(
    user_idx: np.ndarray,
    item_idx: np.ndarray,
    ratings: np.ndarray,
    n_users: int,
    n_items: int,
    config: ALSConfig = ALSConfig(),
    mesh: Optional[Mesh] = None,
    axis: str = "data",
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 5,
    timings: Optional[dict] = None,
    profile_dir: Optional[str] = None,
) -> ALSModelArrays:
    """Train ALS factors from COO ratings.

    With a mesh, packed segments and factor rows are sharded over
    ``axis`` and counter-side factors replicated; each half-iteration's
    Gramian + factor handoff generates the all-reduce/all-gather pattern
    over ICI.

    With ``checkpoint_dir``, factor state saves every ``checkpoint_every``
    iterations and training resumes from the latest step after an
    interruption (mid-training checkpoint/resume — absent in the
    reference, SURVEY.md §5).

    ``timings``, if given, receives a phase breakdown: ``pack_s`` (host
    geometry/packing), ``device_put_s`` (host->device transfer —
    single-device runs ship only the narrowed COO, ``wire_mb``; the
    padded layout is built in HBM by _device_scatter_pack),
    ``compile_s`` (a zero-iteration run that builds the executable
    before the timed loop — the trip count is dynamic, so the real run
    reuses it), ``device_loop_s`` (accumulated across checkpoint chunks
    when checkpointing), and ``padded_slots`` (total segment-grid slots
    both sides, the denominator for hardware-busyness numbers). At
    ML-20M scale host prep and the transfer are distinct from the
    on-device solve loop, and MFU must be computed against the latter.
    """
    import time as _time

    n_shards = mesh.shape[axis] if mesh is not None else 1

    t_phase = _time.perf_counter()
    user_idx = np.asarray(user_idx, np.int32)
    item_idx = np.asarray(item_idx, np.int32)
    ratings_f = np.asarray(ratings, np.float32)

    def fp_material() -> bytes:
        return user_idx.tobytes() + item_idx.tobytes() + ratings_f.tobytes()

    if mesh is None:
        # Device-side packing: the COO crosses the link once WITHOUT its
        # row-id plane — the host stable-sorts by user (radix, ~1 s at
        # 20M), so user ids rebuild on device from the CSR offsets
        # (_device_pack_presorted) and only the narrowed item ids +
        # ratings (nibble-packed when half-step) travel. At ML-20M that is
        # ~51 MB on the wire instead
        # of ~140 MB, and ONE device sort instead of two (the item side
        # still lax.sorts by item key, consuming the rebuilt user ids).
        wire = build_host_wire(
            user_idx, item_idx, ratings_f, n_users, n_items, config
        )
        logger.info(
            "ALS: %d users (%d segments of %d), %d items (%d segments of "
            "%d), %d ratings, rank %d",
            n_users, wire.geo_u.total, wire.L_u, n_items, wire.geo_i.total,
            wire.L_i, len(ratings_f), config.rank,
        )
        if timings is not None:
            timings["pack_s"] = _time.perf_counter() - t_phase
        return train_from_wire(
            wire, config,
            timings=timings,
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every,
            profile_dir=profile_dir,
            _fp_material=fp_material,
        )

    # Mesh path: host-side packing + sharded placement — the packed
    # arrays must be laid out per the mesh sharding anyway.
    counts_u = np.bincount(user_idx, minlength=n_users).astype(np.int32)
    counts_i = np.bincount(item_idx, minlength=n_items).astype(np.int32)
    L_u = auto_segment_length(
        user_idx, n_users, config.segment_length, counts=counts_u
    )
    L_i = auto_segment_length(
        item_idx, n_items, config.segment_length, counts=counts_i
    )
    geo_u = _segment_geometry(counts_u, n_users, L_u, n_shards, config.chunk_slots)
    geo_i = _segment_geometry(counts_i, n_items, L_i, n_shards, config.chunk_slots)
    logger.info(
        "ALS: %d users (%d segments of %d), %d items (%d segments of %d), "
        "%d ratings, rank %d",
        n_users, geo_u.total, L_u, n_items, geo_i.total, L_i,
        len(ratings_f), config.rank,
    )

    row_sharded = P(axis)
    # segment arrays are [C, Sc(, L)]; the segment dim (Sc, a multiple of
    # the shard count) shards over the mesh axis, the chunk dim C is the
    # device-loop trip dim and stays unsharded
    seg_sharded2 = P(None, axis)
    seg_sharded3 = P(None, axis, None)
    X0, Y0 = _factor_init_host(n_users, n_items, config, n_shards)
    X = _place(mesh, X0, row_sharded)
    Y = _place(mesh, Y0, row_sharded)

    user_side = pack_segments(
        user_idx, item_idx, ratings_f, n_users, L_u,
        n_shards, config.chunk_slots,
    )
    item_side = pack_segments(
        item_idx, user_idx, ratings_f, n_items, L_i,
        n_shards, config.chunk_slots,
    )
    if timings is not None:
        timings["pack_s"] = _time.perf_counter() - t_phase
    t_phase = _time.perf_counter()

    def put_pack(side: PackedSide):
        return (
            _place(mesh, side.seg_rows, seg_sharded2),
            _place(mesh, side.cols, seg_sharded3),
            _place(mesh, side.vals, seg_sharded3),
            _place(mesh, side.rem, seg_sharded2),
        )

    user_pack = put_pack(user_side)
    item_pack = put_pack(item_side)

    user_lam_h, user_obs_h = _lam_obs_host(counts_u, n_users, X.shape[0], config)
    item_lam_h, item_obs_h = _lam_obs_host(counts_i, n_items, Y.shape[0], config)
    user_lam = _place(mesh, user_lam_h, row_sharded)
    item_lam = _place(mesh, item_lam_h, row_sharded)
    user_has_obs = _place(mesh, user_obs_h, row_sharded)
    item_has_obs = _place(mesh, item_obs_h, row_sharded)
    if timings is not None:
        jax.block_until_ready(
            (user_pack, item_pack, user_lam, item_lam,
             user_has_obs, item_has_obs)
        )
        timings["device_put_s"] = _time.perf_counter() - t_phase
        timings["padded_slots"] = geo_u.total * L_u + geo_i.total * L_i
    return _train_packed(
        user_pack, item_pack, X, Y,
        user_lam, item_lam, user_has_obs, item_has_obs,
        config=config, mesh=mesh, axis=axis,
        n_users=n_users, n_items=n_items,
        checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
        timings=timings, profile_dir=profile_dir, fp_material=fp_material,
    )


# --- training telemetry (the observability tentpole's device-loop leg):
# per-sweep convergence rows recorded by the fused loop land in the
# process-global metrics registry, so /metrics on any in-process server
# (and status.json via continuous.py) carries the convergence state of
# the latest round. Families are get-or-create per call — a dict lookup,
# training-round granularity, not a hot path. ---


def _record_compile(outcome: str, busy_s: float = 0.0) -> None:
    """Compile/AOT-cache accounting: ``outcome`` is ``warmed`` (a
    background start_compile_async warm-up built+executed the
    executable), ``cached`` (the geometry bucket was already warm — the
    warm-up skip), ``inline`` (training compiled on the caller's
    thread), or ``error``."""
    reg = _metrics.get_registry()
    reg.counter(
        "pio_als_compile_total",
        "ALS iteration-executable compile events by outcome",
        labels=("outcome",),
    ).labels(outcome=outcome).inc()
    if outcome in ("warmed", "inline"):
        # the geometry-bucket ladder reports into the shared
        # executable-cache accounting (cold-site attribution included:
        # an inline compile under a serving/ingest compile_site counts
        # in pio_cold_compiles_total)
        _cc.record_executable_compile("als-geometry", busy_s)
    if busy_s:
        reg.counter(
            "pio_als_compile_seconds_total",
            "Cumulative seconds spent compiling/warming ALS executables",
        ).inc(busy_s)
    with _WARMED_LOCK:
        n_warm = len(_WARMED_GEOMETRIES)
    reg.gauge(
        "pio_als_warm_geometries",
        "Distinct bucketed geometries whose iteration executable this "
        "process has warmed",
    ).set(n_warm)


def _fetch_telemetry(tel_parts, rows_per_sweep: int = 1) -> Optional[np.ndarray]:
    """Concatenate the per-chunk telemetry buffers into one
    [n_sweeps x rows_per_sweep, TELEMETRY_COLS] host array (rows past
    the TELEMETRY_SLOTS sweep budget per chunk were dropped by the
    in-loop scatter; the subspace solver's buffers carry rows_per_sweep
    block rows per sweep). Multi-host-sharded outputs skip telemetry
    rather than force a cross-process gather."""
    rps = max(1, int(rows_per_sweep))
    rows = []
    for tel, n in tel_parts:
        k = min(int(n), TELEMETRY_SLOTS) * rps
        if k <= 0:
            continue
        if not getattr(tel, "is_fully_addressable", True):
            return None
        rows.append(np.asarray(jax.device_get(tel))[:k])
    if not rows:
        return None
    return np.concatenate(rows, axis=0)


def _sweep_aggregate(sweep_rows: np.ndarray, rows_per_sweep: int) -> np.ndarray:
    """Collapse per-block telemetry rows to one row per sweep: the delta
    columns combine as sqrt(mean(block_rms²)) — exact, since blocks are
    disjoint column sets of equal width — and the per-sweep columns
    (factor RMS, objective) come from the sweep's last block row."""
    rps = max(1, int(rows_per_sweep))
    if rps == 1:
        return sweep_rows
    per = sweep_rows.reshape(-1, rps, sweep_rows.shape[-1])
    out = per[:, -1, :].copy()
    out[:, 0] = np.sqrt(np.mean(np.square(per[:, :, 0]), axis=1))
    out[:, 1] = np.sqrt(np.mean(np.square(per[:, :, 1]), axis=1))
    return out


def _record_sweep_telemetry(
    sweep_rows: np.ndarray,
    device_loop_s: Optional[float],
    n_executed: Optional[int] = None,
    rows_per_sweep: int = 1,
    implicit: bool = False,
) -> None:
    reg = _metrics.get_registry()
    rps = max(1, int(rows_per_sweep))
    per_sweep = _sweep_aggregate(sweep_rows, rps)
    # the telemetry buffer caps at TELEMETRY_SLOTS sweeps per fused-loop
    # call; the sweep counter (and the per-sweep time gauge) must count
    # EXECUTED sweeps, not fetched rows, or a >64-sweep round undercounts
    n = len(per_sweep)
    executed = n if n_executed is None else int(n_executed)
    reg.counter(
        "pio_train_sweeps_total", "ALS sweeps executed by the fused loop"
    ).inc(executed)
    h = reg.histogram(
        "pio_train_sweep_factor_delta",
        "Per-sweep factor-delta RMS (the convergence proxy), by side",
        labels=("side",),
        buckets=_metrics.CONVERGENCE_BUCKETS,
    )
    g_last = reg.gauge(
        "pio_train_last_factor_delta",
        "Factor-delta RMS of the latest round's final sweep, by side",
        labels=("side",),
    )
    for side, col in (("user", 0), ("item", 1)):
        child = h.labels(side=side)
        for v in per_sweep[:, col]:
            if np.isfinite(v):
                child.observe(float(v))
        last = float(per_sweep[-1, col])
        if np.isfinite(last):
            g_last.labels(side=side).set(last)
    if rps > 1:
        # per-block convergence curve of the subspace solver: every
        # block row's update RMS, by side (docs/OBSERVABILITY.md)
        hb = reg.histogram(
            "pio_train_block_factor_delta",
            "Per-block subspace-update RMS of the iALS++ solver, by side",
            labels=("side",),
            buckets=_metrics.CONVERGENCE_BUCKETS,
        )
        for side, col in (("user", 0), ("item", 1)):
            child = hb.labels(side=side)
            for v in sweep_rows[:, col]:
                if np.isfinite(v):
                    child.observe(float(v))
    if implicit:
        obj = float(per_sweep[-1, 4])
        if np.isfinite(obj):
            reg.gauge(
                "pio_train_objective",
                "Implicit (Hu-Koren-Volinsky) training objective at the "
                "latest round's final sweep, Gramian-trick full-matrix "
                "term included",
            ).set(obj)
    if device_loop_s is not None and executed:
        reg.histogram(
            "pio_train_device_loop_seconds",
            "Fused-device-loop wall clock per training round",
            buckets=_metrics.LATENCY_BUCKETS_S,
        ).observe(device_loop_s)
        reg.gauge(
            "pio_train_sweep_seconds",
            "Average device seconds per sweep, latest round",
        ).set(device_loop_s / executed)


def _train_packed(
    user_pack,
    item_pack,
    X: jax.Array,
    Y: jax.Array,
    user_lam: jax.Array,
    item_lam: jax.Array,
    user_has_obs: jax.Array,
    item_has_obs: jax.Array,
    *,
    config: ALSConfig,
    mesh: Optional[Mesh],
    axis: str,
    n_users: int,
    n_items: int,
    checkpoint_dir: Optional[str],
    checkpoint_every: int,
    timings: Optional[dict],
    profile_dir: Optional[str],
    fp_material,  # Callable[[], bytes] — data identity for checkpoints
    compile_wait=None,  # callable from start_compile_async, or None
    factor_slots_out: Optional[dict] = None,  # receives final device X/Y
) -> ALSModelArrays:
    """The shared training tail: compile warm-up, checkpoint/resume, the
    fused iteration loop, and the factor fetch. Every entry path (COO,
    host wire, streaming pipeline, mesh pack) converges here, so the
    device program — and its timings contract — is identical for all."""
    import time as _time

    n_shards = mesh.shape[axis] if mesh is not None else 1
    rep_sharding = NamedSharding(mesh, P()) if mesh is not None else None
    row_sharded = P(axis) if mesh is not None else P()
    row_sharding = NamedSharding(mesh, row_sharded) if mesh is not None else None

    # HBM residency ledger: the live factor state is resident for the
    # whole fused loop. The Anchor ties the entry to this frame, so an
    # exception mid-train still zeroes it; the explicit close below
    # fires on the normal path right after the factors come home.
    _ledger_anchor = _dl.Anchor()
    _fs_label, _fs_bytes, _fs_members = _dl.device_footprint(X, Y)
    _ledger_handle = _dl.get_ledger().register(
        component="train-factors",
        nbytes=_fs_bytes,
        device=_fs_label,
        anchor=_ledger_anchor,
        members=_fs_members,
    )
    logger.info("ALS: factor state resident as %s", _fs_members)

    def run_iters(X, Y, n_iters: int):
        return _run_iterations(
            X, Y, user_pack, item_pack,
            user_lam, item_lam, user_has_obs, item_has_obs,
            config.alpha, jnp.int32(n_iters),
            implicit=config.implicit_prefs,
            compute_dtype=config.compute_dtype,
            rep_sharding=rep_sharding,
            row_sharding=row_sharding,
            telemetry=config.sweep_telemetry,
            solver=config.solver, block_size=config.block_size,
        )

    if timings is not None:
        # the packs were dispatched asynchronously: wait them out (under
        # the background compile, if one is running) so that their tail
        # is billed to them and the loop's timer starts on an idle device
        t_phase = _time.perf_counter()
        jax.block_until_ready((user_pack, item_pack))
        timings["device_pack_exposed_s"] = _time.perf_counter() - t_phase

    if compile_wait is not None:
        # the executable was compiled on a background thread while
        # scan/pack/transfer ran (start_compile_async); only the residual
        # wait — usually zero — is exposed wall clock
        t_phase = _time.perf_counter()
        rec = compile_wait()
        if timings is not None:
            timings["compile_exposed_s"] = _time.perf_counter() - t_phase
            if "busy_s" in rec:
                timings["compile_s"] = rec["busy_s"]
        if rec.get("error") and timings is not None:
            # best-effort warm-up failed; compile inline so the loop
            # timing stays clean
            t_phase = _time.perf_counter()
            with _device_loop_guard():
                jax.block_until_ready(run_iters(X + 0, Y + 0, 0))
            timings["compile_s"] = _time.perf_counter() - t_phase
            _record_compile("inline", timings["compile_s"])
    elif timings is not None:
        # compile outside the timed loop: a ZERO-iteration run builds the
        # same executable the real run reuses (dynamic trip count).
        # Donation consumes its inputs, so feed it copies of the factor
        # arrays (cheap HBM-side copies).
        t_phase = _time.perf_counter()
        with _device_loop_guard():
            jax.block_until_ready(run_iters(X + 0, Y + 0, 0))
        timings["compile_s"] = _time.perf_counter() - t_phase
        _record_compile("inline", timings["compile_s"])

    from predictionio_tpu.workflow.checkpoint import StepCheckpointer

    checkpoint_every = max(1, checkpoint_every)
    ckpt = StepCheckpointer(checkpoint_dir, every=checkpoint_every)
    start_it = 0
    fingerprint = None
    if ckpt.enabled:
        # run identity: same data + same config (iteration count aside) may
        # resume; anything else starts fresh. Guards against silently
        # reusing a finished run's factors after new events arrive, and
        # against shape mismatches from changed user/item counts — the
        # PADDED row dims are part of the identity, so a checkpoint
        # written under a different padding rule (e.g. pre-row-bucketing)
        # restarts cleanly instead of crashing resume on a shape mismatch
        fingerprint = np.frombuffer(
            hashlib.sha256(
                fp_material()
                + repr(dataclasses.replace(config, iterations=0)).encode()
                + f"{n_users},{n_items},{n_shards}".encode()
                + f";rows={X.shape[0]},{Y.shape[0]}".encode()
            ).digest(),
            dtype=np.uint8,
        )
        state = ckpt.restore_latest()
        if state is not None:
            saved_it = int(state["iteration"])
            if not np.array_equal(
                np.asarray(state.get("fingerprint")), fingerprint
            ):
                logger.info(
                    "checkpoint in %s is from a different run (data/config "
                    "changed); training from scratch", checkpoint_dir,
                )
            elif saved_it > config.iterations:
                # can't "untrain": a checkpoint past the requested
                # iteration count would silently return an over-trained
                # model, so start fresh
                logger.info(
                    "checkpoint at iteration %d exceeds requested %d; "
                    "training from scratch", saved_it, config.iterations,
                )
            else:
                start_it = saved_it
                X = _place(mesh, np.asarray(state["X"], np.float32), row_sharded)
                Y = _place(mesh, np.asarray(state["Y"], np.float32), row_sharded)
                logger.info("resuming ALS from iteration %d", start_it)

    from predictionio_tpu.utils.profiling import trace as _profiler_trace

    # per-op observability of the hot loop (SURVEY.md §5): with a
    # profile_dir, EXACTLY the timed device loop(s) run under
    # jax.profiler.trace — no pack/transfer/compile events mixed in
    # (bench.py --trace-loop reduces the trace to docs/ALS_LOOP_TRACE.json).
    # Covers both the single-program path and the checkpoint-chunked loop.
    tel_parts: List[Tuple[jax.Array, int]] = []
    try:
        with _device_loop_guard(), _profiler_trace(profile_dir):
            if not ckpt.enabled:
                # the entire loop is one device program
                if config.iterations > start_it:
                    n_sweeps = config.iterations - start_it
                    t_phase = _time.perf_counter()
                    X, Y, tel = run_iters(X, Y, n_sweeps)
                    tel_parts.append((tel, n_sweeps))
                    if timings is not None or profile_dir is not None:
                        jax.block_until_ready((X, Y))
                    if timings is not None:
                        # recorded before the tracer exits so trace
                        # collection overhead never inflates the loop time
                        timings["device_loop_s"] = (
                            _time.perf_counter() - t_phase
                        )
            else:
                # chunk the fused loop at the checkpoint cadence
                it = start_it
                while it < config.iterations:
                    chunk = min(checkpoint_every, config.iterations - it)
                    t_phase = _time.perf_counter()
                    X, Y, tel = run_iters(X, Y, chunk)
                    tel_parts.append((tel, chunk))
                    if timings is not None:
                        jax.block_until_ready((X, Y))
                        timings["device_loop_s"] = timings.get(
                            "device_loop_s", 0.0
                        ) + (_time.perf_counter() - t_phase)
                    it += chunk
                    logger.debug(
                        "ALS iteration %d/%d done", it, config.iterations
                    )
                    # hand the (possibly mesh-sharded) factor arrays to
                    # orbax as-is: StandardSave handles sharded jax.Arrays
                    # natively, and np.asarray would both crash on
                    # non-fully-addressable multi-host arrays and force a
                    # device->host copy per chunk
                    ckpt.maybe_save(
                        it,
                        {
                            "iteration": it,
                            "X": X,
                            "Y": Y,
                            "fingerprint": fingerprint,
                        },
                        force=True,  # chunk boundaries ARE the cadence
                    )
                    # The next run_iters call DONATES X/Y (donate_argnums),
                    # overwriting these buffers in place; orbax's save may
                    # still be copying them device->host. Block until the
                    # save has committed before handing the buffers back.
                    ckpt.wait_until_finished()
    finally:
        ckpt.close()

    if factor_slots_out is not None:
        # the donated slots' FINAL buffers: after the loop X/Y are fresh
        # device arrays (donation consumed the inputs, not these) — the
        # resident-pack path parks them for the next round's warm start
        # so no factor state ever re-crosses the host→device link
        factor_slots_out["X"] = X
        factor_slots_out["Y"] = Y
    with _device_loop_guard():
        if getattr(X, "is_fully_addressable", True) and getattr(
            Y, "is_fully_addressable", True
        ):
            # one device_get for both factor matrices
            X_host, Y_host = jax.device_get((X, Y))
            X_host, Y_host = np.asarray(X_host), np.asarray(Y_host)
        else:
            X_host, Y_host = _fetch_global(X), _fetch_global(Y)
        rows_per_sweep = config.telemetry_rows_per_sweep
        sweep_rows = (
            _fetch_telemetry(tel_parts, rows_per_sweep)
            if config.sweep_telemetry
            else None
        )
    _ledger_handle.close()
    if sweep_rows is not None and len(sweep_rows):
        _record_sweep_telemetry(
            sweep_rows,
            None if timings is None else timings.get("device_loop_s"),
            n_executed=sum(n for _, n in tel_parts),
            rows_per_sweep=rows_per_sweep,
            implicit=config.implicit_prefs,
        )
        if timings is not None:
            per_sweep = _sweep_aggregate(sweep_rows, rows_per_sweep)
            timings["sweep_telemetry"] = [
                {
                    "dx": float(r[0]), "dy": float(r[1]),
                    "x_rms": float(r[2]), "y_rms": float(r[3]),
                    # objective only carries meaning in implicit mode;
                    # explicit rounds keep the historical 4-key rows
                    **(
                        {"objective": float(r[4])}
                        if config.implicit_prefs
                        else {}
                    ),
                }
                for r in per_sweep
            ]
            if rows_per_sweep > 1:
                timings["block_telemetry"] = [
                    {
                        "sweep": ri // rows_per_sweep,
                        "block": ri % rows_per_sweep,
                        "dx": float(r[0]), "dy": float(r[1]),
                    }
                    for ri, r in enumerate(sweep_rows)
                ]
    # OWN the returned factors: on the CPU backend device_get is
    # zero-copy (owndata=False views over XLA-owned buffers). A model —
    # or the delta fold's warm-start seed — outlives the jax.Arrays it
    # was fetched from, and re-reading the view after later donated
    # executions recycled that memory produced flaky NaNs and exit
    # segfaults. One catalog-sized memcpy buys unconditional safety.
    if not X_host.flags.owndata:
        X_host = X_host.copy()
    if not Y_host.flags.owndata:
        Y_host = Y_host.copy()
    return ALSModelArrays(X_host[:n_users], Y_host[:n_items])


def _fetch_global(arr) -> np.ndarray:
    """Materialize a (possibly multi-host-sharded) factor matrix on every
    host. Single-host arrays fetch directly; on a mesh spanning processes
    each host holds only its row shards, so the full matrix assembles via
    an all-gather over DCN (np.asarray would crash on the
    non-fully-addressable array)."""
    if getattr(arr, "is_fully_addressable", True):
        return np.asarray(arr)
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(arr, tiled=True))


# --- prediction / evaluation helpers ---


@jax.jit
def _predict_pairs(X, Y, u, i):
    return jnp.sum(X[u] * Y[i], axis=-1)


def predict_ratings(
    model: ALSModelArrays, user_idx, item_idx, chunk: int = 1_048_576
) -> np.ndarray:
    """Predicted rating for each (user, item) pair, chunked through device."""
    X = jnp.asarray(model.user_factors)
    Y = jnp.asarray(model.item_factors)
    u = np.asarray(user_idx, np.int32)
    i = np.asarray(item_idx, np.int32)
    outs = []
    for s in range(0, len(u), chunk):
        outs.append(np.asarray(_predict_pairs(X, Y, u[s : s + chunk], i[s : s + chunk])))
    return np.concatenate(outs) if outs else np.zeros(0, np.float32)


def rmse(model: ALSModelArrays, user_idx, item_idx, ratings) -> float:
    pred = predict_ratings(model, user_idx, item_idx)
    err = pred - np.asarray(ratings, np.float32)
    return float(np.sqrt(np.mean(err * err)))


def _topn_packed_impl(factors_q, Y, n):
    # float32 in, float32 out: a TPU's default matmul precision rounds
    # float32 operands to bfloat16, which reorders near-tied items
    scores = jnp.dot(
        factors_q, Y.T, preferred_element_type=jnp.float32,
        precision="highest",
    )
    s, i = jax.lax.top_k(scores, n)  # [B, n] each — one MXU matmul + top_k
    return _pack_topn(s, i)


def _pack_topn(scores, idx):
    """Scores + indices in ONE int32 buffer: one device->host fetch per
    batch. The scores travel as raw float32 BITS beside the int32 indices,
    never the indices as float bits: a small integer's bits read as a
    float32 are a subnormal, and a TPU flushes subnormals to zero — every
    served index came back 0 on the chip. (A float CAST of the indices
    would corrupt ids >= 2^24 instead.)"""
    return jnp.concatenate(
        [jax.lax.bitcast_convert_type(scores, jnp.int32), idx], axis=1
    )


def unpack_topn(packed: np.ndarray, n: int) -> Tuple[np.ndarray, np.ndarray]:
    """(scores [B, n] float32, indices [B, n] int32) from a host copy of
    the packed buffer."""
    packed = np.asarray(packed)
    return (
        np.ascontiguousarray(packed[:, :n]).view(np.float32),
        np.ascontiguousarray(packed[:, n:]),
    )


_topn_packed = jax.jit(_topn_packed_impl, static_argnames=("n",))


@functools.partial(jax.jit, static_argnames=("n", "out_s"))
def _topn_packed_sharded(factors_q, Y, n, out_s):
    """Mesh-path top-N with the output PINNED row-sharded. XLA's sharding
    propagation is free to replicate the result of the per-shard
    matmul+top_k (and does on some backends/core counts), which would put
    a B×catalog-independent collective on the serving hot path;
    ``out_s`` (a hashable NamedSharding, so it rides the jit cache as a
    static) keeps each device holding only its query rows' results."""
    return jax.lax.with_sharding_constraint(
        _topn_packed_impl(factors_q, Y, n), out_s
    )


@functools.partial(jax.jit, static_argnames=("n",))
def _topn_packed_chain(factors_q, Y, n, n_iters):
    """n_iters chained top-N passes in ONE dispatch — a measurement tool:
    per-pass device time = (t(K) - t(1)) / (K - 1) cancels the dispatch
    and fetch overhead, which would otherwise swamp the sub-millisecond
    compute. The query is perturbed per iteration so XLA cannot hoist
    the matmul out of the loop."""
    init = jnp.zeros((factors_q.shape[0], 2 * n), jnp.int32)

    def body(i, _):
        qq = factors_q + i.astype(jnp.float32) * 1e-7
        return _topn_packed_impl(qq, Y, n)

    return jax.lax.fori_loop(0, n_iters, body, init)


# serving top-k executable keys this process already compiled (the
# _topn_packed jit caches are process-global, so the seen-set is too)
_TOPK_SEEN: set = set()


class ServingFactors:
    """Device-resident factors for the serving hot path.

    Transfers the factor matrices to device once; each request then ships
    only the query rows up and one packed result buffer down.

    With a ``mesh``, serving is data-parallel: the item factor matrix
    replicates across the mesh (every device holds the catalog), query
    batches shard rows over the mesh's ``axis``, and each device runs the
    matmul + top_k on its row shard — no collective on the hot path, B×
    the single-chip throughput.
    """

    def __init__(
        self,
        user_factors: np.ndarray,
        item_factors: np.ndarray,
        mesh: Optional[Mesh] = None,
        axis: str = "data",
    ):
        if mesh is not None and mesh.shape[axis] == 1:
            mesh = None
        self.mesh = mesh
        self._axis = axis
        self.user_factors = np.asarray(user_factors)
        if mesh is None:
            self._uf_dev = jax.device_put(
                np.asarray(user_factors, np.float32)
            )
            self._if_dev = jax.device_put(
                np.asarray(item_factors, np.float32)
            )
        else:
            rep = NamedSharding(mesh, P())
            self._uf_dev = jax.device_put(
                np.asarray(user_factors, np.float32), rep
            )
            self._if_dev = jax.device_put(
                np.asarray(item_factors, np.float32), rep
            )
        self.n_items = self._if_dev.shape[0]
        # HBM residency ledger: the replicated serving upload — the
        # footprint counts every per-device COPY (physical bytes), and
        # the member map attributes each copy to its device for drift
        # reconciliation. No explicit free path exists (release_serving
        # just drops the reference and the buffers free by refcount),
        # so the anchor finalizer IS the close — the ledger entry
        # zeroes when the last reference (including a straggler
        # batch's) resolves.
        label, nbytes, members = _dl.device_footprint(
            self._uf_dev, self._if_dev
        )
        self._ledger = _dl.get_ledger().register(
            component="serving-factors",
            nbytes=nbytes,
            device=label,
            anchor=self,
            members=members,
        )

    def topn_by_rows(self, user_rows: np.ndarray, n: int):
        """Top-N for explicit query factor rows [B, k]."""
        b = len(user_rows)
        packed_dev = self.topn_packed_device(user_rows, n)
        # the blocking fetch: queueing, execution and the copy back, as
        # the host sees them
        with _tracing.stage(_tracing.DEVICE_WAIT):
            packed = np.asarray(packed_dev)
        with _tracing.stage(_tracing.BUILD):
            return unpack_topn(packed[:b], n)

    def topn_packed_device(self, user_rows: np.ndarray, n: int) -> jax.Array:
        """Device-resident top-N: upload query rows, run the matmul+top_k,
        return the packed result buffer WITHOUT fetching it to host. Lets
        latency instrumentation separate compute from the device->host hop.

        The row dimension is padded to the next power of two (min 8) so a
        serving workload with varying batch sizes compiles O(log max_batch)
        executables instead of one per distinct size — a cold compile costs
        seconds, which under concurrent load turns the micro-batching
        executor into a compile queue. Callers slice the padding off.
        """
        from predictionio_tpu.ops.similarity import pad_rows_pow2

        with _tracing.stage(_tracing.HOST_PREP):
            q = pad_rows_pow2(user_rows, 8)
        # executable-cache accounting for the serving top-k ladder: the
        # jit cache is keyed by (padded batch, catalog shape, n); a new
        # key is a compile — cold if it lands inside a serving batch
        exec_key = (
            q.shape, self._if_dev.shape, n, self.mesh is None,
        )
        # dispatch: the query rows' upload (its own stage inside) and
        # the program's call returning (asynchronous: the device may
        # still be running)
        with _tracing.stage(_tracing.DISPATCH):
            if self.mesh is None:
                with _tracing.stage(_tracing.UPLOAD):
                    q_dev = jax.device_put(q)
                with _cc.track_compile("serving-topk", _TOPK_SEEN, exec_key):
                    return _topn_packed(q_dev, self._if_dev, n)
            # shard_batch further pads so the batch divides the mesh axis
            # (a no-op for power-of-two axes), then places row-sharded
            from predictionio_tpu.parallel.mesh import shard_batch

            with _tracing.stage(_tracing.UPLOAD):
                q_dev, _ = shard_batch(self.mesh, q, self._axis)
            with _cc.track_compile("serving-topk", _TOPK_SEEN, exec_key):
                return _topn_packed_sharded(
                    q_dev, self._if_dev, n,
                    NamedSharding(self.mesh, P(self._axis)),
                )

    def warm(self, n: int = 16, max_batch: int = 128) -> None:
        """Compile every padded-batch-size executable the serving path can
        hit (deploy-time warm-up; see BaseAlgorithm.warm). With row
        padding to powers of two this is O(log max_batch) compiles."""
        k = self._uf_dev.shape[1]
        n = min(n, self.n_items)
        b = 8
        while True:
            self.topn_by_rows(np.zeros((b, k), np.float32), n)
            if b >= max_batch:
                break
            b *= 2

    def measure_compute_ms(
        self, user_rows: np.ndarray, n: int, iters: int = 256, reps: int = 5
    ) -> float:
        """Amortized per-call device compute time of the top-N op: a
        chained on-device loop of `iters` passes in one dispatch, so the
        dispatch+fetch overhead contributes once and cancels in
        (t(iters) - t(1)) / (iters - 1)."""
        import time as _time

        if self.mesh is None:
            q = jax.device_put(np.asarray(user_rows, np.float32))
        else:
            # match the serving placement (row-sharded over the mesh) so
            # the chain's operands live on compatible device sets and the
            # measurement times the sharded executable serving actually runs
            from predictionio_tpu.parallel.mesh import shard_batch

            q, _ = shard_batch(
                self.mesh, np.asarray(user_rows, np.float32), self._axis
            )

        def chain(k):
            return _topn_packed_chain(q, self._if_dev, n, jnp.int32(k))

        chain(1).block_until_ready()  # compile (trip count is dynamic)
        samples = []
        for _ in range(reps):
            t0 = _time.perf_counter()
            chain(1).block_until_ready()
            t1 = _time.perf_counter() - t0
            t0 = _time.perf_counter()
            chain(iters).block_until_ready()
            tk = _time.perf_counter() - t0
            samples.append((tk - t1) / (iters - 1) * 1000.0)
        return float(np.median(samples))

    def topn_by_user(self, user_ids: Sequence[int], n: int):
        """Top-N for known user indices (gathers rows host-side; the row
        count is tiny relative to the item matmul)."""
        with _tracing.stage(_tracing.HOST_PREP):
            rows = self.user_factors[np.asarray(user_ids, np.int64)]
        return self.topn_by_rows(rows, n)


def recommend_batch(
    query_factors: np.ndarray, item_factors: np.ndarray, n: int
) -> Tuple[np.ndarray, np.ndarray]:
    """One-shot top-N (transfers factors each call — use ServingFactors on
    the serving path). Returns (scores [B, n], item indices [B, n])."""
    return unpack_topn(
        _topn_packed(
            jax.device_put(np.asarray(query_factors, np.float32)),
            jax.device_put(np.asarray(item_factors, np.float32)),
            n,
        ),
        n,
    )
