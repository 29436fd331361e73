"""Cosine-similarity scoring over factor matrices.

The kernel behind the similarproduct template (reference
examples/scala-parallel-similarproduct/multi/src/main/scala/
ALSAlgorithm.scala predict: per-candidate ``sum over query items of
cosine(queryFactor, candidateFactor)``, computed there as an RDD
mapValues over every product). Here the factor matrix is L2-normalized
once at model build, so a whole query batch scores as ONE [Q, k] x [k, N]
MXU matmul summed over the query axis.

Multi-chip: with a ``mesh``, the [N, k] candidate matrix shards rows over
the mesh's data axis (the catalog is the big operand); the small query
block replicates, each device scores its candidate shard locally, and the
[N] score vector comes back row-sharded — no collective on the hot path.
This is the TPU analog of the reference scoring candidates with an RDD
mapValues over cluster partitions.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from predictionio_tpu.parallel.mesh import shard_batch


def pow2_at_least(n: int, floor: int = 1) -> int:
    """Next power of two >= n (and >= floor) — THE serving bucketing
    rule (cosine-sum rows, ALS top-N batches, retrieval top-k and
    id-list widths), centralized so executables bucket identically
    everywhere and the rule can't drift."""
    return max(floor, 1 << (max(n, 1) - 1).bit_length())


def pad_rows_pow2(rows: np.ndarray, min_rows: int) -> np.ndarray:
    """Pad the leading axis with zero rows to the next power of two
    (>= min_rows), so executables bucket by O(log) widths instead of one
    per distinct size. Shared by the cosine-sum path here and the ALS
    serving top-N (ops/als.py) so the bucketing rule can't drift."""
    rows = np.asarray(rows, np.float32)
    n = rows.shape[0]
    n_pad = pow2_at_least(n, min_rows)
    if n_pad == n:
        return rows
    return np.concatenate(
        [rows, np.zeros((n_pad - n, rows.shape[1]), np.float32)]
    )


def normalize_rows(factors: np.ndarray) -> np.ndarray:
    """L2-normalize rows; zero rows stay zero (cosine with a zero vector
    is 0 in the reference's cosine helper)."""
    f = np.asarray(factors, np.float32)
    norms = np.linalg.norm(f, axis=1, keepdims=True)
    return np.where(norms > 0, f / np.where(norms == 0, 1, norms), 0.0)


@jax.jit
def _cosine_sum(query_normed, all_normed):
    # [Q, k] x [k, N] -> sum over Q -> [N]. ``highest``: a TPU's default
    # matmul precision rounds float32 operands to bfloat16, and these
    # cosines have to order items as a float32 host reference does (the
    # fault PR 21 found in the recommendation path)
    sims = jnp.dot(
        query_normed, all_normed.T, preferred_element_type=jnp.float32,
        precision="highest",
    )
    return sims.sum(axis=0)


def _device_bytes_limit(device) -> Optional[int]:
    """What the device says it can hold (None where the backend reports
    nothing, as the CPU's does)."""
    stats = device.memory_stats() or {}
    return stats.get("bytes_limit")


class SimilarityScorer:
    """Device-resident normalized factors; each call ships only the query
    rows up and one score vector down.

    With a ``mesh``, the candidate matrix is row-sharded over the mesh's
    ``axis`` (zero-padded so rows divide the axis size — zero rows score
    cosine 0 and are sliced off the result)."""

    def __init__(
        self,
        factors: np.ndarray,
        mesh: Optional[Mesh] = None,
        axis: str = "data",
    ):
        if mesh is not None and mesh.shape[axis] == 1:
            mesh = None
        self.mesh = mesh
        devices = (
            list(mesh.devices.flat) if mesh is not None else jax.devices()[:1]
        )
        need = int(np.prod(np.shape(factors))) * 4 // len(devices)
        limit = _device_bytes_limit(devices[0])
        if limit is not None and need > limit:
            raise ValueError(
                f"SimilarityScorer keeps the normalized table in float32 "
                f"on the device: {need:,} B a device, which holds "
                f"{limit:,} B. Serve a table of this size through "
                "ItemRetriever with precision=\"int8\" or \"bf16\" (the "
                "engines' `precision` param) instead"
            )
        self.normed = normalize_rows(factors)
        if mesh is None:
            self._dev = jax.device_put(jnp.asarray(self.normed))
        else:
            self._dev, _ = shard_batch(mesh, self.normed, axis)
        # HBM residency ledger: released by refcount (no explicit free
        # path), so the anchor finalizer is the close
        from predictionio_tpu.utils import device_ledger as _ledger

        label, nbytes, members = _ledger.device_footprint(self._dev)
        self._ledger = _ledger.get_ledger().register(
            component="similarity-factors",
            nbytes=nbytes,
            device=label,
            anchor=self,
            members=members,
        )

    @property
    def n(self) -> int:
        return self.normed.shape[0]

    def cosine_sum(self, query_rows: np.ndarray) -> np.ndarray:
        """Sum of cosine similarities of every row of the matrix against
        the (already-normalized) query rows: [N] scores.

        The query axis pads to a power of two (min 4) with zero rows —
        a zero row contributes cosine 0 to every sum, so results are
        unchanged while serving workloads with varying query-item counts
        share O(log max_q) compiled executables instead of one per
        distinct count (a cold compile on live traffic costs seconds)."""
        q = pad_rows_pow2(np.atleast_2d(query_rows), 4)
        if self.mesh is not None:
            q_dev = jax.device_put(q, NamedSharding(self.mesh, P(None, None)))
        else:
            q_dev = jnp.asarray(q)
        return np.asarray(_cosine_sum(q_dev, self._dev))[: self.n]

    def warm(self, max_q: int = 16) -> None:
        """Compile every padded-query-width executable a query of up to
        ``max_q`` items can hit — including the bucket a non-power-of-two
        max_q pads INTO (deploy-time warm-up; see BaseAlgorithm.warm).
        Routes through ``cosine_sum`` so the warmed executables carry the
        SAME input shardings serving traffic will present (a direct
        `_cosine_sum` call with an uncommitted query would warm a
        different jit cache entry on mesh-backed scorers)."""
        k = self.normed.shape[1]
        q = 4
        while True:
            self.cosine_sum(np.zeros((q, k), np.float32))
            if q >= max_q:
                break
            q *= 2
