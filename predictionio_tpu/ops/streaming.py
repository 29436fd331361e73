"""Streaming store→device ALS training pipeline.

Replaces the materialize-everything-then-train path for event-store
training with a chunked pipeline in which the serial phase chain of the
monolithic path — store scan, host pack, host→device transfer, XLA
compile — overlaps:

- the store scan (``data.store.PEventStore.stream_columns``) runs on a
  background thread, pushing fixed-size columnar batches through a
  BOUNDED queue;
- each batch is folded into incremental pack state (dense per-side row
  ids, per-row observation counts, a per-batch stable presort by user)
  while the scan of the next batch is still running;
- the moment the scan ends, bucket geometry is known and the iteration
  executable starts compiling on its own thread
  (``als.start_compile_async``), hiding XLA compile under the remaining
  host work;
- the presorted batches merge into the final :class:`als.HostWire` with
  one vectorized counting-sort scatter (no global 20M-element argsort on
  the critical path — the per-batch sorts already happened under the
  scan);
- the wire ships with chunked, double-buffered async ``device_put``:
  transfer of chunk k+1 overlaps the device-side nibble unpack of chunk
  k, and factor-state placement overlaps both.

This is the shape of ALX's pre-bucketed TPU input pipeline
(PAPERS.md — arXiv:2112.02194) and of the GPU MF literature's
transfer/compute overlap (arXiv:1603.03820), applied to the event-store
flagship flow. The wire produced here is byte-identical to the
monolithic ``als.build_host_wire`` output for the same scan, so the
device program — and the trained factors — match the monolithic path.

A process-global **pack-artifact cache** keyed by the store's cheap
state fingerprint (``LEvents.store_fingerprint``: event counts, max
ids/times, tombstone populations) makes a repeat train over an
unchanged store skip scan+pack entirely: the cached wire goes straight
to device. The fingerprint is read BEFORE the scan starts, so an entry
can only ever be labeled with a state at least as old as its data — a
write racing the scan makes the next lookup miss, never hit stale. The
producing DAO is held by weakref and compared by identity, so a
different storage universe (or a GC'd-and-reused object address) can
never satisfy a lookup.
"""

from __future__ import annotations

import dataclasses
import logging
import queue as _queue
import threading
import time
import weakref
from collections import OrderedDict
from typing import Optional, Tuple

import numpy as np

from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.ops import als as _als

logger = logging.getLogger(__name__)


# --- pack-artifact cache ---


@dataclasses.dataclass
class _PackEntry:
    scope_ref: "weakref.ref"  # the producing events DAO, by identity
    fingerprint: tuple  # store state the wire was packed from
    wire: "_als.HostWire"
    user_index: BiMap
    item_index: BiMap
    # --- delta-fold state (round 9): a pack entry IS the foldable
    # checkpoint — the cached wire losslessly inverts to the old COO
    # (als.wire_coo), the cursor says which store prefix it covers, and
    # the trained factors seed the next round's warm start. No extra
    # event-sized buffers beyond the wire the cache already held.
    cursor: Optional[tuple] = None  # storage delta cursor, None: no delta
    arrays: Optional["_als.ALSModelArrays"] = None  # factors of this wire
    # HBM residency ledger entry (device="host": cached wires are host
    # RAM, but they are long-lived residency the capacity view must see)
    ledger: Optional[object] = None
    # device-resident arm (round 17): when set, the wire's COO planes +
    # factor state live in HBM under this handle and ``wire`` is the
    # STRIPPED metadata shell (wire.stripped) — delta rounds scatter
    # onto the resident buffers instead of re-shipping the store
    resident: Optional["ResidentPack"] = None

    def resident_bytes(self) -> int:
        wire = self.wire
        total = (
            wire.iw.nbytes
            + wire.vw.nbytes
            + wire.counts_u.nbytes
            + wire.counts_i.nbytes
            + sum(int(a.nbytes) for a in wire.aux.values())
        )
        if self.arrays is not None:
            total += (
                self.arrays.user_factors.nbytes
                + self.arrays.item_factors.nbytes
            )
        return int(total)


_PACK_CACHE: "OrderedDict[tuple, _PackEntry]" = OrderedDict()
_PACK_CACHE_LOCK = threading.Lock()
# wires are ~50 MB at ML-20M scale; a small LRU covers the retrain and
# warm-bench cases without growing with app count
PACK_CACHE_MAX_ENTRIES = 4


def _cache_counter():
    """The registry family behind the hit/miss/fold counters — one
    ``pio_pack_cache_total{outcome=...}`` counter per outcome, visible
    in every server's /metrics, not just the PhaseTimer text summary."""
    from predictionio_tpu.utils import metrics as _metrics

    return _metrics.get_registry().counter(
        "pio_pack_cache_total",
        "Pack-artifact cache lookups by outcome (hit/miss/fold)",
        labels=("outcome",),
    )


def pack_cache_clear() -> None:
    """Drop every cached wire AND its cursor-keyed fold state (the
    delta-training checkpoint rides in the same entry), and reset the
    hit/miss/fold counters."""
    with _PACK_CACHE_LOCK:
        evicted = list(_PACK_CACHE.values())
        _PACK_CACHE.clear()
    for entry in evicted:
        _release_resident(entry)
        if entry.ledger is not None:
            entry.ledger.close()
    _cache_counter().reset()


def pack_cache_stats() -> dict:
    """Lifetime {'hit', 'miss', 'fold'} counters (reset by
    pack_cache_clear), read from the metrics registry."""
    c = _cache_counter()
    return {
        k: int(c.labels(outcome=k).value) for k in ("hit", "miss", "fold")
    }


def _stat_bump(kind: str) -> None:
    _cache_counter().labels(outcome=kind).inc()


def _cache_key(stream, config) -> Optional[tuple]:
    # the wire depends on config only through its pack geometry knobs
    if (
        stream.cache_key is None
        or stream.cache_scope is None
        or stream.fingerprint is None
    ):
        return None
    return (stream.cache_key, config.segment_length, config.chunk_slots)


def _cache_lookup(stream, config, any_fingerprint: bool):
    key = _cache_key(stream, config)
    if key is None:
        return None
    with _PACK_CACHE_LOCK:
        entry = _PACK_CACHE.get(key)
        if entry is None:
            return None
        # identity, not id(): the weakref keeps a dead DAO's entry from
        # ever matching a new object that reused its address
        if entry.scope_ref() is not stream.cache_scope:
            return None
        if not any_fingerprint and entry.fingerprint != stream.fingerprint:
            return None
        _PACK_CACHE.move_to_end(key)
        return entry


def _cache_get(stream, config) -> Optional[_PackEntry]:
    """Exact-state lookup: same DAO identity AND same fingerprint."""
    return _cache_lookup(stream, config, any_fingerprint=False)


def _cache_get_foldable(stream, config) -> Optional[_PackEntry]:
    """Stale-state lookup for the delta fold: same key and DAO identity,
    fingerprint MOVED (the exact-match path already missed), and the
    entry carries a cursor to scan the delta from."""
    entry = _cache_lookup(stream, config, any_fingerprint=True)
    if entry is None or entry.cursor is None:
        return None
    return entry


def _cache_put(
    stream, config, wire, user_index, item_index,
    fingerprint=None, cursor=None,
) -> Optional[_PackEntry]:
    key = _cache_key(stream, config)
    if key is None:
        return None
    try:
        ref = weakref.ref(stream.cache_scope)
    except TypeError:  # unweakrefable DAO: no caching
        return None
    entry = _PackEntry(
        ref,
        stream.fingerprint if fingerprint is None else fingerprint,
        wire, user_index, item_index, cursor=cursor,
    )
    from predictionio_tpu.utils import device_ledger as _ledger

    entry.ledger = _ledger.get_ledger().register(
        component="pack-cache",
        nbytes=entry.resident_bytes(),
        device=_ledger.HOST_DEVICE,
        anchor=entry,
    )
    evicted = []
    with _PACK_CACHE_LOCK:
        displaced = _PACK_CACHE.pop(key, None)
        if displaced is not None:
            evicted.append(displaced)
        _PACK_CACHE[key] = entry
        while len(_PACK_CACHE) > PACK_CACHE_MAX_ENTRIES:
            evicted.append(_PACK_CACHE.popitem(last=False)[1])
    for old in evicted:
        _release_resident(old)
        if old.ledger is not None:
            old.ledger.close()
    return entry


# --- device-resident pack (round 17) ---
#
# ALX keeps factor and rating state resident on the accelerator between
# solve rounds and moves only what changed (PAPERS.md, arXiv:2112.02194).
# Here that means: after a full round ships the wire, the device copies
# of the COO planes, the CSR/segment-geometry offsets, and the trained
# factor slots PARK in HBM under a ResidentPack handle (registered in
# the device ledger's ``train-pack`` component, so retention is measured
# and leak-gated). The next delta round then computes its id resolution
# and scatter bookkeeping on host (delta-sized) and applies ONE on-device
# scatter into the resident planes — nothing store-sized crosses the
# host→device link, converting round cost from O(store) to O(delta).
#
# The device arm is an optimization of the host fold, never a semantic
# fork: any condition it cannot scatter through — segment-geometry
# buckets grew, a row crossed a segment boundary, unseen ids arrived,
# the value tier or id dtype would change, the device/mesh changed, or
# the cursor invalidated — demotes the pack (device_get restores the
# byte-identical host wire) and takes the existing host fold. Packs
# release on continuous-loop shutdown, on fallback, and on cache
# eviction; ``pio_resident_pack_bytes`` must read zero afterwards.

_RESIDENT_ENABLED = False


def resident_training_enabled() -> bool:
    return _RESIDENT_ENABLED


def set_resident_training(enabled: bool) -> bool:
    """Toggle the device-resident incremental-pack arm (default OFF —
    batch trains gain nothing from parking state in HBM; the continuous
    loop turns it on for its lifetime). Returns the previous setting."""
    global _RESIDENT_ENABLED
    with _PACK_CACHE_LOCK:
        prev = _RESIDENT_ENABLED
        _RESIDENT_ENABLED = bool(enabled)
    return prev


def _resident_bytes_gauge():
    from predictionio_tpu.utils import metrics as _metrics

    return _metrics.get_registry().gauge(
        "pio_resident_pack_bytes",
        "Bytes of training-pack state (COO planes, segment geometry, "
        "factor slots) parked device-resident between continuous rounds",
        labels=("device",),
    )


def _resident_rounds_counter():
    from predictionio_tpu.utils import metrics as _metrics

    return _metrics.get_registry().counter(
        "pio_resident_pack_rounds_total",
        "Streaming train rounds by resident-pack outcome: scatter "
        "(delta applied on device), fallback (pack demoted to the host "
        "fold), cold (no pack involved)",
        labels=("outcome",),
    )


def _delta_upload_gauge():
    from predictionio_tpu.utils import metrics as _metrics

    return _metrics.get_registry().gauge(
        "pio_train_delta_upload_bytes",
        "Host→device bytes the last streaming train round uploaded "
        "(resident scatter rounds: delta rows + touched regularizer "
        "entries only; full rounds: the whole wire + factor state)",
    )


def _refresh_resident_gauge(device_label: str) -> None:
    from predictionio_tpu.utils import device_ledger as _ledger

    _resident_bytes_gauge().labels(device=device_label).set(
        float(
            _ledger.get_ledger().total_bytes(
                component="train-pack", device=device_label
            )
        )
    )


@dataclasses.dataclass
class ResidentPack:
    """The device-resident arm of one :class:`_PackEntry`: the wire's
    COO planes, CSR/segment-geometry offsets, and the trained factor
    state, all as device arrays. The paired entry's ``wire`` is stripped
    to its metadata shell while a pack is live; ``_reconstruct_wire``
    restores the byte-identical host wire from these buffers."""

    # wire planes: item ids (uint16|int32) and value codes (int8 decoded
    # from nibbles, or float32), both length plane_len, user-sorted
    i_plane: object
    v_plane: object
    # aux CSR offsets / segment bases (aux_pad'd int32 device copies)
    su: object
    bu: object
    si: object
    bi: object
    # flat segment-geometry arrays (int32): the per-round device pack
    # consumes these instead of re-uploading geo.seg_rows/geo.rem
    seg_rows_u: object
    rem_u: object
    seg_rows_i: object
    rem_i: object
    # padded factor slots (the fused loop's donated X/Y round-trip back
    # here after every round) + the non-donated lam/obs vectors
    X: object
    Y: object
    user_lam: object
    item_lam: object
    user_obs: object
    item_obs: object
    # host-side metadata
    device: object  # jax device the buffers live on (identity-compared)
    device_label: str
    plane_len: int  # bucketed COO length of the planes
    n: int  # real (unpadded) observation count
    v_lo: int  # min/max of the REAL int8 value codes (nibble recompute)
    v_hi: int
    config_key: tuple  # _als.config_train_key(...) the factor state matches
    ledger: object = None  # train-pack LedgerHandle
    valid: bool = True

    _ARRAY_FIELDS = (
        "i_plane", "v_plane", "su", "bu", "si", "bi",
        "seg_rows_u", "rem_u", "seg_rows_i", "rem_i",
        "X", "Y", "user_lam", "item_lam", "user_obs", "item_obs",
    )

    def device_arrays(self) -> list:
        return [
            a
            for a in (getattr(self, f) for f in self._ARRAY_FIELDS)
            if a is not None
        ]

    def device_bytes(self) -> int:
        return int(sum(int(a.nbytes) for a in self.device_arrays()))

    def release(self) -> None:
        """Close the ledger entry and drop every device reference
        (idempotent; the buffers free by refcount once training's own
        references go)."""
        self.valid = False
        if self.ledger is not None and not self.ledger.closed:
            self.ledger.close()
        for f in self._ARRAY_FIELDS:
            setattr(self, f, None)
        _refresh_resident_gauge(self.device_label)


def _release_resident(entry: _PackEntry) -> None:
    """Release an entry's device pack WITHOUT restoring the host wire —
    only for entries being discarded (eviction, cache clear)."""
    pack = entry.resident
    if pack is None:
        return
    entry.resident = None
    pack.release()


def _reconstruct_wire(entry: _PackEntry) -> "_als.HostWire":
    """The full host wire of a resident entry, rebuilt byte-identically
    from the device planes (every device copy is an exact integer image
    of the host plane it replaced) and the retained geometry."""
    meta = entry.wire
    if not meta.stripped:
        return meta
    import jax

    pack = entry.resident
    i_host = np.asarray(jax.device_get(pack.i_plane))
    v_host = np.asarray(jax.device_get(pack.v_plane))
    vw = _als._pack_nibbles_host(v_host) if meta.nibble else v_host
    aux = {
        "su": _als.aux_pad(meta.geo_u.starts.astype(np.int32)),
        "bu": _als.aux_pad(meta.geo_u.seg_base.astype(np.int32)),
        "si": _als.aux_pad(meta.geo_i.starts.astype(np.int32)),
        "bi": _als.aux_pad(meta.geo_i.seg_base.astype(np.int32)),
    }
    return dataclasses.replace(
        meta, iw=i_host, vw=vw, aux=aux, stripped=False
    )


def _demote_resident(entry: _PackEntry) -> None:
    """Fallback-to-host: restore the entry's full host wire from the
    device planes, then release the pack (train-pack ledger → 0). The
    entry stays a valid host-fold checkpoint."""
    if entry.resident is None:
        return
    restored = _reconstruct_wire(entry)
    with _PACK_CACHE_LOCK:
        entry.wire = restored
    _release_resident(entry)
    if entry.ledger is not None and not entry.ledger.closed:
        entry.ledger.set(entry.resident_bytes())


def release_resident_packs() -> int:
    """Demote every cached entry's device-resident pack back to its
    host wire — continuous-loop shutdown and promotion handoff call
    this so the ``train-pack`` ledger reads zero afterwards. Returns
    the number of packs released."""
    with _PACK_CACHE_LOCK:
        entries = list(_PACK_CACHE.values())
    released = 0
    for entry in entries:
        if entry.resident is not None:
            _demote_resident(entry)
            released += 1
    return released


def _resident_usable(pack: Optional[ResidentPack]) -> bool:
    """A pack is only reusable on the device that owns its buffers —
    a backend/mesh change between rounds demotes instead."""
    if pack is None or not pack.valid or pack.i_plane is None:
        return False
    import jax

    return jax.devices()[0] is pack.device


def _resolve_existing(codes, names_arr, index: BiMap):
    """Resolve delta codes (the delta stream's shared code space) to
    the cached side's EXISTING dense ids. Returns None when any name is
    unseen — the resident scatter cannot grow a side's id space (a new
    id reshuffles the sorted-name relabel), so the caller falls back."""
    codes = np.asarray(codes, np.int64)
    if not len(codes):
        return codes
    uniq = np.unique(codes)
    lut = np.zeros(int(uniq[-1]) + 1, np.int64)
    names = np.asarray(names_arr)
    for c in uniq:
        dense = index.get(str(names[int(c)]))
        if dense is None:
            return None
        lut[int(c)] = dense
    return lut[codes]


def _establish_resident(
    entry: _PackEntry, wire, device_wire, factor_state, fs_out, config
) -> Optional[ResidentPack]:
    """Park a just-trained round's device state under a ResidentPack:
    the shipped planes/aux keep living in HBM, the geometry arrays the
    per-round device pack needs are placed once, and the fused loop's
    final X/Y slots (``fs_out``) carry the trained factors without ever
    re-crossing the link. The entry's host wire is then stripped to its
    metadata shell — the redundant host plane copy frees (satellite:
    the ``pack-cache`` host ledger entry shrinks accordingly)."""
    X, Y = fs_out.get("X"), fs_out.get("Y")
    if X is None or Y is None:
        return None
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.utils import device_ledger as _ledger

    i_dev, v_dev, aux_dev = device_wire
    if wire.nibble:
        codes = _als._unpack_nibbles_host(wire.vw)
        v_lo, v_hi = int(codes.min()), int(codes.max())
    elif wire.vw.dtype == np.int8:
        v_lo, v_hi = int(wire.vw.min()), int(wire.vw.max())
    else:
        v_lo = v_hi = 0
    # the long-lived device placements below are the reviewed resident
    # sites the device-residency lint allowlists (tests/test_lint.py):
    # every buffer registers in the train-pack ledger entry right after
    entry.resident = ResidentPack(
        i_plane=i_dev,
        v_plane=v_dev,
        su=jnp.asarray(aux_dev["su"]),
        bu=jnp.asarray(aux_dev["bu"]),
        si=jnp.asarray(aux_dev["si"]),
        bi=jnp.asarray(aux_dev["bi"]),
        seg_rows_u=jnp.asarray(wire.geo_u.seg_rows),
        rem_u=jnp.asarray(wire.geo_u.rem),
        seg_rows_i=jnp.asarray(wire.geo_i.seg_rows),
        rem_i=jnp.asarray(wire.geo_i.rem),
        X=X, Y=Y,
        user_lam=factor_state[2], item_lam=factor_state[3],
        user_obs=factor_state[4], item_obs=factor_state[5],
        device=jax.devices()[0],
        device_label=_ledger.device_label_of(i_dev),
        plane_len=int(i_dev.shape[0]),
        n=int(wire.counts_u.sum()),
        v_lo=v_lo, v_hi=v_hi,
        config_key=_als.config_train_key(config),
    )
    pack = entry.resident
    label, nbytes, members = _ledger.device_footprint(
        *pack.device_arrays()
    )
    pack.ledger = _ledger.get_ledger().register(
        component="train-pack",
        nbytes=nbytes,
        device=label,
        anchor=pack,
        members=members,
    )
    with _PACK_CACHE_LOCK:
        entry.wire = dataclasses.replace(
            wire, iw=wire.iw[:0], vw=wire.vw[:0], aux={}, stripped=True
        )
    if entry.ledger is not None and not entry.ledger.closed:
        entry.ledger.set(entry.resident_bytes())
    _refresh_resident_gauge(pack.device_label)
    return pack


# --- incremental pack state ---


class _SideCodes:
    """Dense per-side row ids over the stream's SHARED code space.

    The stream's batches carry codes from one table-global dictionary
    (users and items mixed); each solve side needs its own dense 0..n-1
    id space. Dense ids are assigned in first-appearance order as
    batches fold in, and the shared code of each dense id is kept so the
    stream's post-scan ``names`` array resolves dense ids to id strings.
    """

    def __init__(self):
        self._dense_of = np.full(1024, -1, np.int64)
        self._code_chunks = []
        self.n = 0

    def fold(self, codes: np.ndarray) -> np.ndarray:
        codes = np.asarray(codes)
        if not len(codes):
            return np.empty(0, np.int32)
        hi = int(codes.max()) + 1
        if hi > len(self._dense_of):
            grown = np.full(max(hi, 2 * len(self._dense_of)), -1, np.int64)
            grown[: len(self._dense_of)] = self._dense_of
            self._dense_of = grown
        dense = self._dense_of[codes]
        miss = dense < 0
        if miss.any():
            new_codes = codes[miss]
            uniq, first = np.unique(new_codes, return_index=True)
            uniq = uniq[np.argsort(first, kind="stable")]  # appearance order
            self._dense_of[uniq] = np.arange(
                self.n, self.n + len(uniq), dtype=np.int64
            )
            self._code_chunks.append(uniq)
            self.n += len(uniq)
            dense = self._dense_of[codes]
        return dense.astype(np.int32)

    def codes(self) -> np.ndarray:
        """Shared code of each dense id (dense-id order)."""
        if not self._code_chunks:
            return np.empty(0, np.int64)
        return np.concatenate(self._code_chunks)


def _grow_add(acc: np.ndarray, add: np.ndarray) -> np.ndarray:
    if len(add) > len(acc):
        grown = np.zeros(len(add), np.int64)
        grown[: len(acc)] = acc
        acc = grown
    acc[: len(add)] += add
    return acc


def _scatter_merge(
    batches, n, n_users, n_items, geo_u,
    remap_u=None, remap_i=None,
):
    """Counting-sort merge of user-presorted COO batches into the final
    sentinel-padded item/value planes. Each batch must be sorted by its
    user ids; ``remap_u``/``remap_i`` optionally relabel per-batch ids
    into the final dense spaces (the relabeling must be injective and,
    for the sort to survive it, monotone — both the provisional→sorted
    relabel of the full scan and the old→merged relabel of the delta
    fold are). Scattering batch b's run of user u right after the runs
    batches 0..b-1 wrote reproduces EXACTLY the stable global argsort of
    the monolithic packer: per user, batches in scan order, original
    order within."""
    pad = (_als._bucket_count(n) - n) if n else 1
    iw = np.full(n + pad, n_items, np.int32)  # padding -> sentinel id
    vw = np.zeros(n + pad, np.float32)
    heads = geo_u.starts[:-1].copy()  # [n_users] int64 write heads
    for u, i, v in batches:
        m = len(u)
        if not m:
            continue
        idx = np.arange(m, dtype=np.int64)
        newgrp = np.empty(m, bool)
        newgrp[0] = True
        np.not_equal(u[1:], u[:-1], out=newgrp[1:])
        first = np.maximum.accumulate(np.where(newgrp, idx, 0))
        u_f = remap_u[u] if remap_u is not None else u
        pos = heads[u_f] + (idx - first)
        iw[pos] = remap_i[i] if remap_i is not None else i
        vw[pos] = v
        heads += np.bincount(u_f, minlength=n_users)
    return iw, vw


def _scan_worker(stream, q: "_queue.Queue", box: dict) -> None:
    """Drive the store scan, pushing batches through the bounded queue.
    Runs the generator ON THIS THREAD (the sqlite backend reads through
    per-thread WAL snapshot connections, so the scan never contends with
    the consumer); resolves ``stream.names`` here too, since it is only
    valid after exhaustion."""
    busy = 0.0
    try:
        it = iter(stream)
        while True:
            t0 = time.perf_counter()
            try:
                batch = next(it)
            except StopIteration:
                break
            busy += time.perf_counter() - t0
            q.put(batch)
        t0 = time.perf_counter()
        box["names"] = stream.names
        box["cursor"] = getattr(stream, "cursor", None)
        busy += time.perf_counter() - t0
    except BaseException as e:
        box["error"] = e
    finally:
        box["scan_s"] = busy
        box["done_at"] = time.perf_counter()
        q.put(None)


def _scan_and_pack(stream, config, timings: dict, queue_batches: int):
    """Consume a ColumnarStream into a HostWire + id indexes, folding
    each batch while the scan of the next runs on the producer thread.

    Returns ``(wire, user_index, item_index, compile_wait, cursor)`` or
    None for an empty scan (callers fall back to the materialized path,
    whose sanity check owns the user-facing error)."""
    q: "_queue.Queue" = _queue.Queue(maxsize=max(1, queue_batches))
    box: dict = {}
    th = threading.Thread(
        target=_scan_worker, args=(stream, q, box),
        daemon=True, name="als-stream-scan",
    )
    th.start()

    uspace, ispace = _SideCodes(), _SideCodes()
    counts_u = np.zeros(0, np.int64)
    counts_i = np.zeros(0, np.int64)
    batches = []
    n = 0
    fold_busy = 0.0
    while True:
        batch = q.get()
        if batch is None:
            break
        e_codes, t_codes, values = batch
        t0 = time.perf_counter()
        u = uspace.fold(e_codes)
        i = ispace.fold(t_codes)
        # stable presort by user NOW, under the scan of the next batch;
        # the merge below then only scatters — no global argsort on the
        # exposed critical path
        order = np.argsort(u, kind="stable")
        u, i = u[order], i[order]
        v = np.asarray(values, np.float32)[order]
        counts_u = _grow_add(counts_u, np.bincount(u, minlength=uspace.n))
        counts_i = _grow_add(counts_i, np.bincount(i, minlength=ispace.n))
        batches.append((u, i, v))
        n += len(v)
        fold_busy += time.perf_counter() - t0
    th.join()
    if "error" in box:
        raise box["error"]
    timings["scan_s"] = box.get("scan_s", 0.0)
    timings["fold_s"] = fold_busy
    if n == 0:
        return None
    t_scan_done = box["done_at"]

    # Final dense ids relabel the provisional (first-appearance) ids
    # into SORTED-NAME order — the order every monolithic scan
    # (presence-bitmap page remap, np.unique concat, BiMap.string_int)
    # assigns — so the wire below is byte-identical to the monolithic
    # packer's and the trained factors match it exactly, not just up to
    # a row permutation. The relabeling is catalog-sized, not
    # event-sized.
    names = box["names"]
    u_names = names[uspace.codes()]
    i_names = names[ispace.codes()]
    n_users, n_items = uspace.n, ispace.n
    perm_u = np.argsort(u_names)
    perm_i = np.argsort(i_names)
    remap_u = np.empty(n_users, np.int32)
    remap_u[perm_u] = np.arange(n_users, dtype=np.int32)
    remap_i = np.empty(n_items, np.int32)
    remap_i[perm_i] = np.arange(n_items, dtype=np.int32)
    counts_u32 = np.zeros(n_users, np.int64)
    counts_u32[: len(counts_u)] = counts_u
    counts_u32 = counts_u32[perm_u].astype(np.int32)
    counts_i32 = np.zeros(n_items, np.int64)
    counts_i32[: len(counts_i)] = counts_i
    counts_i32 = counts_i32[perm_i].astype(np.int32)
    L_u = _als.auto_segment_length(
        None, n_users, config.segment_length, counts=counts_u32
    )
    L_i = _als.auto_segment_length(
        None, n_items, config.segment_length, counts=counts_i32
    )
    geo_u = _als._segment_geometry(
        counts_u32, n_users, L_u, 1, config.chunk_slots
    )
    geo_i = _als._segment_geometry(
        counts_i32, n_items, L_i, 1, config.chunk_slots
    )
    # geometry known: compile starts NOW, under merge+narrow+transfer
    compile_wait = _als.start_compile_async(
        n_users, n_items, geo_u, geo_i, L_u, L_i, config
    )

    # Counting-sort merge (shared helper). Each batch is presorted by
    # PROVISIONAL user id; the provisional→sorted relabel is injective,
    # so equal-user runs stay contiguous and the within-batch occurrence
    # rank computed from the provisional grouping is also the rank under
    # final ids.
    iw, vw = _scatter_merge(
        batches, n, n_users, n_items, geo_u,
        remap_u=remap_u, remap_i=remap_i,
    )
    batches.clear()

    wire = _als.finish_wire(
        iw, vw, n_users, n_items, L_u, L_i, geo_u, geo_i,
        counts_u32, counts_i32,
    )
    user_index = BiMap(
        {str(nm): j for j, nm in enumerate(u_names[perm_u])}
    )
    item_index = BiMap(
        {str(nm): j for j, nm in enumerate(i_names[perm_i])}
    )
    now = time.perf_counter()
    # exposed = the tail the scan could not hide: late folds + geometry
    # + merge + narrow/nibble + index build
    timings["pack_exposed_s"] = max(0.0, now - t_scan_done)
    timings["pack_s"] = fold_busy + timings["pack_exposed_s"]
    return wire, user_index, item_index, compile_wait, box.get("cursor")


# --- delta fold (round 9) ---
#
# Retrain cost proportional to the delta: the storage layer scans ONLY
# the rows committed after the cached entry's cursor
# (LEvents.stream_columns_delta); here the cached wire losslessly
# inverts back to the old user-major COO (als.wire_coo), the delta's ids
# merge into the old sorted-name spaces (a monotone relabel, so the old
# batch stays user-sorted), and ONE counting-sort scatter re-finishes
# the wire — O(total events) of vectorized host work, no store rescan,
# no per-batch argsorts. The result is byte-identical to a cold full
# scan of the grown store, because per user the folded sequence (old
# wire order, then delta in scan order) IS the cold scan's sequence —
# the storage layer's cursor validation guarantees nothing already
# folded was deleted, reordered, or resealed out from under us, and
# falls back to the full repack otherwise.


def _names_of(index: BiMap) -> np.ndarray:
    """A BiMap's keys as a sorted object-str array (BiMaps here are
    always built from sorted name arrays, so iteration order is sorted
    order)."""
    out = np.empty(len(index), object)
    out[:] = [str(k) for k in index]
    return out


def _merge_sorted_names(old_names: np.ndarray, add_names: np.ndarray):
    """Merge ``add_names`` (sorted, disjoint from ``old_names``) into
    the sorted ``old_names``. Returns ``(merged, old_to_new)`` where
    ``old_to_new`` is the (monotone) relabel of old dense ids."""
    if not len(add_names):
        return old_names, np.arange(len(old_names), dtype=np.int64)
    old_pos = (
        np.arange(len(old_names), dtype=np.int64)
        + np.searchsorted(add_names, old_names)
    )
    new_pos = (
        np.arange(len(add_names), dtype=np.int64)
        + np.searchsorted(old_names, add_names)
    )
    merged = np.empty(len(old_names) + len(add_names), object)
    merged[old_pos] = old_names
    merged[new_pos] = add_names
    return merged, old_pos


def _side_fold_codes(codes: np.ndarray, names_arr, old_names: np.ndarray):
    """Fold one side's delta codes (in the DELTA stream's shared code
    space) into the cached side's sorted-name space, extending it with
    unseen names. Delta-sized work only. Returns
    ``(merged_names, old_to_new, dense_codes)``."""
    if not len(codes):
        return (
            old_names,
            np.arange(len(old_names), dtype=np.int64),
            codes.astype(np.int64),
        )
    uniq = np.unique(codes)  # distinct delta codes, ascending
    uniq_names = np.empty(len(uniq), object)
    uniq_names[:] = [str(x) for x in np.asarray(names_arr)[uniq]]
    if len(old_names):
        pos = np.minimum(
            np.searchsorted(old_names, uniq_names), len(old_names) - 1
        )
        is_old = old_names[pos] == uniq_names
    else:
        is_old = np.zeros(len(uniq_names), bool)
    add = np.sort(uniq_names[~is_old])  # distinct by construction
    merged, old_to_new = _merge_sorted_names(old_names, add)
    lut = np.zeros(int(uniq[-1]) + 1, np.int64)
    lut[uniq] = np.searchsorted(merged, uniq_names)
    return merged, old_to_new, lut[np.asarray(codes, np.int64)]


def _scan_delta(dstream, timings: dict) -> Optional[dict]:
    """Consume a delta stream into flat code/value arrays (shared by
    the host fold and the resident scatter arm). Returns None when the
    stream cannot vouch for its own chain (no cursor) — the caller
    falls back to the full repack."""
    t0 = time.perf_counter()
    parts = []
    n_delta = 0
    for e, g, v in dstream:
        parts.append(
            (
                np.asarray(e, np.int64),
                np.asarray(g, np.int64),
                np.asarray(v, np.float32),
            )
        )
        n_delta += len(v)
    new_cursor = dstream.cursor
    if new_cursor is None:
        return None
    timings["delta_scan_s"] = time.perf_counter() - t0
    if parts:
        e_codes = np.concatenate([p[0] for p in parts])
        g_codes = np.concatenate([p[1] for p in parts])
        dv = np.concatenate([p[2] for p in parts])
        names_arr = dstream.names
    else:
        e_codes = g_codes = np.empty(0, np.int64)
        dv = np.empty(0, np.float32)
        names_arr = None
    return {
        "e_codes": e_codes,
        "g_codes": g_codes,
        "dv": dv,
        "names": names_arr,
        "cursor": new_cursor,
        "fingerprint": dstream.fingerprint,
        "n_delta": n_delta,
    }


def _fold_delta(entry: _PackEntry, dstream, config, timings: dict):
    """Fold a delta stream into a cached pack entry: re-finished wire,
    merged id indexes, warm-start factor seeds, and the chained cursor.
    Returns None when the delta stream cannot vouch for its own chain
    (no cursor) — the caller falls back to the full repack.

    With residency enabled and a device pack on the entry, the delta is
    first offered to the on-device scatter arm; any condition it cannot
    scatter through demotes the pack (restoring the byte-identical host
    wire) and the host fold runs unchanged."""
    scanned = _scan_delta(dstream, timings)
    if scanned is None:
        return None
    if _RESIDENT_ENABLED and entry.resident is not None:
        folded = _fold_delta_resident(entry, scanned, config, timings)
        if folded is not None:
            return folded
    if entry.resident is not None:
        _demote_resident(entry)
        timings["resident"] = "fallback"
    return _fold_delta_host(entry, scanned, config, timings)


def _fold_delta_host(
    entry: _PackEntry, scanned: dict, config, timings: dict
):
    """The host fold (round 9): invert the cached wire to COO, merge
    the delta in, re-finish. Needs the entry's FULL host wire — a
    resident entry is demoted before this runs."""
    n_delta = scanned["n_delta"]
    new_cursor = scanned["cursor"]
    t0 = time.perf_counter()
    old_u_names = _names_of(entry.user_index)
    old_i_names = _names_of(entry.item_index)
    e_codes = scanned["e_codes"]
    g_codes = scanned["g_codes"]
    dv = scanned["dv"]
    names_arr = scanned["names"]
    u_names, u_old2new, du = _side_fold_codes(
        e_codes, names_arr, old_u_names
    )
    i_names, i_old2new, di = _side_fold_codes(
        g_codes, names_arr, old_i_names
    )
    n_users, n_items = len(u_names), len(i_names)

    old_wire = entry.wire
    counts_u = np.zeros(n_users, np.int64)
    counts_u[u_old2new] = old_wire.counts_u
    counts_u += np.bincount(du, minlength=n_users)
    counts_i = np.zeros(n_items, np.int64)
    counts_i[i_old2new] = old_wire.counts_i
    counts_i += np.bincount(di, minlength=n_items)
    counts_u32 = counts_u.astype(np.int32)
    counts_i32 = counts_i.astype(np.int32)

    L_u = _als.auto_segment_length(
        None, n_users, config.segment_length, counts=counts_u32
    )
    L_i = _als.auto_segment_length(
        None, n_items, config.segment_length, counts=counts_i32
    )
    geo_u = _als._segment_geometry(
        counts_u32, n_users, L_u, 1, config.chunk_slots
    )
    geo_i = _als._segment_geometry(
        counts_i32, n_items, L_i, 1, config.chunk_slots
    )
    # geometry known: compile starts NOW, under the merge + transfer
    compile_wait = _als.start_compile_async(
        n_users, n_items, geo_u, geo_i, L_u, L_i, config
    )

    # old COO straight off the cached wire (user-major, original
    # per-user order — exactly the cold scan's prefix), relabeled by the
    # MONOTONE old→merged LUT so it stays user-sorted; the delta gets
    # its own stable presort, preserving scan order within each user
    ou, oi, ov = _als.wire_coo(old_wire)
    ou = u_old2new[ou].astype(np.int64)
    oi = i_old2new[oi]
    order = np.argsort(du, kind="stable")
    n = len(ov) + n_delta
    iw, vw = _scatter_merge(
        [(ou, oi, ov), (du[order], di[order], dv[order])],
        n, n_users, n_items, geo_u,
    )
    wire = _als.finish_wire(
        iw, vw, n_users, n_items, L_u, L_i, geo_u, geo_i,
        counts_u32, counts_i32,
    )
    user_index = BiMap({str(nm): j for j, nm in enumerate(u_names)})
    item_index = BiMap({str(nm): j for j, nm in enumerate(i_names)})

    warm = None
    k = config.rank
    if (
        entry.arrays is not None
        and entry.arrays.user_factors.shape == (old_wire.n_users, k)
        and entry.arrays.item_factors.shape == (old_wire.n_items, k)
    ):
        # previous factors carry over row-by-row; new users solve from
        # the item side on the first half-sweep, new items get the same
        # fresh nonnegative init a cold train would give them
        X0 = np.zeros((n_users, k), np.float32)
        X0[u_old2new] = entry.arrays.user_factors
        Y0 = np.ascontiguousarray(
            _als._factor_init_host(n_users, n_items, config, 1)[1][
                :n_items
            ]
        )
        Y0[i_old2new] = entry.arrays.item_factors
        warm = _als.ALSModelArrays(user_factors=X0, item_factors=Y0)

    timings["fold_exposed_s"] = time.perf_counter() - t0
    return {
        "wire": wire,
        "user_index": user_index,
        "item_index": item_index,
        "compile_wait": compile_wait,
        "cursor": new_cursor,
        "fingerprint": scanned["fingerprint"],
        "warm": warm,
        "delta_events": n_delta,
    }


def _fold_delta_resident(
    entry: _PackEntry, scanned: dict, config, timings: dict
) -> Optional[dict]:
    """The on-device scatter arm of the delta fold. Host work here is
    delta-sized (id resolution, sort, shift prefix-sums come from
    catalog-sized bincounts); the only host→device traffic is the delta
    rows themselves plus the touched regularizer entries. Returns None
    whenever the scatter cannot reproduce the cold wire byte-for-byte —
    the caller demotes the pack and takes the host fold.

    Fallback triggers, each checked against what a cold re-finish of
    the grown store would produce: an unseen user/item id (the
    sorted-name relabel would reshuffle old rows), a value outside the
    pack's int8 half-step tier, a changed auto segment length, a row
    crossing a segment boundary or the segment grid re-bucketing
    (seg_rows/chunk mismatch), an item-id plane dtype flip, a
    training-semantics change (any ``config_train_key`` component:
    rank/reg/reg_mode, an implicit flip, an alpha retune, a solver or
    block-size change — the parked factors were trained under different
    semantics and must not warm-start the new ones), and a device
    change (caught by ``_resident_usable`` upstream)."""
    pack = entry.resident
    if not _resident_usable(pack) or pack.X is None or pack.Y is None:
        return None
    if pack.config_key != _als.config_train_key(config):
        return None
    old = entry.wire
    names_arr = scanned["names"]
    du = _resolve_existing(scanned["e_codes"], names_arr, entry.user_index)
    if du is None:
        return None
    di = _resolve_existing(scanned["g_codes"], names_arr, entry.item_index)
    if di is None:
        return None
    t0 = time.perf_counter()
    d = int(scanned["n_delta"])
    dv = scanned["dv"]
    n_users, n_items = old.n_users, old.n_items

    # value-tier stability: the merged plane must stay on the pack's
    # tier or the cold wire's value dtype would differ
    if old.v_scale == 0.5:
        doubled = dv * 2.0
        codes = np.rint(doubled)
        if d and (
            np.abs(doubled - codes).max() != 0.0
            or np.abs(codes).max() > 127
        ):
            return None
        d_codes = codes.astype(np.int8)
    else:
        d_codes = dv.astype(np.float32)

    counts_u = old.counts_u.astype(np.int64) + np.bincount(
        du, minlength=n_users
    )
    counts_i = old.counts_i.astype(np.int64) + np.bincount(
        di, minlength=n_items
    )
    counts_u32 = counts_u.astype(np.int32)
    counts_i32 = counts_i.astype(np.int32)
    n_new = pack.n + d
    L_u = _als.auto_segment_length(
        None, n_users, config.segment_length, counts=counts_u32
    )
    L_i = _als.auto_segment_length(
        None, n_items, config.segment_length, counts=counts_i32
    )
    if L_u != old.L_u or L_i != old.L_i:
        return None
    geo_u = _als._segment_geometry(
        counts_u32, n_users, L_u, 1, config.chunk_slots
    )
    geo_i = _als._segment_geometry(
        counts_i32, n_items, L_i, 1, config.chunk_slots
    )
    for g2, g1 in ((geo_u, old.geo_u), (geo_i, old.geo_i)):
        if (
            g2.n_chunks != g1.n_chunks
            or g2.sc != g1.sc
            or g2.total != g1.total
            or not np.array_equal(g2.seg_rows, g1.seg_rows)
        ):
            return None
    P_old = pack.plane_len
    P_new = _als._bucket_count(n_new)
    i_dtype = old.iw.dtype  # stripped planes keep their dtype
    top_id = n_items if P_new > n_new else n_items - 1
    if np.dtype(np.uint16 if top_id < 65536 else np.int32) != i_dtype:
        return None
    if d_codes.dtype == np.int8:
        v_lo = min(pack.v_lo, int(d_codes.min()) if d else pack.v_lo)
        v_hi = max(pack.v_hi, int(d_codes.max()) if d else pack.v_hi)
        nibble = P_new % 2 == 0 and v_lo >= 0 and v_hi <= 15
    else:
        v_lo = v_hi = 0
        nibble = False

    compile_wait = _als.start_compile_async(
        n_users, n_items, geo_u, geo_i, L_u, L_i, config
    )

    import jax
    import jax.numpy as jnp

    upload = 0
    weighted = config.reg_mode == "weighted"
    i3, v3 = pack.i_plane, pack.v_plane
    su2, si2 = pack.su, pack.si
    rem_u2, rem_i2 = pack.rem_u, pack.rem_i
    user_lam2, item_lam2 = pack.user_lam, pack.item_lam
    if d:
        order = np.argsort(du, kind="stable")
        du_s = du[order].astype(np.int32)
        di_s = di[order].astype(i_dtype)
        dc_s = d_codes[order]
        du_dev = jax.device_put(du_s)
        di_dev = jax.device_put(di_s)
        dv_dev = jax.device_put(dc_s)
        upload += du_s.nbytes + di_s.nbytes + dc_s.nbytes

        # per-row delta counts and their prefix shifts, on device from
        # the uploaded ids alone (+1 slot so padding rows gather 0)
        dense_u = jnp.zeros((n_users + 1,), jnp.int32).at[du_dev].add(1)
        dense_i = (
            jnp.zeros((n_items + 1,), jnp.int32)
            .at[di_dev.astype(jnp.int32)]
            .add(1)
        )
        sh_u = jnp.concatenate(
            [
                jnp.zeros((1,), jnp.int32),
                jnp.cumsum(dense_u[:n_users], dtype=jnp.int32),
            ]
        )
        sh_i = jnp.concatenate(
            [
                jnp.zeros((1,), jnp.int32),
                jnp.cumsum(dense_i[:n_items], dtype=jnp.int32),
            ]
        )

        # old planes → shifted slots: rebuild each slot's user key from
        # the resident CSR offsets (the _device_pack_presorted trick),
        # shift by how many delta rows land before that user, and move.
        # new_pos is strictly increasing; old padding slots carry
        # sentinel/zero and either rewrite identical values or drop.
        marks = (
            jnp.zeros((P_old + 1,), jnp.int32)
            .at[pack.su[1:]]
            .add(1, mode="drop")
        )
        keys = jnp.cumsum(marks[:P_old], dtype=jnp.int32)
        new_pos = jnp.arange(P_old, dtype=jnp.int32) + sh_u[keys]
        opts = dict(
            unique_indices=True, indices_are_sorted=True, mode="drop"
        )
        init_id = n_items if P_new > n_new else 0
        i2 = (
            jnp.full((P_new,), init_id, dtype=pack.i_plane.dtype)
            .at[new_pos]
            .set(pack.i_plane, **opts)
        )
        v2 = (
            jnp.zeros((P_new,), pack.v_plane.dtype)
            .at[new_pos]
            .set(pack.v_plane, **opts)
        )

        # delta rows append after each user's old run: occurrence rank
        # within the (user-sorted) delta + the user's new end offset
        idx = jnp.arange(d, dtype=jnp.int32)
        newgrp = jnp.concatenate(
            [jnp.ones((1,), bool), du_dev[1:] != du_dev[:-1]]
        )
        first = jax.lax.cummax(jnp.where(newgrp, idx, 0))
        d_pos = pack.su[du_dev + 1] + sh_u[du_dev] + (idx - first)
        i3 = i2.at[d_pos].set(di_dev, **opts)
        v3 = v2.at[d_pos].set(dv_dev, **opts)

        # CSR offsets shift by the per-user/item prefix counts (edge
        # padding rides the clip to the final total); segment bases are
        # unchanged (seg_rows equality above), and only each row's LAST
        # segment gains the row's delta count
        su2 = pack.su + sh_u[
            jnp.clip(
                jnp.arange(pack.su.shape[0], dtype=jnp.int32), 0, n_users
            )
        ]
        si2 = pack.si + sh_i[
            jnp.clip(
                jnp.arange(pack.si.shape[0], dtype=jnp.int32), 0, n_items
            )
        ]
        seg_idx_u = jnp.arange(pack.seg_rows_u.shape[0], dtype=jnp.int32)
        is_last_u = (seg_idx_u + 1) == pack.bu[pack.seg_rows_u + 1]
        rem_u2 = pack.rem_u + jnp.where(
            is_last_u, dense_u[pack.seg_rows_u], 0
        )
        seg_idx_i = jnp.arange(pack.seg_rows_i.shape[0], dtype=jnp.int32)
        is_last_i = (seg_idx_i + 1) == pack.bi[pack.seg_rows_i + 1]
        rem_i2 = pack.rem_i + jnp.where(
            is_last_i, dense_i[pack.seg_rows_i], 0
        )

        if weighted:
            # weighted regularization tracks counts: upload the
            # host-computed values at the touched rows (guaranteed
            # bit-equal to a cold _lam_obs_host; obs never changes —
            # touched rows already had observations)
            lam_u_full, _ = _als._lam_obs_host(
                counts_u32, n_users, pack.user_lam.shape[0], config
            )
            uniq_u = np.unique(du_s).astype(np.int32)
            vals_u = np.ascontiguousarray(lam_u_full[uniq_u])
            user_lam2 = pack.user_lam.at[jax.device_put(uniq_u)].set(
                jax.device_put(vals_u),
                unique_indices=True, indices_are_sorted=True,
            )
            lam_i_full, _ = _als._lam_obs_host(
                counts_i32, n_items, pack.item_lam.shape[0], config
            )
            uniq_i = np.unique(di_s.astype(np.int64)).astype(np.int32)
            vals_i = np.ascontiguousarray(lam_i_full[uniq_i])
            item_lam2 = pack.item_lam.at[jax.device_put(uniq_i)].set(
                jax.device_put(vals_i),
                unique_indices=True, indices_are_sorted=True,
            )
            upload += (
                uniq_u.nbytes + vals_u.nbytes
                + uniq_i.nbytes + vals_i.nbytes
            )

    new_meta = dataclasses.replace(
        old,
        geo_u=geo_u, geo_i=geo_i,
        counts_u=counts_u32, counts_i=counts_i32,
        iw=np.empty(0, i_dtype),
        vw=np.empty(0, np.uint8 if nibble else d_codes.dtype),
        nibble=nibble, aux={}, stripped=True,
    )
    pack.i_plane, pack.v_plane = i3, v3
    pack.su, pack.si = su2, si2
    pack.rem_u, pack.rem_i = rem_u2, rem_i2
    pack.user_lam, pack.item_lam = user_lam2, item_lam2
    pack.plane_len = P_new
    pack.n = n_new
    pack.v_lo, pack.v_hi = v_lo, v_hi
    if pack.ledger is not None and not pack.ledger.closed:
        pack.ledger.set(pack.device_bytes())
    _refresh_resident_gauge(pack.device_label)
    with _PACK_CACHE_LOCK:
        entry.wire = new_meta
        entry.fingerprint = scanned["fingerprint"]
        entry.cursor = scanned["cursor"]
    if entry.ledger is not None and not entry.ledger.closed:
        entry.ledger.set(entry.resident_bytes())

    timings["fold_exposed_s"] = time.perf_counter() - t0
    timings["resident"] = "scatter"
    timings["delta_upload_bytes"] = int(upload)
    return {
        "wire": new_meta,
        "user_index": entry.user_index,
        "item_index": entry.item_index,
        "compile_wait": compile_wait,
        "cursor": scanned["cursor"],
        "fingerprint": scanned["fingerprint"],
        "warm": None,
        "delta_events": d,
        "resident_pack": pack,
        "device_wire": (
            i3, v3, {"su": su2, "bu": pack.bu, "si": si2, "bi": pack.bi}
        ),
        "geo_dev": (pack.seg_rows_u, rem_u2, pack.seg_rows_i, rem_i2),
        "factor_state": (
            pack.X, pack.Y, user_lam2, item_lam2,
            pack.user_obs, pack.item_obs,
        ),
        "upload_bytes": int(upload),
    }


# --- transfer ---


def _ship_wire(wire: "_als.HostWire", n_chunks: int = 2) -> tuple:
    """Double-buffered wire transfer: the COO planes split into chunks
    whose async ``device_put``s pipeline, and each value chunk's
    device-side nibble unpack dispatches as soon as its bytes are
    enqueued — so transfer of chunk k+1 overlaps unpack of chunk k.
    Returns the ``(i_dev, v_dev, aux_dev)`` pre-shipped wire
    ``als.device_pack_from_wire`` consumes."""
    import jax
    import jax.numpy as jnp

    def parts(a: np.ndarray):
        if n_chunks <= 1 or len(a) < 2 * n_chunks:
            return [a]
        step = -(-len(a) // n_chunks)
        step += step % 2  # even boundary: value pairs stay byte-aligned
        return [a[s : s + step] for s in range(0, len(a), step)]

    dev_i = [jax.device_put(p) for p in parts(wire.iw)]
    dev_v = []
    for p in parts(wire.vw):
        d = jax.device_put(p)
        dev_v.append(_als._unpack_nibbles(d) if wire.nibble else d)
    i_dev = dev_i[0] if len(dev_i) == 1 else jnp.concatenate(dev_i)
    v_dev = dev_v[0] if len(dev_v) == 1 else jnp.concatenate(dev_v)
    aux_dev = jax.device_put(wire.aux)  # enqueued last: fences the queue
    return i_dev, v_dev, aux_dev


# --- the pipeline entry ---


@dataclasses.dataclass
class StreamTrainResult:
    arrays: "_als.ALSModelArrays"
    user_index: BiMap
    item_index: BiMap
    timings: dict


def _attribute_phases(timer, timings: dict) -> None:
    """Record the pipeline's sub-phases on the workflow PhaseTimer,
    marking the ones that ran UNDER another phase as overlapped so the
    run summary's wall-clock accounting stays honest."""
    add = getattr(timer, "add", None)
    if add is None:
        return
    for name, key, overlapped in (
        ("stream:scan", "scan_s", True),
        ("stream:fold", "fold_s", True),
        ("stream:delta-scan", "delta_scan_s", False),
        ("stream:delta-fold", "fold_exposed_s", False),
        ("stream:pack-exposed", "pack_exposed_s", False),
        ("stream:device-put-exposed", "device_put_exposed_s", False),
        ("stream:device-pack-dispatch", "device_pack_dispatch_s", False),
        ("stream:device-pack-exposed", "device_pack_exposed_s", False),
        ("stream:compile", "compile_s", True),
        ("stream:compile-exposed", "compile_exposed_s", False),
        ("stream:device-loop", "device_loop_s", False),
    ):
        if timings.get(key):
            add(name, timings[key], overlapped=overlapped)
    note = getattr(timer, "note", None)
    if note is None:
        return
    # the pack cache is not silent: this round's outcome, the lifetime
    # hit/miss/fold counters, and the delta size land in the summary
    if timings.get("pack_cache"):
        note("pack_cache", timings["pack_cache"])
    stats = pack_cache_stats()
    note(
        "pack_cache_stats",
        f"hit={stats['hit']} miss={stats['miss']} fold={stats['fold']}",
    )
    if "delta_events" in timings:
        note("delta_events", timings["delta_events"])
    if timings.get("resident"):
        # device-resident pack outcome (round 17): scatter / fallback /
        # cold — the continuous loop's RoundReport picks this up
        note("resident", timings["resident"])
    # convergence telemetry from the fused loop (ops/als.py): the sweep
    # count and the final factor-delta RMS are the round's convergence
    # headline; the full curve stays in timings["sweep_telemetry"] and
    # the registry histograms
    tel = timings.get("sweep_telemetry")
    if tel:
        note("sweeps", len(tel))
        note(
            "final_factor_delta",
            f"user={tel[-1]['dx']:.2e} item={tel[-1]['dy']:.2e}",
        )
        # implicit mode only: the HKV objective at the final sweep
        # (ops/als.py telemetry) — the training-loss headline the
        # continuous round line and RoundReport surface
        if "objective" in tel[-1]:
            note("objective", f"{tel[-1]['objective']:.6g}")


def train_als_streaming(
    stream,
    config: "_als.ALSConfig",
    *,
    timings: Optional[dict] = None,
    timer=None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 5,
    profile_dir: Optional[str] = None,
    queue_batches: int = 4,
    ship_chunks: int = 2,
    cache: bool = True,
    delta: bool = True,
    warm_sweeps: int = 2,
) -> Optional[StreamTrainResult]:
    """Train ALS from a ``ColumnarStream`` through the overlapped
    pipeline (module docstring). Returns None when ``stream`` is None or
    the scan is empty — callers fall back to the materialized
    ``train_als`` path and its error reporting.

    With ``delta`` (and ``cache``) on, a store that GREW since the
    cached round skips the full rescan: the delta fold (module comment
    above) re-finishes the cached wire from only the new rows, and
    training warm-starts from the previous round's factors with a
    ``warm_sweeps`` iteration budget (0 disables the reduced budget) —
    retrain cost proportional to the delta, not the store. Any change
    the storage cursor cannot vouch for (deletes, tombstones, bulk
    imports, resealing) falls back to the full repack automatically.

    ``timings`` gains the pipeline's phase split: ``scan_s``/``fold_s``/
    ``compile_s`` (busy, overlapped), ``pack_exposed_s``/
    ``device_put_exposed_s``/``compile_exposed_s`` (critical-path wall),
    ``pack_cache`` ("hit"/"miss"/"fold"/"off") with ``delta_events``/
    ``delta_scan_s``/``fold_exposed_s`` on fold rounds, plus the usual
    ``device_loop_s``/``padded_slots``/``wire_mb`` from the shared
    training tail.
    """
    if stream is None:
        return None
    timings = {} if timings is None else timings
    t_start = time.perf_counter()

    warm_arrays = None
    train_config = config
    cache_entry: Optional[_PackEntry] = None
    resident_round = False  # wire planes already live in HBM
    resident_pack: Optional[ResidentPack] = None
    resident_geo = None
    resident_wire_dev = None
    pre_factor_state = None  # scatter rounds: device-resident factors
    demoted = False  # a resident pack fell back to host this round
    entry = _cache_get(stream, config) if cache else None
    if entry is not None:
        _stat_bump("hit")
        timings["pack_cache"] = "hit"
        timings["scan_s"] = timings["fold_s"] = 0.0
        timings["pack_exposed_s"] = 0.0
        cache_entry = entry
        if entry.resident is not None:
            if _RESIDENT_ENABLED and _resident_usable(entry.resident):
                # zero-upload hit: planes + geometry stay resident; the
                # factor state is rebuilt fresh below, so the trained
                # result is the plain hit path's, bit for bit
                resident_round = True
                resident_pack = entry.resident
            else:
                _demote_resident(entry)
                demoted = True
        wire = entry.wire
        user_index, item_index = entry.user_index, entry.item_index
        compile_wait = _als.start_compile_async(
            wire.n_users, wire.n_items, wire.geo_u, wire.geo_i,
            wire.L_u, wire.L_i, config,
        )
        logger.info(
            "streaming ALS: pack cache HIT (%d users, %d items, %.1f MB "
            "wire%s) — skipping scan+pack", wire.n_users, wire.n_items,
            wire.wire_mb, ", device-resident" if resident_round else "",
        )
    else:
        folded = None
        prior = (
            _cache_lookup(stream, config, any_fingerprint=True)
            if cache
            else None
        )
        if delta and prior is not None and prior.cursor is not None:
            dfactory = getattr(stream, "delta_factory", None)
            if dfactory is not None:
                dstream = dfactory(prior.cursor)
                if dstream is not None:
                    folded = _fold_delta(prior, dstream, config, timings)
        if timings.get("resident") == "fallback":
            demoted = True
        if folded is not None:
            _stat_bump("fold")
            timings["pack_cache"] = "fold"
            timings["delta_events"] = folded["delta_events"]
            timings["scan_s"] = timings["fold_s"] = 0.0
            timings["pack_exposed_s"] = 0.0
            wire = folded["wire"]
            user_index = folded["user_index"]
            item_index = folded["item_index"]
            compile_wait = folded["compile_wait"]
            warm_arrays = folded["warm"]
            if "resident_pack" in folded:
                # the device arm already scattered the delta into the
                # resident planes and updated the entry in place — no
                # _cache_put (that would displace the entry and release
                # the very pack this round trains from)
                resident_round = True
                resident_pack = folded["resident_pack"]
                resident_wire_dev = folded["device_wire"]
                resident_geo = folded["geo_dev"]
                pre_factor_state = folded["factor_state"]
                cache_entry = prior
            else:
                cache_entry = _cache_put(
                    stream, config, wire, user_index, item_index,
                    fingerprint=folded["fingerprint"],
                    cursor=folded["cursor"],
                )
            if (
                (warm_arrays is not None or pre_factor_state is not None)
                and 0 < warm_sweeps < config.iterations
            ):
                # warm-started factors recover full quality in a few
                # sweeps after a small delta (ALX / GPU-MF, PAPERS.md);
                # the iteration count is a dynamic scalar, so the warm
                # executable is the cold one — no recompile
                train_config = dataclasses.replace(
                    config, iterations=warm_sweeps
                )
                timings["warm_sweeps"] = warm_sweeps
            logger.info(
                "streaming ALS: delta %s of %d events into cached "
                "wire (%d users, %d items) — skipping full rescan",
                "SCATTER" if resident_round else "FOLD",
                folded["delta_events"], wire.n_users, wire.n_items,
            )
        else:
            if prior is not None and prior.resident is not None:
                # the full repack replaces the entry: restore the host
                # wire and release the pack, so the train-pack ledger
                # reads zero on this fallback round even if the rescan
                # comes up empty
                _demote_resident(prior)
                demoted = True
            _stat_bump("miss" if cache else "off")
            timings["pack_cache"] = "miss" if cache else "off"
            packed = _scan_and_pack(stream, config, timings, queue_batches)
            if packed is None:
                return None
            wire, user_index, item_index, compile_wait, cursor = packed
            if cache:
                cache_entry = _cache_put(
                    stream, config, wire, user_index, item_index,
                    cursor=cursor,
                )

    from predictionio_tpu.utils import device_ledger as _ledger

    fs_out: Optional[dict] = (
        {}
        if (_RESIDENT_ENABLED and cache_entry is not None and not demoted)
        else None
    )
    staging = None
    if resident_round:
        # nothing store-sized crosses the link: planes, aux, and
        # geometry are already device-resident under the train-pack
        # ledger — no staging entry, no transfer fence
        pack = resident_pack
        if pre_factor_state is not None:
            device_wire = resident_wire_dev
            factor_state = pre_factor_state
        else:
            device_wire = (
                pack.i_plane, pack.v_plane,
                {"su": pack.su, "bu": pack.bu,
                 "si": pack.si, "bi": pack.bi},
            )
            resident_geo = (
                pack.seg_rows_u, pack.rem_u, pack.seg_rows_i, pack.rem_i
            )
            factor_state = _als.init_factor_state_single(
                wire.counts_u, wire.counts_i, wire.n_users, wire.n_items,
                train_config,
            )
            timings["delta_upload_bytes"] = int(
                factor_state[1].nbytes
                + sum(int(a.nbytes) for a in factor_state[2:])
            )
        timings["device_put_exposed_s"] = 0.0
    else:
        # ship (async) first, then factor-state init: the RNG + small
        # factor/regularizer puts run while the wire chunks are in flight
        device_wire = _ship_wire(wire, n_chunks=ship_chunks)
        # HBM residency ledger: the staged wire is device-resident from
        # ship until the device pack consumes it; the Anchor backstops an
        # exception path, the explicit close below the normal one
        _staging_anchor = _ledger.Anchor()
        _st_label, _st_bytes, _st_members = _ledger.device_footprint(
            device_wire[0], device_wire[1], *device_wire[2].values()
        )
        staging = _ledger.get_ledger().register(
            component="stream-staging",
            nbytes=_st_bytes,
            device=_st_label,
            anchor=_staging_anchor,
            members=_st_members,
        )
        factor_state = _als.init_factor_state_single(
            wire.counts_u, wire.counts_i, wire.n_users, wire.n_items,
            train_config,
            warm=(
                None
                if warm_arrays is None
                else (warm_arrays.user_factors, warm_arrays.item_factors)
            ),
        )
        timings["delta_upload_bytes"] = int(
            wire.iw.nbytes + wire.vw.nbytes
            + sum(int(a.nbytes) for a in wire.aux.values())
            + factor_state[1].nbytes
            + (factor_state[0].nbytes if warm_arrays is not None else 0)
            + sum(int(a.nbytes) for a in factor_state[2:])
        )
        import jax

        t0 = time.perf_counter()
        # waits out the transfers and the concat/unpack tail behind them
        jax.block_until_ready(device_wire)
        timings["device_put_exposed_s"] = time.perf_counter() - t0

    try:
        arrays = _als.train_from_wire(
            wire, train_config,
            device_wire=device_wire,
            timings=timings,
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every,
            profile_dir=profile_dir,
            compile_wait=compile_wait,
            factor_state=factor_state,
            geo_dev=resident_geo,
            factor_slots_out=fs_out,
            _fp_material=(
                (
                    lambda: repr(
                        (cache_entry.fingerprint, cache_entry.cursor)
                    ).encode()
                )
                if resident_round
                else None
            ),
        )
    except BaseException:
        if resident_round and cache_entry is not None:
            # the donated X/Y slots may be consumed mid-loop; the
            # planes are not — restore the host wire and release the
            # pack so a failed round never strands train-pack bytes
            if resident_pack is not None:
                resident_pack.X = resident_pack.Y = None
            if cache_entry.resident is not None:
                _demote_resident(cache_entry)
            with _PACK_CACHE_LOCK:
                cache_entry.arrays = None
        raise
    finally:
        if staging is not None:
            staging.close()
    if cache_entry is not None:
        # the trained factors ride the entry so the NEXT delta round can
        # warm-start; plain attribute store under the cache lock (the
        # entry may already have been evicted — harmless)
        with _PACK_CACHE_LOCK:
            cache_entry.arrays = arrays
        if cache_entry.ledger is not None and not cache_entry.ledger.closed:
            cache_entry.ledger.set(cache_entry.resident_bytes())
    if fs_out is not None and cache_entry is not None:
        if (
            resident_round
            and resident_pack is not None
            and resident_pack.valid
        ):
            if fs_out.get("X") is None or fs_out.get("Y") is None:
                # defensive: without the final slots the pack has no
                # factors for the next scatter — demote instead of
                # keeping consumed references alive
                resident_pack.X = resident_pack.Y = None
                _demote_resident(cache_entry)
            else:
                # the fused loop's final device X/Y round-trip back
                # into the pack (donation consumed the previous slots);
                # lam/obs follow so the next scatter reuses them
                resident_pack.X = fs_out["X"]
                resident_pack.Y = fs_out["Y"]
                resident_pack.user_lam = factor_state[2]
                resident_pack.item_lam = factor_state[3]
                resident_pack.user_obs = factor_state[4]
                resident_pack.item_obs = factor_state[5]
                resident_pack.config_key = _als.config_train_key(config)
                if (
                    resident_pack.ledger is not None
                    and not resident_pack.ledger.closed
                ):
                    resident_pack.ledger.set(resident_pack.device_bytes())
                _refresh_resident_gauge(resident_pack.device_label)
        elif (
            not resident_round
            and cache_entry.resident is None
            and not wire.stripped
        ):
            _establish_resident(
                cache_entry, wire, device_wire, factor_state, fs_out,
                config,
            )
    if _RESIDENT_ENABLED:
        outcome = timings.get("resident") or (
            "scatter" if resident_round
            else ("fallback" if demoted else "cold")
        )
        timings["resident"] = outcome
        _resident_rounds_counter().labels(outcome=outcome).inc()
    if "delta_upload_bytes" in timings:
        _delta_upload_gauge().set(float(timings["delta_upload_bytes"]))
    timings["stream_wall_s"] = time.perf_counter() - t_start
    if timer is not None:
        _attribute_phases(timer, timings)
    return StreamTrainResult(
        arrays=arrays, user_index=user_index, item_index=item_index,
        timings=timings,
    )
