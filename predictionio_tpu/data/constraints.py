"""TTL-cached constraint-entity reader for the serving hot path.

The reference's e-commerce template re-reads the ``unavailableItems``
constraint entity from the event store INSIDE every predict
(ALSAlgorithm.scala of the train-with-rate-event variant) — ported
literally, that put one storage round trip (and, with the ``http``
backend, one gateway RPC) on every served batch, and a stalled store
stalled serving. This module extracts that read behind a TTL cache with
OUT-OF-BAND refresh:

- ``get()`` returns the cached set and NEVER touches the store once
  primed: past HALF the TTL it kicks a single background refresh thread
  (so that a ``$set`` is honoured by every query sent later than the TTL
  after its acknowledgement, as long as queries keep ticking) and
  keeps serving the cached value, so a store stall can no longer block
  a batch (only the very first call, typically at deploy, reads
  inline).
- Refreshes that CHANGE the set notify ``on_change`` listeners — the
  retrieval tier (ops/retrieval.py) subscribes to rebuild its resident
  on-device candidacy mask, which is what "refreshed out-of-band on
  constraint-entity change" means end to end. The mask's device
  residency is accounted in the HBM ledger under the retriever's
  ``<component>-mask`` entry (utils/device_ledger.py): every
  constraint-driven re-upload re-``set``s that entry, so
  ``pio_device_ledger_bytes`` tracks the mask through its whole
  refresh lifecycle.
- Every read outcome is counted in
  ``pio_constraint_cache_total{outcome=hit|miss|error}`` (miss = an
  actual store read, inline or background; error = the store raised and
  the cached value was served).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, FrozenSet, List, Optional

from predictionio_tpu.utils import metrics as _metrics

logger = logging.getLogger(__name__)


def _m_outcomes():
    return _metrics.get_registry().counter(
        "pio_constraint_cache_total",
        "Constraint-entity reads by outcome (hit=served from cache, "
        "miss=store read, error=store failed and cache served)",
        labels=("outcome",),
    )


def read_constraint_items(
    app_name: str,
    entity_id: str = "unavailableItems",
    prop: str = "items",
    storage=None,
    timeout_seconds: Optional[float] = 10.0,
) -> FrozenSet[str]:
    """One store read of the latest ``$set`` on the constraint entity
    (reference semantics: only the single latest event counts)."""
    from predictionio_tpu.data.store import LEventStore

    events = list(
        LEventStore(storage).find_by_entity(
            app_name=app_name,
            entity_type="constraint",
            entity_id=entity_id,
            event_names=["$set"],
            limit=1,
            latest=True,
            timeout_seconds=timeout_seconds,
        )
    )
    if events:
        return frozenset(events[0].properties.get_or_else(prop, []))
    return frozenset()


class ConstraintCache:
    """TTL cache over one constraint entity's item set.

    Thread-safe; shared by the predict hot path (``get``) and the
    retrieval mask-refresh path (``on_change`` listeners fire from the
    background refresh thread whenever the set changes). ``ttl_s=0``
    disables caching entirely (every ``get`` reads inline — the
    pre-round-12 behavior, kept for tests that assert store-read
    semantics)."""

    def __init__(
        self,
        app_name: str,
        entity_id: str = "unavailableItems",
        prop: str = "items",
        ttl_s: float = 5.0,
        storage=None,
        reader: Optional[Callable[[], FrozenSet[str]]] = None,
    ):
        self.app_name = app_name
        self.ttl_s = float(ttl_s)
        self._reader = reader or (
            lambda: read_constraint_items(
                app_name, entity_id=entity_id, prop=prop, storage=storage
            )
        )
        self._lock = threading.Lock()
        self._value: Optional[FrozenSet[str]] = None
        self._loaded_at = 0.0
        self._refreshing = False
        self._listeners: List[Callable[[FrozenSet[str]], None]] = []

    def on_change(self, fn: Callable[[FrozenSet[str]], None]) -> None:
        """Register a listener called (from the refreshing thread) with
        the NEW set whenever a refresh observes a change."""
        with self._lock:
            self._listeners.append(fn)

    @property
    def age_s(self) -> float:
        with self._lock:
            if self._value is None:
                return float("inf")
            return time.monotonic() - self._loaded_at

    def get(self) -> FrozenSet[str]:
        """The constraint set, from cache. Primed + fresh -> hit. Primed
        + stale -> hit NOW, one background refresh kicked (out-of-band:
        the caller's batch never waits on the store). Unprimed -> one
        inline read (deploy-time)."""
        with self._lock:
            value = self._value
            # refresh at HALF the TTL: a set written just after a read
            # is then served within ttl_s of its acknowledgement (half a
            # TTL until the next refresh is kicked, the rest for that
            # read and the listeners' re-upload), which is the guarantee
            # the TTL names; a refresh kicked only AT the TTL would
            # honour it a read too late
            stale = (
                value is not None
                and self.ttl_s > 0
                and (time.monotonic() - self._loaded_at) > self.ttl_s / 2
            )
            kick = stale and not self._refreshing
            if kick:
                self._refreshing = True
        if value is None or self.ttl_s <= 0:
            return self._read_inline()
        _m_outcomes().labels(outcome="hit").inc()
        if kick:
            threading.Thread(
                target=self._refresh_bg, daemon=True,
                name=f"constraint-refresh:{self.app_name}",
            ).start()
        return value

    def refresh(self) -> bool:
        """Force one inline read; returns whether the set changed.
        Listeners fire on change. Used by tests and by deploy-time
        priming; the serving path never calls it."""
        before = self._value
        value = self._read_inline()
        changed = before is not None and value != before
        if changed:
            self._notify(value)
        return changed or before is None

    def _read_inline(self) -> FrozenSet[str]:
        try:
            value = self._reader()
            _m_outcomes().labels(outcome="miss").inc()
        except Exception as e:
            _m_outcomes().labels(outcome="error").inc()
            logger.error("Error when reading constraint entity: %s", e)
            with self._lock:
                if self._value is None:
                    # error-PRIME: an unprimed cache whose first read
                    # fails (store down at deploy) must not stay
                    # unprimed — that would put a blocking inline read
                    # (up to the reader timeout) on EVERY batch until
                    # the store recovers. Serve the empty set as the
                    # cached value instead; the normal TTL tick retries
                    # out-of-band and the on_change listeners fire once
                    # the store answers.
                    self._value = frozenset()
                    self._loaded_at = time.monotonic()
                return self._value
        with self._lock:
            self._value = value
            self._loaded_at = time.monotonic()
        return value

    def _refresh_bg(self) -> None:
        try:
            before = self._value
            value = self._read_inline()
            if before is not None and value != before:
                self._notify(value)
        finally:
            with self._lock:
                self._refreshing = False

    def _notify(self, value: FrozenSet[str]) -> None:
        with self._lock:
            listeners = list(self._listeners)
        for fn in listeners:
            try:
                fn(value)
            except Exception:
                logger.exception("constraint on_change listener failed")
