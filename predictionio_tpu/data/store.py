"""Event store access layer for engine developers.

Capability parity with the reference's store layer
(data/src/main/scala/io/prediction/data/store/): ``PEventStore``
(PEventStore.scala:30 — find + aggregateProperties by app *name*),
``LEventStore`` (LEventStore.scala:146 — findByEntity serving-time lookups
with timeout), and app-name/channel resolution (Common.scala:28-49).

Where the reference returns RDDs, the batch API here returns host lists
plus a columnar view (``EventColumns``) holding dense numpy id/value
columns with BiMap indexes — the form that `jax.device_put` moves straight
into HBM for kernel consumption (SURVEY.md §7 step 1).
"""

from __future__ import annotations

import dataclasses
import datetime as _dt
import threading
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.data.event import Event, PropertyMap
from predictionio_tpu.data.storage import Storage, get_storage
from predictionio_tpu.data.storage.base import UNSET, OptFilter


class AppNotFoundError(KeyError):
    pass


class ChannelNotFoundError(KeyError):
    pass


def app_name_to_id(
    app_name: str, channel_name: Optional[str] = None, storage: Optional[Storage] = None
) -> Tuple[int, Optional[int]]:
    """Resolve appName (+ optional channel) to ids
    (reference store/Common.scala:28-49)."""
    storage = storage or get_storage()
    app = storage.get_meta_data_apps().get_by_name(app_name)
    if app is None:
        raise AppNotFoundError(f"App {app_name!r} does not exist; use pio app new")
    channel_id: Optional[int] = None
    if channel_name is not None:
        channels = storage.get_meta_data_channels().get_by_app_id(app.id)
        match = [c for c in channels if c.name == channel_name]
        if not match:
            raise ChannelNotFoundError(
                f"Channel {channel_name!r} does not exist in app {app_name!r}"
            )
        channel_id = match[0].id
    return app.id, channel_id


@dataclasses.dataclass
class EventColumns:
    """Column-oriented batch of (entity, target, value) triples with dense
    indexes — the device-bound form of an event scan."""

    entity_index: BiMap  # entityId -> dense int
    target_index: BiMap  # targetEntityId -> dense int
    entity_idx: np.ndarray  # [n] int32
    target_idx: np.ndarray  # [n] int32
    values: np.ndarray  # [n] float32
    events: List[Event]  # originating events (host metadata)

    @property
    def n(self) -> int:
        return len(self.values)


class PEventStore:
    """Batch event reads by app name (reference PEventStore.scala:30-116)."""

    def __init__(self, storage: Optional[Storage] = None):
        self._storage = storage

    @property
    def storage(self) -> Storage:
        return self._storage or get_storage()

    def find(
        self,
        app_name: str,
        channel_name: Optional[str] = None,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        entity_type: Optional[str] = None,
        entity_id: Optional[str] = None,
        event_names: Optional[Sequence[str]] = None,
        target_entity_type: OptFilter = UNSET,
        target_entity_id: OptFilter = UNSET,
    ) -> Iterator[Event]:
        app_id, channel_id = app_name_to_id(app_name, channel_name, self.storage)
        return self.storage.get_p_events().find(
            app_id=app_id,
            channel_id=channel_id,
            start_time=start_time,
            until_time=until_time,
            entity_type=entity_type,
            entity_id=entity_id,
            event_names=event_names,
            target_entity_type=target_entity_type,
            target_entity_id=target_entity_id,
        )

    def aggregate_properties(
        self,
        app_name: str,
        entity_type: str,
        channel_name: Optional[str] = None,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        required: Optional[Sequence[str]] = None,
    ) -> Dict[str, PropertyMap]:
        app_id, channel_id = app_name_to_id(app_name, channel_name, self.storage)
        return self.storage.get_p_events().aggregate_properties(
            app_id=app_id,
            entity_type=entity_type,
            channel_id=channel_id,
            start_time=start_time,
            until_time=until_time,
            required=required,
        )

    def extract_entity_map(
        self,
        app_name: str,
        entity_type: str,
        mapper,
        channel_name: Optional[str] = None,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        required: Optional[Sequence[str]] = None,
    ):
        """Fold an entity type's property history into a typed
        :class:`~predictionio_tpu.data.entity_map.EntityMap` (reference
        PEvents.extractEntityMap, data/storage/PEvents.scala:73-102):
        aggregate ``$set/$unset/$delete``, drop entities missing a
        ``required`` property, and apply ``mapper(PropertyMap) -> A``.
        The resulting dense indices are what device kernels consume as
        factor/feature matrix rows."""
        from predictionio_tpu.data.entity_map import EntityMap

        props = self.aggregate_properties(
            app_name,
            entity_type=entity_type,
            channel_name=channel_name,
            start_time=start_time,
            until_time=until_time,
            required=required,
        )
        return EntityMap({eid: mapper(pm) for eid, pm in props.items()})

    # --- columnar view: events -> device-ready arrays ---

    _NATIVE_FILTERS = frozenset(
        (
            "channel_name", "start_time", "until_time", "entity_type",
            "target_entity_type", "event_names",
        )
    )

    def find_columns(
        self,
        app_name: str,
        value_of=None,
        entity_index: Optional[BiMap] = None,
        target_index: Optional[BiMap] = None,
        value_spec=None,
        **find_kwargs,
    ) -> EventColumns:
        """Scan events and columnarize (entityId, targetEntityId, value).

        The value rule is declarative by default (``value_spec``, a
        ``columnar.ValueSpec`` — property name, default, and per-event
        constant overrides like the recommendation template's buy->4.0),
        which lets the backend run its NATIVE columnar scan: binary page
        decode + SQL-evaluated residual on sqlite, packed columns over
        the wire on the http backend — no per-event Python objects
        (reference HBPEvents.scala:84-90's partitioned scan). On that
        path the returned ``events`` list is empty.

        Passing a ``value_of(event) -> float`` callable (or filters the
        native scan does not support, e.g. ``entity_id``) falls back to
        the per-event path, where ``events`` carries the scanned Events.
        Existing BiMaps may be passed to keep indices aligned across
        scans (e.g. train vs eval); both paths honor them and index
        distinct ids in sorted order.
        """
        from predictionio_tpu.data.storage.columnar import ValueSpec

        if value_of is None and set(find_kwargs) <= self._NATIVE_FILTERS:
            spec = value_spec or ValueSpec()
            kwargs = dict(find_kwargs)
            app_id, channel_id = app_name_to_id(
                app_name, kwargs.pop("channel_name", None), self.storage
            )
            cols = self.storage.get_p_events().find_columns_native(
                app_id=app_id,
                channel_id=channel_id,
                value_spec=spec,
                **kwargs,
            )
            if cols is not None:
                return self._from_columnar(cols, entity_index, target_index)

        events = [
            e
            for e in self.find(app_name, **find_kwargs)
            if e.target_entity_id is not None
        ]
        if value_of is None:
            spec = value_spec or ValueSpec()
            value_of = spec.value_of

        if entity_index is None:
            entity_index = BiMap.string_int(e.entity_id for e in events)
        if target_index is None:
            target_index = BiMap.string_int(e.target_entity_id for e in events)
        kept = [
            e
            for e in events
            if e.entity_id in entity_index and e.target_entity_id in target_index
        ]
        entity_idx = np.fromiter(
            (entity_index[e.entity_id] for e in kept), np.int32, count=len(kept)
        )
        target_idx = np.fromiter(
            (target_index[e.target_entity_id] for e in kept), np.int32, count=len(kept)
        )
        values = np.fromiter(
            (value_of(e) for e in kept), np.float32, count=len(kept)
        )
        return EventColumns(
            entity_index=entity_index,
            target_index=target_index,
            entity_idx=entity_idx,
            target_idx=target_idx,
            values=values,
            events=kept,
        )

    def stream_columns(
        self,
        app_name: str,
        value_spec=None,
        channel_name: Optional[str] = None,
        batch_rows: int = 1_048_576,
        **find_kwargs,
    ):
        """Chunked columnar scan for the streaming store→device training
        pipeline (``ops/streaming.py``): a ``columnar.ColumnarStream`` of
        batches in one shared code space, carrying the store's pre-scan
        fingerprint and a cache identity for the pack-artifact cache.

        Only the native filter set is streamable (the per-event fallback
        would defeat the point); backends without a chunked scan wrap the
        monolithic native scan in a one-batch stream, so callers keep one
        code path. Returns None when the filters need the per-event path
        or the backend has no native scan at all — callers fall back to
        ``find_columns`` + the materialized trainer.
        """
        from predictionio_tpu.data.storage.columnar import (
            ColumnarStream,
            ValueSpec,
        )

        native = self._NATIVE_FILTERS - {"channel_name"}
        if not set(find_kwargs) <= native:
            return None
        spec = value_spec or ValueSpec()
        app_id, channel_id = app_name_to_id(
            app_name, channel_name, self.storage
        )
        le = self.storage.get_p_events()
        key = (
            "stream", app_id, channel_id, spec,
            tuple(
                (k, tuple(v) if isinstance(v, (list, tuple)) else v)
                for k, v in sorted(find_kwargs.items())
            ),
        )
        stream = le.stream_columns_native(
            app_id=app_id, channel_id=channel_id, value_spec=spec,
            batch_rows=batch_rows, **find_kwargs,
        )
        if stream is None:
            # one-batch fallback: fingerprint read BEFORE the scan so a
            # cached artifact can never be labeled newer than its data
            fp = le.store_fingerprint(app_id, channel_id)
            cols = le.find_columns_native(
                app_id=app_id, channel_id=channel_id, value_spec=spec,
                **find_kwargs,
            )
            if cols is None:
                return None
            stream = ColumnarStream.from_columnar(cols, fingerprint=fp)

        def delta_factory(cursor):
            """Delta scan of the same app/filters from a prior scan's
            cursor (None when the backend has no delta path or the
            cursor no longer covers a clean prefix). The returned
            stream keeps this factory, so delta rounds chain."""
            dstream = le.stream_columns_delta(
                app_id=app_id, channel_id=channel_id, cursor=cursor,
                value_spec=spec, batch_rows=batch_rows, **find_kwargs,
            )
            if dstream is not None:
                dstream.cache_key = key
                dstream.cache_scope = le
                dstream.delta_factory = delta_factory
            return dstream

        stream.cache_key = key
        stream.cache_scope = le
        stream.delta_factory = delta_factory
        return stream

    @staticmethod
    def _from_columnar(
        cols,
        entity_index: Optional[BiMap],
        target_index: Optional[BiMap],
    ) -> EventColumns:
        """ColumnarEvents -> EventColumns: build BiMaps from the (sorted)
        name dictionaries, or remap onto caller-provided BiMaps with a
        vectorized lookup table, dropping rows with unknown ids."""

        def index_and_map(names, codes, provided: Optional[BiMap]):
            if provided is None:
                index = BiMap(
                    {str(n): j for j, n in enumerate(names)}
                )
                return index, codes, None
            lut = np.array(
                [provided.get(str(n), -1) for n in names], np.int32
            )
            mapped = lut[codes] if len(codes) else codes
            return provided, mapped, mapped >= 0

        e_index, e_idx, e_ok = index_and_map(
            cols.entity_names, cols.entity_codes, entity_index
        )
        t_index, t_idx, t_ok = index_and_map(
            cols.target_names, cols.target_codes, target_index
        )
        values = cols.values
        if e_ok is not None or t_ok is not None:
            keep = np.ones(len(values), bool)
            if e_ok is not None:
                keep &= e_ok
            if t_ok is not None:
                keep &= t_ok
            e_idx, t_idx, values = e_idx[keep], t_idx[keep], values[keep]
        return EventColumns(
            entity_index=e_index,
            target_index=t_index,
            entity_idx=e_idx.astype(np.int32),
            target_idx=t_idx.astype(np.int32),
            values=values.astype(np.float32),
            events=[],
        )


class _DaemonLookupPool:
    """Bounded pool of DAEMON worker threads for deadline-enforced
    serving lookups. A timed-out lookup's worker keeps running until the
    backend returns — with a fully stuck backend up to max_workers
    threads wedge and later lookups spend their deadline in the queue,
    still raising TimeoutError on schedule (the reference's Await.result
    behaves the same way: the HBase client call keeps running after the
    TimeoutException, LEventStore.scala:146-230). Daemon threads matter:
    concurrent.futures' workers are non-daemon and joined at interpreter
    exit, so one truly-stuck backend call would hang process shutdown
    forever."""

    def __init__(self, max_workers: int = 8):
        import queue

        self._q: "queue.Queue" = queue.Queue()
        self._lock = threading.Lock()
        self._spawned = 0
        self._max = max_workers

    def _worker(self) -> None:
        while True:
            fn, box, done = self._q.get()
            try:
                box["result"] = fn()
            except BaseException as e:  # delivered to the caller
                box["error"] = e
            done.set()

    def submit(self, fn):
        with self._lock:
            if self._spawned < self._max:
                self._spawned += 1
                threading.Thread(
                    target=self._worker,
                    daemon=True,
                    name=f"levents-{self._spawned}",
                ).start()
        box: dict = {}
        done = threading.Event()
        self._q.put((fn, box, done))
        return box, done


_LOOKUP_POOL = _DaemonLookupPool(max_workers=8)


def _with_deadline(fn, timeout_seconds: Optional[float]):
    """Run ``fn`` under a wall-clock deadline; raises TimeoutError.
    ``timeout_seconds`` of None/0/negative means no deadline (inline)."""
    if not timeout_seconds or timeout_seconds <= 0:
        return fn()
    box, done = _LOOKUP_POOL.submit(fn)
    if not done.wait(timeout_seconds):
        raise TimeoutError(
            f"LEventStore lookup exceeded {timeout_seconds}s; a slow "
            "backend must not stall the serving hot path"
        )
    if "error" in box:
        raise box["error"]
    return box["result"]


class LEventStore:
    """Serving-time entity reads (reference LEventStore.scala:146-230).

    The wall-clock ``timeout_seconds`` is ENFORCED (round 4): with the
    ``http`` storage backend in the loop a slow gateway can stall the
    serving hot path, exactly the failure the reference's
    Await.result(timeout) guards against. The lookup materializes on a
    worker thread and raises ``TimeoutError`` past the deadline; serving
    engines catch it and degrade (e.g. ecommerce's rule reads fall back
    to empty sets). Pass ``timeout_seconds=None`` (or <= 0) to run
    inline without a deadline.
    """

    def __init__(self, storage: Optional[Storage] = None):
        self._storage = storage

    @property
    def storage(self) -> Storage:
        return self._storage or get_storage()

    def find_by_entity(
        self,
        app_name: str,
        entity_type: str,
        entity_id: str,
        channel_name: Optional[str] = None,
        event_names: Optional[Sequence[str]] = None,
        target_entity_type: OptFilter = UNSET,
        target_entity_id: OptFilter = UNSET,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        limit: Optional[int] = None,
        latest: bool = True,
        timeout_seconds: Optional[float] = 10.0,
    ) -> Iterator[Event]:
        def lookup() -> List[Event]:
            app_id, channel_id = app_name_to_id(
                app_name, channel_name, self.storage
            )
            # materialize inside the deadline: the backend may hand back
            # a lazy iterator whose cost lands on first next()
            return list(
                self.storage.get_l_events().find(
                    app_id=app_id,
                    channel_id=channel_id,
                    start_time=start_time,
                    until_time=until_time,
                    entity_type=entity_type,
                    entity_id=entity_id,
                    event_names=event_names,
                    target_entity_type=target_entity_type,
                    target_entity_id=target_entity_id,
                    limit=limit,
                    reversed=latest,
                )
            )

        return iter(_with_deadline(lookup, timeout_seconds))

    def find_by_entities(
        self,
        app_name: str,
        entity_type: str,
        entity_ids: Sequence[str],
        event_names: Sequence[str],
        target_entity_type: str,
        channel_name: Optional[str] = None,
        timeout_seconds: Optional[float] = 10.0,
    ):
        """A micro-batch's read: ``{entity id: [(event, target entity
        id, event time ms), ...]}``, newest first, in one pass over the
        store where the backend has one (``LEvents.find_by_entities``).
        Nothing is kept between calls: what another process committed
        before the call is in the answer."""

        def lookup():
            app_id, channel_id = app_name_to_id(
                app_name, channel_name, self.storage
            )
            return self.storage.get_l_events().find_by_entities(
                app_id, channel_id, entity_type=entity_type,
                entity_ids=list(entity_ids), event_names=list(event_names),
                target_entity_type=target_entity_type,
            )

        return _with_deadline(lookup, timeout_seconds)

    def find(
        self,
        app_name: str,
        channel_name: Optional[str] = None,
        timeout_seconds: Optional[float] = 10.0,
        **find_kwargs,
    ) -> Iterator[Event]:
        def lookup() -> List[Event]:
            app_id, channel_id = app_name_to_id(
                app_name, channel_name, self.storage
            )
            return list(
                self.storage.get_l_events().find(
                    app_id=app_id, channel_id=channel_id, **find_kwargs
                )
            )

        return iter(_with_deadline(lookup, timeout_seconds))
