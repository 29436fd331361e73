"""Storage DAO interfaces and metadata records.

Capability parity with the reference storage layer
(data/src/main/scala/io/prediction/data/storage/): the ``LEvents`` event DAO
trait (LEvents.scala:37-328), and the seven metadata DAOs — Apps
(Apps.scala:29-57), AccessKeys (AccessKeys.scala:31-64), Channels
(Channels.scala:29-78), EngineManifests (EngineManifests.scala:34-62),
EngineInstances (EngineInstances.scala:43-94), EvaluationInstances
(EvaluationInstances.scala:39-78), Models (Models.scala:30-48).

The reference splits event access into a local (LEvents) and a Spark-RDD
(PEvents) trait; in the single-controller TPU runtime one DAO serves both
roles — bulk reads return host iterators that the store layer columnarizes
into device-bound batches (see predictionio_tpu.data.store).
"""

from __future__ import annotations

import abc
import dataclasses
import datetime as _dt
import re
import secrets
import threading
from typing import (
    Dict, Iterable, Iterator, List, Optional, Sequence, Tuple, Union,
)


class DAOCacheMixin:
    """Per-(DAO class, namespace) instance cache for backend StorageClients
    (the reference caches clients per source, Storage.scala:202-208). Call
    ``_init_dao_cache`` in __init__; pass a lock to share one with other
    client state (e.g. sqlite's connection lock)."""

    def _init_dao_cache(self, lock: Optional[threading.Lock] = None) -> None:
        self._daos: Dict[str, object] = {}
        self._dao_lock = lock if lock is not None else threading.Lock()

    def dao(self, cls, namespace: str):
        key = f"{cls.__name__}:{namespace}"
        with self._dao_lock:
            if key not in self._daos:
                self._daos[key] = cls(
                    client=self, config=self.config, namespace=namespace
                )
            return self._daos[key]


class _Unset:
    """Sentinel distinguishing 'filter not given' from 'filter for absent'."""

    _instance: Optional["_Unset"] = None

    def __new__(cls):
        if cls._instance is None:
            cls._instance = super().__new__(cls)
        return cls._instance

    def __repr__(self) -> str:
        return "UNSET"


UNSET = _Unset()
OptFilter = Union[_Unset, None, str]

from predictionio_tpu.data.event import Event  # noqa: E402


class StorageError(Exception):
    """Backend failure (reference StorageException, Storage.scala:85-105)."""


class StorageSaturatedError(StorageError):
    """The write path is at capacity RIGHT NOW (a bounded group-commit
    queue refused a unit within its admission window). Distinct from a
    plain StorageError so frontends can answer deliberate backpressure
    (503 + ``Retry-After``) instead of parking a handler thread
    unboundedly behind a wedged or overloaded committer. ``retry_after_s``
    is the hint frontends surface to clients."""

    def __init__(self, message: str, retry_after_s: float = 1.0):
        super().__init__(message)
        self.retry_after_s = float(retry_after_s)


class PartialBatchError(StorageError):
    """An ``insert_batch`` where some per-partition slices committed and
    others failed. ``event_ids`` is the full assigned-id list (input
    order); ``failed_ids`` the subset whose slice did NOT commit — so a
    caller (the batch REST route) can report per-event outcomes instead
    of disavowing the whole batch after part of it is durable.
    ``retry_after_s``, when set, marks the failures as capacity refusals
    (the :class:`StorageSaturatedError` case scoped to a slice): the
    failed slots are retryable after backoff, and frontends answer them
    503 instead of 500."""

    def __init__(
        self,
        message: str,
        event_ids,
        failed_ids,
        retry_after_s: Optional[float] = None,
    ):
        super().__init__(message)
        self.event_ids = list(event_ids)
        self.failed_ids = frozenset(failed_ids)
        self.retry_after_s = (
            None if retry_after_s is None else float(retry_after_s)
        )


class LEvents(abc.ABC):
    """Event CRUD DAO (reference LEvents.scala:37-328).

    All operations are synchronous; the reference's Future-based API exists
    to paper over blocking JVM clients, which a Python host thread does not
    need. REST servers run these on worker threads.
    """

    @abc.abstractmethod
    def init(self, app_id: int, channel_id: Optional[int] = None) -> bool:
        """Initialize the backing table/namespace for an app (channel)."""

    @abc.abstractmethod
    def remove(self, app_id: int, channel_id: Optional[int] = None) -> bool:
        """Remove all data for an app (channel)."""

    @abc.abstractmethod
    def close(self) -> None:
        """Release client connections."""

    @abc.abstractmethod
    def insert(self, event: Event, app_id: int, channel_id: Optional[int] = None) -> str:
        """Insert one event; returns the assigned eventId."""

    @abc.abstractmethod
    def get(
        self, event_id: str, app_id: int, channel_id: Optional[int] = None
    ) -> Optional[Event]:
        """Get one event by id."""

    @abc.abstractmethod
    def delete(
        self, event_id: str, app_id: int, channel_id: Optional[int] = None
    ) -> bool:
        """Delete one event by id; returns whether it existed."""

    @abc.abstractmethod
    def find(
        self,
        app_id: int,
        channel_id: Optional[int] = None,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        entity_type: Optional[str] = None,
        entity_id: Optional[str] = None,
        event_names: Optional[Sequence[str]] = None,
        target_entity_type: OptFilter = UNSET,
        target_entity_id: OptFilter = UNSET,
        limit: Optional[int] = None,
        reversed: bool = False,
    ) -> Iterator[Event]:
        """Find events with the reference's 9 filter dimensions
        (LEvents.scala:164-176). ``start_time`` inclusive, ``until_time``
        exclusive. ``target_entity_type=None`` (explicitly) filters for
        events *without* a target entity; leave UNSET to not filter.
        ``limit=None`` or -1 returns all. ``reversed`` returns descending
        event-time order."""

    # --- derived operations ---

    def find_by_entities(
        self,
        app_id: int,
        channel_id: Optional[int] = None,
        *,
        entity_type: str,
        entity_ids: Sequence[str],
        event_names: Sequence[str],
        target_entity_type: str,
    ) -> Dict[str, List[Tuple[str, str, int]]]:
        """The serving path's read of a whole micro-batch: for each of
        ``entity_ids`` its ``(event, target entity id, event time ms)``
        triples among ``event_names`` with a target of
        ``target_entity_type``, newest first. One pass over the store a
        call where the backend has one (sqlite); here one ``find`` an
        entity. It reads what is committed when it is called, from
        whichever process: nothing is cached between calls."""
        out: Dict[str, List[Tuple[str, str, int]]] = {}
        for entity_id in dict.fromkeys(entity_ids):
            out[entity_id] = [
                (e.event, e.target_entity_id,
                 int(e.event_time.timestamp() * 1000))
                for e in self.find(
                    app_id=app_id, channel_id=channel_id,
                    entity_type=entity_type, entity_id=entity_id,
                    event_names=list(event_names),
                    target_entity_type=target_entity_type, reversed=True,
                )
                if e.target_entity_id
            ]
        return out

    def aggregate_properties(
        self,
        app_id: int,
        entity_type: str,
        channel_id: Optional[int] = None,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        required: Optional[Sequence[str]] = None,
    ) -> Dict[str, "PropertyMap"]:
        """Aggregate $set/$unset/$delete into per-entity PropertyMaps
        (reference LEvents.futureAggregateProperties:191-214)."""
        from predictionio_tpu.data.aggregator import aggregate_properties

        events = self.find(
            app_id=app_id,
            channel_id=channel_id,
            start_time=start_time,
            until_time=until_time,
            entity_type=entity_type,
            event_names=["$set", "$unset", "$delete"],
        )
        result = aggregate_properties(events)
        if required:
            req = list(required)
            result = {
                k: v for k, v in result.items() if all(r in v for r in req)
            }
        return result

    def aggregate_properties_of_entity(
        self,
        app_id: int,
        entity_type: str,
        entity_id: str,
        channel_id: Optional[int] = None,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
    ) -> Optional["PropertyMap"]:
        """Single-entity variant (reference LEvents.scala:234-253)."""
        from predictionio_tpu.data.aggregator import aggregate_properties_single

        events = self.find(
            app_id=app_id,
            channel_id=channel_id,
            start_time=start_time,
            until_time=until_time,
            entity_type=entity_type,
            entity_id=entity_id,
            event_names=["$set", "$unset", "$delete"],
        )
        return aggregate_properties_single(events)

    def insert_batch(
        self,
        events: Sequence[Event],
        app_id: int,
        channel_id: Optional[int] = None,
    ) -> List[str]:
        """Insert a group of events as ONE batch, returning their ids in
        input order. This is the group-commit unit of the event tier:
        the ``/batch/events.json`` route hands its whole request here,
        so a backend can make it one transaction instead of N.

        Contract for backends that override it: the batch must be
        atomic per storage partition — a reader may never observe part
        of a partition's slice (sqlite commits each shard's slice as one
        transaction; memory applies the whole batch under one lock
        acquisition). This generic fallback loops ``insert`` and is NOT
        atomic — acceptable for backends with per-event durability only.
        """
        return [self.insert(e, app_id, channel_id) for e in events]

    def write(
        self, events: Iterable[Event], app_id: int, channel_id: Optional[int] = None
    ) -> List[str]:
        """Bulk insert (reference PEvents.write:169-181) — rides the
        batch path so backends with a group-commit writer coalesce it."""
        return self.insert_batch(list(events), app_id, channel_id)

    # --- columnar scan path (round 4; reference analog: the partitioned
    # columnar scans HBPEvents.scala:84-90 / JDBCPEvents.scala:51-129) ---

    def insert_columns(
        self,
        app_id: int,
        channel_id: Optional[int] = None,
        *,
        event: str,
        entity_type: str,
        target_entity_type: str,
        entity_ids: Sequence[str],
        target_ids: Sequence[str],
        values: Sequence[float],
        value_property: str = "rating",
        event_time: Optional[_dt.datetime] = None,
        event_times_ms: Optional[Sequence[int]] = None,
    ) -> int:
        """Bulk-append target-carrying interaction events from columns.

        Backends with a columnar page store (sqlite) override this with a
        vectorized dictionary-encoded append; this generic fallback
        constructs one Event per row. ``event`` must be a plain
        interaction event (not a ``$``-prefixed special event — those
        carry property semantics the columnar form does not model).
        ``event_times_ms`` gives per-row millisecond timestamps (import
        round-trips); otherwise every row gets ``event_time`` (default
        now). Returns the number of events written.
        """
        if event.startswith("$"):
            raise StorageError(
                f"insert_columns cannot write special event {event!r}"
            )
        if event_times_ms is not None and len(event_times_ms) != len(values):
            # validate BEFORE the lazy generator: a short array failing
            # mid-write would leave a partial import behind
            raise ValueError("event_times_ms length differs")
        from predictionio_tpu.data.event import DataMap, Event

        t = event_time or _dt.datetime.now(_dt.timezone.utc)

        def when(j: int) -> _dt.datetime:
            if event_times_ms is None:
                return t
            return _dt.datetime.fromtimestamp(
                event_times_ms[j] / 1000.0, _dt.timezone.utc
            )

        self.write(
            (
                Event(
                    event=event,
                    entity_type=entity_type,
                    entity_id=str(e),
                    target_entity_type=target_entity_type,
                    target_entity_id=str(g),
                    properties=DataMap({value_property: float(v)}),
                    event_time=when(j),
                )
                for j, (e, g, v) in enumerate(
                    zip(entity_ids, target_ids, values)
                )
            ),
            app_id,
            channel_id,
        )
        return len(values)

    def insert_columns_encoded(
        self,
        app_id: int,
        channel_id: Optional[int] = None,
        *,
        event: str,
        entity_type: str,
        target_entity_type: str,
        entity_names,
        entity_codes,
        target_names,
        target_codes,
        values,
        value_property: str = "rating",
        event_time: Optional[_dt.datetime] = None,
        event_times_ms=None,
    ) -> int:
        """``insert_columns`` with pre-factorized id columns (distinct
        name dictionaries + int32 codes) — what travels over the storage
        gateway wire. Backends with a dictionary-encoded page store
        (sqlite) consume this directly; this generic fallback expands the
        codes back to id strings."""
        import numpy as np

        e_names = np.asarray(entity_names, object)
        g_names = np.asarray(target_names, object)
        return self.insert_columns(
            app_id,
            channel_id,
            event=event,
            entity_type=entity_type,
            target_entity_type=target_entity_type,
            entity_ids=e_names[np.asarray(entity_codes, np.int64)],
            target_ids=g_names[np.asarray(target_codes, np.int64)],
            values=values,
            value_property=value_property,
            event_time=event_time,
            event_times_ms=event_times_ms,
        )

    def find_columns_native(
        self,
        app_id: int,
        channel_id: Optional[int] = None,
        *,
        value_spec=None,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        entity_type: Optional[str] = None,
        target_entity_type: OptFilter = UNSET,
        event_names: Optional[Sequence[str]] = None,
    ):
        """Columnar scan: dictionary-encoded (entity, target, value)
        triples of every target-carrying event matching the filters
        (``ColumnarEvents``). ``value_spec`` (a ``columnar.ValueSpec``)
        declares how an event becomes a value, so backends can evaluate
        it vectorized (SQL / page decode) instead of per event.

        This generic implementation columnarizes ``find()`` results
        host-side; the sqlite backend overrides it with a binary page
        scan and the http backend forwards it to the gateway so the wire
        carries packed columns, not per-event JSON.
        """
        from predictionio_tpu.data.storage.columnar import (
            ValueSpec,
            from_events,
        )

        events = list(
            self.find(
                app_id=app_id,
                channel_id=channel_id,
                start_time=start_time,
                until_time=until_time,
                entity_type=entity_type,
                target_entity_type=target_entity_type,
                event_names=event_names,
            )
        )
        return from_events(events, value_spec or ValueSpec())

    def stream_columns_native(
        self,
        app_id: int,
        channel_id: Optional[int] = None,
        *,
        value_spec=None,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        entity_type: Optional[str] = None,
        target_entity_type: OptFilter = UNSET,
        event_names: Optional[Sequence[str]] = None,
        batch_rows: int = 1_048_576,
    ):
        """Chunked columnar scan (``columnar.ColumnarStream``): fixed-size
        batches in one shared code space, so the training pipeline can
        pack batch k while the backend scans batch k+1.

        Returns None when the backend has no chunked path — callers fall
        back to ``find_columns_native`` (one batch, no overlap). The
        sqlite backend overrides this with a per-page binary scan.
        """
        return None

    def stream_columns_delta(
        self,
        app_id: int,
        channel_id: Optional[int] = None,
        *,
        cursor: tuple,
        value_spec=None,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        entity_type: Optional[str] = None,
        target_entity_type: OptFilter = UNSET,
        event_names: Optional[Sequence[str]] = None,
        batch_rows: int = 1_048_576,
    ):
        """Incremental columnar scan: ONLY the target-carrying events
        committed after ``cursor`` (an opaque value a previous
        ``stream_columns_native``/``stream_columns_delta`` of the SAME
        app/channel/filters exposed via ``ColumnarStream.cursor``), in
        the order a full rescan would emit them after the rows the
        cursor already covered. The returned stream's own ``cursor``
        (valid after exhaustion) chains the next round.

        Contract — a backend may only return a stream when appending the
        delta to the prior scan reproduces a full rescan of the CURRENT
        store exactly; anything that rewrote or reordered already-scanned
        rows (deletes, tombstones, explicit-id re-posts, bulk-import page
        changes, a changed shard layout) must return ``None`` instead, so
        the caller falls back to a full repack. This default has no delta
        path at all; sqlite scans above per-shard rowid high-water marks
        (compaction watermarks guarantee sealed prefixes never re-issue
        rowids), memory replays its append-only tail.
        """
        return None

    def store_fingerprint(
        self, app_id: int, channel_id: Optional[int] = None
    ) -> Optional[tuple]:
        """Cheap state fingerprint of one app/channel's event store —
        event counts, max ids/times, tombstone state — used to key the
        pack-artifact cache: a repeat train whose fingerprint matches the
        cached one skips scan+pack entirely. Must change whenever a scan
        of the store could return different columns (insert, bulk import,
        delete). None disables caching for this backend.
        """
        return None


# --- metadata records ---


@dataclasses.dataclass(frozen=True)
class App:
    """An app record (reference Apps.scala:29)."""

    id: int
    name: str
    description: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class AccessKey:
    """An access key granting event-API access to an app
    (reference AccessKeys.scala:31). Empty ``events`` permits all."""

    key: str
    appid: int
    events: tuple = ()

    def __post_init__(self):
        object.__setattr__(self, "events", tuple(self.events))


@dataclasses.dataclass(frozen=True)
class Channel:
    """A named event channel within an app (reference Channels.scala:29)."""

    id: int
    name: str
    appid: int

    NAME_RE = re.compile(r"^[a-zA-Z0-9-]{1,16}$")

    @staticmethod
    def is_valid_name(s: str) -> bool:
        return bool(Channel.NAME_RE.match(s))


@dataclasses.dataclass(frozen=True)
class EngineManifest:
    """A built engine's registration (reference EngineManifests.scala:34)."""

    id: str
    version: str
    name: str
    description: Optional[str] = None
    files: tuple = ()
    engine_factory: str = ""

    def __post_init__(self):
        object.__setattr__(self, "files", tuple(self.files))


@dataclasses.dataclass(frozen=True)
class EngineInstance:
    """A training-run record (reference EngineInstances.scala:43-94)."""

    id: str
    status: str
    start_time: _dt.datetime
    end_time: _dt.datetime
    engine_id: str
    engine_version: str
    engine_variant: str
    engine_factory: str
    batch: str = ""
    env: Dict[str, str] = dataclasses.field(default_factory=dict)
    spark_conf: Dict[str, str] = dataclasses.field(default_factory=dict)
    data_source_params: str = ""
    preparator_params: str = ""
    algorithms_params: str = ""
    serving_params: str = ""


@dataclasses.dataclass(frozen=True)
class EvaluationInstance:
    """An evaluation-run record (reference EvaluationInstances.scala:39-78)."""

    id: str
    status: str
    start_time: _dt.datetime
    end_time: _dt.datetime
    evaluation_class: str = ""
    engine_params_generator_class: str = ""
    batch: str = ""
    env: Dict[str, str] = dataclasses.field(default_factory=dict)
    spark_conf: Dict[str, str] = dataclasses.field(default_factory=dict)
    evaluator_results: str = ""
    evaluator_results_html: str = ""
    evaluator_results_json: str = ""


@dataclasses.dataclass(frozen=True)
class Model:
    """A serialized model blob keyed by engine-instance id
    (reference Models.scala:30)."""

    id: str
    models: bytes


# --- metadata DAO interfaces ---


class Apps(abc.ABC):
    @abc.abstractmethod
    def insert(self, app: App) -> Optional[int]:
        """Insert; id 0 means auto-assign. Returns the assigned id."""

    @abc.abstractmethod
    def get(self, app_id: int) -> Optional[App]: ...

    @abc.abstractmethod
    def get_by_name(self, name: str) -> Optional[App]: ...

    @abc.abstractmethod
    def get_all(self) -> List[App]: ...

    @abc.abstractmethod
    def update(self, app: App) -> bool: ...

    @abc.abstractmethod
    def delete(self, app_id: int) -> bool: ...


class AccessKeys(abc.ABC):
    @abc.abstractmethod
    def insert(self, access_key: AccessKey) -> Optional[str]:
        """Insert; empty key means generate. Returns the key."""

    @abc.abstractmethod
    def get(self, key: str) -> Optional[AccessKey]: ...

    @abc.abstractmethod
    def get_all(self) -> List[AccessKey]: ...

    @abc.abstractmethod
    def get_by_app_id(self, app_id: int) -> List[AccessKey]: ...

    @abc.abstractmethod
    def update(self, access_key: AccessKey) -> bool: ...

    @abc.abstractmethod
    def delete(self, key: str) -> bool: ...

    @staticmethod
    def generate_key() -> str:
        """64-char URL-safe random key (reference AccessKeys.scala:44-49)."""
        while True:
            k = secrets.token_urlsafe(48).replace("-", "8").replace("_", "9")
            if len(k) >= 64:
                return k[:64]


class Channels(abc.ABC):
    @abc.abstractmethod
    def insert(self, channel: Channel) -> Optional[int]:
        """Insert; id 0 means auto-assign. Returns the assigned id."""

    @abc.abstractmethod
    def get(self, channel_id: int) -> Optional[Channel]: ...

    @abc.abstractmethod
    def get_by_app_id(self, app_id: int) -> List[Channel]: ...

    @abc.abstractmethod
    def delete(self, channel_id: int) -> bool: ...


class EngineManifests(abc.ABC):
    @abc.abstractmethod
    def insert(self, manifest: EngineManifest) -> None: ...

    @abc.abstractmethod
    def get(self, id: str, version: str) -> Optional[EngineManifest]: ...

    @abc.abstractmethod
    def get_all(self) -> List[EngineManifest]: ...

    @abc.abstractmethod
    def update(self, manifest: EngineManifest, upsert: bool = False) -> None: ...

    @abc.abstractmethod
    def delete(self, id: str, version: str) -> None: ...


class EngineInstances(abc.ABC):
    @abc.abstractmethod
    def insert(self, instance: EngineInstance) -> str:
        """Insert; empty id means generate. Returns the id."""

    @abc.abstractmethod
    def get(self, id: str) -> Optional[EngineInstance]: ...

    @abc.abstractmethod
    def get_all(self) -> List[EngineInstance]: ...

    @abc.abstractmethod
    def get_latest_completed(
        self, engine_id: str, engine_version: str, engine_variant: str
    ) -> Optional[EngineInstance]:
        """Latest COMPLETED instance for an engine variant
        (reference EngineInstances.getLatestCompleted:79)."""

    @abc.abstractmethod
    def get_completed(
        self, engine_id: str, engine_version: str, engine_variant: str
    ) -> List[EngineInstance]: ...

    @abc.abstractmethod
    def update(self, instance: EngineInstance) -> None: ...

    @abc.abstractmethod
    def delete(self, id: str) -> None: ...


class EvaluationInstances(abc.ABC):
    @abc.abstractmethod
    def insert(self, instance: EvaluationInstance) -> str: ...

    @abc.abstractmethod
    def get(self, id: str) -> Optional[EvaluationInstance]: ...

    @abc.abstractmethod
    def get_all(self) -> List[EvaluationInstance]: ...

    @abc.abstractmethod
    def get_completed(self) -> List[EvaluationInstance]: ...

    @abc.abstractmethod
    def update(self, instance: EvaluationInstance) -> None: ...

    @abc.abstractmethod
    def delete(self, id: str) -> None: ...


class Models(abc.ABC):
    @abc.abstractmethod
    def insert(self, model: Model) -> None: ...

    @abc.abstractmethod
    def get(self, id: str) -> Optional[Model]: ...

    @abc.abstractmethod
    def delete(self, id: str) -> None: ...


# re-exported for type hints in aggregate_properties
from predictionio_tpu.data.event import PropertyMap  # noqa: E402

STATUS_INIT = "INIT"
STATUS_TRAINING = "TRAINING"
STATUS_EVALUATING = "EVALUATING"
STATUS_COMPLETED = "COMPLETED"
STATUS_FAILED = "FAILED"
