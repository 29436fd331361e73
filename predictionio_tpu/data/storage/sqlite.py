"""SQLite storage backend — the persistent embedded default.

Plays the role of the reference's JDBC backend
(data/src/main/scala/io/prediction/data/storage/jdbc/): one database file
holds the metadata tables and per-app/channel event tables named
``events_<app>[_<channel>]`` (the reference's table-per-app/channel scheme,
JDBCUtils/HBEventsUtil). Event rows carry a millisecond timestamp column for
ordered range scans (the role of the HBase row-key time component,
hbase/HBEventsUtil.scala:82-130).

Write-path scale-out (the role of the reference's HBase region servers):

- **Group commit.** Single-event inserts do not commit their own
  transaction. REST worker threads enqueue rows onto a bounded per-shard
  queue; a committer thread per shard coalesces queued rows into ONE
  multi-row transaction (flush at ``GROUP_COMMIT_EVENTS`` rows or
  ``GROUP_COMMIT_MS`` after the batch opened, whichever first — a solo
  row with an idle queue flushes immediately). The caller's ``insert``
  returns only after its batch's COMMIT, so the 201 ack still means
  durable-to-WAL; what changes is that N concurrent inserts now cost one
  commit instead of N.

- **Hash sharding.** With ``PIO_STORAGE_SOURCES_<NAME>_SHARDS = K`` (>1),
  single-event rows split across K independent sqlite files
  (``<path>.shard<k>``) by a stable hash of the entity id. Each shard has
  its own connection, lock, WAL write slot, and committer — concurrent
  writers stop serializing on one lock. The main file keeps the metadata
  tables, the columnar page store, and the (possibly pre-sharding) row
  table, which participates in every scan as shard "-1"; turning shards
  on for an existing database is therefore seamless. Events of one
  entity always land in one shard, so per-entity order is preserved and
  the streaming scan's counting-sort merge reproduces the single-file
  wire byte-for-byte (``ops/streaming.py``).
"""

from __future__ import annotations

import dataclasses
import datetime as _dt
import json
import logging
import os
import queue as _queue
import time as _time
import zlib

from predictionio_tpu.utils.fs import fs_basedir
import sqlite3
import threading
from typing import Dict, Iterator, List, Optional, Sequence

from predictionio_tpu.data.event import (
    DataMap,
    Event,
    format_iso8601,
    new_event_id,
    parse_iso8601,
)
from predictionio_tpu.data.storage import base
from predictionio_tpu.data.storage.base import (
    UNSET,
    AccessKey,
    App,
    Channel,
    EngineInstance,
    EngineManifest,
    EvaluationInstance,
    Model,
    OptFilter,
    PartialBatchError,
    StorageError,
)


logger = logging.getLogger(__name__)


def _ms(t: _dt.datetime) -> int:
    if t.tzinfo is None:
        t = t.replace(tzinfo=_dt.timezone.utc)
    return int(t.timestamp() * 1000)


def _utc_iso(t: _dt.datetime) -> str:
    """UTC-normalized fixed-width ISO8601, so lexicographic TEXT ordering is
    chronological (used for instance start/end times in ORDER BY)."""
    if t.tzinfo is None:
        t = t.replace(tzinfo=_dt.timezone.utc)
    return format_iso8601(t.astimezone(_dt.timezone.utc))


class _LockedCursor:
    """Runs a statement under the client lock and materializes results, so
    concurrent REST worker threads never interleave cursor state on the
    shared connection."""

    __slots__ = ("_rows", "rowcount", "lastrowid")

    def __init__(self, client: "StorageClient", sql: str, params=()):
        with client.lock:
            cur = client.conn.execute(sql, params)
            self._rows = cur.fetchall() if cur.description is not None else []
            self.rowcount = cur.rowcount
            self.lastrowid = cur.lastrowid

    def fetchone(self):
        return self._rows[0] if self._rows else None

    def fetchall(self):
        return self._rows


def _open_wal_conn(path: str) -> sqlite3.Connection:
    """Open a writer connection in the mode every concurrent path here
    assumes: WAL (readers on other connections see a consistent snapshot
    while one writer proceeds), busy_timeout for multi-process writers
    (gateway + CLI) briefly contending for the single WAL write slot, and
    synchronous=NORMAL — WAL's standard production pairing: commits
    append to the WAL without an fsync each (integrity is preserved on
    crash; only the tail of very recent commits may be lost on power
    failure). Per-event REST ingest is commit-bound — FULL measured ~380
    events/s vs ~thousands with NORMAL on the same rig."""
    conn = sqlite3.connect(path, check_same_thread=False)
    conn.execute("PRAGMA journal_mode=WAL")
    conn.execute("PRAGMA busy_timeout=5000")
    conn.execute("PRAGMA synchronous=NORMAL")
    return conn


class _InsertUnit:
    """One atomic slice of committer work: a statement plus the rows to
    executemany it with. All rows of a unit commit together or not at
    all — a unit is one REST insert (1 row) or one ``insert_batch`` slice
    (the ``/batch/events.json`` group), so a reader can never observe a
    torn unit."""

    __slots__ = ("sql", "rows", "error", "done", "trace")

    def __init__(self, sql: str, rows: list):
        self.sql = sql
        self.rows = rows
        self.error: Optional[BaseException] = None
        self.done = threading.Event()
        # the caller's ambient trace (if any), captured HERE because
        # submit() runs on the caller's thread — the committer thread
        # records its flush span into each unit's trace
        from predictionio_tpu.utils import tracing as _tracing

        self.trace = _tracing.current()

    # generous: a unit is at most one committer flush (~512 rows), but
    # it may queue behind a full backlog on a slow disk — this bound
    # exists to surface a wedged committer, not to deadline healthy I/O
    WAIT_S = 600.0

    def wait(self, timeout: Optional[float] = None) -> None:
        if not self.done.wait(self.WAIT_S if timeout is None else timeout):
            # the unit is NOT cancelled — it may still commit after this
            # raises, so the outcome is unknown, not "failed": a caller
            # that blind-retries could duplicate the event
            raise StorageError(
                "group-commit writer did not resolve within "
                f"{self.WAIT_S if timeout is None else timeout}s; "
                "outcome UNKNOWN (the batch may still commit) — "
                "investigate the committer before retrying"
            )
        if self.error is not None:
            raise self.error


class _GroupCommitter:
    """Per-shard group-commit thread: worker threads enqueue
    :class:`_InsertUnit`s on a bounded queue; this thread coalesces them
    into one multi-row transaction. Flush policy: at ``max_rows`` rows or
    ``max_delay_s`` after the batch opened, whichever first; a solo unit
    with an idle queue flushes immediately, so sequential callers pay no
    accumulation latency — batching kicks in exactly when concurrency
    exists. Callers block on ``unit.wait()``, so their ack still means
    the rows are committed (durable to the WAL)."""

    _STOP = object()

    # watchdog deadline for one flush: a healthy multi-row COMMIT is
    # milliseconds; a flush silent past this long while mid-batch flips
    # every in-process server's /readyz to 503 (utils/health.py). Class
    # attribute so tests (and operators with slow disks) can tune it
    # before opening storage.
    HEARTBEAT_DEADLINE_S = 30.0

    # admission control: at most this many queued units, and a submit
    # blocked longer than the admission window is REFUSED with
    # StorageSaturatedError instead of parking the caller's handler
    # thread behind a wedged committer (frontends answer it as 503 +
    # Retry-After). Class attributes so tests can shrink them before
    # opening storage.
    QUEUE_MAX_UNITS = 4096
    ADMIT_WAIT_S = 0.25

    def __init__(self, shard: "_ShardState", max_rows: int, max_delay_s: float):
        from predictionio_tpu.utils import health as _health
        from predictionio_tpu.utils import metrics as _metrics

        self._shard = shard
        self._max_rows = max(1, int(max_rows))
        self._max_delay_s = max(0.0, float(max_delay_s))
        self._q: "_queue.Queue[_InsertUnit]" = _queue.Queue(
            maxsize=self.QUEUE_MAX_UNITS
        )
        self._thread: Optional[threading.Thread] = None
        self._start_lock = threading.Lock()
        # per-shard flush accounting in the process-global registry
        # (labels carry the shard file name, so a K-sharded store shows
        # K series): flush count, rows per flush, commit latency
        reg = _metrics.get_registry()
        shard_name = os.path.basename(shard.path) or shard.path
        self._m_flushes = reg.counter(
            "pio_group_commit_flushes_total",
            "Group-commit flushes (one multi-row COMMIT each)",
            labels=("shard",),
        ).labels(shard=shard_name)
        self._m_flush_rows = reg.histogram(
            "pio_group_commit_flush_rows",
            "Rows coalesced into one group-commit flush",
            labels=("shard",),
            buckets=_metrics.ROW_COUNT_BUCKETS,
        ).labels(shard=shard_name)
        self._m_flush_seconds = reg.histogram(
            "pio_group_commit_flush_seconds",
            "Wall clock of one group-commit flush (execute + COMMIT)",
            labels=("shard",),
            buckets=_metrics.LATENCY_BUCKETS_S,
        ).labels(shard=shard_name)
        # daemon watchdog: busy exactly for the span of one flush, so a
        # wedged COMMIT (locked file, dead disk) reads as a stall while
        # an idle committer stays healthy. Keyed by shard file name like
        # the flush metrics — committers of one process that share a
        # basename share the verdict, which is what readiness wants.
        self._hb = _health.heartbeat(
            f"sqlite-committer:{shard_name}",
            deadline_s=self.HEARTBEAT_DEADLINE_S,
        )
        # a same-named heartbeat may predate this committer (an earlier
        # store in this process); the CURRENT class deadline wins
        self._hb.deadline_s = float(self.HEARTBEAT_DEADLINE_S)

    def close(self, timeout: float = 10.0) -> None:
        """Drain-and-stop: queued units ahead of the sentinel still
        commit, then the thread exits. Idempotent; a never-started
        committer has nothing to stop."""
        t = self._thread
        if t is None or not t.is_alive():
            return
        self._q.put(self._STOP)
        t.join(timeout)

    def submit(self, sql: str, rows: list) -> _InsertUnit:
        unit = _InsertUnit(sql, rows)
        if self._thread is None:
            with self._start_lock:
                if self._thread is None:
                    t = threading.Thread(
                        target=self._run, daemon=True,
                        name="sqlite-group-commit",
                    )
                    t.start()
                    self._thread = t
        try:
            # bounded admission: refuse (typed) rather than park the
            # caller unboundedly when the queue is saturated — REST
            # frontends turn the refusal into 503 + Retry-After
            self._q.put(unit, timeout=self.ADMIT_WAIT_S)
        except _queue.Full:
            from predictionio_tpu.utils import metrics as _metrics

            _metrics.get_registry().counter(
                "pio_group_commit_saturated_total",
                "Write submissions refused because the group-commit "
                "queue stayed full past the admission window "
                "(surfaced to clients as 503 + Retry-After)",
                labels=("shard",),
            ).labels(
                shard=os.path.basename(self._shard.path) or self._shard.path
            ).inc()
            raise base.StorageSaturatedError(
                f"group-commit queue for {self._shard.path!r} is "
                f"saturated ({self.QUEUE_MAX_UNITS} queued units); "
                "the write was NOT accepted — retry after backoff",
                retry_after_s=1.0,
            )
        return unit

    def _run(self) -> None:
        while True:
            try:
                if not self._drain_one_batch():
                    return  # close() sentinel
            except BaseException:  # the loop must survive anything —
                # but never silently: an exception here (outside
                # _commit_batch's own handling) means some units may
                # never resolve and their callers will time out
                logger.exception(
                    "group-commit loop error; queued units may be lost"
                )
                continue

    def _drain_one_batch(self) -> bool:
        unit = self._q.get()
        if unit is self._STOP:
            return False
        batch = [unit]
        n = len(unit.rows)
        deadline = _time.monotonic() + self._max_delay_s
        while n < self._max_rows:
            try:
                nxt = self._q.get_nowait()
            except _queue.Empty:
                if len(batch) == 1:
                    break  # solo unit, idle queue: zero added latency
                remaining = deadline - _time.monotonic()
                if remaining <= 0:
                    break
                try:
                    nxt = self._q.get(timeout=remaining)
                except _queue.Empty:
                    break
            if nxt is self._STOP:
                self._q.put(nxt)  # commit this batch, stop next round
                break
            batch.append(nxt)
            n += len(nxt.rows)
        self._commit_batch(batch)
        return True

    def _commit_batch(self, batch: list) -> None:
        from predictionio_tpu.utils import tracing as _tracing
        from predictionio_tpu.utils.compilation_cache import compile_site

        t0 = _time.perf_counter()
        t0_wall = _time.time()
        shard = self._shard
        # the flush is a latency-critical site: an executable compile
        # in here (nothing should compile during an ingest flush, which
        # is exactly why one must be loudly attributable) counts in
        # pio_cold_compiles_total{site="ingest"}
        with self._hb.busy(), compile_site("ingest"), shard.lock:
            try:
                for u in batch:
                    shard.conn.executemany(u.sql, u.rows)
                fault = shard.commit_fault  # test-only crash injection
                if fault is not None:
                    fault()
                shard.conn.commit()
            except BaseException as e:
                try:
                    shard.conn.rollback()
                except sqlite3.Error:
                    pass
                if len(batch) == 1:
                    batch[0].error = e
                else:
                    # poison isolation: replay each unit as its own
                    # transaction so one bad unit cannot fail its
                    # coalesced neighbors; each replay stays unit-atomic
                    # and consults the fault hook too, so crash tests
                    # can abort coalesced batches, not just solo units
                    for u in batch:
                        try:
                            shard.conn.executemany(u.sql, u.rows)
                            fault = shard.commit_fault
                            if fault is not None:
                                fault()
                            shard.conn.commit()
                        except BaseException as ue:
                            try:
                                shard.conn.rollback()
                            except sqlite3.Error:
                                pass
                            u.error = ue
            finally:
                # bookkeeping BEFORE done.set(): a caller unblocked by
                # its unit must observe the flush span/counters of the
                # COMMIT that acked it (and never block on a recording
                # failure)
                try:
                    elapsed = _time.perf_counter() - t0
                    n_rows = sum(len(u.rows) for u in batch)
                    self._m_flushes.inc()
                    self._m_flush_rows.observe(n_rows)
                    self._m_flush_seconds.observe(elapsed)
                    for u in batch:
                        if u.trace is not None:
                            _tracing.record_span(
                                "group-commit-flush", u.trace.trace_id,
                                parent_id=u.trace.span_id, start_s=t0_wall,
                                duration_s=elapsed,
                                attrs={"rows": n_rows, "units": len(batch)},
                            )
                except Exception:
                    logger.exception("group-commit flush bookkeeping failed")
                for u in batch:
                    u.done.set()


class _ShardState:
    """One event-row write slot: a sqlite connection, its lock, its
    thread-local WAL snapshot read connections, and its group committer.
    The main database file is wrapped in one of these (sharing the
    client's connection and lock); with ``SHARDS`` > 1, each shard file
    gets an independent one — an independent WAL write slot."""

    def __init__(
        self,
        path: str,
        conn: sqlite3.Connection,
        lock,
        gc_rows: int,
        gc_delay_s: float,
    ):
        self.path = path
        self.conn = conn
        self.lock = lock
        self._read_local = threading.local()
        # memoized POSITIVE table-existence results (see _exists_memo)
        self.known_tables: set = set()
        # test-only fault injection: called between the batch's last
        # execute and its COMMIT (crash-consistency tests)
        self.commit_fault = None
        self.committer = _GroupCommitter(self, gc_rows, gc_delay_s)

    @staticmethod
    def open(path: str, gc_rows: int, gc_delay_s: float) -> "_ShardState":
        return _ShardState(
            path, _open_wal_conn(path), threading.RLock(), gc_rows,
            gc_delay_s,
        )

    def execute(self, sql: str, params=()) -> _LockedCursor:
        return _LockedCursor(self, sql, params)

    def commit(self) -> None:
        with self.lock:
            self.conn.commit()

    def read_execute(self, sql: str, params=()):
        """Run a read-only statement on a thread-local WAL connection —
        no writer lock held, so long scans and concurrent writes overlap.
        Returns a live cursor (fetchone/fetchall). :memory: databases are
        not shareable across connections and fall back to the locked
        shared connection.

        Because the existence check and the read no longer share one lock
        scope, a concurrent table drop (app delete) can surface here as
        sqlite's raw OperationalError — it is re-raised as StorageError so
        read paths keep their documented error contract."""
        if self.path == ":memory:":
            return self.execute(sql, params)
        conn = getattr(self._read_local, "conn", None)
        if conn is None:
            conn = sqlite3.connect(self.path)
            conn.execute("PRAGMA busy_timeout=5000")
            conn.execute("PRAGMA query_only=ON")
            self._read_local.conn = conn
        try:
            return conn.execute(sql, params)
        except sqlite3.OperationalError as e:
            if "no such table" in str(e):
                raise StorageError(str(e)) from e
            raise

    def read_snapshot(self, stmts):
        """Run several read statements inside ONE read transaction, so
        they observe a single WAL snapshot — the segment-tier scans need
        the compaction watermark and the segment manifest to be a
        consistent pair (a compaction commits both in one transaction;
        two autocommit reads could straddle it and double- or
        zero-count the sealed rows). Returns a list of fetchall lists.
        :memory: databases fall back to the shared locked connection
        (writes serialize on the same lock, so the pair is consistent
        there too)."""
        if self.path == ":memory:":
            with self.lock:
                return [
                    self.conn.execute(sql, params).fetchall()
                    for sql, params in stmts
                ]
        conn = self.read_execute("SELECT 1").connection
        out = []
        conn.execute("BEGIN")
        try:
            for sql, params in stmts:
                try:
                    out.append(conn.execute(sql, params).fetchall())
                except sqlite3.OperationalError as e:
                    if "no such table" in str(e):
                        raise StorageError(str(e)) from e
                    raise
        finally:
            conn.execute("COMMIT")
        return out

    def has_table(self, table: str) -> bool:
        """Memoized (positive results only) existence probe against THIS
        shard's file; a table created later must be seen, so negatives
        re-probe."""
        if table in self.known_tables:
            return True
        row = self.execute(
            "SELECT name FROM sqlite_master WHERE type='table' AND name=?",
            (table,),
        ).fetchone()
        if row is not None:
            self.known_tables.add(table)
            return True
        return False

    def submit_rows(self, sql: str, rows: list) -> _InsertUnit:
        """Hand rows to the group committer; returns the unit to wait
        on. The caller sees the commit (or the unit's error) via
        ``unit.wait()``."""
        return self.committer.submit(sql, rows)


class StorageClient(base.DAOCacheMixin):
    """Shared sqlite connection per source (reference caches clients per
    source name, Storage.scala:202-208). ``check_same_thread=False`` plus a
    lock serializes WRITE access from REST worker threads; bulk reads run
    on per-thread WAL snapshot connections (``read_execute``), so a
    training scan never blocks ingest and ingest never stalls a scan —
    the concurrency role of the reference's HBase client pool +
    region-parallel reads (hbase/StorageClient.scala:40,
    HBPEvents.scala:84-90).

    Source properties (``PIO_STORAGE_SOURCES_<NAME>_<KEY>``):

    - ``PATH``: database file (default ``<fs_basedir>/storage.db``)
    - ``SHARDS``: event-row shard count K (default 1). K > 1 opens K
      extra files ``<PATH>.shard<k>``, each an independent WAL write
      slot with its own group committer; single-event inserts hash to a
      shard by entity id (module docstring).
    - ``GROUP_COMMIT_EVENTS`` / ``GROUP_COMMIT_MS``: committer flush
      thresholds — rows per transaction (default 512) and max
      accumulation window in ms once a batch has ≥ 2 units (default 2).
    """

    def __init__(self, config=None):
        self.config = config
        props = getattr(config, "properties", {}) or {}
        path = props.get("PATH") or os.path.join(
            fs_basedir(),
            "storage.db",
        )
        if path != ":memory:":
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        self.path = path
        self.conn = _open_wal_conn(path)
        self.lock = threading.RLock()
        self._init_dao_cache(self.lock)
        self.shard_count = self._pin_shard_count(
            max(1, int(props.get("SHARDS", 1) or 1))
        )
        gc_rows = int(props.get("GROUP_COMMIT_EVENTS", 512) or 512)
        gc_delay_s = float(props.get("GROUP_COMMIT_MS", 2.0) or 0.0) / 1e3
        # unit-atomicity granularity: batches up to this many rows per
        # shard commit as ONE unit; larger slices (bulk imports through
        # write()) split into chunks so no single unit can outgrow a
        # committer flush (see SQLiteLEvents.insert_batch)
        self.gc_rows = max(1, gc_rows)
        # the main file as a write slot (shares this conn + lock): the
        # K==1 write target, and always scanned as the legacy/residual
        # row store
        self.main_store = _ShardState(
            self.path, self.conn, self.lock, gc_rows, gc_delay_s
        )
        if self.shard_count <= 1:
            self.event_shards = [self.main_store]
        else:
            self.event_shards = [
                _ShardState.open(
                    ":memory:" if path == ":memory:"
                    else f"{path}.shard{k}",
                    gc_rows, gc_delay_s,
                )
                for k in range(self.shard_count)
            ]

    def _pin_shard_count(self, configured: int) -> int:
        """The shard count is part of the DATA layout (crc32 % K routes
        every entity), so it is pinned in the main file at first use and
        validated on every open: reopening a K-sharded database with a
        different K (or none) would silently hide the shard files' rows
        from every scan, or re-route entities away from their history.
        Changing K requires export + re-import. Read-only files (and
        pre-pin single-file databases) skip the pin and keep K=1
        semantics."""
        try:
            with self.lock:
                self.conn.execute(
                    "CREATE TABLE IF NOT EXISTS pio_shard_meta ("
                    "key TEXT PRIMARY KEY, value TEXT)"
                )
                # OR IGNORE: multi-process workers (SO_REUSEPORT) race
                # this first-open write; losers read the winner's pin
                self.conn.execute(
                    "INSERT OR IGNORE INTO pio_shard_meta VALUES "
                    "('shard_count', ?)",
                    (str(configured),),
                )
                self.conn.commit()
                row = self.conn.execute(
                    "SELECT value FROM pio_shard_meta WHERE key='shard_count'"
                ).fetchone()
        except sqlite3.OperationalError:
            # e.g. a read-only database file: honor the configuration
            # (reads of a sharded db still need the right K to fan out)
            return configured
        pinned = int(row[0])
        if pinned == configured:
            return pinned
        if pinned == 1:
            # 1 -> K is the safe upgrade: every existing row is in the
            # main file, which is always scanned first, and no entity
            # has shard-file history to be re-routed away from
            with self.lock:
                self.conn.execute(
                    "UPDATE pio_shard_meta SET value=? "
                    "WHERE key='shard_count'",
                    (str(configured),),
                )
                self.conn.commit()
            return configured
        raise StorageError(
            f"database {self.path!r} was sharded with SHARDS={pinned} "
            f"but is being opened with SHARDS={configured}; the shard "
            "count routes entities to files and cannot change in place "
            "once rows exist in shard files — reopen with "
            f"SHARDS={pinned}, or export and re-import to re-shard"
        )

    def close(self) -> None:
        """Stop every shard's committer (draining queued units) and
        close the shard + main connections. For embedders that own a
        Storage universe's lifecycle; the module-default client lives
        for the process."""
        for shard in self.event_shards:
            shard.committer.close()
        if self.main_store not in self.event_shards:
            self.main_store.committer.close()
        for shard in self.event_shards:
            if shard is not self.main_store:
                with shard.lock:
                    shard.conn.close()
        with self.lock:
            self.conn.close()

    def shard_index_for(self, entity_id) -> int:
        """Stable entity→shard hash (crc32, not ``hash()`` — per-process
        salting would scatter one entity across files between runs)."""
        if self.shard_count <= 1:
            return 0
        return zlib.crc32(str(entity_id).encode("utf-8")) % self.shard_count

    def shard_for(self, entity_id) -> _ShardState:
        return self.event_shards[self.shard_index_for(entity_id)]

    def row_stores(self) -> List[_ShardState]:
        """Every store holding event ROWS, scan order: the main file
        first (legacy/pre-sharding rows), then the hash shards."""
        if self.shard_count <= 1:
            return [self.main_store]
        return [self.main_store] + self.event_shards

    def execute(self, sql: str, params=()) -> _LockedCursor:
        return _LockedCursor(self, sql, params)

    def read_execute(self, sql: str, params=()):
        """Snapshot read against the MAIN file (see
        :meth:`_ShardState.read_execute`)."""
        return self.main_store.read_execute(sql, params)

    def commit(self) -> None:
        with self.lock:
            self.conn.commit()

def _pent_dtype():
    """One row of the pages' entity index: the row's number in its
    page, its target's dictionary code, its value, its time (20 bytes,
    packed)."""
    import numpy as np

    return np.dtype(
        [("idx", "<i4"), ("target", "<i4"), ("val", "<f4"), ("ms", "<i8")]
    )


_GEN_SCHEMA = (
    "CREATE TABLE IF NOT EXISTS pio_table_gen "
    "(tbl TEXT PRIMARY KEY, gen INTEGER NOT NULL)"
)


def _table_name(namespace: str, suffix: str) -> str:
    ns = "".join(c if c.isalnum() else "_" for c in (namespace or "pio"))
    return f"{ns}_{suffix}"


class _StaleWatermark(Exception):
    """Another compactor advanced this store's watermark first; the
    round's files are abandoned (optimistic concurrency)."""


class SQLiteLEvents(base.LEvents):
    def __init__(self, client: StorageClient, config=None, namespace: str = ""):
        self._c = client
        self._ns = namespace or "pio"
        self._pages_schema_ok: set = set()
        self._seg_schema_ok: set = set()
        # path -> SegmentData, LRU (see _open_segment); segment files
        # are immutable, so entries never go stale (remove()/app delete
        # clears them)
        from collections import OrderedDict

        self._seg_cache: "OrderedDict[str, object]" = OrderedDict()
        # table -> the dictionary's names by code, as far as this
        # process has read them. Codes are given once and never change
        # (AUTOINCREMENT, no update, no delete short of remove()), so
        # what is cached stays true whoever writes; a code past the end
        # reads the new tail
        self._dict_cache: Dict[str, list] = {}
        # what the reads by entity cost, for tests and the curious:
        # pages whose blobs were decoded, rows of the entity index read
        self.read_stats = {"pages_decoded": 0, "index_rows": 0}
        # test-only crash injection: called between segment-file write
        # and the manifest commit (compaction crash-consistency tests)
        self.compact_fault = None

    def _ensure_pages_schema(self, t: str) -> None:
        """Migrate page tables from older layouts (memoized per table):
        databases whose events table predates the page store get the
        _pages/_dict tables created here (init() never re-runs for an
        existing app), and page tables created before a column existed
        are ALTERed (additive-only)."""
        if t in self._pages_schema_ok:
            return
        with self._c.lock:
            if not self._exists(t):
                # app never init()ed — read paths must stay read-only and
                # must not plant orphan page tables (do not memoize: the
                # app may be init()ed later)
                return
            try:
                # IF NOT EXISTS both statements: a no-op on an up-to-date
                # database, and self-heals one where only part of the
                # page schema was ever committed
                self._create_page_tables(t)
                self._c.commit()
            except sqlite3.OperationalError:
                # e.g. a read-only database file: reads proceed
                # (page-path callers guard on table existence);
                # writes surface sqlite's own error at INSERT time
                return
            cols = {
                row[1]
                for row in self._c.execute(
                    f"PRAGMA table_info({t}_pages)"
                ).fetchall()
            }
            if "dead" not in cols:
                self._c.execute(f"ALTER TABLE {t}_pages ADD COLUMN dead BLOB")
                self._c.commit()
            self._pages_schema_ok.add(t)

    def _events_table(self, app_id: int, channel_id: Optional[int]) -> str:
        name = _table_name(self._ns, f"events_{int(app_id)}")
        if channel_id is not None:
            name += f"_{int(channel_id)}"
        return name

    @staticmethod
    def _create_row_table(store, t: str) -> None:
        """Event-row DDL, identical in the main file and every shard
        file. Caller holds the store's lock.

        ``rid INTEGER PRIMARY KEY AUTOINCREMENT`` makes rowids strictly
        monotonic for the table's whole lifetime (sqlite_sequence keeps
        the high-water mark across deletes): the compaction tier's
        per-store watermark — "rowids <= W are sealed into segments" —
        stays sound even after every row below it is physically
        deleted, because no future insert can ever be assigned a rowid
        under W. Tables created before this schema (plain implicit
        rowid) are migrated on their first compaction
        (:meth:`_ensure_monotonic_rowids`)."""
        store.conn.execute(
            f"""CREATE TABLE IF NOT EXISTS {t} (
                rid INTEGER PRIMARY KEY AUTOINCREMENT,
                id TEXT UNIQUE NOT NULL,
                event TEXT NOT NULL,
                entity_type TEXT NOT NULL,
                entity_id TEXT NOT NULL,
                target_entity_type TEXT,
                target_entity_id TEXT,
                properties TEXT,
                event_time TEXT NOT NULL,
                event_time_ms INTEGER NOT NULL,
                tags TEXT,
                pr_id TEXT,
                creation_time TEXT NOT NULL
            )"""
        )
        store.conn.execute(
            f"CREATE INDEX IF NOT EXISTS {t}_time ON {t} (event_time_ms)"
        )
        store.conn.execute(
            f"CREATE INDEX IF NOT EXISTS {t}_entity ON {t} "
            f"(entity_type, entity_id, event_time_ms)"
        )

    def init(self, app_id: int, channel_id: Optional[int] = None) -> bool:
        t = self._events_table(app_id, channel_id)
        with self._c.lock:
            self._create_row_table(self._c.main_store, t)
            self._create_page_tables(t)
            self._c.commit()
        for shard in self._c.event_shards:
            if shard is self._c.main_store:
                continue
            with shard.lock:
                self._create_row_table(shard, t)
                shard.conn.commit()
        return True

    def _create_page_tables(self, t: str) -> None:
        """Columnar page store DDL (see data/storage/columnar.py): bulk
        imports land here as dictionary-encoded numpy blobs — the role of
        the reference's HBase regions feeding partitioned columnar scans
        (hbase/HBPEvents.scala:84-90). Single-event inserts keep using
        the row table; scans merge both. Caller holds the lock."""
        self._c.execute(
            f"""CREATE TABLE IF NOT EXISTS {t}_pages (
                page INTEGER PRIMARY KEY AUTOINCREMENT,
                event TEXT NOT NULL,
                entity_type TEXT NOT NULL,
                target_entity_type TEXT NOT NULL,
                prop TEXT NOT NULL,
                n INTEGER NOT NULL,
                min_ms INTEGER NOT NULL,
                max_ms INTEGER NOT NULL,
                entities BLOB NOT NULL,
                targets BLOB NOT NULL,
                vals BLOB NOT NULL,
                times BLOB NOT NULL,
                dead BLOB
            )"""
        )
        self._c.execute(
            f"""CREATE TABLE IF NOT EXISTS {t}_dict (
                id INTEGER PRIMARY KEY AUTOINCREMENT,
                name TEXT UNIQUE NOT NULL
            )"""
        )
        # the pages' entity index (build_entity_index): one row for each
        # (entity code, page) pair that has events, holding that
        # entity's rows of that page packed (row number, target code,
        # value, time), so a read by entity costs the entity's events
        # and decodes no page. _pent_pages lists the pages it covers.
        self._c.execute(
            f"""CREATE TABLE IF NOT EXISTS {t}_pent (
                ecode INTEGER NOT NULL,
                page INTEGER NOT NULL,
                packed BLOB NOT NULL,
                PRIMARY KEY (ecode, page)
            ) WITHOUT ROWID"""
        )
        self._c.execute(
            f"CREATE TABLE IF NOT EXISTS {t}_pent_pages "
            f"(page INTEGER PRIMARY KEY)"
        )

    def remove(self, app_id: int, channel_id: Optional[int] = None) -> bool:
        t = self._events_table(app_id, channel_id)
        # collect segment file paths before the manifest drops
        seg_paths: List[str] = []
        if self._c.main_store.has_table(f"{t}_segments"):
            try:
                seg_paths = [
                    r[0]
                    for r in self._c.execute(
                        f"SELECT path FROM {t}_segments"
                    ).fetchall()
                ]
            except StorageError:
                pass
        with self._c.lock:
            self._c.execute(f"DROP TABLE IF EXISTS {t}")
            self._c.execute(f"DROP TABLE IF EXISTS {t}_pages")
            self._c.execute(f"DROP TABLE IF EXISTS {t}_dict")
            self._c.execute(f"DROP TABLE IF EXISTS {t}_pent")
            self._c.execute(f"DROP TABLE IF EXISTS {t}_pent_pages")
            self._dict_cache.pop(t, None)
            self._c.execute(f"DROP TABLE IF EXISTS {t}_segments")
            self._c.execute(f"DROP TABLE IF EXISTS {t}_compaction")
            # bump the table GENERATION: DROP resets the AUTOINCREMENT
            # sequence, so without this a delta cursor taken before a
            # wipe-and-reimport of a same-sized dataset could validate
            # against the recreated table and serve the stale wire
            self._c.execute(_GEN_SCHEMA)
            self._c.execute(
                "INSERT INTO pio_table_gen (tbl, gen) VALUES (?, 2) "
                "ON CONFLICT(tbl) DO UPDATE SET gen = gen + 1",
                (t,),
            )
            self._c.commit()
            self._c.main_store.known_tables.discard(t)
            self._c.main_store.known_tables.discard(f"{t}_segments")
            self._seg_schema_ok.discard(t)
        for path in seg_paths:
            self._seg_cache.pop(path, None)
            try:
                os.remove(path)
            except OSError:
                pass
        for shard in self._c.event_shards:
            if shard is self._c.main_store:
                continue
            with shard.lock:
                shard.conn.execute(f"DROP TABLE IF EXISTS {t}")
                shard.conn.commit()
                shard.known_tables.discard(t)
        return True

    def close(self) -> None:
        pass

    def _exists(self, table: str) -> bool:
        cur = self._c.execute(
            "SELECT name FROM sqlite_master WHERE type='table' AND name=?", (table,)
        )
        return cur.fetchone() is not None

    def _exists_memo(self, table: str) -> bool:
        """_exists with positive-result memoization for hot write paths:
        the per-event sqlite_master probe was a measurable share of REST
        ingest. Only positive results memoize (a table created later must
        be seen); remove() invalidates. A table dropped by ANOTHER
        process after memoization surfaces as StorageError from the
        statement itself rather than this probe."""
        return self._c.main_store.has_table(table)

    def _ensure_shard_table(self, shard: _ShardState, t: str) -> None:
        """Shard files are populated lazily: a database init()ed before
        sharding was enabled (or before this app existed) gets the row
        table created in the shard on first write to it. The MAIN file's
        table is the authority on whether the app is initialized — this
        is only reached after that check passed."""
        if shard is self._c.main_store or shard.has_table(t):
            return
        with shard.lock:
            self._create_row_table(shard, t)
            shard.conn.commit()
            shard.known_tables.add(t)

    # event-row column list (no rid): names both the insert slots and
    # every row SELECT, so the schema can carry the rid column without
    # positional drift between old and migrated tables
    _ROW_COLS = (
        "id, event, entity_type, entity_id, target_entity_type, "
        "target_entity_id, properties, event_time, event_time_ms, tags, "
        "pr_id, creation_time"
    )
    _INSERT_SQL = (
        "INSERT OR REPLACE INTO {t} ("
        "id, event, entity_type, entity_id, target_entity_type, "
        "target_entity_id, properties, event_time, event_time_ms, tags, "
        "pr_id, creation_time) VALUES (?,?,?,?,?,?,?,?,?,?,?,?)"
    )

    @staticmethod
    def _event_row(event: Event, eid: str) -> tuple:
        return (
            eid,
            event.event,
            event.entity_type,
            event.entity_id,
            event.target_entity_type,
            event.target_entity_id,
            json.dumps(event.properties.to_json()),
            format_iso8601(event.event_time),
            _ms(event.event_time),
            json.dumps(list(event.tags)),
            event.pr_id,
            format_iso8601(event.creation_time),
        )

    def _scrub_duplicate_ids(self, t: str, spares) -> None:
        """INSERT OR REPLACE only replaces within ONE file — a client
        re-posting an EXPLICIT event id whose old row lives in another
        row store (pre-sharding main rows, or the same id re-posted with
        a different entity) would otherwise leave a stale duplicate that
        get() keeps returning. ``spares`` is ``[(event_id, keep_store)]``;
        each id is deleted from every OTHER row store in one batched
        transaction per store. Called AFTER the replacement row's commit:
        a failed insert then never loses the old row (the reverse order
        could drop the event entirely), at the price that a crash in the
        narrow window between commit and scrub leaves a duplicate of an
        explicitly re-posted id — duplicates over data loss. Explicit ids
        are the rare path (imports, updates); server-generated ids never
        pay this probe."""
        if not spares:
            return
        for store in self._c.row_stores():
            ids = [eid for eid, keep in spares if keep is not store]
            if not ids or not store.has_table(t):
                continue
            with store.lock:
                deleted = False
                for s in range(0, len(ids), 500):  # bound-param headroom
                    part = ids[s : s + 500]
                    cur = store.conn.execute(
                        f"DELETE FROM {t} WHERE id IN "
                        f"({','.join('?' * len(part))})",
                        part,
                    )
                    deleted = deleted or cur.rowcount > 0
                if deleted:
                    store.conn.commit()
                else:
                    store.conn.rollback()
        # a compacted copy of a re-posted id lives in an immutable
        # segment, out of DELETE's reach — tombstone it in the manifest
        # (explicit ids are the rare path; server-generated ids never
        # reach here)
        self._tombstone_segment_ids(t, [eid for eid, _ in spares])

    def insert(self, event: Event, app_id: int, channel_id: Optional[int] = None) -> str:
        """Single-event insert through the per-shard GROUP COMMITTER: the
        row is enqueued, the shard's committer coalesces it with whatever
        else is in flight into one transaction, and this call returns
        after that transaction's COMMIT — the returned id is durable (to
        the WAL) exactly as before, but N concurrent inserts now pay one
        commit, not N."""
        t = self._events_table(app_id, channel_id)
        eid = event.event_id or new_event_id()
        if not self._exists_memo(t):
            raise StorageError(f"events table {t} not initialized")
        shard = self._c.shard_for(event.entity_id)
        self._ensure_shard_table(shard, t)
        shard.submit_rows(
            self._INSERT_SQL.format(t=t), [self._event_row(event, eid)]
        ).wait()
        if event.event_id:
            self._scrub_duplicate_ids(t, [(eid, shard)])
        return eid

    def insert_batch(
        self,
        events: Sequence[Event],
        app_id: int,
        channel_id: Optional[int] = None,
    ) -> List[str]:
        """Batch insert (the ``/batch/events.json`` path): the batch is
        split by shard and each shard's slice rides the group committer
        as an atomic unit — a reader can never observe part of a unit.
        Slices larger than ``GROUP_COMMIT_EVENTS`` rows (bulk imports
        through ``write()``) split into chunked units of that size, so
        no unit can outgrow a committer flush; the <=50-event REST batch
        is always one unit per shard. With K > 1 a batch spanning shards
        is atomic PER SHARD, not globally — a failure after some shards
        committed raises :class:`PartialBatchError` naming exactly which
        event ids did NOT land, so the REST route reports per-event
        outcomes. Shard slices commit in parallel; this returns after
        every slice resolves."""
        events = list(events)
        if not events:
            return []
        t = self._events_table(app_id, channel_id)
        if not self._exists_memo(t):
            raise StorageError(f"events table {t} not initialized")
        eids = [e.event_id or new_event_id() for e in events]
        # duplicate EXPLICIT ids within one batch are last-wins, exactly
        # like single-file INSERT OR REPLACE: earlier occurrences never
        # reach a shard, so the post-commit scrub can't delete the
        # survivor from its own store
        last_slot: Dict[str, int] = {
            eid: j
            for j, (event, eid) in enumerate(zip(events, eids))
            if event.event_id
        }
        by_shard: Dict[int, list] = {}  # shard idx -> [(row, eid)]
        explicit: list = []  # (eid, keep_store) to scrub post-commit
        for j, (event, eid) in enumerate(zip(events, eids)):
            if event.event_id and last_slot[eid] != j:
                continue  # superseded later in this same batch
            k = self._c.shard_index_for(event.entity_id)
            if event.event_id:
                explicit.append((eid, self._c.event_shards[k]))
            by_shard.setdefault(k, []).append((self._event_row(event, eid), eid))
        sql = self._INSERT_SQL.format(t=t)
        chunk = self._c.gc_rows
        units: list = []  # (unit, [eids])
        # bounded admission can refuse a LATER unit after earlier units
        # of this same batch were enqueued (and will commit). A bare
        # StorageSaturatedError here would tell the caller "nothing was
        # admitted — retry the whole batch", and a retry of auto-id
        # events would re-insert the committed slices under fresh ids.
        # So the refusal is only propagated as-is when NO unit made it
        # into a queue; otherwise the refused/unsubmitted slices join
        # the PartialBatchError's failed set (marked retryable-after-
        # backoff) after the enqueued units resolve.
        unsubmitted: list = []  # eids of slices never enqueued
        admit_error: Optional[base.StorageSaturatedError] = None
        for k, pairs in by_shard.items():
            shard = self._c.event_shards[k]
            self._ensure_shard_table(shard, t)
            for s in range(0, len(pairs), chunk):
                part = pairs[s : s + chunk]
                if admit_error is not None:
                    unsubmitted.extend(eid for _, eid in part)
                    continue
                try:
                    units.append(
                        (
                            shard.submit_rows(
                                sql, [row for row, _ in part]
                            ),
                            [eid for _, eid in part],
                        )
                    )
                except base.StorageSaturatedError as e:
                    admit_error = e
                    unsubmitted.extend(eid for _, eid in part)
        if admit_error is not None and not units:
            raise admit_error  # truly nothing admitted: batch-retry safe
        failed: list = []
        first_error: Optional[BaseException] = None
        for unit, unit_eids in units:
            try:
                unit.wait()
            except BaseException as e:
                failed.extend(unit_eids)
                if first_error is None:
                    first_error = e
        failed.extend(unsubmitted)
        # scrub explicit ids only where the REPLACEMENT actually landed
        # (a failed unit must keep the old copy — see _scrub_duplicate_ids)
        failed_set = set(failed)
        self._scrub_duplicate_ids(
            t, [(eid, keep) for eid, keep in explicit if eid not in failed_set]
        )
        if first_error is not None or admit_error is not None:
            err = first_error if first_error is not None else admit_error
            if len(failed) == len(eids):
                raise err  # nothing landed: plain error
            raise PartialBatchError(
                f"{len(failed)}/{len(eids)} batch events failed to "
                f"commit: {err}",
                event_ids=eids,
                failed_ids=failed,
                # the backoff hint marks EVERY failed slot as a
                # capacity refusal, so it is only attached when no
                # unit failed hard — a mixed batch must not label
                # commit failures as 503-retryable saturation
                retry_after_s=(
                    admit_error.retry_after_s
                    if admit_error is not None and first_error is None
                    else None
                ),
            ) from err
        return eids

    @staticmethod
    def _row_to_event(row) -> Event:
        return Event(
            event_id=row[0],
            event=row[1],
            entity_type=row[2],
            entity_id=row[3],
            target_entity_type=row[4],
            target_entity_id=row[5],
            properties=DataMap(json.loads(row[6]) if row[6] else {}),
            event_time=parse_iso8601(row[7]),
            tags=tuple(json.loads(row[9]) if row[9] else ()),
            pr_id=row[10],
            creation_time=parse_iso8601(row[11]),
        )

    @staticmethod
    def _parse_page_id(event_id: str):
        """Bulk-imported events carry synthetic ids ``pg-<page>-<idx>``."""
        if not event_id.startswith("pg-"):
            return None
        try:
            _, page, idx = event_id.split("-", 2)
            return int(page), int(idx)
        except ValueError:
            return None

    def _get_page_event(
        self, t: str, page: int, idx: int
    ) -> Optional[Event]:
        import numpy as np

        self._ensure_pages_schema(t)
        with self._c.lock:
            if not self._exists(f"{t}_pages"):
                return None
            row = self._c.execute(
                f"SELECT event, entity_type, target_entity_type, prop, n, "
                f"entities, targets, vals, times, dead "
                f"FROM {t}_pages WHERE page=?",
                (page,),
            ).fetchone()
        if row is None or idx >= row[4]:
            return None
        ev, et, tet, prop, n, eb, gb, vb, tb, db = row
        if db is not None and np.frombuffer(db, np.uint8)[idx]:
            return None  # tombstoned
        names = self._dict_names(t)
        when = _dt.datetime.fromtimestamp(
            int(np.frombuffer(tb, np.int64)[idx]) / 1000.0, _dt.timezone.utc
        )
        return Event(
            event_id=f"pg-{page}-{idx}",
            event=ev,
            entity_type=et,
            entity_id=names[np.frombuffer(eb, np.int32)[idx]],
            target_entity_type=tet,
            target_entity_id=names[np.frombuffer(gb, np.int32)[idx]],
            properties=DataMap(
                {prop: float(np.frombuffer(vb, np.float32)[idx])}
            ),
            event_time=when,
            creation_time=when,
        )

    def get(
        self, event_id: str, app_id: int, channel_id: Optional[int] = None
    ) -> Optional[Event]:
        t = self._events_table(app_id, channel_id)
        pg = self._parse_page_id(event_id)
        if pg is not None:
            return self._get_page_event(t, *pg)
        with self._c.lock:
            if not self._exists(t):
                raise StorageError(f"events table {t} not initialized")
        # event ids don't encode their shard (the entity hash needs the
        # entity id), so probe each row store; K is small and the id
        # column is the primary key
        for store in self._c.row_stores():
            if not store.has_table(t):
                continue
            row = store.execute(
                f"SELECT {self._ROW_COLS} FROM {t} WHERE id=?", (event_id,)
            ).fetchone()
            if row:
                return self._row_to_event(row)
        # compacted events keep their original ids inside segment files
        return self._get_segment_event(t, event_id)

    def _delete_page_event(self, t: str, page: int, idx: int) -> bool:
        """Delete one row of a page by marking its tombstone bit. The
        page is never compacted, so the positional event ids
        (``pg-<page>-<idx>``) of the surviving rows stay STABLE — a
        compaction would silently re-address later rows, making a second
        delete remove the wrong event. A fully-dead page is dropped."""
        import numpy as np

        self._ensure_pages_schema(t)
        with self._c.lock:
            if not self._exists(f"{t}_pages"):
                return False
            row = self._c.execute(
                f"SELECT n, dead FROM {t}_pages WHERE page=?", (page,)
            ).fetchone()
            if row is None or idx >= row[0]:
                return False
            n, dead_blob = row
            dead = (
                np.frombuffer(dead_blob, np.uint8).copy()
                if dead_blob is not None
                else np.zeros(n, np.uint8)
            )
            if dead[idx]:
                return False  # already deleted
            dead[idx] = 1
            if int(dead.sum()) == n:
                self._c.conn.execute(
                    f"DELETE FROM {t}_pages WHERE page=?", (page,)
                )
            else:
                self._c.conn.execute(
                    f"UPDATE {t}_pages SET dead=? WHERE page=?",
                    (dead.tobytes(), page),
                )
            self._c.conn.commit()
            return True

    def delete(
        self, event_id: str, app_id: int, channel_id: Optional[int] = None
    ) -> bool:
        t = self._events_table(app_id, channel_id)
        pg = self._parse_page_id(event_id)
        if pg is not None:
            return self._delete_page_event(t, *pg)
        with self._c.lock:
            if not self._exists(t):
                raise StorageError(f"events table {t} not initialized")
        # deletes are rare: a direct per-store transaction, not the
        # group committer (same shard probe rationale as get()). A
        # sealed copy may ALSO exist in the segment tier (always, after
        # compaction; plus a grace-window row copy) — tombstone it too,
        # or the event would resurface on the next scan.
        deleted = False
        for store in self._c.row_stores():
            if not store.has_table(t):
                continue
            with store.lock:
                cur = store.conn.execute(
                    f"DELETE FROM {t} WHERE id=?", (event_id,)
                )
                store.conn.commit()
            if cur.rowcount > 0:
                deleted = True
                break
        return self._tombstone_segment_ids(t, [event_id]) or deleted

    @staticmethod
    def _find_clauses(
        start_time, until_time, entity_type, entity_id, event_names,
        target_entity_type, target_entity_id,
    ):
        clauses: List[str] = []
        params: list = []
        if start_time is not None:
            clauses.append("event_time_ms >= ?")
            params.append(_ms(start_time))
        if until_time is not None:
            clauses.append("event_time_ms < ?")
            params.append(_ms(until_time))
        if entity_type is not None:
            clauses.append("entity_type = ?")
            params.append(entity_type)
        if entity_id is not None:
            clauses.append("entity_id = ?")
            params.append(entity_id)
        if event_names is not None:
            if event_names:
                clauses.append(
                    "event IN (" + ",".join("?" * len(event_names)) + ")"
                )
                params.extend(event_names)
            else:
                clauses.append("1=0")  # empty allow-list matches nothing
        if target_entity_type is not UNSET:
            if target_entity_type is None:
                clauses.append("target_entity_type IS NULL")
            else:
                clauses.append("target_entity_type = ?")
                params.append(target_entity_type)
        if target_entity_id is not UNSET:
            if target_entity_id is None:
                clauses.append("target_entity_id IS NULL")
            else:
                clauses.append("target_entity_id = ?")
                params.append(target_entity_id)
        return clauses, params

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
        t = self._events_table(app_id, channel_id)
        clauses, params = self._find_clauses(
            start_time, until_time, entity_type, entity_id, event_names,
            target_entity_type, target_entity_id,
        )
        with self._c.lock:
            if not self._exists(t):
                raise StorageError(f"events table {t} not initialized")
        marks, segs = self._segment_state(t)
        # the potentially-large scans run on snapshot connections, so
        # concurrent ingest proceeds while these fetches stream; sharded
        # stores fan out per shard and merge (stable sort: ties keep
        # main-store-then-shard, insertion order). An entity_id filter
        # pins the events to ONE shard (the insert hash), so the serving
        # find-by-entity path scans main + that shard, not all K.
        all_stores = self._c.row_stores()
        keys = list(range(len(all_stores)))
        if entity_id is not None and self._c.shard_count > 1:
            keys = [0, all_stores.index(self._c.shard_for(entity_id))]
        row_events: List[Event] = []
        n_stores = 0
        for key in keys:
            store = all_stores[key]
            if not store.has_table(t):
                continue
            n_stores += 1
            sql = f"SELECT {self._ROW_COLS} FROM {t}"
            store_clauses = list(clauses)
            store_params = list(params)
            pred = self._residual_clause(marks, key)
            if pred is not None:  # sealed prefix lives in segments now
                store_clauses.append(pred[0])
                store_params.extend(pred[1])
            if store_clauses:
                sql += " WHERE " + " AND ".join(store_clauses)
            sql += f" ORDER BY event_time_ms {'DESC' if reversed else 'ASC'}"
            if limit is not None and limit >= 0:
                sql += f" LIMIT {int(limit)}"  # per-store bound; re-cut below
            row_events.extend(
                self._row_to_event(r)
                for r in store.read_execute(sql, store_params).fetchall()
            )
        # merge compacted segment events and bulk-imported page events
        # (rare on this legacy path — the training scan is
        # find_columns_native; here both decode into Event objects so
        # find() stays a complete view of the store)
        seg_events = self._segment_events(
            t, segs, start_time, until_time, entity_type, entity_id,
            event_names, target_entity_type, target_entity_id,
            store_keys=set(keys), limit=limit, reversed=reversed,
        )
        page_events = self._page_events(
            t, start_time, until_time, entity_type, entity_id, event_names,
            target_entity_type, target_entity_id,
        )
        if not page_events and not seg_events and n_stores <= 1:
            return iter(row_events)
        # stable sort: segment events (the sealed, older prefix) sort
        # before the residual rows they precede on equal timestamps
        merged = seg_events + row_events + page_events
        merged.sort(key=lambda e: _ms(e.event_time), reverse=reversed)
        if limit is not None and limit >= 0:
            merged = merged[: int(limit)]
        return iter(merged)

    # --- columnar page store (see data/storage/columnar.py) ---

    _PAGE_ROWS = 1 << 20

    def _dict_encode(self, t: str, names) -> "np.ndarray":
        """Distinct id strings -> global dictionary codes (insert-if-new)."""
        import numpy as np

        strs = [str(n) for n in names]
        with self._c.lock:
            self._c.conn.executemany(
                f"INSERT OR IGNORE INTO {t}_dict (name) VALUES (?)",
                ((s,) for s in strs),
            )
            mapping: Dict[str, int] = {}
            if len(strs) > 50_000:
                # a bulk import's worth of names: one scan of the
                # dictionary, not a thousand statements of 900 names
                mapping.update(self._c.conn.execute(
                    f"SELECT name, id FROM {t}_dict"
                ).fetchall())
            else:
                chunk = 900  # sqlite bound-parameter limit headroom
                for s in range(0, len(strs), chunk):
                    part = strs[s : s + chunk]
                    rows = self._c.conn.execute(
                        f"SELECT name, id FROM {t}_dict WHERE name IN "
                        f"({','.join('?' * len(part))})",
                        part,
                    ).fetchall()
                    mapping.update(rows)
            self._c.conn.commit()
        return np.array([mapping[s] for s in strs], np.int32)

    def _dict_names(self, t: str) -> "np.ndarray":
        """Global dictionary as an id-indexed name array."""
        import numpy as np

        rows = self._c.read_execute(
            f"SELECT id, name FROM {t}_dict"
        ).fetchall()
        size = (max(r[0] for r in rows) + 1) if rows else 0
        arr = np.empty(size, object)
        for i, name in rows:
            arr[i] = name
        return arr

    def insert_columns(
        self,
        app_id: int,
        channel_id: Optional[int] = None,
        *,
        event: str,
        entity_type: str,
        target_entity_type: str,
        entity_ids,
        target_ids,
        values,
        value_property: str = "rating",
        event_time: Optional[_dt.datetime] = None,
        event_times_ms=None,
    ) -> int:
        from predictionio_tpu.data.storage.columnar import encode_strings

        e_names, e_codes = encode_strings(entity_ids)
        g_names, g_codes = encode_strings(target_ids)
        return self.insert_columns_encoded(
            app_id,
            channel_id,
            event=event,
            entity_type=entity_type,
            target_entity_type=target_entity_type,
            entity_names=e_names,
            entity_codes=e_codes,
            target_names=g_names,
            target_codes=g_codes,
            values=values,
            value_property=value_property,
            event_time=event_time,
            event_times_ms=event_times_ms,
        )

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
        """Vectorized bulk append: dictionary-encode the (pre-factorized)
        id columns and store numpy blobs as pages — 20M events import in
        seconds where the row path takes minutes (the role of the
        reference's HBase bulk region writes). ``event_times_ms`` keeps
        per-row timestamps (import round-trips); otherwise every row gets
        ``event_time`` (default now)."""
        import numpy as np

        if event.startswith("$"):
            raise StorageError(
                f"insert_columns cannot write special event {event!r}"
            )
        t = self._events_table(app_id, channel_id)
        with self._c.lock:
            if not self._exists(t):
                raise StorageError(f"events table {t} not initialized")
        # pre-page-store databases lack the _pages/_dict tables entirely
        self._ensure_pages_schema(t)
        vals = np.asarray(values, np.float32)
        e_codes = np.asarray(entity_codes, np.int32)
        g_codes = np.asarray(target_codes, np.int32)
        n = len(vals)
        if n != len(e_codes) or n != len(g_codes):
            raise ValueError("entity/target/values column lengths differ")
        if n == 0:
            return 0
        e_glob = self._dict_encode(t, entity_names)[e_codes]
        g_glob = self._dict_encode(t, target_names)[g_codes]
        if event_times_ms is not None:
            times = np.asarray(event_times_ms, np.int64)
            if len(times) != n:
                raise ValueError("event_times_ms length differs")
        else:
            tms = _ms(event_time or _dt.datetime.now(_dt.timezone.utc))
            times = np.full(n, tms, np.int64)
        with self._c.lock:
            for s in range(0, n, self._PAGE_ROWS):
                e = slice(s, min(s + self._PAGE_ROWS, n))
                cnt = e.stop - e.start
                ts = times[e]
                self._c.conn.execute(
                    f"INSERT INTO {t}_pages (event, entity_type, "
                    "target_entity_type, prop, n, min_ms, max_ms, "
                    "entities, targets, vals, times) "
                    "VALUES (?,?,?,?,?,?,?,?,?,?,?)",
                    (
                        event, entity_type, target_entity_type,
                        value_property, cnt, int(ts.min()), int(ts.max()),
                        e_glob[e].tobytes(), g_glob[e].tobytes(),
                        vals[e].tobytes(), ts.tobytes(),
                    ),
                )
            self._c.conn.commit()
        return n

    @staticmethod
    def _page_filter(
        start_time, until_time, entity_type, event_names,
        target_entity_type,
    ):
        """Page-level WHERE ``(clauses, params)`` shared by every page
        scan (monolithic, streaming, legacy find view), or None when no
        page can match. Pages only hold target-carrying events, so an
        explicit target_entity_type IS NULL filter matches none."""
        if target_entity_type is None:  # explicit "no target" filter
            return None
        clauses, params = [], []
        if event_names is not None:
            if not event_names:
                return None
            clauses.append(
                "event IN (" + ",".join("?" * len(event_names)) + ")"
            )
            params.extend(event_names)
        if entity_type is not None:
            clauses.append("entity_type = ?")
            params.append(entity_type)
        if target_entity_type is not UNSET:
            clauses.append("target_entity_type = ?")
            params.append(target_entity_type)
        if start_time is not None:
            clauses.append("max_ms >= ?")
            params.append(_ms(start_time))
        if until_time is not None:
            clauses.append("min_ms < ?")
            params.append(_ms(until_time))
        return clauses, params

    def _page_rows(
        self, t, start_time, until_time, entity_type, event_names,
        target_entity_type,
    ):
        """Pages matching the coarse (page-level) filters."""
        filt = self._page_filter(
            start_time, until_time, entity_type, event_names,
            target_entity_type,
        )
        if filt is None:
            return []
        self._ensure_pages_schema(t)
        clauses, params = filt
        sql = (
            f"SELECT page, event, entity_type, target_entity_type, prop, "
            f"n, min_ms, max_ms, entities, targets, vals, times, dead "
            f"FROM {t}_pages"
        )
        if clauses:
            sql += " WHERE " + " AND ".join(clauses)
        with self._c.lock:
            if not self._exists(f"{t}_pages"):
                return []
        return self._c.read_execute(sql, params).fetchall()

    def _page_events(
        self, t, start_time, until_time, entity_type, entity_id,
        event_names, target_entity_type, target_entity_id,
    ) -> List[Event]:
        """Decode page rows into Event objects (legacy find() view)."""
        import numpy as np

        if target_entity_id is None:
            return []
        if entity_id is not None:
            return self._page_events_by_entity(
                t, start_time, until_time, entity_type, entity_id,
                event_names, target_entity_type, target_entity_id,
            )
        pages = self._page_rows(
            t, start_time, until_time, entity_type, event_names,
            target_entity_type,
        )
        if not pages:
            return []

        # the target filter compares int32 dict CODES, not strings (the
        # entity filter went through the entity index above)
        g_code = None
        if target_entity_id is not UNSET:
            g_code = self._dict_code(t, target_entity_id)
            if g_code is None:
                return []
        names = self._dict_names(t)
        out: List[Event] = []
        lo = _ms(start_time) if start_time is not None else None
        hi = _ms(until_time) if until_time is not None else None
        for (
            page, ev, et, tet, prop, n, min_ms, max_ms, eb, gb, vb, tb, db
        ) in pages:
            self.read_stats["pages_decoded"] += 1
            e = np.frombuffer(eb, np.int32)
            g = np.frombuffer(gb, np.int32)
            v = np.frombuffer(vb, np.float32)
            ts = np.frombuffer(tb, np.int64)
            keep = (
                np.frombuffer(db, np.uint8) == 0
                if db is not None
                else np.ones(n, bool)
            )
            if lo is not None:
                keep = keep & (ts >= lo)
            if hi is not None:
                keep = keep & (ts < hi)
            if g_code is not None:
                keep = keep & (g == g_code)
            for j in np.nonzero(keep)[0]:
                when = _dt.datetime.fromtimestamp(
                    ts[j] / 1000.0, _dt.timezone.utc
                )
                out.append(
                    Event(
                        event_id=f"pg-{page}-{int(j)}",
                        event=ev,
                        entity_type=et,
                        entity_id=names[e[j]],
                        target_entity_type=tet,
                        target_entity_id=names[g[j]],
                        properties=DataMap({prop: float(v[j])}),
                        event_time=when,
                        creation_time=when,
                    )
                )
        return out

    # --- reads by entity: the pages' entity index ---

    def build_entity_index(
        self, app_id: int, channel_id: Optional[int] = None
    ) -> int:
        """Index every bulk-imported page not yet covered, by entity;
        returns how many pages it indexed. One pass over those pages,
        kept in the store: ``find_by_entities`` and an entity-filtered
        ``find`` call it when they meet an uncovered page, and a loader
        may call it once after its import so that no query pays."""
        return self._build_entity_index(
            self._events_table(app_id, channel_id)
        )

    def _build_entity_index(self, t: str) -> int:
        import numpy as np

        self._ensure_pages_schema(t)
        with self._c.lock:
            if not self._exists(f"{t}_pent"):
                return 0
            todo = [
                r[0] for r in self._c.conn.execute(
                    f"SELECT page FROM {t}_pages WHERE page NOT IN "
                    f"(SELECT page FROM {t}_pent_pages)"
                ).fetchall()
            ]
            for page in todo:
                row = self._c.conn.execute(
                    f"SELECT entities, targets, vals, times FROM {t}_pages "
                    f"WHERE page=?", (page,),
                ).fetchone()
                if row is None:
                    continue
                self.read_stats["pages_decoded"] += 1
                e = np.frombuffer(row[0], np.int32)
                order = np.argsort(e, kind="stable")
                packed = np.empty(len(e), _pent_dtype())
                packed["idx"] = order
                packed["target"] = np.frombuffer(row[1], np.int32)[order]
                packed["val"] = np.frombuffer(row[2], np.float32)[order]
                packed["ms"] = np.frombuffer(row[3], np.int64)[order]
                codes = e[order]
                starts = np.flatnonzero(
                    np.concatenate([[True], codes[1:] != codes[:-1]])
                )
                ends = np.append(starts[1:], len(codes))
                blob = packed.tobytes()
                size = _pent_dtype().itemsize
                self._c.conn.executemany(
                    f"INSERT OR REPLACE INTO {t}_pent (ecode, page, packed) "
                    f"VALUES (?,?,?)",
                    (
                        (int(codes[a]), page, blob[a * size:b * size])
                        for a, b in zip(starts.tolist(), ends.tolist())
                    ),
                )
                self._c.conn.execute(
                    f"INSERT OR IGNORE INTO {t}_pent_pages (page) VALUES (?)",
                    (page,),
                )
                self._c.conn.commit()
        return len(todo)

    def _dict_code(self, t: str, name: str) -> Optional[int]:
        """The dictionary code of one name, or None (no such name, or no
        dictionary: an app that never had a bulk import)."""
        if not self._exists_memo(f"{t}_dict"):
            return None
        row = self._c.execute(
            f"SELECT id FROM {t}_dict WHERE name=?", (name,)
        ).fetchone()
        return row[0] if row else None

    def _dict_name_of(self, t: str, codes) -> list:
        """Names of dictionary codes, from this process's copy of the
        dictionary (see ``_dict_cache``), read further when a code lies
        past its end."""
        names = self._dict_cache.setdefault(t, [None])
        top = max(codes, default=0)
        if top >= len(names):
            with self._c.lock:  # one reader extends the copy at a time
                if top >= len(names):
                    for code, name in self._c.read_execute(
                        f"SELECT id, name FROM {t}_dict WHERE id >= ? "
                        f"ORDER BY id", (len(names),),
                    ).fetchall():
                        names.extend([None] * (code - len(names)))
                        names.append(name)
        return [names[c] for c in codes]

    def _entity_page_rows(
        self, t, entity_type, entity_ids, event_names, target_entity_type,
        start_time=None, until_time=None,
    ):
        """{entity id: [(page row, packed rows of the entity in it)]}
        through the entity index, dead rows dropped: what a read by
        entity costs is the entity's events. ``page row`` is (page,
        event, entity_type, target_entity_type, prop)."""
        import numpy as np

        filt = self._page_filter(
            start_time, until_time, entity_type, event_names,
            target_entity_type,
        )
        if filt is None or not entity_ids:
            return {}
        self._ensure_pages_schema(t)
        with self._c.lock:
            if not self._exists(f"{t}_pent"):
                return {}
        clauses, params = filt
        sql = (
            f"SELECT page, event, entity_type, target_entity_type, prop, "
            f"dead IS NOT NULL FROM {t}_pages"
        )
        if clauses:
            sql += " WHERE " + " AND ".join(clauses)
        pages = {r[0]: r for r in self._c.read_execute(sql, params).fetchall()}
        if not pages:
            return {}
        covered = {
            r[0] for r in self._c.read_execute(
                f"SELECT page FROM {t}_pent_pages"
            ).fetchall()
        }
        if not covered.issuperset(pages):
            self._build_entity_index(t)
        ids = list(dict.fromkeys(entity_ids))
        code_of: Dict[str, int] = {}
        found: Dict[int, list] = {}
        chunk = 900  # sqlite's bound-parameter limit, with headroom
        for s in range(0, len(ids), chunk):
            part = ids[s:s + chunk]
            marks = ",".join("?" * len(part))
            code_of.update(self._c.read_execute(
                f"SELECT name, id FROM {t}_dict WHERE name IN ({marks})",
                part,
            ).fetchall())
        codes = list(code_of.values())
        for s in range(0, len(codes), chunk):
            part = codes[s:s + chunk]
            marks = ",".join("?" * len(part))
            for ecode, page, blob in self._c.read_execute(
                f"SELECT ecode, page, packed FROM {t}_pent "
                f"WHERE ecode IN ({marks})", part,
            ).fetchall():
                if page in pages:
                    self.read_stats["index_rows"] += 1
                    found.setdefault(ecode, []).append(
                        (page, np.frombuffer(blob, _pent_dtype()))
                    )
        dead: Dict[int, "np.ndarray"] = {}
        touched = {
            page for rows in found.values() for page, _ in rows
            if pages[page][5]
        }
        for page in touched:  # pages with tombstones: rare
            row = self._c.read_execute(
                f"SELECT dead FROM {t}_pages WHERE page=?", (page,)
            ).fetchone()
            if row is not None and row[0] is not None:
                dead[page] = np.frombuffer(row[0], np.uint8)
        lo = _ms(start_time) if start_time is not None else None
        hi = _ms(until_time) if until_time is not None else None
        out: Dict[str, list] = {}
        for name, code in code_of.items():
            rows = []
            for page, packed in found.get(code, ()):
                if page in dead:
                    packed = packed[dead[page][packed["idx"]] == 0]
                if lo is not None:
                    packed = packed[packed["ms"] >= lo]
                if hi is not None:
                    packed = packed[packed["ms"] < hi]
                if len(packed):
                    rows.append((pages[page], packed))
            if rows:
                out[name] = rows
        return out

    def _page_events_by_entity(
        self, t, start_time, until_time, entity_type, entity_id,
        event_names, target_entity_type, target_entity_id,
    ) -> List[Event]:
        """``_page_events`` for one entity, through the entity index."""
        g_code = None
        if target_entity_id is not UNSET:
            g_code = self._dict_code(t, target_entity_id)
            if g_code is None:
                return []
        out: List[Event] = []
        for (page, ev, et, tet, prop, _), packed in self._entity_page_rows(
            t, entity_type, [entity_id], event_names, target_entity_type,
            start_time, until_time,
        ).get(entity_id, ()):
            if g_code is not None:
                packed = packed[packed["target"] == g_code]
            names = self._dict_name_of(t, packed["target"].tolist())
            for idx, name, val, ms in zip(
                packed["idx"].tolist(), names, packed["val"].tolist(),
                packed["ms"].tolist(),
            ):
                when = _dt.datetime.fromtimestamp(
                    ms / 1000.0, _dt.timezone.utc
                )
                out.append(
                    Event(
                        event_id=f"pg-{page}-{idx}", event=ev,
                        entity_type=et, entity_id=entity_id,
                        target_entity_type=tet, target_entity_id=name,
                        properties=DataMap({prop: float(val)}),
                        event_time=when, creation_time=when,
                    )
                )
        return out

    def find_by_entities(
        self,
        app_id: int,
        channel_id: Optional[int] = None,
        *,
        entity_type: str,
        entity_ids: Sequence[str],
        event_names: Sequence[str],
        target_entity_type: str,
    ) -> Dict[str, List[tuple]]:
        """One pass over the store for a whole micro-batch (see
        ``base.LEvents.find_by_entities``): one indexed statement over
        the row table of each store for all the entities, one over the
        pages' entity index; sealed segments, which have no index by
        entity yet, still cost one scan an entity. Every statement runs
        on a read connection that sees what any process has committed:
        an event the Event Server acknowledged is in the answer."""
        t = self._events_table(app_id, channel_id)
        with self._c.lock:
            if not self._exists(t):
                raise StorageError(f"events table {t} not initialized")
        ids = list(dict.fromkeys(entity_ids))
        out: Dict[str, List[tuple]] = {i: [] for i in ids}
        if not ids or not event_names:
            return out
        marks_, segs = self._segment_state(t)
        ev_marks = ",".join("?" * len(event_names))
        chunk = 500
        for key, store in enumerate(self._c.row_stores()):
            if not store.has_table(t):
                continue
            pred = self._residual_clause(marks_, key)
            for s in range(0, len(ids), chunk):
                part = ids[s:s + chunk]
                sql = (
                    f"SELECT entity_id, event, target_entity_id, "
                    f"event_time_ms FROM {t} WHERE entity_type = ? AND "
                    f"entity_id IN ({','.join('?' * len(part))}) AND "
                    f"event IN ({ev_marks}) AND target_entity_type = ?"
                )
                params = [entity_type, *part, *event_names,
                          target_entity_type]
                if pred is not None:
                    sql += " AND " + pred[0]
                    params.extend(pred[1])
                for eid, ev, target, ms in store.read_execute(
                    sql, params
                ).fetchall():
                    if target:
                        out[eid].append((ev, target, ms))
        if segs:
            for eid in ids:
                out[eid].extend(
                    (e.event, e.target_entity_id, _ms(e.event_time))
                    for e in self._segment_events(
                        t, segs, None, None, entity_type, eid,
                        list(event_names), target_entity_type, UNSET,
                    )
                    if e.target_entity_id
                )
        for eid, rows in self._entity_page_rows(
            t, entity_type, ids, list(event_names), target_entity_type,
        ).items():
            for (_, ev, _, _, _, _), packed in rows:
                names = self._dict_name_of(t, packed["target"].tolist())
                out[eid].extend(
                    (ev, name, ms)
                    for name, ms in zip(names, packed["ms"].tolist())
                )
        for rows in out.values():
            rows.sort(key=lambda r: -r[2])
        return out

    def iter_row_events(
        self, app_id: int, channel_id: Optional[int] = None
    ) -> Iterator[Event]:
        """Row-store events ONLY (no page merge) — the export path pairs
        this with iter_export_pages so neither side is double-counted.
        Sharded stores merge every shard's rows back into one
        time-ordered view."""
        t = self._events_table(app_id, channel_id)
        with self._c.lock:
            if not self._exists(t):
                raise StorageError(f"events table {t} not initialized")
        marks, _ = self._segment_state(t)
        queries: list = []  # (store, sql, params)
        for key, store in enumerate(self._c.row_stores()):
            if not store.has_table(t):
                continue
            sql = f"SELECT {self._ROW_COLS} FROM {t}"
            pred = self._residual_clause(marks, key)
            params: list = []
            if pred is not None:  # sealed rows export via segments
                sql += f" WHERE {pred[0]}"
                params = pred[1]
            sql += " ORDER BY event_time_ms ASC"
            queries.append((store, sql, params))
        if len(queries) <= 1:
            # single store: Event objects materialize one at a time as
            # the consumer (e.g. the parquet export writer) iterates —
            # a 20M-row export must not hold 20M Events at once
            rows = (
                queries[0][0].read_execute(
                    queries[0][1], queries[0][2]
                ).fetchall()
                if queries
                else []
            )
            return (self._row_to_event(r) for r in rows)
        events = [
            self._row_to_event(r)
            for store, sql, params in queries
            for r in store.read_execute(sql, params).fetchall()
        ]
        events.sort(key=lambda e: _ms(e.event_time))
        return iter(events)

    def iter_export_pages(
        self, app_id: int, channel_id: Optional[int] = None
    ) -> Iterator[dict]:
        """Bulk-export view of the page store: one dict of decoded numpy
        columns per page (live rows only), for vectorized writers —
        exporting 20M events must not build 20M Event objects any more
        than importing them does. Keys: event, entity_type,
        target_entity_type, prop, event_ids, entity_ids, target_ids,
        values, times_ms."""
        import numpy as np

        t = self._events_table(app_id, channel_id)
        with self._c.lock:
            if not self._exists(t):
                raise StorageError(f"events table {t} not initialized")
        self._ensure_pages_schema(t)
        with self._c.lock:
            if not self._exists(f"{t}_pages"):
                return
        page_ids = [
            r[0]
            for r in self._c.read_execute(
                f"SELECT page FROM {t}_pages ORDER BY page"
            ).fetchall()
        ]
        if not page_ids:
            return
        names = self._dict_names(t)
        for page_id in page_ids:
            # one page's blobs at a time: peak memory stays one page, and
            # the snapshot connection never touches the writer lock
            row = self._c.read_execute(
                f"SELECT page, event, entity_type, target_entity_type, "
                f"prop, n, entities, targets, vals, times, dead "
                f"FROM {t}_pages WHERE page=?",
                (page_id,),
            ).fetchone()
            if row is None:
                continue  # deleted since listing
            (page, ev, et, tet, prop, n, eb, gb, vb, tb, db) = row
            alive = (
                np.nonzero(np.frombuffer(db, np.uint8) == 0)[0]
                if db is not None
                else np.arange(n)
            )
            if not len(alive):
                continue
            # positional ids stay stable across tombstones: the index in
            # the id is the ORIGINAL slot, not the live rank
            event_ids = np.char.add(
                f"pg-{page}-", alive.astype("U10")
            ).astype(object)
            yield {
                "event": ev,
                "entity_type": et,
                "target_entity_type": tet,
                "prop": prop,
                "event_ids": event_ids,
                "entity_ids": names[np.frombuffer(eb, np.int32)[alive]],
                "target_ids": names[np.frombuffer(gb, np.int32)[alive]],
                "values": np.frombuffer(vb, np.float32)[alive],
                "times_ms": np.frombuffer(tb, np.int64)[alive],
            }

    # --- compacted columnar segment tier (data/storage/segments.py) ---
    #
    # Immutable segment files hold sealed cold prefixes of each row
    # store; a manifest + per-store watermark in the MAIN database makes
    # them atomically visible and excludes the sealed rowid ranges from
    # every residual row scan. Scans fan out over
    # pages -> per store: [segments, residual rows] — exactly the event
    # order of an uncompacted store, so the counting-sort merge's wire
    # stays byte-identical (segments module docstring).

    def _seg_dir(self) -> str:
        return f"{self._c.path}.segments"

    def _ensure_segment_schema(self, t: str) -> None:
        """Create the manifest + compaction-state tables (main db)."""
        if t in self._seg_schema_ok:
            return
        with self._c.lock:
            self._c.execute(
                f"""CREATE TABLE IF NOT EXISTS {t}_segments (
                    segment INTEGER PRIMARY KEY AUTOINCREMENT,
                    store INTEGER NOT NULL,
                    n INTEGER NOT NULL,
                    min_rowid INTEGER NOT NULL,
                    max_rowid INTEGER NOT NULL,
                    min_ms INTEGER NOT NULL,
                    max_ms INTEGER NOT NULL,
                    events TEXT NOT NULL,
                    entity_types TEXT NOT NULL,
                    target_entity_types TEXT NOT NULL,
                    path TEXT NOT NULL,
                    checksum INTEGER NOT NULL,
                    created_ms INTEGER NOT NULL,
                    dead BLOB
                )"""
            )
            self._c.execute(
                f"""CREATE TABLE IF NOT EXISTS {t}_compaction (
                    store INTEGER PRIMARY KEY,
                    watermark INTEGER NOT NULL,
                    cleaned INTEGER NOT NULL,
                    holdouts BLOB,
                    last_ms INTEGER NOT NULL
                )"""
            )
            self._c.commit()
            self._seg_schema_ok.add(t)

    def _segment_state(self, t: str):
        """One consistent snapshot of (compaction marks, live segment
        manifest): ``marks`` is ``{store_key: (watermark, holdout rowid
        tuple, cleaned, last_ms)}``, ``segs`` a list of manifest dicts
        ordered by (store, segment id) — which IS rowid order, because
        each store's watermark only advances. Store keys index
        ``row_stores()`` (0 = main file, then hash shards); the pair is
        read in ONE read transaction so a racing compaction commit can
        never double- or zero-count sealed rows."""
        if not self._c.main_store.has_table(f"{t}_segments"):
            return {}, []
        import numpy as np

        rows_marks, rows_segs = self._c.main_store.read_snapshot(
            [
                (
                    f"SELECT store, watermark, cleaned, holdouts, last_ms "
                    f"FROM {t}_compaction",
                    (),
                ),
                (
                    f"SELECT segment, store, n, min_rowid, max_rowid, "
                    f"min_ms, max_ms, events, entity_types, "
                    f"target_entity_types, path, checksum, created_ms, "
                    f"dead FROM {t}_segments ORDER BY store, segment",
                    (),
                ),
            ]
        )
        marks = {
            int(r[0]): (
                int(r[1]),
                tuple(
                    int(x) for x in np.frombuffer(r[3], np.int64)
                )
                if r[3]
                else (),
                int(r[2]),
                int(r[4]),
            )
            for r in rows_marks
        }
        segs = [
            {
                "segment": r[0], "store": r[1], "n": r[2],
                "min_rowid": r[3], "max_rowid": r[4], "min_ms": r[5],
                "max_ms": r[6], "events": json.loads(r[7]),
                "entity_types": json.loads(r[8]),
                "target_entity_types": json.loads(r[9]), "path": r[10],
                "checksum": r[11], "created_ms": r[12], "dead": r[13],
            }
            for r in rows_segs
        ]
        return marks, segs

    # open-segment LRU bound: entries are mmap-backed (resident pages
    # are OS page cache, evictable), so the cap limits mappings/handles,
    # not data bytes
    _SEG_CACHE_MAX = 128

    def _open_segment(self, path: str):
        """Open (and cache) one immutable segment file. The cache is
        instance-scoped, LRU-bounded, and keyed by path; files never
        change in place (writes go through temp + rename under a fresh
        name), so entries can't go stale — only cold."""
        from predictionio_tpu.data.storage import segments as _seg

        data = self._seg_cache.get(path)
        if data is None:
            try:
                data = _seg.SegmentData(path)
            except (OSError, _seg.SegmentReadError) as e:
                raise StorageError(f"segment unreadable: {e}") from e
            self._seg_cache[path] = data
            while len(self._seg_cache) > self._SEG_CACHE_MAX:
                self._seg_cache.pop(next(iter(self._seg_cache)))
        else:
            self._seg_cache.move_to_end(path)
        return data

    @staticmethod
    def _and_extras(*extras):
        """AND-combine optional pre-bound ``(clause, params)`` predicates
        (None entries skipped; None when nothing remains)."""
        parts = [e for e in extras if e is not None]
        if not parts:
            return None
        return (
            " AND ".join(f"({c})" for c, _ in parts),
            [p for _, ps in parts for p in ps],
        )

    @staticmethod
    def _residual_clause(marks, store_key: int):
        """SQL predicate excluding the compacted prefix of one row
        store (``None`` when nothing is compacted): rows above the
        watermark, plus the bounded holdout set the compactor could not
        columnarize."""
        mark = marks.get(store_key) if marks else None
        if not mark or mark[0] <= 0:
            return None
        wm, holdouts = mark[0], mark[1]
        if holdouts:
            # holdout rowids inline as integer literals, not bound
            # parameters: max_holdouts (4096) exceeds older sqlite's
            # 999-variable limit, and these are int64s from our own
            # manifest — nothing to escape
            inlist = ",".join(str(int(h)) for h in holdouts)
            return f"(rowid > ? OR rowid IN ({inlist}))", [wm]
        return "rowid > ?", [wm]

    @staticmethod
    def _segs_match(
        seg: dict, event_names, entity_type, target_entity_type, lo, hi
    ) -> bool:
        """Coarse manifest-level pruning, mirroring ``_page_filter``."""
        if target_entity_type is None:  # explicit "no target" filter
            return False
        if event_names is not None and not (
            set(event_names) & set(seg["events"])
        ):
            return False
        if entity_type is not None and entity_type not in seg["entity_types"]:
            return False
        if (
            target_entity_type is not UNSET
            and target_entity_type not in seg["target_entity_types"]
        ):
            return False
        if lo is not None and seg["max_ms"] < lo:
            return False
        if hi is not None and seg["min_ms"] >= hi:
            return False
        return True

    def _seg_dead(self, seg: dict):
        import numpy as np

        if seg["dead"] is None:
            return None
        return np.frombuffer(seg["dead"], np.uint8)

    def _segment_events(
        self, t, segs, start_time, until_time, entity_type, entity_id,
        event_names, target_entity_type, target_entity_id,
        store_keys=None, limit=None, reversed=False,
    ) -> List[Event]:
        """Decode matching segment rows into Event objects (the legacy
        ``find()`` view), original ids and creation times preserved.
        With ``limit``, only the per-segment top-``limit`` rows by event
        time decode (the global top-limit is a subset of the union of
        per-segment top-limits), so a bounded serving query never pays a
        full-dataset decode."""
        import numpy as np

        if not segs or target_entity_id is None:
            return []
        lo = _ms(start_time) if start_time is not None else None
        hi = _ms(until_time) if until_time is not None else None
        wanted = [
            s
            for s in segs
            if (store_keys is None or s["store"] in store_keys)
            and self._segs_match(
                s, event_names, entity_type, target_entity_type, lo, hi
            )
        ]
        if not wanted:
            return []
        e_code = g_code = None
        if entity_id is not None or target_entity_id is not UNSET:
            def code_of(name: str):
                row = self._c.execute(
                    f"SELECT id FROM {t}_dict WHERE name=?", (name,)
                ).fetchone()
                return row[0] if row else None

            if entity_id is not None:
                e_code = code_of(entity_id)
                if e_code is None:
                    return []
            if target_entity_id is not UNSET:
                g_code = code_of(target_entity_id)
                if g_code is None:
                    return []
        names = self._dict_names(t)
        out: List[Event] = []
        for seg in wanted:
            data = self._open_segment(seg["path"])
            keep = data.keep_mask(
                lo_ms=lo, hi_ms=hi, entity_type=entity_type,
                target_entity_type=(
                    None if target_entity_type is None else target_entity_type
                ),
                target_entity_type_set=target_entity_type is not UNSET,
                event_names=event_names, dead=self._seg_dead(seg),
            )
            e = data.column("entities")
            if e_code is not None:
                m = e == e_code
                keep = m if keep is None else (keep & m)
            if g_code is not None:
                m = data.column("targets") == g_code
                keep = m if keep is None else (keep & m)
            idx = np.nonzero(keep)[0] if keep is not None else np.arange(data.n)
            if not len(idx):
                continue
            if limit is not None and 0 <= limit < len(idx):
                t_of = data.column("times_ms")[idx]
                order = np.argsort(
                    -t_of if reversed else t_of, kind="stable"
                )[:limit]
                idx = idx[np.sort(order)]  # keep row order among chosen
            g = data.column("targets")
            v = data.column("values")
            ts = data.column("times_ms")
            cts = data.column("ctimes_ms")
            ev = data.column("evcodes")
            pr = data.column("propcodes")
            et = data.column("etcodes")
            tet = data.column("tetcodes")
            ids = data.column("ids")
            for j in idx:
                prop = data.props[pr[j]]
                when = _dt.datetime.fromtimestamp(
                    ts[j] / 1000.0, _dt.timezone.utc
                )
                out.append(
                    Event(
                        event_id=ids[j].decode("utf-8"),
                        event=data.event_names[ev[j]],
                        entity_type=data.entity_types[et[j]],
                        entity_id=names[e[j]],
                        target_entity_type=data.target_entity_types[tet[j]],
                        target_entity_id=names[g[j]],
                        properties=DataMap(
                            {prop: float(v[j])} if prop else {}
                        ),
                        event_time=when,
                        creation_time=_dt.datetime.fromtimestamp(
                            cts[j] / 1000.0, _dt.timezone.utc
                        ),
                    )
                )
        return out

    def _get_segment_event(self, t: str, event_id: str) -> Optional[Event]:
        """Probe the segment tier for one event by its ORIGINAL id."""
        import numpy as np

        _, segs = self._segment_state(t)
        if not segs:
            return None
        needle = event_id.encode("utf-8")
        names = None
        for seg in segs:
            data = self._open_segment(seg["path"])
            ids = data.column("ids")
            if len(needle) > ids.dtype.itemsize:
                continue
            hit = data.id_rows([needle])
            if not len(hit):
                continue
            j = int(hit[0])
            dead = self._seg_dead(seg)
            if dead is not None and dead[j]:
                continue
            if names is None:
                names = self._dict_names(t)
            prop = data.props[data.column("propcodes")[j]]
            when = _dt.datetime.fromtimestamp(
                data.column("times_ms")[j] / 1000.0, _dt.timezone.utc
            )
            return Event(
                event_id=event_id,
                event=data.event_names[data.column("evcodes")[j]],
                entity_type=data.entity_types[data.column("etcodes")[j]],
                entity_id=names[data.column("entities")[j]],
                target_entity_type=data.target_entity_types[
                    data.column("tetcodes")[j]
                ],
                target_entity_id=names[data.column("targets")[j]],
                properties=DataMap(
                    {prop: float(data.column("values")[j])} if prop else {}
                ),
                event_time=when,
                creation_time=_dt.datetime.fromtimestamp(
                    data.column("ctimes_ms")[j] / 1000.0, _dt.timezone.utc
                ),
            )
        return None

    def _tombstone_segment_ids(self, t: str, ids: Sequence[str]) -> bool:
        """Set the manifest dead bit for any segment rows carrying these
        ids (delete of a compacted event; explicit-id re-post scrub).
        Segments stay immutable — liveness lives in the manifest."""
        import numpy as np

        if not ids:
            return False
        _, segs = self._segment_state(t)
        if not segs:
            return False
        needles = [i.encode("utf-8") for i in ids]
        changed = False
        for seg in segs:
            data = self._open_segment(seg["path"])
            col = data.column("ids")
            fit = [b for b in needles if len(b) <= col.dtype.itemsize]
            if not fit:
                continue
            hits = data.id_rows(fit)
            if not len(hits):
                continue
            with self._c.lock:
                row = self._c.execute(
                    f"SELECT dead FROM {t}_segments WHERE segment=?",
                    (seg["segment"],),
                ).fetchone()
                if row is None:
                    continue
                dead = (
                    np.frombuffer(row[0], np.uint8).copy()
                    if row[0] is not None
                    else np.zeros(data.n, np.uint8)
                )
                if dead[hits].all():
                    continue
                dead[hits] = 1
                self._c.execute(
                    f"UPDATE {t}_segments SET dead=? WHERE segment=?",
                    (dead.tobytes(), seg["segment"]),
                )
                self._c.commit()
                changed = True
        return changed

    def _ensure_monotonic_rowids(self, store, t: str) -> None:
        """Migrate a pre-segment-tier row table (implicit rowid) to the
        AUTOINCREMENT schema, preserving every rowid. Without this, a
        compaction that empties the table would let sqlite re-issue
        rowids UNDER the watermark — silently invisible events. One
        full-table rewrite, once per store file."""
        ok = getattr(store, "rid_ok", None)
        if ok is None:
            ok = store.rid_ok = set()
        if t in ok:
            return
        with store.lock:
            row = store.conn.execute(
                "SELECT sql FROM sqlite_master WHERE type='table' AND name=?",
                (t,),
            ).fetchone()
            if row is None:
                return
            if "AUTOINCREMENT" in (row[0] or ""):
                ok.add(t)
                return
            mig = f"{t}__rid_mig"
            store.conn.execute(f"DROP TABLE IF EXISTS {mig}")
            self._create_row_table(store, mig)
            # _create_row_table names indexes after its table argument;
            # drop the migration-name indexes and let the final CREATE
            # below rebuild them under the real name
            store.conn.execute(f"DROP INDEX IF EXISTS {mig}_time")
            store.conn.execute(f"DROP INDEX IF EXISTS {mig}_entity")
            store.conn.execute(
                f"INSERT INTO {mig} (rid, {self._ROW_COLS}) "
                f"SELECT rowid, {self._ROW_COLS} FROM {t} ORDER BY rowid"
            )
            store.conn.execute(f"DROP TABLE {t}")
            store.conn.execute(f"ALTER TABLE {mig} RENAME TO {t}")
            store.conn.execute(
                f"CREATE INDEX IF NOT EXISTS {t}_time ON {t} (event_time_ms)"
            )
            store.conn.execute(
                f"CREATE INDEX IF NOT EXISTS {t}_entity ON {t} "
                f"(entity_type, entity_id, event_time_ms)"
            )
            store.conn.commit()
            ok.add(t)

    def _sweep_orphan_segments(self, t: str, live_paths, now_ms: int) -> None:
        """Delete segment files this table owns that no manifest row
        references (a crash between file write and manifest commit, or
        a lost optimistic-concurrency race). Age-gated so a concurrent
        compactor's just-written, not-yet-committed files survive."""
        seg_dir = self._seg_dir()
        if not os.path.isdir(seg_dir):
            return
        prefix = f"{t}."
        cutoff_s = (now_ms / 1000.0) - 3600.0
        for name in os.listdir(seg_dir):
            if not name.startswith(prefix):
                continue
            path = os.path.join(seg_dir, name)
            if path in live_paths:
                continue
            try:
                if os.path.getmtime(path) < cutoff_s:
                    os.remove(path)
                    logger.info("swept orphan segment %s", path)
            except OSError:
                pass

    def compact_app(
        self, app_id: int, channel_id: Optional[int] = None, *, policy=None,
        now_ms: Optional[int] = None,
    ) -> dict:
        """One compaction round for one app/channel: per row store, seal
        the cold qualified prefix above the watermark into immutable
        segment file(s), register them + the advanced watermark in ONE
        main-db transaction, then (grace period permitting) physically
        delete sealed rows. Returns counters for observability. Safe to
        run concurrently with writers, scans, and other compactors (the
        manifest commit re-validates the watermark it started from and
        aborts if another compactor advanced it first)."""
        import time as _t

        from predictionio_tpu.data.storage import segments as _seg

        if self._c.path == ":memory:":
            return {"skipped": "memory database has no segment tier"}
        policy = policy or _seg.CompactionPolicy()
        t = self._events_table(app_id, channel_id)
        with self._c.lock:
            if not self._exists(t):
                return {"skipped": "not initialized"}
        now = int(now_ms if now_ms is not None else _t.time() * 1000)
        self._ensure_segment_schema(t)
        os.makedirs(self._seg_dir(), exist_ok=True)
        cutoff = now - int(policy.cold_s * 1000)
        result = {
            "sealed_events": 0, "segments": 0, "holdouts_added": 0,
            "rows_deleted": 0,
        }
        marks, segs = self._segment_state(t)
        for key, store in enumerate(self._c.row_stores()):
            if not store.has_table(t):
                continue
            sealed = self._compact_store(
                t, key, store, marks, policy, cutoff, now
            )
            for k, v in sealed.items():
                result[k] = result.get(k, 0) + v
        # physical cleanup + orphan sweep run AFTER sealing so a fresh
        # manifest state is observed; both are idempotent
        marks, segs = self._segment_state(t)
        deleted = self._cleanup_sealed_rows(t, marks, segs, policy, now)
        result["rows_deleted"] += deleted
        self._sweep_orphan_segments(
            t, {s["path"] for s in segs}, now
        )
        self._record_compaction_metrics(t, result, marks)
        if result["segments"]:
            logger.info(
                "compacted app %s%s: %d events into %d segment(s)",
                app_id, f"/{channel_id}" if channel_id else "",
                result["sealed_events"], result["segments"],
            )
        return result

    def _record_compaction_metrics(self, t: str, result: dict, marks) -> None:
        """Registry bookkeeping for one compaction round: lifetime
        totals (rounds, sealed events/segments, holdouts, physical
        deletes) plus the per-store rowid watermark as a gauge — the
        numbers ``CachedCompactionStatus`` recomputes with COUNT(*)
        scans, available here for free as monotone counters."""
        from predictionio_tpu.utils import metrics as _metrics

        reg = _metrics.get_registry()
        reg.counter(
            "pio_compaction_rounds_total",
            "Completed compaction rounds (per events table)",
            labels=("table",),
        ).labels(table=t).inc()
        totals = reg.counter(
            "pio_compaction_total",
            "Lifetime compaction work by kind (sealed_events, segments, "
            "holdouts_added, rows_deleted)",
            labels=("table", "kind"),
        )
        for kind in (
            "sealed_events", "segments", "holdouts_added", "rows_deleted"
        ):
            v = result.get(kind, 0)
            if v:
                totals.labels(table=t, kind=kind).inc(v)
        wm = reg.gauge(
            "pio_compaction_watermark",
            "Per-store sealed-rowid watermark (rows at or below are "
            "segment-resident)",
            labels=("table", "store"),
        )
        for store_key, mark in (marks or {}).items():
            watermark = mark[0] if isinstance(mark, tuple) else mark
            wm.labels(table=t, store=str(store_key)).set(float(watermark))

    def _compact_store(
        self, t, key, store, marks, policy, cutoff, now
    ) -> dict:
        import numpy as np

        from predictionio_tpu.data.storage import segments as _seg

        mark = marks.get(key, (0, (), 0, 0))
        wm, holdouts = mark[0], list(mark[1])
        if len(holdouts) >= policy.max_holdouts:
            return {}
        self._ensure_monotonic_rowids(store, t)
        rows = store.read_execute(
            f"SELECT rowid, {self._ROW_COLS} FROM {t} WHERE rowid > ? "
            f"ORDER BY rowid LIMIT ?",
            (wm, int(policy.max_rows)),
        ).fetchall()
        if not rows:
            return {}
        qual = _seg.RowQualifier()
        new_holdouts: list = []
        hi = wm
        day_ms = 86_400_000
        for row in rows:
            if row[9] > cutoff:  # event_time_ms
                if row[9] <= now + day_ms:
                    # genuinely recent (will cool): the cold prefix
                    # ends here
                    break
                # far-future-dated junk never cools — a break here
                # would stall the watermark for the whole store
                # forever; bounded holdout instead
                if len(holdouts) + len(new_holdouts) >= policy.max_holdouts:
                    break
                new_holdouts.append(row[0])
                hi = row[0]
                continue
            if qual.offer(row):
                hi = row[0]
            else:
                if len(holdouts) + len(new_holdouts) >= policy.max_holdouts:
                    break
                new_holdouts.append(row[0])
                hi = row[0]
        if qual.n < max(1, int(policy.min_events)):
            return {}
        # table-global dict codes for the id columns (the page store's
        # code space, so segment batches merge without re-encoding)
        e_uniq, e_inv = np.unique(
            np.asarray(qual.entity_ids, object), return_inverse=True
        )
        g_uniq, g_inv = np.unique(
            np.asarray(qual.target_ids, object), return_inverse=True
        )
        e_codes = self._dict_encode(t, e_uniq)[e_inv]
        g_codes = self._dict_encode(t, g_uniq)[g_inv]
        cols = qual.finish(e_codes, g_codes)
        files: list = []  # (path, footer)
        try:
            for s in range(0, cols.n, int(policy.rows_per_segment)):
                part = cols.slice(s, min(s + int(policy.rows_per_segment), cols.n))
                path = os.path.join(
                    self._seg_dir(),
                    f"{t}.k{key}.{int(part.rids[0])}-{int(part.rids[-1])}"
                    f".{now}-{s}.seg",
                )
                footer = _seg.write_segment_file(path, part)
                files.append((path, footer))
            fault = self.compact_fault
            if fault is not None:
                fault()
            with self._c.lock:
                # BEGIN IMMEDIATE takes the write lock BEFORE the
                # watermark re-read, so the check and the commit are one
                # atomic unit ACROSS PROCESSES too (a deferred
                # transaction would upgrade at the first INSERT — after
                # the check — letting two compactor processes both pass
                # it and register overlapping segment sets)
                self._c.conn.commit()  # close any implicit txn first
                self._c.conn.execute("BEGIN IMMEDIATE")
                try:
                    cur = self._c.conn.execute(
                        f"SELECT watermark FROM {t}_compaction "
                        f"WHERE store=?",
                        (key,),
                    ).fetchone()
                    if cur is not None and int(cur[0]) != wm:
                        # another compactor advanced this store first:
                        # our range overlaps its segments — abandon ours
                        raise _StaleWatermark()
                    for path, footer in files:
                        self._c.conn.execute(
                            f"INSERT INTO {t}_segments (store, n, "
                            f"min_rowid, max_rowid, min_ms, max_ms, "
                            f"events, entity_types, target_entity_types, "
                            f"path, checksum, created_ms, dead) "
                            f"VALUES (?,?,?,?,?,?,?,?,?,?,?,?,NULL)",
                            (
                                key, footer["n"], footer["min_rowid"],
                                footer["max_rowid"], footer["min_ms"],
                                footer["max_ms"],
                                json.dumps(footer["event_names"]),
                                json.dumps(footer["entity_types"]),
                                json.dumps(footer["target_entity_types"]),
                                path, footer["checksum"], now,
                            ),
                        )
                    all_holdouts = np.asarray(
                        holdouts + new_holdouts, np.int64
                    )
                    self._c.conn.execute(
                        f"INSERT OR REPLACE INTO {t}_compaction "
                        f"(store, watermark, cleaned, holdouts, last_ms) "
                        f"VALUES (?,?,?,?,?)",
                        (
                            key, int(hi), int(mark[2]),
                            all_holdouts.tobytes()
                            if len(all_holdouts)
                            else None,
                            now,
                        ),
                    )
                    self._c.commit()
                except BaseException:
                    # NEVER leave the IMMEDIATE transaction open with
                    # partial manifest rows: an unrelated later commit
                    # on this shared connection would persist segments
                    # WITHOUT the watermark advance — every sealed row
                    # then scans twice, forever
                    try:
                        self._c.conn.rollback()
                    except sqlite3.Error:
                        pass
                    raise
            # TOCTOU reconciliation: a delete() (or an explicit-id
            # re-post's REPLACE) that removed a sealed row AFTER our
            # snapshot but BEFORE the manifest commit found no segment
            # to tombstone — re-check the sealed range and tombstone
            # whatever vanished from the row store (deletes after the
            # commit see the manifest and tombstone themselves)
            self._reconcile_sealed_rows(t, store, files, wm, hi)
        except _StaleWatermark:
            for path, _ in files:
                try:
                    os.remove(path)
                except OSError:
                    pass
            return {}
        except BaseException:
            # crash path (incl. injected faults): files may remain as
            # orphans but the manifest never saw them — rows stay
            # authoritative, the sweep reclaims the files later
            raise
        return {
            "sealed_events": int(cols.n),
            "segments": len(files),
            "holdouts_added": len(new_holdouts),
        }

    def _reconcile_sealed_rows(self, t, store, files, wm, hi) -> None:
        """Post-commit sweep of the sealed range: any rowid the segment
        carries that is no longer in the row store was deleted (or
        REPLACE-moved by an explicit-id re-post) during the compaction
        window — tombstone it in the manifest so it cannot resurrect.
        Idempotent; races with concurrent deletes only double-set the
        same dead bits."""
        import numpy as np

        present = np.fromiter(
            (
                r[0]
                for r in store.read_execute(
                    f"SELECT rowid FROM {t} WHERE rowid > ? AND rowid <= ?",
                    (wm, hi),
                ).fetchall()
            ),
            np.int64,
        )
        present.sort()
        for path, footer in files:
            data = self._open_segment(path)
            rids = data.column("rids")
            if len(present):
                pos = np.clip(
                    np.searchsorted(present, rids), 0, len(present) - 1
                )
                found = present[pos] == rids
            else:
                found = np.zeros(len(rids), bool)
            missing = np.nonzero(~found)[0]
            if not len(missing):
                continue
            with self._c.lock:
                row = self._c.execute(
                    f"SELECT segment, dead FROM {t}_segments WHERE path=?",
                    (path,),
                ).fetchone()
                if row is None:
                    continue
                dead = (
                    np.frombuffer(row[1], np.uint8).copy()
                    if row[1] is not None
                    else np.zeros(data.n, np.uint8)
                )
                dead[missing] = 1
                self._c.execute(
                    f"UPDATE {t}_segments SET dead=? WHERE segment=?",
                    (dead.tobytes(), row[0]),
                )
                self._c.commit()
            logger.info(
                "compaction reconciliation: %d row(s) deleted during the "
                "seal window tombstoned in %s", len(missing), path,
            )

    def _cleanup_sealed_rows(self, t, marks, segs, policy, now) -> int:
        """Physically delete sealed rows once their segments are older
        than the grace period (scans snapshot the manifest at start, so
        rows must outlive any scan that began before the seal).
        Idempotent: a crash between the delete and the ``cleaned`` mark
        just re-deletes nothing next round."""
        deleted = 0
        grace_ms = int(policy.grace_s * 1000)
        for key, store in enumerate(self._c.row_stores()):
            mark = marks.get(key)
            if mark is None:
                continue
            wm, holdouts, cleaned = mark[0], mark[1], mark[2]
            eligible = [
                s["max_rowid"]
                for s in segs
                if s["store"] == key
                and s["max_rowid"] > cleaned
                and s["created_ms"] + grace_ms <= now
            ]
            if not eligible:
                continue
            upto = max(eligible)
            if not store.has_table(t):
                continue
            # delete (cleaned, upto] minus holdouts as open intervals
            # between consecutive holdout rowids — bounded statements
            bounds = sorted(
                h for h in holdouts if cleaned < h <= upto
            )
            spans = []
            lo = cleaned
            for h in bounds:
                if h - 1 > lo:
                    spans.append((lo, h - 1))
                lo = h
            if upto > lo:
                spans.append((lo, upto))
            with store.lock:
                for lo_ex, hi_in in spans:
                    cur = store.conn.execute(
                        f"DELETE FROM {t} WHERE rowid > ? AND rowid <= ?",
                        (lo_ex, hi_in),
                    )
                    deleted += max(0, cur.rowcount)
                store.conn.commit()
            with self._c.lock:
                self._c.execute(
                    f"UPDATE {t}_compaction SET cleaned=? WHERE store=?",
                    (int(upto), key),
                )
                self._c.commit()
        return deleted

    def compaction_stats(
        self, app_id: int, channel_id: Optional[int] = None
    ) -> Optional[dict]:
        """Observability summary for status.json / the admin listing:
        segment count, live compacted events, residual row events, the
        compacted fraction of the scannable store, and the last
        compaction timestamp."""
        import numpy as np

        t = self._events_table(app_id, channel_id)
        with self._c.lock:
            if not self._exists(t):
                return None
        marks, segs = self._segment_state(t)
        seg_events = 0
        for s in segs:
            dead = self._seg_dead(s)
            seg_events += int(s["n"]) - (
                int(dead.sum()) if dead is not None else 0
            )
        row_events = 0
        for key, store in enumerate(self._c.row_stores()):
            if not store.has_table(t):
                continue
            pred = self._residual_clause(marks, key)
            sql = f"SELECT COUNT(*) FROM {t}"
            params: list = []
            if pred is not None:
                sql += f" WHERE {pred[0]}"
                params = pred[1]
            row_events += int(store.read_execute(sql, params).fetchone()[0])
        page_events = 0
        self._ensure_pages_schema(t)
        with self._c.lock:
            have_pages = self._exists(f"{t}_pages")
        if have_pages:
            page_events = int(
                self._c.read_execute(
                    f"SELECT COALESCE(TOTAL(n), 0) FROM {t}_pages"
                ).fetchone()[0]
            )
            for (db,) in self._c.read_execute(
                f"SELECT dead FROM {t}_pages WHERE dead IS NOT NULL"
            ).fetchall():
                page_events -= int(np.frombuffer(db, np.uint8).sum())
        total = seg_events + row_events + page_events
        return {
            "segments": len(segs),
            "segmentEvents": seg_events,
            "rowEvents": row_events,
            "pageEvents": page_events,
            "compactedFraction": (seg_events / total) if total else 0.0,
            "lastCompactionMs": max(
                (m[3] for m in marks.values()), default=0
            ),
        }

    def iter_export_segments(
        self, app_id: int, channel_id: Optional[int] = None
    ) -> Iterator[dict]:
        """Bulk-export view of the segment tier: decoded numpy column
        groups, one per homogeneous (event, types, prop) run of each
        segment, live rows only — the near-zero-copy half of segment
        exchange (``tools/export_import.py``). Keys match
        ``iter_export_pages`` plus ``creation_times_ms``; ``event_ids``
        are the ORIGINAL ids, preserved end to end."""
        import numpy as np

        t = self._events_table(app_id, channel_id)
        with self._c.lock:
            if not self._exists(t):
                raise StorageError(f"events table {t} not initialized")
        _, segs = self._segment_state(t)
        if not segs:
            return
        names = self._dict_names(t)
        for seg in segs:
            data = self._open_segment(seg["path"])
            dead = self._seg_dead(seg)
            alive = (
                np.nonzero(dead == 0)[0]
                if dead is not None
                else np.arange(data.n)
            )
            if not len(alive):
                continue
            # group key per row: (event, prop, etype, tetype) — emit
            # maximal CONSECUTIVE runs so row order survives the
            # round trip
            gk = (
                data.column("evcodes").astype(np.int64) * (1 << 48)
                + data.column("propcodes").astype(np.int64) * (1 << 32)
                + data.column("etcodes").astype(np.int64) * (1 << 16)
                + data.column("tetcodes").astype(np.int64)
            )[alive]
            ids = data.ids_str()
            starts = np.concatenate(
                [[0], np.nonzero(gk[1:] != gk[:-1])[0] + 1, [len(alive)]]
            )
            for a, b in zip(starts[:-1], starts[1:]):
                rows = alive[a:b]
                j0 = rows[0]
                yield {
                    "event": data.event_names[data.column("evcodes")[j0]],
                    "entity_type": data.entity_types[
                        data.column("etcodes")[j0]
                    ],
                    "target_entity_type": data.target_entity_types[
                        data.column("tetcodes")[j0]
                    ],
                    "prop": data.props[data.column("propcodes")[j0]],
                    "event_ids": ids[rows],
                    "entity_ids": names[data.column("entities")[rows]],
                    "target_ids": names[data.column("targets")[rows]],
                    "values": data.column("values")[rows],
                    "times_ms": data.column("times_ms")[rows],
                    "creation_times_ms": data.column("ctimes_ms")[rows],
                }

    def insert_segment_encoded(
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
        event_ids,
        value_property: str = "rating",
        event_times_ms=None,
        creation_times_ms=None,
    ) -> int:
        """Import a homogeneous column group DIRECTLY as a sealed
        segment, preserving the original event ids — the receiving half
        of near-zero-copy segment exchange. Append-only: the caller
        (``tools/export_import.py``) falls back to the keyed generic
        path when any sampled id already exists in this store."""
        import time as _t

        import numpy as np

        from predictionio_tpu.data.storage import segments as _seg

        if self._c.path == ":memory:":
            raise StorageError("memory database has no segment tier")
        if event.startswith("$"):
            raise StorageError(
                f"insert_segment cannot write special event {event!r}"
            )
        t = self._events_table(app_id, channel_id)
        with self._c.lock:
            if not self._exists(t):
                raise StorageError(f"events table {t} not initialized")
        vals = np.asarray(values, np.float32)
        n = len(vals)
        if n == 0:
            return 0
        times = np.asarray(event_times_ms, np.int64)
        ctimes = (
            np.asarray(creation_times_ms, np.int64)
            if creation_times_ms is not None
            else times
        )
        ids_b = [str(i).encode("utf-8") for i in event_ids]
        width = max(len(b) for b in ids_b)
        if width > _seg.MAX_ID_BYTES:
            raise StorageError("event id exceeds segment id width")
        e_glob = self._dict_encode(t, np.asarray(entity_names, object))[
            np.asarray(entity_codes, np.int64)
        ]
        g_glob = self._dict_encode(t, np.asarray(target_names, object))[
            np.asarray(target_codes, np.int64)
        ]
        cols = _seg.SegmentColumns(
            rids=np.zeros(n, np.int64),  # no source rows: outside every
            ids=np.array(ids_b, dtype=f"S{width}"),  # cleanup range
            entities=e_glob.astype(np.int32),
            targets=g_glob.astype(np.int32),
            values=vals,
            times_ms=times,
            ctimes_ms=ctimes,
            evcodes=np.zeros(n, np.uint16),
            propcodes=np.zeros(n, np.uint16),
            etcodes=np.zeros(n, np.uint16),
            tetcodes=np.zeros(n, np.uint16),
            event_names=[event],
            props=[value_property],
            entity_types=[entity_type],
            target_entity_types=[target_entity_type],
        )
        now = int(_t.time() * 1000)
        self._ensure_segment_schema(t)
        os.makedirs(self._seg_dir(), exist_ok=True)
        path = os.path.join(
            self._seg_dir(),
            f"{t}.import.{now}-{os.getpid()}-"
            f"{int.from_bytes(os.urandom(4), 'big')}.seg",
        )
        footer = _seg.write_segment_file(path, cols)
        with self._c.lock:
            self._c.conn.execute(
                f"INSERT INTO {t}_segments (store, n, min_rowid, max_rowid, "
                f"min_ms, max_ms, events, entity_types, target_entity_types, "
                f"path, checksum, created_ms, dead) "
                f"VALUES (?,?,?,?,?,?,?,?,?,?,?,?,NULL)",
                (
                    0, footer["n"], 0, 0, footer["min_ms"], footer["max_ms"],
                    json.dumps(footer["event_names"]),
                    json.dumps(footer["entity_types"]),
                    json.dumps(footer["target_entity_types"]),
                    path, footer["checksum"], now,
                ),
            )
            self._c.commit()
        return n

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
        """Binary columnar scan: np.frombuffer over the matching pages +
        a SQL-evaluated residual for row-store events — no per-event
        Python objects on the bulk path (reference
        JDBCPEvents.scala:51-129's partitioned scan)."""
        import numpy as np

        from predictionio_tpu.data.storage.columnar import (
            ColumnarEvents,
            ValueSpec,
        )

        spec = value_spec or ValueSpec()
        t = self._events_table(app_id, channel_id)
        with self._c.lock:
            if not self._exists(t):
                raise StorageError(f"events table {t} not initialized")
        parts: List[ColumnarEvents] = []
        # segment state BEFORE the dict snapshot: a compaction commits
        # its dict inserts first, so any segment this state references
        # resolves through the names we read after it
        marks, segs = self._segment_state(t)
        names = None  # dict snapshot, fetched once on first need

        def dense(codes):
            # compress global dict codes to dense name-sorted
            # indices via a presence bitmap + LUT — three linear
            # passes instead of np.unique's 20M-element argsort
            # (the whole scan's former hot spot)
            seen = np.zeros(len(names), bool)
            seen[codes] = True
            present = np.nonzero(seen)[0]
            pnames = names[present]
            order = np.argsort(pnames)  # distinct-sized
            lut = np.zeros(len(names), np.int32)
            lut[present[order]] = np.arange(
                len(present), dtype=np.int32
            )
            return pnames[order], lut[codes]

        pages = self._page_rows(
            t, start_time, until_time, entity_type, event_names,
            target_entity_type,
        )
        if pages:
            overrides = spec.overrides
            lo = _ms(start_time) if start_time is not None else None
            hi = _ms(until_time) if until_time is not None else None
            e_parts, g_parts, v_parts = [], [], []
            for (
                page, ev, et, tet, prop, n, min_ms, max_ms, eb, gb, vb, tb, db
            ) in pages:
                e = np.frombuffer(eb, np.int32)
                g = np.frombuffer(gb, np.int32)
                ov = overrides.get(ev)
                if ov is not None:
                    v = np.full(n, ov, np.float32)
                elif prop == spec.prop:
                    v = np.frombuffer(vb, np.float32)
                else:  # stored under a different property: all defaults
                    v = np.full(n, spec.default, np.float32)
                needs_time = (lo is not None and min_ms < lo) or (
                    hi is not None and max_ms >= hi
                )
                if needs_time or db is not None:
                    keep = (
                        np.frombuffer(db, np.uint8) == 0
                        if db is not None
                        else np.ones(n, bool)
                    )
                    if needs_time:
                        ts = np.frombuffer(tb, np.int64)
                        if lo is not None:
                            keep = keep & (ts >= lo)
                        if hi is not None:
                            keep = keep & (ts < hi)
                    e, g, v = e[keep], g[keep], v[keep]
                e_parts.append(e)
                g_parts.append(g)
                v_parts.append(v)
            e_all = np.concatenate(e_parts)
            g_all = np.concatenate(g_parts)
            v_all = np.concatenate(v_parts)
            if len(e_all):
                if names is None:
                    names = self._dict_names(t)
                ue_names, e_codes = dense(e_all)
                ug_names, g_codes = dense(g_all)
                parts.append(
                    ColumnarEvents(
                        entity_names=ue_names,
                        target_names=ug_names,
                        entity_codes=e_codes,
                        target_codes=g_codes,
                        values=v_all,
                    )
                )

        # per row store, in deterministic order (main file, then hash
        # shards): first the store's sealed SEGMENTS (its compacted
        # rowid prefix, already in the table-global dict space), then
        # its residual rows — exactly the per-entity event order an
        # uncompacted store's residual scan yields, which is what keeps
        # the merged wire byte-identical. The streaming scan interleaves
        # identically.
        from predictionio_tpu.data.storage.columnar import encode_strings

        lo = _ms(start_time) if start_time is not None else None
        hi = _ms(until_time) if until_time is not None else None
        for key, store in enumerate(self._c.row_stores()):
            seg_e, seg_g, seg_v = [], [], []
            for seg in segs:
                if seg["store"] != key or not self._segs_match(
                    seg, event_names, entity_type, target_entity_type, lo, hi
                ):
                    continue
                data = self._open_segment(seg["path"])
                keep = data.keep_mask(
                    lo_ms=lo, hi_ms=hi, entity_type=entity_type,
                    target_entity_type=(
                        None if target_entity_type is None
                        else target_entity_type
                    ),
                    target_entity_type_set=target_entity_type is not UNSET,
                    event_names=event_names, dead=self._seg_dead(seg),
                )
                e = data.column("entities")
                g = data.column("targets")
                v = data.spec_values(spec)
                if keep is not None:
                    e, g, v = e[keep], g[keep], v[keep]
                if len(v):
                    seg_e.append(e)
                    seg_g.append(g)
                    seg_v.append(v)
            if seg_v:
                if names is None:
                    names = self._dict_names(t)
                ue_names, e_codes = dense(np.concatenate(seg_e))
                ug_names, g_codes = dense(np.concatenate(seg_g))
                parts.append(
                    ColumnarEvents(
                        entity_names=ue_names,
                        target_names=ug_names,
                        entity_codes=e_codes,
                        target_codes=g_codes,
                        values=np.concatenate(seg_v),
                    )
                )
            rows, values, _ = self._residual_scan(
                store, t, spec, start_time, until_time, entity_type,
                target_entity_type, event_names,
                extra=self._residual_clause(marks, key),
            )
            if rows:
                e_names, e_codes = encode_strings([r[0] for r in rows])
                g_names, g_codes = encode_strings([r[1] for r in rows])
                parts.append(
                    ColumnarEvents(
                        entity_names=e_names,
                        target_names=g_names,
                        entity_codes=e_codes,
                        target_codes=g_codes,
                        values=values,
                    )
                )
        return ColumnarEvents.concat(parts)

    def _residual_scan(
        self, store, t, spec, start_time, until_time, entity_type,
        target_entity_type, event_names, extra=None, stats=None,
    ):
        """Row-store residual of a columnar scan (REST-posted tail) for
        ONE row store (the main file or a hash shard) — value evaluated
        IN SQL (CASE per event override + json_extract), so even this
        path never parses JSON in Python. ``extra`` is an optional
        pre-bound ``(clause, params)`` predicate — the segment tier's
        watermark exclusion. Returns ``(rows, values, stat_rows)``: the
        raw (entity_id, target_entity_id, ...) rows, their float32
        training values, and one ``(count, max_rowid)`` pair per entry
        of ``stats`` (a list of pre-bound ``(clause, params)``
        predicates, None clause = whole table), evaluated in the SAME
        read snapshot as the row scan — the delta cursor's coverage
        accounting must be atomic with the rows it vouches for. The
        stat predicates are rowid ranges and watermark bounds only, so
        sqlite answers them from the rowid b-tree without touching the
        filter/json machinery."""
        import numpy as np

        empty_stats = [(0, 0)] * len(stats or [])
        if not store.has_table(t):
            return [], None, empty_stats

        clauses, params = self._find_clauses(
            start_time, until_time, entity_type, None, event_names,
            target_entity_type, UNSET,
        )
        clauses.append("target_entity_id IS NOT NULL")
        if extra is not None:
            clauses.append(extra[0])
            params = list(params) + list(extra[1])
        case_sql = ""
        case_params: list = []
        null_case_sql = ""
        null_case_params: list = []
        for ev_name, const in spec.overrides.items():
            case_sql += "WHEN ? THEN ? "
            case_params.extend([ev_name, float(const)])
            # override events never read the property — mask their type
            # so junk values there stay permitted (value_of skips them)
            null_case_sql += "WHEN ? THEN NULL "
            null_case_params.append(ev_name)
        # json path via parameter; quoted so property names with dots
        # stay one key
        value_sql = (
            "CAST(COALESCE(json_extract(properties, ?), ?) AS REAL)"
        )
        type_sql = "json_type(properties, ?)"
        raw_sql = "json_extract(properties, ?)"
        if case_sql:
            value_sql = f"CASE event {case_sql}ELSE {value_sql} END"
            # mask BOTH helper columns for override events — their
            # properties are never read, so malformed JSON there must not
            # fail the scan (the value CASE short-circuits past it too)
            type_sql = f"CASE event {null_case_sql}ELSE {type_sql} END"
            raw_sql = f"CASE event {null_case_sql}ELSE {raw_sql} END"
        # ORDER BY rowid pins the scan to insertion order. Without it
        # the order is the query planner's choice (the entity index
        # groups by entity id when entity_type filters) — and the
        # segment tier replays sealed rows in ROWID order, so the
        # residual must too or a compacted store's wire would diverge
        # from an uncompacted one's.
        sql = (
            f"SELECT entity_id, target_entity_id, {value_sql}, "
            f"{type_sql}, {raw_sql} FROM {t} "
            "WHERE " + " AND ".join(clauses) + " ORDER BY rowid"
        )
        prop_path = '$."' + spec.prop.replace('"', '""') + '"'
        all_params = (
            case_params + [prop_path, float(spec.default)]
            + null_case_params + [prop_path]
            + null_case_params + [prop_path] + params
        )
        stmts = [(sql, all_params)]
        for stat in stats or []:
            stat_sql = (
                f"SELECT COUNT(*), COALESCE(MAX(rowid), 0) FROM {t}"
            )
            stat_params: list = []
            if stat is not None:
                stat_sql += f" WHERE {stat[0]}"
                stat_params = list(stat[1])
            stmts.append((stat_sql, stat_params))
        results = store.read_snapshot(stmts)
        rows = results[0]
        stat_rows = [
            (int(r[0][0]), int(r[0][1])) for r in results[1:]
        ]
        if not rows:
            return [], None, stat_rows
        # CAST diverges from the per-event path on non-numeric
        # property values (unparseable text silently becomes 0.0;
        # 'nan'/'inf' strings parse in Python but not in CAST) — for
        # the rare rows whose json_type is not numeric, apply the
        # same float() rule ValueSpec.value_of uses, so bad events
        # surface (raise) and parseable text agrees exactly.
        # json null / missing keep the COALESCE default, as value_of
        # keeps its default.
        values = np.fromiter(
            (
                r[2]
                if r[3] in (None, "null", "integer", "real", "true", "false")
                else float(r[4])
                for r in rows
            ),
            np.float32,
            count=len(rows),
        )
        return rows, values, stat_rows

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
        """Chunked binary columnar scan: one batch per page/segment
        (split past ``batch_rows``), all batches in the TABLE-GLOBAL
        dictionary code space, plus per-store residual batches whose new
        ids extend that space. Order per row store: the store's sealed
        SEGMENTS (its compacted rowid prefix), then its residual rows —
        the per-entity event order of an uncompacted store, which keeps
        the merged wire byte-identical. The page-id list and the segment
        manifest are snapshotted up front (ids/manifest only, no blobs),
        so peak memory is one page/segment and anything committed
        mid-scan is simply not part of this scan."""
        import numpy as np

        from predictionio_tpu.data.storage.columnar import (
            ColumnarStream,
            ValueSpec,
        )

        spec = value_spec or ValueSpec()
        t = self._events_table(app_id, channel_id)
        with self._c.lock:
            if not self._exists(t):
                raise StorageError(f"events table {t} not initialized")
        # fingerprint (and the table generation) BEFORE the scan: a
        # concurrent write during the scan then makes the next cache
        # lookup miss, never hit stale
        fingerprint = self.store_fingerprint(app_id, channel_id)
        generation = self._table_generation(t)
        self._ensure_pages_schema(t)
        # segment state BEFORE the dict snapshot (compaction commits its
        # dict inserts first, so every referenced code resolves)
        marks, segs = self._segment_state(t)
        # The dict-name snapshot and the page-id listing are DEFERRED to
        # first iteration: a continuous-training fold round constructs
        # this stream only for its fingerprint/cursor identity and never
        # consumes it — eager setup would charge every delta round an
        # O(vocab) dict read it doesn't use.
        dict_snapshot, enc, names = self._residual_code_space(t)

        def _page_id_listing() -> List[int]:
            # ids only, no blobs (peak memory stays one page); the
            # filter is the SAME clause builder the monolithic scan
            # uses, so both paths select identical pages by construction
            filt = self._page_filter(
                start_time, until_time, entity_type, event_names,
                target_entity_type,
            )
            if filt is None:
                return []
            clauses, params = filt
            sql = f"SELECT page FROM {t}_pages"
            if clauses:
                sql += " WHERE " + " AND ".join(clauses)
            with self._c.lock:
                have_pages = self._exists(f"{t}_pages")
            if not have_pages:
                return []
            return [
                r[0]
                for r in self._c.read_execute(
                    sql + " ORDER BY page", params
                ).fetchall()
            ]
        # per row store (residual-live count, max residual rowid), read
        # in the SAME snapshot as that store's residual row scan —
        # finalized into the delta cursor on exhaustion. Snapshot
        # atomicity is what keeps the cursor exactly consistent with the
        # folded data: a row committed after the snapshot has a higher
        # rowid and is the next delta's business, never skipped, never
        # double-folded.
        cursor_state = {
            "stores": [(0, 0) for _ in self._c.row_stores()],
        }

        def batches():
            overrides = spec.overrides
            lo = _ms(start_time) if start_time is not None else None
            hi = _ms(until_time) if until_time is not None else None
            # snapshot order: segment state was read above; pages are
            # listed BEFORE the dict snapshot (writers commit dict
            # entries first, pages second — listing first guarantees
            # every listed page's global codes resolve in the names
            # snapshot, and the residual enc() extras can never collide
            # with codes a racing import minted)
            page_ids = _page_id_listing()
            dict_snapshot()
            for page_id in page_ids:
                row = self._c.read_execute(
                    f"SELECT event, prop, n, min_ms, max_ms, entities, "
                    f"targets, vals, times, dead FROM {t}_pages "
                    f"WHERE page=?",
                    (page_id,),
                ).fetchone()
                if row is None:
                    continue  # deleted since listing
                ev, prop, n, min_ms, max_ms, eb, gb, vb, tb, db = row
                e = np.frombuffer(eb, np.int32)
                g = np.frombuffer(gb, np.int32)
                ov = overrides.get(ev)
                if ov is not None:
                    v = np.full(n, ov, np.float32)
                elif prop == spec.prop:
                    v = np.frombuffer(vb, np.float32)
                else:  # stored under a different property: all defaults
                    v = np.full(n, spec.default, np.float32)
                needs_time = (lo is not None and min_ms < lo) or (
                    hi is not None and max_ms >= hi
                )
                if needs_time or db is not None:
                    keep = (
                        np.frombuffer(db, np.uint8) == 0
                        if db is not None
                        else np.ones(n, bool)
                    )
                    if needs_time:
                        ts = np.frombuffer(tb, np.int64)
                        if lo is not None:
                            keep = keep & (ts >= lo)
                        if hi is not None:
                            keep = keep & (ts < hi)
                    e, g, v = e[keep], g[keep], v[keep]
                for s in range(0, len(v), batch_rows):
                    sl = slice(s, s + batch_rows)
                    if len(v[sl]):
                        yield e[sl], g[sl], v[sl]
            # per row store, in deterministic order (main file, then
            # hash shards — the same order find_columns_native
            # concatenates them): the store's segments (already in the
            # global dict code space, like pages), then its residual
            # rows. All stores' residual ids map into ONE shared code
            # space through a name->code dict; unseen ids extend it
            # (the residual is the REST tail — small next to the
            # page/segment bulk). Events of one entity live in one
            # shard, so each entity's events keep their per-store order
            # and the consumer's stable counting-sort merge reproduces
            # the single-file, uncompacted wire byte-for-byte.
            tet_set = target_entity_type is not UNSET
            for key, store in enumerate(self._c.row_stores()):
                for seg in segs:
                    if seg["store"] != key or not self._segs_match(
                        seg, event_names, entity_type, target_entity_type,
                        lo, hi,
                    ):
                        continue
                    data = self._open_segment(seg["path"])
                    keep = data.keep_mask(
                        lo_ms=lo, hi_ms=hi, entity_type=entity_type,
                        target_entity_type=(
                            None if target_entity_type is None
                            else target_entity_type
                        ),
                        target_entity_type_set=tet_set,
                        event_names=event_names, dead=self._seg_dead(seg),
                    )
                    e = data.column("entities")
                    g = data.column("targets")
                    v = data.spec_values(spec)
                    if keep is not None:
                        e, g, v = e[keep], g[keep], v[keep]
                    for s in range(0, len(v), batch_rows):
                        sl = slice(s, s + batch_rows)
                        if len(v[sl]):
                            yield e[sl], g[sl], v[sl]
                residual_pred = self._residual_clause(marks, key)
                rows, values, stats = self._residual_scan(
                    store, t, spec, start_time, until_time, entity_type,
                    target_entity_type, event_names,
                    extra=residual_pred,
                    # UNFILTERED residual-live coverage, same snapshot
                    stats=[residual_pred],
                )
                cursor_state["stores"][key] = stats[0]
                if not rows:
                    continue
                e_codes = enc([r[0] for r in rows])
                g_codes = enc([r[1] for r in rows])
                for s in range(0, len(values), batch_rows):
                    sl = slice(s, s + batch_rows)
                    if len(values[sl]):
                        yield e_codes[sl], g_codes[sl], values[sl]

        def cursor():
            return self._delta_cursor(
                cursor_state["stores"], marks, segs, fingerprint,
                generation,
            )

        return ColumnarStream(
            batches(), names, fingerprint=fingerprint, cursor_fn=cursor
        )

    # --- delta scan (incremental training, round 9) ---
    #
    # A scan's cursor records, per row store, the high-water rowid it
    # covered (the store's max rowid at the scan's snapshot, residual
    # and sealed alike), how many LIVE rows sat at or below it —
    # unfiltered: residual-live count + sealed-live manifest sums — and
    # the compaction state (watermark + holdouts) it replayed under;
    # the page-store signature rides along whole. The delta scan
    # re-validates all of it: rowids are AUTOINCREMENT (PR 4 migrated
    # every row table) so the covered prefix can never grow back, the
    # live count at or below the mark is monotone non-increasing under
    # the only mutations sqlite allows (delete, tombstone, explicit-id
    # re-post — which reassigns the rowid), and compaction only moves
    # rows across the segment/residual split without changing the sum.
    # Count equality therefore PROVES the folded prefix is still
    # exactly what a full rescan would emit first — and the delta is
    # every matching row above the mark, sealed segments first (their
    # manifest order IS rowid order), then residual rows, the same
    # order the full scan interleaves. Everything the validation reads
    # is rowid-b-tree range counts and manifest/dead-bitmap sums — no
    # per-row filter or json evaluation, so polling a quiet 20M store
    # costs milliseconds, not a scan.

    @staticmethod
    def _seg_live_count(seg, dead_arr) -> int:
        n = int(seg["n"])
        return n - int(dead_arr.sum()) if dead_arr is not None else n

    def _residual_code_space(self, t: str):
        """The streaming scans' shared code space: a DEFERRED
        table-global dict snapshot, the residual-tail string encoder
        over it, and the post-iteration ``names`` resolver. One
        implementation for the native scan AND the delta scan — the
        fold's wire byte-identity depends on both paths encoding
        residual ids identically (code seeding, extra-name append
        order, names() concatenation), so they must never diverge.

        Deferral matters twice over: a continuous-training fold round
        constructs the native stream only for its fingerprint/cursor
        identity, and an empty delta round has no residual rows — in
        both cases the O(vocab) dict read never happens. Call
        ``snapshot()``/``enc()`` only AFTER the data they cover was
        listed: the dict is append-only, so a later snapshot is always
        a superset of the codes that data references, and extras minted
        past it can never collide."""
        import numpy as np

        state: dict = {"names": None, "extra": [], "code_of": None}

        def snapshot():
            if state["names"] is None:
                state["names"] = self._dict_names(t)
            return state["names"]

        def enc(strs):
            if state["code_of"] is None:
                state["code_of"] = {
                    str(nm): j for j, nm in enumerate(snapshot())
                }
            code_of = state["code_of"]
            out = np.empty(len(strs), np.int32)
            for j, s in enumerate(strs):
                c = code_of.get(s)
                if c is None:
                    c = len(code_of)
                    code_of[s] = c
                    state["extra"].append(s)
                out[j] = c
            return out

        def names():
            base_names = snapshot()
            if not state["extra"]:
                return base_names
            extra = np.empty(len(state["extra"]), object)
            extra[:] = state["extra"]
            return np.concatenate([base_names, extra])

        return snapshot, enc, names

    def _table_generation(self, t: str) -> int:
        """Monotone per-events-table generation (main db, survives the
        table itself): ``remove()`` bumps it, so a delta cursor taken
        before a DROP — which resets the AUTOINCREMENT sequence — can
        never validate against the recreated table."""
        with self._c.lock:
            self._c.execute(_GEN_SCHEMA)
            row = self._c.execute(
                "SELECT gen FROM pio_table_gen WHERE tbl=?", (t,)
            ).fetchone()
            if row is not None:
                return int(row[0])
            self._c.execute(
                "INSERT INTO pio_table_gen (tbl, gen) VALUES (?, 1)",
                (t,),
            )
            self._c.commit()
            return 1

    def _delta_cursor(
        self, stores, marks, segs, fingerprint, generation
    ) -> tuple:
        """Assemble the opaque cursor from the per-store residual
        coverage (``(residual-live count, max residual rowid)`` read in
        the residual scan's snapshot), the segment manifest, the
        compaction snapshot, the pre-scan fingerprint's page-store
        component, and the table generation."""
        parts = []
        for key, (rcount, rmax) in enumerate(stores):
            sealed_live = 0
            seg_max = 0
            for seg in segs:
                if seg["store"] != key:
                    continue
                sealed_live += self._seg_live_count(
                    seg, self._seg_dead(seg)
                )
                seg_max = max(seg_max, int(seg["max_rowid"]))
            hwm = max(int(rmax), seg_max)
            mark = marks.get(key) if marks else None
            wm = mark[0] if mark else 0
            holds = mark[1] if mark else ()
            parts.append(
                (
                    hwm,
                    int(rcount) + sealed_live,
                    int(wm),
                    tuple(h for h in holds if h <= hwm),
                )
            )
        pages_sig = (
            (fingerprint[2], fingerprint[3]) if fingerprint else None
        )
        return (
            "sqlite-delta", int(generation), len(parts), tuple(parts),
            pages_sig,
        )

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
        """Incremental columnar scan above a prior scan's cursor
        (``base.LEvents.stream_columns_delta``). Returns None — full
        repack — whenever appending the delta could NOT reproduce a full
        rescan: page-store changes (bulk imports order before all row
        stores), any shrink of the matching live rows at or below a
        store's high-water mark (delete / tombstone / explicit-id
        re-post), new holdouts at or below the mark or a watermark that
        moved past interleaved holdouts (both reorder the already-folded
        prefix), or a changed shard layout."""
        import numpy as np

        from predictionio_tpu.data.storage.columnar import (
            ColumnarStream,
            ValueSpec,
        )

        if (
            not isinstance(cursor, tuple)
            or len(cursor) != 5
            or cursor[0] != "sqlite-delta"
        ):
            return None
        spec = value_spec or ValueSpec()
        t = self._events_table(app_id, channel_id)
        with self._c.lock:
            if not self._exists(t):
                return None
        stores = self._c.row_stores()
        if cursor[2] != len(stores):
            return None  # shard layout changed under the cursor
        generation = self._table_generation(t)
        if cursor[1] != generation:
            # the table was dropped and recreated since the cursor:
            # its AUTOINCREMENT sequence restarted, so rowid/count
            # arithmetic against the old prefix proves nothing
            return None
        # fingerprint BEFORE the scan (labels the folded artifact; a
        # racing write makes the next cache lookup miss, never hit stale)
        fingerprint = self.store_fingerprint(app_id, channel_id)
        self._ensure_pages_schema(t)
        marks, segs = self._segment_state(t)
        pages_sig = (
            (fingerprint[2], fingerprint[3]) if fingerprint else None
        )
        if pages_sig != cursor[4]:
            return None  # page store changed: pages order before rows
        lo = _ms(start_time) if start_time is not None else None
        hi = _ms(until_time) if until_time is not None else None

        per_store = []  # (seg_parts, rows, values) to emit, store order
        new_parts = []  # the chained cursor's per-store records
        for key, store in enumerate(stores):
            hwm, live_then, wm_then, holds_then = cursor[3][key]
            mark = marks.get(key) if marks else None
            wm_now = mark[0] if mark else 0
            holds_now = mark[1] if mark else ()
            if tuple(h for h in holds_now if h <= hwm) != holds_then:
                # compaction held out rows inside the folded prefix: a
                # full rescan now replays them AFTER sealed rows the
                # fold placed them before
                return None
            if holds_then and wm_now != wm_then:
                # sealed rows moved past interleaved holdouts (see
                # docs/PERF.md, delta training): replay order of the
                # folded prefix changed
                return None
            sealed_le = 0  # live sealed rows at or below the mark
            sealed_above = 0  # live sealed rows above it (delta region)
            seg_max = 0
            seg_parts = []  # (SegmentData, mask): matching rows > hwm
            for seg in segs:
                if seg["store"] != key:
                    continue
                seg_max = max(seg_max, int(seg["max_rowid"]))
                dead_arr = self._seg_dead(seg)
                if seg["max_rowid"] <= hwm:
                    sealed_le += self._seg_live_count(seg, dead_arr)
                elif seg["min_rowid"] > hwm:
                    sealed_above += self._seg_live_count(seg, dead_arr)
                else:  # straddles the mark: split by source rowid
                    data = self._open_segment(seg["path"])
                    rid = data.column("rids")
                    alive = (
                        dead_arr == 0
                        if dead_arr is not None
                        else np.ones(data.n, bool)
                    )
                    sealed_le += int(
                        np.count_nonzero(alive & (rid <= hwm))
                    )
                    sealed_above += int(
                        np.count_nonzero(alive & (rid > hwm))
                    )
                if seg["max_rowid"] > hwm and self._segs_match(
                    seg, event_names, entity_type, target_entity_type,
                    lo, hi,
                ):
                    data = self._open_segment(seg["path"])
                    keep = data.keep_mask(
                        lo_ms=lo, hi_ms=hi, entity_type=entity_type,
                        target_entity_type=(
                            None if target_entity_type is None
                            else target_entity_type
                        ),
                        target_entity_type_set=(
                            target_entity_type is not UNSET
                        ),
                        event_names=event_names, dead=self._seg_dead(seg),
                    )
                    if keep is None:
                        keep = np.ones(data.n, bool)
                    dm = keep & (data.column("rids") > hwm)
                    if dm.any():
                        seg_parts.append((data, dm))
            residual_pred = self._residual_clause(marks, key)
            rows, values, stats = self._residual_scan(
                store, t, spec, start_time, until_time, entity_type,
                target_entity_type, event_names,
                extra=self._and_extras(
                    residual_pred, ("rowid > ?", [hwm])
                ),
                # same-snapshot coverage accounting, rowid ranges only:
                # live residual rows at/below the mark, and the count +
                # max rowid of the delta region
                stats=[
                    self._and_extras(
                        residual_pred, ("rowid <= ?", [hwm])
                    ),
                    self._and_extras(
                        residual_pred, ("rowid > ?", [hwm])
                    ),
                ],
            )
            (resid_le, _), (resid_above, resid_max_above) = stats
            if resid_le + sealed_le != live_then:
                return None  # the folded prefix shrank: full repack
            new_hwm = max(hwm, seg_max, resid_max_above)
            new_live = live_then + resid_above + sealed_above
            new_holds = tuple(h for h in holds_now if h <= new_hwm)
            new_parts.append((new_hwm, new_live, int(wm_now), new_holds))
            per_store.append((seg_parts, rows, values))

        # shared deferred code space (see _residual_code_space): an
        # empty delta round — common while polling — never pays the
        # O(vocab) dict read, and the residual encoding is the SAME
        # implementation the native scan uses, byte for byte
        _, enc, names = self._residual_code_space(t)

        new_cursor = (
            "sqlite-delta", generation, len(new_parts),
            tuple(new_parts), pages_sig,
        )

        def batches():
            for seg_parts, rows, values in per_store:
                for data, dm in seg_parts:
                    e = data.column("entities")[dm]
                    g = data.column("targets")[dm]
                    v = data.spec_values(spec)[dm]
                    for s in range(0, len(v), batch_rows):
                        sl = slice(s, s + batch_rows)
                        if len(v[sl]):
                            yield e[sl], g[sl], v[sl]
                if not rows:
                    continue
                e_codes = enc([r[0] for r in rows])
                g_codes = enc([r[1] for r in rows])
                for s in range(0, len(values), batch_rows):
                    sl = slice(s, s + batch_rows)
                    if len(values[sl]):
                        yield e_codes[sl], g_codes[sl], values[sl]

        return ColumnarStream(
            batches(), names, fingerprint=fingerprint,
            cursor_fn=lambda: new_cursor,
        )

    def store_fingerprint(
        self, app_id: int, channel_id: Optional[int] = None
    ) -> Optional[tuple]:
        """Cheap store-state aggregates: per row store (the main file
        plus every hash shard) a (count, max rowid, max event time)
        triple, + page store (count, max page id, total rows, max time)
        + exact tombstone populations + the segment manifest (id, n,
        dead population per segment). Every mutating path moves at
        least one component: inserts bump their shard's counts/max-rowid
        (INSERT OR REPLACE reassigns the rowid), bulk imports add pages,
        compactions register segments, deletes shrink counts or flip
        tombstone bits. Row triples apply the segment tier's residual
        predicate, so the DEFERRED physical delete of sealed rows (pure
        space reclaim, no logical change) never moves the fingerprint —
        the pack cache keeps hitting across cleanups. Costs a few
        aggregate scans plus one pass over the (rare) dead blobs."""
        import numpy as np

        t = self._events_table(app_id, channel_id)
        with self._c.lock:
            if not self._exists(t):
                return None
        marks, segs = self._segment_state(t)
        row_parts = []
        for key, store in enumerate(self._c.row_stores()):
            if not store.has_table(t):
                row_parts.append((0, 0, 0))
                continue
            sql = (
                f"SELECT COUNT(*), COALESCE(MAX(rowid), 0), "
                f"COALESCE(MAX(event_time_ms), 0) FROM {t}"
            )
            pred = self._residual_clause(marks, key)
            params: list = []
            if pred is not None:
                sql += f" WHERE {pred[0]}"
                params = pred[1]
            row_parts.append(
                tuple(store.read_execute(sql, params).fetchone())
            )
        row = tuple(row_parts)
        seg_sig = tuple(
            (
                s["segment"], s["n"],
                int(np.frombuffer(s["dead"], np.uint8).sum())
                if s["dead"] is not None
                else 0,
            )
            for s in segs
        )
        pages = (0, 0, 0, 0)
        dead_sig: tuple = ()
        self._ensure_pages_schema(t)
        with self._c.lock:
            have_pages = self._exists(f"{t}_pages")
        if have_pages:
            pages = tuple(
                self._c.read_execute(
                    f"SELECT COUNT(*), COALESCE(MAX(page), 0), "
                    f"COALESCE(TOTAL(n), 0), COALESCE(MAX(max_ms), 0) "
                    f"FROM {t}_pages"
                ).fetchone()
            )
            dead_sig = tuple(
                (page, int(np.frombuffer(db, np.uint8).sum()))
                for page, db in self._c.read_execute(
                    f"SELECT page, dead FROM {t}_pages "
                    f"WHERE dead IS NOT NULL ORDER BY page"
                ).fetchall()
            )
        return ("sqlite", row, pages, dead_sig, seg_sig)


class _SQLiteMetaBase:
    def __init__(self, client: StorageClient, config=None, namespace: str = ""):
        self._c = client
        self._ns = namespace or "pio"
        with self._c.lock:
            self._create()
            self._c.commit()

    def _t(self, suffix: str) -> str:
        return _table_name(self._ns, suffix)

    def _create(self) -> None:
        raise NotImplementedError


class SQLiteApps(_SQLiteMetaBase, base.Apps):
    def _create(self):
        self._c.execute(
            f"""CREATE TABLE IF NOT EXISTS {self._t('apps')} (
                id INTEGER PRIMARY KEY AUTOINCREMENT,
                name TEXT NOT NULL UNIQUE,
                description TEXT)"""
        )

    def insert(self, app: App) -> Optional[int]:
        with self._c.lock:
            try:
                if app.id:
                    cur = self._c.execute(
                        f"INSERT INTO {self._t('apps')} (id,name,description) VALUES (?,?,?)",
                        (app.id, app.name, app.description),
                    )
                else:
                    cur = self._c.execute(
                        f"INSERT INTO {self._t('apps')} (name,description) VALUES (?,?)",
                        (app.name, app.description),
                    )
                self._c.commit()
                return cur.lastrowid if not app.id else app.id
            except sqlite3.IntegrityError:
                return None

    def get(self, app_id: int) -> Optional[App]:
        row = self._c.execute(
            f"SELECT id,name,description FROM {self._t('apps')} WHERE id=?", (app_id,)
        ).fetchone()
        return App(*row) if row else None

    def get_by_name(self, name: str) -> Optional[App]:
        row = self._c.execute(
            f"SELECT id,name,description FROM {self._t('apps')} WHERE name=?", (name,)
        ).fetchone()
        return App(*row) if row else None

    def get_all(self) -> List[App]:
        rows = self._c.execute(
            f"SELECT id,name,description FROM {self._t('apps')} ORDER BY id"
        ).fetchall()
        return [App(*r) for r in rows]

    def update(self, app: App) -> bool:
        with self._c.lock:
            cur = self._c.execute(
                f"UPDATE {self._t('apps')} SET name=?,description=? WHERE id=?",
                (app.name, app.description, app.id),
            )
            self._c.commit()
            return cur.rowcount > 0

    def delete(self, app_id: int) -> bool:
        with self._c.lock:
            cur = self._c.execute(
                f"DELETE FROM {self._t('apps')} WHERE id=?", (app_id,)
            )
            self._c.commit()
            return cur.rowcount > 0


class SQLiteAccessKeys(_SQLiteMetaBase, base.AccessKeys):
    def _create(self):
        self._c.execute(
            f"""CREATE TABLE IF NOT EXISTS {self._t('access_keys')} (
                key TEXT PRIMARY KEY, appid INTEGER NOT NULL, events TEXT)"""
        )

    def insert(self, access_key: AccessKey) -> Optional[str]:
        key = access_key.key or self.generate_key()
        with self._c.lock:
            try:
                self._c.execute(
                    f"INSERT INTO {self._t('access_keys')} VALUES (?,?,?)",
                    (key, access_key.appid, json.dumps(list(access_key.events))),
                )
                self._c.commit()
                return key
            except sqlite3.IntegrityError:
                return None

    @staticmethod
    def _row(row) -> AccessKey:
        return AccessKey(row[0], row[1], tuple(json.loads(row[2] or "[]")))

    def get(self, key: str) -> Optional[AccessKey]:
        row = self._c.execute(
            f"SELECT * FROM {self._t('access_keys')} WHERE key=?", (key,)
        ).fetchone()
        return self._row(row) if row else None

    def get_all(self) -> List[AccessKey]:
        return [
            self._row(r)
            for r in self._c.execute(
                f"SELECT * FROM {self._t('access_keys')}"
            ).fetchall()
        ]

    def get_by_app_id(self, app_id: int) -> List[AccessKey]:
        return [
            self._row(r)
            for r in self._c.execute(
                f"SELECT * FROM {self._t('access_keys')} WHERE appid=?", (app_id,)
            ).fetchall()
        ]

    def update(self, access_key: AccessKey) -> bool:
        with self._c.lock:
            cur = self._c.execute(
                f"UPDATE {self._t('access_keys')} SET appid=?,events=? WHERE key=?",
                (access_key.appid, json.dumps(list(access_key.events)), access_key.key),
            )
            self._c.commit()
            return cur.rowcount > 0

    def delete(self, key: str) -> bool:
        with self._c.lock:
            cur = self._c.execute(
                f"DELETE FROM {self._t('access_keys')} WHERE key=?", (key,)
            )
            self._c.commit()
            return cur.rowcount > 0


class SQLiteChannels(_SQLiteMetaBase, base.Channels):
    def _create(self):
        self._c.execute(
            f"""CREATE TABLE IF NOT EXISTS {self._t('channels')} (
                id INTEGER PRIMARY KEY AUTOINCREMENT,
                name TEXT NOT NULL, appid INTEGER NOT NULL)"""
        )

    def insert(self, channel: Channel) -> Optional[int]:
        if not Channel.is_valid_name(channel.name):
            return None
        with self._c.lock:
            if channel.id:
                self._c.execute(
                    f"INSERT INTO {self._t('channels')} (id,name,appid) VALUES (?,?,?)",
                    (channel.id, channel.name, channel.appid),
                )
                cid = channel.id
            else:
                cur = self._c.execute(
                    f"INSERT INTO {self._t('channels')} (name,appid) VALUES (?,?)",
                    (channel.name, channel.appid),
                )
                cid = cur.lastrowid
            self._c.commit()
            return cid

    def get(self, channel_id: int) -> Optional[Channel]:
        row = self._c.execute(
            f"SELECT id,name,appid FROM {self._t('channels')} WHERE id=?",
            (channel_id,),
        ).fetchone()
        return Channel(*row) if row else None

    def get_by_app_id(self, app_id: int) -> List[Channel]:
        rows = self._c.execute(
            f"SELECT id,name,appid FROM {self._t('channels')} WHERE appid=?",
            (app_id,),
        ).fetchall()
        return [Channel(*r) for r in rows]

    def delete(self, channel_id: int) -> bool:
        with self._c.lock:
            cur = self._c.execute(
                f"DELETE FROM {self._t('channels')} WHERE id=?", (channel_id,)
            )
            self._c.commit()
            return cur.rowcount > 0


class SQLiteEngineManifests(_SQLiteMetaBase, base.EngineManifests):
    def _create(self):
        self._c.execute(
            f"""CREATE TABLE IF NOT EXISTS {self._t('engine_manifests')} (
                id TEXT, version TEXT, name TEXT, description TEXT,
                files TEXT, engine_factory TEXT,
                PRIMARY KEY (id, version))"""
        )

    def insert(self, manifest: EngineManifest) -> None:
        self.update(manifest, upsert=True)

    def get(self, id: str, version: str) -> Optional[EngineManifest]:
        row = self._c.execute(
            f"SELECT * FROM {self._t('engine_manifests')} WHERE id=? AND version=?",
            (id, version),
        ).fetchone()
        if not row:
            return None
        return EngineManifest(
            row[0], row[1], row[2], row[3], tuple(json.loads(row[4] or "[]")), row[5]
        )

    def get_all(self) -> List[EngineManifest]:
        rows = self._c.execute(
            f"SELECT * FROM {self._t('engine_manifests')}"
        ).fetchall()
        return [
            EngineManifest(r[0], r[1], r[2], r[3], tuple(json.loads(r[4] or "[]")), r[5])
            for r in rows
        ]

    def update(self, manifest: EngineManifest, upsert: bool = False) -> None:
        with self._c.lock:
            self._c.execute(
                f"INSERT OR REPLACE INTO {self._t('engine_manifests')} VALUES (?,?,?,?,?,?)",
                (
                    manifest.id,
                    manifest.version,
                    manifest.name,
                    manifest.description,
                    json.dumps(list(manifest.files)),
                    manifest.engine_factory,
                ),
            )
            self._c.commit()

    def delete(self, id: str, version: str) -> None:
        with self._c.lock:
            self._c.execute(
                f"DELETE FROM {self._t('engine_manifests')} WHERE id=? AND version=?",
                (id, version),
            )
            self._c.commit()


_EI_COLS = (
    "id, status, start_time, end_time, engine_id, engine_version, "
    "engine_variant, engine_factory, batch, env, spark_conf, "
    "data_source_params, preparator_params, algorithms_params, serving_params"
)


class SQLiteEngineInstances(_SQLiteMetaBase, base.EngineInstances):
    def _create(self):
        self._c.execute(
            f"""CREATE TABLE IF NOT EXISTS {self._t('engine_instances')} (
                id TEXT PRIMARY KEY, status TEXT, start_time TEXT, end_time TEXT,
                engine_id TEXT, engine_version TEXT, engine_variant TEXT,
                engine_factory TEXT, batch TEXT, env TEXT, spark_conf TEXT,
                data_source_params TEXT, preparator_params TEXT,
                algorithms_params TEXT, serving_params TEXT)"""
        )

    @staticmethod
    def _row(r) -> EngineInstance:
        return EngineInstance(
            id=r[0],
            status=r[1],
            start_time=parse_iso8601(r[2]),
            end_time=parse_iso8601(r[3]),
            engine_id=r[4],
            engine_version=r[5],
            engine_variant=r[6],
            engine_factory=r[7],
            batch=r[8] or "",
            env=json.loads(r[9] or "{}"),
            spark_conf=json.loads(r[10] or "{}"),
            data_source_params=r[11] or "",
            preparator_params=r[12] or "",
            algorithms_params=r[13] or "",
            serving_params=r[14] or "",
        )

    def _write(self, i: EngineInstance) -> None:
        self._c.execute(
            f"INSERT OR REPLACE INTO {self._t('engine_instances')} "
            f"VALUES (?,?,?,?,?,?,?,?,?,?,?,?,?,?,?)",
            (
                i.id,
                i.status,
                _utc_iso(i.start_time),
                _utc_iso(i.end_time),
                i.engine_id,
                i.engine_version,
                i.engine_variant,
                i.engine_factory,
                i.batch,
                json.dumps(i.env),
                json.dumps(i.spark_conf),
                i.data_source_params,
                i.preparator_params,
                i.algorithms_params,
                i.serving_params,
            ),
        )

    def insert(self, instance: EngineInstance) -> str:
        import uuid

        iid = instance.id or uuid.uuid4().hex[:17]
        with self._c.lock:
            self._write(dataclasses.replace(instance, id=iid))
            self._c.commit()
        return iid

    def get(self, id: str) -> Optional[EngineInstance]:
        row = self._c.execute(
            f"SELECT {_EI_COLS} FROM {self._t('engine_instances')} WHERE id=?", (id,)
        ).fetchone()
        return self._row(row) if row else None

    def get_all(self) -> List[EngineInstance]:
        rows = self._c.execute(
            f"SELECT {_EI_COLS} FROM {self._t('engine_instances')}"
        ).fetchall()
        return [self._row(r) for r in rows]

    def get_completed(
        self, engine_id: str, engine_version: str, engine_variant: str
    ) -> List[EngineInstance]:
        rows = self._c.execute(
            f"SELECT {_EI_COLS} FROM {self._t('engine_instances')} "
            "WHERE status=? AND engine_id=? AND engine_version=? AND engine_variant=? "
            "ORDER BY start_time DESC",
            (base.STATUS_COMPLETED, engine_id, engine_version, engine_variant),
        ).fetchall()
        return [self._row(r) for r in rows]

    def get_latest_completed(
        self, engine_id: str, engine_version: str, engine_variant: str
    ) -> Optional[EngineInstance]:
        out = self.get_completed(engine_id, engine_version, engine_variant)
        return out[0] if out else None

    def update(self, instance: EngineInstance) -> None:
        with self._c.lock:
            self._write(instance)
            self._c.commit()

    def delete(self, id: str) -> None:
        with self._c.lock:
            self._c.execute(
                f"DELETE FROM {self._t('engine_instances')} WHERE id=?", (id,)
            )
            self._c.commit()


class SQLiteEvaluationInstances(_SQLiteMetaBase, base.EvaluationInstances):
    def _create(self):
        self._c.execute(
            f"""CREATE TABLE IF NOT EXISTS {self._t('evaluation_instances')} (
                id TEXT PRIMARY KEY, status TEXT, start_time TEXT, end_time TEXT,
                evaluation_class TEXT, engine_params_generator_class TEXT,
                batch TEXT, env TEXT, spark_conf TEXT,
                evaluator_results TEXT, evaluator_results_html TEXT,
                evaluator_results_json TEXT)"""
        )

    @staticmethod
    def _row(r) -> EvaluationInstance:
        return EvaluationInstance(
            id=r[0],
            status=r[1],
            start_time=parse_iso8601(r[2]),
            end_time=parse_iso8601(r[3]),
            evaluation_class=r[4] or "",
            engine_params_generator_class=r[5] or "",
            batch=r[6] or "",
            env=json.loads(r[7] or "{}"),
            spark_conf=json.loads(r[8] or "{}"),
            evaluator_results=r[9] or "",
            evaluator_results_html=r[10] or "",
            evaluator_results_json=r[11] or "",
        )

    def _write(self, i: EvaluationInstance) -> None:
        self._c.execute(
            f"INSERT OR REPLACE INTO {self._t('evaluation_instances')} "
            f"VALUES (?,?,?,?,?,?,?,?,?,?,?,?)",
            (
                i.id,
                i.status,
                _utc_iso(i.start_time),
                _utc_iso(i.end_time),
                i.evaluation_class,
                i.engine_params_generator_class,
                i.batch,
                json.dumps(i.env),
                json.dumps(i.spark_conf),
                i.evaluator_results,
                i.evaluator_results_html,
                i.evaluator_results_json,
            ),
        )

    def insert(self, instance: EvaluationInstance) -> str:
        import uuid

        iid = instance.id or uuid.uuid4().hex[:17]
        with self._c.lock:
            self._write(dataclasses.replace(instance, id=iid))
            self._c.commit()
        return iid

    def get(self, id: str) -> Optional[EvaluationInstance]:
        row = self._c.execute(
            f"SELECT * FROM {self._t('evaluation_instances')} WHERE id=?", (id,)
        ).fetchone()
        return self._row(row) if row else None

    def get_all(self) -> List[EvaluationInstance]:
        rows = self._c.execute(
            f"SELECT * FROM {self._t('evaluation_instances')}"
        ).fetchall()
        return [self._row(r) for r in rows]

    def get_completed(self) -> List[EvaluationInstance]:
        rows = self._c.execute(
            f"SELECT * FROM {self._t('evaluation_instances')} "
            "WHERE status=? ORDER BY start_time DESC",
            (base.STATUS_COMPLETED,),
        ).fetchall()
        return [self._row(r) for r in rows]

    def update(self, instance: EvaluationInstance) -> None:
        with self._c.lock:
            self._write(instance)
            self._c.commit()

    def delete(self, id: str) -> None:
        with self._c.lock:
            self._c.execute(
                f"DELETE FROM {self._t('evaluation_instances')} WHERE id=?", (id,)
            )
            self._c.commit()


class SQLiteModels(_SQLiteMetaBase, base.Models):
    def _create(self):
        self._c.execute(
            f"""CREATE TABLE IF NOT EXISTS {self._t('models')} (
                id TEXT PRIMARY KEY, models BLOB)"""
        )

    def insert(self, model: Model) -> None:
        with self._c.lock:
            self._c.execute(
                f"INSERT OR REPLACE INTO {self._t('models')} VALUES (?,?)",
                (model.id, model.models),
            )
            self._c.commit()

    def get(self, id: str) -> Optional[Model]:
        row = self._c.execute(
            f"SELECT id, models FROM {self._t('models')} WHERE id=?", (id,)
        ).fetchone()
        return Model(row[0], row[1]) if row else None

    def delete(self, id: str) -> None:
        with self._c.lock:
            self._c.execute(
                f"DELETE FROM {self._t('models')} WHERE id=?", (id,)
            )
            self._c.commit()
