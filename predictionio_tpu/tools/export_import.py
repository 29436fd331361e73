"""Event export/import as JSON lines or Parquet.

Capability parity with the reference export/import jobs
(tools/src/main/scala/io/prediction/tools/export/EventsToFile.scala:39-104
— PEvents.find -> json4s strings -> text file OR Parquet via SQLContext
:85-100; imprt/FileToEvents.scala:84-95 — textFile -> read[Event] ->
PEvents.write). JSON-lines writes one event per line in the API JSON
format, so exports round-trip through import and are compatible with
event-server payload shapes. Parquet writes a columnar file (one column
per event field, timestamps at full microsecond precision, properties as
a JSON-encoded string column) via pyarrow — gated: a clear error tells
the user to install pyarrow when the optional dependency is absent.
Import auto-detects the format from the file's magic bytes.
"""

from __future__ import annotations

import json
import logging
from typing import List, Optional

from predictionio_tpu.data.event import DataMap, Event, parse_iso8601
from predictionio_tpu.data.storage import Storage, get_storage
from predictionio_tpu.data.store import app_name_to_id

logger = logging.getLogger(__name__)

FORMATS = ("json", "parquet")


def _require_pyarrow():
    try:
        import pyarrow
        import pyarrow.parquet
    except ImportError as e:  # pragma: no cover - image has pyarrow
        raise RuntimeError(
            "the parquet format requires the optional pyarrow dependency "
            "(pip install pyarrow); use --format json instead"
        ) from e
    return pyarrow, pyarrow.parquet


def events_to_file(
    app_name: str,
    path: str,
    channel_name: Optional[str] = None,
    storage: Optional[Storage] = None,
    format: str = "json",
) -> int:
    """Export all events of an app (channel) to a JSON-lines or Parquet
    file (reference EventsToFile.scala:85-100 offers the same choice).
    Returns the number of events written."""
    if format not in FORMATS:
        raise ValueError(f"unknown export format {format!r}; pick {FORMATS}")
    storage = storage or get_storage()
    app_id, channel_id = app_name_to_id(app_name, channel_name, storage)
    le = storage.get_p_events()
    if format == "parquet" and hasattr(le, "iter_export_pages"):
        # split export: row-store events through the generic batch
        # writer, bulk pages AND compacted segments as vectorized column
        # batches (exporting 20M events must not build 20M Event objects
        # any more than importing them does). Segment groups carry the
        # ORIGINAL event ids + creation times, so the import side can
        # re-seal them as segments — the near-zero-copy exchange.
        import itertools

        column_groups = le.iter_export_pages(app_id, channel_id)
        if hasattr(le, "iter_export_segments"):
            column_groups = itertools.chain(
                column_groups, le.iter_export_segments(app_id, channel_id)
            )
        n = _write_parquet(
            path,
            le.iter_row_events(app_id, channel_id),
            page_columns=column_groups,
        )
        logger.info(
            "exported %d events of app %s to %s (parquet, columnar "
            "pages + segments)", n, app_name, path,
        )
        return n
    events_iter = le.find(app_id=app_id, channel_id=channel_id)
    if format == "parquet":
        n = _write_parquet(path, events_iter)
    else:
        n = 0
        with open(path, "w") as f:
            for event in events_iter:
                f.write(json.dumps(event.to_json()) + "\n")
                n += 1
    logger.info(
        "exported %d events of app %s to %s (%s)", n, app_name, path, format
    )
    return n


def file_to_events(
    app_name: str,
    path: str,
    channel_name: Optional[str] = None,
    storage: Optional[Storage] = None,
) -> int:
    """Import events from a JSON-lines or Parquet file (auto-detected by
    the Parquet magic bytes). Returns the number inserted."""
    storage = storage or get_storage()
    app_id, channel_id = app_name_to_id(app_name, channel_name, storage)
    with open(path, "rb") as f:
        is_parquet = f.read(4) == b"PAR1"
    if is_parquet:
        # qualify and import PER ROW GROUP: the split exporter writes
        # row events and each bulk page as separate groups, so a mixed
        # file's homogeneous page groups still take the bulk path while
        # only the heterogeneous groups fall back to per-event reads —
        # and peak memory is a couple of groups, not the file. Reads +
        # qualification run in a prefetch thread PIPELINED against the
        # inserts (sqlite releases the GIL during its C work), so the
        # re-import wall clock is ~max(read+qualify, insert) instead of
        # their sum — the remaining gap to a native bulk import.
        import queue
        import threading

        _, pq = _require_pyarrow()
        pf = pq.ParquetFile(path)
        total = bulk = 0
        le = storage.get_p_events()
        q: "queue.Queue" = queue.Queue(maxsize=2)
        stop = threading.Event()

        def produce():
            try:
                for g in range(pf.num_row_groups):
                    if stop.is_set():
                        return
                    table = pf.read_row_group(g)
                    try:
                        prepared = _columnar_import_qualify(table)
                    except Exception as e:
                        # best-effort over possibly-foreign files: any
                        # unexpected column type / cast error means
                        # "does not qualify" -> generic reader
                        logger.debug(
                            "columnar import path disqualified: %s", e
                        )
                        prepared = None
                    q.put(("group", table, prepared))
                q.put(("done", None, None))
            except BaseException as e:  # surfaced by the consumer loop
                q.put(("error", e, None))

        producer = threading.Thread(target=produce, daemon=True)
        producer.start()
        try:
            while True:
                kind, table, prepared = q.get()
                if kind == "done":
                    break
                if kind == "error":
                    raise table
                if prepared is not None and "event_ids" in prepared:
                    # segment-export group (real ids preserved): re-seal
                    # it directly as a segment when the backend has the
                    # tier AND none of the sampled ids already exist —
                    # re-importing into the source app must stay
                    # idempotent, which only the keyed generic path is
                    n = _import_segment_group(
                        le, app_id, channel_id, prepared
                    )
                    if n is None:
                        group_events = _events_from_table(table)
                        le.write(group_events, app_id, channel_id)
                        n = len(group_events)
                    else:
                        bulk += n
                elif prepared is not None:
                    # the WRITE stays outside the producer's qualify
                    # net: a failed/ambiguous bulk write must surface,
                    # not silently fall through to the generic reader
                    # and double-import whatever already landed
                    n = le.insert_columns_encoded(
                        app_id, channel_id, **prepared
                    )
                    bulk += n
                else:
                    group_events = _events_from_table(table)
                    le.write(group_events, app_id, channel_id)
                    n = len(group_events)
                total += n
        finally:
            # a failed insert must not strand the producer on the
            # bounded queue (leaking the thread, the open file, and
            # buffered tables): signal it, drain, and join
            stop.set()
            while True:
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
            producer.join(timeout=30)
        logger.info(
            "imported %d events into app %s (%d via the columnar bulk "
            "path)", total, app_name, bulk,
        )
        return total
    else:
        events = []
        with open(path) as f:
            for line_no, line in enumerate(f, 1):
                line = line.strip()
                if not line:
                    continue
                try:
                    events.append(Event.from_json(json.loads(line)))
                except Exception as e:
                    raise ValueError(
                        f"{path}:{line_no}: invalid event: {e}"
                    ) from e
    storage.get_p_events().write(events, app_id, channel_id)
    logger.info("imported %d events into app %s", len(events), app_name)
    return len(events)


def _import_segment_group(le, app_id, channel_id, prepared):
    """Land a real-id column group as a sealed segment (near-zero-copy
    import), or return None to route it through the generic reader:
    when the backend has no segment tier, or when any sampled id
    already exists here (idempotent re-import needs the keyed path —
    the segment tier is append-only)."""
    insert_segment = getattr(le, "insert_segment_encoded", None)
    if insert_segment is None:
        return None
    ids = prepared["event_ids"]
    probe = {str(ids[0]), str(ids[len(ids) // 2]), str(ids[-1])}
    try:
        for eid in probe:
            if le.get(eid, app_id, channel_id) is not None:
                return None
        return insert_segment(app_id, channel_id, **prepared)
    except Exception:
        logger.warning(
            "segment import path failed; falling back to the generic "
            "reader", exc_info=True,
        )
        return None


def _columnar_import_qualify(table):
    """Qualify a HOMOGENEOUS parquet row group for the bulk path: one
    event name, one entity/target type pair, no tags/prId, event ids
    absent or page-synthetic (real ids must be preserved, and only the
    generic reader's keyed inserts stay idempotent across re-imports),
    millisecond-representable event times, and every property bag
    exactly ``{"<prop>": <number>}`` with a shared key — the shape
    bulk-rating exports have (or the typed propKey/propValue sidecar the
    exporter writes). Qualified groups route through
    LEvents.insert_columns (binary event pages on sqlite; packed columns
    over the gateway wire) so a 20M-event import takes seconds, not the
    minutes of the one-Event-object-per-row path. Returns None when the
    group does not qualify — heterogeneous events, sub-millisecond
    timestamps (the page store keeps ms; the bulk path must not truncate
    what the generic reader round-trips), empty/varied property bags —
    and raises on surprising column types (the caller treats any raise
    as "does not qualify" too). Checks are vectorized pyarrow compute,
    so disqualifying a large mixed file is cheap."""
    import re as _re

    import numpy as np

    pa, _ = _require_pyarrow()
    import pyarrow.compute as pc

    n = table.num_rows
    if n == 0:
        return None
    cols = {name: table.column(name) for name in table.column_names}
    required = {
        "event", "entityType", "entityId", "targetEntityType",
        "targetEntityId", "properties", "eventTime",
    }
    if not required <= set(cols):
        return None

    def single_value(name):
        uniq = pc.unique(cols[name].combine_chunks())
        if len(uniq) != 1 or not uniq[0].is_valid:
            return None
        return uniq[0].as_py()

    event = single_value("event")
    entity_type = single_value("entityType")
    target_entity_type = single_value("targetEntityType")
    if not event or event.startswith("$") or not entity_type:
        return None
    if not target_entity_type:
        return None
    for name in ("entityId", "targetEntityId", "eventTime"):
        if pc.sum(pc.cast(pc.is_null(cols[name]), pa.int64())).as_py():
            return None
    # event ids: absent or page-synthetic ("pg-<page>-<idx>" —
    # source-local positional handles with no meaning in another store)
    # keep the plain bulk path. A group where EVERY row carries a real,
    # unique, bounded-width id is a SEGMENT export: qualify it with the
    # ids (and creation times below) preserved, so the import side can
    # re-seal it as a segment. Mixed/partial ids take the generic path,
    # which preserves them row by row and stays idempotent across
    # re-imports (INSERT OR REPLACE keyed on id).
    event_ids = None
    if "eventId" in cols:
        ids = cols["eventId"].combine_chunks()
        n_real = pc.sum(pc.cast(pc.is_valid(ids), pa.int64())).as_py() or 0
        if n_real:
            synthetic = pc.match_substring_regex(ids, "^pg-[0-9]+-[0-9]+$")
            ok = pc.sum(pc.cast(synthetic, pa.int64())).as_py() or 0
            if ok != n_real and not (ok == 0 and n_real == n):
                return None
            if ok == 0 and n_real == n:
                from predictionio_tpu.data.storage.segments import (
                    MAX_ID_BYTES,
                )

                ids_np = ids.to_numpy(zero_copy_only=False)
                if len(np.unique(ids_np)) != n or max(
                    len(str(i).encode("utf-8")) for i in ids_np
                ) > MAX_ID_BYTES:
                    return None
                event_ids = ids_np
    if "prId" in cols and pc.sum(
        pc.cast(pc.is_valid(cols["prId"]), pa.int64())
    ).as_py():
        return None
    if "tags" in cols:
        tags = cols["tags"].combine_chunks()
        if hasattr(tags, "values"):
            # O(1): a list column's flattened child holds every element
            # of every list — zero length means no event carries tags
            # (a per-row list_value_length scan cost 0.3 s per 1M rows)
            if len(tags.values):
                return None
        else:
            lens = pc.fill_null(pc.list_value_length(tags), 0)
            if pc.sum(lens).as_py():
                return None

    # typed sidecar columns first (written by this package's own page
    # exporter): the property key/value arrive as real columns, so the
    # regex re-parse of 20M JSON strings — the dominant re-import cost,
    # and JSON this very exporter rendered — is skipped. A file carrying
    # a fully-valid sidecar is opting into the documented bulk form; the
    # `properties` JSON stays in the file for generic readers.
    prop_key = values = None
    if "propKey" in cols and "propValue" in cols:
        key = single_value("propKey")
        pv = cols["propValue"].combine_chunks()
        props_col = cols["properties"].combine_chunks()
        if (
            key
            and not pc.sum(pc.cast(pc.is_null(pv), pa.int64())).as_py()
            # null bags would be rejected by the regex path; the sidecar
            # must not be laxer (same vectorized cost, ~0.01 s/M)
            and not pc.sum(
                pc.cast(pc.is_null(props_col), pa.int64())
            ).as_py()
        ):
            # Consistency probes against the authoritative properties
            # JSON: a file whose bags were edited after export (or an
            # inconsistent foreign writer) falls through to the
            # fully-validating regex path / generic reader instead of
            # silently importing divergent sidecar values.
            def bag_matches(j: int) -> bool:
                try:
                    parsed = json.loads(props_col[j].as_py())
                except (ValueError, TypeError):
                    return False
                if not (
                    isinstance(parsed, dict)
                    and set(parsed) == {key}
                    and isinstance(parsed[key], (int, float))
                    and not isinstance(parsed[key], bool)
                ):
                    return False
                p = np.float32(parsed[key])
                v = np.float32(pv[j].as_py())
                return bool(p == v) or bool(np.isnan(p) and np.isnan(v))

            def sidecar_sample_agrees(pv_np: "np.ndarray") -> bool:
                # Vectorized sample validation: regex-parse
                # a bounded strided SAMPLE of the properties JSON —
                # always including the rows holding the sidecar's min
                # and max, so the cheap aggregates (non-null count was
                # checked above; extrema here; elementwise equality
                # implies the sample sums agree) cannot diverge
                # unnoticed. A bag altered ONLY at unsampled interior
                # rows still slips through — full validation is exactly
                # the 20M-string reparse this path exists to skip — but
                # bulk edits and shifted/scaled value columns now fail
                # qualification at ~4k parses per row group.
                idx = np.linspace(
                    0, n - 1, num=min(n, 4096), dtype=np.int64
                )
                finite = np.isfinite(pv_np)
                if finite.any():
                    extremes = np.array(
                        [
                            int(np.nanargmin(np.where(finite, pv_np, np.nan))),
                            int(np.nanargmax(np.where(finite, pv_np, np.nan))),
                        ],
                        dtype=np.int64,
                    )
                    idx = np.concatenate([idx, extremes])
                idx = np.unique(idx)
                pattern = (
                    '^\\{"'
                    + _re.escape(key)
                    + '": (?P<v>-?[0-9]+(?:\\.[0-9]+)?'
                    + "(?:[eE][-+]?[0-9]+)?)\\}$"
                )
                sampled = props_col.take(pa.array(idx))
                extracted = pc.extract_regex(sampled, pattern)
                nulls = pc.is_null(extracted).to_numpy(
                    zero_copy_only=False
                )
                if nulls.any():
                    # the numeric regex can't express NaN/±Infinity
                    # (json.dumps renders the bare tokens); those few
                    # rows fall back to the exact json parse instead of
                    # disqualifying a legitimate export
                    if not all(
                        bag_matches(int(j)) for j in idx[nulls]
                    ):
                        return False
                parsed = np.asarray(
                    pc.fill_null(
                        pc.struct_field(extracted, "v"), "0"
                    ).to_numpy(zero_copy_only=False),
                    dtype="U32",
                ).astype(np.float32)
                sample = pv_np[idx]
                ok = (
                    (parsed == sample)
                    | (np.isnan(parsed) & np.isnan(sample))
                    | nulls  # already validated row-exactly above
                )
                return bool(ok.all())

            pv_np = pv.to_numpy(zero_copy_only=False).astype(np.float32)
            if all(
                bag_matches(j) for j in {0, n // 2, n - 1}
            ) and sidecar_sample_agrees(pv_np):
                prop_key = key
                values = pv_np

    if values is None:
        # property bags: all exactly {"<key>": <number>} sharing one key.
        # All-empty bags fall back too — the bulk form would have to
        # invent a value where the generic reader faithfully stores an
        # empty bag.
        props = cols["properties"].combine_chunks()
        first = next((v.as_py() for v in props if v.is_valid), None)
        if first is None:
            return None
        parsed = json.loads(first)
        if not (
            isinstance(parsed, dict)
            and len(parsed) == 1
            and isinstance(next(iter(parsed.values())), (int, float))
            and not isinstance(next(iter(parsed.values())), bool)
        ):
            return None
        prop_key = next(iter(parsed))
        if pc.sum(pc.cast(pc.is_null(props), pa.int64())).as_py():
            return None  # mixed empty/non-empty bags: fall back
        pattern = (
            '^\\{"'
            + _re.escape(prop_key)
            + '": (?P<v>-?[0-9]+(?:\\.[0-9]+)?(?:[eE][-+]?[0-9]+)?)\\}$'
        )
        extracted = pc.extract_regex(props, pattern)
        if pc.sum(pc.cast(pc.is_null(extracted), pa.int64())).as_py():
            return None  # some bag deviates: fall back
        values = np.asarray(
            pc.struct_field(extracted, "v").to_numpy(zero_copy_only=False),
            dtype="U32",
        ).astype(np.float32)

    times = cols["eventTime"].combine_chunks()
    if not pa.types.is_timestamp(times.type):
        return None
    # safe cast: sub-millisecond timestamps raise -> caught by the
    # wrapper -> generic path keeps their full precision
    times_ms = (
        pc.cast(times, pa.timestamp("ms", tz="UTC"))
        .cast(pa.int64())
        .to_numpy(zero_copy_only=False)
        .astype(np.int64)
    )
    # ids leave as (distinct names, int32 codes) via arrow's C++
    # dictionary_encode — materializing 20M Python id strings and
    # re-factorizing them in numpy (encode_strings) cost ~1/4 of the
    # whole re-import; the dictionary path hands insert_columns_encoded
    # exactly the form the page store wants
    def encode(name):
        enc = pc.dictionary_encode(cols[name].combine_chunks())
        return (
            enc.dictionary.to_numpy(zero_copy_only=False),
            enc.indices.to_numpy(zero_copy_only=False).astype(
                np.int32, copy=False
            ),
        )

    e_names, e_codes = encode("entityId")
    g_names, g_codes = encode("targetEntityId")
    prepared = dict(
        event=event,
        entity_type=entity_type,
        target_entity_type=target_entity_type,
        entity_names=e_names,
        entity_codes=e_codes,
        target_names=g_names,
        target_codes=g_codes,
        values=values,
        value_property=prop_key,
        event_times_ms=times_ms,
    )
    if event_ids is not None:
        # a real-id (segment) group must also round-trip its creation
        # times to re-seal losslessly; sub-ms creation times fall back
        # to the generic reader via the safe-cast raise
        ctimes = cols.get("creationTime")
        if ctimes is None:
            return None
        ctimes = ctimes.combine_chunks()
        if not pa.types.is_timestamp(ctimes.type):
            return None
        prepared["event_ids"] = event_ids
        prepared["creation_times_ms"] = (
            pc.cast(ctimes, pa.timestamp("ms", tz="UTC"))
            .cast(pa.int64())
            .to_numpy(zero_copy_only=False)
            .astype(np.int64)
        )
    return prepared


# --- parquet columnar layout ---

_PARQUET_STRING_COLS = (
    # (column name, Event attribute)
    ("eventId", "event_id"),
    ("event", "event"),
    ("entityType", "entity_type"),
    ("entityId", "entity_id"),
    ("targetEntityType", "target_entity_type"),
    ("targetEntityId", "target_entity_id"),
    ("prId", "pr_id"),
)


_PARQUET_BATCH_ROWS = 65_536


def _page_columns_to_table(pa, schema, ts, page: dict):
    """One bulk page -> one pyarrow table, all columns vectorized.

    Values render as %.9g (round-trips float32 exactly) inside the
    single-key JSON shape the columnar importer recognizes, so a page
    export re-imports through the bulk path byte-faithfully."""
    import numpy as np

    n = len(page["values"])
    const = lambda v: pa.array([v] * n, type=pa.string())  # noqa: E731
    values = page["values"]
    vals_str = np.char.mod("%.9g", values)
    bad = np.nonzero(~np.isfinite(values))[0]
    if bad.size:
        # the fixed-width U array is sized by the widest finite rendering;
        # "-Infinity" (9 chars) would silently truncate without widening
        if vals_str.dtype.itemsize < np.dtype("U9").itemsize:
            vals_str = vals_str.astype("U9")
        for j in bad:  # rare: render the tokens json.loads accepts
            v = float(values[j])
            vals_str[j] = (
                "NaN" if v != v else ("Infinity" if v > 0 else "-Infinity")
            )
    # the key goes through json.dumps so quotes/backslashes/control
    # chars escape correctly. Empty-prop rows (segment groups of
    # propertyless events) render an empty bag.
    if page["prop"]:
        props = np.char.add(
            np.char.add("{%s: " % json.dumps(page["prop"]), vals_str), "}"
        )
        props = props.tolist()
    else:
        props = [None] * n
    times = pa.array(page["times_ms"] * 1000, type=pa.int64()).cast(ts)
    ctimes = (
        pa.array(
            np.asarray(page["creation_times_ms"], np.int64) * 1000,
            type=pa.int64(),
        ).cast(ts)
        if page.get("creation_times_ms") is not None
        else times
    )
    cols = {
        "eventId": pa.array(page["event_ids"], type=pa.string()),
        "event": const(page["event"]),
        "entityType": const(page["entity_type"]),
        # pyarrow converts numpy str arrays directly (no per-element
        # Python round trip); np.str_ is a str subclass
        "entityId": pa.array(
            np.asarray(page["entity_ids"], object), type=pa.string()
        ),
        "targetEntityType": const(page["target_entity_type"]),
        "targetEntityId": pa.array(
            np.asarray(page["target_ids"], object), type=pa.string()
        ),
        "prId": pa.array([None] * n, type=pa.string()),
        "properties": pa.array(props, type=pa.string()),
        "tags": pa.array([[]] * n, type=pa.list_(pa.string())),
        "eventTime": times,
        "creationTime": ctimes,
        "propKey": const(page["prop"]) if page["prop"] else pa.array(
            [None] * n, type=pa.string()
        ),
        "propValue": pa.array(
            np.asarray(values, np.float64), type=pa.float64()
        ),
    }
    return pa.table(cols, schema=schema)


def _write_parquet(path: str, events, page_columns=None) -> int:
    """Streams row-group batches through a ParquetWriter — like the JSON
    path, peak memory is one batch, not the whole event history.
    ``page_columns`` (bulk pages as decoded numpy columns) append as
    vectorized tables after the row events."""
    import itertools

    pa, pq = _require_pyarrow()
    ts = pa.timestamp("us", tz="UTC")
    schema = pa.schema(
        [pa.field(name, pa.string()) for name, _ in _PARQUET_STRING_COLS]
        + [
            # properties keep their JSON shape in one string column: the
            # bag is schemaless across events, so flattening to columns
            # would make the file schema depend on the data (the reference
            # lets SQLContext infer a merged schema, EventsToFile.scala:
            # 93-97; a JSON column round-trips losslessly without that
            # inference machinery)
            pa.field("properties", pa.string()),
            pa.field("tags", pa.list_(pa.string())),
            # full microsecond precision — better than the API JSON's
            # millisecond rendering
            pa.field("eventTime", ts),
            pa.field("creationTime", ts),
            # typed sidecar for bulk-page groups: the single property's
            # key + value as real columns. The JSON `properties` column
            # stays authoritative for generic readers; the sidecar lets
            # re-import skip regex-parsing 20M JSON strings this very
            # exporter rendered (the round-4 import/export asymmetry).
            # Null on row-event groups.
            pa.field("propKey", pa.string()),
            pa.field("propValue", pa.float64()),
        ]
    )
    events = iter(events)
    n = 0
    with pq.ParquetWriter(path, schema) as writer:
        while True:
            batch = list(itertools.islice(events, _PARQUET_BATCH_ROWS))
            if not batch and n > 0:
                break
            cols = {
                name: pa.array(
                    [getattr(e, attr) for e in batch], type=pa.string()
                )
                for name, attr in _PARQUET_STRING_COLS
            }
            cols["properties"] = pa.array(
                [
                    json.dumps(e.properties.to_json())
                    if len(e.properties)
                    else None
                    for e in batch
                ],
                type=pa.string(),
            )
            cols["tags"] = pa.array(
                [list(e.tags) for e in batch], type=pa.list_(pa.string())
            )
            cols["eventTime"] = pa.array(
                [e.event_time for e in batch], type=ts
            )
            cols["creationTime"] = pa.array(
                [e.creation_time for e in batch], type=ts
            )
            cols["propKey"] = pa.array([None] * len(batch), type=pa.string())
            cols["propValue"] = pa.array(
                [None] * len(batch), type=pa.float64()
            )
            writer.write_table(pa.table(cols, schema=schema))
            n += len(batch)
            if len(batch) < _PARQUET_BATCH_ROWS:
                break
        if page_columns is not None:
            for page in page_columns:
                writer.write_table(
                    _page_columns_to_table(pa, schema, ts, page)
                )
                n += len(page["values"])
    return n


def _read_parquet(path: str) -> List[Event]:
    _, pq = _require_pyarrow()
    return _events_from_table(pq.read_table(path))


def _events_from_table(table) -> List[Event]:
    import datetime as _dt

    rows = table.to_pylist()
    events = []
    for row in rows:
        props = row.get("properties")
        kwargs = {
            attr: row.get(name) for name, attr in _PARQUET_STRING_COLS
        }
        for time_field in ("eventTime", "creationTime"):
            v = row.get(time_field)
            if isinstance(v, str):  # files written by other tools
                v = parse_iso8601(v)
            elif isinstance(v, _dt.datetime) and v.tzinfo is None:
                v = v.replace(tzinfo=_dt.timezone.utc)
            row[time_field] = v
        events.append(
            Event(
                properties=DataMap(json.loads(props) if props else None),
                event_time=row["eventTime"],
                tags=tuple(row.get("tags") or ()),
                creation_time=row["creationTime"],
                **kwargs,
            )
        )
    return events
