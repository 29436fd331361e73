"""``similar-queries``: the Similar Product template on the normal path. A
seeded ``SPModel`` instance whose float32 table is a file (the model is a
``PersistentModel``: `pio deploy` maps it), deployed with `pio deploy` at
the configuration's residency precision, and an open loop of filtered
`POST /queries.json` from a generator process of its own
(``lib/similar.py``). No event is written and no store is read in the
window. Every answer is held to its query's filters; a sample is held to
the float64 reference (``lib/reference_similar.py``) over the mapped
table, after the server has stopped. The scrapes' reader, the capture,
the ticker, the trace's reduction and the result line are the other two
kinds', by import."""

from __future__ import annotations

import io
import json
import os
import sys
import threading
import time
import zipfile

import numpy as np

from .. import compare, counts, counts_quantized, layers, loadgen
from .. import reference_similar, similar
from ..cells import (
    Run, batches_seen, breakdown, reduce_trace, result_line, settle_disk,
    write_variant,
)
from ..children import (
    BENCH, UPGRADE_CHECK_LINE, CellFailed, Deployed, child_env,
    device_memory, device_of, http_json, json_lines, pio, run_child, say,
)
from .open_loop_queries import ACCESS_KEY, _capture, _tick

# the count of the two-stage program's work, kept with the benchmark and
# made known to ``layers.read`` here (``counts.py`` is not this PR's)
counts.COUNTS.setdefault(
    "topn_batches_quantized", counts_quantized.topn_batches_quantized)


def similar_stage(name, *args):
    return [sys.executable, os.path.join(BENCH, "lib", "similar.py"), name,
            *[str(a) for a in args]]


def memory_of(pid):
    """{VmRSS, RssAnon, RssFile} of a child in bytes (None where /proc
    has none): the mapped table's pages are ``RssFile``, the page
    cache's to keep or drop; what the server allocated is ``RssAnon``.
    From ``/proc/<pid>/statm`` (resident and shared pages: a kernel may
    leave the split out of ``status``)."""
    try:
        with open(f"/proc/{pid}/statm") as f:
            _, resident, shared = (int(v) for v in f.read().split()[:3])
    except (OSError, ValueError):
        return None
    page = os.sysconf("SC_PAGE_SIZE")
    return {"VmRSS": resident * page, "RssAnon": (resident - shared) * page,
            "RssFile": shared * page}


def start_server(run: Run):
    """Set-up: the instance written (the table drawn into its file), `pio
    deploy` ready and holding the chip (table mapped and quantized, the
    warm ladder compiled), one query of each shape, a few seconds of the
    cell's own traffic."""
    t = run.config["timeouts"]
    host = child_env(run.work, host_only=True)
    variant = write_variant(run)
    config_path = os.path.join(run.work, "config.json")
    with open(config_path, "w") as f:
        json.dump(run.config, f)
    run_child("app_new", pio("app", "new", "bench"), host, run.work, 120)
    seconds, text, _ = run_child(
        "write_instance",
        similar_stage("write_instance", run.work, variant, config_path,
                      run.seed),
        host, run.work, t["write_instance_s"])
    written = json_lines(text)[-1]
    say(phase="write_instance", seconds=seconds, **written)
    settle_disk()  # the table's pages, before the server maps them
    server = Deployed(
        "deploy", run.work, variant, written["instance_id"],
        child_env(run.work, chips=run.chips),
        extra=("--accesskey", ACCESS_KEY),
    )
    try:
        ready_s, status = server.wait_ready(timeout=t["deploy_s"])
        device, _ = device_of(server.log_text())
        peaks = run.peaks(device)
        say(phase="deploy", ready_seconds=ready_s, device=device,
            ledger=status.get("deviceLedger", {}).get("breakdown"),
            memory=memory_of(server.proc.pid))
        ctx = {"config_path": config_path,
               "factors_path": written["factors_path"],
               "setup": {"write_instance_s": seconds,
                         "write_parts": {k: written[k] for k in
                                         ("fill_s", "index_s", "save_s")},
                         "deploy_ready_s": ready_s}}
        sched = similar.make_schedule(run.traffic, run.config, 4.0, run.seed)
        asked = set()
        for k in range(len(sched["due"])):  # one query of each shape
            if sched["shapes"][k] not in asked:
                asked.add(sched["shapes"][k])
                http_json(server.url + "/queries.json",
                          similar.body_of(sched, k), timeout=t["first_query_s"])
        t_warm = time.time()
        offer(run, server, ctx, run.traffic, run.traffic["warmup_seconds"])
        while UPGRADE_CHECK_LINE not in server.log_text():
            if time.time() - t_warm > run.traffic["settle_timeout_s"]:
                raise CellFailed(
                    f"the server never logged {UPGRADE_CHECK_LINE!r}")
            time.sleep(0.25)
        ctx["setup"]["warmup_traffic_s"] = time.time() - t_warm
        say(phase="warmup_traffic", seconds=time.time() - t_warm)
    except BaseException:
        server.stop()
        raise
    return server, ctx, device, peaks


def offer(run: Run, server, ctx, traffic, seconds, box=None):
    """One window of ``traffic`` against the server, offered by the
    generator's own process, a /metrics scrape on either side; with
    ``box`` a profiler capture runs beside it."""
    settle_disk()
    n = sum(1 for name in os.listdir(run.work) if name.startswith("offer_"))
    spec_path = os.path.join(run.work, f"offer_{n}.spec.json")
    out_path = os.path.join(run.work, f"offer_{n}.out.json")
    with open(spec_path, "w") as f:
        json.dump({"host": "127.0.0.1", "port": server.port,
                   "traffic": traffic, "seconds": seconds, "seed": run.seed,
                   "config_path": ctx["config_path"]}, f)
    scrape_before = server.metrics()
    t_ready = time.time()
    tracer = None
    if box is not None:
        tracer = threading.Thread(
            target=_capture, daemon=True,
            args=(server, traffic["trace_seconds"],
                  seconds * traffic["trace_at"], box))
        tracer.start()
    tick = {"stop": False, "worst": {}}
    ticker = threading.Thread(target=_tick, args=(tick,), daemon=True)
    ticker.start()
    try:
        run_child(
            f"offer_{n}", similar_stage("offer", spec_path, out_path),
            child_env(run.work, host_only=True), run.work,
            timeout=seconds + traffic["answer_timeout_s"] + 60)
    finally:
        tick["stop"] = True
        ticker.join()
    if tracer is not None:
        tracer.join(timeout=300)
    with open(out_path) as f:
        got = json.load(f)
    out = [[sent, answered, status, body.encode("latin-1")]
           for sent, answered, status, body in got["out"]]
    got.update(
        out=out, t_ready=t_ready, seconds=seconds,
        scrapes=(scrape_before, server.metrics()),
        sent=np.array([np.nan if r[0] is None else r[0] for r in out]),
        answered=np.array([np.nan if r[1] is None else r[1] for r in out]),
        parent_tick=max(
            [(ms, second - got["t_open"]) for second, ms in
             tick["worst"].items() if second >= got["t_open"]] or [(0.0, 0.0)]),
    )
    return got


def run_cell(run: Run) -> dict:
    shape, t_setup = run.config["shape"], time.time()
    server, ctx, device, peaks = start_server(run)
    try:
        box = {} if run.trace else None
        got = offer(run, server, ctx, run.traffic, run.seconds, box)
        setup_s = got["t_ready"] - t_setup
        scrape_before, scrape_after = got["scrapes"]
        device_mem = device_memory(scrape_after)
        memory = memory_of(server.proc.pid) or {}
    finally:
        server.stop()  # the chip is free and the server's state gone
    sched = similar.make_schedule(run.traffic, run.config, run.seconds, run.seed)
    due, sent, answered, out = sched["due"], got["sent"], got["answered"], got["out"]
    latency_ms = (answered - due) * 1e3
    last = float(np.nanmax(answered))
    metrics = {
        "setup_s": {"value": setup_s, "unit": "s"},
        "query_p50_ms": {"value": loadgen.percentile(latency_ms, 50), "unit": "ms"},
    }
    device_out = dict(
        device or {}, **device_mem, server_rss_bytes=memory.get("VmRSS"),
        server_rss_anon_bytes=memory.get("RssAnon"),
        server_rss_file_bytes=memory.get("RssFile"),
    )
    late_ms = (sent - due) * 1e3
    lag = np.asarray(got["lag"]["worst_ms_by_second"])
    both = {"prom": (scrape_before, scrape_after)}
    extra_out = {"setup": ctx["setup"], "window": {
        "worst_ms": float(np.nanmax(latency_ms)),
        "p95_ms": loadgen.percentile(latency_ms, 95),
        "p99_ms": loadgen.percentile(latency_ms, 99),
        "late_p95_ms": loadgen.percentile(late_ms, 95),
        "over_1s": int(np.sum(latency_ms > 1e3)),
        "loadgen_lag_max_ms": float(np.max(lag)),
        "parent_tick_max_ms": got["parent_tick"][0],
        "batch_fill": layers.read(both, "prom:pio_serving_batch_fill:mean"),
        "refine_ms": 1e3 * (layers.read(
            both, "prom:pio_serving_batch_refine_seconds:mean") or 0.0),
    }}
    if run.trace:
        t_capture = box.get("t_start", np.inf) - got["t_open"]
        calm = due < t_capture
        tctx = {
            "prom": (scrape_before, scrape_after), "shape": shape,
            "peaks": peaks,
            "loadgen": {
                "late_p95_ms": loadgen.percentile(late_ms[calm], 95),
                "p99_ms": loadgen.percentile(latency_ms[calm], 99),
                "p95_ms": loadgen.percentile(latency_ms[calm], 95),
                "lag_max_ms": float(np.max(
                    lag[:max(1, int(min(t_capture, len(lag))))])),
            },
        }
        if "archive" in box:
            trace_dir = os.path.join(run.work, "capture")
            zipfile.ZipFile(io.BytesIO(box["archive"])).extractall(trace_dir)
            reduced = reduce_trace(run, trace_dir)
            dev = reduced.get("device")
            batches = batches_seen(reduced)
            fill = layers.read(tctx, "prom:pio_serving_batch_fill:mean")
            tctx.update(
                trace=reduced, trace_window_s=box["seconds"],
                seen={"batches": batches, "queries": batches * (fill or 0.0)})
            if dev:
                device_out.update(busy_s=dev["busy_s"], window_s=box["seconds"],
                                  busy_by_plane=dev["busy_by_plane"])

                def in_flight(at):
                    n = int(np.sum((got["t_open"] + sent <= at)
                                   & (at < got["t_open"] + answered)))
                    return (f"host prep or response ({n} in flight)" if n
                            else "waiting for a request")

                extra_out["breakdown"] = breakdown(
                    reduced, box["t_start"], in_flight)
            say(phase="trace", layout=reduced.get("layout"),
                file_bytes=reduced.get("file_bytes"), seen=tctx["seen"])
        else:
            say(phase="trace", failed=True, error=box.get("error"))
        metrics.update(layers.evaluate(tctx, run.layer_defs))

    t_ref = time.time()
    cold = layers.read(both, "prom:pio_cold_compiles_total:delta")
    on_host = layers.read(both, "prom:pio_similar_host_fallback_total:delta")
    numbers, checked, compared = verify(
        run, sched, got, np.load(ctx["factors_path"], mmap_mode="r"),
        cold or 0.0, float("inf") if on_host is None else on_host)
    n_ok = checked["well_formed"] - numbers.wrong
    metrics["queries_per_s"] = {
        "value": n_ok / max(run.seconds, last), "unit": "queries/s"}
    extra_out["checked"] = dict(checked, compared=compared)
    say(phase="reference", seconds=time.time() - t_ref, compared=compared)
    return result_line(
        run, numbers=numbers.out, attempted=len(out),
        failed=len(out) - n_ok, metrics=metrics, device=device_out,
        extra=extra_out)


def verify(run, sched, got, Y, cold_compiles, host_fallbacks):
    """The comparison that decides ``correct``: (Numbers, the counts of
    what was checked, how many answers the reference was asked about).
    ``Y`` is the float32 item table the set-up wrote (mapped).
    ``host_fallbacks`` counts the window's queries answered off the
    device: the cell sends none over the ladder's top, so one is a
    regression that moved its queries to the CPU. The engine renders the
    family from deploy on: a scrape without it does not hold."""
    numbers = compare.Numbers(run.config["limits"])
    numbers.add("cold_compiles_in_window", cold_compiles)
    numbers.add("host_fallbacks", host_fallbacks)
    cats = similar.item_categories(run.config["shape"], run.config)
    checked = check_answers(sched, got, cats)
    for name in ("answers_missing_or_malformed", "filter_violations"):
        numbers.add(name, checked[name])
    numbers.wrong += len(checked["wrong"])
    queries = sample_queries(run, sched, checked)
    hit = total = 0
    if queries:
        Q = reference_similar.query_vectors(lambda ids: Y[ids], queries)
        hit, total = reference_similar.serve_numbers(
            numbers, queries, reference_similar.reference_topn(
                queries, Q, reference_similar.file_blocks(Y), cats))
    counts_ = {k: checked[k] for k in ("well_formed", "by_shape")}
    counts_.update(reference_items=total, reference_items_served=hit)
    return numbers, counts_, len(queries)


def check_answers(sched, got, cats):
    """Every answer of the window against its own query: status 200, at
    most ``num`` distinct well-formed items, none of them a query item
    or blacklisted, all inside the whitelist and the category, every
    score positive. The facts the sample needs are kept."""
    counts_ = {"answers_missing_or_malformed": 0, "filter_violations": 0}
    facts, wrong, by_shape = {}, set(), {}
    for k, (_, _, status, body) in enumerate(got["out"]):
        answer = (reference_similar.parse_answer(body, int(sched["nums"][k]))
                  if status == 200 else None)
        if answer is None:
            counts_["answers_missing_or_malformed"] += 1
            continue
        items, scores = answer
        exclude = np.union1d(sched["items"][k],
                             sched["black"].get(k, np.zeros(0, np.int64)))
        white = sched["white"].get(k)
        category = int(sched["category"][k]) if sched["category"][k] >= 0 else None
        if (np.isin(items, exclude).any() or (scores <= 0).any()
                or (white is not None and not np.isin(items, white).all())
                or (category is not None and (cats[items] != category).any())):
            counts_["filter_violations"] += 1
            wrong.add(k)
        name = similar.SHAPES[int(sched["shapes"][k])]
        by_shape[name] = by_shape.get(name, 0) + 1
        facts[k] = {"items": sched["items"][k], "exclude": exclude,
                    "white": white, "category": category,
                    "num": int(sched["nums"][k]), "served": items,
                    "served_scores": scores, "k": k}
    return dict(counts_, well_formed=len(facts), wrong=wrong, facts=facts,
                by_shape=by_shape)


def sample_queries(run, sched, checked):
    """The answers the reference is asked about: ``verify.per_shape`` of
    every shape and the rest to ``verify.answers`` in all, drawn from
    the seed."""
    facts = checked["facts"]
    ok = np.fromiter(facts, np.int64, len(facts))
    rng = np.random.default_rng([int(run.seed), 13])
    shapes = sched["shapes"][ok]
    pick = set()
    for s in range(len(similar.SHAPES)):
        mine = ok[shapes == s]
        take = min(len(mine), run.config["verify"]["per_shape"])
        pick.update(rng.choice(mine, size=take, replace=False).tolist())
    rest = np.setdiff1d(ok, np.fromiter(pick, np.int64, len(pick)))
    more = max(0, min(len(rest), run.config["verify"]["answers"] - len(pick)))
    pick.update(rng.choice(rest, size=more, replace=False).tolist())
    return [facts[k] for k in sorted(pick)]
