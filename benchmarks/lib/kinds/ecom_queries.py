"""``ecom-queries``: the E-Commerce Recommendation template on the normal
path. A seeded ``ECommModel`` instance deployed with `pio deploy`, the
event store beside it (histories bulk-imported, the ``unavailableItems``
constraint set), `pio eventserver` for the window's writes, and an open
loop of filtered `POST /queries.json` from a generator process of its own
(``lib/ecom.py``). Every answer is held to the query's filters and to the
configuration's two guarantees; a sample is held to the float64 reference
(``lib/reference_ecom.py``) under the masks it was served with."""

from __future__ import annotations

import concurrent.futures
import io
import json
import os
import subprocess
import sys
import threading
import time
import zipfile

import numpy as np

from .. import compare, data, ecom, layers, loadgen, reference_ecom
from ..cells import (
    Run, batches_seen, breakdown, reduce_trace, result_line, settle_disk,
    write_variant,
)
from ..children import (
    BENCH, CHILDREN, UPGRADE_CHECK_LINE, CellFailed, Deployed, child_env,
    device_memory, device_of, free_port, http_json, json_lines, last_line,
    pio, run_child, say, stop_child,
)
from .open_loop_queries import ACCESS_KEY, _capture, _tick


def ecom_stage(name, *args):
    return [sys.executable, os.path.join(BENCH, "lib", "ecom.py"), name,
            *[str(a) for a in args]]


class EventServer:
    """One `pio eventserver` child."""

    def __init__(self, work, env):
        self.port = free_port()
        self.log_path = os.path.join(work, "eventserver.log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            pio("eventserver", "--ip", "127.0.0.1", "--port", str(self.port)),
            env=env, cwd=work, stdout=self._log, stderr=subprocess.STDOUT,
        )
        CHILDREN.append(self.proc)

    def log_text(self):
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def wait_ready(self, timeout):
        deadline = time.time() + timeout
        while time.time() < deadline:
            if self.proc.poll() is not None:
                raise CellFailed(
                    f"eventserver: exited: {last_line(self.log_text())}")
            try:
                http_json(f"http://127.0.0.1:{self.port}/", timeout=5)
                return
            except Exception:  # a boundary: not listening yet
                time.sleep(0.25)
        raise CellFailed(
            f"eventserver: never became ready: {last_line(self.log_text())}")

    def stop(self):
        stop_child(self.proc)
        self._log.close()


def start_servers(run: Run):
    """Set-up: the instance written and the store loaded side by side,
    `pio deploy` ready and holding the chip (its warm ladder compiled),
    `pio eventserver` up, one query of each shape, a few seconds of the
    cell's own traffic. Returns what the window and the comparison need."""
    t = run.config["timeouts"]
    host = child_env(run.work, host_only=True)
    variant = write_variant(run)
    paths = {"config": os.path.join(run.work, "config.json"),
             "unavailable": os.path.join(run.work, "unavailable.npy")}
    with open(paths["config"], "w") as f:
        json.dump(run.config, f)
    run_child("app_new", pio("app", "new", "bench"), host, run.work, 120)
    with concurrent.futures.ThreadPoolExecutor(2) as pool:
        write = pool.submit(
            run_child, "write_instance",
            ecom_stage("write_instance", run.work, variant, paths["config"],
                       run.seed),
            host, run.work, t["write_instance_s"])
        load = pool.submit(
            run_child, "load",
            ecom_stage("load", paths["config"], run.seed, paths["unavailable"]),
            host, run.work, t["load_s"])
        seconds_w, text_w, _ = write.result()
        seconds_l, text_l, _ = load.result()
    written, loaded = json_lines(text_w)[-1], json_lines(text_l)[-1]
    say(phase="write_instance", seconds=seconds_w, **written)
    say(phase="load", seconds=seconds_l,
        **{k: v for k, v in loaded.items() if k != "access_key"})
    events = EventServer(run.work, host)
    server = Deployed(
        "deploy", run.work, variant, written["instance_id"],
        child_env(run.work, chips=run.chips),
        extra=("--accesskey", ACCESS_KEY),
    )
    try:
        ready_s, status = server.wait_ready(timeout=t["deploy_s"])
        events.wait_ready(timeout=120)
        device, _ = device_of(server.log_text())
        peaks = run.peaks(device)
        say(phase="deploy", ready_seconds=ready_s, device=device,
            ledger=status.get("deviceLedger", {}).get("breakdown"),
            rss_bytes=rss_of(server.proc.pid))
        ctx = {"paths": paths, "access_key": loaded["access_key"],
               "events": events, "setup": {
                   "write_instance_s": seconds_w, "load_s": seconds_l,
                   "load_parts": {k: loaded[k] for k in
                                  ("make_s", "load_s", "index_s")},
                   "deploy_ready_s": ready_s}}
        sched = ecom.make_schedule(run.traffic, run.config, 4.0, run.seed)
        for k in range(len(sched["due"])):  # one query of each shape
            if sched["shapes"][k] in (ecom.PLAIN, ecom.CATEGORY, ecom.WHITE_LIST):
                http_json(server.url + "/queries.json",
                          ecom.body_of(sched, k), timeout=t["first_query_s"])
        t_warm = time.time()
        warm = offer(run, server, ctx, run.traffic,
                     run.traffic["warmup_seconds"])
        ctx["warm_views"] = warm_views(run, warm)
        while UPGRADE_CHECK_LINE not in server.log_text():
            if time.time() - t_warm > run.traffic["settle_timeout_s"]:
                raise CellFailed(
                    f"the server never logged {UPGRADE_CHECK_LINE!r}")
            time.sleep(0.25)
        ctx["setup"]["warmup_traffic_s"] = time.time() - t_warm
        say(phase="warmup_traffic", seconds=time.time() - t_warm)
    except BaseException:
        server.stop()
        events.stop()
        raise
    return server, ctx, device, peaks


def rss_of(pid):
    """Resident bytes of a child, from /proc (None where there is none)."""
    try:
        with open(f"/proc/{pid}/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return None


def offer(run: Run, server, ctx, traffic, seconds, box=None):
    """One window of ``traffic`` against the two servers, offered by the
    generator's own process, a /metrics scrape on either side; with
    ``box`` a profiler capture runs beside it."""
    settle_disk()
    n = sum(1 for name in os.listdir(run.work) if name.startswith("offer_"))
    spec_path = os.path.join(run.work, f"offer_{n}.spec.json")
    out_path = os.path.join(run.work, f"offer_{n}.out.json")
    with open(spec_path, "w") as f:
        json.dump({
            "host": "127.0.0.1", "port": server.port,
            "event_port": ctx["events"].port, "access_key": ctx["access_key"],
            "traffic": traffic, "seconds": seconds, "seed": run.seed,
            "config_path": ctx["paths"]["config"],
            "unavailable_path": ctx["paths"]["unavailable"],
        }, f)
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
            f"offer_{n}", ecom_stage("offer", spec_path, out_path),
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


def warm_views(run, got):
    """The view events the warm-up window wrote, by user code, as events
    long acknowledged: {code: [(posted, acked, item id)]}."""
    sched = ecom.make_schedule(run.traffic, run.config, got["seconds"], run.seed)
    out = {}
    for k, how in got["sent_as"].items():
        if how.get("viewed") and how.get("status") == 201:
            out.setdefault(int(sched["users"][int(k)]), []).append(
                (-np.inf, -np.inf, reference_ecom.item_id(how["viewed"])))
    return out


def run_cell(run: Run) -> dict:
    shape, t_setup = run.config["shape"], time.time()
    server, ctx, device, peaks = start_servers(run)
    try:
        box = {} if run.trace else None
        got = offer(run, server, ctx, run.traffic, run.seconds, box)
        setup_s = got["t_ready"] - t_setup
        scrape_before, scrape_after = got["scrapes"]
        memory = device_memory(scrape_after)
        server_rss = rss_of(server.proc.pid)
    finally:
        server.stop()  # the chip is free and the server's state gone
        ctx["events"].stop()
    sched = ecom.make_schedule(run.traffic, run.config, run.seconds, run.seed)
    due, sent, answered, out = sched["due"], got["sent"], got["answered"], got["out"]
    latency_ms = (answered - due) * 1e3
    last = float(np.nanmax(answered))
    metrics = {
        "setup_s": {"value": setup_s, "unit": "s"},
        "query_p50_ms": {"value": loadgen.percentile(latency_ms, 50), "unit": "ms"},
    }
    device_out = dict(device or {}, **memory, server_rss_bytes=server_rss)
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
        "empty_answers": layers.read(
            both, "prom:pio_ecom_empty_answers_total:delta"),
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
    on_host = layers.read(both, "prom:pio_ecom_host_fallback_total:delta")
    numbers, checked, compared = verify(
        run, sched, got, ctx.get("warm_views"), cold or 0.0,
        float("inf") if on_host is None else on_host)
    n_ok = checked["well_formed"] - numbers.wrong
    metrics["queries_per_s"] = {
        "value": n_ok / max(run.seconds, last), "unit": "queries/s"}
    extra_out["checked"] = dict(checked, compared=compared)
    say(phase="reference", seconds=time.time() - t_ref, compared=compared)
    return result_line(
        run, numbers=numbers.out, attempted=len(out),
        failed=len(out) - n_ok, metrics=metrics, device=device_out,
        extra=extra_out)


def verify(run, sched, got, warm_views, cold_compiles, host_fallbacks):
    """The comparison that decides ``correct``: (Numbers, the counts of
    what was checked, how many answers the reference was asked about).
    ``host_fallbacks`` counts the window's queries answered off the
    device (over the warm ladder's top): the cell sends none, so one is
    a regression that moved its queries to the CPU. The engine renders
    the family from deploy on: a scrape without it does not hold."""
    shape = run.config["shape"]
    numbers = compare.Numbers(run.config["limits"])
    numbers.add("cold_compiles_in_window", cold_compiles)
    numbers.add("host_fallbacks", host_fallbacks)
    checked = check_answers(run, sched, got, warm_views or {})
    for name in ("answers_missing_or_malformed", "filter_violations",
                 "seen_after_write_violations", "stale_constraint_answers"):
        numbers.add(name, checked[name])
    numbers.wrong += len(checked["wrong"])
    Y = data.seeded_factors(shape["n_items"], shape["rank"], run.seed, 1)
    queries = sample_queries(run, sched, checked, Y)
    if queries:
        ref = reference_ecom.reference_topn(
            queries, Y, checked["cats"], checked["unavailable"])
        reference_ecom.serve_numbers(numbers, queries, ref)
    counts = {k: checked[k] for k in (
        "well_formed", "returns_sent_plain", "views_not_acknowledged",
        "constraint_added", "by_shape")}
    return numbers, counts, len(queries)


def check_answers(run, sched, got, warm_views):
    """Every answer of the window against the query's own filters and the
    configuration's two guarantees; the per-answer facts the sample needs
    are kept (``facts[k]``)."""
    config, shape = run.config, run.config["shape"]
    ttl = config["engine"]["algorithms"][0]["params"]["constraint_ttl_s"]
    history = ecom.History(config, run.seed)
    cats = ecom.item_categories(shape, config)
    gone0 = np.zeros(shape["n_items"], bool)
    gone0[ecom.unavailable_items(shape, config, run.seed)] = True
    gone1, con = gone0.copy(), got.get("constraint")
    added = np.zeros(0, np.int64)
    set_posted = set_acked = np.inf
    if con and con.get("status") == 201:
        added = np.array([reference_ecom.item_id(i) for i in con["added"]],
                         np.int64)
        added = added[~gone0[added]]
        gone1[added] = True
        set_posted, set_acked = con["posted"], con["acked"]
    # the views the window (and the warm-up before it) wrote, by user code
    views = {}
    for k, how in (warm_views or {}).items():
        views.setdefault(k, []).extend(how)
    for k, how in got["sent_as"].items():
        if how.get("viewed") and how.get("status") == 201:
            views.setdefault(int(sched["users"][int(k)]), []).append(
                (how["posted"], how["acked"],
                 reference_ecom.item_id(how["viewed"])))
    counts = dict.fromkeys(
        ("answers_missing_or_malformed", "filter_violations",
         "seen_after_write_violations", "stale_constraint_answers",
         "returns_sent_plain", "views_not_acknowledged"), 0)
    facts, wrong, by_shape = {}, set(), {}
    for k, (s_k, a_k, status, body) in enumerate(got["out"]):
        answer = (reference_ecom.parse_answer(body, int(sched["nums"][k]))
                  if status == 200 else None)
        if answer is None:
            counts["answers_missing_or_malformed"] += 1
            continue
        items, scores = answer
        code, shape_k = int(sched["users"][k]), int(sched["shapes"][k])
        how = got["sent_as"].get(str(k), {})
        definite = [i for p, a, i in views.get(code, ()) if a < s_k]
        unsure = [i for p, a, i in views.get(code, ()) if p < a_k and a >= s_k]
        seen = np.union1d(history.seen(code), np.asarray(definite, np.int64))
        black = np.zeros(0, np.int64)
        bad = False
        if shape_k == ecom.BLACK_RETURN:
            if how.get("black"):
                black = np.array([reference_ecom.item_id(i)
                                  for i in how["black"]], np.int64)
            else:
                counts["returns_sent_plain"] += 1
        if shape_k == ecom.VIEW_RETURN:
            if how.get("viewed") is None:
                counts["returns_sent_plain"] += 1
            elif how.get("status") != 201:
                counts["views_not_acknowledged"] += 1
            elif reference_ecom.item_id(how["viewed"]) in items:
                counts["seen_after_write_violations"] += 1
                bad = True
        white = sched["white"].get(k) if shape_k == ecom.WHITE_LIST else None
        category = int(sched["category"][k]) if shape_k == ecom.CATEGORY else None
        bad |= bool(
            gone0[items].any() or np.isin(items, seen).any()
            or np.isin(items, black).any() or (scores <= 0).any()
            or (white is not None and not np.isin(items, white).all())
            or (category is not None and (cats[items] != category).any()))
        if bad:
            counts["filter_violations"] += 1
        late = s_k > set_acked + ttl
        if late and np.isin(items, added).any():
            counts["stale_constraint_answers"] += 1
            bad = True
        if bad:
            wrong.add(k)
        by_shape[ecom.SHAPES[shape_k]] = by_shape.get(ecom.SHAPES[shape_k], 0) + 1
        facts[k] = {
            "items": items, "scores": scores, "white": white,
            "category": category, "version": int(late),
            "exclude": np.union1d(seen, black),
            "recent": [i for _, _, i in sorted(
                ((a, p, i) for p, a, i in views.get(code, ()) if a < s_k),
                reverse=True)] + [i for _, i in sorted(
                    history.views(code), reverse=True)],
            # neither mask nor seen set is certain: a view in flight, or
            # a query sent between the $set's post and its TTL
            "unsure": bool(unsure) or (set_posted <= s_k <= set_acked + ttl),
        }
    return dict(counts, well_formed=len(facts), wrong=wrong, facts=facts,
                cats=cats, unavailable=[gone0, gone1], by_shape=by_shape,
                constraint_added=int(len(added)))


def sample_queries(run, sched, checked, Y):
    """The answers the reference is asked about: every return that was
    served under masks that are certain, and of every shape enough to
    make ``verify.answers`` in all, drawn from the seed."""
    shape, facts = run.config["shape"], checked["facts"]
    sure = np.array([k for k, f in facts.items() if not f["unsure"]], np.int64)
    rng = np.random.default_rng([int(run.seed), 13])
    shapes = sched["shapes"][sure]
    pick = set(sure[(shapes == ecom.BLACK_RETURN)
                    | (shapes == ecom.VIEW_RETURN)].tolist())
    want = run.config["verify"]["answers"]
    for s in (ecom.PLAIN, ecom.CATEGORY, ecom.WHITE_LIST):
        mine = sure[shapes == s]
        take = min(len(mine), run.config["verify"]["per_shape"])
        pick.update(rng.choice(mine, size=take, replace=False).tolist())
    rest = np.setdiff1d(sure, np.fromiter(pick, np.int64, len(pick)))
    more = max(0, min(len(rest), want - len(pick)))
    pick.update(rng.choice(rest, size=more, replace=False).tolist())
    pick = sorted(pick)
    n_held = sched["n_held"]
    known = [k for k in pick if sched["users"][k] < n_held]
    X = data.Rows(
        data.seeded_factors(shape["n_users"], shape["rank"], run.seed, 0),
        sched["users"][known])
    queries = []
    for k in pick:
        f, code = facts[k], int(sched["users"][k])
        if code < n_held:
            row = X[np.array([code])][0].astype(np.float64)
        else:
            row = reference_ecom.recent_vector(Y, f["recent"])
        queries.append({
            "row": row, "cosine": code >= n_held, "exclude": f["exclude"],
            "white": f["white"], "category": f["category"],
            "version": f["version"], "num": int(sched["nums"][k]),
            "served": f["items"], "served_scores": f["scores"], "k": k,
        })
    return queries
