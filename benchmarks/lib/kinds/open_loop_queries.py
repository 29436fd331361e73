"""``open-loop-queries``: a seeded engine instance deployed with
`pio deploy`, and an open loop of `POST /queries.json` at a fixed rate
from a generator process of its own."""

from __future__ import annotations

import base64
import io
import json
import os
import threading
import time
import zipfile

import numpy as np

from .. import compare, data, layers, loadgen
from ..cells import (
    Run, batches_seen, breakdown, reduce_trace, result_line, settle_disk,
    write_variant,
)
from ..children import (
    UPGRADE_CHECK_LINE, CellFailed, Deployed, child_env, device_memory,
    device_of, http_json, json_lines, pio, run_child, say, stage,
)

ACCESS_KEY = "bench"  # gates POST /debug/profile on the deployed server
TICK_S = 0.01


def _capture(server, seconds, delay, box):
    """POST /debug/profile for ``seconds``, ``delay`` into the window.
    Runs beside the generator."""
    try:
        time.sleep(delay)
        box["t_start"] = time.time()
        payload = http_json(
            f"{server.url}/debug/profile?seconds={seconds}"
            f"&accessKey={ACCESS_KEY}", payload={}, timeout=seconds + 120,
        )
        box["archive"] = base64.b64decode(payload["archive_b64"])
        box["seconds"] = payload["seconds"]
    except Exception as e:  # a boundary: the run goes on, untraced
        box["error"] = repr(e)


def _tick(box):
    """The parent's own witness of a stall: sleeps ``TICK_S`` at a time
    while the generator's process runs and notes the worst overrun. A
    host that froze every process shows here as well as in the
    generator's loop; a generator or a server that stalled alone does
    not."""
    while not box["stop"]:
        t = time.time()
        time.sleep(TICK_S)
        over_ms = (time.time() - t - TICK_S) * 1e3
        box["worst"][int(t)] = max(box["worst"].get(int(t), 0.0), over_ms)


def start_server(run: Run):
    """Set-up of a serving cell: the seeded instance written, `pio deploy`
    ready and holding the chip, one query per warm tier, a few seconds of
    the cell's own traffic. Returns the server, the factor rows the
    reference will need, the device and its peaks."""
    shape = run.config["shape"]
    host = child_env(run.work, host_only=True)
    variant = write_variant(run)
    run_child("app_new", pio("app", "new", "bench"), host, run.work, 120)
    seconds, text, _ = run_child(
        "write_instance",
        stage("write_instance", run.work, variant, shape["n_users"],
              shape["n_items"], shape["rank"], run.seed),
        host, run.work, 600,
    )
    written = json_lines(text)[-1]
    say(phase="write_instance", seconds=seconds, **written)
    server = Deployed(
        "deploy", run.work, variant, written["instance_id"],
        child_env(run.work, chips=run.chips),
        extra=("--accesskey", ACCESS_KEY),
    )
    try:
        # the same factors, for the reference, while the server loads: the
        # item table and the rows of the users this window will ask about
        _, users, _ = loadgen.make_schedule(run.traffic, run.seconds, run.seed)
        X = data.Rows(
            data.seeded_factors(shape["n_users"], shape["rank"], run.seed, 0),
            users,
        )
        Y = data.seeded_factors(shape["n_items"], shape["rank"], run.seed, 1)
        ready_s, status = server.wait_ready(timeout=900)
        device, _ = device_of(server.log_text())
        peaks = run.peaks(device)
        say(phase="deploy", ready_seconds=ready_s, device=device,
            ledger=status.get("deviceLedger", {}).get("breakdown"))
        for num in run.traffic["num"]["values"]:  # one query per warm tier
            http_json(server.url + "/queries.json",
                      {"user": data.user_name(0), "num": int(num)})
        # and a few seconds of the cell's own traffic, untimed: the first
        # batches of each padded size stall (0.5 s at the 95th percentile of
        # a window's first half, PERF.md section 6), and that is set-up
        t_warm = time.time()
        offer(run, server, run.traffic, run.traffic["warmup_seconds"])
        # ... and until the server has said what it says once after its
        # start (the daily upgrade check fires 10 s after it listens and
        # stalled every first window for 0.5-2 s)
        while UPGRADE_CHECK_LINE not in server.log_text():
            if time.time() - t_warm > run.traffic["settle_timeout_s"]:
                raise CellFailed(
                    f"the server never logged {UPGRADE_CHECK_LINE!r}")
            time.sleep(0.25)
        say(phase="warmup_traffic", seconds=time.time() - t_warm)
    except BaseException:
        server.stop()
        raise
    return server, X, Y, device, peaks


def offer(run: Run, server, traffic, seconds, box=None):
    """One window of ``traffic`` against the server, offered by the
    generator's own process, a /metrics scrape on either side; with
    ``box`` a profiler capture runs beside it."""
    due, users, nums = loadgen.make_schedule(traffic, seconds, run.seed)
    settle_disk()
    n = sum(1 for name in os.listdir(run.work) if name.startswith("offer_"))
    spec_path = os.path.join(run.work, f"offer_{n}.spec.json")
    out_path = os.path.join(run.work, f"offer_{n}.out.json")
    with open(spec_path, "w") as f:
        json.dump({"host": "127.0.0.1", "port": server.port,
                   "traffic": traffic, "seconds": seconds,
                   "seed": run.seed}, f)
    scrape_before = server.metrics()
    t_ready = time.time()
    tracer = None
    if box is not None:
        tracer = threading.Thread(
            target=_capture, daemon=True,
            args=(server, traffic["trace_seconds"],
                  seconds * traffic["trace_at"], box),
        )
        tracer.start()
    tick = {"stop": False, "worst": {}}  # unix second -> worst overrun, ms
    ticker = threading.Thread(target=_tick, args=(tick,), daemon=True)
    ticker.start()
    try:
        run_child(
            f"offer_{n}", stage("offer", spec_path, out_path),
            child_env(run.work, host_only=True), run.work,
            timeout=seconds + traffic["answer_timeout_s"] + 60,
        )
    finally:
        tick["stop"] = True
        ticker.join()
    if tracer is not None:
        tracer.join(timeout=300)
    with open(out_path) as f:
        got = json.load(f)
    out = [[sent, answered, status, body.encode("latin-1")]
           for sent, answered, status, body in got["out"]]
    return {
        "due": due, "users": users, "nums": nums, "out": out,
        "t_open": got["t_open"], "t_ready": t_ready,
        "scrapes": (scrape_before, server.metrics()),
        "sent": np.array([np.nan if rec[0] is None else rec[0] for rec in out]),
        "answered": np.array([np.nan if rec[1] is None else rec[1] for rec in out]),
        "lag": got["lag"],
        # the parent's ticks once the window was open (before, it was
        # starting the generator)
        "parent_tick": max(
            [(ms, second - got["t_open"]) for second, ms in
             tick["worst"].items() if second >= got["t_open"]] or [(0.0, 0.0)]
        ),
    }


def run_cell(run: Run) -> dict:
    shape, t_setup = run.config["shape"], time.time()
    server, X, Y, device, peaks = start_server(run)
    try:
        box = {} if run.trace else None
        got = offer(run, server, run.traffic, run.seconds, box)
        setup_s = got["t_ready"] - t_setup
        scrape_before, scrape_after = got["scrapes"]
        memory = device_memory(scrape_after)
    finally:
        server.stop()  # the chip is free and the server's state gone
    due, users, nums, out, t_open, sent, answered = (
        got[k] for k in ("due", "users", "nums", "out", "t_open", "sent", "answered")
    )
    answers, shaped = compare.parse_answers(out, nums)
    latency_ms = (answered - due) * 1e3
    last = float(np.nanmax(answered))
    metrics = {
        "setup_s": {"value": setup_s, "unit": "s"},
        "query_p50_ms": {"value": loadgen.percentile(latency_ms, 50), "unit": "ms"},
    }
    device_out = dict(device or {}, **memory)

    # where in the window the worst waits fell, and who saw them: the
    # generator's own loop, the parent's ticker, the server's histogram.
    # A stall is the server's, the generator's or the whole host's, and
    # the next reader has only this line to tell by
    late_ms = (sent - due) * 1e3
    lag = np.asarray(got["lag"]["worst_ms_by_second"])
    worst = int(np.nanargmax(latency_ms))
    both = {"prom": (scrape_before, scrape_after)}
    extra_out = {"window": {
        "worst_ms": float(latency_ms[worst]), "worst_due_s": float(due[worst]),
        "p95_ms": loadgen.percentile(latency_ms, 95),
        "p99_ms": loadgen.percentile(latency_ms, 99),
        "late_p95_ms": loadgen.percentile(late_ms, 95),
        "late_max_ms": float(np.nanmax(late_ms)),
        "over_1s": int(np.sum(latency_ms > 1e3)),
        "loadgen_lag_max_ms": float(np.max(lag)),
        "loadgen_lag_max_at_s": int(np.argmax(lag)),
        "loadgen_lag_seconds_over_20ms": [
            [int(k), float(lag[k])] for k in np.flatnonzero(lag > 20.0)[:20]
        ],
        "parent_tick_max_ms": got["parent_tick"][0],
        "parent_tick_max_at_s": got["parent_tick"][1],
        "server_p99_s": layers.read(
            both, "prom:pio_serving_latency_seconds:quantile:0.99"),
        "server_p9999_s": layers.read(
            both, "prom:pio_serving_latency_seconds:quantile:0.9999"),
    }}
    if run.trace:
        # the generator's account covers the requests due before the
        # capture began: the profiler stalls the server it traces
        t_capture = box.get("t_start", np.inf) - t_open
        calm = due < t_capture
        ctx = {
            "prom": (scrape_before, scrape_after), "shape": shape,
            "peaks": peaks,
            "loadgen": {
                "late_p95_ms": loadgen.percentile((sent - due)[calm] * 1e3, 95),
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
            batches = batches_seen(reduced)
            fill = layers.read(ctx, "prom:pio_serving_batch_fill:mean")
            ctx.update(
                trace=reduced, trace_window_s=box["seconds"],
                seen={"batches": batches, "queries": batches * (fill or 0.0)},
            )
            dev = reduced.get("device")
            if dev:
                device_out.update(busy_s=dev["busy_s"], window_s=box["seconds"],
                                  busy_by_plane=dev["busy_by_plane"])

                def in_flight(at):
                    n = int(np.sum((t_open + sent <= at) & (at < t_open + answered)))
                    return (f"host prep or response ({n} in flight)" if n
                            else "waiting for a request")

                extra_out["breakdown"] = breakdown(reduced, box["t_start"], in_flight)
            say(phase="trace", layout=reduced.get("layout"),
                file_bytes=reduced.get("file_bytes"), seen=ctx["seen"])
        else:
            say(phase="trace", failed=True, error=box.get("error"))
        metrics.update(layers.evaluate(ctx, run.layer_defs))

    # the comparison: a sample of the window's answers drawn from the seed,
    # the longest among them, against float64 scores over the same factors
    t_ref = time.time()
    numbers = compare.Numbers(run.config["limits"])
    cold = layers.read(both, 'prom:pio_cold_compiles_total:delta')
    numbers.add("cold_compiles_in_window", cold or 0.0)
    numbers.add("answers_missing_or_malformed", int(np.sum(~shaped)))
    pick = compare.sample_answers(
        nums, shaped, run.config["verify"]["answers"], run.seed
    )
    compare.serve_numbers(
        numbers, answers, pick, users, nums, X, Y,
    )
    n_ok = int(np.sum(shaped)) - numbers.wrong
    metrics["queries_per_s"] = {
        "value": n_ok / max(run.seconds, last), "unit": "queries/s",
    }
    say(phase="reference", seconds=time.time() - t_ref, compared=len(pick))
    return result_line(
        run, numbers=numbers.out, attempted=len(out),
        failed=len(out) - n_ok, metrics=metrics, device=device_out,
        extra=extra_out,
    )
