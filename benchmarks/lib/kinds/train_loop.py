"""``train-loop``: whole `pio train` processes back to back over a seeded
event store, while the window is open; the last runs to its end."""

from __future__ import annotations

import os
import time

import numpy as np

from .. import compare, data, layers, reference
from ..cells import (
    Run, breakdown, reduce_trace, result_line, settle_disk, write_variant,
)
from ..children import (
    COMPLETED_LINE, DEVICE_LINE, CellFailed, cache_entries, child_env,
    json_lines, parse_train, pio, record_time, run_child, say, stage,
)


def store_events(run, u, i, r) -> None:
    """The events as the bulk insert reads them."""
    for column, values in zip("uir", (u, i, r)):
        np.save(os.path.join(run.work, f"bench_{column}.npy"), values)


def load_export(directory):
    """One exported model: factors and the raw id of each factor row."""
    return tuple(
        np.load(os.path.join(directory, f"{n}.npy")) for n in
        ("user_factors", "item_factors", "user_ids", "item_ids")
    )


def timed_train(run, name, variant, env, extra=(), timeout=300):
    """One `pio train` child and everything the harness reads from it.
    The harness's own clock is read as the two lines show in its log: the
    one that says it holds the chip, and the one that says the instance is
    completed."""
    seen = {"device": DEVICE_LINE, "model": COMPLETED_LINE}
    seconds, text, t0 = run_child(
        name, pio("train", "-v", variant, *extra), env, run.work, timeout,
        watch=seen,
    )
    got = parse_train(text, t0)
    got.update(wall_s=seconds, t_spawn=t0, text=text)
    log = got["log"]
    log["process_wall_s"] = seconds
    log["stage_s"] = got["phases"].get("train[0]:ALSAlgorithm")
    if None not in seen.values():
        log["start_to_device_s"] = seen["device"] - t0
        log["store_to_model_s"] = seen["model"] - seen["device"]
        log["persist_exit_s"] = t0 + seconds - seen["model"]
    return got


def run_cell(run: Run) -> dict:
    shape, t_setup = run.config["shape"], time.time()
    host = child_env(run.work, host_only=True)
    chip = child_env(run.work, chips=run.chips)
    u, i, r = data.synth_ratings(
        shape["n_users"], shape["n_items"], shape["n_events"],
        run.config["data"]["structure_seed"], run.seed,
    )
    store_events(run, u, i, r)
    say(phase="make_data", seconds=time.time() - t_setup, events=len(r))
    run_child("app_new", pio("app", "new", "bench"), host, run.work, 120)
    seconds, text, _ = run_child(
        "load", stage("load", run.work, "bench"), host, run.work, 600
    )
    say(phase="bulk_insert", seconds=seconds, said=json_lines(text)[-1:])
    variant = write_variant(run)

    # one untimed train fills the compile cache; the first run in a
    # checkout compiles here
    warm = timed_train(run, "warmup_train", variant, chip, timeout=1100)
    device = warm["device"]
    peaks = run.peaks(device)
    say(phase="warmup_train", seconds=warm["wall_s"], device=device,
        instance=warm["instance_id"], cache_misses=len(warm["cache_misses"]))
    if warm["errors"] or not warm["instance_id"]:
        raise CellFailed(f"the warm-up train failed: {warm['errors'][:3]}")
    settle_disk()
    setup_s = time.time() - t_setup
    entries_before = cache_entries()

    # the window: whole `pio train` processes, back to back
    trains, failed, t_open = [], 0, time.time()
    trace_dir = os.path.join(run.work, "profile")
    while time.time() - t_open < run.seconds:
        traced = run.trace and not trains
        try:
            got = timed_train(
                run, f"train_{len(trains)}", variant, chip,
                ["--profile-dir", trace_dir] if traced else [],
            )
        except CellFailed:
            failed += 1
            break
        got["traced"] = traced
        trains.append(got)
        say(phase=f"train_{len(trains) - 1}", seconds=got["wall_s"],
            phases_s=got["phases"], log=got["log"],
            cache_misses=got["cache_misses"], memory=got["memory"])
    window_s = time.time() - t_open
    compiled = cache_entries() - entries_before
    present_u, du = data.dense_codes(u, shape["n_users"])
    present_i, di = data.dense_codes(i, shape["n_items"])
    attempted = len(trains) + failed
    metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
    if trains:  # all the window's time over its trains
        metrics["train_wall_s"] = {
            "value": window_s / len(trains), "unit": "s",
        }

    # memory: the allocator's counter as each timed train logs it. On this
    # runtime it leaves a running program's temporaries out (PERF.md)
    counter_peak = max(
        [v for t in trains for k, v in (t["memory"] or {}).items()
         if k.endswith("peak_bytes_in_use")] or [0]
    )
    device_out = dict(device or {}, memory_peak_bytes=int(counter_peak))

    # the trace of the first timed train, reduced once the chip is free
    extra_out = {}
    if run.trace and trains:
        first = trains[0]
        reduced = reduce_trace(run, trace_dir)
        marks = first["marks"]
        t_trace = marks.get("trace_start", first["t_spawn"])
        window = marks.get("phases_logged", t_trace) - t_trace
        # the inner stage as the trains without the profiler ran it
        # (where the window held the traced train alone, that one)
        inner = [(t["traced"], t["log"]["store_to_model_s"]) for t in trains
                 if "store_to_model_s" in t["log"]]
        plain = [s for traced, s in inner if not traced] or [s for _, s in inner]
        ctx = {
            "phases": first["phases"],
            "log": dict(first["log"], store_to_model_s=(
                float(np.mean(plain)) if plain else None)),
            "trace": reduced,
            "trace_window_s": window, "shape": shape, "peaks": peaks,
            "seen": {"events": len(r), "users": len(present_u),
                     "items": len(present_i)},
        }
        metrics.update(layers.evaluate(ctx, run.layer_defs))
        dev = reduced.get("device")
        if dev:
            device_out.update(busy_s=dev["busy_s"], window_s=window,
                              busy_by_plane=dev["busy_by_plane"])
            said = sorted(
                (record_time(rec), rec.get("message", "").split("\n")[0])
                for rec in json_lines(first["text"]) if rec.get("ts")
            )

            def last_said(at):
                before = [msg for t, msg in said if t <= at]
                return "after: " + (before[-1] if before else "spawn")

            extra_out["breakdown"] = breakdown(reduced, t_trace, last_said)
        say(phase="trace", layout=reduced.get("layout"),
            file_bytes=reduced.get("file_bytes"), error=reduced.get("error"))

    # the comparison: every model a timed train persisted, against the
    # float64 reference from the same data and the same initial state
    numbers = compare.Numbers(run.config["limits"])
    numbers.add("compiled_in_window", compiled)
    numbers.add("trains_failed", failed + sum(
        1 for t in trains if t["errors"] or not t["instance_id"]
        or "store_to_model_s" not in t["log"]
    ))
    ids = [t["instance_id"] for t in trains if t["instance_id"]]
    if ids:
        t_ref = time.time()
        algo = run.config["engine"]["algorithms"][0]["params"]
        run_child("export", stage("export", run.work, *ids), host,
                  run.work, 300)
        X_ref, Y_ref = reference.als_reference(
            du, di, r, len(present_u), len(present_i), rank=algo["rank"],
            iterations=algo["num_iterations"], reg=algo["lambda_"],
            seed=run.seed,
        )
        for instance_id in ids:
            X, Y, rows_u, rows_i = load_export(
                os.path.join(run.work, "export", instance_id)
            )
            compare.train_numbers(
                numbers, X, Y, rows_u, rows_i, X_ref, Y_ref, present_u,
                present_i,
            )
        say(phase="reference", seconds=time.time() - t_ref, models=len(ids))
    extra_out["trains"] = [t["log"] for t in trains]
    return result_line(
        run, numbers=numbers.out, attempted=attempted,
        failed=failed + numbers.wrong, metrics=metrics, device=device_out,
        extra=extra_out,
    )
