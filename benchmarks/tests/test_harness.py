"""The harness end to end at the tiny size, for both kinds of cell; a CPU
run refused as a measurement; a new configuration, mix, cell and metric
added as files and entries only; the seeded instance read back; the
children's storage layout (a sqlite file a repository) and the reason a
failed child leaves on the ``no result:`` line."""

from __future__ import annotations

import json
import os
import sqlite3
import subprocess
import sys

import numpy as np
import pytest

import tiny
from lib import data

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def tmp(tmp_path_factory):
    return str(tmp_path_factory.mktemp("bench"))


def check_line(line, metric_names):
    assert list(line)[:5] == KEYS and list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(metric_names) <= set(line["metrics"])
    for m in line["metrics"].values():
        assert isinstance(m["value"], float) and m["unit"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    for n in line["compared"].values():
        assert n["value"] <= n["limit"]
    json.dumps(line)


def test_train_cell_end_to_end(tmp):
    line = tiny.tiny_run(tmp, tiny.TRAIN)
    check_line(line, ["train_wall_s", "setup_s"])
    assert not any("query" in k for k in line["metrics"])


def test_train_cell_traced_reports_its_layers(tmp):
    line = tiny.tiny_run(tmp, tiny.TRAIN, trace=True)
    # no device plane on the CPU: the trace metrics stay out, never 0
    check_line(line, ["train_start_to_device_s", "train_host_exposed_s",
                      "train_device_pack_s", "train_device_loop_s",
                      "train_persist_exit_s"])
    assert "als_loop_mfu" not in line["metrics"]
    assert "train_wall_s" not in line["metrics"]
    assert "train_store_to_model_s" in line["metrics"]


def test_serve_cell_end_to_end(tmp):
    line = tiny.tiny_run(tmp, tiny.SERVE)
    check_line(line, ["query_p50_ms", "queries_per_s", "setup_s"])
    assert "query_tail_p95_ms" not in line["metrics"]
    assert line["attempted"] == 80
    assert line["metrics"]["queries_per_s"]["value"] == pytest.approx(40, rel=0.1)


def test_serve_cell_traced_reports_its_layers(tmp):
    line = tiny.tiny_run(tmp, tiny.SERVE, trace=True)
    check_line(line, ["serve_queue_fill", "serve_server_p50_ms",
                      "loadgen_late_p95_ms", "loadgen_lag_max_ms",
                      "query_tail_p95_ms", "query_p99_ms"])
    assert "query_p50_ms" not in line["metrics"]
    assert "topn_roofline" not in line["metrics"]


def test_a_cpu_run_is_refused_as_a_measurement(tmp):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run(
        [sys.executable, os.path.join(tiny.BENCH, "run.py"), "--workload",
         tiny.SERVE, "--seed", "3", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert got.returncode != 0
    assert "refusing to time a CPU" in got.stderr
    assert not any(ln.startswith('{"correct"') for ln in got.stdout.splitlines())


def test_new_files_and_entries_are_enough(tmp):
    """A later PR adds a configuration, a mix, a cell and a per-layer metric
    over a source kind that exists, and edits no file that is there."""
    bench, manifest = tiny.make_bench(os.path.join(tmp, "added"))
    with open(os.path.join(bench, "configs", "reco-msd-d2048.json")) as f:
        config = json.load(f)
    config["shape"].update(n_items=500)
    with open(os.path.join(bench, "configs", "reco-small.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "traffic", "open-loop-steady.json")) as f:
        traffic = json.load(f)
    traffic.update(rate_per_s=25, num={"values": [8], "weights": [1.0]})
    with open(os.path.join(bench, "traffic", "open-loop-slow.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(bench, "layer_metrics", "serve_server_p90_ms.json"), "w") as f:
        json.dump({"read": "prom:pio_serving_latency_seconds:quantile:0.9",
                   "scale": 1000}, f)
    manifest["workloads"].append({
        "name": "reco-small.query-slow", "config": "reco-small",
        "traffic": "open-loop-slow", "chips": 1, "why": "a test",
    })
    manifest["per_layer"].append({
        "name": "serve_server_p90_ms", "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": "http to response",
        "moves": "query_p50_ms", "workloads": ["reco-small.query-slow"],
    })
    for m in manifest["end_to_end"]:
        if tiny.SERVE in m.get("workloads", []):
            m["workloads"].append("reco-small.query-slow")
    line = tiny.tiny_run(tmp, "reco-small.query-slow", trace=True,
                         bench=bench, manifest=manifest)
    assert line["correct"] and line["attempted"] == 50
    assert list(line["metrics"]) == ["serve_server_p90_ms"]


def test_seeded_instance_reads_back(tmp):
    """The writer's blob goes through ``loads_model`` and holds the seed's
    factors: what `pio deploy` will serve is what the reference scores."""
    from lib import children

    work = os.path.join(tmp, "roundtrip")
    os.makedirs(work)
    bench, manifest = tiny.make_bench(os.path.join(tmp, "rt"))
    run = tiny.harness.build_run(manifest, tiny.SERVE, 77, 1.0, False, work,
                                 bench=bench, require_tpu=False)
    variant = tiny.harness.cells.write_variant(run)
    env = children.child_env(work, host_only=True)
    children.run_child("app_new", children.pio("app", "new", "bench"), env, work, 120)
    _, text, _ = children.run_child(
        "write", children.stage("write_instance", work, variant, 50, 30, 8, 77),
        env, work, 120,
    )
    instance_id = children.json_lines(text)[-1]["instance_id"]
    children.run_child("export", children.stage("export", work, instance_id),
                       env, work, 120)
    d = os.path.join(work, "export", instance_id)
    assert np.array_equal(np.load(os.path.join(d, "user_factors.npy")),
                          data.seeded_factors(50, 8, 77, 0))
    assert np.array_equal(np.load(os.path.join(d, "item_factors.npy")),
                          data.seeded_factors(30, 8, 77, 1))
    assert np.array_equal(np.load(os.path.join(d, "item_ids.npy")), np.arange(30))


def test_children_keep_metadata_and_events_in_files_of_their_own(tmp_path):
    """Set-up children write side by side, and sqlite has one write lock
    a file: the two repositories that they write name different files,
    both inside the run's scratch directory, for every kind of child."""
    from lib import children

    work = str(tmp_path)
    for host_only in (True, False):
        env = children.child_env(work, host_only=host_only)
        meta = tiny.sqlite_path(env, "METADATA")
        events = tiny.sqlite_path(env, "EVENTDATA")
        assert meta != events
        assert {os.path.dirname(meta), os.path.dirname(events)} == {work}
        assert env["PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE"] == "LOCALFS"
        assert not any("pio.db" in v for k, v in env.items()
                       if k.startswith("PIO_STORAGE_"))


@pytest.mark.parametrize("chips", [1, 4])
def test_a_rehearsals_children_see_the_cells_chips(tmp_path, monkeypatch,
                                                     chips):
    """Under ``JAX_PLATFORMS=cpu`` a child that would hold the chip gets
    the cell's count of virtual devices; on the chip, and in a host-only
    child, no flag is set: every chip stays visible."""
    from lib import children

    monkeypatch.delenv("XLA_FLAGS", raising=False)
    work = str(tmp_path)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert children.child_env(work, chips=chips)["XLA_FLAGS"] == (
        f"--xla_force_host_platform_device_count={chips}")
    assert "XLA_FLAGS" not in children.child_env(
        work, host_only=True, chips=chips)
    monkeypatch.delenv("JAX_PLATFORMS")
    assert "XLA_FLAGS" not in children.child_env(work, chips=chips)


def test_a_metadata_write_does_not_wait_for_the_event_stores_lock(tmp_path):
    """The storage registry as the layout leans on it: two sources of type
    sqlite with different PATHs are independent files, and an insert into
    a METADATA DAO returns while another connection holds the write lock
    of the EVENTDATA file (longer than the client's busy timeout would
    have waited: it never waits)."""
    from lib import children
    from predictionio_tpu.data.storage import Storage
    from predictionio_tpu.data.storage.base import App

    env = children.child_env(str(tmp_path), host_only=True)
    config = {k: v for k, v in env.items() if k.startswith("PIO_STORAGE_")}
    storage = Storage(config)
    storage.get_l_events().init(1)
    meta = tiny.sqlite_path(env, "METADATA")
    events = tiny.sqlite_path(env, "EVENTDATA")
    holder = sqlite3.connect(events, timeout=0.1)
    holder.execute("BEGIN IMMEDIATE")
    try:
        apps = Storage(config).get_meta_data_apps()  # a client opened under the lock
        app_id = apps.insert(App(id=0, name="beside", description=None))
        assert apps.get_by_name("beside").id == app_id
        with pytest.raises(sqlite3.OperationalError, match="locked"):
            other = sqlite3.connect(events, timeout=0.1)
            other.execute("BEGIN IMMEDIATE")  # the lock is real
    finally:
        holder.rollback()
        holder.close()
    tables = "SELECT name FROM sqlite_master WHERE type='table'"
    in_meta = {r[0] for r in sqlite3.connect(meta).execute(tables)}
    in_events = {r[0] for r in sqlite3.connect(events).execute(tables)}
    assert any("apps" in t for t in in_meta)
    assert not any("apps" in t for t in in_events)
    assert any("pio_event" in t for t in in_events)
    assert not any("pio_event" in t for t in in_meta)


DYING = "raise ValueError('the reason, on one line')"


def test_a_child_that_dies_says_why(tmp_path):
    """``run_child``'s CellFailed ends in the traceback's last line, a
    timeout's in the last line the child wrote."""
    from lib import children

    work = str(tmp_path)
    env = children.child_env(work, host_only=True)
    with pytest.raises(children.CellFailed) as e:
        children.run_child("dying", [sys.executable, "-c", DYING], env, work, 60)
    assert str(e.value) == (
        "dying: exit 1: ValueError: the reason, on one line")
    slow = "import time; print('still loading', flush=True); time.sleep(60)"
    with pytest.raises(children.CellFailed) as e:
        children.run_child("slow", [sys.executable, "-c", slow], env, work, 1.0)
    assert str(e.value) == "slow: exit timeout: still loading"
    assert children.last_line("") == "(no output)"
    assert children.last_line(
        'x\n{"level": "ERROR", "message": "first\\nthe cause"}\n\n'
    ) == "the cause"


def test_a_failed_stage_is_on_the_no_result_line(tmp_path, monkeypatch, capsys):
    """The harness's one line on standard error names the stage, its exit
    code and its reason; no result line is printed and the code is 2."""
    harness = tiny.harness

    def dies(run):
        harness.children.run_child(
            "write_instance", [sys.executable, "-c", DYING],
            harness.children.child_env(run.work, host_only=True), run.work, 60)

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(harness, "run_cell", dies)
    monkeypatch.setattr(harness, "keep_logs", lambda work, args: None)
    rc = harness.main(["--workload", tiny.SERVE, "--seed", "3",
                       "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc == 2
    assert err.splitlines()[-1] == (
        "no result: write_instance: exit 1: "
        "ValueError: the reason, on one line")
    assert not any(ln.startswith('{"correct"') for ln in out.splitlines())


def test_a_server_that_exits_says_why(tmp_path):
    """``Deployed.wait_ready`` gives the last line of the server's log."""
    from lib import children

    work = str(tmp_path)
    server = children.Deployed(
        "deploy", work, os.path.join(work, "no-such-engine.json"),
        "no-such-instance", children.child_env(work, host_only=True))
    try:
        with pytest.raises(children.CellFailed) as e:
            server.wait_ready(timeout=120)
    finally:
        server.stop()
    text = str(e.value)
    assert text.startswith("deploy: exited: ") and len(text) > len(
        "deploy: exited: ") and "\n" not in text
    assert text.endswith(children.last_line(server.log_text()))
