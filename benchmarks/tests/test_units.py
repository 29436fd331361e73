"""The benchmark's own arithmetic, on the CPU with no chip: the counts
functions against hand-worked values, the generator's schedule, the
source vocabulary, the trace reduction on made-up planes, and the
references against the repo's float64 oracle."""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

import tiny  # noqa: F401  (puts benchmarks/ on the path)
from lib import compare, counts, data, layers, loadgen, reference, trace

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
ML20M = {"rank": 32, "iterations": 10}
SEEN = {"events": 20_000_000, "users": 138_493, "items": 26_744}


def test_als_loop_counts_by_hand():
    work = counts.als_loop(ML20M, SEEN)
    # 2 nnz k^2 = 4.096e10 and 2 nnz k = 1.28e9 a side a sweep; the solves
    # (138,493 + 26,744) x 32^3 / 3 = 1.80e9 a sweep: 0.86 TFLOP in all
    by_hand = 10 * (2 * (4.096e10 + 1.28e9) + 165_237 * 32**3 / 3)
    assert work["flops"] == pytest.approx(by_hand)
    assert work["flops"] == pytest.approx(0.863e12, rel=0.01)
    # (12 + 128) B an observation a side, and the tables written: 56 GB
    assert work["bytes"] == pytest.approx(
        10 * (2 * 20e6 * 140 + 165_237 * 128)
    )
    seconds, bound = counts.roofline_seconds(work, PEAKS)
    assert bound == "bytes" and seconds == pytest.approx(0.0686, rel=0.01)


def test_topn_counts_by_hand():
    shape = {"n_items": 41_140, "rank": 2048}
    work = counts.topn_batches(shape, {"queries": 128, "batches": 1})
    assert work["flops"] == pytest.approx(2 * 128 * 41_140 * 2048)
    assert work["bytes"] == pytest.approx(41_140 * 2048 * 4 + 128 * 2048 * 4)
    # one full batch: 0.41 ms by bytes, 0.11 ms by one bf16 pass
    seconds, bound = counts.roofline_seconds(work, PEAKS)
    assert bound == "bytes" and seconds == pytest.approx(0.41e-3, rel=0.02)
    one = counts.topn_queries(shape, {"queries": 1})
    assert one["flops"] == pytest.approx(2 * 41_140 * 2048)


TRAFFIC = {
    "rate_per_s": 50, "schedule_seed": 9, "users": {"n": 1000, "zipf_s": 1.0},
    "num": {"values": [4, 10, 16], "weights": [0.2, 0.6, 0.2]},
}


def test_schedule_is_the_same_work_in_another_order():
    due_a, users_a, nums_a = loadgen.make_schedule(TRAFFIC, 10, seed=1)
    due_b, users_b, nums_b = loadgen.make_schedule(TRAFFIC, 10, seed=2**31 + 5)
    again = loadgen.make_schedule(TRAFFIC, 10, seed=1)
    assert len(due_a) == len(due_b) == 500
    assert due_a[0] == 0.0 and due_a[-1] < 10 and np.all(np.diff(due_a) >= 0)
    assert all(np.array_equal(x, y) for x, y in zip(again, (due_a, users_a, nums_a)))
    assert not np.array_equal(users_a, users_b)
    assert sorted(users_a) == sorted(users_b) and sorted(nums_a) == sorted(nums_b)
    def gaps(due):  # the last request's gap runs to the window's end
        return sorted(np.append(np.diff(due), 10 - due[-1]))

    assert np.allclose(gaps(due_a), gaps(due_b))
    assert users_a.max() < 1000 and set(nums_a) <= {4, 10, 16}
    # zipf: the first ids carry most of the traffic
    assert np.mean(users_a < 100) > 0.5


def test_sources_read_or_return_nothing():
    ctx = {
        "phases": {"stream:device-loop": 4.0, "a": 1.0, "b": 2.0},
        "log": {"start_to_device_s": 9.5},
        "trace": {"device": {"busy_s": 2.0, "matching": {
            "jit_x": {"seconds": 0.5, "events": 3}}}},
        "trace_window_s": 8.0, "shape": ML20M, "seen": SEEN, "peaks": PEAKS,
    }
    assert layers.read(ctx, "phase:a+b") == 3.0
    assert layers.read(ctx, "phase:a+missing") is None
    assert layers.read(ctx, "log:start_to_device_s") == 9.5
    assert layers.read(ctx, "trace:idle_share") == 0.75
    assert layers.read(ctx, "trace:ops_matching:jit_x") == 0.5
    share = layers.read(ctx, "share:als_loop:mfu/phase:stream:device-loop")
    assert share == pytest.approx(0.863e12 / 197e12 / 4.0, rel=0.01)
    assert layers.read(ctx, "share:als_loop:roofline/trace:ops_matching:nope") is None
    assert layers.read({}, "trace:idle_share") is None
    assert layers.read({"trace": {"device": None}}, "trace:busy_s") is None
    assert layers.trace_patterns(
        ["share:f:mfu/trace:ops_matching:jit_x", "phase:a"]) == ["jit_x"]
    got = layers.evaluate(ctx, [
        {"name": "idle", "unit": "%", "read": "trace:idle_share", "scale": 100},
        {"name": "gone", "unit": "s", "read": "phase:missing"},
    ])
    assert got == {"idle": {"value": 75.0, "unit": "%"}}


SCRAPES = (
    'h_bucket{le="0.01"} 0\nh_bucket{le="0.1"} 10\nh_bucket{le="+Inf"} 10\n'
    "h_sum 0.4\nh_count 10\nc_total{site=\"serving\"} 1\n",
    'h_bucket{le="0.01"} 10\nh_bucket{le="0.1"} 40\nh_bucket{le="+Inf"} 40\n'
    "h_sum 1.9\nh_count 40\nc_total{site=\"serving\"} 1\n",
)


def test_prometheus_deltas():
    ctx = {"prom": SCRAPES}
    assert layers.read(ctx, "prom:h:mean") == pytest.approx(0.05)
    assert layers.read(ctx, "prom:h:count") == 30
    assert layers.read(ctx, "prom:c_total:delta") == 0
    # rises: 10 under 0.01, 20 more under 0.1; the median lies in the second
    assert layers.read(ctx, "prom:h:quantile:0.5") == pytest.approx(0.0325)
    assert layers.read(ctx, "prom:absent:mean") is None


def test_trace_reduction_on_made_up_planes():
    us = 1000
    ops = [
        ("while", 0, 100 * us), ("fusion.1", 0, 40 * us),
        ("fusion.2", 50 * us, 90 * us), ("copy", 200 * us, 250 * us),
    ]
    planes = [
        ("/host:CPU", [("python", [("f", 0, 10**9)])]),
        ("/device:TPU:0", [
            ("XLA Modules", [("jit_loop(1)", 0, 100 * us),
                             ("jit_other(2)", 200 * us, 250 * us)]),
            ("XLA Ops", ops),
        ]),
    ]
    got = trace.reduce_planes(planes, ["jit_loop", "nothing"])
    device = got["device"]
    assert device["busy_s"] == pytest.approx(150e-6)  # a union, not a sum
    assert device["matching"]["jit_loop"]["seconds"] == pytest.approx(100e-6)
    assert device["matching"]["nothing"]["seconds"] == 0
    own = {name: s for name, s, _ in device["ops"]}
    assert own["while"] == pytest.approx(20e-6)  # less what its children cover
    assert own["fusion.1"] == pytest.approx(40e-6)
    assert device["gaps"][0] == [100 * us, pytest.approx(100e-6)]
    assert trace.reduce_planes(planes[:1])["device"] is None  # a CPU trace
    # a batch is one run of each program the cell's files name (a sharded
    # program and the merge after it): not one a program
    from lib import cells
    assert cells.batches_seen(got) == 1
    assert cells.batches_seen(
        trace.reduce_planes(planes, ["jit_loop", "jit_other"])) == 1
    assert cells.batches_seen(trace.reduce_planes(planes[:1])) == 0
    # a second chip that did less: the average, and each plane's own
    two = trace.reduce_planes(
        planes + [("/device:TPU:1", [("XLA Ops", [("fusion.1", 0, 30 * us)])])])
    assert two["device"]["busy_by_plane"] == {
        "/device:TPU:0": pytest.approx(150e-6),
        "/device:TPU:1": pytest.approx(30e-6)}
    assert two["device"]["busy_s"] == pytest.approx(90e-6)


def test_recorded_trace_reduces():
    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(here, "data")
    if not os.path.isdir(path):
        pytest.skip("no recorded trace beside the tests")
    with open(os.path.join(path, "expected.json")) as f:
        expected = json.load(f)
    got = trace.reduce_dir(path, list(expected["matching"]))
    assert got["device_planes"] == expected["device_planes"]
    assert got["device"]["busy_s"] == pytest.approx(expected["busy_s"])
    for pattern, seconds in expected["matching"].items():
        assert got["device"]["matching"][pattern]["seconds"] == pytest.approx(seconds)


def test_als_reference_equals_the_repos_oracle():
    from predictionio_tpu.ops.als_reference import train_als_reference

    u, i, r = data.synth_ratings(300, 200, 8000, 41, 7)
    pu, du = data.dense_codes(u, 300)
    pi, di = data.dense_codes(i, 200)
    assert np.array_equal(pu, np.unique(u)) and np.array_equal(pu[du], u)
    kw = dict(rank=8, iterations=5, reg=0.05, seed=7)
    X, Y = reference.als_reference(du, di, r, len(pu), len(pi), **kw)
    Xo, Yo = train_als_reference(du, di, r, len(pu), len(pi), **kw)
    # the oracle starts from float64 draws, the program from their float32
    assert np.abs(X - Xo).max() < 5e-6 and np.abs(Y - Yo).max() < 5e-6
    Xc, _ = reference.als_reference(
        du, di, r, len(pu), len(pi), precision="bfloat16", **kw
    )
    assert np.linalg.norm(Xc - X) / np.linalg.norm(X) > 1e-3


def test_bfloat16_rounding():
    x = np.array([1.0, 1.00390625, 1.005859375, -3.14159], np.float32)
    got = reference.round_bfloat16(x)
    assert got.tolist() == [1.0, 1.0, 1.0078125, -3.140625]


def test_seeded_factors_do_not_depend_on_threads():
    a = data.seeded_factors(1000, 16, 2**31 + 7, 0)
    b = data.seeded_factors(1000, 16, 2**31 + 7, 0)
    c = data.seeded_factors(1000, 16, 2**31 + 7, 1)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    scores = a @ c.T
    assert abs(scores.std() - 1.0) < 0.05  # scaled so that scores are O(1)


def test_numbers_hold_the_worst_and_refuse_nan():
    numbers = compare.Numbers({"gap": 1.0})
    assert numbers.add("gap", 0.5) and numbers.add("gap", 0.2)
    assert numbers.out["gap"]["value"] == 0.5
    assert not numbers.add("gap", float("nan")) or not numbers.out["gap"]["ok"]
    numbers = compare.Numbers({"gap": 1.0})
    assert not numbers.add("gap", float("nan"))


# --- kinds of cell and chips, found by data ---


def a_run(tmp_path, kind, chips=1, require_tpu=True):
    from lib import cells

    return cells.Run(
        name="c", config={}, traffic={"kind": kind}, layer_defs=[],
        end_to_end=[], peaks_table={"TPU v5 lite": {
            "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
            "source": "a table"}},
        seed=1, seconds=1.0, trace=False, work=str(tmp_path), chips=chips,
        require_tpu=require_tpu,
    )


def test_a_new_kind_of_cell_is_a_new_file(tmp_path):
    """``lib/kinds/<kind>.py`` is found by the traffic file's ``kind``: a
    later PR's kind is a file of its own (here beside the real ones by
    way of the package's path, so that the test writes nothing there)."""
    import lib.kinds
    from lib import cells, children

    (tmp_path / "echo_loop.py").write_text(
        "def run_cell(run):\n    return {'kind': run.traffic['kind']}\n"
    )
    lib.kinds.__path__.append(str(tmp_path))
    try:
        run = a_run(tmp_path, "echo-loop")
        assert tiny.harness.run_cell(run) == {"kind": "echo-loop"}
    finally:
        lib.kinds.__path__.remove(str(tmp_path))
    for kind in ("train-loop", "open-loop-queries"):
        assert callable(cells.kind_of(a_run(tmp_path, kind)).run_cell)
    with pytest.raises(children.CellFailed, match="no kind of cell"):
        cells.kind_of(a_run(tmp_path, "no-such-kind"))


def test_chips_are_the_cells_own(tmp_path):
    """A cell gets the chips it asks for or no result; the peaks are per
    chip in the table and the cell's chips times that in the shares."""
    from lib import children

    one = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    four = dict(one, count=4)
    assert a_run(tmp_path, "k").peaks(one)["bf16_flops_per_s"] == 197e12
    peaks = a_run(tmp_path, "k", chips=4).peaks(four)
    assert peaks["bf16_flops_per_s"] == 4 * 197e12
    assert peaks["hbm_bytes_per_s"] == 4 * 819e9 and peaks["source"] == "a table"
    for chips, device in ((4, one), (1, four)):
        with pytest.raises(children.CellFailed, match="chip"):
            a_run(tmp_path, "k", chips=chips).peaks(device)
    with pytest.raises(children.CellFailed, match="refusing to time a CPU"):
        a_run(tmp_path, "k").peaks(dict(one, platform="cpu"))
    with pytest.raises(children.CellFailed, match="no peaks"):
        a_run(tmp_path, "k").peaks(dict(one, kind="TPU v9"))
    # a rehearsal: the platform is not checked, the count is
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    assert a_run(tmp_path, "k", chips=4, require_tpu=False).peaks(
        dict(cpu, count=4)) is None
    with pytest.raises(children.CellFailed, match="asks for 4 chip"):
        a_run(tmp_path, "k", chips=4, require_tpu=False).peaks(cpu)


def scrape_of(ledger=(), drift=(), in_use=()):
    """A /metrics scrape with the families the memory reading takes:
    ``ledger`` (device label, component, bytes), ``drift`` (device
    label, bytes), ``in_use`` (device id, bytes)."""
    lines = [
        f'pio_device_ledger_bytes{{device="{d}",component="{c}",owner="i"}} {b}'
        for d, c, b in ledger]
    lines += [f'pio_device_ledger_drift_bytes{{device="{d}"}} {b}'
              for d, b in drift]
    for d, b in in_use:
        lines += [
            f'pio_device_memory_bytes{{device="{d}",stat="bytes_in_use"}} {b}',
            f'pio_device_memory_bytes{{device="{d}",stat="peak_bytes_in_use"}}'
            f' {2 * b}']
    return "\n".join(lines) + "\n"


@pytest.mark.parametrize("host_bytes", [0, 65_536])
def test_memory_of_one_device_is_the_ledger_and_drift_reading(host_bytes):
    """On one chip the device's ``bytes_in_use`` is what ledger + drift
    read, less the ledger's entries on the host, which are named."""
    from lib import children

    device = "TPU_0(process=0,(0,0,0,0))"
    ledger = [(device, "serving-factors", 5_000_000_000),
              (device, "serving-factors-norms", 21_000_000)]
    if host_bytes:
        ledger.append(("host", "pack-cache", host_bytes))
    got = children.device_memory(scrape_of(
        ledger=ledger, drift=[(device, 207_552)],
        in_use=[(0, 5_021_207_552)]))
    assert got["memory_peak_bytes"] == 5_021_207_552
    assert got["memory_by_device"] == [5_021_207_552]
    assert got["memory_peak_bytes"] + host_bytes == got["ledger_plus_drift_bytes"]
    assert got["ledger_host_bytes"] == (
        {"pack-cache": host_bytes} if host_bytes else {})


def test_memory_reads_what_the_program_renders(monkeypatch):
    """The families and labels as the server renders them at a scrape
    (the ledger reconciled against each device, then each device's
    ``memory_stats()`` recorded), for a table sharded over four chips."""
    import jax
    from lib import children
    from predictionio_tpu.utils import device_ledger, health, metrics

    class Chip:
        def __init__(self, n, in_use):
            self.id, self.in_use = n, in_use

        def __str__(self):
            return f"TPU_{self.id}(process=0,({self.id},0,0,0))"

        def memory_stats(self):
            return {"bytes_in_use": self.in_use,
                    "peak_bytes_in_use": 2 * self.in_use}

    in_use = [4_900_000_000, 4_950_000_000, 4_800_000_000, 4_810_000_000]
    # the runtime's order need not be the ids': the reading is by id
    chips = [Chip(n, in_use[n]) for n in (2, 0, 3, 1)]
    monkeypatch.setattr(jax, "local_devices", lambda: chips)
    share = 4_824_250_000
    ledger = device_ledger.DeviceLedger()
    ledger.register("similarproduct", 4 * share, device=f"{chips[1]}x4",
                    members={str(c): share for c in chips})
    ledger.register("pack-cache", 1_000)  # host memory, on no device
    try:
        ledger.reconcile()
        health.record_memory_gauges()
        got = children.device_memory(metrics.get_registry().render())
    finally:
        metrics.get_registry().reset()
    assert got["memory_by_device"] == in_use
    assert got["memory_peak_bytes"] == 4_950_000_000
    assert got["memory_source"] == "bytes_in_use"
    assert got["ledger_bytes"] == 4 * share + 1_000
    assert got["ledger_host_bytes"] == {"pack-cache": 1_000}
    # the reading it replaces: all four chips' residency and one's drift
    assert got["ledger_plus_drift_bytes"] == (
        4 * share + 1_000 + 4_950_000_000 - share)


def test_memory_without_device_stats_falls_back_to_the_ledger():
    """The CPU gives no ``memory_stats()``: no drift, no bytes in use;
    the ledger's total stands in and the line says so."""
    from lib import children

    got = children.device_memory(scrape_of(
        ledger=[("TFRT_CPU_0x4", "similarproduct", 2_560_000),
                ("TFRT_CPU_0x4", "similarproduct-mask", 640_000)]))
    assert got["memory_peak_bytes"] == got["ledger_bytes"] == 3_200_000
    assert got["memory_source"].startswith("ledger+drift")
    assert "memory_by_device" not in got
    assert children.device_memory("")["memory_peak_bytes"] == 0


def test_the_generators_lag_is_seen():
    """A loop held up for 80 ms shows in the second it fell in."""
    import asyncio
    import time

    async def main():
        state = {"t0": time.perf_counter()}
        lag = {"worst_ms_by_second": [0.0] * 3}
        watcher = asyncio.ensure_future(loadgen._watch_lag(state, lag))
        await asyncio.sleep(0.05)
        time.sleep(0.08)  # blocks the loop
        await asyncio.sleep(0.05)
        watcher.cancel()
        return lag["worst_ms_by_second"]

    worst = asyncio.run(main())
    assert 60 < worst[0] < 500 and worst[1] == 0.0


def test_rows_stand_in_for_the_table():
    table = np.arange(40, dtype=np.float32).reshape(10, 4)
    users = np.array([7, 2, 7, 9, 2])
    rows = data.Rows(table, users)
    assert rows.rows.shape == (3, 4)
    assert np.array_equal(rows[users], table[users])
    assert np.array_equal(rows[np.array([9])], table[[9]])
