"""The Similar Product cell over four chips, float32 rows sharded: its tiny
CPU rehearsal on four virtual devices end to end, the merge's metrics on
its scrapes, and the sharded kernel's roofline share on a synthetic
trace of four device planes."""

from __future__ import annotations

import glob
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402
import tiny_similar  # noqa: E402
from lib import cells, layers, trace  # noqa: E402

CELL = "simprod-amazon-d512-f32x4.query-detail-page"
CONFIG = "simprod-amazon-d512-f32x4"
KERNEL = r"jit__shard_topk_kernel\b"


def make_bench(tmp):
    """``tiny_similar.make_bench`` with this cell's configuration cut to
    the same tiny size (the traffic file is the cells' shared one)."""
    bench, manifest = tiny_similar.make_bench(tmp)
    path = os.path.join(bench, "configs", CONFIG + ".json")
    with open(path) as f:
        config = json.load(f)
    config["shape"] = dict(tiny_similar.SHAPE)
    config["verify"] = {"answers": 60, "per_shape": 8}
    config["engine"]["algorithms"][0]["params"].update(
        rank=tiny_similar.SHAPE["rank"], warm_max_batch=16)
    with open(path, "w") as f:
        json.dump(config, f)
    return bench, manifest


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("similar_x4"))
    bench, manifest = make_bench(tmp)
    line = tiny.tiny_run(tmp, CELL, seed=2**31 + 39, seconds=8.0, trace=True,
                         bench=bench, manifest=manifest)
    return tmp, line


def test_the_four_chip_cell_rehearses_correct_on_four_devices(rehearsal):
    tmp, line = rehearsal
    assert line["correct"], line["compared"]
    assert set(line["compared"]) == {
        "cold_compiles_in_window", "host_fallbacks",
        "answers_missing_or_malformed", "filter_violations",
        "recall_at_num", "score_err", "order_err"}
    assert line["device"]["count"] == 4
    checked = line["checked"]
    assert checked["reference_items_served"] == checked["reference_items"]
    [log] = glob.glob(os.path.join(tmp, "work_*", "deploy.log"))
    with open(log) as f:
        assert (f"ItemRetriever[similarproduct]: {tiny_similar.SHAPE['n_items']}"
                " items (rank 32, float32) resident row-sharded over 4 devices"
                ) in f.read()


def test_the_merge_reads_on_the_rehearsals_scrapes(rehearsal):
    _, line = rehearsal
    m = line["metrics"]
    assert m["serve_batch_merge_ms"]["value"] > 0
    rows = m["retrieval_merge_rows"]["value"]
    # padded batch rows (8 or 16) x 4 shards x each shard's top 16
    assert rows > 0 and rows % (8 * 4 * 16) == 0
    # no refine at float32, and a CPU trace has no device plane
    assert "simprod_refine_ms" not in m
    for name in ("shard_topk_roofline", "merge_device_ms", "serve_step_mfu"):
        assert name not in m, name


def planes(n_planes, ms_by_plane, runs=12):
    """A trace of ``n_planes`` device planes, each with ``runs`` runs of
    the sharded program (on its module line, its ops inside them) and
    one merge after each; run times on plane p add to ``ms_by_plane[p]``."""
    out = []
    for p in range(n_planes):
        modules, ops, t = [], [], 0
        each = int(ms_by_plane[p] * 1e6 / runs)
        for r in range(runs):
            modules.append((f"jit__shard_topk_kernel({r})", t, t + each))
            ops.append(("fusion.1", t, t + each // 2))
            ops.append(("fusion.2", t + each // 2, t + each))
            t += each
            modules.append(("jit__merge_candidates(1)", t, t + 50_000))
            ops.append(("sort.3", t, t + 50_000))
            t += 2_000_000
        out.append((f"/device:TPU:{p}", [("XLA Modules", modules),
                                          ("XLA Ops", ops)]))
    return out


def share(run, n_planes, ms_by_plane, shape, chips):
    reduced = trace.reduce_planes(
        planes(n_planes, ms_by_plane), [KERNEL, r"jit__merge_candidates\b"])
    batches = cells.batches_seen(reduced)
    ctx = {"trace": reduced, "trace_window_s": 2.0, "shape": shape,
           "seen": {"batches": batches, "queries": 2.0 * batches},
           "peaks": run.peaks({"platform": "tpu", "kind": "TPU v5 lite",
                               "count": chips})}
    defs = [d for d in run.layer_defs
            if d["name"] in ("shard_topk_roofline", "merge_device_ms")]
    return batches, layers.evaluate(ctx, defs)


def test_the_sharded_roofline_is_one_chips_shard_against_one_chips_peak():
    """The whole table's bytes over the four chips' peaks and the mean
    time of a plane read as one chip's quarter against one chip's peak."""
    manifest = tiny.harness.load_json(tiny.ROOT, "BENCHMARK.json")
    run = tiny.harness.build_run(manifest, CELL, 1, 40.0, True, "unused")
    assert run.chips == 4
    shape = {"n_items": 9_400_000, "rank": 512}
    batches, four = share(run, 4, [90.0, 94.0, 96.0, 100.0], shape, 4)
    assert batches == 12
    run.chips = 1
    quarter = {"n_items": shape["n_items"] // 4, "rank": 512}
    _, one = share(run, 1, [95.0], quarter, 1)
    assert four["shard_topk_roofline"]["value"] == pytest.approx(
        one["shard_topk_roofline"]["value"], rel=1e-4)
    # 12 runs of 4.7 GB against 819 GB/s is 70.5 ms of the 95 a plane
    assert four["shard_topk_roofline"]["value"] == pytest.approx(
        100 * 12 * 9_400_000 * 512 * 4 / (4 * 819e9) / 0.095, rel=1e-3)
    # the merge: 12 runs of 50 us on each plane
    assert four["merge_device_ms"]["value"] == pytest.approx(0.6)
