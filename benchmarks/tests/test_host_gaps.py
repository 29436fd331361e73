"""``host_gaps`` over synthetic planes in ``reduce_planes``' tuple form,
and the stage metrics of the tiny CPU serving cell: the ten stage means
and the executor's count of batches that met no wait in its traced line,
none in its untraced one; the two collector metrics over a window that
holds a full collection."""

from __future__ import annotations

import json
import os

import pytest

import tiny
import host_gaps as hg

MS = 10**6

# a device that works 1 ms at a time and idles 10, 20 and 5 ms between
DEVICE = ("/device:TPU:0", [
    ("XLA Ops", [
        ("fusion", 0 * MS, 1 * MS), ("fusion", 11 * MS, 12 * MS),
        ("fusion", 32 * MS, 33 * MS), ("fusion", 38 * MS, 39 * MS),
    ]),
    ("XLA Modules", [("jit__topn_packed", 0, 39 * MS)]),
])
HOST = ("/host:CPU", [
    # the serve thread: predict around dispatch, wholly over the 10 ms gap
    ("python", [
        ("pio:predict", 0, 12 * MS), ("pio:dispatch", 2 * MS, 6 * MS),
        # ... and the first 8 ms of the 20 ms gap
        ("pio:finish", 12 * MS, 20 * MS),
        ("PjitFunction(_topn_packed_impl)", 2 * MS, 3 * MS),
    ]),
    # the collector: the last 12 ms of the 20 ms gap
    ("python", [("pio:collect", 20 * MS, 32 * MS)]),
    ("tf_pjrt", [("end: custom-call", 0, 39 * MS)]),
])


def by(row):
    return {(thread, name): s for thread, name, s in row["by"]}


def test_gaps_are_the_longest_first_and_attributed():
    rows = hg.host_gaps([HOST, DEVICE])
    assert [round(r["seconds"], 6) for r in rows] == [0.020, 0.010, 0.005]
    assert [round(r["at_s"], 6) for r in rows] == [0.012, 0.001, 0.033]


def test_a_gap_split_between_two_threads():
    split = hg.host_gaps([HOST, DEVICE])[0]
    assert by(split) == pytest.approx({
        ("python#0", "pio:finish"): 0.008, ("python#1", "pio:collect"): 0.012,
    })
    assert split["covered"] == pytest.approx(1.0)
    assert split["unannotated_s"] == pytest.approx(0.0)


def test_a_gap_wholly_under_one_annotation_charges_the_innermost():
    whole = hg.host_gaps([HOST, DEVICE])[1]
    # predict is open for all 10 ms; 4 of them (2..6 ms) are its dispatch
    assert by(whole) == pytest.approx({
        ("python#0", "pio:predict"): 0.006, ("python#0", "pio:dispatch"): 0.004,
    })
    assert whole["covered"] == pytest.approx(1.0)


def test_an_unannotated_gap_says_so():
    bare = hg.host_gaps([HOST, DEVICE])[2]
    assert bare["by"] == [] and bare["covered"] == 0.0
    assert bare["unannotated_s"] == pytest.approx(0.005)
    lines = hg.render(hg.host_gaps([HOST, DEVICE]))
    assert len(lines) == 3 and lines[2].endswith("unannotated 0.005000")
    assert "python#1 pio:collect 0.012000" in lines[0]


def test_no_device_plane_is_said_not_raised(tmp_path):
    assert hg.host_gaps([HOST]) == []
    assert "nothing to attribute" in hg.render([])[0]
    assert hg.main(["host_gaps.py", str(tmp_path)]) == 2


STAGE_METRICS = [
    "serve_http_mean_ms", "serve_server_mean_ms", "serve_queue_wait_mean_ms",
    "serve_slot_wait_mean_ms", "serve_predict_mean_ms", "serve_finish_mean_ms",
    "serve_batch_host_prep_ms", "serve_batch_dispatch_ms",
    "serve_batch_device_wait_ms", "serve_batch_build_ms",
    "serve_batches_immediate",  # PR 27: batches that found a serve slot free
]
# a full collection comes once in a thousand requests or so: the chip's
# 16,000-request window holds a dozen, the tiny cell's 80 requests none
GC_METRICS = ["serve_gc_full_pause_mean_ms", "serve_gc_full_pauses"]


def listed_metrics():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        return {m["name"]: m for m in json.load(f)["per_layer"]}


def test_the_manifest_lists_the_stage_metrics_for_the_serving_cell():
    """Every serving cell reports the stage metrics (the steady cell, and
    since PRs 28 and 33 the e-commerce and similar-product cells): each
    lists all the cells that report the end-to-end metric it moves."""
    listed = listed_metrics()
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (moved,) = [m for m in manifest["end_to_end"] if m["name"] == "query_p50_ms"]
    assert tiny.SERVE in moved["workloads"]
    for name in STAGE_METRICS + GC_METRICS:
        assert listed[name]["workloads"] == moved["workloads"]
        assert listed[name]["source"] == "program_counter"
        assert listed[name]["moves"] == "query_p50_ms"
        assert os.path.isfile(
            os.path.join(tiny.BENCH, "layer_metrics", name + ".json"))


def test_full_collection_metrics_read_a_window_that_holds_one():
    """The two collector metrics through the real hook, exposition and
    reader: a window with a full collection reads both, a window
    without one leaves both out (never a 0)."""
    import gc

    from lib import layers
    from predictionio_tpu.utils import health, metrics

    listed = listed_metrics()
    definitions = []
    for name in GC_METRICS:
        with open(os.path.join(
                tiny.BENCH, "layer_metrics", name + ".json")) as f:
            definitions.append(
                dict(json.load(f), name=name, unit=listed[name]["unit"]))
    health.install_gc_pause_hook()
    gc.collect()  # the family has a first sample
    before = metrics.get_registry().render()
    gc.collect()
    gc.collect(0)  # a young collection is not a full one
    after = metrics.get_registry().render()
    got = layers.evaluate({"prom": (before, after)}, definitions)
    assert got["serve_gc_full_pauses"] == {"value": 1.0, "unit": "pauses"}
    assert got["serve_gc_full_pause_mean_ms"]["value"] > 0.0
    assert layers.evaluate({"prom": (after, after)}, definitions) == {}


@pytest.fixture(scope="module")
def tmp(tmp_path_factory):
    return str(tmp_path_factory.mktemp("bench"))


def test_serve_cell_traced_line_holds_the_stage_metrics(tmp):
    line = tiny.tiny_run(tmp, tiny.SERVE, trace=True)
    assert line["correct"] is True
    got = {k: line["metrics"][k]["value"] for k in STAGE_METRICS}
    # what the stages add up to: the four per-request means make the
    # server's mean less the parse, the batch stages lie inside predict.
    # The parse is a fixed 0.1-0.2 ms: 3 % of the chip's request, and
    # since PR 27 took the 2 ms batching window out, 11-12 % of the 2 ms
    # request of this size on this CPU
    four = sum(got[f"serve_{k}_mean_ms"]
               for k in ("queue_wait", "slot_wait", "predict", "finish"))
    assert four <= got["serve_server_mean_ms"] <= got["serve_http_mean_ms"]
    assert four >= 0.75 * got["serve_server_mean_ms"]
    batch = sum(got[f"serve_batch_{k}_ms"]
                for k in ("host_prep", "dispatch", "device_wait", "build"))
    assert 0.5 * got["serve_predict_mean_ms"] <= batch <= got["serve_predict_mean_ms"]
    # queries sent a few at a time find the one serve slot free most times
    assert 0 < got["serve_batches_immediate"] <= line["attempted"]


def test_serve_cell_untraced_line_holds_none_of_them(tmp):
    line = tiny.tiny_run(tmp, tiny.SERVE)
    assert not set(STAGE_METRICS + GC_METRICS) & set(line["metrics"])
