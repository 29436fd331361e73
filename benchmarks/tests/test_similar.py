"""The Similar Product cell: its tiny CPU rehearsal end to end, the inputs
it shares with the program, and the control of its comparison: planted
faults (a query item served, a category ignored, a blacklisted item, a
shortlist one wide, a query answered on the host, the int8 scores served
as they are, a bfloat16 refine) each turn ``correct`` false."""

from __future__ import annotations

import copy
import glob
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import control_similar  # noqa: E402
import tiny_similar  # noqa: E402
from lib import counts, data, reference_similar, similar  # noqa: E402
from lib.kinds import similar_queries  # noqa: E402


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """One traced tiny run, and what its comparison was given."""
    seen = {}
    real = similar_queries.verify

    def keeping(run, sched, got, Y, cold, on_host):
        seen.update(run=run, sched=sched, got=got, Y=np.array(Y))
        return real(run, sched, got, Y, cold, on_host)

    similar_queries.verify = keeping
    try:
        line = tiny_similar.tiny_run(
            str(tmp_path_factory.mktemp("similar")), trace=True,
            seed=2**31 + 33)
    finally:
        similar_queries.verify = real
    return line, seen


def test_tiny_rehearsal_is_correct_and_reports_the_cells_metrics(rehearsal):
    line, _ = rehearsal
    assert line["correct"], line["compared"]
    assert set(line["compared"]) == {
        "cold_compiles_in_window", "host_fallbacks",
        "answers_missing_or_malformed", "filter_violations",
        "recall_at_num", "score_err", "order_err"}
    assert line["attempted"] == 240 and line["failed"] == 0
    for name in ("simprod_refine_ms", "retrieval_shortlist_rows",
                 "simprod_query_items_mean", "serve_batch_device_wait_ms",
                 "serve_predict_mean_ms", "retrieval_operand_puts"):
        assert line["metrics"][name]["value"] > 0, name
    assert "retrieval_refine_changed" in line["metrics"]
    # a CPU trace has no device plane: the shares are left out, not 0
    assert "retrieval_2s_roofline" not in line["metrics"]
    # the device hands the refine 64 candidates a query
    assert line["metrics"]["retrieval_shortlist_rows"]["value"] == 240 * 64
    checked = line["checked"]
    assert set(checked["by_shape"]) == set(similar.SHAPES)
    assert checked["compared"] >= 40
    assert checked["reference_items_served"] == checked["reference_items"]
    assert line["device"]["server_rss_anon_bytes"] > 0
    # the stages of a batch, the refine among them, add to its predict
    m = line["metrics"]
    stages = sum(m[k]["value"] for k in (
        "serve_batch_host_prep_ms", "ecom_mask_prep_ms",
        "serve_batch_dispatch_ms", "serve_batch_device_wait_ms",
        "simprod_refine_ms", "serve_batch_build_ms"))
    assert 0.8 * m["serve_predict_mean_ms"]["value"] <= stages
    assert stages <= m["serve_predict_mean_ms"]["value"]


def test_a_four_chip_cell_rehearses_its_row_sharded_path(tmp_path):
    """A cell that asks for four chips is rehearsed on four virtual
    devices: the cell at float32 in a manifest of the test's own, its
    table row-sharded over them, every answer held to the same
    comparison. The CPU has no device stats: memory is the ledger's."""
    bench, manifest = tiny_similar.make_bench(str(tmp_path))
    path = os.path.join(bench, "configs", "simprod-amazon-d512.json")
    with open(path) as f:
        config = json.load(f)
    config["engine"]["algorithms"][0]["params"]["precision"] = "float32"
    with open(path, "w") as f:
        json.dump(config, f)
    for cell in manifest["workloads"]:
        if cell["name"] == tiny_similar.CELL:
            cell["chips"] = 4
    path = os.path.join(bench, "four-chips.json")
    with open(path, "w") as f:
        json.dump(manifest, f)
    line = tiny_similar.tiny_run(
        str(tmp_path), seed=2**31 + 38, bench=bench,
        manifest=tiny_similar.harness.load_json(path))
    assert line["correct"], line["compared"]
    assert line["device"]["count"] == 4
    assert line["device"]["memory_source"].startswith("ledger+drift")
    [log] = glob.glob(os.path.join(str(tmp_path), "work_*", "deploy.log"))
    with open(log) as f:
        assert (f"ItemRetriever[similarproduct]: {tiny_similar.SHAPE['n_items']}"
                " items (rank 32, float32) resident row-sharded over 4 devices"
                ) in f.read()


def verdict(seen, got, host_fallbacks=0.0):
    numbers, _, _ = similar_queries.verify(
        seen["run"], seen["sched"], got, seen["Y"], 0.0, host_fallbacks)
    return {k: v["value"] for k, v in numbers.out.items() if not v["ok"]}


def answer_of(got, k):
    return json.loads(got["out"][k][3])["itemScores"]


def put_first_item(got, k, item_id):
    scored = answer_of(got, k)
    scored[0]["item"] = data.item_name(item_id)
    got["out"][k][3] = json.dumps({"itemScores": scored}).encode()


def first_of_shape(seen, got, *shapes):
    sched = seen["sched"]
    return next(k for k in range(len(sched["due"]))
                if sched["shapes"][k] in shapes and answer_of(got, k))


def test_the_untouched_answers_pass_the_comparison_again(rehearsal):
    _, seen = rehearsal
    assert verdict(seen, seen["got"]) == {}


def test_a_query_answered_on_the_host_is_not_correct(rehearsal):
    _, seen = rehearsal
    assert "host_fallbacks" in verdict(seen, seen["got"], host_fallbacks=1.0)


def test_a_query_item_served_is_not_correct(rehearsal):
    _, seen = rehearsal
    got = copy.deepcopy(seen["got"])
    k = first_of_shape(seen, got, similar.PLAIN)
    put_first_item(got, k, int(seen["sched"]["items"][k][0]))
    assert "filter_violations" in verdict(seen, got)


def test_a_category_ignored_is_not_correct(rehearsal):
    _, seen = rehearsal
    got, sched = copy.deepcopy(seen["got"]), seen["sched"]
    cats = similar.item_categories(seen["run"].config["shape"],
                                   seen["run"].config)
    k = first_of_shape(seen, got, similar.CATEGORY)
    put_first_item(got, k, int(np.flatnonzero(cats != sched["category"][k])[-1]))
    assert "filter_violations" in verdict(seen, got)


def test_a_blacklisted_item_served_is_not_correct(rehearsal):
    _, seen = rehearsal
    got = copy.deepcopy(seen["got"])
    k = first_of_shape(seen, got, similar.BLACK_LIST, similar.CATEGORY_BLACK)
    put_first_item(got, k, int(seen["sched"]["black"][k][0]))
    assert "filter_violations" in verdict(seen, got)


def test_an_item_outside_the_whitelist_is_not_correct(rehearsal):
    _, seen = rehearsal
    got = copy.deepcopy(seen["got"])
    k = first_of_shape(seen, got, similar.WHITE_LIST)
    white = seen["sched"]["white"][k]
    outside = next(i for i in range(len(seen["Y"])) if i not in white
                   and i not in seen["sched"]["items"][k])
    put_first_item(got, k, outside)
    assert "filter_violations" in verdict(seen, got)


def test_a_missing_answer_is_not_correct(rehearsal):
    _, seen = rehearsal
    got = copy.deepcopy(seen["got"])
    got["out"][3][2] = 500
    assert "answers_missing_or_malformed" in verdict(seen, got)


def sampled(seen):
    run, sched = seen["run"], seen["sched"]
    cats = similar.item_categories(run.config["shape"], run.config)
    checked = similar_queries.check_answers(sched, seen["got"], cats)
    return similar_queries.sample_queries(run, sched, checked), cats


@pytest.mark.parametrize("stand_in, passes", [
    ("program", True), ("shortlist_one_wide", False),
    ("int8_scores_served", False), ("bfloat16_refine", False)])
def test_the_stand_ins_are_not_correct(rehearsal, stand_in, passes):
    """The control: the reference's own answers put in the program's
    place pass; a shortlist one wide (the device's best candidate alone,
    then whatever follows), the int8 stage-1 scores served as they are
    and a refine in bfloat16 do not."""
    _, seen = rehearsal
    queries, cats = sampled(seen)
    Y = seen["Y"]
    got = control_similar.stand_ins(
        seen["run"].config, queries, lambda ids: Y[ids],
        lambda: reference_similar.file_blocks(Y, 4096), cats,
        [stand_in])[stand_in]
    assert all(n["ok"] for n in got.values()) == passes, got


def test_the_benchmarks_reference_is_the_programs_reference():
    """``lib/reference_similar.py`` (blocked, many queries) against
    ``models/similarproduct/reference.py`` (one query at a time)."""
    sys.path.insert(0, os.path.dirname(BENCH))
    from predictionio_tpu.models.similarproduct import reference

    rng = np.random.default_rng(3)
    n_items, k = 700, 16
    Y = rng.standard_normal((n_items, k)).astype(np.float32)
    Y[9] = 0.0
    cats = rng.integers(0, 6, n_items).astype(np.int32)
    queries = []
    for u in range(6):
        items = rng.choice(n_items, 1 + u, replace=False)
        black = rng.choice(n_items, 40, replace=False)
        queries.append({
            "items": items, "exclude": np.union1d(items, black),
            "black": black,
            "white": (np.sort(rng.choice(n_items, 300, replace=False))
                      if u % 2 else None),
            "category": int(u) if u in (1, 2, 4) else None,
            "num": 10, "served": np.zeros(0, np.int64)})
    Q = reference_similar.query_vectors(lambda ids: Y[ids], queries)
    got = reference_similar.reference_topn(
        queries, Q, reference_similar.file_blocks(Y, 256), cats)
    item_index = {data.item_name(j): j for j in range(n_items)}
    for q, g in zip(queries, got):
        body = {"items": [data.item_name(i) for i in q["items"]], "num": 10,
                "blackList": [data.item_name(i) for i in q["black"]]}
        if q["white"] is not None:
            body["whiteList"] = [data.item_name(i) for i in q["white"]]
        if q["category"] is not None:
            body["categories"] = [f"c{q['category']}"]
        want = reference.predict(
            Y, item_index, body, item_categories=cats[:, None],
            category_names=[f"c{j}" for j in range(6)])
        assert [item_index[i] for i, _ in want] == g["best_items"].tolist()
        np.testing.assert_allclose([s for _, s in want], g["best_scores"],
                                   rtol=1e-12)


def test_the_table_in_row_blocks_is_the_seeded_table():
    """``fill_factors`` (pieces of a stream, into a file) draws what
    ``data.seeded_factors`` draws in one call a stream."""
    n, k = 3 * similar.FILL_ROWS * data.FACTOR_BLOCKS // 4 + 5, 8
    table = np.zeros((n, k), np.float32)
    similar.fill_factors(table, 2**31 + 9)
    np.testing.assert_array_equal(
        table, data.seeded_factors(n, k, 2**31 + 9, 1))


def test_schedule_is_the_same_for_parent_and_generator_and_keeps_the_mix():
    with open(os.path.join(BENCH, "configs", "simprod-amazon-d512.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", "similar-detail-page.json")) as f:
        traffic = json.load(f)
    config["shape"] = dict(tiny_similar.SHAPE)
    traffic["rate_per_s"] = 200
    a = similar.make_schedule(traffic, config, 10.0, 2**31 + 5)
    b = similar.make_schedule(traffic, config, 10.0, 2**31 + 5)
    c = similar.make_schedule(traffic, config, 10.0, 9)
    assert len(a["due"]) == 2000 and a["due"][0] == 0 and a["due"][-1] < 10
    for key in ("due", "nums", "shapes", "category"):
        np.testing.assert_array_equal(a[key], b[key])
    assert all(np.array_equal(x, y) for x, y in zip(a["items"], b["items"]))
    # another seed offers the same multiset of nums, in another order
    assert sorted(a["nums"]) == sorted(c["nums"])
    assert not np.array_equal(a["nums"], c["nums"])
    share = np.bincount(a["shapes"], minlength=5) / 2000.0
    for s, want in enumerate((0.5, 0.3, 0.1, 0.05, 0.05)):
        assert abs(share[s] - want) < 0.04
    sizes = np.array([len(i) for i in a["items"]])
    assert sizes.min() == 1 and sizes.max() <= 10
    assert abs(np.mean(sizes == 1) - 0.6) < 0.06
    cats = similar.item_categories(config["shape"], config)
    for k in range(2000):
        body = similar.body_of(a, k)
        shape = a["shapes"][k]
        assert ("categories" in body) == (
            shape in (similar.CATEGORY, similar.CATEGORY_BLACK))
        assert ("blackList" in body) == (
            shape in (similar.BLACK_LIST, similar.CATEGORY_BLACK))
        assert ("whiteList" in body) == (shape == similar.WHITE_LIST)
        if "categories" in body:
            assert body["categories"] == [
                similar.category_name(cats[a["items"][k][0]])]
        # the ladder's tops: 10 query items + 50 under 64, 200 under 256
        assert len(body["items"]) + len(body.get("blackList", ())) <= 60
        assert len(body.get("whiteList", ())) <= 200
        if shape == similar.WHITE_LIST:
            assert len(set(cats[a["white"][k]].tolist())) == 1


def test_the_quantized_count_reads_the_int8_table_once_a_batch():
    from lib import counts_quantized

    assert counts.COUNTS["topn_batches_quantized"] is (
        counts_quantized.topn_batches_quantized)
    shape = {"n_items": 9_400_000, "rank": 512}
    work = counts.COUNTS["topn_batches_quantized"](
        shape, {"batches": 1.0, "queries": 8.0})
    assert work["bytes"] == 9_400_000 * 512 + 8 * 9_400_000 + 8 * 512 * 4
    assert work["flops"] == 2.0 * 8 * 9_400_000 * 512
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    seconds, bound = counts.roofline_seconds(work, peaks)
    assert bound == "bytes" and 0.0059 < seconds < 0.0061
    # at the ladder's widest batch bytes still bind
    full = counts.COUNTS["topn_batches_quantized"](
        shape, {"batches": 1.0, "queries": 32.0})
    assert counts.roofline_seconds(full, peaks)[1] == "bytes"
    # a quarter of what the float32 count would charge
    f32 = counts.topn_batches(shape, {"batches": 1.0, "queries": 8.0})
    assert 3.9 < f32["bytes"] / work["bytes"] < 4.0
