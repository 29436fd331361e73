"""The e-commerce cell: its tiny CPU rehearsal end to end, the inputs it
shares with the program, and the control of its comparison: planted
faults (a seen item served, a stale constraint, a category ignored, a
viewed item served again, a query answered on the host, bfloat16 products) each turn ``correct`` false.
The set-up's two stages that run side by side share no write lock: the
instance is written while the event store's file is held."""

from __future__ import annotations

import copy
import json
import os
import sqlite3
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny_ecom  # noqa: E402
from lib import data, ecom, reference_ecom  # noqa: E402
from lib.kinds import ecom_queries  # noqa: E402


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """One traced tiny run, and what its comparison was given."""
    seen = {}
    real = ecom_queries.verify

    def keeping(run, sched, got, warm_views, cold, on_host):
        seen.update(run=run, sched=sched, got=got, warm_views=warm_views)
        return real(run, sched, got, warm_views, cold, on_host)

    ecom_queries.verify = keeping
    try:
        line = tiny_ecom.tiny_run(
            str(tmp_path_factory.mktemp("ecom")), trace=True, seed=2**31 + 77)
    finally:
        ecom_queries.verify = real
    return line, seen


def test_tiny_rehearsal_is_correct_and_reports_the_cells_metrics(rehearsal):
    line, _ = rehearsal
    assert line["correct"], line["compared"]
    assert set(line["compared"]) == {
        "cold_compiles_in_window", "host_fallbacks",
        "answers_missing_or_malformed",
        "filter_violations", "seen_after_write_violations",
        "stale_constraint_answers", "rank_gap", "score_err"}
    assert line["attempted"] == 240 and line["failed"] == 0
    for name in ("ecom_store_read_ms", "ecom_mask_prep_ms",
                 "ecom_recent_queries", "ecom_list_pad_waste",
                 "serve_batch_host_prep_ms", "serve_batch_device_wait_ms"):
        assert line["metrics"][name]["value"] > 0, name
    # a CPU trace has no device plane: the shares are left out, not 0
    assert "retrieval_roofline" not in line["metrics"]
    checked = line["checked"]
    assert set(checked["by_shape"]) == set(ecom.SHAPES)
    assert checked["constraint_added"] > 0 and checked["compared"] >= 60
    assert line["setup"]["load_parts"]["index_s"] >= 0


def verdict(seen, got, host_fallbacks=0.0):
    numbers, _, _ = ecom_queries.verify(
        seen["run"], seen["sched"], got, seen["warm_views"], 0.0,
        host_fallbacks)
    return {k: v["value"] for k, v in numbers.out.items() if not v["ok"]}


def answer_of(got, k):
    return json.loads(got["out"][k][3])["itemScores"]


def put_first_item(got, k, item_id):
    scored = answer_of(got, k)
    scored[0]["item"] = data.item_name(item_id)
    got["out"][k][3] = json.dumps({"itemScores": scored}).encode()


def test_the_untouched_answers_pass_the_comparison_again(rehearsal):
    _, seen = rehearsal
    assert verdict(seen, seen["got"]) == {}


def test_a_query_answered_on_the_host_is_not_correct(rehearsal):
    _, seen = rehearsal
    assert "host_fallbacks" in verdict(seen, seen["got"], host_fallbacks=1.0)


def test_a_seen_item_served_is_not_correct(rehearsal):
    _, seen = rehearsal
    got, sched = copy.deepcopy(seen["got"]), seen["sched"]
    history = ecom.History(seen["run"].config, seen["run"].seed)
    k = next(k for k in range(len(sched["due"]))
             if sched["shapes"][k] == ecom.PLAIN and answer_of(got, k))
    put_first_item(got, k, int(history.seen(int(sched["users"][k]))[0]))
    assert "filter_violations" in verdict(seen, got)


def test_a_category_ignored_is_not_correct(rehearsal):
    _, seen = rehearsal
    got, sched = copy.deepcopy(seen["got"]), seen["sched"]
    cats = ecom.item_categories(seen["run"].config["shape"], seen["run"].config)
    k = next(k for k in range(len(sched["due"]))
             if sched["shapes"][k] == ecom.CATEGORY and answer_of(got, k))
    other = int(np.flatnonzero(cats != sched["category"][k])[-1])
    put_first_item(got, k, other)
    assert "filter_violations" in verdict(seen, got)


def test_a_stale_constraint_is_not_correct(rehearsal):
    _, seen = rehearsal
    got, sched = copy.deepcopy(seen["got"]), seen["sched"]
    ttl = seen["run"].config["engine"]["algorithms"][0]["params"][
        "constraint_ttl_s"]
    con = got["constraint"]
    k = next(k for k in range(len(sched["due"]))
             if got["out"][k][0] > con["acked"] + ttl and answer_of(got, k))
    put_first_item(got, k, reference_ecom.item_id(con["added"][0]))
    assert "stale_constraint_answers" in verdict(seen, got)
    # the same answer sent inside the TTL breaks no guarantee
    early = copy.deepcopy(seen["got"])
    k = next(k for k in range(len(sched["due"]))
             if early["out"][k][0] < con["posted"] and answer_of(early, k))
    assert "stale_constraint_answers" not in verdict(seen, early)


def test_a_viewed_item_served_again_is_not_correct(rehearsal):
    _, seen = rehearsal
    got = copy.deepcopy(seen["got"])
    k, how = next((int(k), how) for k, how in got["sent_as"].items()
                  if how.get("viewed") and how.get("status") == 201
                  and answer_of(got, int(k)))
    put_first_item(got, k, reference_ecom.item_id(how["viewed"]))
    failed = verdict(seen, got)
    assert "seen_after_write_violations" in failed


def test_a_missing_answer_is_not_correct(rehearsal):
    _, seen = rehearsal
    got = copy.deepcopy(seen["got"])
    got["out"][3][2] = 500
    assert "answers_missing_or_malformed" in verdict(seen, got)


def test_bfloat16_products_are_not_correct(rehearsal):
    """The control: the reference in the program's place, both operands
    of the product rounded to bfloat16."""
    _, seen = rehearsal
    run, sched = seen["run"], seen["sched"]
    shape = run.config["shape"]
    checked = ecom_queries.check_answers(
        run, sched, seen["got"], seen["warm_views"])
    Y = data.seeded_factors(shape["n_items"], shape["rank"], run.seed, 1)
    queries = ecom_queries.sample_queries(run, sched, checked, Y)
    from lib import compare

    for precision, passes in (("float64", True), ("bfloat16", False)):
        reference_ecom.control_answers(
            queries, Y, checked["cats"], checked["unavailable"], precision)
        numbers = compare.Numbers(run.config["limits"])
        reference_ecom.serve_numbers(numbers, queries, reference_ecom.reference_topn(
            queries, Y, checked["cats"], checked["unavailable"]))
        assert all(n["ok"] for n in numbers.out.values()) == passes, numbers.out


def test_the_benchmarks_reference_is_the_programs_reference():
    """``lib/reference_ecom.py`` (blocked, many queries) against
    ``models/ecommerce/reference.py`` (one query at a time)."""
    sys.path.insert(0, os.path.dirname(BENCH))
    from predictionio_tpu.models.ecommerce import reference

    rng = np.random.default_rng(3)
    n_items, k = 700, 16
    X = rng.standard_normal((5, k)).astype(np.float32)
    Y = rng.standard_normal((n_items, k)).astype(np.float32)
    cats = rng.integers(0, 6, n_items).astype(np.int32)
    gone = np.zeros(n_items, bool)
    gone[rng.choice(n_items, 30, replace=False)] = True
    queries = []
    for u in range(5):
        recent = rng.choice(n_items, 12, replace=False).tolist()
        cosine = u >= 3
        queries.append({
            "row": (reference_ecom.recent_vector(Y, recent) if cosine
                    else X[u].astype(np.float64)),
            "cosine": cosine, "recent": recent,
            "exclude": np.sort(rng.choice(n_items, 40, replace=False)),
            "white": (np.sort(rng.choice(n_items, 300, replace=False))
                      if u % 2 else None),
            "category": int(u) if u in (1, 2, 4) else None,
            "version": 0, "num": 10, "served": np.zeros(0, np.int64)})
    got = reference_ecom.reference_topn(queries, Y, cats, [gone], block=256)
    item_index = {data.item_name(j): j for j in range(n_items)}
    for u, (q, g) in enumerate(zip(queries, got)):
        body = {"user": "x" if q["cosine"] else f"u{u}", "num": 10,
                "blackList": []}
        if q["white"] is not None:
            body["whiteList"] = [data.item_name(i) for i in q["white"]]
        if q["category"] is not None:
            body["categories"] = [f"c{q['category']}"]
        want = reference.predict(
            X, Y, {f"u{j}": j for j in range(5)}, item_index, body,
            seen=[data.item_name(i) for i in q["exclude"]],
            recent=[data.item_name(i) for i in q["recent"]],
            unavailable=[data.item_name(i) for i in np.flatnonzero(gone)],
            item_categories=cats[:, None],
            category_names=[f"c{j}" for j in range(6)])
        assert [item_index[i] for i, _ in want] == g["best_items"].tolist()
        np.testing.assert_allclose([s for _, s in want], g["best_scores"],
                                   rtol=1e-12)


def test_schedule_is_the_same_for_parent_and_generator_and_keeps_the_mix():
    with open(os.path.join(BENCH, "configs", "ecom-taobao-d512.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", "ecom-filtered.json")) as f:
        traffic = json.load(f)
    config["shape"] = dict(tiny_ecom.SHAPE)
    config["data"].update(held_users=600, visitors=50)
    traffic["rate_per_s"] = 200
    a = ecom.make_schedule(traffic, config, 10.0, 2**31 + 5)
    b = ecom.make_schedule(traffic, config, 10.0, 2**31 + 5)
    c = ecom.make_schedule(traffic, config, 10.0, 9)
    assert len(a["due"]) == 2000 and a["due"][0] == 0 and a["due"][-1] < 10
    for key in ("due", "users", "nums", "shapes", "category", "ref"):
        np.testing.assert_array_equal(a[key], b[key])
    # another seed offers the same multiset of nums, in another order
    assert sorted(a["nums"]) == sorted(c["nums"])
    assert not np.array_equal(a["nums"], c["nums"])
    share = np.bincount(a["shapes"], minlength=5) / 2000.0
    assert abs(share[ecom.CATEGORY] - 0.3) < 0.04
    returns = np.flatnonzero(a["ref"] >= 0)
    assert len(returns) > 200
    for k in returns:  # the same user as a request at least a second earlier
        j = a["ref"][k]
        assert a["users"][k] == a["users"][j]
        assert a["due"][j] <= a["due"][k] - traffic["return_after_s"]
    assert all(50 <= len(w) <= 200 or len(w) < 50 for w in a["white"].values())


def test_the_instance_is_written_while_the_event_store_is_locked(tmp_path):
    """The cell's set-up writes the instance beside the bulk load, whose
    few transactions hold the event store's write lock for seconds each
    (longer, on a busy disk, than the program's sqlite client waits: 5 s).
    Here the lock of the EVENTDATA file is held for the whole of the
    tiny ``write_instance`` stage: with metadata in that same file the
    stage dies of ``database is locked``; with a file of its own it
    returns an instance id, and that instance is in the metadata file."""
    from lib import cells, children

    work = str(tmp_path / "work")
    os.makedirs(work)
    bench, manifest = tiny_ecom.make_bench(str(tmp_path / "bench"))
    run = tiny_ecom.harness.build_run(
        manifest, tiny_ecom.CELL, 2**31 + 9, 1.0, False, work, bench=bench,
        require_tpu=False)
    variant = cells.write_variant(run)
    config_path = os.path.join(work, "config.json")
    with open(config_path, "w") as f:
        json.dump(run.config, f)
    env = children.child_env(work, host_only=True)
    children.run_child("app_new", children.pio("app", "new", "bench"),
                       env, work, 120)
    holder = sqlite3.connect(
        tiny_ecom.tiny.sqlite_path(env, "EVENTDATA"), timeout=0.1)
    holder.execute("BEGIN IMMEDIATE")  # as the bulk import's transaction does
    try:
        _, text, _ = children.run_child(
            "write_instance",
            ecom_queries.ecom_stage("write_instance", work, variant,
                                    config_path, run.seed),
            env, work, 120)
    finally:
        holder.rollback()
        holder.close()
    written = children.json_lines(text)[-1]
    assert written["instance_id"] and written["model_bytes"] > 0
    meta = sqlite3.connect(tiny_ecom.tiny.sqlite_path(env, "METADATA"))
    (table,) = [r[0] for r in meta.execute(
        "SELECT name FROM sqlite_master WHERE type='table'")
        if "engine_instances" in r[0]]
    assert meta.execute(f"SELECT id FROM {table}").fetchall() == [
        (written["instance_id"],)]
