"""The control of each comparison comes out as not correct, and the
timed path broken underneath the harness does too: once for each fault
the cells can have."""

from __future__ import annotations

import json
import os

import pytest

import tiny
import control


@pytest.fixture(scope="module")
def tmp(tmp_path_factory):
    return str(tmp_path_factory.mktemp("control"))


def tiny_config(tmp, name):
    bench, _ = tiny.make_bench(os.path.join(tmp, name))
    with open(os.path.join(bench, "configs", name + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(bench, "traffic", "open-loop-steady.json")) as f:
        return config, json.load(f)


def test_train_control_and_faults_fail(tmp):
    config, _ = tiny_config(tmp, "reco-ml20m-r32")
    got = control.train_control(config, seed=3, faults=True)
    assert set(got) == {"control_bfloat16", "a_sweep_left_out",
                        "half_the_events", "a_row_altered"}
    for name, numbers in got.items():
        assert not all(n["ok"] for n in numbers.values()), name


def test_serve_control_fails(tmp):
    config, traffic = tiny_config(tmp, "reco-msd-d2048")
    got = control.serve_control(config, traffic, seed=3, seconds=2.0)
    numbers = got["control_bfloat16"]
    assert not numbers["score_err"]["ok"]


# --- the timed path broken underneath the harness ---


def a_sweep_left_out(monkeypatch):
    from lib import cells
    from lib.kinds import train_loop

    def one_sweep_fewer(run):
        path = cells.write_variant(run)
        with open(path) as f:
            engine = json.load(f)
        engine["algorithms"][0]["params"]["num_iterations"] -= 1
        with open(path, "w") as f:
            json.dump(engine, f)
        return path

    monkeypatch.setattr(train_loop, "write_variant", one_sweep_fewer)


def half_the_events(monkeypatch):
    from lib.kinds import train_loop

    store = train_loop.store_events
    monkeypatch.setattr(
        train_loop, "store_events",
        lambda run, u, i, r: store(run, *(v[:len(r) // 2] for v in (u, i, r))),
    )


def a_row_altered(monkeypatch):
    from lib.kinds import train_loop

    load = train_loop.load_export

    def altered(directory):
        X, *rest = load(directory)
        X[len(X) // 2] *= 1.5
        return (X, *rest)

    monkeypatch.setattr(train_loop, "load_export", altered)


def an_item_altered(monkeypatch):
    from lib import compare

    parse = compare.parse_answers

    def altered(out, nums):
        answers, shaped = parse(out, nums)
        first = next(a for a in answers if a is not None)
        first[0][0] = (first[0][0] + 1) % 700  # the tiny catalog's items
        return answers, shaped

    monkeypatch.setattr(compare, "parse_answers", altered)


@pytest.mark.parametrize("fault", [a_sweep_left_out, half_the_events,
                                   a_row_altered])
def test_a_broken_train_reads_not_correct(tmp, monkeypatch, fault):
    fault(monkeypatch)
    line = tiny.tiny_run(tmp, tiny.TRAIN)
    assert line["correct"] is False, line["compared"]


def test_an_altered_answer_reads_not_correct(tmp, monkeypatch):
    an_item_altered(monkeypatch)
    line = tiny.tiny_run(tmp, tiny.SERVE)
    assert line["correct"] is False and line["failed"] >= 1
