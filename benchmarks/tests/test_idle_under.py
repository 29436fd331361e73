"""``idle_under`` over synthetic planes in ``reduce_planes``' tuple form:
one device plane, two averaged, a host phase nested in another, and host
events that cross the capture's edges."""

from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import idle_under as iu  # noqa: E402

MS = 10**6
ROUND_TRIP = r"^pio:(upload|dispatch|merge|device_wait)$"


def device(name, ops):
    return (name, [("XLA Ops", [("fusion", s * MS, e * MS) for s, e in ops])])


def host(*threads):
    return ("/host:CPU", [
        ("python", [(n, s * MS, e * MS) for n, s, e in events])
        for events in threads
    ])


# busy 0-1, 11-12, 32-33: idle 1-11 and 12-32
ONE = device("/device:TPU:0", [(0, 1), (11, 12), (32, 33)])


def test_one_plane_counts_idle_time_under_matching_events_only():
    planes = [ONE, host([
        ("pio:dispatch", 2, 6),        # 4 ms idle
        ("pio:device_wait", 6, 11.5),  # 5 ms idle, 0.5 ms busy
        ("pio:build", 12, 20),         # idle, but no round trip
        ("pio:dispatch_late", 20, 25),  # the regex is anchored
    ])]
    got = iu.idle_under(planes, [ROUND_TRIP, r"^pio:build$"])
    assert got[ROUND_TRIP] == pytest.approx(0.009)
    assert got[r"^pio:build$"] == pytest.approx(0.008)


def test_two_planes_give_the_mean_of_the_planes():
    # the second device is busy 0-5 and 30-33: idle 5-30
    two = device("/device:TPU:1", [(0, 5), (30, 33)])
    planes = [ONE, two, host([("pio:device_wait", 0, 33)])]
    # plane 0 idles 10 + 20 ms, plane 1 25 ms, all under the wait
    assert iu.idle_under(planes, [ROUND_TRIP])[ROUND_TRIP] == pytest.approx(
        (0.030 + 0.025) / 2)


def test_a_nested_event_and_another_threads_are_counted_once():
    planes = [ONE, host(
        [("pio:dispatch", 2, 10), ("pio:upload", 3, 5)],
        [("pio:device_wait", 4, 8)],  # another serve thread, inside it
    )]
    assert iu.idle_under(planes, [ROUND_TRIP])[ROUND_TRIP] == pytest.approx(
        0.008)


def test_events_across_the_captures_edges_count_inside_it_alone():
    # open before the first op and after the last: the device's window
    # (0-33 ms) bounds what is idle
    planes = [ONE, host([("pio:device_wait", -5, 3), ("pio:merge", 30, 40)])]
    assert iu.idle_under(planes, [ROUND_TRIP])[ROUND_TRIP] == pytest.approx(
        0.002 + 0.002)


def test_no_device_plane_reads_nothing():
    assert iu.idle_under([host([("pio:dispatch", 0, 5)])], [ROUND_TRIP]) is None
    # a pattern nothing matches reads zero where there is a device
    assert iu.idle_under([ONE], ["^pio:nothing$"]) == {"^pio:nothing$": 0.0}


def test_merged_and_overlap():
    assert iu.merged([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert iu.overlap_ns([(0, 3), (5, 8)], [[2, 6], [7, 10]]) == 1 + 1 + 1
