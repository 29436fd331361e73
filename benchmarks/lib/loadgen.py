"""The open-loop load generator: a process of its own (the ``offer``
stage of ``stages.py``), one thread, no JAX, its garbage collector off.

Requests are *due* on a schedule that does not wait for answers; each is
timed from the instant it was due, so a stall is charged to every request
it delays. How late the generator itself sent is reported beside it, and
so is how long its own event loop was ever held up (``lag``): a tail is
the server's only where the generator's loop ran on time.
"""

from __future__ import annotations

import asyncio
import json
import time

import numpy as np

from . import data


def make_schedule(traffic, seconds, seed):
    """Due times, users and nums for one window.

    The multiset of gaps, users and nums is fixed by the traffic file's
    ``schedule_seed`` and the window's length; ``seed`` only shuffles the
    order. Every seed so offers exactly round(rate x seconds) requests,
    all due inside the window, the first at 0."""
    n = int(round(traffic["rate_per_s"] * seconds))
    base = np.random.default_rng(traffic["schedule_seed"])
    gaps = base.exponential(size=n)
    gaps *= seconds / gaps.sum()
    users = data.zipf_ids(
        traffic["users"]["n"], traffic["users"]["zipf_s"], n, base
    )
    nums = base.choice(
        np.asarray(traffic["num"]["values"]), size=n,
        p=np.asarray(traffic["num"]["weights"], np.float64),
    )
    order = np.random.default_rng(seed)
    gaps = gaps[order.permutation(n)]
    pick = order.permutation(n)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due, users[pick], nums[pick].astype(np.int64)


def request_bytes(host, user, num) -> bytes:
    body = json.dumps({"user": data.user_name(user), "num": int(num)}).encode()
    return (
        b"POST /queries.json HTTP/1.1\r\nHost: " + host.encode() + b"\r\n"
        b"Content-Type: application/json\r\nConnection: keep-alive\r\n"
        b"Content-Length: " + str(len(body)).encode() + b"\r\n\r\n" + body
    )


async def _read_response(reader):
    head = await reader.readuntil(b"\r\n\r\n")
    status = int(head.split(b" ", 2)[1])
    length = 0
    for line in head.split(b"\r\n")[1:]:
        if line[:15].lower() == b"content-length:":
            length = int(line[15:])
    return status, await reader.readexactly(length)


async def _connection(host, port, state):
    """One keep-alive connection: takes the next request not yet taken,
    waits until it is due, sends it, waits for its answer."""
    due, payloads, out, t0 = (
        state["due"], state["payloads"], state["out"], state["t0"]
    )
    reader = writer = None
    while True:
        k = state["next"]
        if k >= len(due):
            break
        state["next"] = k + 1
        wait = t0 + due[k] - time.perf_counter()
        if wait > 0:
            await asyncio.sleep(wait)
        rec = out[k]
        try:
            if writer is None:
                reader, writer = await asyncio.open_connection(host, port)
            rec[0] = time.perf_counter() - t0  # sent
            writer.write(payloads[k])
            await writer.drain()
            status, body = await asyncio.wait_for(
                _read_response(reader), state["timeout_s"]
            )
            rec[1] = time.perf_counter() - t0  # answered
            rec[2], rec[3] = status, body
        except (OSError, asyncio.IncompleteReadError, asyncio.TimeoutError,
                asyncio.LimitOverrunError, ValueError, IndexError) as e:
            rec[1] = time.perf_counter() - t0
            rec[2], rec[3] = -1, repr(e).encode()
            if writer is not None:
                writer.close()
            reader = writer = None
    if writer is not None:
        writer.close()


LAG_STEP_S = 0.005


async def _watch_lag(state, lag):
    """Sleeps ``LAG_STEP_S`` at a time beside the connections and notes by
    how much each sleep overran, the worst of each second of the window
    (ms; the last entry takes whatever comes after the last due time):
    the loop was blocked, or the process was not scheduled."""
    worst = lag["worst_ms_by_second"]
    while True:
        t = time.perf_counter()
        await asyncio.sleep(LAG_STEP_S)
        over_ms = (time.perf_counter() - t - LAG_STEP_S) * 1e3
        second = min(max(int(t - state["t0"]), 0), len(worst) - 1)
        worst[second] = max(worst[second], over_ms)


async def _drive(host, port, due, payloads, connections, timeout_s):
    out = [[None, None, None, None] for _ in due]
    state = {
        "due": due, "payloads": payloads, "out": out, "next": 0,
        "timeout_s": timeout_s, "t0": time.perf_counter() + 0.05,
    }
    t0_wall = time.time() + 0.05
    lag = {"worst_ms_by_second": [0.0] * (int(due[-1]) + 2)}
    watcher = asyncio.ensure_future(_watch_lag(state, lag))
    await asyncio.gather(
        *[_connection(host, port, state) for _ in range(connections)]
    )
    watcher.cancel()
    return out, t0_wall, lag


def drive(host, port, due, users, nums, connections, timeout_s=60.0):
    """Offer the schedule; returns per request [sent, answered, status,
    body] (seconds from the window's start), the wall-clock time of that
    start, and the loop's lag (``_watch_lag``). Waits for every answer, up
    to ``timeout_s`` each."""
    payloads = [
        request_bytes(f"{host}:{port}", u, n) for u, n in zip(users, nums)
    ]
    return asyncio.run(
        _drive(host, port, due, payloads, connections, timeout_s)
    )


def percentile(values, q):
    """The q-th percentile by linear interpolation (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))
