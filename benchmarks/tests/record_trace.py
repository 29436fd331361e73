#!/usr/bin/env python3
"""Records the small device trace that ``test_recorded_trace_reduces``
reads, and what the reduction makes of it. Run once on the chip:

    python3 benchmarks/tests/record_trace.py chiprun_out/recorded

It holds the chip itself (one process, a few small programs), with the
profiler's host and Python tracers off so that the file stays small.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from lib import trace


@jax.jit
def small_topn(x, y):
    return jax.lax.top_k(x @ y.T, 16)


@jax.jit
def small_sum(x):
    return jnp.sum(x * x)


def main(out_dir) -> int:
    x = jnp.ones((32, 256), jnp.float32)
    y = jnp.ones((4096, 256), jnp.float32)
    jax.block_until_ready((small_topn(x, y), small_sum(x)))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=options)
    for _ in range(5):
        jax.block_until_ready(small_topn(x, y))
    jax.block_until_ready(small_sum(x))
    jax.profiler.stop_trace()
    patterns = ["jit_small_topn", "jit_small_sum"]
    got = trace.reduce_dir(out_dir, patterns)
    expected = {
        "device_planes": got["device_planes"],
        "busy_s": got["device"]["busy_s"],
        "matching": {p: got["device"]["matching"][p]["seconds"] for p in patterns},
        "events": {p: got["device"]["matching"][p]["events"] for p in patterns},
        "device_kind": jax.devices()[0].device_kind,
    }
    with open(os.path.join(out_dir, "expected.json"), "w") as f:
        json.dump(expected, f, indent=1)
    print(json.dumps(expected), got["file_bytes"])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
