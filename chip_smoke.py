#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the recommendation template's main path once, through the entry
points a user calls, at ML-20M width (138,493 users x 26,744 items, rank
32, 10 iterations, 20M `rate` events made from ``--seed``):

    PIO_STORAGE_* (sqlite + localfs)  ->  pio app new  ->  bulk insert
    ->  pio train  ->  pio train again (compile-cache hit, new process)
    ->  pio deploy  ->  POST /queries.json  ->  GET /stop

and checks what comes out by the repo's own means: the served item lists
against a plain numpy top-N over the persisted factors, the full model's
train RMSE on a seeded sample, and a second small app trained by the same
``pio train`` against the float64 oracle (ops/als_reference.py).

One process per chip: this parent never imports JAX. Train and deploy
are child processes, one at a time; host-only helpers (bulk insert,
factor export) are children held to the CPU. The device block of the
last line is read from the train child's own log.

    python chip_smoke.py             one chip (what the driver runs)
    python chip_smoke.py --chips 4   ONLY the four-chip path and what it
                                     is compared with (sharded pio train
                                     vs a one-chip child, sharded int8
                                     deploy vs a one-device deploy)
    python chip_smoke.py --tiny      a rehearsal size for a machine with
                                     no chip; always ends "ok": false

Every stdout line is one JSON object. The last is
``{"ok": ..., "device": {"platform", "kind", "count"}}``; the exit code
is 0 only when it says ``"ok": true``.
"""

from __future__ import annotations

import argparse
import datetime as dt
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

FULL = dict(
    n_users=138_493, n_items=26_744, n_events=20_000_000, rank=32,
    iterations=10, rmse_sample=2_000_000,
    parity_users=3_000, parity_items=2_000, parity_events=150_000,
)
TINY = dict(
    n_users=3_000, n_items=800, n_events=120_000, rank=8,
    iterations=5, rmse_sample=20_000,
    parity_users=300, parity_items=200, parity_events=8_000,
)
REG = 0.05
MAIN_APP, PARITY_APP = "ml20m", "parity"
ENGINE_FACTORY = (
    "predictionio_tpu.models.recommendation.RecommendationEngineFactory"
)
WARM_NUM = 16  # ALSAlgorithmParams.warm_num default: top-k tiers warmed
COLD_NUM = 40  # above warm_num: the next pow2 tier compiles on this query
WARMUP_FAILED = "ALS warm-up compile failed"  # ops/als.py's error line

FAILURES: list = []
CHILDREN: list = []
# the driver allows 1200 s; the four-chip path is run by the builder
TIME_LIMIT_S = {1: 1150.0, 4: 1500.0}
DEADLINE = float("inf")  # set by main()


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def check(name: str, ok: bool, **detail) -> bool:
    emit(check=name, ok=bool(ok), **detail)
    if not ok:
        FAILURES.append(name)
    return bool(ok)


class PhaseFailed(Exception):
    pass


# --- data, made from the seed (numpy only) ---


def synth_ratings(n_users, n_items, n_events, seed):
    """MovieLens-20M-shaped synthetic ratings (the dataset itself is not
    in the image and the chip machine has no network): low-rank-plus-noise
    scores on a lognormal-activity x zipf-popularity long tail, snapped
    to ML-20M's 0.5-step 0.5..5.0 scale. Ids are popularity-ordered."""
    rng = np.random.default_rng(seed)
    k0 = 12
    U = (rng.standard_normal((n_users, k0)) / np.sqrt(k0)).astype(np.float32)
    V = (rng.standard_normal((n_items, k0)) / np.sqrt(k0)).astype(np.float32)
    u_p = rng.lognormal(0, 1.1, n_users)
    u_p /= u_p.sum()
    i_p = 1.0 / np.arange(1, n_items + 1) ** 0.9
    i_p /= i_p.sum()
    u = rng.choice(n_users, size=n_events, p=u_p).astype(np.int32)
    i = rng.choice(n_items, size=n_events, p=i_p).astype(np.int32)
    raw = np.empty(n_events, np.float32)
    for s in range(0, n_events, 4_000_000):
        e = min(s + 4_000_000, n_events)
        raw[s:e] = np.einsum("nk,nk->n", U[u[s:e]], V[i[s:e]])
    scores = 3.0 + 1.3 * raw + 0.5 * rng.standard_normal(n_events)
    r = np.clip(np.round(scores * 2.0) / 2.0, 0.5, 5.0).astype(np.float32)
    return u, i, r


def parity_subset(u, i, r, size, seed):
    """The head of both popularity tails, small enough for the float64
    oracle (minutes at 20M, seconds here)."""
    sub = (u < size["parity_users"]) & (i < size["parity_items"])
    su, si, sr = u[sub], i[sub], r[sub]
    if len(su) > size["parity_events"]:
        keep = np.random.default_rng(seed + 1).choice(
            len(su), size=size["parity_events"], replace=False
        )
        keep.sort()
        su, si, sr = su[keep], si[keep], sr[keep]
    return su, si, sr


def user_name(v) -> str:
    return f"u{int(v):06d}"


def item_name(v) -> str:
    return f"i{int(v):05d}"


# --- children ---


def child_env(work, *, host_only=False, chips=None, rehearsal_devices=None):
    """Environment of a child. ``host_only`` children (bulk insert,
    export) are held to the CPU so they can never take the chip.
    ``chips`` narrows what a TPU child can see, through the runtime's own
    per-process visibility settings, before it imports JAX. On a CPU
    rehearsal (JAX_PLATFORMS=cpu in our own environment)
    ``rehearsal_devices`` virtual devices stand in for the chips."""
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env.update(
        PIO_LOG_FORMAT="json",
        PIO_FS_BASEDIR=os.path.join(work, "fs"),
        PIO_STORAGE_SOURCES_SQLITE_TYPE="sqlite",
        PIO_STORAGE_SOURCES_SQLITE_PATH=os.path.join(work, "pio.db"),
        PIO_STORAGE_SOURCES_LOCALFS_TYPE="localfs",
        PIO_STORAGE_SOURCES_LOCALFS_PATH=os.path.join(work, "models"),
        PIO_STORAGE_REPOSITORIES_METADATA_NAME="pio_meta",
        PIO_STORAGE_REPOSITORIES_METADATA_SOURCE="SQLITE",
        PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME="pio_event",
        PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE="SQLITE",
        PIO_STORAGE_REPOSITORIES_MODELDATA_NAME="pio_model",
        PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE="LOCALFS",
    )
    if host_only:
        env["JAX_PLATFORMS"] = "cpu"
        return env
    # JAX's own switch: its compiler module then logs every persistent
    # cache hit and miss by program name
    env["JAX_DEBUG_LOG_MODULES"] = "jax._src.compiler"
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        env["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count="
            f"{rehearsal_devices or 1}"
        )
    elif chips is not None:
        # the same settings `pio deploy --workers` hands its workers
        from predictionio_tpu.tools.cli import tpu_chip_env

        env.update(tpu_chip_env(chips))
    return env


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def remaining(timeout: float) -> float:
    """``timeout``, cut to what the smoke's own time limit leaves."""
    return max(1.0, min(timeout, DEADLINE - time.time()))


def run_child(name, cmd, env, work, timeout):
    """Run one child to its end; returns (seconds, combined output, spawn
    time). Raises PhaseFailed on a non-zero exit or a timeout."""
    log_path = os.path.join(work, f"{name}.log")
    t0 = time.time()
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(
            cmd, env=env, cwd=work, stdout=log, stderr=subprocess.STDOUT
        )
        CHILDREN.append(proc)
        try:
            rc = proc.wait(timeout=remaining(timeout))
        except subprocess.TimeoutExpired:
            stop_child(proc)
            rc = "timeout"
    seconds = time.time() - t0
    with open(log_path, errors="replace") as f:
        text = f.read()
    if rc != 0:
        emit(phase=name, failed=True, rc=rc, seconds=round(seconds, 2),
             tail=text[-3000:])
        raise PhaseFailed(f"{name}: exit {rc}")
    return seconds, text, t0


def stop_child(proc) -> None:
    if proc.poll() is not None:
        return
    proc.terminate()
    try:
        proc.wait(timeout=15)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait(timeout=15)


def pio(*args):
    return [sys.executable, "-m", "predictionio_tpu.tools.cli", *args]


def stage(name, *args):
    return [sys.executable, os.path.abspath(__file__), "--stage", name, *args]


PROBE = (
    "import json, jax\n"
    "d = jax.devices()\n"
    "ms = d[0].memory_stats() or {}\n"
    "print(json.dumps({'platform': d[0].platform,"
    " 'kind': d[0].device_kind, 'count': len(d),"
    " 'devices': [str(x) for x in d],"
    " 'bytes_limit': ms.get('bytes_limit'), 'jax': jax.__version__}))\n"
)


def probe_devices(work, env, name="probe"):
    """What a child that touches JAX finds — before anything long runs.
    The probe exits (releasing the chip) before the next child starts."""
    seconds, text, _ = run_child(
        name, [sys.executable, "-c", PROBE], env, work, timeout=300
    )
    found = json.loads(
        [ln for ln in text.splitlines() if ln.startswith("{")][-1]
    )
    emit(phase=name, seconds=round(seconds, 2), **found)
    return found


# --- reading a pio child's log ---


def log_records(text):
    """(json records, other lines) of a child's combined output."""
    records, other = [], []
    for line in text.splitlines():
        if line.startswith("{"):
            try:
                records.append(json.loads(line))
                continue
            except ValueError:
                pass
        other.append(line)
    return records, other


MESH_RE = re.compile(
    r"created \{'data': (\d+)\} on platform=(\w+) device_kind='([^']*)'"
)
PHASE_RE = re.compile(r"^\s*([\w:\-\[\]]+): ([0-9.]+)s( \[overlapped\])?$")
CACHE_RE = re.compile(
    r"(Persistent compilation cache hit|PERSISTENT COMPILATION CACHE MISS)"
    r" for '([^']+)'"
)


def parse_train(text, t_spawn):
    """Everything the smoke reads from one `pio train` child."""
    records, other = log_records(text)
    out = {
        "instance_id": None, "device": None, "phases": {}, "notes": None,
        "memory": None, "cache_dir": None, "factor_state": None,
        "cache_hits": [], "cache_misses": [], "to_device_s": None,
        "errors": [],
    }
    for line in other:
        m = re.search(r"Training completed\. Engine instance: (\S+)", line)
        if m:
            out["instance_id"] = m.group(1)
    for rec in records:
        msg = rec.get("message", "")
        # JAX's compiler records propagate to the root logger's JSON
        # handler (as well as to JAX's own stderr handler)
        m = CACHE_RE.search(msg)
        if m:
            kind = "cache_hits" if "hit" in m.group(1) else "cache_misses"
            out[kind].append(m.group(2))
        if rec.get("level") in ("ERROR", "CRITICAL"):
            out["errors"].append(msg[:300])
        m = MESH_RE.search(msg)
        if m and out["device"] is None:
            out["device"] = {
                "platform": m.group(2), "kind": m.group(3),
                "count": int(m.group(1)),
            }
            if rec.get("ts"):
                out["to_device_s"] = round(
                    dt.datetime.fromisoformat(rec["ts"]).timestamp()
                    - t_spawn, 2,
                )
        if msg.startswith("training phases:"):
            for line in msg.splitlines()[1:]:
                pm = PHASE_RE.match(line)
                if pm:
                    out["phases"][pm.group(1)] = float(pm.group(2))
                elif line.startswith("notes:"):
                    out["notes"] = line[len("notes: "):]
        if msg.startswith("memory after training: "):
            out["memory"] = json.loads(msg.split(": ", 1)[1])
        if msg.startswith("XLA compilation cache at "):
            out["cache_dir"] = msg[len("XLA compilation cache at "):]
        if msg.startswith("ALS: factor state resident as "):
            out["factor_state"] = msg[len("ALS: factor state resident as "):]
    return out


def cache_entries(cache_dir) -> int:
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    return sum(1 for n in os.listdir(cache_dir) if n.endswith("-cache"))


def pio_train(name, work, variant, env, timeout):
    """One `pio train -v` child: its phase line, and the checks every
    train must pass (ran on the accelerator, no refused warm-up compile,
    nothing logged at error level)."""
    seconds, text, t0 = run_child(
        name, pio("train", "-v", variant), env, work, timeout
    )
    got = parse_train(text, t0)
    peak = {
        k: v for k, v in (got["memory"] or {}).items()
        if k.endswith("peak_bytes_in_use")
    }
    emit(
        phase=name, seconds=round(seconds, 2),
        instance_id=got["instance_id"], device=got["device"],
        start_to_device_s=got["to_device_s"], phases_s=got["phases"],
        notes=got["notes"], peak_bytes_in_use=peak,
        memory=got["memory"], factor_state=got["factor_state"],
        compile_cache={
            "dir": got["cache_dir"],
            "hits": len(got["cache_hits"]),
            "misses": len(got["cache_misses"]),
            "hit_programs": sorted(set(got["cache_hits"])),
            "miss_programs": sorted(set(got["cache_misses"])),
        },
    )
    check(f"{name}:instance", bool(got["instance_id"]))
    check(
        f"{name}:ran_on_tpu",
        bool(got["device"]) and got["device"]["platform"] == "tpu",
        device=got["device"],
    )
    check(
        f"{name}:no_error_lines",
        not got["errors"] and WARMUP_FAILED not in text,
        errors=got["errors"][:5],
    )
    return got


def write_variant(work, name, app, size, seed, **algo_params):
    path = os.path.join(work, f"{name}.json")
    with open(path, "w") as f:
        json.dump(
            {
                "id": name, "version": "1",
                "engineFactory": ENGINE_FACTORY,
                "datasource": {"params": {"app_name": app}},
                "algorithms": [
                    {
                        "name": "als",
                        "params": {
                            "rank": size["rank"],
                            "num_iterations": size["iterations"],
                            "lambda_": REG, "seed": seed, **algo_params,
                        },
                    }
                ],
            },
            f,
        )
    return path


# --- the factors a train persisted, and the references over them ---


class Factors:
    def __init__(self, work, instance_id, n_users, n_items):
        d = os.path.join(work, "export", instance_id)
        self.X = np.load(os.path.join(d, "user_factors.npy"))
        self.Y = np.load(os.path.join(d, "item_factors.npy"))
        self.user_names = np.load(os.path.join(d, "user_names.npy"))
        self.item_names = np.load(os.path.join(d, "item_names.npy"))
        # names are u%06d / i%05d: the raw id is in the name
        self.user_ids = np.array([int(s[1:]) for s in self.user_names])
        self.item_ids = np.array([int(s[1:]) for s in self.item_names])
        self.row_of_user = np.full(n_users, -1, np.int64)
        self.row_of_user[self.user_ids] = np.arange(len(self.user_ids))
        self.row_of_item = np.full(n_items, -1, np.int64)
        self.row_of_item[self.item_ids] = np.arange(len(self.item_ids))
        self.row_of_item_name = {
            str(n): j for j, n in enumerate(self.item_names)
        }

    def rmse(self, u, i, r) -> float:
        ru, ri = self.row_of_user[u], self.row_of_item[i]
        if (ru < 0).any() or (ri < 0).any():
            raise PhaseFailed("a rated user or item has no factor row")
        total = 0.0
        for s in range(0, len(r), 500_000):
            e = slice(s, s + 500_000)
            pred = np.sum(self.X[ru[e]] * self.Y[ri[e]], axis=-1)
            total += float(np.sum((pred - r[e]).astype(np.float64) ** 2))
        return float(np.sqrt(total / len(r)))

    def topn(self, user_id, num):
        """Plain numpy top-N over the persisted factors: the full score
        row, a stable descending sort (lowest index wins ties). Returns
        (item names, the full score row)."""
        scores = self.X[self.row_of_user[user_id]] @ self.Y.T
        order = np.argsort(-scores, kind="stable")[:num]
        return [str(self.item_names[j]) for j in order], scores

    def lists_agree(self, a, b, scores):
        """Identical lists — exact ties aside: where they differ, the two
        items must carry exactly the same reference score. Returns
        (agree, largest score gap between the lists)."""
        if a == b:
            return True, 0.0
        rows = self.row_of_item_name
        if len(a) != len(b) or any(s not in rows for s in a + b):
            return False, float("inf")
        gap = float(np.max(np.abs(
            scores[[rows[s] for s in a]] - scores[[rows[s] for s in b]]
        )))
        return gap == 0.0, gap


def export_factors(work, instance_ids):
    seconds, _, _ = run_child(
        "export", stage("export", work, *instance_ids),
        child_env(work, host_only=True), work, timeout=300,
    )
    emit(phase="export_factors", seconds=round(seconds, 2),
         instances=list(instance_ids))


def finite_and_shaped(name, f, size, n_users_present, n_items_present):
    return check(
        f"{name}:factors_finite_and_shaped",
        f.X.shape == (n_users_present, size["rank"])
        and f.Y.shape == (n_items_present, size["rank"])
        and bool(np.isfinite(f.X).all()) and bool(np.isfinite(f.Y).all()),
        user_factors=list(f.X.shape), item_factors=list(f.Y.shape),
    )


# --- the server ---


def http_json(url, payload=None, headers=None, timeout=120):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data,
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        body = resp.read()
    return json.loads(body) if body[:1] in (b"{", b"[") else body.decode()


class Deployed:
    """One `pio deploy` child, from spawn to GET /stop."""

    def __init__(self, name, work, variant, instance_id, env, extra=()):
        self.name, self.port = name, free_port()
        self.url = f"http://localhost:{self.port}"
        self.log_path = os.path.join(work, f"{name}.log")
        self._log = open(self.log_path, "wb")
        self.t0 = time.time()
        self.proc = subprocess.Popen(
            pio(
                "deploy", "-v", variant, "--port", str(self.port),
                "--engine-instance-id", instance_id, *extra,
            ),
            env=env, cwd=work, stdout=self._log, stderr=subprocess.STDOUT,
        )
        CHILDREN.append(self.proc)

    def log_text(self):
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def wait_ready(self, timeout):
        deadline = time.time() + remaining(timeout)
        while time.time() < deadline:
            if self.proc.poll() is not None:
                emit(phase=self.name, failed=True, rc=self.proc.returncode,
                     tail=self.log_text()[-3000:])
                raise PhaseFailed(f"{self.name}: server exited")
            try:
                status = http_json(self.url + "/status.json", timeout=5)
                return time.time() - self.t0, status
            except (urllib.error.URLError, ConnectionError, OSError):
                time.sleep(0.5)
        emit(phase=self.name, failed=True, rc="never ready",
             tail=self.log_text()[-3000:])
        raise PhaseFailed(f"{self.name}: never became ready")

    def query(self, user, num, trace_id=None):
        t0 = time.perf_counter()
        body = http_json(
            self.url + "/queries.json", {"user": user, "num": num},
            headers={"X-PIO-Trace-Id": trace_id} if trace_id else None,
        )
        return time.perf_counter() - t0, body

    def metrics(self):
        return http_json(self.url + "/metrics")

    def stop(self):
        try:
            http_json(self.url + "/stop", timeout=10)
            self.proc.wait(timeout=60)
        except Exception:
            pass
        stop_child(self.proc)
        self._log.close()


def metric_samples(text, family):
    """``{label string: value}`` of one family in a /metrics scrape."""
    out = {}
    for line in text.splitlines():
        if line.startswith(family + "{") or line.startswith(family + " "):
            head, _, value = line.rpartition(" ")
            out[head[len(family):]] = float(value)
    return out


def served_items(body):
    return [s["item"] for s in body.get("itemScores", [])]


def served_score_error(body, factors, ref_scores) -> float:
    """Largest |served score - numpy score of the same item|."""
    rows = factors.row_of_item_name
    pairs = [
        (s["score"], ref_scores[rows[s["item"]]])
        for s in body.get("itemScores", []) if s["item"] in rows
    ]
    return max((abs(a - float(b)) for a, b in pairs), default=float("inf"))


def pick_users(factors, seed, n):
    rng = np.random.default_rng(seed + 2)
    return [int(v) for v in rng.choice(factors.user_ids, size=n, replace=False)]


def device_labels(status):
    return status.get("deviceLedger", {}).get("breakdown", {})


# --- the one-chip smoke ---


def make_and_load(size, work, seed, with_parity):
    """Seeded events into the store, through `pio app new` and the bulk
    insert child. Returns the main app's (u, i, r), the parity app's (or
    None), and the counts of distinct users and items."""
    t0 = time.time()
    ratings = {MAIN_APP: synth_ratings(
        size["n_users"], size["n_items"], size["n_events"], seed
    )}
    if with_parity:
        ratings[PARITY_APP] = parity_subset(*ratings[MAIN_APP], size, seed)
    for app, columns in ratings.items():
        for column, values in zip("uir", columns):
            np.save(os.path.join(work, f"{app}_{column}.npy"), values)
    u, i, _ = ratings[MAIN_APP]
    distinct = (int(len(np.unique(u))), int(len(np.unique(i))))
    emit(phase="make_data", seconds=round(time.time() - t0, 2), seed=seed,
         events={app: len(cols[2]) for app, cols in ratings.items()},
         distinct_users=distinct[0], distinct_items=distinct[1])

    host = child_env(work, host_only=True)
    for app in ratings:
        run_child(f"app_new_{app}", pio("app", "new", app), host, work, 120)
    seconds, text, _ = run_child(
        "load", stage("load", work, *ratings), host, work, timeout=600
    )
    emit(phase="bulk_insert", seconds=round(seconds, 2),
         apps=[json.loads(ln) for ln in text.splitlines()
               if ln.startswith("{")])
    return ratings[MAIN_APP], ratings.get(PARITY_APP), distinct


def one_chip(args, size, work):
    seed = args.seed
    (u, i, r), (su, si, sr), distinct = make_and_load(
        size, work, seed, with_parity=True
    )
    chip = child_env(work)
    variant = write_variant(work, "engine", MAIN_APP, size, seed)
    first = pio_train("train", work, variant, chip, timeout=900)
    entries_after_first = cache_entries(first["cache_dir"])
    second = pio_train("train_again", work, variant, chip, timeout=600)
    entries_after_second = cache_entries(second["cache_dir"])
    loop_program = "jit__run_iterations"
    check(
        "compile_cache:second_process_hit_the_loop",
        loop_program in second["cache_hits"]
        and loop_program not in second["cache_misses"],
        dir=second["cache_dir"],
        entries_after_first=entries_after_first,
        entries_after_second=entries_after_second,
        compile_s_first=first["phases"].get("stream:compile"),
        compile_s_second=second["phases"].get("stream:compile"),
        second_hits=len(second["cache_hits"]),
        second_misses=len(second["cache_misses"]),
    )
    expected_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        HERE, ".jax_cache"
    )
    check(
        "compile_cache:placed_from_outside",
        first["cache_dir"] == expected_dir == second["cache_dir"],
        expected=expected_dir, reported=first["cache_dir"],
    )
    check(
        "train:took_the_streaming_accelerator_path",
        "stream:device-loop" in first["phases"]
        and "pack_cache=miss" in (first["notes"] or ""),
        notes=first["notes"],
    )

    parity_variant = write_variant(work, "parity", PARITY_APP, size, seed)
    parity = pio_train("train_parity", work, parity_variant, chip, timeout=600)

    ids = [first["instance_id"], second["instance_id"], parity["instance_id"]]
    export_factors(work, ids)
    f1 = Factors(work, ids[0], size["n_users"], size["n_items"])
    f2 = Factors(work, ids[1], size["n_users"], size["n_items"])
    fp = Factors(work, ids[2], size["n_users"], size["n_items"])
    finite_and_shaped("train", f1, size, *distinct)

    # the full-width model on a seeded sample of its own training pairs
    t0 = time.time()
    pick = np.random.default_rng(seed + 3).choice(
        len(r), size=min(size["rmse_sample"], len(r)), replace=False
    )
    rmse_train = f1.rmse(u[pick], i[pick], r[pick])
    check(
        "train:rmse_on_sample_finite",
        bool(np.isfinite(rmse_train)) and rmse_train < 1.5,
        rmse=rmse_train, pairs=len(pick),
        seconds=round(time.time() - t0, 2),
        second_train_factors_equal=bool(
            np.array_equal(f1.X, f2.X) and np.array_equal(f1.Y, f2.Y)
        ),
    )

    # the small app against the float64 oracle, on identical data: ids
    # are zero-padded, so the store's sorted-name dense order is the
    # oracle's integer order and the row-indexed init lines up
    from predictionio_tpu.ops.als_reference import (
        rmse_reference,
        train_als_reference,
    )

    t0 = time.time()
    uniq_u, su_d = np.unique(su, return_inverse=True)
    uniq_i, si_d = np.unique(si, return_inverse=True)
    X_ref, Y_ref = train_als_reference(
        su_d, si_d, sr, len(uniq_u), len(uniq_i), rank=size["rank"],
        iterations=size["iterations"], reg=REG, reg_mode="weighted",
        seed=seed,
    )
    rmse_oracle = rmse_reference(X_ref, Y_ref, su_d, si_d, sr)
    rmse_chip = fp.rmse(su, si, sr)
    check(
        "parity:rmse_within_1e-3_of_float64_oracle",
        abs(rmse_chip - rmse_oracle) <= 1e-3,
        rmse_pio_train=rmse_chip, rmse_oracle=rmse_oracle,
        difference=abs(rmse_chip - rmse_oracle), events=len(sr),
        oracle_seconds=round(time.time() - t0, 2),
    )
    check("no_jax_in_parent", "jax" not in sys.modules)

    server = Deployed("deploy", work, variant, ids[0], chip)
    try:
        ready_s, status = server.wait_ready(timeout=600)
        labels = device_labels(status)
        emit(phase="deploy", ready_seconds=round(ready_s, 2),
             engine_instance=status.get("engineInstanceId"),
             device_ledger=labels)
        check(
            "deploy:server_holds_factors_on_tpu",
            any(
                lbl.upper().startswith("TPU") and "serving-factors" in comps
                for lbl, comps in labels.items()
            ),
            device_ledger=labels,
        )
        users = pick_users(f1, seed, 6)
        plan = [(uid, 10) for uid in users[:5]] + [(users[5], COLD_NUM)]
        for n, (uid, num) in enumerate(plan):
            seconds, body = server.query(
                user_name(uid), num, trace_id=f"smoke-{n}"
            )
            ref_items, ref_scores = f1.topn(uid, num)
            same, gap = f1.lists_agree(
                served_items(body), ref_items, ref_scores
            )
            check(
                f"query_{n}:items_equal_numpy_reference", same,
                user=user_name(uid), num=num, seconds=round(seconds, 4),
                served=served_items(body)[:10], reference=ref_items[:10],
                score_gap=gap,
                score_error=served_score_error(body, f1, ref_scores),
            )
        scrape = server.metrics()
        cold = metric_samples(scrape, "pio_cold_compiles_total")
        ledger = metric_samples(scrape, "pio_device_ledger_bytes")
        check(
            "deploy:cold_compile_seen_and_attributed",
            sum(v for k, v in cold.items() if 'site="serving"' in k) >= 1,
            cold_compiles=cold,
            note=f"num={COLD_NUM} is above warm_num={WARM_NUM}",
        )
        check(
            "deploy:ledger_metric_names_a_tpu_device",
            any("TPU" in k.upper() and v > 0 for k, v in ledger.items()),
            pio_device_ledger_bytes=ledger,
        )
    finally:
        server.stop()
    check(
        "deploy:no_error_lines",
        '"level": "ERROR"' not in server.log_text(),
    )
    return first["device"]


# --- the four-chip path, and what it is compared with ---


def four_chips(args, size, work):
    seed = args.seed
    (u, i, r), _, distinct = make_and_load(
        size, work, seed, with_parity=False
    )

    # what the one-chip comparison child will see, before anything long
    one = child_env(work, chips=[0], rehearsal_devices=1)
    narrowed = probe_devices(work, one, name="probe_one_chip")
    if not check(
        "four_chips:visibility_settings_narrow_a_child_to_one_chip",
        narrowed["count"] == 1, devices=narrowed["devices"],
    ):
        raise PhaseFailed("a child could not be restricted to one chip")

    # int8 residency: the precision is stored with the instance and only
    # serving reads it, so one instance serves both deploys below
    variant = write_variant(
        work, "engine", MAIN_APP, size, seed, precision="int8"
    )
    all_four = child_env(work, rehearsal_devices=4)
    sharded = pio_train("train_four_chips", work, variant, all_four, 900)
    single = pio_train("train_one_chip", work, variant, one, 900)
    check(
        "four_chips:train_meshed_over_four", sharded["device"] is not None
        and sharded["device"]["count"] == 4, device=sharded["device"],
    )
    check(
        "four_chips:comparison_saw_one_chip", single["device"] is not None
        and single["device"]["count"] == 1, device=single["device"],
    )
    per_device = re.findall(r"'([^']+)': (\d+)", sharded["factor_state"] or "")
    check(
        "four_chips:factor_matrix_spread_over_four_devices",
        len(per_device) == 4 and len({b for _, b in per_device}) == 1,
        factor_state=sharded["factor_state"],
        peak_bytes_in_use={
            k: v for k, v in (sharded["memory"] or {}).items()
            if k.endswith("peak_bytes_in_use")
        },
    )

    ids = [sharded["instance_id"], single["instance_id"]]
    export_factors(work, ids)
    f4 = Factors(work, ids[0], size["n_users"], size["n_items"])
    f1 = Factors(work, ids[1], size["n_users"], size["n_items"])
    finite_and_shaped("train_four_chips", f4, size, *distinct)
    pick = np.random.default_rng(seed + 3).choice(
        len(r), size=min(size["rmse_sample"], len(r)), replace=False
    )
    rmse4 = f4.rmse(u[pick], i[pick], r[pick])
    rmse1 = f1.rmse(u[pick], i[pick], r[pick])
    # rows align by NAME (the two trains build their indexes separately)
    x1 = f1.X[f1.row_of_user[f4.user_ids]]
    y1 = f1.Y[f1.row_of_item[f4.item_ids]]
    check(
        "four_chips:sample_rmse_equals_one_chip_to_1e-3",
        abs(rmse4 - rmse1) <= 1e-3,
        rmse_four_chips=rmse4, rmse_one_chip=rmse1,
        difference=abs(rmse4 - rmse1), pairs=len(pick),
        largest_factor_difference={
            "user": float(np.max(np.abs(f4.X - x1))),
            "item": float(np.max(np.abs(f4.Y - y1))),
            "user_factor_scale": float(np.max(np.abs(x1))),
        },
        toy_size_tolerance="rtol 2e-4, atol 2e-5 (__graft_entry__.py)",
    )
    check("no_jax_in_parent", "jax" not in sys.modules)

    users = pick_users(f4, seed, 6)
    plan = [(uid, 10) for uid in users[:5]] + [(users[5], WARM_NUM)]
    answers = {}
    catalog_bytes = len(f4.item_ids) * (size["rank"] + 4 + 4)
    for name, devices in (("deploy_four_devices", "0,1,2,3"),
                          ("deploy_one_device", "0")):
        server = Deployed(
            name, work, variant, ids[0], all_four,
            extra=("--serving-device", devices),
        )
        try:
            ready_s, status = server.wait_ready(timeout=600)
            labels = device_labels(status)
            emit(phase=name, ready_seconds=round(ready_s, 2),
                 serving_precision=status.get("servingPrecision"),
                 device_ledger=labels)
            check(
                f"{name}:server_holds_catalog_on_tpu",
                any(
                    lbl.upper().startswith("TPU")
                    and "recommendation/int8" in comps
                    for lbl, comps in labels.items()
                ),
                device_ledger=labels,
            )
            answers[name] = []
            for n, (uid, num) in enumerate(plan):
                seconds, body = server.query(user_name(uid), num)
                answers[name].append(served_items(body))
                emit(phase=f"{name}:query_{n}", user=user_name(uid),
                     num=num, seconds=round(seconds, 4),
                     served=served_items(body)[:10])
            if devices != "0":
                resident = {
                    lbl: comps["recommendation/int8"]
                    for lbl, comps in labels.items()
                    if "recommendation/int8" in comps
                }
                # one copy of the catalog over four devices: the ledger
                # counts physical bytes, so a replicated catalog reads 4x
                check(
                    "four_chips:int8_catalog_sharded_not_replicated",
                    len(resident) == 1
                    and next(iter(resident)).endswith("x4")
                    and catalog_bytes <= next(iter(resident.values()))
                    < 1.5 * catalog_bytes,
                    resident=resident, one_copy_bytes=catalog_bytes,
                    server_log=[
                        rec["message"]
                        for rec in log_records(server.log_text())[0]
                        if "ItemRetriever[" in rec.get("message", "")
                    ][:1],
                )
        finally:
            server.stop()
    for n, (uid, num) in enumerate(plan):
        four, one_dev = (answers[k][n] for k in
                         ("deploy_four_devices", "deploy_one_device"))
        ref_items, ref_scores = f4.topn(uid, num)
        same, gap = f4.lists_agree(four, one_dev, ref_scores)
        check(
            f"four_chips:query_{n}:four_devices_equal_one_device", same,
            user=user_name(uid), num=num, four=four[:10], one=one_dev[:10],
            score_gap=gap, equals_numpy_reference=four == ref_items,
        )
    return sharded["device"]


# --- host-only stages (children of this script, held to the CPU) ---


def stage_load(work, *app_names):
    """Bulk-insert seeded `rate` events: ``insert_columns_encoded`` is the
    vectorized path `insert_columns` factorizes into — the ids arrive as
    integer codes here, so the 20M-string factorization is skipped."""
    from predictionio_tpu.data.storage import get_storage

    storage = get_storage()
    events = storage.get_l_events()
    for app_name in app_names:
        u, i, r = (
            np.load(os.path.join(work, f"{app_name}_{column}.npy"))
            for column in "uir"
        )
        app = storage.get_meta_data_apps().get_by_name(app_name)
        t0 = time.time()
        present_u, codes_u = np.unique(u, return_inverse=True)
        present_i, codes_i = np.unique(i, return_inverse=True)
        n = events.insert_columns_encoded(
            app.id, event="rate", entity_type="user",
            target_entity_type="item",
            entity_names=[user_name(v) for v in present_u],
            entity_codes=codes_u.astype(np.int32),
            target_names=[item_name(v) for v in present_i],
            target_codes=codes_i.astype(np.int32),
            values=r,
        )
        print(json.dumps({"app": app_name, "events": int(n),
                          "seconds": round(time.time() - t0, 2)}), flush=True)


def stage_export(work, *instance_ids):
    """Persisted factors and their id indexes, as plain arrays."""
    from predictionio_tpu.data.storage import get_storage
    from predictionio_tpu.utils.serialize import loads_model

    models = get_storage().get_model_data_models()
    for instance_id in instance_ids:
        (model,) = loads_model(models.get(instance_id).models)
        d = os.path.join(work, "export", instance_id)
        os.makedirs(d, exist_ok=True)
        np.save(os.path.join(d, "user_factors.npy"), model.arrays.user_factors)
        np.save(os.path.join(d, "item_factors.npy"), model.arrays.item_factors)
        for side, index in (("user", model.user_index),
                            ("item", model.item_index)):
            names = np.empty(len(index), object)
            for name, row in index.items():
                names[row] = name
            np.save(os.path.join(d, f"{side}_names.npy"), names.astype("U"))


def keep_logs(work) -> None:
    """The children's logs outlive the scratch directory: under
    ``chiprun_out/`` (gitignored), which the chip tool brings back."""
    dest = os.path.join(HERE, "chiprun_out", "chip_smoke")
    try:
        os.makedirs(dest, exist_ok=True)
        for name in os.listdir(work):
            if name.endswith(".log"):
                with open(os.path.join(work, name), "rb") as src:
                    src.seek(0, os.SEEK_END)
                    src.seek(max(0, src.tell() - 2 * 2**20))
                    tail = src.read()
                with open(os.path.join(dest, name), "wb") as out:
                    out.write(tail)
    except OSError as e:
        emit(phase="keep_logs", failed=True, error=repr(e))


# --- entry ---


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=41)
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal size; always ends ok=false")
    ap.add_argument("--stage", help=argparse.SUPPRESS)
    ap.add_argument("stage_args", nargs="*", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.stage:
        {"load": stage_load, "export": stage_export}[args.stage](
            *args.stage_args
        )
        return 0

    global DEADLINE
    size = TINY if args.tiny else FULL
    device = None
    work = None
    t_start = time.time()
    DEADLINE = t_start + TIME_LIMIT_S[args.chips]

    def on_sigterm(signum, frame):  # leave nothing running
        raise SystemExit(143)

    signal.signal(signal.SIGTERM, on_sigterm)
    try:
        if not os.path.isdir(os.path.join(HERE, "predictionio_tpu")):
            raise PhaseFailed("predictionio_tpu is not beside chip_smoke.py")
        work = tempfile.mkdtemp(prefix="chip_smoke_")
        emit(phase="start", chips=args.chips, seed=args.seed,
             widths={k: size[k] for k in ("n_users", "n_items", "rank",
                                          "iterations", "n_events")},
             cut=("tiny rehearsal: widths and events cut" if args.tiny
                  else None), work=work)
        found = probe_devices(work, child_env(work))
        device = {k: found[k] for k in ("platform", "kind", "count")}
        on_tpu = found["platform"] == "tpu"
        check("probe:platform_is_tpu", on_tpu, platform=found["platform"])
        if not args.tiny:
            if not on_tpu:
                raise PhaseFailed("JAX found no TPU")
            if found["count"] != args.chips:
                raise PhaseFailed(
                    f"--chips {args.chips} needs exactly {args.chips} "
                    f"device(s), JAX found {found['count']}"
                )
        trained_on = (four_chips if args.chips == 4 else one_chip)(
            args, size, work
        )
        device = trained_on or device
    except PhaseFailed as e:
        FAILURES.append(str(e))
    except Exception as e:  # a smoke reports its own faults as a failure
        import traceback

        emit(phase="smoke", failed=True, error=repr(e),
             traceback=traceback.format_exc()[-3000:])
        FAILURES.append(repr(e))
    finally:
        for proc in CHILDREN:
            stop_child(proc)
        if work:
            keep_logs(work)
            shutil.rmtree(work, ignore_errors=True)
    if args.tiny:
        emit(rehearsal="tiny", checks_passed_platform_aside=not [
            f for f in FAILURES if "tpu" not in f.lower()
        ], note="a rehearsal never ends ok=true: widths are cut")
        FAILURES.append("tiny rehearsal")
    emit(phase="end", seconds=round(time.time() - t_start, 2),
         failures=FAILURES)
    ok = not FAILURES and bool(device) and device["platform"] == "tpu"
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
