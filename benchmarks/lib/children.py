"""Children of the harness, and what it reads from them.

The parent never imports JAX: `pio train` and `pio deploy` are child
processes, one at a time on the chip; host-only helpers are children
held to the CPU. Copied from `chip_smoke.py` (PR 21), which stays as
it is: `child_env`, `run_child`, `stop_child`, `parse_train`,
`cache_entries`, the deploy wrapper and `metric_samples`.
"""

from __future__ import annotations

import datetime as dt
import json
import os
import re
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CHILDREN: list = []

# The lines of the program's log that the harness keys on, all in one
# place (with the patterns of ``parse_train`` below): a reworded line
# shows here, as a train counted failed or a set-up that times out.
WARMUP_FAILED = "ALS warm-up compile failed"  # ops/als.py's error line
DEVICE_LINE = "on platform="  # `pio train` holds the chip (MESH_RE's line)
COMPLETED_LINE = 'completed"'  # the instance's record says so: model persisted
UPGRADE_CHECK_LINE = "upgrade check:"  # the server's, once, 10 s after it listens


class CellFailed(Exception):
    """The run cannot give a result line (no chip, a child died, ...)."""


def say(**fields) -> None:
    """Progress: one JSON object a line, before the result line."""
    print(json.dumps(fields), flush=True)


def cache_dir() -> str:
    """The compile cache: where the environment says, else at a fixed
    path inside the checkout (the path is part of the cache's key)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache"
    )


def child_env(work, *, host_only=False, chips=1):
    """Environment of a child. ``host_only`` children (bulk insert,
    export, trace reduction) are held to the CPU so they can never take
    the chip. A child that holds the chip sees every chip of the
    machine; in a rehearsal on the CPU it sees ``chips`` virtual
    devices, the cell's own count, so that a cell over several chips
    runs its sharded path there too."""
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("BENCH_RUN", None)  # the driver's own; nothing here reads it
    # Each repository has a source of its own, as upstream's
    # conf/pio-env.sh.template lays a deployment out. Set-up children run
    # side by side (an instance written while a store is bulk-loaded):
    # sqlite has one write lock a file, and a writer that waits for it
    # longer than the client's busy timeout fails, so no two of them may
    # write one file. A source's name holds no underscore.
    env.update(
        TMPDIR=work,  # the profile spool and every temp file stay in here
        PIO_LOG_FORMAT="json",
        PIO_FS_BASEDIR=os.path.join(work, "fs"),
        PIO_STORAGE_SOURCES_SQLMETA_TYPE="sqlite",
        PIO_STORAGE_SOURCES_SQLMETA_PATH=os.path.join(work, "meta.db"),
        PIO_STORAGE_SOURCES_SQLEVENTS_TYPE="sqlite",
        PIO_STORAGE_SOURCES_SQLEVENTS_PATH=os.path.join(work, "events.db"),
        PIO_STORAGE_SOURCES_LOCALFS_TYPE="localfs",
        PIO_STORAGE_SOURCES_LOCALFS_PATH=os.path.join(work, "models"),
        PIO_STORAGE_REPOSITORIES_METADATA_NAME="pio_meta",
        PIO_STORAGE_REPOSITORIES_METADATA_SOURCE="SQLMETA",
        PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME="pio_event",
        PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE="SQLEVENTS",
        PIO_STORAGE_REPOSITORIES_MODELDATA_NAME="pio_model",
        PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE="LOCALFS",
    )
    if host_only:
        env["JAX_PLATFORMS"] = "cpu"
        return env
    # JAX's own switch: its compiler module then logs every persistent
    # cache hit and miss by program name
    env["JAX_DEBUG_LOG_MODULES"] = "jax._src.compiler"
    if os.environ.get("JAX_PLATFORMS") == "cpu":  # a rehearsal
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    return env


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def stop_child(proc) -> None:
    if proc.poll() is not None:
        return
    proc.terminate()
    try:
        proc.wait(timeout=15)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait(timeout=15)


def stop_all() -> None:
    for proc in CHILDREN:
        stop_child(proc)


def last_line(text) -> str:
    """The last non-empty line of a child's output: of a child that died
    of an exception the traceback's last, the error and its message; of
    the program's JSON log, the record's message."""
    lines = [ln.strip() for ln in text.splitlines() if ln.strip()]
    if not lines:
        return "(no output)"
    for rec in json_lines(lines[-1]):
        lines += str(rec.get("message", "")).split("\n")
    return [ln for ln in lines if ln.strip()][-1].strip()[:300]


def run_child(name, cmd, env, work, timeout, watch=None):
    """Run one child to its end; returns (seconds, combined output, spawn
    time). Raises CellFailed on a non-zero exit or a timeout, with the
    child's last line of output as the reason. ``watch``
    maps a name to a piece of text: the harness's own clock is read when
    that text first shows in the child's output, into ``watch[name]``."""
    log_path = os.path.join(work, f"{name}.log")
    waiting = dict(watch or {})
    t0 = time.time()
    with open(log_path, "wb") as log, open(log_path, "rb") as seen:
        proc = subprocess.Popen(
            cmd, env=env, cwd=work, stdout=log, stderr=subprocess.STDOUT
        )
        CHILDREN.append(proc)
        tail = b""
        while proc.poll() is None:
            if time.time() - t0 > timeout:
                stop_child(proc)
                break
            if waiting:
                tail = tail[-200:] + seen.read()
                for key, text in list(waiting.items()):
                    if text.encode() in tail:
                        watch[key] = time.time()
                        del waiting[key]
            time.sleep(0.01)
        rc = proc.returncode if time.time() - t0 <= timeout else "timeout"
    for key in waiting:
        watch[key] = None
    seconds = time.time() - t0
    with open(log_path, errors="replace") as f:
        text = f.read()
    if rc != 0:
        say(phase=name, failed=True, rc=rc, seconds=round(seconds, 2),
            tail=text[-3000:])
        raise CellFailed(f"{name}: exit {rc}: {last_line(text)}")
    return seconds, text, t0


def pio(*args):
    return [sys.executable, "-m", "predictionio_tpu.tools.cli", *args]


def stage(name, *args):
    """A host-side helper of the benchmark, as a child."""
    return [sys.executable, os.path.join(BENCH, "lib", "stages.py"), name,
            *[str(a) for a in args]]


def json_lines(text):
    out = []
    for line in text.splitlines():
        if line.startswith("{"):
            try:
                out.append(json.loads(line))
            except ValueError:
                pass
    return out


# --- reading a pio child's log ---

MESH_RE = re.compile(
    r"created \{'data': (\d+)\} on platform=(\w+) device_kind='([^']*)'"
)
PHASE_RE = re.compile(r"^\s*([\w:\-\[\]]+): ([0-9.]+)s( \[overlapped\])?$")
CACHE_RE = re.compile(
    r"(Persistent compilation cache hit|PERSISTENT COMPILATION CACHE MISS)"
    r" for '([^']+)'"
)


def record_time(rec):
    return dt.datetime.fromisoformat(rec["ts"]).timestamp()


def device_of(text):
    """(device dict, unix time of the line) from a pio child's log."""
    for rec in json_lines(text):
        m = MESH_RE.search(rec.get("message", ""))
        if m:
            return {
                "platform": m.group(2), "kind": m.group(3),
                "count": int(m.group(1)),
            }, (record_time(rec) if rec.get("ts") else None)
    return None, None


def parse_train(text, t_spawn):
    """Everything the harness reads from one `pio train` child."""
    out = {
        "instance_id": None, "phases": {}, "notes": None, "memory": None,
        "cache_hits": [], "cache_misses": [], "errors": [], "marks": {},
        "log": {},
    }
    out["device"], t_device = device_of(text)
    out["log"]["start_to_device_s"] = (
        None if t_device is None else t_device - t_spawn
    )
    for line in text.splitlines():
        m = re.search(r"Training completed\. Engine instance: (\S+)", line)
        if m and not line.startswith("{"):
            out["instance_id"] = m.group(1)
    for rec in json_lines(text):
        msg = rec.get("message", "")
        m = CACHE_RE.search(msg)
        if m:
            kind = "cache_hits" if "hit" in m.group(1) else "cache_misses"
            out[kind].append(m.group(2))
        if rec.get("level") in ("ERROR", "CRITICAL"):
            out["errors"].append(msg[:300])
        if msg.startswith("training phases:"):
            out["marks"]["phases_logged"] = record_time(rec)
            for line in msg.splitlines()[1:]:
                pm = PHASE_RE.match(line)
                if pm:
                    out["phases"][pm.group(1)] = float(pm.group(2))
                elif line.startswith("notes:"):
                    out["notes"] = line[len("notes: "):]
        if msg.startswith("memory after training: "):
            out["memory"] = json.loads(msg.split(": ", 1)[1])
        if msg.startswith("writing jax profiler trace to"):
            out["marks"]["trace_start"] = record_time(rec)
    if WARMUP_FAILED in text:
        out["errors"].append(WARMUP_FAILED)
    return out


def cache_entries(path=None) -> int:
    path = path or cache_dir()
    if not os.path.isdir(path):
        return 0
    return sum(1 for n in os.listdir(path) if n.endswith("-cache"))


# --- the server ---


def http_json(url, payload=None, timeout=120):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        body = resp.read()
    return json.loads(body) if body[:1] in (b"{", b"[") else body.decode()


class Deployed:
    """One `pio deploy` child, from spawn to GET /stop."""

    def __init__(self, name, work, variant, instance_id, env, extra=()):
        self.name, self.port = name, free_port()
        self.url = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(work, f"{name}.log")
        self._log = open(self.log_path, "wb")
        self.t0 = time.time()
        self.proc = subprocess.Popen(
            pio(
                "deploy", "-v", variant, "--ip", "127.0.0.1",
                "--port", str(self.port),
                "--engine-instance-id", instance_id, *extra,
            ),
            env=env, cwd=work, stdout=self._log, stderr=subprocess.STDOUT,
        )
        CHILDREN.append(self.proc)

    def log_text(self):
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def wait_ready(self, timeout):
        deadline = time.time() + timeout
        while time.time() < deadline:
            if self.proc.poll() is not None:
                say(phase=self.name, failed=True, rc=self.proc.returncode,
                    tail=self.log_text()[-3000:])
                raise CellFailed(
                    f"{self.name}: exited: {last_line(self.log_text())}")
            try:
                status = http_json(self.url + "/status.json", timeout=5)
                return time.time() - self.t0, status
            except (urllib.error.URLError, ConnectionError, OSError):
                time.sleep(0.25)
        say(phase=self.name, failed=True, rc="never ready",
            tail=self.log_text()[-3000:])
        raise CellFailed(
            f"{self.name}: never became ready: {last_line(self.log_text())}")

    def metrics(self):
        return http_json(self.url + "/metrics")

    def stop(self):
        try:
            http_json(self.url + "/stop", timeout=10)
            self.proc.wait(timeout=60)
        except Exception:  # a boundary: the child is ended either way
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


LABEL_RE = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def labels_of(label_string):
    """``{name: value}`` of one sample's label string (values as they
    are rendered, escapes and all)."""
    return dict(LABEL_RE.findall(label_string))


def device_memory(scrape):
    """The device's memory at a scrape, for the result line's ``device``.

    Each device's own ``bytes_in_use`` (``pio_device_memory_bytes``, the
    server's ``memory_stats()`` at every scrape) in device order is
    ``memory_by_device``, and the fullest device's is
    ``memory_peak_bytes``: a table sharded over four chips is a quarter
    of it on each. ``ledger_bytes`` is the residency ledger's total over
    every device. The ledger cannot stand in for a device: a sharded
    buffer is one sample under its span's label, holding the whole.

    Where the backend gives no device stats (the CPU), the peak is the
    ledger + drift reading, ``memory_source`` says so, and nothing is
    read by device. That reading is given beside the other in every
    case (``ledger_plus_drift_bytes``): on one chip it is the device's
    ``bytes_in_use`` plus the ledger's entries on the host, which
    ``ledger_host_bytes`` names by component."""
    ledger = metric_samples(scrape, "pio_device_ledger_bytes")
    total = sum(ledger.values())
    drifts = metric_samples(scrape, "pio_device_ledger_drift_bytes").values()
    host = {}
    for label_string, value in ledger.items():
        labels = labels_of(label_string)
        if labels.get("device") == "host" and value:
            name = labels["component"]
            host[name] = host.get(name, 0) + int(value)
    out = {"ledger_bytes": int(total),
           "ledger_plus_drift_bytes": int(max([total] + [
               total + d for d in drifts])),
           "ledger_host_bytes": host}
    in_use = {}
    for label_string, value in metric_samples(
            scrape, "pio_device_memory_bytes").items():
        labels = labels_of(label_string)
        if labels.get("stat") == "bytes_in_use":
            in_use[int(labels["device"])] = int(value)
    if not in_use:
        out.update(memory_peak_bytes=out["ledger_plus_drift_bytes"],
                   memory_source="ledger+drift: no device stats")
        return out
    by_device = [in_use[d] for d in sorted(in_use)]
    out.update(memory_peak_bytes=max(by_device), memory_by_device=by_device,
               memory_source="bytes_in_use")
    return out
