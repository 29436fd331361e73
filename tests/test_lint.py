"""Tier-1 source lints: ban new ``id(...)``-keyed caches, and ban
blocking calls inside ``async def`` coroutines in ``api/``.

The bug class (PR 1's markov_chain stale-mesh fix): keying a cache or
registry by ``id(obj)`` silently aliases entries when the object dies
and CPython reuses its address — a later, unrelated object then HITS the
dead object's entry. The sanctioned idiom is a ``weakref.ref`` held in
the entry and compared by identity at lookup (see
``ops/streaming.py::_cache_get`` and ``e2/markov_chain.py``).

This test greps the package for ``id(`` and fails on any occurrence not
in the reviewed allowlist below. If you are adding one: either switch to
the weakref-identity idiom, or — if the keyed objects provably outlive
every lookup (e.g. grouping items of ONE in-flight batch) — add the
line to the allowlist with a justification in your PR.
"""

import re
from pathlib import Path

PACKAGE = Path(__file__).resolve().parent.parent / "predictionio_tpu"

# \bid\( — won't match foo_id( / event_id( (the preceding word char
# kills the boundary), but catches id(x) used as a key anywhere,
# including docstrings that *recommend* it
_ID_CALL = re.compile(r"\bid\(")

# (relative path, stripped line) pairs reviewed as safe or as prose
# ABOUT the bug class. Keep this list short and justified:
ALLOWED = {
    # prose documenting why id() keys are forbidden
    (
        "ops/streaming.py",
        "# identity, not id(): the weakref keeps a dead DAO's entry from",
    ),
    (
        "e2/markov_chain.py",
        "object identity: an ``id(mesh)`` key could collide when a dead",
    ),
    (
        "data/storage/columnar.py",
        "compared by IDENTITY, never by a reusable ``id()``);",
    ),
    # groups items of ONE in-flight micro-batch; every keyed object is a
    # live strong reference in the same local list, so no id can alias
    (
        "api/engine_server.py",
        "groups.setdefault(id(item[0]), []).append(item)",
    ),
    # lock table keyed by (id(cache), key): worst case an address reuse
    # SHARES a lock between two caches — coarser locking, never stale
    # data; entries are few (one per live eval cache)
    (
        "controller/fast_eval.py",
        "lock = self._build_locks.setdefault((id(cache), key), threading.Lock())",
    ),
}


def _occurrences():
    found = set()
    for path in sorted(PACKAGE.rglob("*.py")):
        rel = path.relative_to(PACKAGE).as_posix()
        for line in path.read_text(encoding="utf-8").splitlines():
            if _ID_CALL.search(line):
                found.add((rel, line.strip()))
    return found


def test_no_new_id_keyed_caches():
    found = _occurrences()
    new = found - ALLOWED
    assert not new, (
        "new id(...) usage found — id()-keyed caches alias entries when "
        "an address is reused (the markov_chain stale-mesh bug class); "
        "hold a weakref and compare identity at lookup instead, or "
        f"justify an allowlist entry: {sorted(new)}"
    )


def test_allowlist_is_not_stale():
    """Every allowlisted line must still exist — delete entries when the
    code they excuse goes away, so the list can only shrink."""
    found = _occurrences()
    stale = ALLOWED - found
    assert not stale, f"allowlist entries no longer in the tree: {sorted(stale)}"


# --- blocking calls inside event-loop coroutines (api/ only) ---
#
# The bug class (this round's serving-frontend rework): a coroutine on
# the single-threaded asyncio frontend that calls ``time.sleep``, parks
# on an Event/Future ``.wait()``, or blocks in ``Future.result()``
# freezes EVERY connection the loop is serving — exactly the
# thread-parked handoff (``slot["done"].wait()``) the event loop
# replaced, except now it stalls the whole server instead of one
# thread. The sanctioned idioms are ``await asyncio.sleep``,
# ``await asyncio.wrap_future(fut)``, and handing blocking work to an
# executor pool that returns a future the loop awaits.

_BLOCKING_METHOD_NAMES = {"sleep", "wait", "result"}

# (relative path, lineno-independent stripped source line) pairs
# reviewed as safe. Empty today — the async frontend awaits everything;
# add entries only with a justification in your PR.
ASYNC_BLOCKING_ALLOWED: set = set()


def _async_blocking_occurrences():
    import ast

    found = set()
    api_dir = PACKAGE / "api"
    for path in sorted(api_dir.rglob("*.py")):
        rel = ("api/" + path.relative_to(api_dir).as_posix())
        source = path.read_text(encoding="utf-8")
        lines = source.splitlines()
        tree = ast.parse(source, filename=str(path))
        # mark every call that is directly awaited — those are fine
        awaited_calls = {
            id(node.value)
            for node in ast.walk(tree)
            if isinstance(node, ast.Await)
        }

        def scan_async_body(node):
            """Walk an async function's own statements, NOT nested sync
            defs (their bodies run on whatever thread later calls them,
            e.g. executor callbacks — legal places to block)."""
            import ast as _ast

            for child in _ast.iter_child_nodes(node):
                if isinstance(
                    child, (_ast.FunctionDef, _ast.Lambda)
                ):
                    continue
                if isinstance(child, _ast.Call) and id(child) not in awaited_calls:
                    fn = child.func
                    name = None
                    if isinstance(fn, _ast.Attribute):
                        name = fn.attr
                    elif isinstance(fn, _ast.Name):
                        name = fn.id
                    if name in _BLOCKING_METHOD_NAMES:
                        found.add((rel, lines[child.lineno - 1].strip()))
                scan_async_body(child)

        for node in ast.walk(tree):
            if isinstance(node, ast.AsyncFunctionDef):
                scan_async_body(node)
    return found


def test_no_blocking_calls_in_api_coroutines():
    found = _async_blocking_occurrences()
    new = found - ASYNC_BLOCKING_ALLOWED
    assert not new, (
        "blocking call inside an async def in api/ — time.sleep / "
        ".wait() / .result() on the event loop stalls every connection "
        "the loop serves (the thread-parked handoff bug class the async "
        "frontend replaced); await the async equivalent "
        "(asyncio.sleep / wrap_future) or justify an "
        f"ASYNC_BLOCKING_ALLOWED entry: {sorted(new)}"
    )


def test_async_blocking_allowlist_is_not_stale():
    found = _async_blocking_occurrences()
    stale = ASYNC_BLOCKING_ALLOWED - found
    assert not stale, (
        f"async-blocking allowlist entries no longer in the tree: "
        f"{sorted(stale)}"
    )


# --- mutable module-level state in the segment tier ---
#
# The bug class: a compactor (or its caches/locks/thread registries)
# held in module globals is shared by every storage universe in the
# process — one test's daemon outlives its store, a second event server
# inherits the first's threads, and cross-universe state aliases exactly
# like the id()-keyed caches above. data/storage/segments.py is the
# subsystem's home, so it is held to instance-scoped state ONLY: module
# level may bind constants (numbers, strings, tuples of constants),
# classes, and functions — never lists/dicts/sets/locks/threads/queues.

_MUTABLE_STATE_FILES = ("data/storage/segments.py",)

_MUTABLE_CALLS = {
    "dict", "list", "set", "bytearray", "defaultdict", "OrderedDict",
    "Counter", "deque", "Queue", "LifoQueue", "PriorityQueue",
    "SimpleQueue", "Lock", "RLock", "Condition", "Event", "Semaphore",
    "BoundedSemaphore", "Barrier", "Thread", "ThreadPoolExecutor",
    "WeakSet", "WeakKeyDictionary", "WeakValueDictionary",
}

# (relative path, stripped source line) pairs reviewed as safe.
# Shrink-only: delete entries when the code they excuse goes away.
MUTABLE_MODULE_STATE_ALLOWED: set = set()


def _mutable_module_state_occurrences():
    import ast

    found = set()
    for rel in _MUTABLE_STATE_FILES:
        path = PACKAGE / rel
        source = path.read_text(encoding="utf-8")
        lines = source.splitlines()
        tree = ast.parse(source, filename=str(path))

        def is_mutable(node) -> bool:
            if isinstance(
                node,
                (
                    ast.List, ast.Dict, ast.Set, ast.ListComp,
                    ast.DictComp, ast.SetComp, ast.GeneratorExp,
                ),
            ):
                return True
            if isinstance(node, ast.Call):
                fn = node.func
                name = fn.attr if isinstance(fn, ast.Attribute) else (
                    fn.id if isinstance(fn, ast.Name) else None
                )
                return name in _MUTABLE_CALLS
            return False

        for node in ast.iter_child_nodes(tree):
            if isinstance(node, ast.AugAssign):
                # any module-level augmented assignment is mutation of
                # module state — flag unconditionally
                found.add((rel, lines[node.lineno - 1].strip()))
                continue
            if isinstance(node, (ast.Assign, ast.AnnAssign)):
                if node.value is not None and is_mutable(node.value):
                    found.add((rel, lines[node.lineno - 1].strip()))
            # a module-level `global` escape hatch inside a function is
            # the same bug wearing a trench coat
        for node in ast.walk(tree):
            if isinstance(node, ast.Global):
                found.add((rel, lines[node.lineno - 1].strip()))
    return found


# --- unbounded sleep-polling loops in daemon/loop code ---
#
# The bug class (round 9's `pio train --continuous` loop class): a
# `while True:` that sleeps between rounds but checks no shutdown event
# can only be killed, not stopped — SIGTERM handlers can't reach it, the
# current round's model write races process death, and under pytest the
# daemon outlives its storage universe. The sanctioned idiom is
# `while not stop.is_set():` parking on `stop.wait(interval)` (see
# workflow/continuous.py and cmd_compact's daemon mode). Scope: daemon/
# loop code under workflow/ and tools/ — a `while True:` there that
# calls sleep() and never consults an event is flagged; plain read
# loops (no sleep, bounded by data) are not.

_LOOP_LINT_DIRS = ("workflow", "tools")

# (relative path, stripped source line of the `while` statement) pairs
# reviewed as safe. Shrink-only: delete entries when the code they
# excuse goes away. Empty today — both daemon loops are event-checked.
WHILE_TRUE_SLEEP_ALLOWED: set = set()


def _unbounded_poll_loops():
    import ast

    found = set()
    for d in _LOOP_LINT_DIRS:
        for path in sorted((PACKAGE / d).rglob("*.py")):
            rel = f"{d}/" + path.relative_to(PACKAGE / d).as_posix()
            source = path.read_text(encoding="utf-8")
            lines = source.splitlines()
            for node in ast.walk(ast.parse(source, filename=str(path))):
                if not (
                    isinstance(node, ast.While)
                    and isinstance(node.test, ast.Constant)
                    and node.test.value
                ):
                    continue  # only constant-true (`while True:`) loops
                has_sleep = False
                has_shutdown_check = False
                for sub in ast.walk(node):
                    if not isinstance(sub, ast.Call):
                        continue
                    fn = sub.func
                    name = (
                        fn.attr
                        if isinstance(fn, ast.Attribute)
                        else (fn.id if isinstance(fn, ast.Name) else None)
                    )
                    if name == "sleep":
                        has_sleep = True
                    elif name in ("is_set", "wait"):
                        # Event.is_set guard, or Event.wait(interval)
                        # doubling as the sleep — both shutdown-aware
                        has_shutdown_check = True
                if has_sleep and not has_shutdown_check:
                    found.add((rel, lines[node.lineno - 1].strip()))
    return found


def test_no_unbounded_poll_loops_in_daemon_code():
    found = _unbounded_poll_loops()
    new = found - WHILE_TRUE_SLEEP_ALLOWED
    assert not new, (
        "unbounded `while True:` sleep-poll loop in workflow/ or tools/ "
        "— a daemon loop that never checks a shutdown event can only be "
        "killed, not stopped; park on `stop.wait(interval)` under "
        "`while not stop.is_set():` (workflow/continuous.py is the "
        f"reference shape) or justify an allowlist entry: {sorted(new)}"
    )


def test_poll_loop_allowlist_is_not_stale():
    found = _unbounded_poll_loops()
    stale = WHILE_TRUE_SLEEP_ALLOWED - found
    assert not stale, (
        f"poll-loop allowlist entries no longer in the tree: "
        f"{sorted(stale)}"
    )


# --- module-level counter/stat state outside the metrics registry ---
#
# The bug class (this round's observability tentpole): ad-hoc stat
# state at module level — a `_CACHE_STATS = {"hit": 0, ...}` dict, a
# bare counter list — is invisible to /metrics, unmergeable across
# SO_REUSEPORT workers, and needs its own lock discipline. The
# sanctioned home is the process-global registry in utils/metrics.py
# (utils/tracing.py is the tracing counterpart): register a Counter/
# Gauge/Histogram family and every server's /metrics exposes it for
# free. Scope: module-level assignments of PLAIN mutable containers
# (dict/list/set literals or constructor calls) whose target name
# looks stat-like; registry instrument handles (registry.counter(...))
# are the replacement, not a violation.

_STAT_STATE_EXEMPT_FILES = (
    "utils/metrics.py",
    "utils/tracing.py",
    # the heartbeat/watchdog registry is the third sanctioned home for
    # module-level observability state (process-global by design, like
    # the metrics registry it records into)
    "utils/health.py",
)

_STAT_NAME = re.compile(
    r"(?i)(^|_)(stats?|counts?|counters?|metrics?|hist|histogram|"
    r"totals?|latenc\w*|timings?)(_|$|s$)"
)

_STAT_CONTAINER_CALLS = {
    "dict", "list", "set", "Counter", "defaultdict", "OrderedDict",
    "deque",
}

# (relative path, stripped source line) pairs reviewed as safe.
# Shrink-only: delete entries when the code they excuse goes away.
# Empty today — this PR migrated the offenders it seeded with
# (ops/streaming.py's _CACHE_STATS dict, the engine server's reservoir
# and executor tallies) into the registry.
MODULE_STAT_STATE_ALLOWED: set = set()


def _module_stat_state_occurrences():
    import ast

    found = set()
    for path in sorted(PACKAGE.rglob("*.py")):
        rel = path.relative_to(PACKAGE).as_posix()
        if rel in _STAT_STATE_EXEMPT_FILES:
            continue
        source = path.read_text(encoding="utf-8")
        lines = source.splitlines()
        tree = ast.parse(source, filename=str(path))

        def is_plain_container(node) -> bool:
            if isinstance(
                node,
                (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp,
                 ast.SetComp),
            ):
                return True
            if isinstance(node, ast.Call):
                fn = node.func
                name = fn.attr if isinstance(fn, ast.Attribute) else (
                    fn.id if isinstance(fn, ast.Name) else None
                )
                return name in _STAT_CONTAINER_CALLS
            return False

        for node in ast.iter_child_nodes(tree):
            if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                continue
            targets = (
                node.targets if isinstance(node, ast.Assign) else [node.target]
            )
            names = [
                t.id for t in targets if isinstance(t, ast.Name)
            ]
            if not any(_STAT_NAME.search(n) for n in names):
                continue
            if node.value is not None and is_plain_container(node.value):
                found.add((rel, lines[node.lineno - 1].strip()))
    return found


def test_no_module_level_stat_state_outside_metrics_registry():
    found = _module_stat_state_occurrences()
    new = found - MODULE_STAT_STATE_ALLOWED
    assert not new, (
        "module-level counter/stat state outside utils/metrics.py — "
        "ad-hoc stat containers are invisible to /metrics and cannot "
        "merge across SO_REUSEPORT workers; register a Counter/Gauge/"
        "Histogram family in the process-global registry "
        "(utils/metrics.py) instead, or justify an allowlist entry: "
        f"{sorted(new)}"
    )


def test_module_stat_state_allowlist_is_not_stale():
    found = _module_stat_state_occurrences()
    stale = MODULE_STAT_STATE_ALLOWED - found
    assert not stale, (
        f"module-stat-state allowlist entries no longer in the tree: "
        f"{sorted(stale)}"
    )


# --- print() outside the CLI tier ---
#
# The bug class (this round's structured-logging tentpole): ad-hoc
# print(...) status output in library code bypasses the logging tree
# entirely — no level, no logger name, no trace correlation, invisible
# to PIO_LOG_FORMAT=json — and in daemons it interleaves raw on stderr
# with the structured stream. The sanctioned idiom is the module's
# ``logging.getLogger(__name__)`` (utils/logging.py formats it, with
# the ambient trace id attached). Scope: the whole package EXCEPT
# tools/ — the CLI's command OUTPUT (app listings, exported counts) is
# its user interface and legitimately prints; its daemon-loop status
# lines went through the logger this round.

_PRINT_EXEMPT_PREFIX = "tools/"

# (relative path, stripped source line) pairs reviewed as safe.
# Shrink-only: delete entries when the code they excuse goes away.
# Empty today — library code was already print-free.
PRINT_ALLOWED: set = set()


def _print_call_occurrences():
    import ast

    found = set()
    for path in sorted(PACKAGE.rglob("*.py")):
        rel = path.relative_to(PACKAGE).as_posix()
        if rel.startswith(_PRINT_EXEMPT_PREFIX):
            continue
        source = path.read_text(encoding="utf-8")
        lines = source.splitlines()
        for node in ast.walk(ast.parse(source, filename=str(path))):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "print"
            ):
                found.add((rel, lines[node.lineno - 1].strip()))
    return found


def test_no_print_outside_tools():
    found = _print_call_occurrences()
    new = found - PRINT_ALLOWED
    assert not new, (
        "print(...) in library code — status output must ride the "
        "logging tree (logging.getLogger(__name__)) so it carries "
        "level/logger/trace-id and respects PIO_LOG_FORMAT=json "
        "(utils/logging.py); CLI user output belongs in tools/. "
        f"Justify an allowlist entry otherwise: {sorted(new)}"
    )


def test_print_allowlist_is_not_stale():
    found = _print_call_occurrences()
    stale = PRINT_ALLOWED - found
    assert not stale, (
        f"print allowlist entries no longer in the tree: {sorted(stale)}"
    )


# --- Prometheus unit-suffix conventions for registry families ---
#
# The bug class (this round's model-quality tentpole): a family named
# `pio_foo_ms` or a histogram called `pio_bar_total` renders fine but
# breaks every downstream consumer convention — Prometheus tooling
# assumes counters end `_total` and time/size series use base units
# (`_seconds`/`_bytes`). This lint walks every registry registration in
# the package (reg.counter/gauge/histogram with a literal name) and
# enforces: counters end `_total` (counters of seconds/bytes end
# `_seconds_total`/`_bytes_total`), non-counters never end `_total`,
# time series use `_seconds`, size series `_bytes`, and nobody uses a
# non-base unit suffix. utils/metrics.py (the registry itself) is
# exempt; the allowlist is seeded EMPTY and shrink-only.

_METRIC_KINDS = ("counter", "gauge", "histogram")

_NON_BASE_UNIT_SUFFIXES = (
    "_ms", "_millis", "_milliseconds", "_us", "_micros", "_microseconds",
    "_ns", "_nanos", "_minutes", "_hours", "_days", "_kb", "_mb", "_gb",
    "_kib", "_mib", "_gib", "_percent",
)

# (relative path, family name) pairs reviewed as acceptable deviations.
# Shrink-only. pio_retrieval_bytes_per_item is a RATIO (resident bytes
# per catalog item, the quantization capacity figure `pio top` renders
# as PREC detail), not a size series — an `_bytes` suffix would claim a
# summable byte total, which per-item bytes is not.
METRIC_NAME_ALLOWED: set = {
    ("ops/retrieval.py", "pio_retrieval_bytes_per_item"),
}


def _metric_name_violation(name: str, kind: str):
    for suf in _NON_BASE_UNIT_SUFFIXES:
        if name.endswith(suf):
            return (
                f"non-base unit suffix {suf!r} — use _seconds/_bytes "
                "base units"
            )
    if kind == "counter":
        if not name.endswith("_total"):
            return "counter families must end _total"
        if "seconds" in name and not name.endswith("_seconds_total"):
            return "a counter of seconds must end _seconds_total"
        if "bytes" in name and not name.endswith("_bytes_total"):
            return "a counter of bytes must end _bytes_total"
    else:
        if name.endswith("_total"):
            return f"a {kind} must not end _total (counters only)"
        if "seconds" in name and not name.endswith("_seconds"):
            return f"a {kind} of seconds must end _seconds"
        if "bytes" in name and not name.endswith("_bytes"):
            return f"a {kind} of bytes must end _bytes"
    return None


def _metric_name_occurrences():
    import ast

    found = set()
    for path in sorted(PACKAGE.rglob("*.py")):
        rel = path.relative_to(PACKAGE).as_posix()
        if rel == "utils/metrics.py":
            continue  # the registry itself (docstrings, generic helpers)
        tree = ast.parse(
            path.read_text(encoding="utf-8"), filename=str(path)
        )
        for node in ast.walk(tree):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _METRIC_KINDS
            ):
                continue
            if not (
                node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
            ):
                continue  # dynamic names are out of scope for the lint
            name = node.args[0].value
            reason = _metric_name_violation(name, node.func.attr)
            if reason:
                found.add((rel, name, reason))
    return found


def test_metric_families_follow_unit_suffix_conventions():
    found = _metric_name_occurrences()
    new = {
        (rel, name, reason)
        for rel, name, reason in found
        if (rel, name) not in METRIC_NAME_ALLOWED
    }
    assert not new, (
        "registry family name violates Prometheus unit-suffix "
        "conventions (counters end _total, time in _seconds, sizes in "
        "_bytes, no _ms/_mb-style suffixes); rename the family or "
        f"justify an allowlist entry: {sorted(new)}"
    )


def test_metric_name_allowlist_is_not_stale():
    found = {(rel, name) for rel, name, _ in _metric_name_occurrences()}
    stale = METRIC_NAME_ALLOWED - found
    assert not stale, (
        f"metric-name allowlist entries no longer in the tree: "
        f"{sorted(stale)}"
    )


# --- docs drift: every registered family is cataloged ---
#
# The bug class (round 15's telemetry tentpole): a family registered in
# code but absent from docs/OBSERVABILITY.md's catalog is invisible to
# the operators the whole observability tier exists for — dashboards,
# SLOs, and the runbooks reference the catalog, not the source. This
# lint walks every registry registration with a literal name —
# ``reg.counter(...)``/``gauge``/``histogram`` AND the thin wrapper
# idiom (``_counter(...)``/``_gauge(...)``, data/storage/cluster.py) —
# and fails any family name that does not appear in the catalog file.
# The allowlist is seeded EMPTY (the strays this lint found were
# documented when it landed) and is shrink-only.

_DOCS_CATALOG = PACKAGE.parent / "docs" / "OBSERVABILITY.md"

# (relative path, family name) pairs excused from the catalog.
METRIC_DOCS_ALLOWED: set = set()


def _registered_family_names():
    import ast

    found = set()
    for path in sorted(PACKAGE.rglob("*.py")):
        rel = path.relative_to(PACKAGE).as_posix()
        if rel == "utils/metrics.py":
            continue  # the registry itself (docstrings, generic helpers)
        tree = ast.parse(
            path.read_text(encoding="utf-8"), filename=str(path)
        )
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            name = (
                fn.attr if isinstance(fn, ast.Attribute)
                else fn.id if isinstance(fn, ast.Name)
                else None
            )
            # reg.counter(...) and the _counter(...) wrapper idiom both
            # resolve to a registration; lstrip covers the wrappers
            if name is None or name.lstrip("_") not in _METRIC_KINDS:
                continue
            if not (
                node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
            ):
                continue  # dynamic names are out of scope for the lint
            family = node.args[0].value
            if family.startswith("pio_"):
                found.add((rel, family))
    return found


def test_every_registered_metric_family_is_documented():
    catalog = _DOCS_CATALOG.read_text(encoding="utf-8")
    found = _registered_family_names()
    missing = {
        (rel, family)
        for rel, family in found
        if family not in catalog and (rel, family) not in METRIC_DOCS_ALLOWED
    }
    assert not missing, (
        "metric family registered in code but absent from "
        "docs/OBSERVABILITY.md's catalog — the catalog is the operator "
        "contract; document the family (family name, type, labels, "
        "meaning) or justify a METRIC_DOCS_ALLOWED entry: "
        f"{sorted(missing)}"
    )


def test_metric_docs_allowlist_is_not_stale():
    found = _registered_family_names()
    catalog = _DOCS_CATALOG.read_text(encoding="utf-8")
    stale = {
        entry
        for entry in METRIC_DOCS_ALLOWED
        if entry not in found or entry[1] in catalog
    }
    assert not stale, (
        "metric-docs allowlist entries no longer needed (family gone "
        f"or now documented): {sorted(stale)}"
    )


# --- silent exception swallowing in the promotion-critical tiers ---
#
# The bug class (round 13's promotion tentpole): an `except ...: pass`
# in workflow/ or api/ code silently eats the very failures the
# promotion pipeline exists to surface — a swap that half-happened, a
# drain that never resolved, a reload that kept serving a corpse. Every
# handler must either re-raise, return a typed error, or at minimum log
# (logger.debug(..., exc_info=True) is the sanctioned minimum for
# expected-teardown paths). Scope: workflow/ and api/ — the tiers a
# promotion traverses. The allowlist below was reviewed entry by entry
# (all are connection-teardown paths where the peer is already gone)
# and is shrink-only.

_EXCEPT_PASS_DIRS = ("workflow", "api")

# (relative path, stripped source line of the `except` statement) pairs
# reviewed as safe. Shrink-only: delete entries when the code they
# excuse goes away; new silent swallows must log instead.
EXCEPT_PASS_ALLOWED = {
    # loop finished between the closed-check and call_soon_threadsafe —
    # shutdown teardown, nothing to report
    ("api/aio_http.py", "except RuntimeError:"),
    # loop.shutdown_asyncgens during loop teardown; the loop is closing
    # regardless and the server already logged its lifecycle
    ("api/aio_http.py", "except Exception:"),
    # setsockopt(TCP_NODELAY) on a socket the peer may already have
    # closed — a lost latency optimization, not an error
    ("api/aio_http.py", "except OSError:"),
    # peer went away mid-request: normal keep-alive connection death
    ("api/aio_http.py", "except (ConnectionError, asyncio.IncompleteReadError):"),
    # writer.wait_closed on an already-dead transport during teardown
    (
        "api/aio_http.py",
        "except (ConnectionError, OSError, asyncio.CancelledError):",
    ),
    # awaiting the cancelled writer task during connection teardown
    ("api/aio_http.py", "except asyncio.CancelledError:"),
    # close()'s bounded drain of the feedback queue: Empty IS the loop's
    # exit condition
    ("api/engine_server.py", "except queue.Empty:"),
    # the transport cancelled the request (client gone) — the future has
    # no waiter left to inform
    ("api/engine_server.py", "except concurrent.futures.InvalidStateError:"),
}


def _except_pass_occurrences():
    import ast

    found = set()
    for d in _EXCEPT_PASS_DIRS:
        for path in sorted((PACKAGE / d).rglob("*.py")):
            rel = f"{d}/" + path.relative_to(PACKAGE / d).as_posix()
            source = path.read_text(encoding="utf-8")
            lines = source.splitlines()
            for node in ast.walk(ast.parse(source, filename=str(path))):
                if not isinstance(node, ast.ExceptHandler):
                    continue
                if len(node.body) == 1 and isinstance(node.body[0], ast.Pass):
                    found.add((rel, lines[node.lineno - 1].strip()))
    return found


def test_no_silent_exception_swallows_in_promotion_tiers():
    found = _except_pass_occurrences()
    new = found - EXCEPT_PASS_ALLOWED
    assert not new, (
        "silent `except ...: pass` under workflow/ or api/ — swallowed "
        "exceptions are how promotion bugs hide (a half-swapped fleet, "
        "a drain that never resolves); re-raise, return a typed error, "
        "or at least logger.debug(..., exc_info=True), or justify an "
        f"allowlist entry: {sorted(new)}"
    )


def test_except_pass_allowlist_is_not_stale():
    found = _except_pass_occurrences()
    stale = EXCEPT_PASS_ALLOWED - found
    assert not stale, (
        f"except-pass allowlist entries no longer in the tree: "
        f"{sorted(stale)}"
    )


# --- long-lived device placements outside the residency ledger ---
#
# The bug class (round 16's device-observability tentpole): a component
# that parks buffers on device in a long-lived attribute
# (``self._x = jax.device_put(...)``) without registering in the HBM
# residency ledger (utils/device_ledger.py) is exactly the untracked
# residency the ledger-vs-memory_stats drift gauge exists to flag — the
# PR 13 leak class was only findable by reading code. Scope: ops/ and
# api/ — the tiers that own resident serving/training state. A flagged
# assignment must either register a LedgerHandle covering the buffers
# (the ItemRetriever/ServingFactors idiom: register at construction
# with an ``anchor`` finalizer, explicit close on the free path) or be
# allowlisted with a justification. The allowlist below was seeded
# from a review of every existing site — each one IS covered by a
# ledger registration in the same class — and is shrink-only.

_DEVICE_RESIDENCY_DIRS = ("ops", "api")

# call names whose result parked in a self attribute is device residency
_DEVICE_PLACEMENT_CALLS = {"device_put", "put"}

# ops/streaming.py (round 17) parks long-lived device buffers on cache
# objects rather than ``self`` (``entry.resident = ResidentPack(...)``
# holds the resident COO planes + factor slots between continuous
# rounds), so for that file the lint widens to ANY attribute receiver
# and to the calls that build/absorb device arrays there. Everything it
# flags must register a train-pack LedgerHandle or be allowlisted.
_DEVICE_RESIDENCY_WIDENED = {
    "ops/streaming.py": {"device_put", "put", "asarray", "ResidentPack"},
}

# (relative path, stripped source line) pairs reviewed as safe: every
# entry's buffers are registered in the device ledger by the same
# class (ItemRetriever registers component + component-mask handles;
# ServingFactors registers serving-factors with an anchor finalizer).
DEVICE_RESIDENCY_ALLOWED = {
    # ItemRetriever.__init__ / set_excluded_ids: covered by the
    # _ledger_factors/_ledger_mask handles registered right below them
    # (y_host is the precision-selected storage rows — f32/bf16/int8 —
    # and _scale_dev the int8 per-row scales, all in the factors handle;
    # the float32 rows go up through _upload_padded, block by block,
    # on a mesh a shard a device through _upload_row_sharded)
    ("ops/retrieval.py", "self._y_dev = ("),
    ("ops/retrieval.py", "self._scale_dev = ("),
    ("ops/retrieval.py", "self._rn_dev = put(rn)"),
    ("ops/retrieval.py", "self._allow_dev = put(self._valid)"),
    ("ops/retrieval.py", "self._rn_dev = jax.device_put(rn, NamedSharding(mesh, P(axis)))"),
    ("ops/retrieval.py", "self._allow_dev = jax.device_put("),
    ("ops/retrieval.py", "self._allow_dev = ("),
    # the per-item category codes: resident beside the mask and
    # counted in the same _ledger_mask handle (device_footprint of both)
    ("ops/retrieval.py", "self._codes_dev = put(codes)"),
    ("ops/retrieval.py", "self._codes_dev = jax.device_put("),
    # ServingFactors.__init__: covered by the serving-factors handle
    # with the anchor finalizer (release is refcount-driven)
    ("ops/als.py", "self._uf_dev = jax.device_put("),
    ("ops/als.py", "self._if_dev = jax.device_put("),
    # SimilarityScorer.__init__: covered by the similarity-factors
    # handle registered right below (anchor finalizer, refcount free)
    ("ops/similarity.py", "self._dev = jax.device_put(jnp.asarray(self.normed))"),
    # _establish_resident: the resident incremental pack — covered by
    # the train-pack handle registered over device_footprint(*arrays)
    # right below, with an anchor finalizer on the pack itself;
    # release()/demotion close the handle and zero the gauge
    ("ops/streaming.py", "entry.resident = ResidentPack("),
}


def _device_residency_occurrences():
    import ast

    found = set()
    for d in _DEVICE_RESIDENCY_DIRS:
        for path in sorted((PACKAGE / d).rglob("*.py")):
            rel = f"{d}/" + path.relative_to(PACKAGE / d).as_posix()
            source = path.read_text(encoding="utf-8")
            lines = source.splitlines()
            tree = ast.parse(source, filename=str(path))
            placement_calls = _DEVICE_RESIDENCY_WIDENED.get(
                rel, _DEVICE_PLACEMENT_CALLS
            )
            any_receiver = rel in _DEVICE_RESIDENCY_WIDENED

            def places_on_device(node) -> bool:
                for sub in ast.walk(node):
                    if not isinstance(sub, ast.Call):
                        continue
                    fn = sub.func
                    name = (
                        fn.attr if isinstance(fn, ast.Attribute)
                        else fn.id if isinstance(fn, ast.Name)
                        else None
                    )
                    if name in placement_calls:
                        return True
                return False

            for node in ast.walk(tree):
                if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                    continue
                targets = (
                    node.targets
                    if isinstance(node, ast.Assign)
                    else [node.target]
                )
                to_attr = any(
                    isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and (any_receiver or t.value.id == "self")
                    for t in targets
                )
                if not to_attr or node.value is None:
                    continue
                if places_on_device(node.value):
                    found.add((rel, lines[node.lineno - 1].strip()))
    return found


def test_long_lived_device_placements_route_through_ledger():
    found = _device_residency_occurrences()
    new = found - DEVICE_RESIDENCY_ALLOWED
    assert not new, (
        "long-lived device placement (self.<attr> = device_put(...)) "
        "under ops/ or api/ without a reviewed ledger registration — "
        "untracked residency is invisible to pio_device_ledger_bytes "
        "and reads as drift (the PR 13 leak class); register a "
        "LedgerHandle (utils/device_ledger.py, see ItemRetriever / "
        "ServingFactors) covering the buffers, then allowlist the "
        f"line with a justification: {sorted(new)}"
    )


def test_device_residency_allowlist_is_not_stale():
    found = _device_residency_occurrences()
    stale = DEVICE_RESIDENCY_ALLOWED - found
    assert not stale, (
        f"device-residency allowlist entries no longer in the tree: "
        f"{sorted(stale)}"
    )


def test_no_mutable_module_state_in_segment_tier():
    found = _mutable_module_state_occurrences()
    new = found - MUTABLE_MODULE_STATE_ALLOWED
    assert not new, (
        "mutable module-level state in the segment tier — compactor "
        "daemons, caches, and locks must hang off an instance owned by "
        "a server or CLI run, never the module (cross-universe aliasing "
        "and leaked daemon threads); move it into a class or justify an "
        f"allowlist entry: {sorted(new)}"
    )


def test_mutable_module_state_allowlist_is_not_stale():
    found = _mutable_module_state_occurrences()
    stale = MUTABLE_MODULE_STATE_ALLOWED - found
    assert not stale, (
        f"mutable-module-state allowlist entries no longer in the "
        f"tree: {sorted(stale)}"
    )


# --- storage-tier robustness lints (round 14's cluster tentpole) ---
#
# The bug classes: (1) a bare `except Exception: pass` in storage code
# silently eats exactly the transport/backend failures the cluster
# tier's circuit breakers, staleness marks, and PartialBatchError
# attribution exist to SURFACE — a swallowed write error is an acked
# event that never happened; (2) a socket operation with no deadline
# (`timeout=None`) parks a scan or write behind a wedged gateway node
# forever instead of failing fast into the retry/breaker path
# (data/storage/http.py propagates PIO_STORAGE_CLIENT_TIMEOUT_S as the
# socket timeout for precisely this reason). Scope: data/storage/.
# Both allowlists were seeded from a review of every existing site —
# the review found only narrowly-typed handlers (OSError on os.remove,
# sqlite3.Error on rollback) and timeout-carrying connections, so both
# seed EMPTY and are shrink-only.

STORAGE_DIR = PACKAGE / "data" / "storage"

# (relative path, stripped `except` line) pairs reviewed as safe.
STORAGE_EXCEPT_PASS_ALLOWED: set = set()

# (relative path, stripped source line of the unbounded call).
STORAGE_UNBOUNDED_SOCKET_ALLOWED: set = set()

# connection-constructing calls that accept a `timeout` kwarg; calling
# them without one (or with timeout=None) under data/storage/ is the
# unbounded-socket bug class
_SOCKET_CALL_NAMES = {
    "HTTPConnection",
    "HTTPSConnection",
    "create_connection",
    "urlopen",
}


def _storage_rel(path) -> str:
    return "data/storage/" + path.relative_to(STORAGE_DIR).as_posix()


def _storage_broad_except_pass_occurrences():
    import ast

    found = set()
    for path in sorted(STORAGE_DIR.rglob("*.py")):
        source = path.read_text(encoding="utf-8")
        lines = source.splitlines()
        for node in ast.walk(ast.parse(source, filename=str(path))):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if not (
                len(node.body) == 1 and isinstance(node.body[0], ast.Pass)
            ):
                continue
            # bare `except:` or the broad Exception/BaseException —
            # narrowly-typed teardown handlers (OSError on os.remove)
            # are allowed to pass
            broad = node.type is None or (
                isinstance(node.type, ast.Name)
                and node.type.id in ("Exception", "BaseException")
            )
            if broad:
                found.add(
                    (_storage_rel(path), lines[node.lineno - 1].strip())
                )
    return found


def _storage_unbounded_socket_occurrences():
    import ast

    found = set()
    for path in sorted(STORAGE_DIR.rglob("*.py")):
        source = path.read_text(encoding="utf-8")
        lines = source.splitlines()
        for node in ast.walk(ast.parse(source, filename=str(path))):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            name = (
                fn.id if isinstance(fn, ast.Name)
                else fn.attr if isinstance(fn, ast.Attribute)
                else None
            )
            bad = False
            if name in _SOCKET_CALL_NAMES:
                kw = {k.arg: k.value for k in node.keywords}
                t = kw.get("timeout")
                bad = (
                    ("timeout" not in kw and not any(
                        k.arg is None for k in node.keywords  # **kwargs
                    ))
                    or isinstance(t, ast.Constant) and t.value is None
                )
            elif name == "settimeout":
                args = list(node.args)
                bad = bool(args) and (
                    isinstance(args[0], ast.Constant)
                    and args[0].value is None
                )
            if bad:
                found.add(
                    (_storage_rel(path), lines[node.lineno - 1].strip())
                )
    return found


def test_no_broad_except_pass_in_storage_tier():
    found = _storage_broad_except_pass_occurrences()
    new = found - STORAGE_EXCEPT_PASS_ALLOWED
    assert not new, (
        "bare `except Exception: pass` under data/storage/ — a "
        "swallowed storage failure is an acked write that never "
        "happened (the cluster tier's breakers and PartialBatchError "
        "attribution depend on failures SURFACING); narrow the type, "
        "re-raise, or log, or justify an allowlist entry: "
        f"{sorted(new)}"
    )


def test_storage_except_pass_allowlist_is_not_stale():
    found = _storage_broad_except_pass_occurrences()
    stale = STORAGE_EXCEPT_PASS_ALLOWED - found
    assert not stale, (
        f"storage except-pass allowlist entries no longer in the "
        f"tree: {sorted(stale)}"
    )


def test_no_unbounded_socket_ops_in_storage_tier():
    found = _storage_unbounded_socket_occurrences()
    new = found - STORAGE_UNBOUNDED_SOCKET_ALLOWED
    assert not new, (
        "socket operation without a timeout under data/storage/ — an "
        "unbounded connect/read parks the caller behind a wedged "
        "gateway node forever instead of failing fast into the "
        "retry/circuit-breaker path; pass timeout= (see "
        "PIO_STORAGE_CLIENT_TIMEOUT_S in data/storage/http.py) or "
        f"justify an allowlist entry: {sorted(new)}"
    )


def test_storage_unbounded_socket_allowlist_is_not_stale():
    found = _storage_unbounded_socket_occurrences()
    stale = STORAGE_UNBOUNDED_SOCKET_ALLOWED - found
    assert not stale, (
        f"storage unbounded-socket allowlist entries no longer in "
        f"the tree: {sorted(stale)}"
    )


# --- retrieval top-k widths route through the pow2 ladder ---
#
# The bug class (PR 8's blacklist-width lesson, now with a quantized
# shortlist tier multiplying the executable space): a serving call
# site that passes a raw query `num` straight into a retrieval top-k
# entry point compiles ONE executable per distinct num — under varied
# live traffic that turns the micro-batch executor into a compile
# queue. Every function that calls a retrieval top-k entry point
# (`topn`/`topn_by_user`/`topn_by_rows`/`topn_packed_device`) must
# route its width through `retrieval.pow2_topk_width` in the SAME
# function (the ladder also records padding waste per site).
# ops/retrieval.py and ops/als.py are exempt — they ARE the ladder's
# implementation (internal stage widths are already pow2-derived, and
# warm() deliberately walks the ladder tiers). The allowlist is
# seeded EMPTY and shrink-only.

_TOPK_ENTRY_POINTS = (
    "topn", "topn_by_user", "topn_by_rows", "topn_packed_device",
)

_TOPK_LINT_EXEMPT_FILES = ("ops/retrieval.py", "ops/als.py")

# (relative path, enclosing function name) pairs excused from routing.
SHORTLIST_WIDTH_ALLOWED: set = set()


def _unrouted_topk_occurrences():
    import ast

    found = set()
    for path in sorted(PACKAGE.rglob("*.py")):
        rel = path.relative_to(PACKAGE).as_posix()
        if rel in _TOPK_LINT_EXEMPT_FILES:
            continue
        tree = ast.parse(
            path.read_text(encoding="utf-8"), filename=str(path)
        )
        for node in ast.walk(tree):
            if not isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef)
            ):
                continue
            calls_topk = False
            calls_router = False
            for sub in ast.walk(node):
                if not (
                    isinstance(sub, ast.Call)
                    and isinstance(sub.func, (ast.Attribute, ast.Name))
                ):
                    continue
                attr = (
                    sub.func.attr
                    if isinstance(sub.func, ast.Attribute)
                    else sub.func.id
                )
                if attr in _TOPK_ENTRY_POINTS:
                    calls_topk = True
                if attr == "pow2_topk_width":
                    calls_router = True
            if calls_topk and not calls_router:
                found.add((rel, node.name))
    return found


def test_topk_widths_route_through_pow2_ladder():
    found = _unrouted_topk_occurrences()
    new = found - SHORTLIST_WIDTH_ALLOWED
    assert not new, (
        "retrieval top-k call site without pow2_topk_width in the "
        "same function — a raw width is one compiled executable per "
        "distinct num (and on a quantized retriever also pins an "
        "unwarmed stage-1 shortlist width); route the width through "
        "retrieval.pow2_topk_width or justify an allowlist entry: "
        f"{sorted(new)}"
    )


def test_shortlist_width_allowlist_is_not_stale():
    found = _unrouted_topk_occurrences()
    stale = SHORTLIST_WIDTH_ALLOWED - found
    assert not stale, (
        f"shortlist-width allowlist entries no longer in the tree: "
        f"{sorted(stale)}"
    )


# --- subspace solver param coherence (round 19) ---
#
# Any construction of an ALS param/config object with solver="subspace"
# must pass a block_size that the iALS++ blocked solver can use: a
# positive integer literal that divides the (statically visible) rank.
# A violating combination raises at runtime (ops/als.validate_solver),
# but only on the code path that builds it — this lint moves the check
# to test time for every in-repo construction, bench configs included
# (a bench gate that dies an hour in on a bad literal is the expensive
# version of this assert).

_SUBSPACE_CTOR_NAMES = ("ALSConfig",)
_SUBSPACE_CTOR_SUFFIX = "AlgorithmParams"
# default rank of every ALS params class AND ALSConfig (ops/als.py)
_SUBSPACE_DEFAULT_RANK = 10

# (relative path, line description) pairs excused from the lint.
SUBSPACE_PARAMS_ALLOWED: set = set()


def _subspace_param_violations():
    import ast

    paths = sorted(PACKAGE.rglob("*.py")) + [PACKAGE.parent / "bench.py"]
    found = set()
    for path in paths:
        try:
            rel = path.relative_to(PACKAGE).as_posix()
        except ValueError:
            rel = path.name
        tree = ast.parse(
            path.read_text(encoding="utf-8"), filename=str(path)
        )
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            name = (
                fn.attr if isinstance(fn, ast.Attribute)
                else fn.id if isinstance(fn, ast.Name)
                else None
            )
            if name is None or not (
                name in _SUBSPACE_CTOR_NAMES
                or name.endswith(_SUBSPACE_CTOR_SUFFIX)
            ):
                continue
            kw = {
                k.arg: k.value for k in node.keywords if k.arg is not None
            }
            solver = kw.get("solver")
            if not (
                isinstance(solver, ast.Constant)
                and solver.value == "subspace"
            ):
                continue
            where = f"{rel}:{node.lineno}"
            bs = kw.get("block_size")
            if bs is None:
                found.add((where, "solver='subspace' without block_size"))
                continue
            if not (
                isinstance(bs, ast.Constant)
                and isinstance(bs.value, int)
                and not isinstance(bs.value, bool)
            ):
                found.add(
                    (where, "block_size must be an int literal here")
                )
                continue
            if bs.value <= 0:
                found.add((where, f"block_size={bs.value} <= 0"))
                continue
            rank = kw.get("rank")
            if rank is None and any(
                k.arg is None for k in node.keywords
            ):
                continue  # rank travels in **kwargs: runtime-checked
            rank_val = (
                rank.value
                if isinstance(rank, ast.Constant)
                and isinstance(rank.value, int)
                else _SUBSPACE_DEFAULT_RANK if rank is None
                else None
            )
            if rank_val is None:
                continue  # dynamic rank: runtime-checked
            if rank_val % bs.value != 0:
                found.add(
                    (
                        where,
                        f"block_size={bs.value} does not divide "
                        f"rank={rank_val}",
                    )
                )
    return found


def test_subspace_block_size_divides_rank():
    found = _subspace_param_violations()
    new = found - SUBSPACE_PARAMS_ALLOWED
    assert not new, (
        "solver='subspace' construction whose block_size cannot drive "
        "the iALS++ blocked solver (ops/als.validate_solver would "
        "raise at runtime); fix the literal or justify a "
        f"SUBSPACE_PARAMS_ALLOWED entry: {sorted(new)}"
    )


def test_subspace_params_allowlist_is_not_stale():
    found = _subspace_param_violations()
    stale = SUBSPACE_PARAMS_ALLOWED - found
    assert not stale, (
        f"subspace-params allowlist entries no longer in the tree: "
        f"{sorted(stale)}"
    )


# --- experiment allocation determinism ------------------------------
#
# The sticky-allocation contract (workflow/experiment.py): every
# SO_REUSEPORT worker and every restart must map the same user to the
# same variant with ZERO coordination. That only holds if the
# allocation path is a pure function of (salt, user_key, split) — any
# randomness or clock read silently breaks stickiness and corrupts the
# sequential test's exchangeability assumption.
#
# Scope of the ban:
#   1. ALL of workflow/experiment.py: no random-source calls anywhere
#      (the module's runner legitimately reads time.time for horizon
#      bookkeeping, so clocks are only banned in the pure functions);
#   2. the pure allocation functions (allocate*, split_edges,
#      user_key_from_query, ActiveExperiment.route): no clock reads;
#   3. the QueryAPI allocation hook in api/engine_server.py
#      (_handle_query_nowait, _finish_query, and every
#      experiment-named function): no random-source calls.
#
# Shrink-only allowlist, seeded empty on purpose: additions require a
# reviewed justification in the PR that adds them.

_RANDOM_SOURCE_NAMES = frozenset({
    "random", "randint", "randrange", "choice", "choices", "shuffle",
    "sample", "uniform", "betavariate", "gauss", "normalvariate",
    "getrandbits", "urandom", "token_hex", "token_bytes", "uuid1",
    "uuid4",
})
_CLOCK_NAMES = frozenset({
    "time", "time_ns", "monotonic", "monotonic_ns", "perf_counter",
    "perf_counter_ns", "now", "utcnow",
})
_PURE_ALLOCATION_FNS = frozenset({
    "split_edges", "user_key_from_query", "allocate_bucket", "allocate",
    "route",
})

EXPERIMENT_DETERMINISM_ALLOWED: set = set()


def _experiment_determinism_occurrences():
    import ast

    def call_name(node):
        fn = node.func
        return (
            fn.attr if isinstance(fn, ast.Attribute)
            else fn.id if isinstance(fn, ast.Name)
            else None
        )

    found = set()

    exp_path = PACKAGE / "workflow" / "experiment.py"
    tree = ast.parse(
        exp_path.read_text(encoding="utf-8"), filename=str(exp_path)
    )
    # module-wide random ban
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            name = call_name(node)
            if name in _RANDOM_SOURCE_NAMES:
                found.add((
                    "workflow/experiment.py",
                    f"random source {name}() at line {node.lineno}",
                ))
    # clock ban inside the pure allocation functions
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if node.name not in _PURE_ALLOCATION_FNS:
            continue
        for inner in ast.walk(node):
            if isinstance(inner, ast.Call):
                name = call_name(inner)
                if name in _CLOCK_NAMES:
                    found.add((
                        "workflow/experiment.py",
                        f"clock read {name}() in pure allocation "
                        f"function {node.name}() at line {inner.lineno}",
                    ))

    srv_path = PACKAGE / "api" / "engine_server.py"
    srv_tree = ast.parse(
        srv_path.read_text(encoding="utf-8"), filename=str(srv_path)
    )
    hook_fns = {"_handle_query_nowait", "_finish_query"}
    for node in ast.walk(srv_tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not (node.name in hook_fns or "experiment" in node.name):
            continue
        for inner in ast.walk(node):
            if isinstance(inner, ast.Call):
                name = call_name(inner)
                if name in _RANDOM_SOURCE_NAMES:
                    found.add((
                        "api/engine_server.py",
                        f"random source {name}() in allocation hook "
                        f"{node.name}() at line {inner.lineno}",
                    ))
    return found


def test_experiment_allocation_is_deterministic():
    found = _experiment_determinism_occurrences()
    new = found - EXPERIMENT_DETERMINISM_ALLOWED
    assert not new, (
        "randomness or clock reads in the sticky-allocation path — "
        "variant assignment must be a pure function of "
        "(salt, user_key, split) so SO_REUSEPORT workers and restarts "
        "agree with zero coordination; remove the call or justify an "
        f"EXPERIMENT_DETERMINISM_ALLOWED entry: {sorted(new)}"
    )


def test_experiment_determinism_allowlist_is_not_stale():
    found = _experiment_determinism_occurrences()
    stale = EXPERIMENT_DETERMINISM_ALLOWED - found
    assert not stale, (
        f"experiment-determinism allowlist entries no longer in the "
        f"tree: {sorted(stale)}"
    )
