"""The e-commerce cell's inputs, its host-side stages and its load generator.

Everything is made from seeds with numpy alone. The *structure* (how long
each user's history is, which category an item has, the gaps, shapes and
nums of the schedule) comes from seeds fixed in the configuration and the
traffic file; ``--seed`` draws the factors, the items inside the
histories, the unavailable items and the order of the schedule. The
stages run as children (``python benchmarks/lib/ecom.py <stage> ...``),
held to the CPU; ``offer`` is the generator and imports neither the
program nor JAX.
"""

from __future__ import annotations

import asyncio
import datetime as dt
import json
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import data, loadgen  # noqa: E402

PLAIN, CATEGORY, BLACK_RETURN, WHITE_LIST, VIEW_RETURN = range(5)
SHAPES = ("plain", "categories", "blackList", "whiteList", "return_after_view")
# the nine days of the UserBehavior log (2017-11-25 to 2017-12-03)
T0_MS = int(dt.datetime(2017, 11, 25, tzinfo=dt.timezone.utc).timestamp() * 1e3)
NINE_DAYS_MS = 9 * 86_400_000


def out(**fields) -> None:
    print(json.dumps(fields), flush=True)


def user_name(code, n_held) -> str:
    """Entity names: the users with factors are ``u%06d``; codes from
    ``n_held`` up are visitors, ``v%06d``, whom the model does not know."""
    code = int(code)
    return data.user_name(code) if code < n_held else f"v{code - n_held:06d}"


def category_name(c) -> str:
    return f"c{int(c):04d}"


def item_categories(shape, config) -> np.ndarray:
    """[n_items] int32: one category an item, category sizes Zipf(1.0)
    over the categories (assumed; the source gives the counts only)."""
    rng = np.random.default_rng([config["data"]["structure_seed"], 1])
    return data.zipf_ids(
        shape["n_categories"], config["data"]["category_zipf_s"],
        shape["n_items"], rng,
    ).astype(np.int32)


def unavailable_items(shape, config, seed) -> np.ndarray:
    """Sorted ids of the items unavailable at deploy (a share of all)."""
    n = int(round(shape["n_items"] * config["data"]["unavailable_share"]))
    rng = np.random.default_rng([int(seed), 7])
    return np.sort(rng.choice(shape["n_items"], size=n, replace=False))


def held_users(config) -> int:
    """How many users' histories the store holds (all, or the cut)."""
    return int(config["data"].get("held_users") or config["shape"]["n_users"])


def histories(config, seed):
    """The store's events: (entity code, item, is_buy, time ms), sorted by
    entity. Codes below ``held_users`` are the known users in id order,
    the rest the visitors with 1-10 views each. History lengths are
    lognormal with the source's mean (structure seed: the same lengths
    every run), items inside them Zipf(0.9) over popularity-ordered item
    ids (``--seed``), times uniform over the log's nine days."""
    shape, d = config["shape"], config["data"]
    n_held, n_vis = held_users(config), int(d["visitors"])
    srng = np.random.default_rng([d["structure_seed"], 2])
    mu = np.log(d["history_mean"]) - d["history_sigma"] ** 2 / 2.0
    counts = np.clip(
        np.rint(srng.lognormal(mu, d["history_sigma"], n_held)), 1,
        d["history_cap"],
    ).astype(np.int64)
    vis_counts = srng.integers(1, 11, n_vis)
    counts = np.concatenate([counts, vis_counts])
    entity = np.repeat(np.arange(n_held + n_vis, dtype=np.int32), counts)
    n = len(entity)
    rng = np.random.default_rng([int(seed), 3])
    items = data.zipf_ids(shape["n_items"], d["item_zipf_s"], n, rng).astype(np.int32)
    buy_share = shape["events"]["buy"] / (
        shape["events"]["buy"] + shape["events"]["view"])
    is_buy = rng.random(n) < buy_share
    is_buy[entity >= n_held] = False  # the visitors have views only
    times = T0_MS + rng.integers(0, NINE_DAYS_MS, n)
    return entity, items, is_buy, times.astype(np.int64)


class History:
    """The bulk-loaded store as the comparison needs it: each entity's
    seen items and its views newest first."""

    def __init__(self, config, seed):
        self.entity, self.items, self.is_buy, self.times = histories(config, seed)
        self.starts = np.searchsorted(
            self.entity, np.arange(int(self.entity[-1]) + 2))

    def rows(self, code):
        return slice(self.starts[code], self.starts[code + 1])

    def seen(self, code) -> np.ndarray:
        return self.items[self.rows(code)]

    def views(self, code):
        """[(time ms, item)] of the entity's view events."""
        r = self.rows(code)
        keep = ~self.is_buy[r]
        return list(zip(self.times[r][keep].tolist(),
                        self.items[r][keep].tolist()))


# --- the schedule ---


def make_schedule(traffic, config, seconds, seed):
    """One window's requests, the same for the parent and the generator.

    The multiset of gaps, nums and shapes is fixed by ``schedule_seed``
    and the window's length; ``seed`` shuffles their order and draws the
    users, categories and whitelists. A *return* (shapes blackList and
    return_after_view) takes the user of a request due at least
    ``return_after_s`` earlier (``ref``); where none exists it is plain."""
    shape = config["shape"]
    n = int(round(traffic["rate_per_s"] * seconds))
    n_held = held_users(config)
    base = np.random.default_rng(traffic["schedule_seed"])
    gaps = base.exponential(size=n)
    gaps *= seconds / gaps.sum()
    nums = base.choice(
        np.asarray(traffic["num"]["values"]), size=n,
        p=np.asarray(traffic["num"]["weights"], np.float64))
    weights = np.asarray([traffic["shapes"][s] for s in SHAPES], np.float64)
    shapes = base.choice(len(SHAPES), size=n, p=weights / weights.sum())
    visitor = base.random(n) < traffic["users"]["visitor_share"]
    order = np.random.default_rng([int(seed), 11])
    due = np.concatenate([[0.0], np.cumsum(gaps[order.permutation(n)])[:-1]])
    nums = nums[order.permutation(n)].astype(np.int64)
    pick = order.permutation(n)
    shapes, visitor = shapes[pick], visitor[pick]
    users = data.zipf_ids(n_held, traffic["users"]["zipf_s"], n, order)
    users[visitor] = n_held + order.integers(
        0, config["data"]["visitors"], int(visitor.sum()))
    cats = item_categories(shape, config)
    # the category of a uniformly drawn item: large ones are asked most
    category = cats[order.integers(0, shape["n_items"], n)]
    ref = np.full(n, -1, np.int64)
    returns = np.flatnonzero((shapes == BLACK_RETURN) | (shapes == VIEW_RETURN))
    earlier = np.searchsorted(due, due[returns] - traffic["return_after_s"],
                              side="right")  # requests due early enough
    for k, m in zip(returns, earlier):
        if m > 0:
            ref[k] = int(order.integers(0, m))
            users[k] = users[ref[k]]
        else:
            shapes[k] = PLAIN
    white = {}
    lo, hi = traffic["white_list_items"]
    by_cat = np.argsort(cats, kind="stable")
    starts = np.searchsorted(cats[by_cat], np.arange(shape["n_categories"] + 1))
    for k in np.flatnonzero(shapes == WHITE_LIST):
        c = int(category[k])
        members = by_cat[starts[c]:starts[c + 1]]
        size = min(len(members), int(order.integers(lo, hi + 1)))
        white[int(k)] = np.sort(order.choice(members, size=size, replace=False))
    return {"due": due, "users": users, "nums": nums, "shapes": shapes,
            "category": category, "ref": ref, "white": white,
            "n_held": n_held}


def body_of(sched, k, black=None):
    """The JSON body of request ``k`` (upstream's field names)."""
    body = {"user": user_name(sched["users"][k], sched["n_held"]),
            "num": int(sched["nums"][k])}
    shape = sched["shapes"][k]
    if shape == CATEGORY:
        body["categories"] = [category_name(sched["category"][k])]
    elif shape == WHITE_LIST:
        body["whiteList"] = [data.item_name(i) for i in sched["white"][k]]
    elif shape == BLACK_RETURN and black:
        body["blackList"] = black
    return body


def http_bytes(host, path, body) -> bytes:
    raw = json.dumps(body).encode()
    return (
        b"POST " + path.encode() + b" HTTP/1.1\r\nHost: " + host.encode()
        + b"\r\nContent-Type: application/json\r\nConnection: keep-alive\r\n"
        b"Content-Length: " + str(len(raw)).encode() + b"\r\n\r\n" + raw
    )


FIRST_ITEM = re.compile(rb'"item":\s*"([^"]+)"')


def served_items(body):
    """Item names of an answer's body, or None."""
    try:
        return [s["item"] for s in json.loads(body)["itemScores"]]
    except (ValueError, KeyError, TypeError):
        return None


# --- the generator ---


class EventClient:
    """One keep-alive connection to the Event Server: events go out one
    after another, each timed from its post to its acknowledgement."""

    def __init__(self, host, port, key, timeout_s):
        self.host, self.port, self.timeout_s = host, port, timeout_s
        self.path = f"/events.json?accessKey={key}"
        self.lock, self.reader, self.writer = asyncio.Lock(), None, None

    async def post(self, event, t0):
        """(posted, acked, status), seconds from the window's start."""
        async with self.lock:
            posted = time.perf_counter() - t0
            try:
                if self.writer is None:
                    self.reader, self.writer = await asyncio.open_connection(
                        self.host, self.port)
                self.writer.write(http_bytes(
                    f"{self.host}:{self.port}", self.path, event))
                await self.writer.drain()
                status, _ = await asyncio.wait_for(
                    loadgen._read_response(self.reader), self.timeout_s)
            except (OSError, asyncio.IncompleteReadError,
                    asyncio.TimeoutError, ValueError, IndexError):
                status = -1
                if self.writer is not None:
                    self.writer.close()
                self.reader = self.writer = None
            return posted, time.perf_counter() - t0, status


async def _connection(state):
    """One keep-alive connection to the engine: takes the next request
    not yet taken, waits until it is due (and, for a return after a view,
    until the Event Server has acknowledged the view), builds its body
    from what has been answered so far, sends it, waits for its answer."""
    sched, out_, t0 = state["sched"], state["out"], state["t0"]
    due, host = sched["due"], state["host"]
    reader = writer = None
    while True:
        k = state["next"]
        if k >= len(due):
            break
        state["next"] = k + 1
        wait = t0 + due[k] - time.perf_counter()
        if wait > 0:
            await asyncio.sleep(wait)
        rec, black = out_[k], None
        shape, j = sched["shapes"][k], int(sched["ref"][k])
        if shape == VIEW_RETURN:
            await state["view_done"][k].wait()
        elif shape == BLACK_RETURN:
            black = served_items(out_[j][3]) if out_[j][2] == 200 else None
            state["sent_as"][k] = {"black": black}
        payload = http_bytes(host, "/queries.json", body_of(sched, k, black))
        try:
            if writer is None:
                reader, writer = await asyncio.open_connection(
                    state["ip"], state["port"])
            rec[0] = time.perf_counter() - t0  # sent
            writer.write(payload)
            await writer.drain()
            status, body = await asyncio.wait_for(
                loadgen._read_response(reader), state["timeout_s"])
            rec[1] = time.perf_counter() - t0  # answered
            rec[2], rec[3] = status, body
            first = FIRST_ITEM.search(body) if status == 200 else None
            if first and state["constraint"] is None:
                state["firsts"].add(first.group(1).decode())
        except (OSError, asyncio.IncompleteReadError, asyncio.TimeoutError,
                asyncio.LimitOverrunError, ValueError, IndexError) as e:
            rec[1] = time.perf_counter() - t0
            rec[2], rec[3] = -1, repr(e).encode()
            if writer is not None:
                writer.close()
            reader = writer = None
    if writer is not None:
        writer.close()


async def _views(state, events):
    """For each return after a view, ``view_lead_s`` before it is due:
    POST a ``view`` of the first item of the earlier answer to the Event
    Server and wait for the acknowledgement. An earlier answer that is
    not in yet (or is empty) leaves the return a plain query."""
    sched, t0 = state["sched"], state["t0"]

    async def one(k):
        j = int(sched["ref"][k])
        wait = t0 + sched["due"][k] - state["view_lead_s"] - time.perf_counter()
        if wait > 0:
            await asyncio.sleep(wait)
        items = (served_items(state["out"][j][3])
                 if state["out"][j][2] == 200 else None)
        if items:
            posted, acked, status = await events.post({
                "event": "view", "entityType": "user",
                "entityId": user_name(sched["users"][k], sched["n_held"]),
                "targetEntityType": "item", "targetEntityId": items[0],
            }, t0)
            state["sent_as"][k] = {"viewed": items[0], "posted": posted,
                                   "acked": acked, "status": status}
        else:
            state["sent_as"][k] = {"viewed": None}
        state["view_done"][k].set()

    await asyncio.gather(*[one(k) for k in state["view_done"]])


async def _constraint(state, events, spec):
    """At ``constraint.at_s``: one ``$set`` of ``unavailableItems`` that
    adds the first item of every answer seen so far to the deploy-time
    list (upstream reads the single latest ``$set``, so it carries both)."""
    t0 = state["t0"]
    wait = t0 + spec["traffic"]["constraint"]["at_s"] - time.perf_counter()
    if wait > 0:
        await asyncio.sleep(wait)
    added = sorted(state["firsts"])
    state["constraint"] = {"added": added}  # and no first item is noted later
    posted, acked, status = await events.post({
        "event": "$set", "entityType": "constraint",
        "entityId": "unavailableItems",
        "properties": {"items": state["unavailable"] + added},
    }, t0)
    state["constraint"].update(posted=posted, acked=acked, status=status)


async def _drive(spec, sched):
    due = sched["due"]
    out_ = [[None, None, None, None] for _ in due]
    unavailable = [data.item_name(i) for i in np.load(spec["unavailable_path"])]
    state = {
        "firsts": set(), "unavailable": unavailable,
        "sched": sched, "out": out_, "next": 0, "sent_as": {},
        "timeout_s": spec["traffic"]["answer_timeout_s"],
        "view_lead_s": spec["traffic"]["view_lead_s"],
        "host": f"{spec['host']}:{spec['port']}", "ip": spec["host"],
        "port": spec["port"], "t0": time.perf_counter() + 0.05,
        "view_done": {int(k): asyncio.Event()
                      for k in np.flatnonzero(sched["shapes"] == VIEW_RETURN)},
        "constraint": None,
    }
    t0_wall = time.time() + 0.05
    events = EventClient(spec["host"], spec["event_port"], spec["access_key"],
                         spec["traffic"]["answer_timeout_s"])
    lag = {"worst_ms_by_second": [0.0] * (int(due[-1]) + 2)}
    watcher = asyncio.ensure_future(loadgen._watch_lag(state, lag))
    side = [asyncio.ensure_future(_views(state, events))]
    if spec["traffic"]["constraint"]["at_s"] < spec["seconds"]:
        side.append(asyncio.ensure_future(_constraint(state, events, spec)))
    await asyncio.gather(
        *[_connection(state) for _ in range(spec["traffic"]["connections"])])
    await asyncio.gather(*side)
    watcher.cancel()
    return out_, t0_wall, lag, state


def stage_offer(spec_path, out_path):
    """One window of the cell's traffic against an engine server and an
    Event Server that are up, from a process that does nothing else."""
    import gc

    with open(spec_path) as f:
        spec = json.load(f)
    with open(spec["config_path"]) as f:
        config = json.load(f)
    sched = make_schedule(spec["traffic"], config, spec["seconds"], spec["seed"])
    gc.disable()  # no collection may hold the loop up; the process is short
    answers, t_open, lag, state = asyncio.run(_drive(spec, sched))
    with open(out_path, "w") as f:
        json.dump({
            "t_open": t_open, "lag": lag, "constraint": state["constraint"],
            "sent_as": {str(k): v for k, v in state["sent_as"].items()},
            "out": [[sent, answered, status, (body or b"").decode("latin-1")]
                    for sent, answered, status, body in answers],
        }, f)
    out(offered=len(answers), lag_max_ms=max(lag["worst_ms_by_second"]))


# --- set-up stages (children held to the CPU) ---


def stage_load(config_path, seed, unavailable_path):
    """The event store as a shop's would stand at deploy: the histories
    bulk-imported (views and buys, sorted by user), the pages indexed by
    entity, the ``unavailableItems`` constraint set. Prints the seconds
    of each part and the app's access key."""
    from predictionio_tpu.data.event import DataMap, Event
    from predictionio_tpu.data.storage import get_storage

    with open(config_path) as f:
        config = json.load(f)
    shape, seed = config["shape"], int(seed)
    storage = get_storage()
    app = storage.get_meta_data_apps().get_by_name("bench")
    key = storage.get_meta_data_access_keys().get_by_app_id(app.id)[0].key
    t0 = time.time()
    entity, items, is_buy, times = histories(config, seed)
    t_made = time.time()
    events = storage.get_l_events()
    n_held = held_users(config)
    n = 0
    for name, pick in (("buy", is_buy), ("view", ~is_buy)):
        present_e, codes_e = data.dense_codes(
            entity[pick], int(entity.max()) + 1)
        present_i, codes_i = data.dense_codes(items[pick], shape["n_items"])
        n += events.insert_columns_encoded(
            app.id, event=name, entity_type="user", target_entity_type="item",
            entity_names=[user_name(v, n_held) for v in present_e],
            entity_codes=codes_e.astype(np.int32),
            target_names=[data.item_name(v) for v in present_i],
            target_codes=codes_i.astype(np.int32),
            values=np.ones(int(pick.sum()), np.float32),
            event_times_ms=times[pick],
        )
    t_loaded = time.time()
    pages = events.build_entity_index(app.id)
    t_indexed = time.time()
    gone = unavailable_items(shape, config, seed)
    np.save(unavailable_path, gone)
    events.insert(Event(
        event="$set", entity_type="constraint", entity_id="unavailableItems",
        properties=DataMap({"items": [data.item_name(i) for i in gone]}),
        event_time=dt.datetime.now(dt.timezone.utc),
    ), app.id)
    out(events=int(n), pages=int(pages), access_key=key,
        make_s=t_made - t0, load_s=t_loaded - t_made,
        index_s=t_indexed - t_loaded, unavailable=len(gone),
        constraint_s=time.time() - t_indexed)


def stage_write_instance(work, variant_path, config_path, seed):
    """A servable ``ECommModel`` whose factors no train produced: seeded
    rows and the items' categories as arrays, written as the workflow
    writes a model (`dumps_model`, the models and engine-instances DAOs),
    as ``stages.py::stage_write_instance`` does for ``ALSModel``."""
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.data.storage import get_storage
    from predictionio_tpu.data.storage.base import EngineInstance, Model
    from predictionio_tpu.models.ecommerce.engine import ECommModel
    from predictionio_tpu.tools.cli import engine_from_variant, load_variant
    from predictionio_tpu.utils.serialize import dumps_model
    from predictionio_tpu.workflow.core_workflow import STATUS_COMPLETED

    with open(config_path) as f:
        config = json.load(f)
    shape, seed = config["shape"], int(seed)
    t0 = time.time()
    model = ECommModel(
        user_factors=data.seeded_factors(shape["n_users"], shape["rank"], seed, 0),
        item_factors=data.seeded_factors(shape["n_items"], shape["rank"], seed, 1),
        user_index=BiMap({data.user_name(j): j for j in range(shape["n_users"])}),
        item_index=BiMap({data.item_name(j): j for j in range(shape["n_items"])}),
        category_names=tuple(
            category_name(c) for c in range(shape["n_categories"])),
        item_categories=item_categories(shape, config)[:, None],
    )
    t_made = time.time()
    variant = load_variant(variant_path)
    engine, factory_path = engine_from_variant(variant)
    params = engine.jvalue_to_engine_params(variant).to_json()
    storage = get_storage()
    now = dt.datetime.now(dt.timezone.utc)
    instance_id = storage.get_meta_data_engine_instances().insert(
        EngineInstance(
            id="", status=STATUS_COMPLETED, start_time=now, end_time=now,
            engine_id=variant["id"], engine_version=variant["version"],
            engine_variant=variant_path, engine_factory=factory_path,
            data_source_params=json.dumps(params["datasource"]),
            preparator_params=json.dumps(params["preparator"]),
            algorithms_params=json.dumps(params["algorithms"]),
            serving_params=json.dumps(params["serving"]),
        )
    )
    blob = dumps_model([model])
    del model
    storage.get_model_data_models().insert(Model(id=instance_id, models=blob))
    out(instance_id=instance_id, model_bytes=len(blob),
        make_s=t_made - t0, write_s=time.time() - t_made)


STAGES = {"load": stage_load, "write_instance": stage_write_instance,
          "offer": stage_offer}

if __name__ == "__main__":
    sys.exit(STAGES[sys.argv[1]](*sys.argv[2:]) or 0)
