"""The Similar Product cell's inputs, its host-side stages and its load
generator.

Everything is made from seeds with numpy alone. The *structure* (which
category an item has, the gaps, nums, shapes, item counts and list sizes
of the schedule) comes from seeds fixed in the configuration and the
traffic file; ``--seed`` draws the factors, the items of every query and
list, and the order of the schedule. The stages run as children
(``python benchmarks/lib/similar.py <stage> ...``), held to the CPU;
``offer`` is the generator (``loadgen._drive`` over this cell's bodies)
and imports neither the program nor JAX.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import datetime as dt
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import data, ecom, loadgen  # noqa: E402

SHAPES = ("plain", "categories", "blackList", "whiteList",
          "category_blackList")
PLAIN, CATEGORY, BLACK_LIST, WHITE_LIST, CATEGORY_BLACK = range(5)
# rows a worker draws at a time while it fills the table (32 MB at 512)
FILL_ROWS = 1 << 14

out = ecom.out
item_categories = ecom.item_categories  # [n_items] int32, Zipf sizes
category_name = ecom.category_name


# --- the schedule ---


def make_schedule(traffic, config, seconds, seed):
    """One window's requests, the same for the parent and the generator.

    The multiset of gaps, nums, shapes, query-item counts and list sizes
    is fixed by ``schedule_seed`` and the window's length; ``seed``
    shuffles their order and draws the items: the query items
    Zipf(``items.zipf_s``) over the whole catalog (a repeated draw inside
    one query is dropped), a blackList the same way, a whiteList uniform
    among the members of the category of a uniformly drawn item (large
    categories are asked most). A ``categories`` query asks for its first
    query item's own category."""
    shape = config["shape"]
    n_items = shape["n_items"]
    n = int(round(traffic["rate_per_s"] * seconds))
    base = np.random.default_rng(traffic["schedule_seed"])
    gaps = base.exponential(size=n)
    gaps *= seconds / gaps.sum()
    nums = base.choice(
        np.asarray(traffic["num"]["values"]), size=n,
        p=np.asarray(traffic["num"]["weights"], np.float64))
    weights = np.asarray([traffic["shapes"][s] for s in SHAPES], np.float64)
    shapes = base.choice(len(SHAPES), size=n, p=weights / weights.sum())
    bands = traffic["items"]["count"]  # [[lo, hi, share], ...]
    band = base.choice(len(bands), size=n,
                       p=np.asarray([b[2] for b in bands], np.float64))
    counts = np.asarray([
        base.integers(bands[b][0], bands[b][1] + 1) for b in band], np.int64)
    black_n = base.integers(traffic["black_list_items"][0],
                            traffic["black_list_items"][1] + 1, n)
    white_n = base.integers(traffic["white_list_items"][0],
                            traffic["white_list_items"][1] + 1, n)
    order = np.random.default_rng([int(seed), 11])
    due = np.concatenate([[0.0], np.cumsum(gaps[order.permutation(n)])[:-1]])
    nums = nums[order.permutation(n)].astype(np.int64)
    pick = order.permutation(n)
    shapes, counts, black_n, white_n = (
        a[pick] for a in (shapes, counts, black_n, white_n))
    zipf_s = traffic["items"]["zipf_s"]
    drawn = data.zipf_ids(n_items, zipf_s, int(counts.sum()), order)
    starts = np.concatenate([[0], np.cumsum(counts)])
    items = [_distinct(drawn[starts[k]:starts[k + 1]]) for k in range(n)]
    cats = item_categories(shape, config)
    category = np.full(n, -1, np.int64)
    asks = np.flatnonzero((shapes == CATEGORY) | (shapes == CATEGORY_BLACK))
    category[asks] = cats[[items[k][0] for k in asks]]
    lists = np.flatnonzero(
        (shapes == BLACK_LIST) | (shapes == CATEGORY_BLACK))
    drawn = data.zipf_ids(n_items, zipf_s, int(black_n[lists].sum()), order)
    starts = np.concatenate([[0], np.cumsum(black_n[lists])])
    black = {int(k): np.unique(drawn[starts[j]:starts[j + 1]])
             for j, k in enumerate(lists)}
    white = {}
    by_cat = np.argsort(cats, kind="stable")
    starts = np.searchsorted(
        cats[by_cat], np.arange(shape["n_categories"] + 1))
    for k in np.flatnonzero(shapes == WHITE_LIST):
        c = int(cats[order.integers(0, n_items)])
        members = by_cat[starts[c]:starts[c + 1]]
        size = min(len(members), int(white_n[k]))
        white[int(k)] = np.sort(
            order.choice(members, size=size, replace=False))
    return {"due": due, "nums": nums, "shapes": shapes, "items": items,
            "category": category, "black": black, "white": white}


def _distinct(ids):
    """The ids in the order drawn, each once."""
    _, first = np.unique(ids, return_index=True)
    return ids[np.sort(first)]


def body_of(sched, k):
    """The JSON body of request ``k`` (upstream's field names)."""
    body = {"items": [data.item_name(i) for i in sched["items"][k]],
            "num": int(sched["nums"][k])}
    if sched["category"][k] >= 0:
        body["categories"] = [category_name(sched["category"][k])]
    if k in sched["black"]:
        body["blackList"] = [data.item_name(i) for i in sched["black"][k]]
    if k in sched["white"]:
        body["whiteList"] = [data.item_name(i) for i in sched["white"][k]]
    return body


# --- the factors, written in row blocks straight into a .npy ---


def stream_pieces(n_rows, rank, seed, stream, side=1):
    """``data.seeded_factors``' recipe, value for value, for one of its
    FACTOR_BLOCKS streams: (first row, [rows, rank] float32 block) pairs
    over that stream's row range, ``FILL_ROWS`` rows at a time (a
    generator draws its normals one after another, so the pieces are the
    whole)."""
    seeds = np.random.SeedSequence([int(seed), side]).spawn(data.FACTOR_BLOCKS)
    edges = np.linspace(0, n_rows, data.FACTOR_BLOCKS + 1).astype(np.int64)
    rng = np.random.default_rng(seeds[stream])
    scale = np.float32(rank ** -0.25)
    for a in range(int(edges[stream]), int(edges[stream + 1]), FILL_ROWS):
        e = min(a + FILL_ROWS, int(edges[stream + 1]))
        block = rng.standard_normal((e - a, rank), dtype=np.float32)
        block *= scale
        yield a, block


def fill_factors(table, seed):
    """The seeded item table into ``table`` ([n_rows, rank] float32, here
    a file mapped into memory), eight threads: never more of it in
    memory than the blocks being drawn."""
    def fill(stream):
        for a, block in stream_pieces(*table.shape, seed, stream):
            table[a:a + len(block)] = block

    with concurrent.futures.ThreadPoolExecutor(8) as pool:
        list(pool.map(fill, range(data.FACTOR_BLOCKS)))


# --- set-up stages (children held to the CPU) ---


def stage_write_instance(work, variant_path, config_path, seed):
    """A servable ``SPModel`` whose factors no train produced, persisted
    as the workflow persists one: the engine-instances DAO, the engine's
    own ``make_serializable_models`` (which calls ``SPModel.save``) and
    the models DAO for the manifest. The float32 table is drawn in row
    blocks straight into the file ``save`` would write, so that set-up
    never holds it; ``save`` finds it in place and flushes it."""
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.data.storage import get_storage
    from predictionio_tpu.data.storage.base import EngineInstance, Model
    from predictionio_tpu.tools.cli import engine_from_variant, load_variant
    from predictionio_tpu.utils.serialize import dumps_model
    from predictionio_tpu.workflow.core_workflow import STATUS_COMPLETED

    with open(config_path) as f:
        config = json.load(f)
    shape, seed = config["shape"], int(seed)
    # first what a program without this configuration refuses: its
    # params, then the model's own file layout
    variant = load_variant(variant_path)
    engine, factory_path = engine_from_variant(variant)
    engine_params = engine.jvalue_to_engine_params(variant)
    from predictionio_tpu.models.similarproduct.engine import SPModel

    params = engine_params.to_json()
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
    t0 = time.time()
    path = SPModel.factors_path(instance_id)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    table = np.lib.format.open_memmap(
        path, mode="w+", dtype=np.float32,
        shape=(shape["n_items"], shape["rank"]))
    fill_factors(table, seed)
    t_filled = time.time()
    model = SPModel(
        item_factors=table,
        item_index=BiMap(
            {data.item_name(j): j for j in range(shape["n_items"])}),
        category_names=tuple(
            category_name(c) for c in range(shape["n_categories"])),
        item_categories=item_categories(shape, config)[:, None],
    )
    t_made = time.time()
    persisted = engine.make_serializable_models(
        None, instance_id, engine_params, [model])
    blob = dumps_model(persisted)
    storage.get_model_data_models().insert(Model(id=instance_id, models=blob))
    out(instance_id=instance_id, factors_path=path,
        table_bytes=os.path.getsize(path), manifest_bytes=len(blob),
        fill_s=t_filled - t0, index_s=t_made - t_filled,
        save_s=time.time() - t_made)


def stage_offer(spec_path, out_path):
    """One window of the cell's traffic against an engine server that is
    up, from a process that does nothing else."""
    import gc

    with open(spec_path) as f:
        spec = json.load(f)
    with open(spec["config_path"]) as f:
        config = json.load(f)
    sched = make_schedule(spec["traffic"], config, spec["seconds"], spec["seed"])
    host = f"{spec['host']}:{spec['port']}"
    payloads = [ecom.http_bytes(host, "/queries.json", body_of(sched, k))
                for k in range(len(sched["due"]))]
    gc.disable()  # no collection may hold the loop up; the process is short
    answers, t_open, lag = asyncio.run(loadgen._drive(
        spec["host"], spec["port"], sched["due"], payloads,
        spec["traffic"]["connections"], spec["traffic"]["answer_timeout_s"]))
    with open(out_path, "w") as f:
        json.dump({
            "t_open": t_open, "lag": lag,
            "out": [[sent, answered, status, (body or b"").decode("latin-1")]
                    for sent, answered, status, body in answers],
        }, f)
    out(offered=len(answers), lag_max_ms=max(lag["worst_ms_by_second"]))


STAGES = {"write_instance": stage_write_instance, "offer": stage_offer}

if __name__ == "__main__":
    sys.exit(STAGES[sys.argv[1]](*sys.argv[2:]) or 0)
