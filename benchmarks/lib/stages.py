"""Helpers that run as children of the harness.

``load``, ``export``, ``write_instance`` and ``reduce_trace`` are held to
the CPU by their environment (they import the program's storage layer, or
JAX's trace reader, and must never take the chip). ``offer`` is the load
generator: it imports neither the program nor JAX.
"""

from __future__ import annotations

import datetime as dt
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import data, trace  # noqa: E402


def out(**fields) -> None:
    print(json.dumps(fields), flush=True)


def stage_load(work, app_name):
    """Bulk-insert seeded `rate` events: ``insert_columns_encoded`` is the
    vectorized path `insert_columns` factorizes into (the ids arrive as
    integer codes, so the 20M-string factorization is skipped)."""
    from predictionio_tpu.data.storage import get_storage

    storage = get_storage()
    u, i, r = (np.load(os.path.join(work, f"{app_name}_{c}.npy")) for c in "uir")
    app = storage.get_meta_data_apps().get_by_name(app_name)
    t0 = time.time()
    present_u, codes_u = data.dense_codes(u, int(u.max()) + 1)
    present_i, codes_i = data.dense_codes(i, int(i.max()) + 1)
    n = storage.get_l_events().insert_columns_encoded(
        app.id, event="rate", entity_type="user", target_entity_type="item",
        entity_names=[data.user_name(v) for v in present_u],
        entity_codes=codes_u.astype(np.int32),
        target_names=[data.item_name(v) for v in present_i],
        target_codes=codes_i.astype(np.int32),
        values=r,
    )
    out(app=app_name, events=int(n), seconds=time.time() - t0)


def stage_export(work, *instance_ids):
    """Persisted factors, as plain arrays in raw-id order. Names are
    u%06d / i%05d, so the raw id is in the name; ``*_ids.npy`` holds the
    raw id of each factor row."""
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
            ids = np.empty(len(index), np.int64)
            for name, row in index.items():
                ids[row] = int(name[1:])
            np.save(os.path.join(d, f"{side}_ids.npy"), ids)
        out(exported=instance_id)


def stage_write_instance(work, variant_path, n_users, n_items, rank, seed):
    """A servable engine instance whose factors no train produced: seeded
    rows, written as the workflow itself writes a model (`dumps_model`,
    the models and engine-instances DAOs). The inverse of ``export``."""
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.data.storage import get_storage
    from predictionio_tpu.data.storage.base import EngineInstance, Model
    from predictionio_tpu.models.recommendation.engine import ALSModel
    from predictionio_tpu.ops.als import ALSModelArrays
    from predictionio_tpu.tools.cli import engine_from_variant, load_variant
    from predictionio_tpu.utils.serialize import dumps_model
    from predictionio_tpu.workflow.core_workflow import STATUS_COMPLETED

    n_users, n_items, rank, seed = (int(v) for v in (n_users, n_items, rank, seed))
    t0 = time.time()
    model = ALSModel(
        arrays=ALSModelArrays(
            user_factors=data.seeded_factors(n_users, rank, seed, 0),
            item_factors=data.seeded_factors(n_items, rank, seed, 1),
        ),
        user_index=BiMap({data.user_name(j): j for j in range(n_users)}),
        item_index=BiMap({data.item_name(j): j for j in range(n_items)}),
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


def stage_reduce_trace(trace_dir, out_path, *patterns):
    """Reduce the newest ``*.xplane.pb`` under ``trace_dir`` (a host-only
    child: the chip's owner has exited or is another process)."""
    with open(out_path, "w") as f:
        json.dump(trace.reduce_dir(trace_dir, patterns), f)
    out(reduced=out_path)


def stage_offer(spec_path, out_path):
    """One window of traffic against a server that is up, from a process
    that does nothing else: the schedule made from the seed, offered, and
    every answer written down for the parent."""
    import gc

    from lib import loadgen

    with open(spec_path) as f:
        spec = json.load(f)
    due, users, nums = loadgen.make_schedule(
        spec["traffic"], spec["seconds"], spec["seed"]
    )
    gc.disable()  # no collection may hold the loop up; the process is short
    answers, t_open, lag = loadgen.drive(
        spec["host"], spec["port"], due, users, nums,
        spec["traffic"]["connections"], spec["traffic"]["answer_timeout_s"],
    )
    with open(out_path, "w") as f:
        json.dump({
            "t_open": t_open, "lag": lag,
            "out": [[sent, answered, status, (body or b"").decode("latin-1")]
                    for sent, answered, status, body in answers],
        }, f)
    out(offered=len(answers), lag_max_ms=max(lag["worst_ms_by_second"]))


STAGES = {
    "load": stage_load, "export": stage_export,
    "write_instance": stage_write_instance,
    "reduce_trace": stage_reduce_trace, "offer": stage_offer,
}

if __name__ == "__main__":
    sys.exit(STAGES[sys.argv[1]](*sys.argv[2:]) or 0)
