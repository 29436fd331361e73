"""End-to-end recommendation engine test: events -> store -> DASE train ->
persisted model -> predict -> k-fold evaluation. The minimum end-to-end
slice of SURVEY.md §7 step 4.
"""

import datetime as dt

import numpy as np
import pytest

from predictionio_tpu.controller import Evaluation, OptionAverageMetric
from predictionio_tpu.data.event import DataMap, Event
from predictionio_tpu.data.storage.base import App, EngineInstance
from predictionio_tpu.data.store import AppNotFoundError, PEventStore
from predictionio_tpu.models.recommendation import (
    ALSAlgorithm,
    ALSAlgorithmParams,
    DataSourceParams,
    PredictedResult,
    Query,
    recommendation_engine,
)
from predictionio_tpu.controller.engine import EngineParams
from predictionio_tpu.controller.params import EmptyParams
from predictionio_tpu.utils.serialize import loads_model
from predictionio_tpu.workflow import CoreWorkflow, WorkflowContext, WorkflowParams


def populate(storage, app_name="testapp", n_users=30, n_items=20, seed=0):
    """Two taste clusters: even users like even items, odd like odd."""
    apps = storage.get_meta_data_apps()
    app_id = apps.insert(App(0, app_name))
    le = storage.get_l_events()
    le.init(app_id)
    rng = np.random.default_rng(seed)
    t0 = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
    for u in range(n_users):
        liked = [i for i in range(n_items) if i % 2 == u % 2]
        for i in rng.choice(liked, size=min(6, len(liked)), replace=False):
            le.insert(
                Event(
                    event="rate",
                    entity_type="user",
                    entity_id=f"u{u}",
                    target_entity_type="item",
                    target_entity_id=f"i{int(i)}",
                    properties=DataMap({"rating": float(rng.integers(4, 6))}),
                    event_time=t0,
                ),
                app_id,
            )
        # also some dislikes of the other cluster
        disliked = [i for i in range(n_items) if i % 2 != u % 2]
        for i in rng.choice(disliked, size=3, replace=False):
            le.insert(
                Event(
                    event="rate",
                    entity_type="user",
                    entity_id=f"u{u}",
                    target_entity_type="item",
                    target_entity_id=f"i{int(i)}",
                    properties=DataMap({"rating": 1.0}),
                    event_time=t0,
                ),
                app_id,
            )
    return app_id


def engine_params(app_name="testapp", eval_k=None, **algo_kw):
    kw = dict(rank=8, num_iterations=8, lambda_=0.05)
    kw.update(algo_kw)
    algo = ALSAlgorithmParams(**kw)
    return EngineParams(
        data_source_params=("", DataSourceParams(app_name=app_name, eval_k=eval_k)),
        preparator_params=("", EmptyParams()),
        algorithm_params_list=(("als", algo),),
        serving_params=("", EmptyParams()),
    )


class TestStorageLayer:
    def test_find_columns(self, mem_storage):
        populate(mem_storage)
        store = PEventStore(mem_storage)
        cols = store.find_columns(
            "testapp", entity_type="user", target_entity_type="item",
            event_names=["rate"],
        )
        assert cols.n == 30 * 9
        assert len(cols.entity_index) == 30
        assert cols.values.max() == 5.0

    def test_unknown_app_raises(self, mem_storage):
        with pytest.raises(AppNotFoundError):
            PEventStore(mem_storage).find_columns("nope")


class TestEndToEnd:
    def test_train_persist_predict(self, mem_storage):
        populate(mem_storage)
        engine = recommendation_engine()
        ctx = WorkflowContext(mode="training", storage=mem_storage)
        now = dt.datetime.now(dt.timezone.utc)
        inst = EngineInstance(
            id="", status="", start_time=now, end_time=now,
            engine_id="rec", engine_version="1", engine_variant="engine.json",
            engine_factory="predictionio_tpu.models.recommendation",
        )
        iid = CoreWorkflow.run_train(engine, engine_params(), inst, ctx=ctx)
        assert iid
        [model] = loads_model(mem_storage.get_model_data_models().get(iid).models)
        # u0 likes even items: top recommendations should be even items it
        # rated highly or similar even items
        result = model.recommend("u0", 5)
        assert len(result.item_scores) == 5
        top_items = [s.item for s in result.item_scores]
        even_frac = sum(1 for it in top_items if int(it[1:]) % 2 == 0) / 5
        assert even_frac >= 0.8, top_items
        # unknown user -> empty result, not a crash
        assert model.recommend("ghost", 5) == PredictedResult()

    def test_batch_predict_matches_single(self, mem_storage):
        populate(mem_storage)
        engine = recommendation_engine()
        ctx = WorkflowContext(storage=mem_storage)
        models = engine.train(ctx, engine_params(), WorkflowParams())
        model = models[0]
        algo = ALSAlgorithm(ALSAlgorithmParams(rank=8))
        queries = [(0, Query("u0", 3)), (1, Query("ghost", 3)), (2, Query("u1", 4))]
        batch = dict(algo.batch_predict(model, queries))
        assert batch[0] == algo.predict(model, Query("u0", 3))
        assert batch[1] == PredictedResult()
        assert len(batch[2].item_scores) == 4

    def test_kfold_evaluation(self, mem_storage):
        populate(mem_storage)
        engine = recommendation_engine()
        ctx = WorkflowContext(storage=mem_storage)

        class PrecisionAtN(OptionAverageMetric):
            def calculate_point(self, q, p, a):
                if not p.item_scores:
                    return None
                hits = sum(1 for s in p.item_scores if s.item in a.items)
                return hits / len(p.item_scores)

        evaluation = Evaluation().set_engine_metric(engine, PrecisionAtN())
        grid = [
            engine_params(eval_k=2),
            engine_params(eval_k=2, rank=2),
        ]
        result = CoreWorkflow.run_evaluation(evaluation, grid, ctx=ctx)
        assert len(result.engine_params_scores) == 2
        assert 0.0 <= result.best_score.score <= 1.0
