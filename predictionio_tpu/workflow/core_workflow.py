"""CoreWorkflow: train/eval lifecycle with instance records + persistence.

Capability parity with reference core/.../workflow/CoreWorkflow.scala:
``run_train`` (:42-93 — context creation, engine.train, model serialization
into MODELDATA, EngineInstance INIT->COMPLETED, stop-after interruption
handling) and ``run_evaluation`` (:96-152 — EvaluationInstance record,
EvaluationWorkflow, result storage in one-liner/HTML/JSON forms). The thin
typed wrappers in reference Workflow.scala:82-135 collapse into these
functions; EvaluationWorkflow.scala:31-42 is ``run_evaluation``'s middle
two lines.
"""

from __future__ import annotations

import dataclasses
import datetime as _dt
import json
import logging
import traceback
from typing import List, Optional, Sequence

from predictionio_tpu.controller.engine import (
    BaseEngine,
    Engine,
    EngineParams,
    StopAfterPrepareInterruption,
    StopAfterReadInterruption,
)
from predictionio_tpu.controller.evaluation import Evaluation
from predictionio_tpu.data.storage.base import (
    STATUS_COMPLETED,
    STATUS_EVALUATING,
    STATUS_FAILED,
    STATUS_INIT,
    STATUS_TRAINING,
    EngineInstance,
    EvaluationInstance,
    Model,
)
from predictionio_tpu.utils import profiling
from predictionio_tpu.utils.serialize import dumps_model
from predictionio_tpu.workflow.context import WorkflowContext, workflow_context
from predictionio_tpu.workflow.workflow_params import WorkflowParams

logger = logging.getLogger(__name__)


def _is_rank_zero() -> bool:
    """True unless this process is a non-zero rank of a multi-host
    runtime. Storage writes (instance records, model blobs, evaluation
    results) happen on rank 0 only — the reference's driver-writes,
    executors-compute split."""
    try:
        import jax

        return jax.process_index() == 0
    except Exception:  # backend not initializable — single host
        return True


def _eval_engine(evaluation, engine_params_list, workflow_params):
    """The engine a grid evaluation runs through. Multi-variant grids
    upgrade a plain Engine to FastEvalEngine: stage results memoize
    across shared params-prefixes and reg-axis variants train in one
    vmapped device program (BaseAlgorithm.train_grid). Results are
    identical to the plain engine — FastEval is the reference's own
    eval-only engine (FastEvalEngine.scala:42-48); it leaves it opt-in
    only because its caches cost memory (WorkflowParams.fast_eval=False
    restores that). Every host of a multi-host run resolves the SAME
    engine here so their collective sequences agree."""
    engine = evaluation.engine
    if (
        workflow_params.fast_eval
        and type(engine) is Engine
        and len(engine_params_list) > 1
    ):
        from predictionio_tpu.controller.fast_eval import FastEvalEngine

        engine = FastEvalEngine(
            engine.data_source_class_map,
            engine.preparator_class_map,
            engine.algorithm_class_map,
            engine.serving_class_map,
        )
    return engine


def _utcnow() -> _dt.datetime:
    return _dt.datetime.now(_dt.timezone.utc)


class CoreWorkflow:
    @staticmethod
    def run_train(
        engine: BaseEngine,
        engine_params: EngineParams,
        engine_instance: EngineInstance,
        ctx: Optional[WorkflowContext] = None,
        workflow_params: Optional[WorkflowParams] = None,
    ) -> Optional[str]:
        """Train and persist. Returns the engine-instance id on success;
        None when interrupted by a stop-after debug flag, or on the
        worker (non-zero) ranks of a multi-host run, which compute but
        leave all storage writes to rank 0."""
        workflow_params = workflow_params or WorkflowParams()
        ctx = ctx or workflow_context(
            mode="training", batch=workflow_params.batch or engine_instance.batch
        )
        if not _is_rank_zero():
            # Worker hosts of a multi-host run participate in rank 0's
            # collectives by executing the same training program, but
            # leave every storage write to rank 0 (reference: only the
            # Spark driver writes; executors compute) — a shared store
            # would otherwise record one duplicate instance+model blob
            # per host.
            try:
                with profiling.trace(workflow_params.profile_dir):
                    engine.train(ctx, engine_params, workflow_params)
            except (
                StopAfterReadInterruption,
                StopAfterPrepareInterruption,
            ) as e:
                logger.info("training interrupted by %s", type(e).__name__)
            return None
        storage = ctx.storage
        instances = storage.get_meta_data_engine_instances()
        # record the resolved params on the instance so deploy can
        # reconstruct EngineParams (reference CreateWorkflow.scala:213-242)
        params_json = engine_params.to_json()
        instance_id = instances.insert(
            dataclasses.replace(
                engine_instance,
                status=STATUS_INIT,
                data_source_params=json.dumps(params_json["datasource"]),
                preparator_params=json.dumps(params_json["preparator"]),
                algorithms_params=json.dumps(params_json["algorithms"]),
                serving_params=json.dumps(params_json["serving"]),
            )
        )
        logger.info("run_train: engine instance %s created", instance_id)
        try:
            instances.update(
                dataclasses.replace(
                    instances.get(instance_id), status=STATUS_TRAINING
                )
            )
            with profiling.trace(workflow_params.profile_dir):
                models = engine.train(ctx, engine_params, workflow_params)
            # resource telemetry for the round: device memory_stats()
            # where the backend provides it, host RSS fallback — gauges
            # the continuous loop / hot-swap operator watches between
            # rounds (a leaking round shows here before it OOMs)
            from predictionio_tpu.utils import health as _health

            # logged as well: a one-shot `pio train` takes its registry
            # with it when it exits
            logger.info(
                "memory after training: %s",
                json.dumps(_health.record_memory_gauges(), sort_keys=True),
            )
            if ctx.timer.records:
                logger.info("training phases:\n%s", ctx.timer.summary())
                hidden = ctx.timer.overlapped_total()
                if hidden:
                    # overlapped records are pipeline busy time hidden
                    # UNDER the read/train walls above (streaming
                    # store→device path) — report what pipelining saved
                    # rather than double-counting it into the total
                    logger.info(
                        "streaming pipeline hid %.3fs of scan/pack/"
                        "compile work under the train wall clock",
                        hidden,
                    )
            if workflow_params.save_model:
                serializable = (
                    engine.make_serializable_models(
                        ctx, instance_id, engine_params, models
                    )
                    if hasattr(engine, "make_serializable_models")
                    else models
                )
                storage.get_model_data_models().insert(
                    Model(id=instance_id, models=dumps_model(serializable))
                )
            instances.update(
                dataclasses.replace(
                    instances.get(instance_id),
                    status=STATUS_COMPLETED,
                    end_time=_utcnow(),
                )
            )
            logger.info("run_train: engine instance %s completed", instance_id)
            return instance_id
        except (StopAfterReadInterruption, StopAfterPrepareInterruption) as e:
            logger.info("training interrupted by %s", type(e).__name__)
            instances.delete(instance_id)
            return None
        except Exception:
            logger.error("training failed:\n%s", traceback.format_exc())
            instances.update(
                dataclasses.replace(
                    instances.get(instance_id),
                    status=STATUS_FAILED,
                    end_time=_utcnow(),
                )
            )
            raise

    @staticmethod
    def run_evaluation(
        evaluation: Evaluation,
        engine_params_list: Sequence[EngineParams],
        evaluation_instance: Optional[EvaluationInstance] = None,
        ctx: Optional[WorkflowContext] = None,
        workflow_params: Optional[WorkflowParams] = None,
    ):
        """Evaluate a params grid; store + return the evaluator result."""
        workflow_params = workflow_params or WorkflowParams()
        engine_params_list = list(engine_params_list)  # may be a generator
        ctx = ctx or workflow_context(mode="evaluation", batch=workflow_params.batch)
        if not _is_rank_zero():
            # Worker hosts compute (joining rank 0's collectives) but
            # leave the instance record + result writes to rank 0. The
            # engine selection MUST mirror rank 0's (shared helper): a
            # FastEval rank 0 training each distinct variant once
            # alongside a plain-engine worker training per variant would
            # issue different collective sequences and deadlock the pod.
            # batch_eval holds ALL the device work; the evaluator stage
            # is host math with side effects (best.json, instance rows)
            # that must happen once — workers skip it and return None.
            engine = _eval_engine(
                evaluation, engine_params_list, workflow_params
            )
            engine.batch_eval(ctx, engine_params_list, workflow_params)
            return None
        storage = ctx.storage
        instances = storage.get_meta_data_evaluation_instances()
        if evaluation_instance is None:
            evaluation_instance = EvaluationInstance(
                id="",
                status="",
                start_time=_utcnow(),
                end_time=_utcnow(),
                evaluation_class=type(evaluation).__name__,
                batch=workflow_params.batch,
            )
        instance_id = instances.insert(
            dataclasses.replace(evaluation_instance, status=STATUS_EVALUATING)
        )
        try:
            engine = _eval_engine(
                evaluation, engine_params_list, workflow_params
            )
            # EvaluationWorkflow.runEvaluation (reference :31-42)
            engine_eval_data_set = engine.batch_eval(
                ctx, engine_params_list, workflow_params
            )
            result = evaluation.evaluator.evaluate_base(
                ctx, evaluation, engine_eval_data_set, workflow_params
            )
        except Exception:
            logger.error("evaluation failed:\n%s", traceback.format_exc())
            instances.update(
                dataclasses.replace(
                    instances.get(instance_id),
                    status=STATUS_FAILED,
                    end_time=_utcnow(),
                )
            )
            raise
        if result.no_save:
            # reference CoreWorkflow.scala:127-129 — result not inserted
            logger.info("evaluation result not inserted into database (no_save)")
            instances.delete(instance_id)
        else:
            instances.update(
                dataclasses.replace(
                    instances.get(instance_id),
                    status=STATUS_COMPLETED,
                    end_time=_utcnow(),
                    evaluator_results=result.to_one_liner(),
                    evaluator_results_html=result.to_html(),
                    evaluator_results_json=result.to_json(),
                )
            )
        logger.info(
            "run_evaluation: instance %s completed: %s",
            instance_id,
            result.to_one_liner(),
        )
        return result
