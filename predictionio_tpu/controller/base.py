"""DASE base abstractions: DataSource / Preparator / Algorithm / Serving.

Capability parity with the reference's type-erased core
(core/src/main/scala/io/prediction/core/BaseDataSource.scala:31,
BasePreparator.scala:32, BaseAlgorithm.scala:55, BaseServing.scala:28,
BaseEngine.scala:35) and the typed controller variants
(controller/{PDataSource,LDataSource,PPreparator,LPreparator,
P2LAlgorithm,PAlgorithm,LAlgorithm,LServing}.scala).

Design divergence, deliberate: the reference needs a P (distributed-model) /
P2L (distributed-train, local-model) / L (local) split because Spark
distinguishes RDD-resident from driver-resident values. JAX erases that
split — a model is a pytree whose leaves may be host numpy arrays or
device-sharded jax.Arrays; the same class covers all three cases. The
``sharded_model`` flag records intent (whether leaves should live sharded in
HBM across the mesh) and decides persistence handling.

Components receive a WorkflowContext (the SparkContext analog carrying
storage + the device mesh) in their lifecycle methods.
"""

from __future__ import annotations

import abc
import inspect
from typing import Any, Generic, List, Optional, Sequence, Tuple, TypeVar

from predictionio_tpu.annotation import developer_api
from predictionio_tpu.controller.params import EmptyParams, Params

TD = TypeVar("TD")  # training data
EI = TypeVar("EI")  # evaluation info
PD = TypeVar("PD")  # prepared data
M = TypeVar("M")  # model
Q = TypeVar("Q")  # query
P = TypeVar("P")  # predicted result
A = TypeVar("A")  # actual result


class SanityCheck(abc.ABC):
    """Data-validation hook (reference controller/SanityCheck.scala:30).
    Implement on TrainingData/PreparedData/models; the workflow invokes
    ``sanity_check()`` after each stage unless skipped."""

    @abc.abstractmethod
    def sanity_check(self) -> None: ...


@developer_api  # reference core/AbstractDoer.scala:25
def doer(cls, params: Optional[Params] = None):
    """Instantiate a controller class with (params) or zero-arg constructor
    (reference Doer.apply, core/AbstractDoer.scala:33-66). The instance's
    params are always available as ``self.params``."""
    params = params if params is not None else EmptyParams()
    # an EmptyParams slot (EngineParams default) upgrades to the class's
    # declared params defaults, mirroring Controller.__init__
    if isinstance(params, EmptyParams) and getattr(cls, "params_class", None):
        params = cls.params_class()
    try:
        sig = inspect.signature(cls.__init__)
        takes_params = any(n != "self" for n in sig.parameters)
    except (TypeError, ValueError):
        takes_params = True
    if takes_params:
        obj = cls(params)
    else:
        obj = cls()
        if not isinstance(getattr(obj, "params", None), Params) or isinstance(
            getattr(obj, "params", None), EmptyParams
        ):
            obj.params = params
    return obj


class Controller:
    """Common base: every DASE component may take a Params in its
    constructor; ``self.params`` is always set (by the ctor or by doer).
    A declared ``params_class`` supplies the default (all-defaults)
    instance when none is given."""

    params_class: Optional[type] = None

    def __init__(self, params: Optional[Params] = None):
        if params is not None:
            self.params = params
        elif type(self).params_class is not None:
            self.params = type(self).params_class()
        else:
            self.params = EmptyParams()


class BaseDataSource(Controller, Generic[TD, EI, Q, A]):
    """Reads training / evaluation data from the event store
    (reference core/BaseDataSource.scala:31-52)."""

    def read_training(self, ctx) -> TD:
        raise NotImplementedError

    def read_eval(self, ctx) -> List[Tuple[TD, EI, List[Tuple[Q, A]]]]:
        """Return evaluation folds: (training data, eval info, (query,
        actual) pairs). Default: no eval data (reference PDataSource
        readEval default)."""
        return []


class BasePreparator(Controller, Generic[TD, PD]):
    """Transforms TrainingData into PreparedData
    (reference core/BasePreparator.scala:32-42)."""

    def prepare(self, ctx, training_data: TD) -> PD:
        raise NotImplementedError


class IdentityPreparator(BasePreparator[TD, TD]):
    """Pass-through preparator (reference controller/IdentityPreparator.scala:30-92)."""

    def prepare(self, ctx, training_data: TD) -> TD:
        return training_data


class BaseAlgorithm(Controller, Generic[PD, M, Q, P]):
    """Trains a model and predicts (reference core/BaseAlgorithm.scala:55-123).

    ``sharded_model=True`` declares that model leaves live device-sharded
    across the mesh (the reference's PAlgorithm role); such models are
    re-materialized at deploy rather than naively serialized, unless the
    model implements PersistentModel.
    """

    sharded_model: bool = False

    # Param field names allowed to differ between variants that train
    # TOGETHER in one batched device program (see train_grid). Empty =
    # this algorithm has no device-side grid path; the eval grid falls
    # back to thread-parallel per-variant training.
    GRID_AXES: Tuple[str, ...] = ()

    # Whether predict/batch_predict dispatches device programs over a
    # multi-process mesh. False (every current algorithm: serving runs
    # local single-device programs) lets a fully grid-pretrained
    # multi-host evaluation thread-parallelize its serving stages; an
    # algorithm that serves THROUGH mesh collectives must set True so
    # the multi-host grid keeps its collective-order-safe serialization
    # (controller/engine.py _run_grid).
    MESH_SERVING: bool = False

    def train(self, ctx, prepared_data: PD) -> M:
        raise NotImplementedError

    @classmethod
    def train_grid(
        cls, ctx, prepared_data: PD, algos: Sequence["BaseAlgorithm"]
    ) -> Optional[List[M]]:
        """Train several param-variants of this algorithm in ONE batched
        device program, returning one model per entry of ``algos`` (same
        order), or None when these variants can't be batched (the caller
        falls back to per-variant ``train``). Called by the FastEval grid
        with variants whose params differ only in ``GRID_AXES`` fields.

        No reference analog: the reference's grid parallelism is host
        threads (`.par`, MetricEvaluator.scala:221-230). On TPU, a
        vmapped train amortizes dispatch and batches the per-variant
        math onto the MXU — see ops/als.py train_als_grid."""
        return None

    def predict(self, model: M, query: Q) -> P:
        raise NotImplementedError

    def batch_predict(self, model: M, queries: Sequence[Tuple[int, Q]]) -> List[Tuple[int, P]]:
        """Predict for indexed queries (reference P2LAlgorithm.batchPredict
        default ``qs.mapValues(predict)``, P2LAlgorithm.scala:66). Override
        with a vectorized device predict for the TPU fast path."""
        return [(i, self.predict(model, q)) for i, q in queries]

    # An algorithm MAY define ``prepare_query(self, model, query)``: the
    # part of a query's serving work that depends on that query alone
    # (a read of the user's history, name -> id lookups), returning an
    # opaque prepared value. The engine server then starts it when the
    # query ARRIVES, on a pool thread of the executor, instead of when
    # its micro-batch closes, and hands the values to ``batch_predict``
    # as a third argument: ``batch_predict(model, queries, prepared)``,
    # ``prepared[k]`` belonging to ``queries[k]``, ``None`` where there
    # is none (and ``prepared`` itself None from callers that prepared
    # nothing: ``pio eval``, ``predict``), which the algorithm then
    # prepares inline by the same function. CONTRACT: the step (and the
    # serving's ``supplement``, applied to the query before it) runs
    # concurrently with ``batch_predict`` and with itself, whatever
    # ``pipeline_depth`` is: it reads shared state and mutates none.
    # No reference analog; an algorithm that does not define it is
    # served exactly as before (no pool, ``batch_predict(model, queries)``).
    prepare_query = None

    def prepare_serving(self, ctx, model: M) -> M:
        """Deploy-time hook between model resolution and warm-up
        (Engine.prepare_deploy calls it per algorithm): attach serving
        resources to the model — e.g. the workflow mesh, so top-N
        serving runs data-parallel over every attached device instead of
        chip 0 only. Default: model unchanged. No reference analog (one
        JVM, no accelerator topology to bind)."""
        return model

    def warm(self, model: M) -> None:
        """Deploy-time warm-up hook (no reference analog — JIT frameworks
        need it): compile the serving executables NOW so the first real
        queries don't pay multi-second cold-compile tail latency. Called
        once per algorithm when a DeployedEngine is constructed. Default:
        nothing."""

    def serving_precision(self, model: M) -> Optional[str]:
        """The residency precision ("float32"/"bf16"/"int8") the model's
        prepared serving state stores the catalog at, or None when no
        quantization-aware serving state exists (training-time predicts,
        or an engine without the retrieval tier). Surfaces in the engine
        server's status.json per deployed version. Default: None."""
        return None

    def release_serving(self, model: M) -> None:
        """Undeploy-time inverse of ``prepare_serving`` (no reference
        analog): free the device-resident serving state a displaced
        model holds, called by the promotion pipeline's drain→release
        step only after the model's last in-flight batch resolved
        (DeployedEngine.release). CONTRACT: a query racing past the
        release must still be servable — implementations null the
        device-state fields so predict falls back to the host
        (training-time) path instead of erroring. Default: nothing."""

    # --- query class resolution (reference queryClass via TypeResolver) ---

    def query_from_json(self, json_obj: Any) -> Q:
        """Build a query from a JSON payload. Default: if the class declares
        a ``query_class`` dataclass, construct it; otherwise pass the raw
        dict through."""
        qcls = getattr(self, "query_class", None)
        if qcls is not None:
            from predictionio_tpu.controller.params import params_from_json

            return params_from_json(json_obj, qcls)
        return json_obj

    def result_to_json(self, result: P) -> Any:
        """Serialize a predicted result to JSON. Dataclasses serialize
        field-wise; other values must be JSON-compatible already."""
        import dataclasses

        if dataclasses.is_dataclass(result) and not isinstance(result, type):
            return dataclasses.asdict(result)
        return result


class BaseServing(Controller, Generic[Q, P]):
    """Combines per-algorithm predictions into the served result
    (reference core/BaseServing.scala:28-51)."""

    def supplement(self, query: Q) -> Q:
        """Pre-process the query (default identity, LServing.scala:31-52)."""
        return query

    def serve(self, query: Q, predictions: Sequence[P]) -> P:
        raise NotImplementedError


class LServing(BaseServing[Q, P]):
    """Alias kept for reference-parity naming."""


class FirstServing(BaseServing[Q, P]):
    """Serves the first algorithm's prediction
    (reference controller/LFirstServing.scala:24-39)."""

    def serve(self, query: Q, predictions: Sequence[P]) -> P:
        return predictions[0]


class AverageServing(BaseServing[Q, float]):
    """Averages numeric predictions
    (reference controller/LAverageServing.scala:24-41)."""

    def serve(self, query: Q, predictions: Sequence[float]) -> float:
        return sum(predictions) / len(predictions)


# reference-parity aliases: the P/P2L/L split collapses in JAX (see module
# docstring); these names exist so engine code reads like the reference's.
PDataSource = BaseDataSource
LDataSource = BaseDataSource
PPreparator = BasePreparator
LPreparator = BasePreparator
P2LAlgorithm = BaseAlgorithm
LAlgorithm = BaseAlgorithm


class PAlgorithm(BaseAlgorithm[PD, M, Q, P]):
    """Algorithm whose model is device-sharded across the mesh
    (reference controller/PAlgorithm.scala:44)."""

    sharded_model = True
