"""Recommendation engine: DASE components around the TPU ALS kernel.

Reference mapping (examples/scala-parallel-recommendation/custom-query/src/main/scala/):
- Query/PredictedResult/ItemScore    <- Engine.scala
- DataSource (PEventStore rate/buy reads, k-fold eval split) <- DataSource.scala
- Preparator (ratings pass-through)  <- Preparator.scala
- ALSAlgorithm (MLlib ALS -> ops.als.train_als; cosine/dot top-N predict)
                                     <- ALSAlgorithm.scala:24-105
- Serving (first prediction)         <- Serving.scala
"""

from __future__ import annotations

import dataclasses
import logging
from typing import List, Optional, Sequence, Tuple

import numpy as np

from predictionio_tpu.controller import (
    BaseAlgorithm,
    BaseDataSource,
    BasePreparator,
    EngineFactory,
    FirstServing,
    Params,
    SanityCheck,
)
from predictionio_tpu.controller.engine import Engine
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.data.store import PEventStore
from predictionio_tpu.ops.als import (
    ALSConfig,
    ALSModelArrays,
    ServingFactors,
    train_als,
    validate_solver,
)
from predictionio_tpu.ops.retrieval import ItemRetriever
from predictionio_tpu.utils import tracing as _tracing

logger = logging.getLogger(__name__)


# --- queries and results (reference Engine.scala) ---


@dataclasses.dataclass(frozen=True)
class Query:
    user: str
    num: int = 10


@dataclasses.dataclass(frozen=True)
class ItemScore:
    item: str
    score: float


@dataclasses.dataclass(frozen=True)
class PredictedResult:
    item_scores: Tuple[ItemScore, ...] = ()

    def __post_init__(self):
        object.__setattr__(
            self,
            "item_scores",
            tuple(
                s if isinstance(s, ItemScore) else ItemScore(**s)
                for s in self.item_scores
            ),
        )


@dataclasses.dataclass(frozen=True)
class ActualResult:
    items: Tuple[str, ...] = ()


# --- training data ---


@dataclasses.dataclass
class Rating:
    user: str
    item: str
    rating: float


@dataclasses.dataclass
class TrainingData(SanityCheck):
    user_idx: np.ndarray
    item_idx: np.ndarray
    ratings: np.ndarray
    user_index: BiMap
    item_index: BiMap

    def sanity_check(self) -> None:
        if len(self.ratings) == 0:
            raise ValueError(
                "ratings is empty — is the event store populated with "
                "rate/buy events?"
            )


class StreamingTrainingData(TrainingData):
    """Lazy TrainingData backed by a chunked store scan.

    The ALS algorithm feeds ``stream_factory`` straight into the
    streaming store→device pipeline (``ops/streaming``) without ever
    materializing the rating columns on host; any other consumer that
    touches the column attributes transparently materializes through the
    monolithic scan, so the DASE contract is unchanged."""

    def __init__(self, stream_factory, loader):
        # no super().__init__: columns materialize on first attribute
        # access through the class-level properties below
        self._stream_factory = stream_factory
        self._loader = loader
        self._td: Optional[TrainingData] = None

    @property
    def stream_factory(self):
        """() -> ColumnarStream for the streaming trainer (a FRESH
        stream per call: fingerprints are read at stream creation)."""
        return self._stream_factory

    def materialize(self) -> TrainingData:
        if self._td is None:
            self._td = self._loader()
        return self._td

    user_idx = property(lambda self: self.materialize().user_idx)
    item_idx = property(lambda self: self.materialize().item_idx)
    ratings = property(lambda self: self.materialize().ratings)
    user_index = property(lambda self: self.materialize().user_index)
    item_index = property(lambda self: self.materialize().item_index)

    def sanity_check(self) -> None:
        # deferred: materializing here would serialize the very scan the
        # pipeline overlaps. The streaming trainer returns None on an
        # empty scan and the algorithm falls back to the materialized
        # path, whose sanity check raises the user-facing error.
        if self._td is not None:
            self._td.sanity_check()


@dataclasses.dataclass
class PreparedData:
    td: TrainingData


@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = "default"
    channel_name: Optional[str] = None
    event_names: Tuple[str, ...] = ("rate", "buy")
    # k-fold eval config (reference DataSource readEval)
    eval_k: Optional[int] = None
    eval_query_num: int = 10
    seed: int = 3


from predictionio_tpu.data.storage.columnar import ValueSpec

# The template's event->rating mapping, declaratively: explicit 'rate'
# events carry a rating property; 'buy' events become rating 4.0
# (reference DataSource.scala implicit mapping). Declarative so the
# store's NATIVE columnar scan evaluates it vectorized (binary pages /
# SQL) instead of calling Python per event. Shared with the
# sliding-window evaluator (models/experimental/movielens_evaluation.py)
# so both always score the same rating scheme.
RATING_SPEC = ValueSpec(
    prop="rating", default=1.0, event_overrides=(("buy", 4.0),)
)


def rating_of_event(e) -> float:
    """Per-event form of RATING_SPEC (callers that hold Event objects)."""
    return RATING_SPEC.value_of(e)


class DataSource(BaseDataSource):
    """Reads rate/buy events into dense-indexed rating columns
    (reference DataSource.scala — PEventStore.find + Rating mapping;
    'buy' events become rating 4.0 like the template's implicit mapping)."""

    params_class = DataSourceParams

    def _read_columns(self, ctx):
        store = PEventStore(ctx.storage)
        return store.find_columns(
            self.params.app_name,
            value_spec=RATING_SPEC,
            channel_name=self.params.channel_name,
            entity_type="user",
            target_entity_type="item",
            event_names=list(self.params.event_names),
        )

    def _stream_columns(self, ctx):
        store = PEventStore(ctx.storage)
        return store.stream_columns(
            self.params.app_name,
            value_spec=RATING_SPEC,
            channel_name=self.params.channel_name,
            entity_type="user",
            target_entity_type="item",
            event_names=list(self.params.event_names),
        )

    def _materialized_training(self, ctx) -> TrainingData:
        cols = self._read_columns(ctx)
        logger.info(
            "DataSource: %d ratings, %d users, %d items",
            cols.n, len(cols.entity_index), len(cols.target_index),
        )
        return TrainingData(
            user_idx=cols.entity_idx,
            item_idx=cols.target_idx,
            ratings=cols.values,
            user_index=cols.entity_index,
            item_index=cols.target_index,
        )

    def read_training(self, ctx) -> TrainingData:
        # streaming handoff: when the store has a native chunked scan,
        # return a LAZY TrainingData so the ALS algorithm can overlap
        # scan/pack/transfer/compile (ops/streaming). The reference's
        # read stage materializes an RDD; here the "RDD" is a stream
        # factory and materialization is the fallback, not the default.
        try:
            stream = self._stream_columns(ctx)
        except Exception:
            stream = None
        if stream is not None:
            # hand the probe stream to its FIRST consumer: sqlite's
            # eager setup (fingerprint aggregates, page listing,
            # dictionary load) should run once per train, not twice.
            # The pre-scan fingerprint read a moment early stays safe —
            # it can only cause a spurious cache miss later, never a
            # stale hit.
            probe = [stream]

            def stream_factory():
                first, probe[0] = probe[0], None
                return first if first is not None else self._stream_columns(
                    ctx
                )

            return StreamingTrainingData(
                stream_factory=stream_factory,
                loader=lambda: self._materialized_training(ctx),
            )
        return self._materialized_training(ctx)

    def read_eval(self, ctx):
        if not self.params.eval_k:
            return []
        cols = self._read_columns(ctx)
        k = self.params.eval_k
        rng = np.random.default_rng(self.params.seed)
        fold_of = rng.integers(0, k, size=cols.n)
        out = []
        inv_item = cols.target_index.inverse()
        inv_user = cols.entity_index.inverse()
        for fold in range(k):
            train_sel = fold_of != fold
            test_sel = ~train_sel
            td = TrainingData(
                user_idx=cols.entity_idx[train_sel],
                item_idx=cols.target_idx[train_sel],
                ratings=cols.values[train_sel],
                user_index=cols.entity_index,
                item_index=cols.target_index,
            )
            # group held-out items per user -> (Query, ActualResult)
            per_user = {}
            for u, i in zip(
                cols.entity_idx[test_sel].tolist(),
                cols.target_idx[test_sel].tolist(),
            ):
                per_user.setdefault(u, []).append(inv_item[i])
            qa = [
                (
                    Query(user=inv_user[u], num=self.params.eval_query_num),
                    ActualResult(items=tuple(items)),
                )
                for u, items in per_user.items()
            ]
            out.append((td, {"fold": fold}, qa))
        return out


class Preparator(BasePreparator):
    """Pass-through (reference Preparator.scala)."""

    def prepare(self, ctx, td: TrainingData) -> PreparedData:
        return PreparedData(td=td)


# --- the ALS algorithm ---


@dataclasses.dataclass(frozen=True)
class ALSAlgorithmParams(Params):
    rank: int = 10
    num_iterations: int = 10
    lambda_: float = 0.01
    alpha: float = 1.0
    implicit_prefs: bool = False
    seed: Optional[int] = 3
    # mid-training checkpoint/resume (absent in the reference, SURVEY §5)
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 5
    # deploy-time warm-up coverage: the largest query `num` and serving
    # batch size to pre-compile for (queries beyond these still work but
    # pay a one-time cold compile on live traffic; match warm_max_batch
    # to ServerConfig.max_batch if you raise that)
    warm_num: int = 16
    warm_max_batch: int = 128
    # delta retrains (pio train --continuous): iteration budget when the
    # pack cache folds a delta and warm-starts from the previous model
    # (ops/streaming). 0 keeps the full num_iterations on delta rounds.
    delta_sweeps: int = 2
    # serving residency precision for the resident item matrix
    # (ops/retrieval.py). "float32" keeps the replicated ServingFactors
    # path; "bf16"/"int8" deploy an ItemRetriever storing the catalog
    # quantized (~2x / ~3.6x fewer resident bytes) and serve via the
    # two-stage shortlist + exact host rescore (recall@n >= 0.999 gated
    # in bench.py)
    precision: str = "float32"
    # stage-1 shortlist width multiplier c (shortlist = pow2(c*n))
    shortlist_mult: int = 4
    # normal-equation solver: "exact" (full rank x rank Cholesky per
    # row) or "subspace" (iALS++ blocked coordinate descent over
    # block_size-wide column blocks — block_size must divide rank)
    solver: str = "exact"
    block_size: int = 0

    def __post_init__(self):
        validate_solver(self.solver, self.block_size, self.rank)


@dataclasses.dataclass
class ALSModel:
    """Trained factors + id indexes. Predict is one gather + one matmul +
    top-k on device (reference ALSAlgorithm predict: cosine over factors,
    ALSAlgorithm.scala:79-105). Device-resident serving state is built
    lazily and excluded from pickling."""

    arrays: ALSModelArrays
    user_index: BiMap
    item_index: BiMap
    _serving: Optional[ServingFactors] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    _inv_item: Optional[BiMap] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    # deploy-time mesh (BaseAlgorithm.prepare_serving): query batches
    # shard over it, catalog replicated — data-parallel top-N. Device
    # state; never pickled.
    _serving_mesh: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    # quantized-residency serving path (ops/retrieval.py), built by
    # prepare_serving when params.precision != "float32". Device state;
    # never pickled.
    _retriever: Optional[ItemRetriever] = dataclasses.field(
        default=None, repr=False, compare=False
    )

    def __getstate__(self):
        state = self.__dict__.copy()
        state["_serving"] = None
        state["_inv_item"] = None
        state["_serving_mesh"] = None
        state["_retriever"] = None
        return state

    def attach_serving_mesh(self, mesh) -> None:
        """Bind serving to a device mesh (drops any single-device state
        already built, so the next predict uses the sharded factors)."""
        self._serving_mesh = mesh
        self._serving = None

    @property
    def serving(self) -> ServingFactors:
        if self._serving is None:
            self._serving = ServingFactors(
                self.arrays.user_factors, self.arrays.item_factors,
                mesh=self._serving_mesh,
            )
        return self._serving

    def recommend(self, user: str, num: int) -> PredictedResult:
        [(_, result)] = self.recommend_many([(0, Query(user, num))])
        return result

    def recommend_many(self, queries) -> List[Tuple[int, PredictedResult]]:
        """Vectorized top-N for indexed queries (the serving batch path).
        Its host phases are ``utils.tracing.stage``s: the float32 path's
        ``dispatch`` and ``device_wait`` are ``ServingFactors``' own."""
        with _tracing.stage(_tracing.HOST_PREP):
            known = [
                (qx, self.user_index[q.user], q.num)
                for qx, q in queries
                if q.user in self.user_index
            ]
            unknown = [
                (qx, PredictedResult())
                for qx, q in queries
                if q.user not in self.user_index
            ]
            if not known:
                return unknown
            max_num = max(n for _, _, n in known)
            # pad the top-k width to a power of two (min 16) so varying
            # query `num`s share O(log) compiled executables instead of
            # one each — the shared ladder rule, which also records the
            # ladder's padding waste in
            # pio_padding_waste_ratio{site="retrieval_topk"}
            from predictionio_tpu.ops.retrieval import pow2_topk_width

            max_num = pow2_topk_width(max_num, len(self.item_index))
            users = [u for _, u, _ in known]
        if self._retriever is not None:
            # quantized residency path: the retriever holds the catalog
            # as int8/bf16 rows and rescores its shortlist exactly
            scores, idx = self._retriever.topn(
                self.arrays.user_factors[np.asarray(users, np.int64)],
                max_num,
            )
        else:
            scores, idx = self.serving.topn_by_user(users, max_num)
        with _tracing.stage(_tracing.BUILD):
            # the inverse index is catalog-sized — build it once, not
            # per request
            if self._inv_item is None:
                self._inv_item = self.item_index.inverse()
            inv_item = self._inv_item
            out = list(unknown)
            for row, (qx, _, num) in enumerate(known):
                item_scores = tuple(
                    ItemScore(
                        item=inv_item[int(idx[row, j])],
                        score=float(scores[row, j]),
                    )
                    for j in range(min(num, max_num))
                )
                out.append((qx, PredictedResult(item_scores=item_scores)))
            return out


class ALSAlgorithm(BaseAlgorithm):
    """ALS on the workflow mesh (replaces MLlib ALS.train/trainImplicit,
    reference ALSAlgorithm.scala:66-73)."""

    params_class = ALSAlgorithmParams
    query_class = Query
    # reg variants of one config train together in a single vmapped
    # program during grid evaluation (ops/als.py train_als_grid)
    GRID_AXES = ("lambda_",)

    @classmethod
    def train_grid(cls, ctx, pd: PreparedData, algos):
        from predictionio_tpu.ops.als import train_als_grid

        base: ALSAlgorithmParams = algos[0].params
        for a in algos:
            p: ALSAlgorithmParams = a.params
            if dataclasses.replace(p, lambda_=0.0) != dataclasses.replace(
                base, lambda_=0.0
            ):
                return None  # differ beyond the reg axis
            if p.checkpoint_dir is not None:
                return None  # checkpoint state is per-run, not per-grid
            if p.solver != "exact":
                return None  # blocked solver trains per-algo, not vmapped
        td = pd.td
        config = ALSConfig(
            rank=base.rank,
            iterations=base.num_iterations,
            reg=0.0,  # per-variant regs travel in the grid axis
            alpha=base.alpha,
            implicit_prefs=base.implicit_prefs,
            seed=base.seed if base.seed is not None else 0,
        )
        arrays_list = train_als_grid(
            td.user_idx, td.item_idx, td.ratings,
            n_users=len(td.user_index), n_items=len(td.item_index),
            config=config,
            regs=[a.params.lambda_ for a in algos],
            mesh=ctx.mesh if ctx is not None else None,
        )
        return [
            ALSModel(
                arrays=arrays,
                user_index=td.user_index,
                item_index=td.item_index,
            )
            for arrays in arrays_list
        ]

    def train(self, ctx, pd: PreparedData) -> ALSModel:
        td = pd.td
        p: ALSAlgorithmParams = self.params
        config = ALSConfig(
            rank=p.rank,
            iterations=p.num_iterations,
            reg=p.lambda_,
            alpha=p.alpha,
            implicit_prefs=p.implicit_prefs,
            seed=p.seed if p.seed is not None else 0,
            solver=p.solver,
            block_size=p.block_size,
        )
        mesh = ctx.mesh if ctx is not None else None
        if mesh is not None and mesh.devices.size == 1:
            # a 1-device mesh is single-device training: drop to the
            # device-pack wire path (streaming-capable, smaller wire)
            mesh = None
        stream_factory = getattr(td, "stream_factory", None)
        if stream_factory is not None and mesh is None:
            from predictionio_tpu.ops.streaming import train_als_streaming

            result = train_als_streaming(
                stream_factory(), config,
                timer=getattr(ctx, "timer", None),
                checkpoint_dir=p.checkpoint_dir,
                checkpoint_every=p.checkpoint_every,
                warm_sweeps=p.delta_sweeps,
            )
            if result is not None:
                return ALSModel(
                    arrays=result.arrays,
                    user_index=result.user_index,
                    item_index=result.item_index,
                )
            # empty/unstreamable scan: the materialized path below owns
            # the error reporting (TrainingData.sanity_check semantics)
            td.materialize().sanity_check()
        arrays = train_als(
            td.user_idx,
            td.item_idx,
            td.ratings,
            n_users=len(td.user_index),
            n_items=len(td.item_index),
            config=config,
            mesh=mesh,
            checkpoint_dir=p.checkpoint_dir,
            checkpoint_every=p.checkpoint_every,
        )
        return ALSModel(
            arrays=arrays, user_index=td.user_index, item_index=td.item_index
        )

    def prepare_serving(self, ctx, model: ALSModel) -> ALSModel:
        """Bind deploy-time serving to the workflow mesh: query batches
        shard over its data axis (catalog replicated), so a multi-chip
        deployment serves at N x the single-chip batch throughput.
        With ``precision`` set to a quantized tier, deploy an
        ItemRetriever instead: the catalog resides as int8/bf16 rows
        (row-sharded over the mesh) and retrieval runs the two-stage
        shortlist + exact rescore."""
        if ctx is not None:
            model.attach_serving_mesh(ctx.mesh)
        p: ALSAlgorithmParams = self.params
        if p.precision != "float32":
            model._retriever = ItemRetriever(
                model.arrays.item_factors,
                mesh=ctx.mesh if ctx is not None else None,
                component="recommendation",
                precision=p.precision,
                shortlist_mult=p.shortlist_mult,
            )
        return model

    def serving_precision(self, model: ALSModel) -> Optional[str]:
        if model._retriever is not None:
            return model._retriever.precision
        if model._serving is not None:
            return "float32"
        return None

    def predict(self, model: ALSModel, query: Query) -> PredictedResult:
        return model.recommend(query.user, query.num)

    def batch_predict(self, model: ALSModel, queries) -> List[Tuple[int, PredictedResult]]:
        return model.recommend_many(queries)

    def release_serving(self, model: ALSModel) -> None:
        """Free a displaced model's device-resident serving state
        (promotion drain→release contract, controller/base.py): drop
        the ServingFactors upload — its device buffers free by refcount
        once the last in-flight batch resolves. A straggler query
        lazily rebuilds ServingFactors from the host arrays (the
        ``serving`` property), so racing past a release degrades to a
        re-upload, never an error."""
        model._serving = None
        model._serving_mesh = None
        retriever, model._retriever = model._retriever, None
        if retriever is not None:
            retriever.free()

    def warm(self, model: ALSModel) -> None:
        """Compile the padded serving executables at deploy (tail-latency
        control; no reference analog — Spark has no JIT cold start).
        Covers every top-k tier up to warm_num and every padded batch
        size up to warm_max_batch. A quantized deployment warms the
        retriever's precision x shortlist ladder instead (the serving
        path never touches ServingFactors then)."""
        p: ALSAlgorithmParams = self.params
        if model._retriever is not None:
            model._retriever.warm(
                n=p.warm_num, max_batch=p.warm_max_batch,
                flag_combos=((False, False),),
                exclude_widths=(1,),
            )
            return
        n = 16
        while True:
            model.serving.warm(n=n, max_batch=p.warm_max_batch)
            if n >= min(p.warm_num, len(model.item_index)):
                break
            n *= 2

    def result_to_json(self, result: PredictedResult):
        # reference wire format (Engine.scala PredictedResult(itemScores))
        return {
            "itemScores": [
                {"item": s.item, "score": s.score}
                for s in result.item_scores
            ]
        }


class Serving(FirstServing):
    """First-algorithm serving (reference Serving.scala)."""


def recommendation_engine() -> Engine:
    return Engine(
        data_source_classes=DataSource,
        preparator_classes=Preparator,
        algorithm_classes={"als": ALSAlgorithm},
        serving_classes=Serving,
    )


class RecommendationEngineFactory(EngineFactory):
    def apply(self) -> Engine:
        return recommendation_engine()
