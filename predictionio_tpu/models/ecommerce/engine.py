"""E-commerce recommendation engine: ALS + business rules at predict time.

Reference mapping (examples/scala-parallel-ecommercerecommendation/
train-with-rate-event/src/main/scala/):
- Query(user, num, categories?, whiteList?, blackList?) /
  PredictedResult(itemScores)                   <- Engine.scala
- DataSource: $set users/items + rate/buy/view events <- DataSource.scala
- ALSAlgorithm: explicit ALS over latest-rating-per-pair; predict for a
  known user = userVector . itemFactors with candidacy filtering; for an
  unknown user = cosine similarity against the user's recently viewed
  items (read from LEventStore at predict time); the effective blacklist
  merges the query's blackList, the user's seen items (when unseenOnly),
  and the live "unavailableItems" constraint entity
                                                <- ALSAlgorithm.scala
- Serving: first prediction                     <- Serving.scala
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Dict, List, NamedTuple, Optional, Sequence, Set, Tuple

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
from predictionio_tpu.data.constraints import (
    ConstraintCache,
    read_constraint_items,
)
from predictionio_tpu.data.store import LEventStore, PEventStore
from predictionio_tpu.ops import retrieval
from predictionio_tpu.ops.als import ALSConfig, train_als, validate_solver
from predictionio_tpu.ops.retrieval import ItemRetriever
from predictionio_tpu.ops.similarity import SimilarityScorer, normalize_rows
from predictionio_tpu.utils import metrics as _metrics
from predictionio_tpu.utils import tracing as _tracing

logger = logging.getLogger(__name__)


# --- what the serving path counts (one family each: a /metrics reader
# sums a family over its labels and cannot pick one) ---


def _m_store_events():
    return _metrics.get_registry().counter(
        "pio_ecom_store_events_read_total",
        "Events the e-commerce engine's query-time store reads returned "
        "(seen and recent-view histories, one read a query)",
    )


def _m_recent_queries():
    return _metrics.get_registry().counter(
        "pio_ecom_recent_queries_total",
        "Queries of users without factors that were served from their "
        "recent views by cosine similarity",
    )


def _m_empty_answers():
    return _metrics.get_registry().counter(
        "pio_ecom_empty_answers_total",
        "Queries answered with no item: a user with neither factors nor "
        "recent views, or filters that leave no candidate with a "
        "positive score",
    )


def _m_host_fallbacks():
    return _metrics.get_registry().counter(
        "pio_ecom_host_fallback_total",
        "Queries answered on the host because a list, a category count "
        "or num lay over the top of the warm ladder (never compiled on "
        "a live batch)",
    )


def _m_pad_waste():
    return _metrics.get_registry().histogram(
        "pio_ecom_list_pad_waste_ratio",
        "Per served micro-batch, 1 - ids asked for / id slots the batch "
        "was padded to (exclusion and inclusion lists on the warm "
        "ladder's widths)",
        buckets=(0.1, 0.25, 0.5, 0.75, 0.9, 0.95, 0.99, 1.0),
    )


@dataclasses.dataclass(frozen=True)
class Query:
    user: str
    num: int = 10
    categories: Optional[Tuple[str, ...]] = None
    white_list: Optional[Tuple[str, ...]] = None
    black_list: Optional[Tuple[str, ...]] = None

    def __post_init__(self):
        for f in ("categories", "white_list", "black_list"):
            v = getattr(self, f)
            if v is not None:
                object.__setattr__(self, f, tuple(v))


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
class Item:
    categories: Tuple[str, ...] = ()


@dataclasses.dataclass
class RateEvent:
    user: str
    item: str
    rating: float
    t: float


@dataclasses.dataclass
class TrainingData(SanityCheck):
    users: Dict[str, dict]
    items: Dict[str, Item]
    rate_events: List[RateEvent]

    def sanity_check(self) -> None:
        if not self.items:
            raise ValueError("items is empty — are item $set events present?")
        if not self.rate_events:
            raise ValueError(
                "rateEvents is empty — are rate/buy events present?"
            )


@dataclasses.dataclass
class PreparedData:
    td: TrainingData


@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = "default"
    channel_name: Optional[str] = None
    # event types read as training signal, and the confidence weight
    # each carries. "rate" events keep their rating property; any other
    # listed event falls back to its entry here (1.0 when absent) — the
    # per-event-type confidence feeding implicit ALS (c = alpha*|r|).
    # Defaults reproduce the reference's rate/buy behavior exactly.
    event_names: Tuple[str, ...] = ("rate", "buy")
    event_weights: Tuple[Tuple[str, float], ...] = (
        ("buy", 4.0),
        ("view", 1.0),
    )


class DataSource(BaseDataSource):
    params_class = DataSourceParams

    def read_training(self, ctx) -> TrainingData:
        store = PEventStore(ctx.storage)
        p = self.params
        users = {
            eid: dict(props)
            for eid, props in store.aggregate_properties(
                p.app_name, entity_type="user", channel_name=p.channel_name
            ).items()
        }
        items = {
            eid: Item(categories=tuple(props.get_or_else("categories", [])))
            for eid, props in store.aggregate_properties(
                p.app_name, entity_type="item", channel_name=p.channel_name
            ).items()
        }
        weights = dict(p.event_weights)
        rates = [
            RateEvent(
                user=e.entity_id,
                item=e.target_entity_id,
                rating=(
                    float(e.properties.get_or_else("rating", 1.0))
                    if e.event == "rate"
                    else float(weights.get(e.event, 1.0))
                ),
                t=e.event_time.timestamp(),
            )
            for e in store.find(
                p.app_name,
                channel_name=p.channel_name,
                entity_type="user",
                event_names=list(p.event_names),
                target_entity_type="item",
            )
        ]
        logger.info(
            "DataSource: %d users, %d items, %d rate events",
            len(users), len(items), len(rates),
        )
        return TrainingData(users=users, items=items, rate_events=rates)


class Preparator(BasePreparator):
    def prepare(self, ctx, td: TrainingData) -> PreparedData:
        return PreparedData(td=td)


@dataclasses.dataclass(frozen=True)
class ECommAlgorithmParams(Params):
    app_name: str = "default"
    unseen_only: bool = False
    seen_events: Tuple[str, ...] = ("buy", "view")
    similar_events: Tuple[str, ...] = ("view",)
    rank: int = 10
    num_iterations: int = 20
    lambda_: float = 0.01
    seed: Optional[int] = None
    # serving-time TTL of the unavailableItems constraint cache
    # (data/constraints.py): a $set is honoured by every query sent
    # later than this after its acknowledgement (past half this age a
    # query batch serves the cached set and kicks an out-of-band
    # refresh — the store is never on the hot path). Training-time predicts (no prepare_serving) keep
    # the reference's read-per-predict semantics.
    constraint_ttl_s: float = 5.0
    # deploy-time warm-up coverage for the retrieval executables: keep
    # warm_max_batch >= the server's --max-batch, or the first saturated
    # micro-batch pays its compile on live traffic (docs/PERF.md)
    warm_num: int = 16
    warm_max_batch: int = 128
    # serving residency precision for the resident item matrix
    # (ops/retrieval.py): "float32" = exact single-stage retrieval;
    # "bf16"/"int8" store the catalog quantized (~2x / ~3.6x fewer
    # resident bytes) and serve via the two-stage shortlist + exact
    # host rescore (recall@n >= 0.999 gated in bench.py)
    precision: str = "float32"
    # stage-1 shortlist width multiplier c (shortlist = pow2(c*n))
    shortlist_mult: int = 4
    # the closed ladder of the serving executables (ops/retrieval.py):
    # exclusion lists (blackList + seen items) and inclusion lists
    # (whiteList) pad to the smallest listed width that holds the
    # batch's longest, batches to 8 doubling up to warm_max_batch;
    # warm() compiles the whole product, and a query over a ladder's
    # top (or over warm_num, or naming more than QUERY_CATEGORIES
    # categories) is answered on the host. Size exclude_widths by the
    # store's histories, include_widths by the longest whiteList the
    # shop's pages send
    exclude_widths: Tuple[int, ...] = (64, 1024)
    include_widths: Tuple[int, ...] = (1024,)
    # implicit-feedback training (MLlib ALS.trainImplicit parity): treat
    # the rating column as a confidence signal c = alpha*|r| on the
    # preference p = 1(r > 0). The real e-commerce workload — view/buy
    # events with per-event-type weights from DataSourceParams — is the
    # intended input.
    implicit_prefs: bool = False
    alpha: float = 1.0
    # "exact" or the iALS++ blocked "subspace" solver (block_size must
    # divide rank)
    solver: str = "exact"
    block_size: int = 0

    def __post_init__(self):
        validate_solver(self.solver, self.block_size, self.rank)


# how many category codes a query ships to the fused program (a
# dimension of every serving executable, so not a knob)
QUERY_CATEGORIES = 4


@dataclasses.dataclass
class ECommModel:
    user_factors: np.ndarray  # [n_users, k]
    item_factors: np.ndarray  # [n_items, k]
    user_index: BiMap
    item_index: BiMap
    # the items' categories as arrays (an item may carry several): no
    # Python object an item, neither in the blob nor before the
    # collector. ``items`` ({dense index: Item}) is accepted at
    # construction and folded into them.
    items: Optional[Dict[int, Item]] = None
    category_names: Tuple[str, ...] = ()
    item_categories: Optional[np.ndarray] = None  # [n_items, C] int32
    _scorer: Optional[SimilarityScorer] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    _inv_item: Optional[BiMap] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    # deploy-time mesh (BaseAlgorithm.prepare_serving). Device state;
    # never pickled.
    _serving_mesh: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    # sharded on-device retrieval state (ops/retrieval.py), built by
    # prepare_serving: mesh-resident item factors + candidacy masks.
    # Device state; never pickled — a hot reload rebuilds it.
    _retriever: Optional[ItemRetriever] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    _constraints: Optional[ConstraintCache] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    _cat_code: Optional[Dict[str, int]] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    _item_names: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False, compare=False
    )

    def __post_init__(self):
        self._fold_items()

    def _fold_items(self) -> None:
        n = self.item_factors.shape[0]
        if self.items is not None:
            self.category_names, self.item_categories = (
                retrieval.category_arrays(self.items, n)
            )
            self.items = None
        elif self.item_categories is None:
            self.item_categories = np.full((n, 1), -1, np.int32)
        self._cat_code = None

    def __setstate__(self, state):
        self.__dict__.update(state)
        for name in ("category_names", "item_categories", "_cat_code",
                     "_item_names"):  # a blob from before the arrays
            self.__dict__.setdefault(
                name, () if name == "category_names" else None
            )
        self.__dict__.pop("_cat_items", None)  # an old blob's index
        self._fold_items()

    def __getstate__(self):
        state = self.__dict__.copy()
        state["_cat_code"] = None
        state["_item_names"] = None
        state["_scorer"] = None
        state["_inv_item"] = None
        state["_serving_mesh"] = None
        state["_retriever"] = None
        state["_constraints"] = None
        return state

    def attach_serving_mesh(self, mesh) -> None:
        self._serving_mesh = mesh
        self._scorer = None

    def normed_rows(self, idx) -> np.ndarray:
        """The L2-normalized factor rows of a few items (zero rows stay
        zero), for cosine query vectors: the catalog is never normalized
        on the host (the retriever folds the norms into its resident
        state, and a second table would be 8.5 GB at 4.16 M x 512)."""
        return normalize_rows(self.item_factors[idx])

    def category_codes(self, categories) -> np.ndarray:
        """Codes of the given category names (names no item carries
        have none: an empty array means NO candidates)."""
        if self._cat_code is None:
            self._cat_code = {
                c: j for j, c in enumerate(self.category_names)
            }
        return retrieval.category_codes(self._cat_code, categories)

    def category_mask(self, categories) -> np.ndarray:
        """[n_items] bool: the item carries one of the categories."""
        return np.isin(
            self.item_categories, self.category_codes(categories)
        ).any(axis=1)

    def category_items(self, categories) -> np.ndarray:
        """Dense indices of items carrying at least one of the given
        categories."""
        return np.flatnonzero(self.category_mask(categories))

    @property
    def item_names(self) -> np.ndarray:
        """Item names by dense index (an object array: not a dict, and
        nothing the collector walks)."""
        if self._item_names is None:
            self._item_names = retrieval.names_by_index(self.item_index)
        return self._item_names

    @property
    def scorer(self) -> SimilarityScorer:
        if self._scorer is None:
            self._scorer = SimilarityScorer(
                self.item_factors, mesh=self._serving_mesh
            )
        return self._scorer

    @property
    def inv_item(self) -> BiMap:
        if self._inv_item is None:
            self._inv_item = self.item_index.inverse()
        return self._inv_item


class PreparedQuery(NamedTuple):
    """``ECommAlgorithm.prepare_query``'s value: what one query needs
    of itself to ride a device batch. ``row`` is None for a user with
    neither factors nor a recent item (the answer is empty)."""

    row: Optional[np.ndarray]  # the user's factors, or the recents' sum
    cosine: bool  # a user without factors: scored by cosine
    exclude: np.ndarray  # ids of seen and blacklisted items
    include: Optional[np.ndarray]  # whiteList ids
    categories: Optional[np.ndarray]  # category codes
    seen: Set[str]  # seen names, for the host path's own filter
    on_host: bool  # a list, the categories or num over the warm ladder


_NO_RECENT_ITEM = PreparedQuery(
    None, True, np.zeros(0, np.int64), None, None, frozenset(), False
)


class ECommAlgorithm(BaseAlgorithm):
    """ALS + predict-time business rules (reference ALSAlgorithm.scala
    of the train-with-rate-event variant). Explicit by default; set
    ``implicit_prefs`` to train confidence-weighted on view/buy events
    (MLlib ALS.trainImplicit semantics)."""

    params_class = ECommAlgorithmParams
    query_class = Query

    def train(self, ctx, pd: PreparedData) -> ECommModel:
        td = pd.td
        p = self.params
        user_index = BiMap.string_int(
            set(td.users.keys()) | {r.user for r in td.rate_events}
        )
        item_index = BiMap.string_int(td.items.keys())
        # latest rating per (user, item) wins (reference reduceByKey by t)
        latest: Dict[Tuple[int, int], Tuple[float, float]] = {}
        for r in td.rate_events:
            if r.item not in item_index:
                logger.info("item %s has no $set event; skipping", r.item)
                continue
            key = (user_index[r.user], item_index[r.item])
            if key not in latest or r.t >= latest[key][0]:
                latest[key] = (r.t, r.rating)
        if not latest:
            raise ValueError("no valid ratings after index mapping")
        triples = [(u, i, v) for (u, i), (_, v) in latest.items()]
        u, i, r = (np.asarray(x) for x in zip(*triples))
        arrays = train_als(
            u.astype(np.int32),
            i.astype(np.int32),
            r.astype(np.float32),
            n_users=len(user_index),
            n_items=len(item_index),
            config=ALSConfig(
                rank=p.rank,
                iterations=p.num_iterations,
                reg=p.lambda_,
                implicit_prefs=p.implicit_prefs,
                alpha=p.alpha,
                seed=p.seed if p.seed is not None else 0,
                solver=p.solver,
                block_size=p.block_size,
            ),
            mesh=ctx.mesh if ctx is not None else None,
        )
        return ECommModel(
            user_factors=arrays.user_factors,
            item_factors=arrays.item_factors,
            user_index=user_index,
            item_index=item_index,
            items={item_index[k]: v for k, v in td.items.items()},
        )  # folded into category arrays by the model

    # --- predict-time business rules ---

    def _seen_items(self, query: Query) -> Set[str]:
        if not self.params.unseen_only:
            return set()
        try:
            events = LEventStore().find_by_entity(
                app_name=self.params.app_name,
                entity_type="user",
                entity_id=query.user,
                event_names=list(self.params.seen_events),
                target_entity_type="item",
            )
            return {
                e.target_entity_id for e in events if e.target_entity_id
            }
        except Exception as e:
            logger.error("Error when reading seen events: %s", e)
            return set()

    def _unavailable_items(self) -> Set[str]:
        """Latest $set on the 'constraint'/'unavailableItems' entity
        (reference considers the single latest event). Training-time
        path: one inline store read per predict/batch, exactly the
        reference semantics. The SERVING path never calls this — the
        prepared serving state holds a ConstraintCache whose TTL'd
        background refresh feeds the on-device mask instead."""
        try:
            return set(read_constraint_items(self.params.app_name))
        except Exception as e:
            logger.error("Error when reading unavailableItems: %s", e)
            return set()

    def _candidate_mask(
        self, model: ECommModel, query: Query, black_list: Set[str]
    ) -> np.ndarray:
        n = model.item_factors.shape[0]
        mask = np.ones(n, bool)
        if query.white_list is not None:
            wl = np.zeros(n, bool)
            wl[[
                model.item_index[i]
                for i in query.white_list
                if i in model.item_index
            ]] = True
            mask &= wl
        mask[[
            model.item_index[i] for i in black_list if i in model.item_index
        ]] = False
        if query.categories is not None:
            mask &= model.category_mask(query.categories)
        return mask

    def prepare_serving(self, ctx, model: ECommModel) -> ECommModel:
        """Build the prepared serving state (registered with the engine
        server's DeployedEngine, so the upload happens ONCE at deploy,
        not per batch): item factors resident on device — row-sharded
        over the workflow mesh when it has >1 device — plus the
        unavailableItems constraint as a resident on-device candidacy
        mask, kept fresh by the TTL'd out-of-band refresh of a
        ConstraintCache. Replaces the host post-filter for every served
        query."""
        mesh = ctx.mesh if ctx is not None else None
        if mesh is not None:
            model.attach_serving_mesh(mesh)
        p = self.params
        retriever = ItemRetriever(
            model.item_factors, mesh=mesh, component="ecommerce",
            precision=p.precision,
            shortlist_mult=p.shortlist_mult,
            category_codes=model.item_categories,
            category_width=QUERY_CATEGORIES,
            exclude_ladder=p.exclude_widths,
            include_ladder=(1, *p.include_widths),
            max_batch=p.warm_max_batch,
        )
        cache = ConstraintCache(
            self.params.app_name, ttl_s=self.params.constraint_ttl_s
        )

        def apply_mask(items) -> None:
            retriever.set_excluded_ids(
                np.asarray(
                    [
                        model.item_index[i]
                        for i in items
                        if i in model.item_index
                    ],
                    np.int64,
                )
            )

        apply_mask(cache.get())  # deploy-time prime (inline read is fine here)
        cache.on_change(apply_mask)
        model._retriever = retriever
        model._constraints = cache
        # a sample from deploy on: a scrape tells "none" from "no family"
        _m_host_fallbacks().inc(0)
        return model

    def serving_precision(self, model: ECommModel) -> Optional[str]:
        if model._retriever is not None:
            return model._retriever.precision
        return None

    def release_serving(self, model: ECommModel) -> None:
        """Free the device-resident serving state of a displaced model
        (promotion drain→release contract, controller/base.py): the
        references are nulled FIRST so a straggler query falls back to
        the host path, then the retriever's buffers drop — freed by
        refcount once the last holder resolves."""
        retriever, model._retriever = model._retriever, None
        model._constraints = None
        model._scorer = None
        if retriever is not None:
            retriever.free()

    def warm(self, model: ECommModel) -> None:
        """Pre-compile the serving executables (see BaseAlgorithm.warm):
        the fused retrieval programs for the prepared state (raw-dot for
        known users, cosine for the similar-items fallback), or the
        legacy cosine-sum path when serving was not prepared."""
        if model._retriever is not None:
            p = self.params
            t0 = time.perf_counter()
            # one flag pair: known users' raw dots and recent-view
            # cosine queries ride one program run (per-row flags)
            model._retriever.warm(
                n=p.warm_num, max_batch=p.warm_max_batch,
                flag_combos=((True, "rows"),),
            )
            model.item_names  # built here, not inside the first batch
            logger.info(
                "ecommerce warm ladder: %d executables in %.1fs",
                model._retriever.ladder_size(
                    tiers=len(model._retriever.warm_tiers(p.warm_num))
                ),
                time.perf_counter() - t0,
            )
        else:
            model.scorer.warm(max_q=16)

    def predict(self, model: ECommModel, query: Query) -> PredictedResult:
        if model._retriever is not None:
            [(_, result)] = self._batch_predict_device(model, [(0, query)])
            return result
        return self._predict_one(model, query, self._unavailable_items())

    def _predict_one(
        self, model: ECommModel, query: Query, unavailable: Set[str]
    ) -> PredictedResult:
        user_idx = model.user_index.get(query.user)
        if user_idx is not None and np.any(model.user_factors[user_idx]):
            uf = model.user_factors[user_idx]
            scores = model.item_factors @ uf  # [n_items]
        else:
            logger.info("no userFeature found for user %s", query.user)
            scores = self._similar_to_recent(model, query)
            if scores is None:
                return PredictedResult()
        return self._finish(model, query, scores, unavailable)

    def _recent_item_idx(
        self, model: ECommModel, query: Query
    ) -> Optional[List[int]]:
        """Dense indices of the user's 10 most recent similar-event
        items (reference predictNewUser's recent-items rule) — the ONE
        place that rule lives; both the host cosine-sum path and the
        device retrieval path score against these rows."""
        try:
            recent = list(
                LEventStore().find_by_entity(
                    app_name=self.params.app_name,
                    entity_type="user",
                    entity_id=query.user,
                    event_names=list(self.params.similar_events),
                    target_entity_type="item",
                    limit=10,
                    latest=True,
                )
            )
        except Exception as e:
            logger.error("Error when reading recent events: %s", e)
            return None
        recent_idx = [
            model.item_index[e.target_entity_id]
            for e in recent
            if e.target_entity_id in model.item_index
        ]
        return recent_idx or None

    def _similar_to_recent(
        self, model: ECommModel, query: Query
    ) -> Optional[np.ndarray]:
        """Unknown user: cosine-sum against the 10 most recent similar-event
        items (reference predictNewUser)."""
        recent_idx = self._recent_item_idx(model, query)
        if recent_idx is None:
            return None
        return model.scorer.cosine_sum(model.scorer.normed[recent_idx])

    def batch_predict(
        self, model, queries, prepared=None
    ) -> List[Tuple[int, PredictedResult]]:
        """Known users score as ONE [B, k] x [k, n_items] matmul; unknown
        users fall back to the per-query similar-items path. The
        query-independent unavailableItems constraint reads once per batch.
        With a prepared serving state the whole batch routes through the
        sharded on-device retrieval path instead, with ``prepared`` the
        queries' ``prepare_query`` values where the engine server made
        them at arrival."""
        if model._retriever is not None:
            return self._batch_predict_device(model, queries, prepared)
        unavailable = self._unavailable_items()
        known = [
            (qi, model.user_index[q.user])
            for qi, q in queries
            if model.user_index.get(q.user) is not None
            and np.any(model.user_factors[model.user_index[q.user]])
        ]
        out: List[Tuple[int, PredictedResult]] = []
        if known:
            U = model.user_factors[[u for _, u in known]]
            all_scores = U @ model.item_factors.T  # [B, n_items]
            by_qi = {qi: all_scores[row] for row, (qi, _) in enumerate(known)}
        else:
            by_qi = {}
        for qi, q in queries:
            if qi in by_qi:
                out.append(
                    (qi, self._finish(model, q, by_qi[qi], unavailable))
                )
            else:
                out.append((qi, self._predict_one(model, q, unavailable)))
        return out

    # --- the sharded on-device retrieval path (prepared serving state) ---

    def _read_history(self, user: str) -> list:
        """One user's ``[(event, item, time ms), ...]``, newest first,
        over the seen events (when ``unseen_only``) and the similar
        events: one read of the event store a query, made by
        ``prepare_query`` after the query has arrived (on the engine
        server's prepare pool, or inside the batch where nothing was
        prepared) and kept nowhere. A query arrives after it was sent
        and the read connection sees every committed write, so an event
        the Event Server acknowledged before the query was sent is in
        it."""
        p = self.params
        names = list(dict.fromkeys(
            (p.seen_events if p.unseen_only else ()) + p.similar_events
        ))
        if not names:
            return []
        try:
            got = LEventStore().find_by_entities(
                app_name=p.app_name, entity_type="user",
                entity_ids=[user], event_names=names,
                target_entity_type="item",
            )[user]
        except Exception as e:
            logger.error("Error when reading the user's events: %s", e)
            return []
        _m_store_events().inc(len(got))
        return got

    def prepare_query(
        self, model: ECommModel, query: Query
    ) -> Optional[PreparedQuery]:
        """What serving ``query`` on the device needs of the query
        alone (BaseAlgorithm.prepare_query): whether the user has
        factors, the one read of the user's history, seen and
        blacklisted names -> the exclusion ids, whiteList -> ids,
        categories -> codes, the summed normalised recent-view row of a
        user without factors, and whether the lists fit the warm
        ladder. The engine server runs it when the query arrives,
        beside the batch ahead; ``_batch_predict_device`` runs it
        inline for a query that comes without (``predict``, ``pio
        eval``), so there is one code path. None when the model holds
        no prepared serving state (the host path reads for itself)."""
        retriever = model._retriever
        if retriever is None:
            return None
        p = self.params
        item_index = model.item_index
        with _tracing.stage(_tracing.HOST_PREP):
            user_idx = model.user_index.get(query.user)
            known = user_idx is not None and bool(
                np.any(model.user_factors[user_idx])
            )
        # a known user is read for the seen items alone; a user without
        # factors also for the recent views
        with _tracing.stage(_tracing.STORE_READ):
            events = (
                self._read_history(query.user)
                if p.unseen_only or not known else ()
            )
        with _tracing.stage(_tracing.MASK_PREP):
            seen = (
                {t for ev, t, _ in events if ev in p.seen_events}
                if p.unseen_only else set()
            )
            if known:
                row = model.user_factors[user_idx]
            else:
                logger.info("no userFeature found for user %s", query.user)
                recent = [
                    t for ev, t, _ in events if ev in p.similar_events
                ][:10]
                recent_idx = [
                    item_index[t] for t in recent if t in item_index
                ]
                if not recent_idx:
                    return _NO_RECENT_ITEM
                row = model.normed_rows(recent_idx).sum(axis=0)
            exclude = np.asarray(
                [item_index[i] for i in seen.union(query.black_list or ())
                 if i in item_index],
                np.int64,
            )
            include = None if query.white_list is None else np.asarray(
                [item_index[i] for i in query.white_list
                 if i in item_index],
                np.int64,
            )
            categories = (
                None if query.categories is None
                else model.category_codes(query.categories)
            )
            on_host = query.num > max(16, p.warm_num) or not retriever.fits(
                exclude=len(exclude),
                include=0 if include is None else len(include),
                categories=0 if categories is None else len(categories),
            )
        return PreparedQuery(
            row, not known, exclude, include, categories, seen, on_host
        )

    def _batch_predict_device(
        self, model: ECommModel, queries, prepared=None
    ) -> List[Tuple[int, PredictedResult]]:
        """The serving hot path: one fused score+mask+top_k run a
        micro-batch, exact-parity with the host ``_finish`` path. What
        depends on one query alone (its store read, its id lists) is
        ``prepare_query``'s: ``prepared[k]`` is its value for
        ``queries[k]`` where the engine server ran it at the query's
        arrival, and a query without one is prepared here, inline, by
        the same function. The batch then stacks the rows and pads the
        lists to the warm ladder's widths (``ItemRetriever.topn``).
        Known users score raw dot products against the resident
        factors; users without factors ride the same run in cosine mode
        (per-row flags) with a summed-normalized-recents query vector.
        Categories travel as a few codes, tested against the resident
        per-item codes in the program; blackList, seen items and
        whiteList as id lists. A query over the ladder's top is
        answered on the host. The unavailableItems set is the batch's,
        not the query's, and never reads the store here:
        ``cache.get()`` is the TTL tick that drives the out-of-band
        mask refresh."""
        unavailable = model._constraints.get()
        retriever = model._retriever
        out: List[Tuple[int, PredictedResult]] = []
        meta, rows, excl, incl, cats, cosine, on_host = (
            [], [], [], [], [], [], []
        )
        if prepared is None:
            prepared = [None] * len(queries)
        values = [
            pq or self.prepare_query(model, q)
            for pq, (_, q) in zip(prepared, queries)
        ]
        with _tracing.stage(_tracing.MASK_PREP):
            for (qi, q), pq in zip(queries, values):
                if pq.row is None:
                    out.append((qi, PredictedResult()))
                    continue
                if pq.cosine:
                    _m_recent_queries().inc()
                if pq.on_host:
                    on_host.append((qi, q, pq))
                    continue
                meta.append((qi, q))
                rows.append(pq.row)
                excl.append(pq.exclude)
                incl.append(pq.include)
                cats.append(pq.categories)
                cosine.append(pq.cosine)
        top = retriever.max_batch
        for s in range(0, len(meta), top):  # a batch over the ladder's top
            out += self._retrieve_group(
                model, meta[s:s + top], rows[s:s + top], excl[s:s + top],
                incl[s:s + top], cats[s:s + top], cosine[s:s + top],
            )
        for qi, q, pq in on_host:
            _m_host_fallbacks().inc()
            # a whiteList's rows alone where there is one; cosine = dot
            # over the item's norm, from the retriever's own norms: no
            # normalized copy of the catalog, no pass over it a query
            at = slice(None) if pq.include is None else np.unique(pq.include)
            part = model.item_factors[at] @ pq.row
            if pq.cosine:
                part *= retriever.reciprocal_norms[at]
            scores = np.zeros(retriever.n_items, np.float32)
            scores[at] = part
            out.append(
                (qi, self._finish(model, q, scores, unavailable, pq.seen))
            )
        empty = sum(1 for _, r in out if not r.item_scores)
        if empty:
            _m_empty_answers().inc(empty)
        return out

    def _retrieve_group(
        self, model: ECommModel, meta, rows, excl, incl, cats, cosine
    ) -> List[Tuple[int, PredictedResult]]:
        if not meta:
            return []
        retriever = model._retriever
        n_req = retrieval.pow2_topk_width(
            max(q.num for _, q in meta), retriever.n_items
        )
        scores, idx = retriever.topn(
            np.stack(rows).astype(np.float32),
            n_req,
            exclude=excl,
            include=incl,
            categories=cats,
            positive_only=True,
            normalize=np.asarray(cosine, bool),
        )
        b_pad, w_excl, w_incl = retriever.last_padded
        asked = sum(len(a) for a in excl) + sum(
            len(a) for a in incl if a is not None
        )
        slots = b_pad * (w_excl + (w_incl if w_incl > 1 else 0))
        _m_pad_waste().observe(1.0 - asked / slots)
        with _tracing.stage(_tracing.BUILD):
            names = model.item_names
            trimmed = retrieval.trimmed_results(
                scores, idx, [q.num for _, q in meta]
            )
            return [
                (
                    qi,
                    PredictedResult(
                        item_scores=tuple(
                            ItemScore(item=names[i], score=s)
                            for i, s in zip(ids.tolist(), ss.tolist())
                        )
                    ),
                )
                for (qi, _), (ids, ss) in zip(meta, trimmed)
            ]

    def _finish(
        self,
        model: ECommModel,
        query: Query,
        scores: np.ndarray,
        unavailable: Set[str],
        seen: Optional[Set[str]] = None,
    ) -> PredictedResult:
        black_list = set(query.black_list or ())
        black_list |= self._seen_items(query) if seen is None else seen
        black_list |= unavailable
        mask = self._candidate_mask(model, query, black_list)
        scores = np.where(mask & (scores > 0), scores, -np.inf)
        num = min(query.num, int((scores > -np.inf).sum()))
        if num <= 0:
            return PredictedResult()
        top = np.argpartition(-scores, num - 1)[:num]
        top = top[np.argsort(-scores[top])]
        return PredictedResult(
            item_scores=tuple(
                ItemScore(item=model.inv_item[int(i)], score=float(scores[i]))
                for i in top
            )
        )

    def query_from_json(self, json_obj) -> Query:
        """Upstream's query spells ``whiteList`` and ``blackList``; the
        dataclass's own field names are taken too."""
        obj = dict(json_obj or {})
        for theirs, ours in (("whiteList", "white_list"),
                             ("blackList", "black_list")):
            if theirs in obj:
                obj[ours] = obj.pop(theirs)
        return super().query_from_json(obj)

    def result_to_json(self, result: PredictedResult):
        return {
            "itemScores": [
                {"item": s.item, "score": s.score}
                for s in result.item_scores
            ]
        }


class Serving(FirstServing):
    pass


def ecommerce_engine() -> Engine:
    return Engine(
        data_source_classes=DataSource,
        preparator_classes=Preparator,
        algorithm_classes={"ecomm": ECommAlgorithm},
        serving_classes=Serving,
    )


class ECommerceEngineFactory(EngineFactory):
    def apply(self) -> Engine:
        return ecommerce_engine()
