"""Similar-product engine: ALS item factors + cosine top-N.

Reference mapping (examples/scala-parallel-similarproduct/multi/src/main/scala/):
- Query(items, num, categories?, whiteList?, blackList?) /
  PredictedResult(itemScores)                  <- Engine.scala
- DataSource: $set users/items + view events   <- DataSource.scala
- Preparator pass-through                      <- Preparator.scala
- ALSAlgorithm: implicit ALS over deduplicated view counts; predict =
  sum-of-cosines of candidate item factors against the query items'
  factors, filtered by candidacy rules          <- ALSAlgorithm.scala
- LikeAlgorithm (the "multi" variant's second algorithm): same ALS but
  over like/dislike events, like=+1 dislike=-1, latest event wins
                                               <- LikeAlgorithm.scala
- Serving sums scores per item across algorithms <- Serving.scala
"""

from __future__ import annotations

import dataclasses
import logging
import os
import pickle
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from predictionio_tpu.controller import (
    BaseAlgorithm,
    BaseDataSource,
    BasePreparator,
    BaseServing,
    EngineFactory,
    Params,
    SanityCheck,
)
from predictionio_tpu.controller.engine import Engine
from predictionio_tpu.controller.persistent_model import (
    PersistentModel,
    local_model_dir,
)
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.data.store import PEventStore
from predictionio_tpu.ops import retrieval
from predictionio_tpu.ops.als import ALSConfig, train_als, validate_solver
from predictionio_tpu.ops.retrieval import ItemRetriever
from predictionio_tpu.utils import metrics as _metrics
from predictionio_tpu.utils import tracing as _tracing

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class Query:
    items: Tuple[str, ...]
    num: int = 10
    categories: Optional[Tuple[str, ...]] = None
    white_list: Optional[Tuple[str, ...]] = None
    black_list: Optional[Tuple[str, ...]] = None

    def __post_init__(self):
        object.__setattr__(self, "items", tuple(self.items))
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
class ViewEvent:
    user: str
    item: str
    t: float


@dataclasses.dataclass
class LikeEvent:
    user: str
    item: str
    t: float
    like: bool  # like=True, dislike=False


@dataclasses.dataclass
class TrainingData(SanityCheck):
    users: Dict[str, dict]
    items: Dict[str, Item]
    view_events: List[ViewEvent]
    like_events: List[LikeEvent] = dataclasses.field(default_factory=list)

    def sanity_check(self) -> None:
        if not self.items:
            raise ValueError("items is empty — are item $set events present?")
        if not self.view_events and not self.like_events:
            raise ValueError("viewEvents is empty — are view events present?")


@dataclasses.dataclass
class PreparedData:
    td: TrainingData


@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = "default"
    channel_name: Optional[str] = None


class DataSource(BaseDataSource):
    """$set users/items + user-view->item events (reference DataSource.scala)."""

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
        views = [
            ViewEvent(
                user=e.entity_id,
                item=e.target_entity_id,
                t=e.event_time.timestamp(),
            )
            for e in store.find(
                p.app_name,
                channel_name=p.channel_name,
                entity_type="user",
                event_names=["view"],
                target_entity_type="item",
            )
        ]
        likes = [
            LikeEvent(
                user=e.entity_id,
                item=e.target_entity_id,
                t=e.event_time.timestamp(),
                like=e.event == "like",
            )
            for e in store.find(
                p.app_name,
                channel_name=p.channel_name,
                entity_type="user",
                event_names=["like", "dislike"],
                target_entity_type="item",
            )
        ]
        logger.info(
            "DataSource: %d users, %d items, %d views, %d likes",
            len(users), len(items), len(views), len(likes),
        )
        return TrainingData(
            users=users, items=items, view_events=views, like_events=likes
        )


class Preparator(BasePreparator):
    def prepare(self, ctx, td: TrainingData) -> PreparedData:
        return PreparedData(td=td)


@dataclasses.dataclass(frozen=True)
class ALSAlgorithmParams(Params):
    rank: int = 10
    num_iterations: int = 20
    lambda_: float = 0.01
    seed: Optional[int] = None
    # read by nothing since PR 33 (the host path is numpy and compiles
    # nothing); kept so that instances stored with it still load
    warm_max_query_items: int = 16
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
    # the closed ladder of the serving executables (ops/retrieval.py),
    # under the e-commerce engine's names: exclusion lists (the query
    # items + blackList) and inclusion lists (whiteList) pad to the
    # smallest listed width that holds the batch's longest, batches to
    # 8 doubling up to warm_max_batch; warm() compiles the whole
    # product, and a query over a ladder's top (or over warm_num, or
    # naming more than QUERY_CATEGORIES categories) is answered on the
    # host. Size exclude_widths by 10 query items plus the longest
    # blackList the shop's pages send, include_widths by the longest
    # whiteList
    exclude_widths: Tuple[int, ...] = (16, 64)
    include_widths: Tuple[int, ...] = (256,)
    # confidence scale for the implicit objective this engine always
    # trains (c = alpha*|r| on view events, MLlib trainImplicit parity)
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

# rows of the float32 table the host path scores at a time
_HOST_BLOCK = 1 << 16


def _m_host_fallbacks():
    return _metrics.get_registry().counter(
        "pio_similar_host_fallback_total",
        "Similar-product queries of a prepared serving state answered "
        "on the host: a list over the warm ladder's top, a num over "
        "warm_num, or more categories than the fused program takes",
    )


def _m_query_items():
    return _metrics.get_registry().histogram(
        "pio_similar_query_items",
        "Items of a similar-product query that the model has factors "
        "for (the rows its query vector sums)",
        buckets=(1, 2, 3, 4, 5, 6, 8, 10, 16, 32),
    )


@dataclasses.dataclass
class SPModel(PersistentModel):
    """Item factors + the items' categories as columns, for similarity
    serving. Nothing a Python object an item: the index is one BiMap,
    the categories are ``[n_items, C]`` codes (``category_arrays``, as
    ``ECommModel`` holds them), and ``items`` (``{dense index: Item}``,
    what a train produces) is folded into them at construction.

    A ``PersistentModel``: ``save`` writes the float32 table as
    ``item_factors.npy`` with the index and the category columns beside
    it under ``<PIO_FS_BASEDIR>/pmodels/<instance id>-<class name>/``,
    and ``load`` MAPS the table (``mmap_mode="r"``), so that a 19 GB
    catalog is never a blob in memory beside the array it holds; the
    model store keeps the manifest alone."""

    item_factors: np.ndarray  # [n_items, k] float32 (a map after load)
    item_index: BiMap
    items: Optional[Dict[int, Item]] = None
    category_names: Tuple[str, ...] = ()
    item_categories: Optional[np.ndarray] = None  # [n_items, C] int32
    # on-device retrieval state (ops/retrieval.py), built by
    # prepare_serving. Device state; never persisted.
    _retriever: Optional[ItemRetriever] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    _cat_code: Optional[Dict[str, int]] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    _item_names: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    _rn: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False, compare=False
    )

    def __post_init__(self):
        n = self.item_factors.shape[0]
        if self.items is not None:
            self.category_names, self.item_categories = (
                retrieval.category_arrays(self.items, n)
            )
            self.items = None
        elif self.item_categories is None:
            self.item_categories = np.full((n, 1), -1, np.int32)

    def __getstate__(self):
        state = self.__dict__.copy()
        for name in ("_retriever", "_cat_code", "_item_names", "_rn"):
            state[name] = None
        return state

    # --- persistence (controller/persistent_model.py) ---

    @classmethod
    def model_dir(cls, id: str) -> str:
        return os.path.join(local_model_dir(), f"{id}-{cls.__name__}")

    @classmethod
    def factors_path(cls, id: str) -> str:
        """Where ``save`` puts the float32 table. A writer that fills
        that file itself (``numpy.lib.format.open_memmap``) and hands
        the map in as ``item_factors`` is not copied: ``save`` flushes
        it."""
        return os.path.join(cls.model_dir(id), "item_factors.npy")

    def save(self, id: str, params: Params, ctx) -> bool:
        os.makedirs(self.model_dir(id), exist_ok=True)
        path, table = self.factors_path(id), self.item_factors
        if (
            isinstance(table, np.memmap) and os.path.exists(path)
            and os.path.samefile(table.filename, path)
        ):
            table.flush()
        else:
            np.save(path, np.asarray(table, np.float32))
        np.save(
            os.path.join(self.model_dir(id), "item_categories.npy"),
            self.item_categories,
        )
        with open(os.path.join(self.model_dir(id), "index.pkl"), "wb") as f:
            pickle.dump(
                {"item_index": self.item_index,
                 "category_names": tuple(self.category_names)},
                f, protocol=pickle.HIGHEST_PROTOCOL,
            )
        return True

    @classmethod
    def load(cls, id: str, params: Params, ctx) -> "SPModel":
        d = cls.model_dir(id)
        with open(os.path.join(d, "index.pkl"), "rb") as f:
            index = pickle.load(f)
        return cls(
            item_factors=np.load(cls.factors_path(id), mmap_mode="r"),
            item_index=index["item_index"],
            category_names=index["category_names"],
            item_categories=np.load(os.path.join(d, "item_categories.npy")),
        )

    # --- what a query needs of the model ---

    @property
    def reciprocal_norms(self) -> np.ndarray:
        """1/||row|| of the float32 table, [n_items]: the retriever's
        own where serving is prepared, else computed once in row blocks
        (4 bytes an item; never a normalized copy of the table)."""
        if self._retriever is not None:
            return self._retriever.reciprocal_norms
        if self._rn is None:
            n = self.item_factors.shape[0]
            rn = np.zeros(n, np.float32)
            for a in range(0, n, _HOST_BLOCK):
                rn[a:a + _HOST_BLOCK] = retrieval._reciprocal_norms(
                    self.item_factors[a:a + _HOST_BLOCK]
                )
            self._rn = rn
        return self._rn

    def category_codes(self, categories) -> np.ndarray:
        """Codes of the given category names (names no item carries
        have none: an empty array means NO candidates)."""
        if self._cat_code is None:
            self._cat_code = {
                c: j for j, c in enumerate(self.category_names)
            }
        return retrieval.category_codes(self._cat_code, categories)

    @property
    def item_names(self) -> np.ndarray:
        """Item names by dense index (an object array: not a second
        pair of dicts, and nothing the collector walks)."""
        if self._item_names is None:
            self._item_names = retrieval.names_by_index(self.item_index)
        return self._item_names

    def _spec(self, query: Query):
        """(query vector, exclusion ids, whitelist ids or None, category
        codes or None), or None when no query item has factors. The
        query vector is the sum of the normalized rows of the query
        items, gathered from the float32 table (at most a handful of
        rows: upstream's cosine sum folded to one [k] row); exclusions
        are the query items themselves plus the blackList."""
        query_idx = [
            self.item_index[i] for i in query.items if i in self.item_index
        ]
        if not query_idx:
            return None
        _m_query_items().observe(len(query_idx))
        at = np.asarray(query_idx, np.int64)
        rows = np.asarray(self.item_factors[at], np.float32)
        qvec = (rows * self.reciprocal_norms[at][:, None]).sum(axis=0)
        excl = set(query_idx)
        excl.update(
            self.item_index[i] for i in query.black_list or ()
            if i in self.item_index
        )
        return (
            qvec, np.asarray(sorted(excl), np.int64),
            retrieval.include_candidates(self.item_index, query.white_list),
            None if query.categories is None
            else self.category_codes(query.categories),
        )

    def _result(self, ids, scores) -> PredictedResult:
        names = self.item_names
        return PredictedResult(
            item_scores=tuple(
                ItemScore(item=names[i], score=s)
                for i, s in zip(ids.tolist(), scores.tolist())
            )
        )

    def similar_batch(
        self, queries, warm_num: int = 16
    ) -> List[Tuple[int, PredictedResult]]:
        """Batched on-device retrieval: every query of the micro-batch
        that fits the warm ladder rides ONE fused cosine
        score+mask+top_k program over the resident factors (requires
        prepare_serving); categories travel as codes, tested against
        the resident per-item codes. A query over the ladder's top is
        answered on the host and counted."""
        retriever = self._retriever
        out: List[Tuple[int, PredictedResult]] = []
        meta, rows, excludes, includes, cats = [], [], [], [], []
        on_host = []
        # the name lookups and the query rows' gather from the table
        with _tracing.stage(_tracing.HOST_PREP):
            for qi, q in queries:
                spec = self._spec(q)
                if spec is None:
                    logger.info(
                        "no item factors for query items %s", q.items
                    )
                    out.append((qi, PredictedResult()))
                    continue
                qvec, excl, incl, codes = spec
                if q.num > max(16, warm_num) or not retriever.fits(
                    exclude=len(excl),
                    include=0 if incl is None else len(incl),
                    categories=0 if codes is None else len(codes),
                ):
                    on_host.append((qi, q, spec))
                    continue
                meta.append((qi, q))
                rows.append(qvec)
                excludes.append(excl)
                includes.append(incl)
                cats.append(codes)
        for qi, q, spec in on_host:
            _m_host_fallbacks().inc()
            out.append((qi, self._similar_host(q, spec)))
        top = retriever.max_batch  # a batch over the ladder's top is split
        for s in range(0, len(meta), top):
            part = meta[s:s + top]
            n_req = retrieval.pow2_topk_width(
                max(q.num for _, q in part), retriever.n_items
            )
            scores, idx = retriever.topn(
                np.stack(rows[s:s + top]).astype(np.float32),
                n_req,
                exclude=excludes[s:s + top],
                include=includes[s:s + top],
                categories=cats[s:s + top],
                positive_only=True,
                normalize=True,
            )
            with _tracing.stage(_tracing.BUILD):
                trimmed = retrieval.trimmed_results(
                    scores, idx, [q.num for _, q in part]
                )
                out += [
                    (qi, self._result(ids, ss))
                    for (qi, _), (ids, ss) in zip(part, trimmed)
                ]
        return out

    def similar(self, query: Query) -> PredictedResult:
        """Reference ALSAlgorithm.predict: sum-of-cosines scoring with
        candidacy filtering and top-num selection. With a prepared
        serving state the scoring+masking+selection runs fused on
        device (similar_batch); the numpy path below is the
        training-time implementation and the answer to a query the warm
        ladder does not hold."""
        if self._retriever is not None:
            [(_, result)] = self.similar_batch([(0, query)])
            return result
        spec = self._spec(query)
        if spec is None:
            logger.info("no item factors for query items %s", query.items)
            return PredictedResult()
        return self._similar_host(query, spec)

    def _similar_host(self, query: Query, spec) -> PredictedResult:
        """The numpy path: the float32 table times the query vector in
        row blocks (numpy's float32 product is a float32 product, what
        ``precision="highest"`` asks of the device), over the whitelist's
        rows alone where there is one; cosine = dot over the row's norm.
        Never a normalized copy of the table, never the device."""
        qvec, excl, incl, codes = spec
        n = self.item_factors.shape[0]
        rn = self.reciprocal_norms
        scores = np.zeros(n, np.float32)
        if incl is not None:
            at = np.unique(incl)
            scores[at] = (
                np.asarray(self.item_factors[at], np.float32) @ qvec
            ) * rn[at]
            mask = np.zeros(n, bool)
            mask[at] = True
        else:
            for a in range(0, n, _HOST_BLOCK):
                scores[a:a + _HOST_BLOCK] = (
                    np.asarray(self.item_factors[a:a + _HOST_BLOCK],
                               np.float32) @ qvec
                ) * rn[a:a + _HOST_BLOCK]
            mask = np.ones(n, bool)
        mask &= scores > 0
        mask[excl] = False  # the query items themselves + blackList
        if codes is not None:
            mask &= np.isin(self.item_categories, codes).any(axis=1)
        live = np.flatnonzero(mask)
        # best first, ties to the lowest index (the device's order)
        top = live[np.lexsort((live, -scores[live]))][: query.num]
        return self._result(top, scores[top])


class LikeSPModel(SPModel):
    """``LikeAlgorithm``'s model: a class of its own so that the multi
    variant's two models persist to two directories."""


class ALSAlgorithm(BaseAlgorithm):
    """Implicit ALS over deduplicated view counts (reference
    ALSAlgorithm.scala train: reduceByKey count -> ALS.trainImplicit)."""

    params_class = ALSAlgorithmParams
    query_class = Query
    model_class = SPModel

    def _ratings(self, td: TrainingData):
        """(user, item) -> value triples. Overridden by LikeAlgorithm."""
        counts: Dict[Tuple[str, str], float] = {}
        for v in td.view_events:
            key = (v.user, v.item)
            counts[key] = counts.get(key, 0.0) + 1.0
        return counts

    def train(self, ctx, pd: PreparedData) -> SPModel:
        td = pd.td
        item_index = BiMap.string_int(td.items.keys())
        user_index = BiMap.string_int(
            set(td.users.keys())
            | {v.user for v in td.view_events}
            | {e.user for e in td.like_events}
        )
        triples = [
            (user_index[u], item_index[i], val)
            for (u, i), val in self._ratings(td).items()
            if i in item_index
        ]
        if not triples:
            raise ValueError(
                "no valid (user, item) events after index mapping"
            )
        u, i, r = (np.asarray(x) for x in zip(*triples))
        p = self.params
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
                implicit_prefs=True,
                alpha=p.alpha,
                seed=p.seed if p.seed is not None else 0,
                solver=p.solver,
                block_size=p.block_size,
            ),
            mesh=ctx.mesh if ctx is not None else None,
        )
        return self.model_class(
            item_factors=arrays.item_factors,
            item_index=item_index,
            items={item_index[i]: item for i, item in td.items.items()},
        )  # folded into category arrays by the model

    def predict(self, model: SPModel, query: Query) -> PredictedResult:
        [(_, result)] = self.batch_predict(model, [(0, query)])
        return result

    def batch_predict(self, model: SPModel, queries):
        """With a prepared serving state the whole micro-batch scores as
        ONE fused retrieval program (model.similar_batch); otherwise the
        per-query numpy path."""
        if model._retriever is not None:
            return model.similar_batch(queries, self.params.warm_num)
        return [(i, model.similar(q)) for i, q in queries]

    def prepare_serving(self, ctx, model: SPModel) -> SPModel:
        """Build the prepared serving state: item factors resident on
        device in the params' ``precision``, row-sharded over the
        workflow mesh when it has >1 device (ops/retrieval.py), the
        items' category codes resident beside them, and the executable
        space closed by the params' ladders: candidacy rules apply as
        on-device masks instead of a host post-filter."""
        mesh = ctx.mesh if ctx is not None else None
        p = self.params
        model._retriever = ItemRetriever(
            model.item_factors, mesh=mesh, component="similarproduct",
            precision=p.precision,
            shortlist_mult=p.shortlist_mult,
            category_codes=model.item_categories,
            category_width=QUERY_CATEGORIES,
            exclude_ladder=p.exclude_widths,
            include_ladder=(1, *p.include_widths),
            max_batch=p.warm_max_batch,
        )
        # samples from deploy on: a scrape tells "none" from "no family"
        _m_host_fallbacks().inc(0)
        return model

    def serving_precision(self, model: SPModel) -> Optional[str]:
        if model._retriever is not None:
            return model._retriever.precision
        return None

    def release_serving(self, model: SPModel) -> None:
        """Free a displaced model's device-resident serving state
        (promotion drain→release contract, controller/base.py): null
        the reference first, so that a straggler is answered by the
        numpy path over the host's float32 table (seconds a query at
        catalog scale, and no device: nothing is uploaded again), then
        drop the retriever's resident buffers."""
        retriever, model._retriever = model._retriever, None
        if retriever is not None:
            model._rn = retriever.reciprocal_norms.copy()
            retriever.free()

    def warm(self, model: SPModel) -> None:
        """Compile the whole closed ladder before the server reports
        ready (see BaseAlgorithm.warm) and build the name table; the
        numpy path of an unprepared model compiles nothing."""
        if model._retriever is None:
            return
        p = self.params
        t0 = time.perf_counter()
        model._retriever.warm(
            n=p.warm_num, max_batch=p.warm_max_batch,
            flag_combos=((True, True),),
        )
        model.item_names  # built here, not inside the first batch
        logger.info(
            "similarproduct warm ladder: %d executables in %.1fs",
            model._retriever.ladder_size(
                tiers=len(model._retriever.warm_tiers(p.warm_num))
            ),
            time.perf_counter() - t0,
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


class LikeAlgorithm(ALSAlgorithm):
    """The multi-variant's second algorithm (reference LikeAlgorithm.scala):
    like/dislike events, like=+1 dislike=-1, LATEST event per (user, item)
    wins; same implicit ALS and cosine predict."""

    model_class = LikeSPModel

    def _ratings(self, td: TrainingData):
        latest: Dict[Tuple[str, str], Tuple[float, float]] = {}
        for e in td.like_events:
            key = (e.user, e.item)
            value = 1.0 if e.like else -1.0
            if key not in latest or e.t >= latest[key][0]:
                latest[key] = (e.t, value)
        return {k: val for k, (_, val) in latest.items()}


@dataclasses.dataclass(frozen=True)
class DIMSUMAlgorithmParams(Params):
    threshold: float = 0.0


@dataclasses.dataclass
class DIMSUMModel:
    """Thresholded item-item cosine similarity matrix + metadata."""

    similarities: np.ndarray  # [n_items, n_items], zeroed under threshold
    item_index: BiMap
    items: Dict[int, Item]
    _inv_index: Optional[BiMap] = dataclasses.field(
        default=None, repr=False, compare=False
    )

    def __getstate__(self):
        state = self.__dict__.copy()
        state["_inv_index"] = None
        return state

    @property
    def inv_index(self) -> BiMap:
        if self._inv_index is None:
            self._inv_index = self.item_index.inverse()
        return self._inv_index


class DIMSUMAlgorithm(BaseAlgorithm):
    """Item-item column similarity of the binary user x item view matrix
    (reference experimental scala-parallel-similarproduct-dimsum,
    DIMSUMAlgorithm.scala: RowMatrix.columnSimilarities(threshold)).

    DIMSUM's sampling approximation exists because the exact Gram matrix
    is shuffle-bound on a Spark cluster; on the MXU the EXACT computation
    is one [I, U] x [U, I] matmul of the normalized view matrix, so this
    computes exact cosine similarities and applies the threshold as a
    filter rather than a sampling parameter."""

    params_class = DIMSUMAlgorithmParams
    query_class = Query

    def train(self, ctx, pd: PreparedData) -> DIMSUMModel:
        import jax
        import jax.numpy as jnp

        from predictionio_tpu.ops.similarity import normalize_rows

        td = pd.td
        user_index = BiMap.string_int(
            set(td.users.keys()) | {v.user for v in td.view_events}
        )
        item_index = BiMap.string_int(td.items.keys())
        R = np.zeros((len(user_index), len(item_index)), np.float32)
        for v in td.view_events:
            if v.item in item_index:
                R[user_index[v.user], item_index[v.item]] = 1.0
        # cosine over columns = normalized-column Gram matrix (one matmul)
        Rn = normalize_rows(R.T)  # [I, U] rows = items, L2-normalized
        sims = np.array(  # writable host copy (np.asarray of a jax.Array is read-only)
            jax.jit(
                lambda a: jnp.dot(a, a.T, preferred_element_type=jnp.float32)
            )(jnp.asarray(Rn))
        )
        np.fill_diagonal(sims, 0.0)
        sims[sims < self.params.threshold] = 0.0
        return DIMSUMModel(
            similarities=sims,
            item_index=item_index,
            items={item_index[i]: item for i, item in td.items.items()},
        )

    def predict(self, model: DIMSUMModel, query: Query) -> PredictedResult:
        query_idx = [
            model.item_index[i] for i in query.items if i in model.item_index
        ]
        if not query_idx:
            return PredictedResult()
        scores = model.similarities[query_idx].sum(axis=0)
        mask = scores > 0
        mask[query_idx] = False
        if query.white_list is not None:
            wl = np.zeros_like(mask)
            wl[[
                model.item_index[i]
                for i in query.white_list
                if i in model.item_index
            ]] = True
            mask &= wl
        if query.black_list is not None:
            mask[[
                model.item_index[i]
                for i in query.black_list
                if i in model.item_index
            ]] = False
        if query.categories is not None:
            cats = set(query.categories)
            for idx in np.nonzero(mask)[0]:
                item = model.items.get(int(idx))
                if item is None or not cats.intersection(item.categories):
                    mask[idx] = False
        scores = np.where(mask, scores, -np.inf)
        num = min(query.num, int(mask.sum()))
        if num <= 0:
            return PredictedResult()
        top = np.argpartition(-scores, num - 1)[:num]
        top = top[np.argsort(-scores[top])]
        return PredictedResult(
            item_scores=tuple(
                ItemScore(item=model.inv_index[int(i)], score=float(scores[i]))
                for i in top
            )
        )

    def result_to_json(self, result: PredictedResult):
        return {
            "itemScores": [
                {"item": s.item, "score": s.score}
                for s in result.item_scores
            ]
        }


class Serving(BaseServing):
    """Sums scores per item across algorithms (reference multi/Serving.scala
    combines standard + like predictions by summed score)."""

    def serve(self, query: Query, predictions: Sequence[PredictedResult]) -> PredictedResult:
        combined: Dict[str, float] = {}
        for p in predictions:
            for s in p.item_scores:
                combined[s.item] = combined.get(s.item, 0.0) + s.score
        top = sorted(combined.items(), key=lambda kv: -kv[1])[: query.num]
        return PredictedResult(
            item_scores=tuple(
                ItemScore(item=i, score=sc) for i, sc in top
            )
        )


def similarproduct_engine() -> Engine:
    return Engine(
        data_source_classes=DataSource,
        preparator_classes=Preparator,
        algorithm_classes={
            "als": ALSAlgorithm,
            "likealgo": LikeAlgorithm,
            "dimsum": DIMSUMAlgorithm,
        },
        serving_classes=Serving,
    )


class SimilarProductEngineFactory(EngineFactory):
    def apply(self) -> Engine:
        return similarproduct_engine()
