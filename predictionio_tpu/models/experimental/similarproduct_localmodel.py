"""Similarproduct with an explicitly LOCAL (host-memory) model.

Reference mapping (examples/experimental/
scala-parallel-similarproduct-localmodel/): the similarproduct template
with the algorithm flipped from PAlgorithm to P2LAlgorithm — the trained
``productFeatures`` are ``collectAsMap``-ed into a plain driver-memory
``Map[Int, Array[Double]]`` and predict walks it with a PriorityQueue
(ALSAlgorithm.scala:25-42, 117-118, predict). The example teaches the
L-vs-P model split: a local model serves without a cluster.

The TPU runtime collapsed that split by design (one BaseAlgorithm; host
arrays ARE local), so the faithful analog keeps the model as a plain
``dict[int, np.ndarray]`` of item features and scores queries with
host-side numpy cosines — no device arrays, no warmed executables. Use
the main template (models/similarproduct) for the device-resident
serving path; this variant demonstrates that a pure-host model slots
into the same DASE plumbing unchanged.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Set

import numpy as np

from predictionio_tpu.controller import EngineFactory, FirstServing
from predictionio_tpu.controller.engine import Engine
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models.similarproduct.engine import (  # noqa: F401
    ALSAlgorithm,
    ALSAlgorithmParams,
    DataSource,
    DataSourceParams,
    Item,
    ItemScore,
    PredictedResult,
    PreparedData,
    Preparator,
    Query,
    TrainingData,
)


@dataclasses.dataclass
class ALSLocalModel:
    """Reference ALSLocalModel (ALSAlgorithm.scala:25-42): a plain
    in-memory map of item -> feature vector plus the id maps."""

    product_features: Dict[int, np.ndarray]
    item_index: BiMap
    items: Dict[int, Item]


class ALSLocalAlgorithm(ALSAlgorithm):
    """Train with the shared implicit-ALS kernel, then materialize the
    model as host dictionaries (the reference's ``collectAsMap``,
    ALSAlgorithm.scala:117-118); predict is pure-numpy cosine scoring."""

    def train(self, ctx, pd: PreparedData) -> ALSLocalModel:
        device_model = super().train(ctx, pd)
        return ALSLocalModel(
            product_features={
                j: np.asarray(device_model.item_factors[j])
                for j in range(device_model.item_factors.shape[0])
            },
            item_index=device_model.item_index,
            # the device model folds its items into category arrays;
            # the local model keeps the reference's map
            items={
                device_model.item_index[i]: item
                for i, item in pd.td.items.items()
            },
        )

    def warm(self, model: ALSLocalModel) -> None:
        """Nothing to compile — the local model never touches the device."""

    def predict(self, model: ALSLocalModel, query: Query) -> PredictedResult:
        # query items -> feature vectors (missing ids skipped, reference
        # predict's flatten over Option)
        q_feats = [
            model.product_features[model.item_index[i]]
            for i in query.items
            if i in model.item_index
            and model.item_index[i] in model.product_features
        ]
        if not q_feats:
            return PredictedResult(item_scores=())

        def as_set(ids) -> Optional[Set[int]]:
            if ids is None:
                return None
            return {
                model.item_index[i] for i in ids if i in model.item_index
            }

        white = as_set(query.white_list)
        black = as_set(query.black_list) or set()
        black |= {
            model.item_index[i] for i in query.items if i in model.item_index
        }
        cats = set(query.categories) if query.categories else None

        def cosine(a: np.ndarray, b: np.ndarray) -> float:
            na, nb = float(np.linalg.norm(a)), float(np.linalg.norm(b))
            if na == 0.0 or nb == 0.0:
                return 0.0
            return float(np.dot(a, b)) / (na * nb)

        scores: List[ItemScore] = []
        inverse = model.item_index.inverse()
        for j, feat in model.product_features.items():
            if white is not None and j not in white:
                continue
            if j in black:
                continue
            if cats is not None:
                item = model.items.get(j)
                if item is None or not cats.intersection(item.categories):
                    continue
            s = sum(cosine(qf, feat) for qf in q_feats)
            if s > 0:
                scores.append(ItemScore(item=inverse[j], score=s))
        scores.sort(key=lambda x: -x.score)
        return PredictedResult(item_scores=tuple(scores[: query.num]))


def similarproduct_localmodel_engine() -> Engine:
    return Engine(
        data_source_classes=DataSource,
        preparator_classes=Preparator,
        algorithm_classes={"als": ALSLocalAlgorithm},
        serving_classes=FirstServing,
    )


class SimilarProductLocalModelEngineFactory(EngineFactory):
    def apply(self) -> Engine:
        return similarproduct_localmodel_engine()
