"""PersistentModel: manual model persistence contract.

Capability parity with reference controller/PersistentModel.scala:48-95 and
LocalFileSystemPersistentModel.scala:44-74. A model class opts into managing
its own persistence (e.g. writing factor shards as npz/orbax checkpoints)
instead of being pickled into the MODELDATA store; the workflow then stores
only a PersistentModelManifest and resolves the loader at deploy time
(reference SparkWorkflowUtils.getPersistentModel, WorkflowUtils.scala:349-383).
"""

from __future__ import annotations

import dataclasses
import importlib
import os

from predictionio_tpu.utils.fs import fs_basedir
import pickle
from typing import Any, Optional

from predictionio_tpu.controller.params import Params


@dataclasses.dataclass(frozen=True)
class PersistentModelManifest:
    """Stored in place of a manually-persisted model
    (reference workflow/PersistentModelManifest.scala:18)."""

    class_name: str


class PersistentModel:
    """Mixin: implement ``save``; provide a classmethod ``load``
    (the reference's companion-object PersistentModelLoader)."""

    def save(self, id: str, params: Params, ctx) -> bool:
        """Persist the model. Return False to fall back to default
        pickling (reference PersistentModel.scala:78-82)."""
        raise NotImplementedError

    @classmethod
    def load(cls, id: str, params: Params, ctx) -> "PersistentModel":
        raise NotImplementedError


def load_persistent_model(
    manifest: PersistentModelManifest, id: str, params: Params, ctx
) -> Any:
    """Resolve the model class from the manifest and call its loader.

    The manifest stores ``module.qualname``; qualname may itself contain
    dots (nested classes), so resolve by importing the longest importable
    module prefix and getattr-walking the remainder.
    """
    parts = manifest.class_name.split(".")
    module = None
    split_at = 0
    for i in range(len(parts) - 1, 0, -1):
        try:
            module = importlib.import_module(".".join(parts[:i]))
            split_at = i
            break
        except ImportError:
            continue
    if module is None:
        raise ImportError(
            f"cannot resolve persistent model class {manifest.class_name!r}"
        )
    cls: Any = module
    for part in parts[split_at:]:
        cls = getattr(cls, part)
    return cls.load(id, params, ctx)


def local_model_dir() -> str:
    """``<PIO_FS_BASEDIR>/pmodels``: where the models that persist
    themselves to the local file system keep their files."""
    d = os.path.join(
        fs_basedir(),
        "pmodels",
    )
    os.makedirs(d, exist_ok=True)
    return d


class LocalFileSystemPersistentModel(PersistentModel):
    """Helper saving via pickle to the local FS
    (reference LocalFileSystemPersistentModel.scala:44-74; Utils.save/load
    controller/Utils.scala)."""

    def save(self, id: str, params: Params, ctx) -> bool:
        from predictionio_tpu.utils.serialize import to_host

        path = os.path.join(local_model_dir(), f"{id}-{type(self).__name__}")
        with open(path, "wb") as f:
            pickle.dump(to_host(self), f, protocol=pickle.HIGHEST_PROTOCOL)
        return True

    @classmethod
    def load(cls, id: str, params: Params, ctx) -> "LocalFileSystemPersistentModel":
        path = os.path.join(local_model_dir(), f"{id}-{cls.__name__}")
        with open(path, "rb") as f:
            return pickle.load(f)
