"""WorkflowContext: the per-run compute/storage context.

The reference creates one SparkContext per workflow run
(core/.../workflow/WorkflowContext.scala:26-45 — app name
"PredictionIO <mode>: <batch>", env passthrough). The TPU analog carries:

- ``storage`` — the configured Storage universe (event + metadata + models)
- ``mesh``    — the `jax.sharding.Mesh` the run's kernels shard over
- ``mode`` / ``batch`` — labels for logging and instance records

The mesh is constructed lazily on first access so host-only workflows
(event import, metadata admin) never touch the accelerator.
"""

from __future__ import annotations

import logging
from typing import Dict, Optional

logger = logging.getLogger(__name__)


class WorkflowContext:
    def __init__(
        self,
        mode: str = "",
        batch: str = "",
        storage=None,
        mesh=None,
        env: Optional[Dict[str, str]] = None,
    ):
        from predictionio_tpu.utils.profiling import PhaseTimer

        self.mode = mode
        self.batch = batch
        self.env = dict(env or {})
        self._storage = storage
        self._mesh = mesh
        # per-run phase timers (SURVEY.md §5: first-class observability)
        self.timer = PhaseTimer()

    @property
    def app_name(self) -> str:
        return f"PredictionIO-TPU {self.mode}: {self.batch}"

    @property
    def storage(self):
        if self._storage is None:
            from predictionio_tpu.data.storage import get_storage

            self._storage = get_storage()
        return self._storage

    @property
    def mesh(self):
        if self._mesh is None:
            from predictionio_tpu.parallel import default_mesh
            from predictionio_tpu.utils.compilation_cache import (
                ensure_compilation_cache,
            )

            # first accelerator touch of the run: make compiled
            # executables persistent so repeat trains/evals/deploys skip
            # the multi-second XLA compile (no reference analog — the
            # JVM substrate has no compilation step)
            ensure_compilation_cache()
            self._mesh = default_mesh()
            first = self._mesh.devices.flat[0]
            logger.info(
                "%s: created %s on platform=%s device_kind=%r",
                self.app_name, dict(self._mesh.shape),
                first.platform, first.device_kind,
            )
        return self._mesh

    def stop(self) -> None:
        """SparkContext.stop analog — nothing to tear down; the mesh is a
        device view, not a resource."""
        self._mesh = None


def workflow_context(
    mode: str = "", batch: str = "", storage=None, mesh=None, env=None
) -> WorkflowContext:
    """Factory mirroring reference WorkflowContext.apply."""
    return WorkflowContext(mode=mode, batch=batch, storage=storage, mesh=mesh, env=env)
