"""Checkpoint/resume for elastic training (beyond the reference).

The reference has no checkpoint story (SURVEY.md §5: contexts are
immutable, recovery is "rebuild and start over") — which leaves the
actual production question unanswered: after `resilience.rebuild_after_
failure` shrinks the group, where does the model state come from? This
module closes that loop with an orbax-backed step store:

    ckpt = StepCheckpointer(dir)
    ckpt.save(step, {"params": params, "opt": opt_state})
    ...crash, rebuild_after_failure -> new (rank, size)...
    step, state = ckpt.load_latest(template)   # shardings preserved

Checkpoints are rank-0-writes / everyone-reads (DDP-style replicated
state; sharded state restores onto whatever shardings the template
carries, so a post-failure SMALLER mesh re-lays the arrays out
automatically — orbax resharding on restore).

Note for host-plane-only trainer processes: orbax imports jax, and jax's
first backend initialization takes the accelerator if there is one. A
process that needs none runs with JAX_PLATFORMS=cpu, so that it neither
waits for nor holds a chip.
"""

from __future__ import annotations

import os
import re
from typing import Any, Optional, Tuple

_STEP_RE = re.compile(r"^step_(\d+)$")


class StepCheckpointer:
    """Durable (dir-per-step, atomic-rename) pytree checkpoints.

    Built on orbax StandardCheckpointer: jax arrays (with shardings),
    numpy arrays, and python scalars all round-trip. Safe against a crash
    mid-save: orbax commits via rename, and load_latest skips uncommitted
    step dirs.
    """

    def __init__(self, directory: str, keep: int = 3):
        import orbax.checkpoint as ocp

        self._dir = os.path.abspath(directory)
        os.makedirs(self._dir, exist_ok=True)
        self._keep = keep
        self._ckpt = ocp.StandardCheckpointer()

    def _step_path(self, step: int) -> str:
        return os.path.join(self._dir, f"step_{step}")

    def steps(self):
        """Committed step numbers, ascending."""
        out = []
        for name in os.listdir(self._dir):
            m = _STEP_RE.match(name)
            if m and self._is_committed(os.path.join(self._dir, name)):
                out.append(int(m.group(1)))
        return sorted(out)

    @staticmethod
    def _is_committed(path: str) -> bool:
        # Orbax writes into a tmp dir and renames on commit; a committed
        # checkpoint contains its metadata file. The writer's _gc may
        # delete a step between our isdir and listdir (rank-0-writes /
        # everyone-reads has no reader coordination) — a vanished dir is
        # simply not a candidate.
        try:
            return os.path.isdir(path) and any(
                name.startswith("_CHECKPOINT_METADATA") or name == "d"
                or name.endswith(".zarray") or name == "_METADATA"
                for name in os.listdir(path))
        except (FileNotFoundError, NotADirectoryError):
            return False

    def save(self, step: int, state: Any, *, force: bool = False) -> None:
        """Write `state` under `step` (typically from rank 0 only —
        checkpoints are rank-0-writes / everyone-reads). Blocks until the
        checkpoint is COMMITTED: orbax saves asynchronously by default,
        and an uncommitted step is exactly what a crash-resume contract
        cannot tolerate."""
        self._ckpt.save(self._step_path(step), state, force=force)
        if hasattr(self._ckpt, "wait_until_finished"):
            self._ckpt.wait_until_finished()
        self._gc()

    def load(self, step: int, template: Optional[Any] = None) -> Any:
        """Restore a specific step. With a template (matching pytree of
        arrays or jax.ShapeDtypeStruct, optionally carrying shardings),
        arrays restore onto the template's shardings — a smaller
        post-failure mesh re-lays the state out automatically."""
        if template is None:
            return self._ckpt.restore(self._step_path(step))
        return self._ckpt.restore(self._step_path(step), template)

    def load_latest(self, template: Optional[Any] = None
                    ) -> Tuple[Optional[int], Optional[Any]]:
        """(step, state) of the newest committed checkpoint, or
        (None, None) when the directory has none. Falls back to the
        next-newest step if the writer's retention GC deletes one
        between listing and restore."""
        for step in reversed(self.steps()):
            try:
                return step, self.load(step, template)
            except FileNotFoundError:
                continue
        return None, None

    def _gc(self) -> None:
        import shutil

        steps = self.steps()
        for step in steps[:-self._keep] if self._keep > 0 else []:
            shutil.rmtree(self._step_path(step), ignore_errors=True)
