"""Sharded checkpoint/resume — the tf.estimator model_dir capability.

The reference delegates checkpointing to the Estimator: PS mode writes to a
shared S3 ``model_dir`` (ps nb cell 4, README.md:63), HVD mode writes locally
on rank 0 only — "to prevent other workers from corrupting them" (hvd:397,
hvd:402-415) — and spot-instance restart resumes from the latest checkpoint
(SURVEY §5).  Here:

* **single-logical-writer by construction**: Orbax coordinates all processes
  of a multi-host run in one atomic save of the sharded TrainState — each
  host writes only its addressable shards; no rank-0 funnel, no corruption
  window to work around.
* **resume = restore latest** into the exact shardings of the running mesh.
* retention (``keep_checkpoints``) and cadence (``checkpoint_every_steps``)
  replace RunConfig's save_checkpoints_* knobs.
"""

from __future__ import annotations

import os

import jax
import orbax.checkpoint as ocp

from ..train.step import TrainState


class Checkpointer:
    def __init__(
        self,
        directory: str | os.PathLike,
        *,
        max_to_keep: int = 3,
        async_save: bool = True,
    ):
        self._mngr = ocp.CheckpointManager(
            os.path.abspath(directory),
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                create=True,
                # async: the device->host copy completes before save()
                # returns (so donated train-state buffers are safe to reuse
                # immediately); only the file serialization runs in the
                # background, overlapped with subsequent train steps.  At
                # north-star table sizes a blocking save would stall training
                # for the full write.
                enable_async_checkpointing=async_save,
            ),
        )

    def save(self, state: TrainState, *, block: bool = False) -> bool:
        """Save at ``state.step``.  Cadence is the CALLER's policy (the train
        loop's ``step % checkpoint_every_steps`` gate) — this class holds no
        interval logic.  A step already on disk is a no-op (so a final save
        after a periodic save at the same step is safe); returns whether a
        save happened.

        Async semantics: each save first barriers on any in-flight previous
        save (``wait_until_finished`` at the next save point), then kicks off
        the new one and returns as soon as the device->host copy is done.
        ``block=True`` additionally waits for the write to hit disk."""
        self._mngr.wait_until_finished()
        step = int(state.step)
        if step in self._mngr.all_steps():
            return False
        saved = self._mngr.save(step, args=ocp.args.StandardSave(state), force=True)
        if block:
            self._mngr.wait_until_finished()
        return bool(saved)

    def wait_until_finished(self) -> None:
        """Barrier on any in-flight async save."""
        self._mngr.wait_until_finished()

    def latest_step(self) -> int | None:
        return self._mngr.latest_step()

    def restore(self, target_state: TrainState, step: int | None = None) -> TrainState:
        """Restore into the shardings/dtypes of ``target_state`` (an existing
        or abstract TrainState from the running mesh)."""
        self._mngr.wait_until_finished()  # an in-flight save may be `step`
        step = self.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError("no checkpoint to restore")
        abstract = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=getattr(x, "sharding", None))
            if hasattr(x, "shape")
            else x,
            target_state,
        )
        return self._mngr.restore(step, args=ocp.args.StandardRestore(abstract))

    def all_steps(self) -> list[int]:
        self._mngr.wait_until_finished()
        return list(self._mngr.all_steps())

    def close(self) -> None:
        self._mngr.wait_until_finished()
        self._mngr.close()


def maybe_clear(directory: str, enabled: bool) -> None:
    """``clear_existing_model`` capability (hvd:66-68, hvd:372-378); remote
    model_dirs clear the object prefix instead."""
    if not enabled:
        return
    from ..data.object_store import get_store, is_url

    if is_url(directory):
        get_store().delete_prefix(directory.rstrip("/") + "/")
    elif os.path.isdir(directory):
        import shutil

        shutil.rmtree(directory)
