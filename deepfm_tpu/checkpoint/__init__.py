from .ckpt import Checkpointer, maybe_clear  # noqa: F401
from .remote import RemoteCheckpointer, make_checkpointer  # noqa: F401
from .reshard import (  # noqa: F401
    restore_resharded,
    restore_resharded_payload,
)


def save_paged(trainer, directory: str) -> dict:
    """Streaming paged checkpoint for a tiered trainer
    (deepfm_tpu/tiered): flush dirty rows+moments hot→host→cold, then
    commit a small metadata record — bytes moved scale with DIRTY rows,
    never the table, unlike the gather-everything Orbax path above.
    Thin indirection so checkpoint/ is
    the one place callers look for every save flavor; the mechanics
    live in ``tiered.trainer.TieredTrainer.save``/``restore``."""
    return trainer.save(directory)


def restore_paged(cfg, directory: str, **kwargs):
    """Counterpart of :func:`save_paged`: cache-COLD resume (tiers
    refill on demand; training continues bit-identically —
    tests/test_tiered.py)."""
    from ..tiered.trainer import TieredTrainer

    return TieredTrainer.restore(cfg, directory, **kwargs)
