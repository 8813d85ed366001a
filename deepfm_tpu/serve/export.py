"""Export + batch inference — the SavedModel/TF-Serving capability.

The reference exports a SavedModel with a raw-tensor serving signature
(``feat_ids`` int64 [None, F], ``feat_vals`` float [None, F] -> ``prob``;
ps:535-551) from hosts[0]/rank 0 only, and its ``infer`` task streams
probabilities to ``pred.txt`` (ps:526-533).

The servable here is a directory artifact:
    servable/
      config.json        — full framework Config (the signature's shape info)
      params/            — Orbax checkpoint of (params, model_state)
Loading returns a jitted ``predict(feat_ids, feat_vals) -> prob`` closure —
the serving signature as an XLA executable rather than a TF graph.
"""

from __future__ import annotations

import json
import os
from typing import Callable, Iterator

import jax
import numpy as np
import orbax.checkpoint as ocp

from ..core.config import Config
from ..models.base import get_model
from ..train.step import TrainState


def export_servable(
    cfg: Config, state: TrainState, directory: str | os.PathLike
) -> str:
    """Write the servable artifact.

    The reference exports from hosts[0]/rank 0 only (ps:548, hvd:475-493) to
    avoid concurrent writers.  Here the Orbax save is a *collective*: in a
    multi-host run every process must call it (each serializes only its
    addressable shards; Orbax coordinates one atomic directory), so all
    processes enter; only process 0 writes the small config.json.
    """
    directory = os.path.abspath(directory)
    os.makedirs(directory, exist_ok=True)
    if jax.process_index() == 0:
        with open(os.path.join(directory, "config.json"), "w") as f:
            json.dump(cfg.to_dict(), f, indent=2)
    ckptr = ocp.StandardCheckpointer()
    payload = {"params": state.params, "model_state": state.model_state}
    path = os.path.join(directory, "params")
    ckptr.save(path, payload, force=True)
    ckptr.wait_until_finished()
    ckptr.close()
    return directory


def _load_config(directory: str) -> Config:
    with open(os.path.join(directory, "config.json")) as f:
        return Config.from_dict(json.load(f))


def _restore_payload(directory: str, init_fn: Callable) -> tuple[dict, dict]:
    """Restore (params, model_state) against the abstract structure implied
    by the config — shape-safe (and silences orbax's no-target warning)."""
    abstract_params, abstract_state = jax.eval_shape(init_fn)
    device = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    abstract_params, abstract_state = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=device),
        (abstract_params, abstract_state),
    )
    ckptr = ocp.StandardCheckpointer()
    payload = ckptr.restore(
        os.path.join(directory, "params"),
        {"params": abstract_params, "model_state": abstract_state},
    )
    ckptr.close()
    return payload["params"], payload["model_state"]


def load_servable(directory: str | os.PathLike) -> tuple[Callable, Config]:
    """Load a CTR servable and return (jitted predict fn, config).

    predict(feat_ids [B, F] int, feat_vals [B, F] f32) -> prob [B] f32 —
    the reference's serving signature (ps:538-547).
    """
    directory = os.path.abspath(directory)
    cfg = _load_config(directory)
    if get_model(cfg.model).apply is None:  # no scoring call
        raise ValueError(
            f"load_servable scores a row with the model's apply; model "
            f"{cfg.model.model_name!r} declares none (a two-tower servable "
            f"loads with serve.load_retrieval_servable)"
        )
    model = get_model(cfg.model)
    params, model_state = _restore_payload(
        directory, lambda: model.init(jax.random.PRNGKey(0), cfg.model)
    )

    @jax.jit
    def predict(feat_ids, feat_vals):
        logits, _ = model.apply(
            params, model_state, feat_ids, feat_vals, cfg=cfg.model, train=False
        )
        return jax.nn.sigmoid(logits)

    return predict, cfg


def load_batching_servable(
    directory: str | os.PathLike,
    *,
    buckets: tuple[int, ...] | None = None,
    max_wait_ms: float = 2.0,
    max_queue_rows: int | None = None,
    precompile: bool = True,
):
    """Load a CTR servable wrapped in the micro-batching engine.

    Returns ``(MicroBatcher, Config)`` — the servable's jitted predict
    closure behind the dynamic batcher (serve/batcher.py): concurrent
    ``score`` calls coalesce into padded bucket shapes, each bucket one
    XLA executable, all compiled here (``precompile=True``) so the first
    live request never pays a compile.  This is the embeddable form of
    what ``serve_forever`` runs behind HTTP.
    """
    from .batcher import DEFAULT_BUCKETS, MicroBatcher

    predict, cfg = load_servable(directory)
    batcher = MicroBatcher(
        predict, cfg.model.field_size,
        buckets=DEFAULT_BUCKETS if buckets is None else buckets,
        max_wait_ms=max_wait_ms, max_queue_rows=max_queue_rows,
    )
    if precompile:
        batcher.precompile()
    return batcher, cfg


def load_retrieval_servable(
    directory: str | os.PathLike,
) -> tuple[Callable, Callable, Config]:
    """Load a two-tower servable: (encode_user, encode_item, config).

    ``encode_user(user_ids [B,Fu] int, user_vals [B,Fu] f32) -> [B,D] f32``
    and symmetrically for items — the dual-encoder serving signature (query
    encoding online, corpus encoding offline for ANN indexing).
    """
    from ..models.two_tower import encode_tower, init_two_tower

    directory = os.path.abspath(directory)
    cfg = _load_config(directory)
    if cfg.model.model_name != "two_tower":
        raise ValueError(
            f"servable holds model {cfg.model.model_name!r}; use load_servable"
        )
    params, _ = _restore_payload(
        directory, lambda: init_two_tower(jax.random.PRNGKey(0), cfg.model)
    )

    @jax.jit
    def encode_user(user_ids, user_vals):
        return encode_tower(
            params, user_ids, user_vals, cfg=cfg.model, side="user"
        )

    @jax.jit
    def encode_item(item_ids, item_vals):
        return encode_tower(
            params, item_ids, item_vals, cfg=cfg.model, side="item"
        )

    return encode_user, encode_item, cfg


def write_predictions(
    probs: Iterator[np.ndarray] | Iterator[float], path: str | os.PathLike
) -> int:
    """The ``infer``-task output: one probability per line (ps:526-533).
    An object-URL path uploads the finished file (spooled via tempfile so
    memory stays O(spool buffer), matching the local streaming write)."""
    from ..data.object_store import get_store, is_url

    count = 0
    if is_url(path):
        import tempfile

        with tempfile.SpooledTemporaryFile(
            max_size=1 << 24, mode="w+b"
        ) as f:
            for p in probs:
                arr = np.atleast_1d(np.asarray(p))
                for v in arr:
                    f.write(f"{float(v):.6f}\n".encode())
                    count += 1
            length = f.tell()
            f.seek(0)
            get_store().put_stream(str(path), f, length)
        return count
    with open(path, "w") as f:
        for p in probs:
            arr = np.atleast_1d(np.asarray(p))
            for v in arr:
                f.write(f"{float(v):.6f}\n")
                count += 1
    return count
