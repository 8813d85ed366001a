"""DeepFM: wide (1st-order) + FM (2nd-order) + deep MLP, TPU-first.

Reproduces the reference forward pass exactly (model_fn, ps:172-260):

    y = FM_B + Σ_f w_f·x_f + 0.5Σ_k((Σ_f e)²−Σ_f e²) + MLP(flatten(e))
    e_fk = V[id_f]_k · x_f
    pred = σ(y)

with the reference's initialization (zeros bias; glorot_normal FM_W/FM_V,
ps:186-198; glorot_uniform MLP kernels + zero biases — the
``contrib.layers.fully_connected`` defaults, ps:233-255), relu MLP with
optional post-activation batch-norm and dropout whose config value is the
TF1 *keep* probability (ps:240-246).

TPU mapping: the two gathers stay f32 (HBM-bound, precision-sensitive sums);
the MLP runs in ``cfg.compute_dtype`` (bf16 by default) so its matmuls hit
the MXU; XLA fuses the FM reductions into a single VPU pass.  Parameters are
kept f32 throughout for optimizer precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.config import ModelConfig
from ..ops.batch_norm import batch_norm, bn_init
from ..ops.embedding import dense_lookup, narrow_ids
from ..ops.fm import fm_first_order, fm_second_order
from ..ops.initializers import glorot_normal, glorot_uniform
from .click_through import register_click_through


def init_mlp(key: jax.Array, in_dim: int, cfg: ModelConfig) -> dict:
    """MLP tower params: hidden layers + linear head (ps:230-255)."""
    params: dict = {}
    dims = [in_dim, *cfg.deep_layers]
    keys = jax.random.split(key, len(cfg.deep_layers) + 1)
    for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
        params[f"layer_{i}"] = {
            "kernel": glorot_uniform(keys[i], (d_in, d_out)),
            "bias": jnp.zeros((d_out,), jnp.float32),
        }
    params["out"] = {
        "kernel": glorot_uniform(keys[-1], (dims[-1], 1)),
        "bias": jnp.zeros((1,), jnp.float32),
    }
    return params


@jax.named_scope("mlp")
def apply_mlp(
    params: dict,
    bn_params: dict | None,
    bn_state: dict | None,
    x: jnp.ndarray,
    *,
    cfg: ModelConfig,
    train: bool,
    rng: jax.Array | None,
) -> tuple[jnp.ndarray, dict]:
    """Shared deep tower: relu FCs (+BN, +dropout at train), linear head.

    Returns ([B] logits contribution, new bn_state).
    """
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    h = x.astype(compute_dtype)
    new_bn_state: dict = {}
    n_layers = len(cfg.deep_layers)
    needs_dropout = train and any(k < 1.0 for k in cfg.dropout_keep[:n_layers])
    if needs_dropout:
        if rng is None:
            raise ValueError(
                "train=True with dropout_keep < 1.0 requires an rng key"
            )
        drop_keys = jax.random.split(rng, n_layers)
    for i in range(n_layers):
        layer = params[f"layer_{i}"]
        h = h @ layer["kernel"].astype(compute_dtype) + layer["bias"].astype(compute_dtype)
        h = jax.nn.relu(h)
        if cfg.batch_norm:
            hf, new_bn_state[f"layer_{i}"] = batch_norm(
                h.astype(jnp.float32),
                bn_params[f"layer_{i}"],
                bn_state[f"layer_{i}"],
                train=train,
                decay=cfg.batch_norm_decay,
            )
            h = hf.astype(compute_dtype)
        if needs_dropout and cfg.dropout_keep[i] < 1.0:
            keep = cfg.dropout_keep[i]
            mask = jax.random.bernoulli(drop_keys[i], keep, h.shape)
            h = jnp.where(mask, h / keep, 0.0).astype(compute_dtype)
    out = params["out"]
    y = h @ out["kernel"].astype(compute_dtype) + out["bias"].astype(compute_dtype)
    return y[:, 0].astype(jnp.float32), new_bn_state


def init_deepfm(key: jax.Array, cfg: ModelConfig) -> tuple[dict, dict]:
    k_w, k_v, k_mlp = jax.random.split(key, 3)
    params = {
        "fm_b": jnp.zeros((1,), jnp.float32),                      # ps:186-188
        "fm_w": glorot_normal(k_w, (cfg.feature_size,)),           # ps:189-191
        "fm_v": glorot_normal(k_v, (cfg.feature_size,                # ps:192-198
                                    cfg.embedding_size)),
        "mlp": init_mlp(k_mlp, cfg.field_size * cfg.embedding_size, cfg),
    }
    state: dict = {}
    if cfg.batch_norm:
        params["bn"] = {}
        state["bn"] = {}
        for i, width in enumerate(cfg.deep_layers):
            params["bn"][f"layer_{i}"], state["bn"][f"layer_{i}"] = bn_init(width)
    return params, state


def apply_deepfm(
    params: dict,
    model_state: dict,
    feat_ids: jnp.ndarray,
    feat_vals: jnp.ndarray,
    *,
    cfg: ModelConfig,
    train: bool = False,
    rng: jax.Array | None = None,
    lookup_fn=dense_lookup,
) -> tuple[jnp.ndarray, dict]:
    """Forward pass: [B, F] int ids + [B, F] f32 vals -> [B] logits."""
    feat_ids = narrow_ids(feat_ids.reshape(-1, cfg.field_size),
                          cfg.feature_size)
    feat_vals = feat_vals.reshape(-1, cfg.field_size).astype(jnp.float32)

    # one lookup for the two tables the ids index: [B, F], [B, F, K]
    with jax.named_scope("lookup"):
        feat_w, rows_v = lookup_fn((params["fm_w"], params["fm_v"]), feat_ids)
        emb = rows_v * feat_vals[..., None]         # e = V[ids] * vals
    with jax.named_scope("fm"):
        y_w = fm_first_order(feat_w, feat_vals)     # ps:206-209
        y_v = fm_second_order(emb)                  # ps:211-217

    # deep tower (ps:228-255)
    deep_in = emb.reshape(emb.shape[0], cfg.field_size * cfg.embedding_size)
    y_d, new_bn = apply_mlp(
        params["mlp"],
        params.get("bn"),
        model_state.get("bn"),
        deep_in,
        cfg=cfg,
        train=train,
        rng=rng,
    )

    logits = params["fm_b"][0] + y_w + y_v + y_d            # ps:257-259
    new_state = dict(model_state)
    if cfg.batch_norm and train:
        new_state["bn"] = new_bn
    return logits, new_state


register_click_through(
    "deepfm", init_deepfm, apply_deepfm,
    tables={"fm_w": "feature_size", "fm_v": "feature_size"},
)
