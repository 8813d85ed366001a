"""DCN-v2: deep & cross network (Wang et al., WWW'21), parallel structure.

Swap-in model family for the DeepFM slot (BASELINE.json config "xDeepFM /
DCN-v2 swap-in").  Keeps the reference scaffold — [B, F] ids/vals schema,
shared scaled-embedding input (ps:212-214), deep tower (ps:230-255), sparse
L2 (ps:275-279) — and replaces the FM second-order term with a stack of
full-rank cross layers over the flattened embedding vector x0 [B, D], D=F·K:

    x_{l+1} = x0 ⊙ (W_l x_l + b_l) + x_l        l = 0..cfg.cross_layers-1
    y_cross = w_out · x_L

Combination is logit-additive (parallel deep & cross), matching the DeepFM
head style: y = b + y_cross + y_deep.

TPU mapping: each cross layer is one [B, D] × [D, D] MXU matmul plus fused
elementwise ops; the stack unrolls at trace time (static ``cross_layers``).
Matmuls run in ``cfg.compute_dtype`` (bf16), params stay f32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.config import ModelConfig
from ..ops.batch_norm import bn_init
from ..ops.embedding import dense_lookup, narrow_ids, scaled_embedding
from ..ops.initializers import glorot_normal, glorot_uniform
from .click_through import register_click_through
from .deepfm import apply_mlp, init_mlp


def init_cross(key: jax.Array, dim: int, num_layers: int) -> dict:
    params: dict = {}
    keys = jax.random.split(key, num_layers + 1)
    for l in range(num_layers):
        params[f"layer_{l}"] = {
            "kernel": glorot_uniform(keys[l], (dim, dim)),
            "bias": jnp.zeros((dim,), jnp.float32),
        }
    params["out"] = {
        "kernel": glorot_uniform(keys[-1], (dim, 1)),
        "bias": jnp.zeros((1,), jnp.float32),
    }
    return params


@jax.named_scope("cross")
def apply_cross(params: dict, x0: jnp.ndarray, *, cfg: ModelConfig) -> jnp.ndarray:
    """x0 [B, D] -> y_cross [B]."""
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    x0c = x0.astype(compute_dtype)
    x = x0c
    for l in range(cfg.cross_layers):
        layer = params[f"layer_{l}"]
        wx = x @ layer["kernel"].astype(compute_dtype) + layer["bias"].astype(
            compute_dtype
        )
        x = x0c * wx + x
    out = params["out"]
    y = x @ out["kernel"].astype(compute_dtype) + out["bias"].astype(compute_dtype)
    return y[:, 0].astype(jnp.float32)


def init_dcnv2(key: jax.Array, cfg: ModelConfig) -> tuple[dict, dict]:
    k_v, k_cross, k_mlp = jax.random.split(key, 3)
    dim = cfg.field_size * cfg.embedding_size
    params = {
        "fm_b": jnp.zeros((1,), jnp.float32),
        "fm_v": glorot_normal(k_v, (cfg.feature_size, cfg.embedding_size)),
        "cross": init_cross(k_cross, dim, cfg.cross_layers),
        "mlp": init_mlp(k_mlp, dim, cfg),
    }
    state: dict = {}
    if cfg.batch_norm:
        params["bn"] = {}
        state["bn"] = {}
        for i, width in enumerate(cfg.deep_layers):
            params["bn"][f"layer_{i}"], state["bn"][f"layer_{i}"] = bn_init(width)
    return params, state


def apply_dcnv2(
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
    feat_ids = narrow_ids(feat_ids.reshape(-1, cfg.field_size),
                          cfg.feature_size)
    feat_vals = feat_vals.reshape(-1, cfg.field_size).astype(jnp.float32)

    with jax.named_scope("lookup"):
        if lookup_fn is dense_lookup:
            emb = scaled_embedding(params["fm_v"], feat_ids, feat_vals)
        else:
            emb = lookup_fn(params["fm_v"], feat_ids) * feat_vals[..., None]

    x0 = emb.reshape(emb.shape[0], cfg.field_size * cfg.embedding_size)
    y_cross = apply_cross(params["cross"], x0, cfg=cfg)
    y_d, new_bn = apply_mlp(
        params["mlp"],
        params.get("bn"),
        model_state.get("bn"),
        x0,
        cfg=cfg,
        train=train,
        rng=rng,
    )

    logits = params["fm_b"][0] + y_cross + y_d
    new_state = dict(model_state)
    if cfg.batch_norm and train:
        new_state["bn"] = new_bn
    return logits, new_state


register_click_through(
    "dcnv2", init_dcnv2, apply_dcnv2,
    tables={"fm_v": "feature_size"},
)
