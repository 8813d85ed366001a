"""xDeepFM: linear + Compressed Interaction Network (CIN) + deep MLP.

Swap-in model family for the DeepFM slot (BASELINE.json config "xDeepFM /
DCN-v2 swap-in ... exercises cross-network kernels").  The reference repo
trains only DeepFM (model_fn, 1-ps-cpu/DeepFM-...py:172-313); xDeepFM keeps
that scaffold — same feature schema [B, F] ids/vals, same first-order term
(ps:207-209), same deep tower (ps:230-255), same sparse-table L2 (ps:275-279)
— and replaces the FM second-order identity with a CIN (Lian et al., KDD'18).

CIN layer k (hidden sizes ``cfg.cin_layers``):

    Z^k   = outer(X^{k-1}, X^0) along fields       [B, H_{k-1}, F, K]
    X^k_h = Σ_{i,j} W^k_{h,i,j} · Z^k_{i,j}        [B, H_k, K]
    p^k   = Σ_K X^k                                 [B, H_k]
    y_cin = w_out · concat_k(p^k)

TPU mapping: each CIN layer is two einsums — a batched outer product and a
contraction against W^k — which XLA fuses into one MXU matmul of shape
[B·K, H·F] × [H·F, H']; everything runs in ``cfg.compute_dtype`` (bf16) like
the MLP tower.  No scalar loops, no dynamic shapes: the layer stack is
unrolled at trace time from the static config tuple.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.config import ModelConfig
from ..ops.batch_norm import bn_init
from ..ops.embedding import dense_lookup, narrow_ids
from ..ops.fm import fm_first_order
from ..ops.initializers import glorot_normal, glorot_uniform
from .click_through import register_click_through
from .deepfm import apply_mlp, init_mlp


def init_cin(key: jax.Array, cfg: ModelConfig) -> dict:
    """CIN filter stack + output head.  W^k has shape [H_{k-1}, F, H_k]."""
    params: dict = {}
    f = cfg.field_size
    sizes = [f, *cfg.cin_layers]
    keys = jax.random.split(key, len(cfg.cin_layers) + 1)
    for k, (h_prev, h_out) in enumerate(zip(sizes[:-1], sizes[1:])):
        params[f"filter_{k}"] = glorot_uniform(
            keys[k], (h_prev * f, h_out)
        ).reshape(h_prev, f, h_out)
    total_pooled = sum(cfg.cin_layers)
    params["out"] = {
        "kernel": glorot_uniform(keys[-1], (total_pooled, 1)),
        "bias": jnp.zeros((1,), jnp.float32),
    }
    return params


@jax.named_scope("cin")
def apply_cin(params: dict, emb: jnp.ndarray, *, cfg: ModelConfig) -> jnp.ndarray:
    """emb [B, F, K] -> y_cin [B] via the compressed interaction stack."""
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    x0 = emb.astype(compute_dtype)                       # [B, F, K]
    xk = x0
    pooled = []
    for k in range(len(cfg.cin_layers)):
        w = params[f"filter_{k}"].astype(compute_dtype)  # [H_prev, F, H_out]
        # outer product along fields then contract with the filter:
        # one fused MXU contraction over (h: H_prev, f: F)
        z = jnp.einsum("bhk,bfk->bhfk", xk, x0)
        xk = jnp.einsum("bhfk,hfo->bok", z, w)           # [B, H_out, K]
        pooled.append(jnp.sum(xk, axis=2))               # sum-pool over K
    p = jnp.concatenate(pooled, axis=1)                  # [B, ΣH]
    out = params["out"]
    y = p @ out["kernel"].astype(compute_dtype) + out["bias"].astype(compute_dtype)
    return y[:, 0].astype(jnp.float32)


def apply_cin_reference(params: dict, emb: jnp.ndarray, *, cfg: ModelConfig) -> jnp.ndarray:
    """O(F²) loop oracle for ``apply_cin`` — test use only (f32 throughout)."""
    x0 = emb.astype(jnp.float32)
    xk = x0
    pooled = []
    for k in range(len(cfg.cin_layers)):
        w = params[f"filter_{k}"].astype(jnp.float32)
        h_prev, f, h_out = w.shape
        outs = []
        for h in range(h_out):
            acc = jnp.zeros(emb.shape[::2])              # [B, K]
            for i in range(h_prev):
                for j in range(f):
                    acc = acc + w[i, j, h] * xk[:, i, :] * x0[:, j, :]
            outs.append(acc)
        xk = jnp.stack(outs, axis=1)
        pooled.append(jnp.sum(xk, axis=2))
    p = jnp.concatenate(pooled, axis=1)
    out = params["out"]
    return (p @ out["kernel"] + out["bias"])[:, 0]


def init_xdeepfm(key: jax.Array, cfg: ModelConfig) -> tuple[dict, dict]:
    k_w, k_v, k_cin, k_mlp = jax.random.split(key, 4)
    params = {
        "fm_b": jnp.zeros((1,), jnp.float32),
        "fm_w": glorot_normal(k_w, (cfg.feature_size,)),
        "fm_v": glorot_normal(k_v, (cfg.feature_size, cfg.embedding_size)),
        "cin": init_cin(k_cin, cfg),
        "mlp": init_mlp(k_mlp, cfg.field_size * cfg.embedding_size, cfg),
    }
    state: dict = {}
    if cfg.batch_norm:
        params["bn"] = {}
        state["bn"] = {}
        for i, width in enumerate(cfg.deep_layers):
            params["bn"][f"layer_{i}"], state["bn"][f"layer_{i}"] = bn_init(width)
    return params, state


def apply_xdeepfm(
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

    # one lookup for the two tables the ids index: [B, F], [B, F, K]
    with jax.named_scope("lookup"):
        feat_w, rows_v = lookup_fn((params["fm_w"], params["fm_v"]), feat_ids)
        emb = rows_v * feat_vals[..., None]
    with jax.named_scope("fm"):
        y_w = fm_first_order(feat_w, feat_vals)

    y_cin = apply_cin(params["cin"], emb, cfg=cfg)

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

    logits = params["fm_b"][0] + y_w + y_cin + y_d
    new_state = dict(model_state)
    if cfg.batch_norm and train:
        new_state["bn"] = new_bn
    return logits, new_state


register_click_through(
    "xdeepfm", init_xdeepfm, apply_xdeepfm,
    tables={"fm_w": "feature_size", "fm_v": "feature_size"},
)
