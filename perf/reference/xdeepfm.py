"""Plain reference of one xDeepFM training step (Lian et al., KDD 2018,
arXiv:1803.05170): linear term + Compressed Interaction Network + MLP.

CIN layer k over the scaled embeddings X⁰ [B, F, K]:
    Z^k   = X^{k-1} ⊗ X⁰ along fields          [B, H_{k-1}, F, K]
    X^k_h = Σ_{i,j} W^k_{i,j,h} · Z^k_{i,j}    [B, H_k, K]
    p^k   = Σ_K X^k;   y_cin = w_out · concat_k p^k + b_out
(the "direct" CIN without the paper's optional split-half; every layer's
feature maps are pooled).  Loss and optimiser as in the DeepFM reference.
Each layer is one ``einsum``, computed in blocks of rows and recomputed in
the backward pass, so that the [rows, H, F, K] outer product of one block is
all that is ever held beside the tables.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import _common as c

_BLOCK_ROWS = 512


def _cin_layer(xk, x0, w):
    """Σ_{h,f} W[h,f,o]·X^{k-1}[b,h,k]·X⁰[b,f,k] -> [B, O, K], by blocks."""
    b = xk.shape[0]
    nb = b // _BLOCK_ROWS if b % _BLOCK_ROWS == 0 else 1
    one = jax.checkpoint(
        lambda ab: jnp.einsum("bhk,bfk,hfo->bok", ab[0], ab[1], w))
    out = jax.lax.map(one, (xk.reshape(nb, b // nb, *xk.shape[1:]),
                            x0.reshape(nb, b // nb, *x0.shape[1:])))
    return out.reshape(b, *out.shape[2:])


def init(key, s: c.Sizes) -> dict:
    k_w, k_v, k_cin, k_mlp = jax.random.split(key, 4)
    f = s.field_size
    sizes = [f, *s.cin_layers]
    keys = jax.random.split(k_cin, len(s.cin_layers) + 1)
    cin = {}
    for k, (h_prev, h_out) in enumerate(zip(sizes[:-1], sizes[1:])):
        cin[f"filter_{k}"] = c.glorot_uniform(
            keys[k], (h_prev * f, h_out)).reshape(h_prev, f, h_out)
    cin["out"] = {"kernel": c.glorot_uniform(keys[-1], (sum(s.cin_layers), 1)),
                  "bias": jnp.zeros((1,), jnp.float32)}
    return {
        "fm_b": jnp.zeros((1,), jnp.float32),
        "fm_w": c.glorot_normal(k_w, (s.feature_size,)),
        "fm_v": c.glorot_normal(k_v, (s.feature_size, s.embedding_size)),
        "cin": cin,
        "mlp": c.init_mlp(k_mlp, f * s.embedding_size, s),
    }


def cin(p: dict, emb, s: c.Sizes, policy: c.Policy):
    dt = c.tower_dtype(policy)
    q = c.fp8_round if policy.mlp_fp8 else (lambda a: a)
    x0 = emb.astype(dt)
    xk, pooled = x0, []
    for k in range(len(s.cin_layers)):
        w = p[f"filter_{k}"].astype(dt)
        xk = _cin_layer(q(xk), q(x0), q(w))
        pooled.append(jnp.sum(xk, axis=2))
    out = p["out"]
    y = (q(jnp.concatenate(pooled, axis=1)) @ q(out["kernel"].astype(dt))
         + out["bias"].astype(dt))
    return y[:, 0].astype(jnp.float32)


def loss(params: dict, batch: dict, rng, s: c.Sizes, policy: c.Policy):
    y_w, emb = c.lookup_terms(params, batch, s, policy)
    y_cin = cin(params["cin"], emb, s, policy)
    y_d = c.mlp(params["mlp"], emb.reshape(emb.shape[0], -1), s, rng, policy)
    logits = params["fm_b"][0] + y_w.astype(jnp.float32) + y_cin + y_d
    return c.bce_with_l2(logits, params, batch, s, policy)


def follow(config: dict, seed: int, batches: list,
           policy: c.Policy = c.Policy()) -> dict:
    return c.follow_steps(init, loss, c.sizes_from_config(config), seed,
                          batches, policy)
