"""Plain reference of one DeepFM training step (Guo et al., IJCAI 2017, as the
aws-samples script builds it): y = b + Σ_f w_f·x_f + ½Σ_k((Σ_f e)² − Σ_f e²)
+ MLP(flatten(e)), e_fk = V[id_f]_k·x_f; loss = mean sigmoid CE +
l2_reg·½(‖W‖² + ‖V‖²); dense Adam over every parameter.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import _common as c


def init(key, s: c.Sizes) -> dict:
    k_w, k_v, k_mlp = jax.random.split(key, 3)
    return {
        "fm_b": jnp.zeros((1,), jnp.float32),
        "fm_w": c.glorot_normal(k_w, (s.feature_size,)),
        "fm_v": c.glorot_normal(k_v, (s.feature_size, s.embedding_size)),
        "mlp": c.init_mlp(k_mlp, s.field_size * s.embedding_size, s),
    }


def loss(params: dict, batch: dict, rng, s: c.Sizes, policy: c.Policy):
    y_w, emb = c.lookup_terms(params, batch, s, policy)
    sum_f = jnp.sum(emb, axis=1)
    y_v = 0.5 * jnp.sum(jnp.square(sum_f) - jnp.sum(jnp.square(emb), axis=1),
                        axis=1)
    y_d = c.mlp(params["mlp"], emb.reshape(emb.shape[0], -1), s, rng, policy)
    logits = (params["fm_b"][0] + y_w.astype(jnp.float32)
              + y_v.astype(jnp.float32) + y_d)
    return c.bce_with_l2(logits, params, batch, s, policy)


def follow(config: dict, seed: int, batches: list,
           policy: c.Policy = c.Policy()) -> dict:
    return c.follow_steps(init, loss, c.sizes_from_config(config), seed,
                          batches, policy)
