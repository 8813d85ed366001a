"""The two-tower family's inference-path encoder pair.

The tower forward exists ONCE (``models/two_tower.encode_tower``): these
apply-only entry points (no loss, no optimizer, params as arguments) are
shared by the funnel index builder (funnel/index.build_index), the funnel's
sharded retrieval executable (funnel/index.build_retrieve_with encodes
queries through the same encode_tower), and the training parity tests — so
serving, indexing, and training can never drift onto different tower math.
Training itself is the shared SPMD step (``parallel/spmd.py``) on the loss
the family declares.
"""

from __future__ import annotations

from functools import partial

import jax

from ..models.two_tower import encode_tower


@partial(jax.jit, static_argnames=("cfg",))
def encode_queries(params, user_ids, user_vals, *, cfg) -> jax.Array:
    """Encode query users: ``(params, [B, Fu] ids, [B, Fu] vals) ->
    [B, D]`` L2-normalized embeddings (``cfg`` is a ModelConfig)."""
    return encode_tower(params, user_ids, user_vals, cfg=cfg, side="user")


@partial(jax.jit, static_argnames=("cfg",))
def encode_items(params, item_ids, item_vals, *, cfg) -> jax.Array:
    """Encode corpus items: ``(params, [B, Fi] ids, [B, Fi] vals) ->
    [B, D]`` L2-normalized embeddings (``cfg`` is a ModelConfig)."""
    return encode_tower(params, item_ids, item_vals, cfg=cfg, side="item")
