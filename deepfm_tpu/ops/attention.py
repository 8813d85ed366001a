"""Causal grouped-query attention over long sequences.

On a TPU the work is JAX's own Pallas kernel (``splash_attention``: a flash
attention whose grid follows the causal mask's live blocks, key-value heads
shared by their query heads inside the kernel, a fused backward).  Its
instructions carry the kernel's name in the compiled step
(``splash_mha_fwd_residuals``, ``splash_mha_dkv_no_residuals``: what the
benchmark's ``splash_attention_roofline`` reads), and its operands (q with
the scale on it, k and v, heads first, as it reads them), its output and its
log-sum-exp carry ``ATTENTION_RESIDUALS`` for a ``jax.checkpoint`` policy:
a rematerialised block that keeps them (``models/lfm2_moe.KEEP``) does not
run the forward twice, nor the norms, RoPE and layout copies that make its
operands (6.1 ms a layer at the cell's size, heads of 64 being half a lane
tile: PERF.md §6, PR 39).

Elsewhere, and for a sequence none of the kernel's tiles divides, XLA's own
ops (``kernel_tile`` decides, from the devices the step is traced for): a
``[heads, S, S]`` score tensor is never formed (4.3 GB a sequence in bfloat16
at 32 heads of 8,192 positions); the queries go block by block, each
block against the keys up to its own end (a static slice, so a block pays
for the triangle it needs and half a block of its diagonal, (n+1)/2n of the
square over n blocks), each block a ``jax.checkpoint`` of its own, sequences
one by one (``lax.map``): the peak is one sequence's one block; q, k, v and
the output carry the same name, so a caller that keeps them runs these
blocks' forward once, and each block's own checkpoint forms its scores again
inside its backward.  On the chip that path reads 1.15 s a step where the
kernel and what surrounds it read 0.06 (PERF.md §6, PR 36): its fusions of a
64-deep contraction run at 0.3 TFLOP/s.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax import lax

from .kept import count, keep

# query rows a block: [heads, 512, S] float32 scores are 0.5 GB at 32 heads
# of 8,192 keys, and 16 blocks unroll into the step
QUERY_BLOCK = 512
# the kernel's query and key-value tile, both ways of differentiation (the
# largest that divides the sequence), and the key-value rows it multiplies
# at a time inside one: of the tiles tried on the chip at the cell's size
# (PERF.md §6, PR 36) 1024 / 512 is the fastest that needs no more of a
# sequence than a multiple of 1,024; 512 and 256 read 1.25× and 1.7× its time
KERNEL_TILES = (1024, 512, 256, 128)
KERNEL_COMPUTE_BLOCK = 512
# checkpoint name of what the kernel's backward needs of its forward
ATTENTION_RESIDUALS = "attention_residuals"


def kernel_tile(positions: int) -> int | None:
    """The kernel's tile for a sequence of ``positions``, or None for XLA's
    ops — observed, not set: the kernel where the trace runs under a mesh of
    TPU devices (the ``shard_map`` of the step builders; a rehearsal compile
    for a described chip sees that chip's, whatever backend the process
    has) and one of its tiles divides the sequence, the largest that does.
    Said once a trace: ``attention: Pallas kernel, tile=… | XLA's blocked
    ops (…), positions=…``."""
    device = jax.sharding.get_abstract_mesh().abstract_device
    kind = device.device_kind if device is not None else "no mesh"
    tile = next((t for t in KERNEL_TILES if positions % t == 0), None)
    if not kind.startswith("TPU"):
        tile, how = None, f"XLA's blocked ops (devices: {kind})"
    elif tile is None:
        how = f"XLA's blocked ops (no tile of {KERNEL_TILES} divides it)"
    else:
        how = f"Pallas kernel, tile={tile}"
    logging.getLogger(__name__).info(
        "attention: %s, positions=%d", how, positions)
    return tile


def rope_tables(positions: int, head_dim: int, theta: float):
    """``(cos, sin)`` [positions, head_dim] of rotary embeddings in the halves
    convention: frequency i = theta^(−2i/d) turns the pair (x_i, x_{i+d/2})."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    angle = jnp.arange(positions, dtype=jnp.float32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)
    return jnp.cos(angle), jnp.sin(angle)


def apply_rope(x, cos, sin):
    """x [B, S, heads, d] rotated by position (the whole head)."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[None, :, None, :] + turned * sin[None, :, None, :]


@functools.partial(jax.checkpoint, static_argnums=(3,))
def _block(q, k, v, start: int):
    """One sequence's query rows [start, start+bq) against the keys [0, len):
    q [bq, G, R, d], k and v [len, G, d] -> [bq, G, R, d]; softmax in
    float32."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("qgrd,kgd->grqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    q_at = start + jnp.arange(q.shape[0])
    seen = jnp.arange(k.shape[0])[None, :] <= q_at[:, None]
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("grqk,kgd->qgrd", p.astype(v.dtype), v)


def _kernel_attention(q, k, v, *, block: int, interpret: bool):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel,
        splash_attention_mask as masks,
    )

    s, hq, d = q.shape[1:]
    inner = min(block, KERNEL_COMPUTE_BLOCK)
    sizes = kernel.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=inner,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=inner,
        use_fused_bwd_kernel=True)
    attend = kernel.make_splash_mha_single_device(
        masks.MultiHeadMask([masks.CausalMask((s, s))] * hq),
        block_sizes=sizes, residual_checkpoint_name=ATTENTION_RESIDUALS,
        interpret=interpret)
    heads_first = lambda x: jnp.swapaxes(x, 1, 2)
    # the kernel takes the scale on the queries; its operands carry the name
    # as it reads them, heads first: its backward reads them again
    q, k, v = (keep(heads_first(x), ATTENTION_RESIDUALS)
               for x in (q * jnp.asarray(d ** -0.5, q.dtype), k, v))
    out = jax.vmap(attend)(q, k, v)
    # the kernel names its own residuals: the output and a float32
    # log-sum-exp a query row
    count(ATTENTION_RESIDUALS, out.shape, out.dtype)
    count(ATTENTION_RESIDUALS, out.shape[:-1], jnp.float32)
    return heads_first(out)


def causal_attention(q, k, v, *, kernel: bool = False,
                     block: int | None = None, interpret: bool = False):
    """softmax(q·kᵀ/√d, causal)·v: q [B, S, Hq, d], k and v [B, S, Hkv, d]
    with Hq a multiple of Hkv (each key-value head serves Hq/Hkv query
    heads, consecutive ones) -> [B, S, Hq, d] in v's dtype.  ``kernel``: the
    Pallas kernel (``interpret`` for a CPU test of it), else XLA's ops."""
    if kernel:
        return _kernel_attention(q, k, v, block=block or KERNEL_TILES[0],
                                 interpret=interpret)
    block = block or QUERY_BLOCK
    q, k, v = (keep(x, ATTENTION_RESIDUALS) for x in (q, k, v))
    b, s, hq, d = q.shape
    g = k.shape[2]
    if s % block:
        block = s
    q = q.reshape(b, s, g, hq // g, d)

    def one(qkv):
        q1, k1, v1 = qkv
        return jnp.concatenate([
            _block(q1[at:at + block], k1[:at + block], v1[:at + block], at)
            for at in range(0, s, block)], axis=0)

    return keep(lax.map(one, (q, k, v)).reshape(b, s, hq, d),
                ATTENTION_RESIDUALS)
