"""Attention over long sequences: causal grouped-query attention, and EVA
(Zheng et al., ICLR 2023: exact softmax terms for a query's own window, every
earlier window through one key and one value a chunk, in the same softmax).

On a TPU the work is JAX's own Pallas kernel (``splash_attention``: a flash
attention whose grid follows the mask's live blocks, key-value heads shared
by their query heads inside the kernel, a fused backward).  Its instructions
carry the kernel's name in the compiled step (``splash_mha_fwd_residuals``,
``splash_mha_dkv_no_residuals``: what the benchmark's
``splash_attention_roofline`` and ``eva_attention_roofline`` read), and its
operands (q with the scale on it, k and v, heads first, as it reads them),
its output and its log-sum-exp carry ``ATTENTION_RESIDUALS`` for a
``jax.checkpoint`` policy: a rematerialised block that keeps them
(``ops/kept.py``) does not run the forward twice, nor the norms, RoPE and
layout copies that make its operands (6.1 ms a layer at the token cell's
size, heads of 64 being half a lane tile: PERF.md §6, PR 39).

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

EVA (``eva_attention``) is the second shape of problem through the same
seam: keys that outnumber the queries — the ``S`` tokens and the ``S/chunk``
chunk summaries ``eva_pool`` makes of them, ``[k ; k̃]`` — under a mask that
is no triangle (``eva_live``: the query's own window, causal inside it;
every chunk of every earlier window).  The kernel takes the mask as an
object that forms a block when its set-up asks for one and computes partial
blocks inside the kernel (``_eva_mask``), so dead blocks are skipped and the
backward, through the summaries to ``k``, ``v``, ``φ`` and ``μ``, is the
kernel's and plain ``jnp``'s own.  XLA's path goes window by window: a
``[heads, window, window + S/chunk]`` score block, never ``[heads, S, S +
S/chunk]``.

A selection of keys that the step computes (``selected_attention``: each
query attends the keys a learned indexer chose for it, ``ops/indexer.py``) is
the third: a mask that is an array, not an object.  The kernel takes one
sequence's ``[1, S, S]`` booleans for all of its heads (its set-up becomes
part of the step: which blocks are dead is counted on the device, and every
live block carries its tile of the mask, which Mosaic reads as int32: 4 MB a
1,024² tile for each head that passes it), sequences one by one.  The
selection arrives as bits (``pack_selection``) and is unpacked where it is
used, both ways of differentiation.  XLA's path is the causal one's, block by
block, with the block's rows of the selection in place of the triangle.  The
indexer's target, the heads' mean probability on the selected keys
(``selected_probabilities``), is a kernel of this module's own where the
attention's runs (``target_tiles``): two sweeps over the key tiles of a query
tile, every head's score tile formed in VMEM both times, the ``live`` tile read
once for all the heads as int8, and only the ``[chunk, ≤S]`` mean written.
XLA's ops for it write the ``[heads, chunk, ≤S]`` float32 score block (1.07 GB
a chunk at 32 heads of 16,384 keys) and pass over it three times more: the
fallback, and the kernel's plain reference in the tests.  The indexer's own
gradient has a kernel beside its equations (``ops/indexer.index_scores_pull``,
chosen by the same observation and this module's key tiles).
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .kept import ATTENTION_RESIDUALS, count, keep

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
# what the index target's kernel may hold of a v5e core's 128 MiB: a
# [R·rows, keys] float32 score tile is 8 MB and its passes hold a few of them
TARGET_VMEM_BYTES = 64 * 2**20


def kernel_tile(positions: int, keys: int | None = None) -> int | None:
    """The kernel's tile for a sequence of ``positions`` (and, where they are
    not as many, ``keys``), or None for XLA's ops — observed, not set: the
    kernel where the trace runs under a mesh of TPU devices (the
    ``shard_map`` of the step builders; a rehearsal compile for a described
    chip sees that chip's, whatever backend the process has) and one of its
    tiles divides the sequence, the largest that does.  Said once a trace:
    ``attention: Pallas kernel, tile=… | XLA's blocked ops (…),
    positions=…``."""
    device = jax.sharding.get_abstract_mesh().abstract_device
    kind = device.device_kind if device is not None else "no mesh"
    lengths = (positions,) if keys is None else (positions, keys)
    tile = next((t for t in KERNEL_TILES
                 if all(n % t == 0 for n in lengths)), None)
    if not kind.startswith("TPU"):
        tile, how = None, f"XLA's blocked ops (devices: {kind})"
    elif tile is None:
        how = f"XLA's blocked ops (no tile of {KERNEL_TILES} divides it)"
    else:
        how = f"Pallas kernel, tile={tile}"
    logging.getLogger(__name__).info(
        "attention: %s, positions=%d%s", how, positions,
        "" if keys is None else f", keys={keys}")
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
def _block(q, k, v, start: int, live=None):
    """One sequence's query rows [start, start+bq) against the keys [0, len):
    q [bq, G, R, d], k and v [len, G, d] -> [bq, G, R, d]; softmax in
    float32.  ``live`` [bq, len]: the keys each row attends, where they are
    not all those up to itself."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("qgrd,kgd->grqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if live is None:
        q_at = start + jnp.arange(q.shape[0])
        live = jnp.arange(k.shape[0])[None, :] <= q_at[:, None]
    p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
    return jnp.einsum("grqk,kgd->qgrd", p.astype(v.dtype), v)


def _splash(mask, q, k, v, *, block: int, interpret: bool):
    """The kernel under one ``mask`` for every head: q [B, H, S, d] with the
    scale on it, k and v [B, Hkv, keys, d] -> [B, H, S, d].  ``mask`` is an
    object known when the step is traced, the same for every sequence, or
    the step's own bits [B, S, keys/8] (``pack_selection``), a sequence's
    for its heads: the kernel's set-up is then part of the step, and the
    sequences go one by one."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel,
        splash_attention_mask as masks,
    )

    inner = min(block, KERNEL_COMPUTE_BLOCK)
    sizes = kernel.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=inner,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=inner,
        use_fused_bwd_kernel=True)
    make = functools.partial(
        kernel.make_splash_mha_single_device, block_sizes=sizes,
        residual_checkpoint_name=ATTENTION_RESIDUALS, interpret=interpret)
    if isinstance(mask, jax.Array):
        out = lax.map(
            lambda a: make(unpack_selection(a[0])[None])(*a[1:]),
            (mask, q, k, v))
    else:
        out = jax.vmap(make(masks.MultiHeadMask([mask] * q.shape[1])))(q, k, v)
    # the kernel names its own residuals: the output and a float32
    # log-sum-exp a query row
    count(ATTENTION_RESIDUALS, out.shape, out.dtype)
    count(ATTENTION_RESIDUALS, out.shape[:-1], jnp.float32)
    return out


def _heads_first(x):
    return jnp.swapaxes(x, 1, 2)


def _kernel_operands(q, k, v):
    """[B, S, heads, d] -> [B, heads, S, d] as the kernel reads them: the
    scale on the queries, heads first, under the name (its backward reads
    them again, and a block that keeps them forms them once)."""
    scale = jnp.asarray(q.shape[-1] ** -0.5, q.dtype)
    return tuple(keep(_heads_first(x), ATTENTION_RESIDUALS)
                 for x in (q * scale, k, v))


def _kernel_attention(q, k, v, *, block: int, interpret: bool):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as masks,
    )

    s = q.shape[1]
    return _heads_first(_splash(
        masks.CausalMask((s, s)), *_kernel_operands(q, k, v),
        block=block, interpret=interpret))


def _blocked_attention(q, k, v, selection, block: int | None):
    """XLA's ops, query block by query block against the keys up to the
    block's end: q [B, S, Hq, d], k and v [B, S, Hkv, d]; ``selection`` None
    (every key up to the query) or bits [B, S, S/8] (``pack_selection``)."""
    block = block or QUERY_BLOCK
    q, k, v = (keep(x, ATTENTION_RESIDUALS) for x in (q, k, v))
    b, s, hq, d = q.shape
    g = k.shape[2]
    if s % block:
        block = s
    q = q.reshape(b, s, g, hq // g, d)

    def one(args):
        q1, k1, v1, *bits = args
        live = [unpack_selection(x) for x in bits]
        return jnp.concatenate([
            _block(q1[at:at + block], k1[:at + block], v1[:at + block], at,
                   *(x[at:at + block, :at + block] for x in live))
            for at in range(0, s, block)], axis=0)

    operands = (q, k, v) if selection is None else (q, k, v, selection)
    return keep(lax.map(one, operands).reshape(b, s, hq, d),
                ATTENTION_RESIDUALS)


def causal_attention(q, k, v, *, kernel: bool = False,
                     block: int | None = None, interpret: bool = False):
    """softmax(q·kᵀ/√d, causal)·v: q [B, S, Hq, d], k and v [B, S, Hkv, d]
    with Hq a multiple of Hkv (each key-value head serves Hq/Hkv query
    heads, consecutive ones) -> [B, S, Hq, d] in v's dtype.  ``kernel``: the
    Pallas kernel (``interpret`` for a CPU test of it), else XLA's ops."""
    if kernel:
        return _kernel_attention(q, k, v, block=block or KERNEL_TILES[0],
                                 interpret=interpret)
    return _blocked_attention(q, k, v, None, block)


# -- keys selected from data ---------------------------------------------------


def pack_selection(live):
    """bool [..., keys] -> uint8 [..., keys/8]: bit b of byte j is key
    ``b·keys/8 + j``, so that packing and unpacking are shifts of eight
    contiguous slabs and no array is read across its lanes."""
    slabs = jnp.split(live.astype(jnp.uint8), 8, axis=-1)
    return functools.reduce(
        jnp.bitwise_or, (slab << b for b, slab in enumerate(slabs)))


def unpack_selection(bits):
    """uint8 [..., keys/8] -> bool [..., keys], ``pack_selection`` undone."""
    return jnp.concatenate(
        [(bits >> b) & 1 for b in range(8)], axis=-1).astype(bool)


def row_softmax_parts(x):
    """``(m, l)`` with ``softmax(x) = exp(x − m) / l`` along the last axis,
    each a pass of its own over ``x`` (``optimization_barrier``): left to
    fuse the three passes of a softmax over float32 rows of 6,144 to 8,192,
    XLA:TPU takes 96 ms where the passes apart take 3 (PERF.md §6, PR 43:
    rows of 4,096 and of 10,240 are not touched by it).  On a TPU it serves
    the ``[chunk, ≤S]`` softmax of the index scores (``ops/indexer._chunk``),
    whose rows pass through those widths too, so the passes stay apart; the
    ``[heads, chunk, ≤S]`` block of ``selected_probabilities`` reaches it
    only where XLA's ops make the target.  No gradient goes through it."""
    m = lax.optimization_barrier(jnp.max(x, axis=-1, keepdims=True))
    return m, lax.optimization_barrier(
        jnp.sum(jnp.exp(x - m), axis=-1, keepdims=True))


# the index target's kernel: the query rows a tile (of a chunk's QUERY_BLOCK)
# and the keys a tile, the largest that divides the keys in hand.  Of the tiles
# tried on the chip at the cell's size (PERF.md §6, PR 44) 128 × 2,048 is the
# fastest: 256 rows are no faster, and a key tile half as wide pays the
# running maxima's and sums' upkeep (one value a vector register's row)
# twice as often: 1.15× the time at 1,024, ≈ 1.5× at 512
TARGET_ROWS = 128
TARGET_KEY_TILES = (2048, 1024, 512, 256, 128)


def target_tiles(kernel: bool, chunk: int, keys: int):
    """``(rows, keys)`` a tile of ``selected_probabilities``' kernel for
    chunks of ``chunk`` queries against multiples of ``keys`` keys, or None
    for XLA's ops — observed as ``kernel_tile`` observes, whose answer
    ``kernel`` is (the attention runs its kernel): the kernel where the
    attention's runs and a tile divides each.  Said once a trace: ``index
    target: Pallas kernel, rows=…, keys tile=… | XLA's ops (…)``."""
    tiles = (TARGET_ROWS if chunk % TARGET_ROWS == 0 else None,
             next((t for t in TARGET_KEY_TILES if keys % t == 0), None))
    if not kernel:
        tiles, how = None, "XLA's ops (as the attention)"
    elif None in tiles:
        tiles, how = None, (f"XLA's ops (no tile divides chunks of {chunk} "
                            f"or {keys} keys)")
    else:
        how = "Pallas kernel, rows=%d, keys tile=%d" % tiles
    logging.getLogger(__name__).info("index target: %s", how)
    return tiles


def _target_kernel(start_ref, q_ref, k_ref, live_ref, p_ref, m_ref, l_ref):
    """One step of the grid (query tile, sweep, key tile) of
    ``selected_probabilities``: q_ref [G, R·rows, d], k_ref [G, keys, d],
    live_ref [rows, keys] int8 -> p_ref [rows, keys] float32; m_ref and l_ref
    [G, R, rows, 1] hold a query tile's running maxima and sums of
    exponentials over sweep 0 and 1/(l·heads) over sweep 1."""
    from jax.experimental import pallas as pl

    tile, sweep, at = (pl.program_id(axis) for axis in range(3))
    groups, r, rows, _ = m_ref.shape
    keys = p_ref.shape[1]
    # key tiles wholly past the tile's last row are dead for every row
    reached = at * keys <= start_ref[0] + (tile + 1) * rows - 1

    def scores(g, dead):
        s = lax.dot_general(q_ref[g], k_ref[g], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        return s.reshape(r, rows, keys) + dead

    def dead_keys():
        return jnp.where(live_ref[...].astype(jnp.int32) != 0,
                         0.0, -jnp.inf)[None]

    @pl.when((sweep == 0) & (at == 0))
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)

    @pl.when((sweep == 0) & reached)
    def _():
        dead = dead_keys()

        def fold(g, _):
            s = scores(g, dead)
            was = m_ref[g]
            m = jnp.maximum(was, jnp.max(s, axis=-1, keepdims=True))
            # a row with no key selected so far: exp(−inf − 0), not of nan
            shift = jnp.where(m == -jnp.inf, 0.0, m)
            l_ref[g] = l_ref[g] * jnp.exp(was - shift) + jnp.sum(
                jnp.exp(s - shift), axis=-1, keepdims=True)
            m_ref[g] = m

        lax.fori_loop(0, groups, fold, None)

    @pl.when((sweep == 1) & (at == 0))
    def _():
        l_ref[...] = 1.0 / (l_ref[...] * (groups * r))

    @pl.when(sweep == 1)
    def _():
        p_ref[...] = jnp.zeros(p_ref.shape, jnp.float32)

    @pl.when((sweep == 1) & reached)
    def _():
        dead = dead_keys()

        def add(g, _):
            p_ref[...] += jnp.sum(
                jnp.exp(scores(g, dead) - m_ref[g]) * l_ref[g], axis=0)

        lax.fori_loop(0, groups, add, None)


def _target_by_kernel(q, k, live, start, tiles, interpret: bool):
    """``selected_probabilities`` as one Pallas kernel that writes no head's
    score: for each tile of ``rows`` queries, sweep 0 over the key tiles
    folds every head's masked ``[R·rows, d]·[d, keys]`` score tile into a
    running maximum and sum of exponentials a (head, row) — the flash
    recurrence, nothing written —, sweep 1 forms the tile again and writes
    the heads' mean of ``exp(s − m)/l`` once.  The tile of ``live`` is read
    once for all the heads, as int8 (Mosaic has no boolean memref); key tiles
    past the tile's last row (``start`` + its rows) are neither fetched nor
    computed."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g, r, bq, d = q.shape
    n = k.shape[1]
    rows, keys = tiles
    start = jnp.full((1,), n if start is None else start, jnp.int32)

    def last(tile, at, start):      # the last key tile the rows reach
        return jnp.minimum(at, (start[0] + (tile + 1) * rows - 1) // keys)

    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bq // rows, 2, n // keys),
        in_specs=[
            pl.BlockSpec((g, r * rows, d), lambda i, s, j, start: (0, i, 0)),
            pl.BlockSpec((g, keys, d),
                         lambda i, s, j, start: (0, last(i, j, start), 0)),
            pl.BlockSpec((rows, keys),
                         lambda i, s, j, start: (i, last(i, j, start))),
        ],
        # sweep 0 writes nothing: it stays on the block sweep 1 starts with
        out_specs=pl.BlockSpec((rows, keys),
                               lambda i, s, j, start: (i, j * s)),
        scratch_shapes=[pltpu.VMEM((g, r, rows, 1), jnp.float32)] * 2)
    pairs = g * r * bq * n
    return pl.pallas_call(
        _target_kernel, grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((bq, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=TARGET_VMEM_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * 2 * pairs * d, transcendentals=2 * pairs,
            bytes_accessed=(q.size * q.dtype.itemsize
                            + 2 * (bq // rows) * k.size * k.dtype.itemsize
                            + 2 * live.size + 4 * bq * n)),
        name="selected_probabilities", interpret=interpret,
    )(start,
      # a query tile's rows of the R heads of a key-value head side by side
      q.reshape(g, r, bq // rows, rows, d).swapaxes(1, 2).reshape(
          g, r * bq, d),
      k, live.astype(jnp.int8))


def selected_probabilities(q, k, live, *, start=None, tiles=None,
                           interpret: bool = False):
    """The heads' mean probability on the keys a query attends: q [G, R, bq,
    d] with the scale on it and k [G, len, d], heads first as the kernel
    reads them, live [bq, len] -> [bq, len] float32, zero off ``live``.
    Scores from the operands as they come, accumulated in float32; maximum,
    exponential, sum, division and the mean over the heads in float32.

    ``tiles`` (``target_tiles``): one Pallas kernel, two sweeps over the key
    tiles of a query tile, no head's score written (``interpret`` for a CPU
    test of it); ``start``, the chunk's first position where no row attends
    a key past itself, lets it skip the key tiles past a query tile's last
    row.  Else XLA's ops: one product a key-value head, its ``R`` query
    heads' rows side by side (``[R·bq, d]·[d, len]``), the ``[G·R, bq,
    len]`` float32 block written and passed over three times more."""
    if tiles is not None:
        return _target_by_kernel(q, k, live, start, tiles, interpret)
    g, r, bq, d = q.shape
    s = jnp.einsum("gmd,gkd->gmk", q.reshape(g, r * bq, d), k,
                   preferred_element_type=jnp.float32)
    s = jnp.where(live, s.reshape(g, r, bq, -1), -jnp.inf)
    m, l = row_softmax_parts(s)
    return jnp.sum(jnp.exp(s - m) / (l * (g * r)), axis=(0, 1))


def selected_attention(q, k, v, selection, *, kernel: bool = False,
                       block: int | None = None, interpret: bool = False):
    """softmax over the SELECTED keys of q·kᵀ/√d, times v: q [B, S, Hq, d],
    k and v [B, S, Hkv, d] as ``causal_attention`` takes them, ``selection``
    [B, S, S/8] the bits of ``pack_selection`` over a [S, S] mask, one a
    sequence, for all of its heads (every query attends a key at least) ->
    [B, S, Hq, d] in v's dtype.  ``kernel``: the Pallas kernel with the
    selection as its mask (``interpret`` for a CPU test of it), else XLA's
    ops, query block by query block."""
    if kernel:
        return _heads_first(_splash(
            selection, *_kernel_operands(q, k, v),
            block=block or KERNEL_TILES[0], interpret=interpret))
    return _blocked_attention(q, k, v, selection, block)


# -- EVA ----------------------------------------------------------------------


def eva_live(q_ids, kv_ids, *, positions: int, window: int, chunk: int):
    """Whether query ``q_ids`` sees key ``kv_ids`` of ``[tokens ; chunk
    summaries]`` (``positions`` tokens, then ``positions/chunk`` summaries):
    a token of its own window, not ahead of it; the summary of a chunk of an
    earlier window.  Integer arrays that broadcast, numpy's (the kernel's
    set-up, the counts) or jax's (inside the kernel, XLA's blocks)."""
    own = ((kv_ids < positions) & (q_ids // window == kv_ids // window)
           & (q_ids >= kv_ids))
    earlier = ((kv_ids >= positions)
               & ((kv_ids - positions) // (window // chunk) < q_ids // window))
    return own | earlier


def _eva_block(rows, columns, *, positions: int, window: int, chunk: int):
    """``eva_live`` over the 1-D ``rows`` × ``columns`` (numpy), formed only
    in the columns some row can see: a token of one of the rows' windows, a
    summary of a chunk before the last of them (every other column is dead
    for each row, by ``eva_live``'s own two clauses).  Of the cell's 272
    blocks of 1,024² that is 38 formed, not 272."""
    row_windows = rows // window
    seen = np.where(
        columns < positions, np.isin(columns // window, row_windows),
        (columns - positions) // (window // chunk) < row_windows.max())
    block = np.zeros((rows.size, columns.size), bool)
    if seen.any():
        block[:, seen] = eva_live(
            rows[:, None], columns[seen][None, :], positions=positions,
            window=window, chunk=chunk)
    return block


@functools.lru_cache(maxsize=None)
def eva_key_counts(positions: int, window: int, chunk: int) -> tuple:
    """(token keys, summary keys) that the queries of one sequence attend,
    summed over them, counted on ``eva_live`` itself window by window."""
    window = min(window, positions)
    columns = np.arange(positions + positions // chunk)
    tokens = summaries = 0
    for at in range(0, positions, window):
        live = _eva_block(np.arange(at, at + window), columns,
                          positions=positions, window=window, chunk=chunk)
        tokens += int(live[:, :positions].sum())
        summaries += int(live[:, positions:].sum())
    return tokens, summaries


@functools.lru_cache(maxsize=None)
def _eva_mask(positions: int, window: int, chunk: int):
    """``eva_live`` as the kernel's mask object: a block of it is formed when
    the kernel's set-up asks for one (``mask[rows, columns]``: which blocks
    are dead, whole or partial), and a partial block is computed inside the
    kernel from the row and column numbers (``q_sequence``,
    ``mask_function``); the [S, S + S/chunk] array is never held."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as masks,
    )

    named = dict(positions=positions, window=window, chunk=chunk)

    class EvaMask(masks.Mask):
        sizes = (positions, window, chunk)
        q_sequence = np.arange(positions, dtype=np.int32)
        mask_function = staticmethod(functools.partial(eva_live, **named))

        @property
        def shape(self):
            return positions, positions + positions // chunk

        def __getitem__(self, idx):
            rows, columns = (np.arange(n)[i] for n, i in zip(self.shape, idx))
            return _eva_block(rows, columns, **named)

        def __eq__(self, other):
            return getattr(other, "sizes", None) == self.sizes

        def __hash__(self):
            return hash(self.sizes)

    return EvaMask()


@jax.named_scope("eva_pool")
def eva_pool(k, v, phi, mu, *, chunk: int):
    """One key and one value a chunk, heads first: k and v [B, H, S, d], phi
    and mu [H, d] -> (k̃, ṽ) [B, H, S/chunk, d] in the dtype they came in,
    float32 inside.  a = softmax over a chunk's tokens of k·φ/√d;  k̃ = Σ a·k
    + μ;  ṽ = Σ a·v.  Element-wise products and sums over 16 rows: no work
    for the MXU."""
    b, h, s, d = k.shape
    f32 = jnp.float32
    kc = k.astype(f32).reshape(b, h, s // chunk, chunk, d)
    vc = v.astype(f32).reshape(b, h, s // chunk, chunk, d)
    phi, mu = (x.astype(f32)[None, :, None, :] for x in (phi, mu))
    a = jax.nn.softmax(
        jnp.sum(kc * phi[..., None, :], axis=-1) * d ** -0.5, axis=-1)
    pooled = lambda x: jnp.sum(a[..., None] * x, axis=3)
    return (keep((pooled(kc) + mu).astype(k.dtype), ATTENTION_RESIDUALS),
            keep(pooled(vc).astype(v.dtype), ATTENTION_RESIDUALS))


def _eva_windows(q, k, v, kt, vt, *, window: int, chunk: int):
    """XLA's ops, window by window: q (scaled), k and v [B, H, S, d], kt and
    vt [B, H, S/chunk, d] -> [B, H, S, d].  Each window's queries against its
    own tokens and every summary (the later windows' masked), softmax in
    float32 over both together; a window a ``jax.checkpoint`` of its own,
    windows and sequences one by one (``lax.map``): the peak is one
    ``[H, window, window + S/chunk]`` score block."""
    b, h, s, d = q.shape
    n = s // window

    @jax.checkpoint
    def one_window(q, k, v, kt, vt, at):
        keys = jnp.concatenate([k, kt], axis=1)
        values = jnp.concatenate([v, vt], axis=1)
        scores = jnp.einsum("hqd,hkd->hqk", q, keys,
                            preferred_element_type=jnp.float32)
        q_ids = at + jnp.arange(window)
        kv_ids = jnp.concatenate([q_ids, s + jnp.arange(s // chunk)])
        live = eva_live(q_ids[:, None], kv_ids[None, :], positions=s,
                        window=window, chunk=chunk)
        p = jax.nn.softmax(jnp.where(live, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", p.astype(values.dtype), values)

    def one_sequence(args):
        q, k, v, kt, vt = args
        by_window = lambda x: jnp.swapaxes(x.reshape(h, n, window, d), 0, 1)
        out = lax.map(
            lambda a: one_window(a[0], a[1], a[2], kt, vt, a[3]),
            (by_window(q), by_window(k), by_window(v),
             jnp.arange(n) * window))
        return jnp.swapaxes(out, 0, 1).reshape(h, s, d)

    return lax.map(one_sequence, (q, k, v, kt, vt))


def eva_attention(q, k, v, phi, mu, *, window: int, chunk: int,
                  kernel: bool = False, block: int | None = None,
                  interpret: bool = False):
    """EVA: q, k and v [B, S, H, d] (one key-value head a query head), phi
    and mu [H, d] -> [B, S, H, d] in v's dtype.  With ``k̃``, ``ṽ`` the chunk
    summaries (``eva_pool``), query t's output is ONE softmax over the tokens
    of its window up to itself and the summaries of every chunk of the
    windows before its own (``eva_live``), times the values ``[v ; ṽ]``.  A
    sequence of one window (``S <= window``) is plain causal attention.
    ``kernel``: the Pallas kernel over the keys ``[k ; k̃]`` (``interpret``
    for a CPU test of it), else XLA's ops by windows."""
    b, s, h, d = q.shape
    window = min(window, s)
    if s % window or window % chunk:
        raise ValueError(
            f"EVA takes whole windows of whole chunks: a sequence of {s} "
            f"with window_size {window} and chunk_size {chunk} is not")
    # heads first, as the kernel and the pooling read them
    q, k, v = _kernel_operands(q, k, v)
    kt, vt = eva_pool(k, v, phi, mu, chunk=chunk)
    if kernel:
        out = _splash(_eva_mask(s, window, chunk), q,
                      jnp.concatenate([k, kt], axis=2),
                      jnp.concatenate([v, vt], axis=2),
                      block=block or KERNEL_TILES[0], interpret=interpret)
    else:
        out = keep(_eva_windows(q, k, v, kt, vt, window=window, chunk=chunk),
                   ATTENTION_RESIDUALS)
    return _heads_first(out)
