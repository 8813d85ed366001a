"""Fused int8 score + running top-K for the quantized retrieval tier.

The quantized funnel scorer (funnel/index.py ``retrieval_mode="int8"``)
never materializes the per-shard ``[B_local, rows_local]`` score tensor:
the item codes stream through in row tiles and a per-query top-(K·os)
accumulator is merged after every tile, so the only f32 live at any point
is tile-sized — the FlashAttention shape applied to top-k selection
(arxiv 2205.14135): tile, score, select, carry ``[B, K·os]`` forward.

Two implementations share that contract:

* :func:`score_topk_tiles` — the lax composition (unrolled tile loop,
  ``lax.top_k`` merge).  This is the portable path; it is what the
  trace audit proves corpus-f32-free and what CPU hosts (and the bench's
  2·10⁶-row synthetic corpus) run.  Three measured facts shape it:
  (1) the dequantize must happen IN FLIGHT — the broadcast multiply-
  reduce ``sum(u[:,None,:] * codes.astype(f32), -1)`` fuses the int8
  load, convert and MAC into one pass (reads 1 byte/element where the
  exact matmul reads 4), while an explicit ``codes.astype(f32)`` before
  a dot materializes the f32 copy and LOSES to the exact matmul (so do
  int8·int8→int32 dots: XLA:CPU emits scalar int8 MACs); (2) the tile
  loop is a python loop over ``dynamic_slice``, not ``lax.scan`` — the
  scan's per-step carry shuffling on XLA:CPU costs ~2× the whole
  scoring pass; (3) ``lax.top_k`` over the raw tile dominates
  (~60 ns/element on CPU), so selection is screened by group maxima:
  rows tile in groups of ``screen_group``, the top-``kos`` GROUPS by
  group max provably contain the top-``kos`` rows (each selected group
  holds a row scoring >= any excluded row), and only ``kos ·
  screen_group`` candidates reach a ``top_k``.  At 2·10⁶ rows, D=32,
  B=8 this composition beats the exact matmul + full top-k ~1.6×.
* :func:`retrieval_topk_kernel` — the Pallas TPU kernel: same tiling,
  but the accumulator lives in VMEM scratch across grid steps and only
  the final ``[B, K·os]`` pair is written back — the score row never
  round-trips HBM at all.  It runs in interpret mode under test and has
  never compiled for a chip: Mosaic has no lowering for the in-kernel
  ``lax.top_k`` (see :func:`resolve_retrieval_kernel`), so only
  ``funnel_pallas="on"`` selects it, and that raises on today's compiler.

Both return ``(scores [B, kos] f32, rows [B, kos] i32)`` sorted by
(-score, row): ``lax.top_k`` keeps the earlier input index on ties, the
accumulator is ordered ahead of each tile, and tiles arrive in row order
— so ties break toward the smaller local row at every merge, matching
the exact path's lexicographic contract.  Rows carrying score ``-inf``
(masked pads, or slots past the corpus) hold meaningless row indices; the
caller masks on the score before trusting them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# scan tile for the lax composition: large tiles amortize the per-tile
# screen + merge (measured on CPU at 2M rows, D=32: 128Ki edges out 64Ki
# and 256Ki).  The Pallas kernel tiles much smaller — its tile must fit
# VMEM next to the accumulator.
DEFAULT_SCAN_TILE = 131072
DEFAULT_KERNEL_TILE = 2048

# rows per screening group, and the unroll budget for the tile loop (past
# it the tile grows instead, keeping the traced program bounded)
DEFAULT_SCREEN_GROUP = 128
_MAX_UNROLL = 64

_NEG_INF = float("-inf")


def _tiled(codes, scales, ids, tile: int):
    """Pad the per-shard arrays to a tile multiple (pad rows id=-1,
    scale 0 — indistinguishable from index pad rows) and reshape to
    ``[n_tiles, tile, ...]``.  int8/i32/f32-vector ops only: nothing
    corpus-sized is ever f32-2D here."""
    rows = codes.shape[0]
    pad = (-rows) % tile
    if pad:
        codes = jnp.pad(codes, ((0, pad), (0, 0)))
        scales = jnp.pad(scales, (0, pad))
        ids = jnp.pad(ids, (0, pad), constant_values=-1)
    nt = (rows + pad) // tile
    return (codes.reshape(nt, tile, codes.shape[1]),
            scales.reshape(nt, tile), ids.reshape(nt, tile), nt)


def score_topk_tiles(u, codes, scales, ids, *, kos: int,
                     tile: int = DEFAULT_SCAN_TILE,
                     screen_group: int = DEFAULT_SCREEN_GROUP):
    """The lax composition: stream row tiles of the int8 corpus, keep a
    running per-query top-``kos``.

    ``u [B, D] f32`` (full-precision queries — asymmetric scoring, the
    ScaNN shape), ``codes [R, D] i8``, ``scales [R] f32``, ``ids [R]
    i32`` (< 0 marks pad rows).  Returns ``(scores [B, kos], rows [B,
    kos])`` with rows as LOCAL row indices.

    Selection is EXACT despite the screening (see module docstring):
    the top-``kos`` groups by group max must contain the top-``kos``
    rows, and because groups are contiguous ascending row ranges and
    ``lax.top_k`` keeps the earlier index on ties, a group winning a
    group-max tie holds only smaller rows than the loser — the
    smaller-row tie-break survives the screen.  Tiles whose size the
    group does not divide (or too small to be worth screening) take the
    plain whole-tile ``top_k``."""
    b = u.shape[0]
    rows = codes.shape[0]
    t = max(1, min(int(tile), rows))
    gr = max(1, int(screen_group))
    if -(-rows // t) > _MAX_UNROLL:
        # grow the tile (rounded up to a group multiple) instead of
        # unrolling an unbounded loop into the traced program
        t = -(-rows // _MAX_UNROLL)
        t = -(-t // gr) * gr
    pad = (-rows) % t
    if pad:
        codes = jnp.pad(codes, ((0, pad), (0, 0)))
        scales = jnp.pad(scales, (0, pad))
        ids = jnp.pad(ids, (0, pad), constant_values=-1)
    nt = (rows + pad) // t
    screen = gr > 1 and t % gr == 0 and (t // gr) >= 2 * kos
    ng = t // gr if screen else 0

    acc_s = jnp.full((b, kos), _NEG_INF, jnp.float32)
    acc_r = jnp.zeros((b, kos), jnp.int32)
    for step in range(nt):
        c = lax.dynamic_slice_in_dim(codes, step * t, t)       # [t, D] i8
        sc = lax.dynamic_slice_in_dim(scales, step * t, t)
        ii = lax.dynamic_slice_in_dim(ids, step * t, t)
        # dequantize in flight: the convert fuses into the reduce, so
        # the scoring pass reads int8 and the largest f32 it produces
        # is the [B, t] tile score (the audit's no-corpus-f32 contract)
        s = jnp.sum(u[:, None, :] * c[None, :, :].astype(jnp.float32),
                    axis=2)                                    # [B, t]
        s = jnp.where(ii[None, :] >= 0, s * sc[None, :], _NEG_INF)
        if screen:
            sg = s.reshape(b, ng, gr)
            gmax = sg.max(axis=2)
            _, gi = lax.top_k(gmax, kos)                       # [B, kos]
            # ascending group order = ascending row order, restoring
            # the smaller-row preference for the candidate top_k
            gi = jnp.sort(gi, axis=1)
            cand = jnp.take_along_axis(
                sg, gi[:, :, None], axis=1
            ).reshape(b, kos * gr)
            crow = (
                gi[:, :, None] * gr
                + jnp.arange(gr, dtype=jnp.int32)[None, None, :]
            ).reshape(b, kos * gr)
            s_t, ci = lax.top_k(cand, kos)
            r_t = jnp.take_along_axis(crow, ci, axis=1) + step * t
        else:
            s_t = s
            r_t = jnp.broadcast_to(
                step * t + jnp.arange(t, dtype=jnp.int32), (b, t)
            )
        # top_k keeps the earlier input position on ties: accumulator
        # entries (all smaller rows) sit ahead of the tile, so the
        # smaller-row tie-break holds inductively across tiles
        cat_s = jnp.concatenate([acc_s, s_t], axis=1)
        cat_r = jnp.concatenate([acc_r, r_t], axis=1)
        acc_s, idx = lax.top_k(cat_s, kos)
        acc_r = jnp.take_along_axis(cat_r, idx, axis=1)
    return acc_s, acc_r


# ---------------------------------------------------------------------------
# the Pallas fused kernel

def _retrieval_kernel_body(u_ref, codes_ref, scales_ref, ids_ref,
                           s_out, r_out, acc_s, acc_r, *, tile, kos):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_s[...] = jnp.full(acc_s.shape, -jnp.inf, jnp.float32)
        acc_r[...] = jnp.zeros(acc_r.shape, jnp.int32)

    # dequantize the VMEM-resident tile and score it against the (small,
    # replicated) query block; f32 MACs — the HBM win is the int8 stream,
    # not the multiplier width (see module docstring)
    t_f32 = codes_ref[...].astype(jnp.float32) * scales_ref[...]   # [t, D]
    s = jnp.dot(u_ref[...], t_f32.T,
                preferred_element_type=jnp.float32)                # [B, t]
    ii = ids_ref[...].reshape(1, tile)
    s = jnp.where(ii >= 0, s, -jnp.inf)
    b = s.shape[0]
    r = i * tile + lax.broadcasted_iota(jnp.int32, (b, tile), 1)
    cat_s = jnp.concatenate([acc_s[...], s], axis=1)
    cat_r = jnp.concatenate([acc_r[...], r], axis=1)
    s2, idx = lax.top_k(cat_s, kos)
    acc_s[...] = s2
    acc_r[...] = jnp.take_along_axis(cat_r, idx, axis=1)

    @pl.when(i == pl.num_programs(0) - 1)
    def _done():
        s_out[...] = acc_s[...]
        r_out[...] = acc_r[...]


@functools.partial(
    jax.jit, static_argnames=("kos", "tile", "interpret")
)
def retrieval_topk_kernel(u, codes, scales, ids, *, kos: int,
                          tile: int = DEFAULT_KERNEL_TILE,
                          interpret: bool = False):
    """Fused score + running top-``kos`` as one ``pallas_call``: the item
    tiles pipeline HBM→VMEM, the accumulator persists in VMEM scratch
    across the (sequential) grid, and only ``[B, kos]`` writes back.

    Same signature and return contract as :func:`score_topk_tiles` —
    the two are interchangeable behind ``build_retrieve_with``."""
    b, d = u.shape
    t = max(1, min(tile, codes.shape[0]))   # both static under jit
    codes_t, scales_t, ids_t, nt = _tiled(codes, scales, ids, t)
    codes_p = codes_t.reshape(nt * t, d)
    scales_p = scales_t.reshape(nt * t, 1)
    ids_p = ids_t.reshape(nt * t, 1)
    kernel = functools.partial(_retrieval_kernel_body, tile=t, kos=kos)
    return pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((b, d), lambda i: (0, 0)),
            pl.BlockSpec((t, d), lambda i: (i, 0)),
            pl.BlockSpec((t, 1), lambda i: (i, 0)),
            pl.BlockSpec((t, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((b, kos), lambda i: (0, 0)),
            pl.BlockSpec((b, kos), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, kos), jnp.float32),
            jax.ShapeDtypeStruct((b, kos), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, kos), jnp.float32),
            pltpu.VMEM((b, kos), jnp.int32),
        ],
        interpret=interpret,
    )(u, codes_p, scales_p, ids_p)


# ---------------------------------------------------------------------------
# gating (the resolve_fused idiom, ops/pallas_ctr.py)

def resolve_retrieval_kernel(setting: str) -> bool:
    """Resolve the ``funnel_pallas`` knob: "on" | "off" | "auto".

    The TPU compiler refuses this kernel today — on the v5e (jax 0.9.0,
    libtpu 0.0.34; CHANGES.md PR 21) Mosaic answers ``NotImplementedError:
    Unimplemented primitive in Pallas TPU lowering for KernelType.TC:
    top_k`` for the in-kernel merge, and the merge is the kernel's design,
    not a line to patch (ROADMAP S6/D5 owns the rewrite-or-delete).  So
    "auto" resolves to the lax composition on every backend, and "on" —
    which always means the COMPILED kernel — raises with the compiler's
    message when the retrieve executable is built.  Nothing probes at run
    time.  Interpret mode is something a CPU test asks for by name
    (``retrieval_topk_kernel(..., interpret=True)``)."""
    return setting == "on"
