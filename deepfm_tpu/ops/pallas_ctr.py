"""Pallas TPU kernel: fused CTR embedding gather + FM interaction.

The DeepFM hot op (reference ps:206-217) is two HBM table gathers followed
by elementwise scaling and the FM reductions.  The bandwidth-dominant part —
the FM_V [V, K] row gather — is hand-scheduled here as a deduplicated DMA
pipeline; the cheap parts (the [V] FM_W gather and the FM first/second-order
reductions) stay in XLA, which fuses them into single VPU passes over the
kernel's output.

Mosaic cannot DMA a K=32-float row at an arbitrary HBM offset (slices along
the minor dimension must be 128-lane tiles), so the kernel works on an
*aligned-window view* of the table:

    table  [V, K]  →  windows [V·K/128, 128]   (4 rows per window for K=32)
    row r lives in window r·K/128 at lane offset (r·K) mod 128

**Dedup-before-DMA** (v2 — fixes the round-1 skewed-id regression): ids are
deduplicated in XLA first (one sort), and the kernel gathers each *unique*
row exactly once, in sorted order:

    XLA   : unique(ids)  →  sorted unique rows + inverse map
    kernel: per unique row, DMA its 128-lane window HBM→VMEM — but only
            when the window differs from the previous row's (sorted ids
            put same-window rows adjacent), NSEM copies in flight
    kernel: log-step forward-fill propagates each DMA'd window to the
            following rows that share it, then a static-roll masked select
            picks the K-lane sub-window per row (VPU)
    XLA   : emb = unique_rows[inverse] * vals   (one dense gather + scale)

On Zipf-skewed Criteo ids a batch of 1024×39 lookups hits only ~30-40% as
many unique rows, and sorted adjacency packs ~`128/K` unique rows per
window, so HBM traffic drops several-fold exactly where the round-1 kernel
lost to XLA (hot windows were re-DMA'd per duplicate).  Uniform ids benefit
from the window packing alone.  The dedup's sort also pays for the
backward: the custom VJP segment-sums row gradients by the same inverse
map and scatter-adds each unique row once — no duplicate-index scatter
serialization.

**On the v5e (jax 0.9.0, libtpu 0.0.34; CHANGES.md PR 21)** the kernel
compiles and matches the lax reference at the reference widths (V=117,581,
F=39, K=32, batch 1024): emb / y_w / y_v bit-equal, gradients within 1.4e-7
relative — ``chip_smoke.py`` keeps that compile-and-compare step.  Its
speed is not measured on today's code.  At this vocab the 15 MB table fits
fast memory, so XLA's plain gather has nothing to lose; the dedup design's
payoff, if any, is the regime where the table does NOT fit (ROADMAP S6
decides whether the kernel stays).  The default stays "off".

Only the gathered working set sits in VMEM, so the kernel scales to
vocabularies far beyond VMEM (the 100M-row north star) — the table stays in
HBM and is touched only near the gathered rows, exactly like the
parameter-server pull the reference does over grpc (README.md:15,63), but at
HBM-DMA latency instead of network latency.

Use ``fused_ctr_interaction`` (the custom-vjp wrapper).  It compiles for
the chip; a CPU test asks for Pallas interpret mode by name
(``interpret=True``, tests/test_pallas_ctr.py) — the model path never does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_N_TILE = 1024          # gathered rows per grid step
_NSEM = 64              # DMA pipeline depth (copies in flight)


def _dedup_plan(flat_ids: jnp.ndarray, per_win: int):
    """XLA-side dedup: one sort over the flat id stream.

    Returns (uids, inv, valid, win, sel, first, dist, dma_rows) where all
    per-row arrays are padded to a ``_N_TILE`` multiple:

      uids    [N]  sorted unique row ids (pad slots hold a repeated id)
      inv     [n]  position of each original id in ``uids``
      valid   [N]  True for real unique slots (False for padding)
      win     [N]  window index per unique row
      sel     [N]  lane-offset selector (0..per_win-1)
      first   [N]  1 where the row's window differs from the previous row's
                   (or at a tile boundary) — exactly the rows the kernel DMAs
      dist    [N]  distance to the row's window source (for forward-fill)
      dma_rows[N]  per tile, at flat index base+d: the row-in-tile of the
                   d-th DMA — lets the kernel retire semaphores in order
    """
    n = flat_ids.shape[0]
    uids, inv, counts = jnp.unique(
        flat_ids, size=n, fill_value=0, return_inverse=True,
        return_counts=True,
    )
    pad = (-n) % _N_TILE
    total = n + pad
    if pad:
        uids = jnp.pad(uids, (0, pad), mode="edge")
        counts = jnp.pad(counts, (0, pad))
    valid = counts > 0
    win = (uids // per_win).astype(jnp.int32)
    sel = (uids % per_win).astype(jnp.int32)
    j = jnp.arange(total, dtype=jnp.int32)
    prev_win = jnp.concatenate([win[:1] - 1, win[:-1]])
    first = ((j % _N_TILE == 0) | (win != prev_win)).astype(jnp.int32)
    src = jax.lax.associative_scan(jnp.maximum, jnp.where(first == 1, j, -1))
    dist = (j - src).astype(jnp.int32)
    n_tiles = total // _N_TILE
    ft = first.reshape(n_tiles, _N_TILE)
    c = jnp.cumsum(ft, axis=1) - 1
    rows = jnp.broadcast_to(
        jnp.arange(_N_TILE, dtype=jnp.int32)[None], (n_tiles, _N_TILE)
    )
    dma_rows = (
        jnp.zeros((n_tiles, _N_TILE), jnp.int32)
        .at[jnp.arange(n_tiles)[:, None], jnp.where(ft == 1, c, _N_TILE)]
        .set(rows, mode="drop")
        .reshape(-1)
    )
    return uids, inv, valid, win, sel, first, dist, dma_rows


def _gather_unique_kernel(
    win_ref, first_ref, dma_rows_ref, sel_ref, dist_ref, table_ref, emb_ref,
    windows, sems, *, per_win,
):
    """Gather one tile of SORTED unique rows, one DMA per distinct window.

    win_ref/first_ref/dma_rows_ref: scalar-prefetch [N] int32 (see
    ``_dedup_plan``); sel_ref/dist_ref: [N_TILE, 1] int32 VMEM;
    table_ref: [V·K/LANES, LANES] f32 HBM (aligned-window view);
    emb_ref: out [N_TILE, K] f32 VMEM; windows: scratch [N_TILE, LANES];
    sems: [NSEM] DMA semaphores.
    """
    i = pl.program_id(0)
    base = i * _N_TILE
    k = emb_ref.shape[1]

    def dma(row, d):
        return pltpu.make_async_copy(
            table_ref.at[win_ref[base + row]],   # (LANES,) aligned window
            windows.at[row],
            sems.at[d % _NSEM],
        )

    def issue(j, cnt):
        f = first_ref[base + j]

        @pl.when(f == 1)
        def _():
            # retire the copy that used this semaphore slot NSEM DMAs ago,
            # then reuse the slot — keeps up to NSEM copies in flight
            @pl.when(cnt >= _NSEM)
            def _():
                dma(dma_rows_ref[base + cnt - _NSEM], cnt - _NSEM).wait()

            dma(j, cnt).start()

        return cnt + f

    total = jax.lax.fori_loop(0, _N_TILE, issue, jnp.int32(0))

    def drain(d, _):
        dma(dma_rows_ref[base + d], d).wait()
        return ()

    jax.lax.fori_loop(jnp.maximum(total - _NSEM, 0), total, drain, ())

    # forward-fill: propagate each DMA'd window down to the rows sharing it.
    # Sorted unique ids put same-window rows adjacent, so a real row's
    # source is at most per_win-1 rows back — ceil(log2(per_win)) passes.
    # At pass b, rows with dist in [2^b, 2^(b+1)) copy from a row whose own
    # dist < 2^b, i.e. already resolved.  (Rows with j < shift would wrap,
    # but their dist ≤ j < shift, so the mask never takes them.)
    w = windows[:]                                       # [N_TILE, LANES]
    d = dist_ref[:]                                      # [N_TILE, 1]
    for b in range(max(0, per_win - 1).bit_length()):
        s = 1 << b
        cand = pltpu.roll(w, shift=s, axis=0)
        w = jnp.where((d >= s) & (d < 2 * s), cand, w)

    # epilogue (VPU): pick the K-lane sub-window per row.  q is static per
    # branch, so roll shifts are static; the dynamic lane offset is resolved
    # by the masked select over LANES/K candidates.
    sel = sel_ref[:]                                     # [N_TILE, 1]
    e = jnp.zeros((_N_TILE, k), jnp.float32)
    for q in range(per_win):
        cand = pltpu.roll(w, shift=(-q * k) % _LANES, axis=1)[:, :k]
        e = jnp.where(sel == q, cand, e)
    emb_ref[:] = e


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gather_unique(fm_v, win, sel, first, dist, dma_rows, *, interpret: bool):
    """Pallas gather of sorted unique rows: [V,K] + plan -> [N, K]."""
    v, k = fm_v.shape
    if _LANES % k:
        raise ValueError(f"embedding_size {k} must divide {_LANES}")
    per_win = _LANES // k

    # aligned-window view: pad rows to a window multiple, flatten, refold
    v_pad = (-v) % per_win
    table = fm_v if not v_pad else jnp.pad(fm_v, ((0, v_pad), (0, 0)))
    table = table.reshape(-1, _LANES)                    # [Vp·K/LANES, LANES]

    n = win.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                           # win, first, dma_rows
        grid=(n // _N_TILE,),
        in_specs=[
            pl.BlockSpec((_N_TILE, 1), lambda i, *_: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((_N_TILE, 1), lambda i, *_: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=pl.BlockSpec(
            (_N_TILE, k), lambda i, *_: (i, 0), memory_space=pltpu.VMEM
        ),
        scratch_shapes=[
            pltpu.VMEM((_N_TILE, _LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((_NSEM,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_gather_unique_kernel, per_win=per_win),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, k), jnp.float32),
        interpret=interpret,
    )(win, first, dma_rows, sel[:, None], dist[:, None], table)


# The dedup plan rides scalar-prefetch (SMEM, 1 MB): three int32 arrays of
# the flat id-stream length must fit, capping one kernel invocation at
# ~87k ids (measured: 160k ids over-subscribes SMEM 1.83M/1.00M).  Larger
# batches are mapped through the kernel in row chunks — FM terms and emb
# rows are independent per batch row, so chunking the batch axis is exact.
_MAX_FLAT_IDS = 65_536


def fused_ctr_interaction(fm_w, fm_v, ids, vals, interpret=False):
    """Fused gather + FM: (fm_w [V], fm_v [V,K], ids [B,F], vals [B,F]) ->
    (emb [B,F,K], y_w [B], y_v [B]).  emb is already vals-scaled (ps:212-214);
    y_w/y_v are the first/second-order FM terms (ps:207-217).  Out-of-range
    ids clip to [0, V-1] like ``jnp.take(mode='clip')``.  Batches whose flat
    id stream exceeds the SMEM plan budget are processed in row chunks via
    ``lax.map`` (dedup is then chunk-local; table cotangents accumulate
    across chunks in the scan)."""
    ids = ids.reshape(-1, ids.shape[-1])
    vals = vals.reshape(ids.shape)
    b, f = ids.shape
    rows_per_chunk = max(_MAX_FLAT_IDS // f, 1)
    if b <= rows_per_chunk:
        return _fused_chunk(fm_w, fm_v, ids, vals, interpret)
    pad = (-b) % rows_per_chunk
    if pad:
        ids = jnp.concatenate([ids, jnp.zeros((pad, f), ids.dtype)])
        vals = jnp.concatenate([vals, jnp.zeros((pad, f), vals.dtype)])
    emb, y_w, y_v = jax.lax.map(
        lambda iv: _fused_chunk(fm_w, fm_v, iv[0], iv[1], interpret),
        (ids.reshape(-1, rows_per_chunk, f), vals.reshape(-1, rows_per_chunk, f)),
    )
    k = emb.shape[-1]
    return emb.reshape(-1, f, k)[:b], y_w.reshape(-1)[:b], y_v.reshape(-1)[:b]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _fused_chunk(fm_w, fm_v, ids, vals, interpret=False):
    """One SMEM-sized chunk of the fused gather+FM (see the public wrapper)."""
    out, _ = _forward(fm_w, fm_v, ids, vals, interpret)
    return out


def _forward(fm_w, fm_v, ids, vals, interpret):
    ids = ids.reshape(-1, ids.shape[-1])
    vals = vals.astype(jnp.float32)
    b, f = ids.shape
    v, k = fm_v.shape
    # clip in the incoming (possibly int64) dtype FIRST: casting an
    # unvalidated id >= 2**31 would wrap onto an arbitrary in-range row
    # before the clip could bound it (same contract as ops.embedding
    # narrow_ids)
    ids = jnp.clip(ids, 0, v - 1).astype(jnp.int32)
    flat = ids.reshape(-1)
    uids, inv, valid, win, sel, first, dist, dma_rows = _dedup_plan(
        flat, _LANES // k
    )
    rows_u = _gather_unique(
        fm_v, win, sel, first, dist, dma_rows, interpret=interpret
    )
    emb = rows_u[inv].reshape(b, f, k) * vals[..., None]
    # small gather + reductions stay in XLA: fused into one pass over emb
    w_rows = jnp.take(fm_w, ids, axis=0)
    y_w = jnp.sum(w_rows * vals, axis=1)
    sum_e = jnp.sum(emb, axis=1)
    y_v = 0.5 * jnp.sum(
        jnp.square(sum_e) - jnp.sum(jnp.square(emb), axis=1), axis=1
    )
    return (emb, y_w, y_v), (ids, uids, inv, valid, rows_u)


def _fused_fwd(fm_w, fm_v, ids, vals, interpret):
    out, (ids2d, uids, inv, valid, rows_u) = _forward(
        fm_w, fm_v, ids, vals, interpret
    )
    return out, (fm_w, fm_v, ids2d, vals, uids, inv, valid, rows_u)


def _fused_bwd(interpret, res, cotangents):
    """Backward in plain XLA, deduplicated: row grads are segment-summed by
    the forward's inverse map, so the table scatter-add touches each unique
    row once — no duplicate-index serialization on skewed ids."""
    fm_w, fm_v, ids, vals, uids, inv, valid, rows_u = res
    g_emb, g_yw, g_yv = cotangents
    v, k = fm_v.shape
    vals = vals.astype(jnp.float32)
    v_rows = rows_u[inv].reshape(*ids.shape, k)            # [B, F, K]
    e = v_rows * vals[..., None]
    sum_e = jnp.sum(e, axis=1)                             # [B, K]
    # ∂y_v/∂e_bfk = Σ_f' e_bf'k − e_bfk  (derivative of the FM identity)
    g_e = g_emb + g_yv[:, None, None] * (sum_e[:, None, :] - e)
    d_v_rows = g_e * vals[..., None]
    n_seg = uids.shape[0]
    d_u = jax.ops.segment_sum(
        d_v_rows.reshape(-1, k), inv, num_segments=n_seg
    )
    d_uw = jax.ops.segment_sum(
        (g_yw[:, None] * vals).reshape(-1), inv, num_segments=n_seg
    )
    scatter_idx = jnp.where(valid, uids, v)                # OOB pads drop
    d_fm_v = jnp.zeros_like(fm_v).at[scatter_idx].add(d_u, mode="drop")
    d_fm_w = jnp.zeros_like(fm_w).at[scatter_idx].add(d_uw, mode="drop")
    w_rows = jnp.take(fm_w, ids, axis=0)
    d_vals = jnp.sum(g_e * v_rows, axis=-1) + g_yw[:, None] * w_rows
    return d_fm_w, d_fm_v, None, d_vals.astype(vals.dtype)


_fused_chunk.defvjp(_fused_fwd, _fused_bwd)


def resolve_fused(setting: str, embedding_size: int) -> bool:
    """Resolve ModelConfig.fused_kernel: "on" | "off" | "auto".

    "on" always means the COMPILED kernel: off a TPU, or for a shape or
    kernel the compiler refuses, the call raises with the compiler's
    message.  "auto" takes the kernel exactly where it applies — a TPU
    backend and an embedding_size that divides the 128-lane window — and
    the XLA gather path elsewhere.  "off" keeps the XLA gather path.
    Interpret mode is never a resolution: a CPU test asks for it by name
    (``fused_ctr_interaction(..., interpret=True)`` or
    ``pltpu.force_tpu_interpret_mode``)."""
    if setting == "on":
        if _LANES % embedding_size:
            raise ValueError(
                f"fused_kernel='on' needs embedding_size dividing {_LANES}, "
                f"got {embedding_size}"
            )
        return True
    if setting == "auto":
        from ..core.platform import is_tpu_backend

        return _LANES % embedding_size == 0 and is_tpu_backend()
    return False
