"""Per-row symmetric int8 quantization of the item-tower embedding matrix.

The quantized retrieval tier (funnel/index.py ``retrieval_mode="int8"``)
stores the corpus twice: the f32 ``item_emb`` rows it already had (the
exact-rescore source — only ever read through a shortlist-sized gather)
and an int8 code matrix + per-row f32 scale derived here.  Scoring then
streams 1 byte/element instead of 4 — the retrieval matmul is bandwidth-
bound at corpus scale, so the code stream is where the latency goes —
while the oversampled shortlist is re-scored against the exact f32 rows
before anything crosses a collective (ScaNN's asymmetric score-then-
rescore shape, arxiv 1908.10396).

Per-row symmetric means ``codes[i] = round(emb[i] / scales[i])`` with
``scales[i] = max|emb[i]| / 127``: zero is exactly representable (pad
rows stay exactly zero), and the worst-case per-element reconstruction
error is ``scales[i] / 2`` — recorded per publish as the quantization
error bound so the manifest carries the quality budget alongside the
measured recall (funnel/recall.py).

The int8 tier scores with :func:`score_topk_tiles`, a lax scan that never
materializes the per-shard ``[B_local, rows_local]`` score tensor: the
item codes stream through in row tiles and a per-query top-(K·os)
accumulator is merged after every tile, so the only f32 live at any point
is tile-sized — the FlashAttention shape applied to top-k selection
(arxiv 2205.14135): tile, score, select, carry ``[B, K·os]`` forward.  It
is what the trace audit proves corpus-f32-free.  Three measured facts
shape it: (1) the dequantize must happen IN FLIGHT — the broadcast
multiply-reduce ``sum(u[:,None,:] * codes.astype(f32), -1)`` fuses the
int8 load, convert and MAC into one pass (reads 1 byte/element where the
exact matmul reads 4), while an explicit ``codes.astype(f32)`` before a
dot materializes the f32 copy and LOSES to the exact matmul (so do
int8·int8→int32 dots: XLA:CPU emits scalar int8 MACs); (2) the tile loop
is a python loop over ``dynamic_slice``, not ``lax.scan`` — the scan's
per-step carry shuffling on XLA:CPU costs ~2× the whole scoring pass;
(3) ``lax.top_k`` over the raw tile dominates (~60 ns/element on CPU), so
selection is screened by group maxima: rows tile in groups of
``screen_group``, the top-``kos`` GROUPS by group max provably contain
the top-``kos`` rows (each selected group holds a row scoring >= any
excluded row), and only ``kos · screen_group`` candidates reach a
``top_k``.  At 2·10⁶ rows, D=32, B=8 this composition beats the exact
matmul + full top-k ~1.6×.

It returns ``(scores [B, kos] f32, rows [B, kos] i32)`` sorted by
(-score, row): ``lax.top_k`` keeps the earlier input index on ties, the
accumulator is ordered ahead of each tile, and tiles arrive in row order
— so ties break toward the smaller local row at every merge, matching
the exact path's lexicographic contract.  Rows carrying score ``-inf``
(masked pads, or slots past the corpus) hold meaningless row indices; the
caller masks on the score before trusting them.
"""

from __future__ import annotations

import numpy as np

# the knob's legal values (core/config.py validates, funnel/index.py
# resolves): "auto" picks int8 once the index capacity crosses
# AUTO_INT8_MIN_ROWS — below that the exact matmul is already cheap and
# bit-parity beats an (oversample, min_recall) budget nobody needed
RETRIEVAL_MODES = ("exact", "int8", "auto")
AUTO_INT8_MIN_ROWS = 1 << 20

_QMAX = 127.0

# scan tile: large tiles amortize the per-tile screen + merge (measured on
# CPU at 2M rows, D=32: 128Ki edges out 64Ki and 256Ki)
DEFAULT_SCAN_TILE = 131072

# rows per screening group, and the unroll budget for the tile loop (past
# it the tile grows instead, keeping the traced program bounded)
DEFAULT_SCREEN_GROUP = 128
_MAX_UNROLL = 64

_NEG_INF = float("-inf")


def resolve_retrieval_mode(mode: str, capacity: int) -> str:
    """Resolve the ``funnel_retrieval`` knob to a concrete mode.

    Resolution keys on the index CAPACITY (static serving geometry), not
    the live item count: the mode picks which executables compile at
    boot, and a corpus that grows across republishes must not flip the
    payload tree mid-traffic."""
    if mode not in RETRIEVAL_MODES:
        raise ValueError(
            f"funnel_retrieval={mode!r} is not one of {RETRIEVAL_MODES}"
        )
    if mode == "auto":
        return "int8" if int(capacity) >= AUTO_INT8_MIN_ROWS else "exact"
    return mode


def quantize_rows(emb: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """``[N, D] f32 -> (codes [N, D] int8, scales [N] f32)``.

    All-zero rows (index pad rows) quantize to scale 0 + zero codes, so a
    dequantized pad row is exactly zero — the pad-masking invariant
    (id < 0 ⇒ -inf) never depends on quantization noise."""
    emb = np.asarray(emb, np.float32)
    if emb.ndim != 2:
        raise ValueError(f"expected [N, D] embeddings, got shape {emb.shape}")
    amax = np.abs(emb).max(axis=1)
    scales = (amax / _QMAX).astype(np.float32)
    safe = np.where(scales > 0, scales, 1.0)
    codes = np.clip(np.rint(emb / safe[:, None]), -_QMAX, _QMAX)
    return codes.astype(np.int8), scales


def dequantize_rows(codes: np.ndarray, scales: np.ndarray) -> np.ndarray:
    """The scorer's reconstruction: ``codes * scales[:, None]`` in f32."""
    return (np.asarray(codes, np.float32)
            * np.asarray(scales, np.float32)[:, None])


def quantization_stats(emb: np.ndarray, codes: np.ndarray,
                       scales: np.ndarray) -> dict:
    """The error budget a publish records next to the measured recall:
    worst observed per-element reconstruction error, the analytic bound
    (``max(scales) / 2``), and the worst per-row score perturbation for a
    unit query (``||err_row||_2`` — Cauchy-Schwarz on ``u·err``)."""
    emb = np.asarray(emb, np.float32)
    err = emb - dequantize_rows(codes, scales)
    row_l2 = np.sqrt((err * err).sum(axis=1)) if emb.size else np.zeros(0)
    return {
        "max_abs_err": float(np.abs(err).max()) if emb.size else 0.0,
        "err_bound": float(scales.max() / 2.0) if np.size(scales) else 0.0,
        "max_row_score_err": float(row_l2.max()) if emb.size else 0.0,
    }


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
    import jax.numpy as jnp
    from jax import lax

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
