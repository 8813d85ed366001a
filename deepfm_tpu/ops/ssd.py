"""The Mamba-2 mixer (Dao & Gu, "Transformers are SSMs", ICML 2024: the
state-space duality) as ``model_type: nemotron_h`` runs it, on the heads this
shard holds.

With ``H`` heads of ``P`` channels held here, ``G`` groups of state size
``N`` (a group serves ``H/G`` consecutive heads), ``K`` taps and every
projection without bias:

    [z | xBC | dt] = W_in·x              z, x: H·P; B, C: G·N each; dt: H
    xBC_t = silu(Σ_j w_j ⊙ xBC_{t−K+1+j} + b)   causal depthwise, zeros before 0
    Δ = softplus(dt + dt_bias);  A = −exp(A_log);  a_t = exp(Δ_t·A)
    S_t = a_t·S_{t−1} + Δ_t·x_t ⊗ B_t      S [P, N] a head, S_{−1} = 0
    y_t = S_t·C_t + D·x_t
    out = W_out·n_group(y ⊙ silu(z))      RMSNorm over each group's channels

The scan goes a chunk of ``Q`` positions at a time (the duality's chunked
form).  With X = Δ·x [Q, P], L_i = Σ_{k≤i} Δ_k·A the log-decay summed up to
position i of the chunk, and S_in the state the chunk starts from:

    Y     = (C·Bᵀ ∘ Λ)·X + e^L ∘ (C·S_inᵀ)      Λ_ij = e^{L_i − L_j}, j ≤ i
    S_out = e^{L_Q}·S_in + (e^{L_Q − L} ∘ X)ᵀ·B

On a TPU one Pallas kernel makes it (``ssd_scan_fwd``: the instruction's
name) and one its gradient (``ssd_scan_bwd``): a grid over (sequence, head)
with the chunks in order, the state in VMEM in float32 — the forward carries
S and writes each chunk's S_in, the backward sweeps the chunks in reverse and
carries dS.  Elsewhere the same algebra in ``jnp``, every chunk at once and
the states passed by a ``lax.scan`` over the chunks, a ``jax.checkpoint``
of its own differentiated by JAX.
Which runs is observed as ``ops/attention.kernel_tile`` observes it: from the
devices the step is traced for, no option.

The four products of a chunk take their operands in the caller's dtype
(``compute_dtype``: X, B and C arrive in it, the decayed product and the
state are cast to it where they enter a product) with float32 accumulation;
Δ, the decays, the state carried from chunk to chunk and everything
element-wise in float32.  The scan's operands and its output (and the
kernel's chunk states) carry ``ATTENTION_RESIDUALS``: a rematerialised block
that keeps them (``ops/kept.py``) runs neither the scan nor the layout
copies around it again.
"""

from __future__ import annotations

import functools
import logging
import math

import jax
import jax.numpy as jnp
from jax import lax

from .dense import kept_mm
from .kept import ATTENTION_RESIDUALS, count, keep

F32 = jnp.float32


def scan_kernel(positions: int, chunk: int) -> bool:
    """Whether the scan runs as the Pallas kernel — observed, not set: where
    the trace runs under a mesh of TPU devices (a rehearsal compile for a
    described chip sees that chip's) and the chunk divides the sequence.
    Said once a trace: ``ssd scan: Pallas kernel, chunk=… | XLA's ops (…)``."""
    device = jax.sharding.get_abstract_mesh().abstract_device
    kind = device.device_kind if device is not None else "no mesh"
    kernel = kind.startswith("TPU") and positions % chunk == 0
    if not kind.startswith("TPU"):
        how = f"XLA's ops (devices: {kind})"
    elif not kernel:
        how = f"XLA's ops (a chunk of {chunk} does not divide {positions})"
    else:
        how = "Pallas kernel"
    logging.getLogger(__name__).info("ssd scan: %s, chunk=%d, positions=%d",
                                     how, chunk, positions)
    return kernel


def causal_conv(x, w, b):
    """x [B, T, C] float32, w [K, C], b [C] -> silu(Σ_j w[j] ⊙ x_{t−K+1+j}
    + b), zeros before the sequence's start."""
    taps, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(w[j] * padded[:, j:j + t] for j in range(taps)) + b)


def gated_norm(y, z, gain, groups: int, eps: float):
    """n_group(y ⊙ silu(z)): y, z [..., C] float32, an RMSNorm over each of
    ``groups`` contiguous parts of the C channels, then the gain [C]."""
    y = y * jax.nn.silu(z)
    shape = y.shape
    y = y.reshape(*shape[:-1], groups, -1)
    y = y * lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True) + eps)
    return y.reshape(shape) * gain


# -- the chunked scan by XLA's ops -------------------------------------------


@functools.partial(jax.checkpoint, static_argnums=(4,))
def _scan_by_ops(xs, l, b, c, chunk: int):
    """xs [B, T, H, P], l [B, T, H] (L, a chunk's sums), b and c [B, T, G,
    N] -> y [B, T, H, P] float32: every chunk's products at once, the states
    passed from chunk to chunk by a scan.  A ``jax.checkpoint`` of its own:
    a caller that keeps its operands and its output runs none of it again,
    and its backward forms its products again inside itself."""
    bsz, t, h, p = xs.shape
    g, n = b.shape[2:]
    nc, dt = t // chunk, xs.dtype
    xs = xs.reshape(bsz, nc, chunk, h, p)
    l = l.reshape(bsz, nc, chunk, h)
    b, c = (jnp.repeat(a.reshape(bsz, nc, chunk, g, n), h // g, axis=3)
            for a in (b, c))
    # Λ below the diagonal; its diagonal is 1 whatever L is, and written so:
    # as e^{L_i − L_i} the diagonal's two equal and opposite terms of L's
    # gradient would dwarf what is left where the decays are steep
    below = jnp.tri(chunk, k=-1, dtype=bool)[None, None, :, :, None]
    decay = jnp.exp(jnp.where(
        below, l[:, :, :, None, :] - l[:, :, None, :, :], -jnp.inf)
    ) + jnp.eye(chunk)[None, None, :, :, None]
    cb = jnp.einsum("bcihn,bcjhn->bcijh", c, b, preferred_element_type=F32)
    y = jnp.einsum("bcijh,bcjhp->bcihp", (cb * decay).astype(dt), xs,
                   preferred_element_type=F32)
    last = l[:, :, -1]                                         # [B, nc, H]
    tail = jnp.exp(last[:, :, None, :] - l)[..., None]
    local = jnp.einsum("bcjhp,bcjhn->bchpn", (xs * tail).astype(dt), b,
                       preferred_element_type=F32)

    def carry(s, chunk_of):
        local, last = chunk_of
        return jnp.exp(last)[:, :, None, None] * s + local, s

    _, s_in = lax.scan(carry, jnp.zeros((bsz, h, p, n), F32),
                       (jnp.moveaxis(local, 1, 0), jnp.moveaxis(last, 1, 0)))
    head = jnp.exp(l)[..., None]
    y = y + jnp.einsum("bcihn,bchpn->bcihp", (c * head).astype(dt),
                       jnp.moveaxis(s_in, 0, 1).astype(dt),
                       preferred_element_type=F32)
    return y.reshape(bsz, t, h, p)


# -- the chunked scan by one Pallas kernel each way ----------------------------


def _nt(a, b):
    """a·bᵀ, float32 accumulation."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=F32)


def _tn(a, b):
    """aᵀ·b, float32 accumulation."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=F32)


def _nn(a, b):
    return jnp.dot(a, b, preferred_element_type=F32)


def _decays(l_row):
    """A chunk's L as a row [1, Q] -> (Λ [Q, Q], the mask of the pairs
    below its diagonal, e^L and e^{L_Q − L} as columns [Q, 1], e^{L_Q} [1,
    1], the row mask of position Q−1).  Λ's diagonal is 1, not e^{L_i −
    L_i}: its two terms of L's gradient cancel and are left out."""
    q = l_row.shape[1]
    rows = jnp.transpose(jnp.broadcast_to(l_row, (q, q)))     # [i, j] = L_i
    i = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    j = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    below = j < i
    decay = jnp.where(below, jnp.exp(jnp.where(
        below, rows - jnp.transpose(rows), -jnp.inf)),
        jnp.where(i == j, 1.0, 0.0))
    l = rows[:, :1]
    last = l_row[:, q - 1:]
    return (decay, below, jnp.exp(l), jnp.exp(last - l), jnp.exp(last),
            lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1)


def _fwd_kernel(x_ref, l_ref, b_ref, c_ref, y_ref, s_ref, state):
    """One (sequence, head, chunk) of the forward: x_ref [Q, P], l_ref [1, Q],
    b_ref and c_ref [Q, N] -> y_ref [Q, P], s_ref [P, N] the state the chunk
    starts from; ``state`` [P, N] float32 carries it on."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros(state.shape, F32)

    x, b, c = x_ref[...], b_ref[...], c_ref[...]
    dt = x.dtype
    decay, _, head, tail, whole, _ = _decays(l_ref[...])
    s = state[...]
    s_ref[...] = s
    y = _nn((_nt(c, b) * decay).astype(dt), x)
    y_ref[...] = y + _nt((c * head).astype(dt), s.astype(dt))
    state[...] = whole * s + _tn((x * tail).astype(dt), b)


def _bwd_kernel(x_ref, l_ref, b_ref, c_ref, s_ref, dy_ref,
                dx_ref, dl_ref, db_ref, dc_ref, dstate):
    """One (sequence, head, chunk) of the gradient, the chunks in reverse:
    the forward's operands, its s_ref and dy_ref [Q, P] float32 -> dx_ref
    [Q, P], dl_ref [1, Q], db_ref and dc_ref [Q, N] (this head's part of its
    group's), all float32; ``dstate`` [P, N] float32 carries dS back from
    the chunk after."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros(dstate.shape, F32)

    x, b, c = x_ref[...], b_ref[...], c_ref[...]
    dt = x.dtype
    decay, below, head, tail, whole, at_last = _decays(l_ref[...])
    s, ds = s_ref[...], dstate[...]
    dy = dy_ref[...].astype(dt)
    m = _nt(c, b) * decay                       # the forward's C·Bᵀ ∘ Λ
    dm = _nt(dy, x)                             # ∂/∂M = dY·Xᵀ
    dg = (dm * decay).astype(dt)                # ∂/∂(C·Bᵀ)
    dys = _nn(dy, s.astype(dt))                 # dY·S_in [Q, N]
    xds = _nn(x, ds.astype(dt))                 # X·dS [Q, N]
    dx_ref[...] = _tn(m.astype(dt), dy) + tail * _nt(b, ds.astype(dt))
    dc_ref[...] = _nn(dg, b) + head * dys
    db_ref[...] = _tn(dg, c) + tail * xds
    # L moves Λ below its diagonal (each pair's own difference), e^L and
    # e^{L_Q − L}: the first as the row sums less the column sums of dM ∘ M
    r = jnp.where(below, dm * m, 0.0)
    w = tail * jnp.sum(xds * b, axis=1, keepdims=True)
    last = whole * jnp.sum(jnp.sum(ds * s, axis=1, keepdims=True), axis=0,
                           keepdims=True) + jnp.sum(w, axis=0, keepdims=True)
    dl = (jnp.sum(r, axis=1, keepdims=True)
          - jnp.sum(jnp.transpose(r), axis=1, keepdims=True)
          + head * jnp.sum(dys * c, axis=1, keepdims=True) - w
          + jnp.where(at_last, last, 0.0))
    q = dl.shape[0]
    dl_ref[...] = jnp.transpose(jnp.broadcast_to(dl, (q, q)))[:1, :]
    dstate[...] = _tn(dy, (c * head).astype(dt)) + whole * ds


def _specs(chunk: int, chunks: int, p: int, n: int, group: int,
           reverse: bool):
    """BlockSpecs over the grid (sequence, head, chunk): a head's rows, its
    L, its group's B and C, its chunk's state; the chunks in reverse for the
    backward."""
    from jax.experimental import pallas as pl

    at = (lambda i: chunks - 1 - i) if reverse else (lambda i: i)
    return {
        "x": pl.BlockSpec((None, None, chunk, p), lambda s, h, i: (s, h, at(i), 0)),
        "l": pl.BlockSpec((None, None, 1, chunk), lambda s, h, i: (s, h, 0, at(i))),
        "bc": pl.BlockSpec((None, None, chunk, n),
                           lambda s, h, i: (s, h // group, at(i), 0)),
        "bh": pl.BlockSpec((None, None, chunk, n), lambda s, h, i: (s, h, at(i), 0)),
        "s": pl.BlockSpec((None, None, None, p, n),
                          lambda s, h, i: (s, h, at(i), 0, 0)),
    }


def _chunk_flops(chunk: int, p: int, n: int) -> int:
    """The forward's four products a chunk and head."""
    return 2 * chunk * chunk * (n + p) + 4 * chunk * n * p


def _call(kernel, name: str, in_specs, out_specs, out_shape, operands, *,
          chunk: int, passes: int, interpret: bool):
    """The kernel over the grid (sequence, head, chunk) of ``operands[0]``
    [B, H, T, P], a [P, N] float32 scratch; ``passes`` the forward's
    products a chunk that it runs (the forward 1, the backward 2)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, h, t, p = operands[0].shape
    n = operands[2].shape[3]
    steps = bsz * h * (t // chunk)
    moved = sum(math.prod(a.shape) * jnp.dtype(a.dtype).itemsize
                for a in (*operands, *out_shape))
    return pl.pallas_call(
        kernel, grid=(bsz, h, t // chunk), in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((p, n), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=passes * steps * _chunk_flops(chunk, p, n),
            transcendentals=steps * (chunk * chunk + 3 * chunk),
            bytes_accessed=moved),
        name=name, interpret=interpret)(*operands)


def _forward(chunk: int, interpret: bool, xs, l, b, c):
    """Head-major operands xs [B, H, T, P], l [B, H, 1, T], b and c [B, G,
    T, N] -> (y [B, H, T, P] float32, each chunk's starting state [B, H,
    T/Q, P, N] float32)."""
    bsz, h, t, p = xs.shape
    g, n = b.shape[1], b.shape[3]
    nc = t // chunk
    sp = _specs(chunk, nc, p, n, h // g, reverse=False)
    return _call(
        _fwd_kernel, "ssd_scan_fwd",
        [sp["x"], sp["l"], sp["bc"], sp["bc"]], [sp["x"], sp["s"]],
        [jax.ShapeDtypeStruct((bsz, h, t, p), F32),
         jax.ShapeDtypeStruct((bsz, h, nc, p, n), F32)],
        (xs, l, b, c), chunk=chunk, passes=1, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _scan_by_kernel(chunk: int, interpret: bool, xs, l, b, c):
    y, states = _forward(chunk, interpret, xs, l, b, c)
    count(ATTENTION_RESIDUALS, y.shape, y.dtype)
    count(ATTENTION_RESIDUALS, states.shape, states.dtype)
    return y


def _scan_fwd(chunk, interpret, xs, l, b, c):
    y, states = _forward(chunk, interpret, xs, l, b, c)
    y = keep(y, ATTENTION_RESIDUALS)
    return y, (xs, l, b, c, keep(states, ATTENTION_RESIDUALS))


def _scan_bwd(chunk, interpret, residuals, dy):
    xs, l, b, c, states = residuals
    bsz, h, t, p = xs.shape
    g, n = b.shape[1], b.shape[3]
    nc = t // chunk
    sp = _specs(chunk, nc, p, n, h // g, reverse=True)
    dx, dl, db, dc = _call(
        _bwd_kernel, "ssd_scan_bwd",
        [sp["x"], sp["l"], sp["bc"], sp["bc"], sp["s"], sp["x"]],
        [sp["x"], sp["l"], sp["bh"], sp["bh"]],
        [jax.ShapeDtypeStruct(xs.shape, F32),
         jax.ShapeDtypeStruct(l.shape, F32),
         jax.ShapeDtypeStruct((bsz, h, t, n), F32),
         jax.ShapeDtypeStruct((bsz, h, t, n), F32)],
        (xs, l, b, c, states, dy), chunk=chunk, passes=2, interpret=interpret)

    def by_group(d, like):        # a group's heads share its B and C
        return jnp.sum(d.reshape(bsz, g, h // g, t, n), axis=2).astype(
            like.dtype)

    return dx.astype(xs.dtype), dl, by_group(db, b), by_group(dc, c)


_scan_by_kernel.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(xs, logdecay, b, c, *, chunk: int, kernel: bool,
             interpret: bool = False):
    """The scan's sum over positions, D's term left out: xs = Δ·x [B, T, H,
    P] and b, c [B, T, G, N] in the products' dtype, logdecay = Δ·A [B, T, H]
    float32 -> y [B, T, H, P] float32, y_t = Σ_{s≤t} (Π_{s<k≤t} a_k)·(C_t·
    B_s)·xs_s.  ``kernel``: the Pallas kernels (``interpret`` for a CPU test
    of them), else XLA's ops; ``chunk`` divides T."""
    bsz, t, h, _ = xs.shape
    l = jnp.cumsum(logdecay.astype(F32).reshape(bsz, t // chunk, chunk, h),
                   axis=2).reshape(bsz, t, h)
    if not kernel:
        return keep(_scan_by_ops(
            *(keep(a, ATTENTION_RESIDUALS) for a in (xs, l, b, c)), chunk),
            ATTENTION_RESIDUALS)
    operands = (jnp.swapaxes(xs, 1, 2),
                jnp.transpose(l, (0, 2, 1))[:, :, None, :],
                jnp.swapaxes(b, 1, 2), jnp.swapaxes(c, 1, 2))
    y = _scan_by_kernel(chunk, interpret,
                        *(keep(a, ATTENTION_RESIDUALS) for a in operands))
    return jnp.swapaxes(y, 1, 2)


def mixer(p: dict, x, *, heads: int, head_dim: int, groups: int,
          chunk: int, eps: float, dtype):
    """The held heads' part of the mixer's output: x [B, T, h] (the block's
    normalised input) -> [B, T, h] in ``dtype``.  ``p``: ``in_proj`` [h,
    2·H·P + 2·G·N + H], ``conv`` [K, H·P + 2·G·N] and ``conv_bias``,
    ``dt_bias``, ``A_log`` and ``D`` [H], ``norm`` [H·P], ``out_proj`` [H·P,
    h]; the state's size N follows from them."""
    bsz, t, _ = x.shape
    inner = heads * head_dim
    n = (p["conv"].shape[1] - inner) // (2 * groups)
    with jax.named_scope("ssm_mixer"):
        # split, then cast: each reader converts its own part of the product
        z, xbc, dt = jnp.split(kept_mm(x, p["in_proj"], dtype),
                               [inner, 2 * inner + 2 * groups * n], axis=-1)
        with jax.named_scope("ssm_conv"):
            xbc = causal_conv(xbc.astype(F32), p["conv"], p["conv_bias"])
        xs, b, c = jnp.split(xbc, [inner, inner + groups * n], axis=-1)
        xs = xs.reshape(bsz, t, heads, head_dim)
        delta = jax.nn.softplus(dt.astype(F32) + p["dt_bias"])
        with jax.named_scope("ssd_scan"):
            y = ssd_scan(
                (xs * delta[..., None]).astype(dtype),
                delta * -jnp.exp(p["A_log"]),
                *(a.reshape(bsz, t, groups, n).astype(dtype) for a in (b, c)),
                chunk=chunk, kernel=scan_kernel(t, chunk))
        y = y + p["D"][:, None] * xs
        y = gated_norm(y.reshape(bsz, t, inner), z.astype(F32), p["norm"],
                       groups, eps)
        return kept_mm(y, p["out_proj"], dtype)
