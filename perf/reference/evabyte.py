"""Plain reference of one EvaByte training step on this chip's share
(``model_type: evabyte``, EvaByte/EvaByte's config.json; EVA: Zheng, Yuan,
Wang, Kong, *Efficient Attention via Control Variates*, ICLR 2023, §4).  With
h the hidden size, d a head's size, s = d^−½, w the window, c the chunk,
n(x) = x·rsqrt(mean(x²) + eps)·(1 + g), every projection without bias:

    block l:   x ← x + W_o·eva(n_attn(x));  x ← x + W₂(silu(W₁ n_ffn(x)) ⊙ W₃ n_ffn(x))
    q, k, v:   heads of W_q x, W_k x, W_v x (the held heads' columns); RoPE
               (halves convention, token positions) on q and k
    pooling    chunk j = tokens [jc, (j+1)c), per held head with φ, μ ∈ R^d:
               a_jm = softmax over m in chunk j of s·(k_m · φ)
               k̃_j = Σ_m a_jm k_m + μ          ṽ_j = Σ_m a_jm v_m
    eva        query t, window W(t) = ⌊t / w⌋:
               L_t = { m : ⌊m / w⌋ = W(t), m ≤ t },  R_t = { j : ⌊jc / w⌋ < W(t) }
               o_t = [ Σ_L e^{s q_t·k_m} v_m + Σ_R e^{s q_t·k̃_j} ṽ_j ]
                     ÷ [ Σ_L e^{s q_t·k_m} + Σ_R e^{s q_t·k̃_j} ]
    heads      z_p = n_out(x)·U_p,  U_p ∈ R^{h×vocabulary},  p = 0…P−1
    loss       per sequence, the mean over p of the mean over t < S−1−p of
               softmax cross-entropy(z_p[t], id[t+1+p]); the step's loss is
               the mean over sequences; dense Adam over every parameter.

Plain ``jax.numpy``, float32, ``highest``; nothing of the program.  What the
published config does not fix is marked ``# assumed`` where it happens, what
leaves the published model ``# departure`` (the configuration file lists both
under ``assumed``).

Computed so that it fits after the window at the published widths (2.5 GB of
float32 weights and as much gradient, Adam's two moments): sequence by
sequence, the layers one stacked tree under ``lax.scan`` with a
``jax.checkpoint`` a block, attention and the SwiGLU window by window
(``lax.map``): a window's queries against its own tokens and every summary,
the later windows' masked.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..check import WHOLE_LEAF_MAX
from . import _common as c
from .lfm2_moe import _mm  # a bfloat16-stated matmul: float32 here, fp8 in the control

TABLE = "byte_embedding"
HEADS = "heads"
# the per-position terms the program's step takes its mean over
# (``models/evabyte.py``): ``perf/control.py`` plants its fault there
PROGRAM_LOSSES = ("position_losses",)


class Sizes(NamedTuple):
    vocab: int
    seq: int
    hidden: int
    layers: int
    width: int
    heads: int
    held: int
    window: int
    chunk: int
    pred_heads: int
    eps: float
    theta: float
    learning_rate: float
    b1: float
    b2: float
    adam_eps: float


def sizes_from_config(config: dict) -> Sizes:
    m, o = config["overrides"]["model"], config["overrides"]["optimizer"]
    if o["name"].lower() != "adam":
        raise ValueError("the plain reference follows Adam only")
    if m.get("l2_reg", 0.0):
        raise ValueError("the plain reference has no table penalty")
    return Sizes(
        vocab=int(m["feature_size"]), seq=int(m["field_size"]),
        hidden=int(m["embedding_size"]), layers=len(m["layer_types"]),
        width=int(m["intermediate_size"]), heads=int(m["num_attention_heads"]),
        held=int(m.get("heads_held") or m["num_attention_heads"]),
        window=min(int(m["window_size"]), int(m["field_size"])),
        chunk=int(m["chunk_size"]), pred_heads=int(m["num_pred_heads"]),
        eps=float(m.get("norm_eps", 1e-5)), theta=float(m["rope_theta"]),
        learning_rate=float(o["learning_rate"]), b1=float(o["adam_b1"]),
        b2=float(o["adam_b2"]), adam_eps=float(o["adam_eps"]),
    )


def init(key, s: Sizes) -> dict:
    """Parameters from the seed.  Three keys: the table's, the heads', the
    layers' (split one a layer, each nine ways: q, k, v, o, φ, μ, W₁, W₃,
    W₂); the layers' leaves stacked [L, …]."""
    # assumed: normal σ = init_std 0.01275 for every matrix and the table
    def normal(k, shape):
        return 0.01275 * jax.random.normal(k, shape, jnp.float32)

    # assumed: φ, μ ~ N(0, 1) clipped to [−1, 1], times d^−½
    def clipped(k, shape):
        return (jnp.clip(jax.random.normal(k, shape, jnp.float32), -1.0, 1.0)
                * shape[-1] ** -0.5)

    h, d = s.hidden, s.hidden // s.heads
    # departure: only the held heads exist here (heads 0 … held−1)
    cols = s.held * d

    def layer(key):
        k = jax.random.split(key, 9)
        # the unit offset: a gain is 1 + g and g starts at 0
        return {"attn_norm": jnp.zeros((h,), jnp.float32),
                "ffn_norm": jnp.zeros((h,), jnp.float32),
                "attention": {"q_proj": normal(k[0], (h, cols)),
                              "k_proj": normal(k[1], (h, cols)),
                              "v_proj": normal(k[2], (h, cols)),
                              "o_proj": normal(k[3], (cols, h)),
                              "phi": clipped(k[4], (s.held, d)),
                              "mu": clipped(k[5], (s.held, d))},
                "dense_ffn": {"w1": normal(k[6], (h, s.width)),
                              "w3": normal(k[7], (h, s.width)),
                              "w2": normal(k[8], (s.width, h))}}

    keys = jax.random.split(key, 3)
    return {TABLE: normal(keys[0], (s.vocab, h)),
            HEADS: normal(keys[1], (h, s.pred_heads * s.vocab)),
            "out_norm": jnp.zeros((h,), jnp.float32),
            "layers": jax.vmap(layer)(jax.random.split(keys[2], s.layers))}


def _norm(x, g, s: Sizes, dt):
    x = x.astype(dt)
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + jnp.asarray(s.eps, dt)
    ) * (1 + g.astype(dt))


def _rope(x, s: Sizes, dt):
    """x [S, heads, d], the whole head rotated, halves convention, θ from the
    config, the token's own position."""
    d = x.shape[-1]
    inv = 1.0 / (s.theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(angle).astype(dt) + turned * jnp.sin(angle).astype(dt)


def _pool(k, v, phi, mu, s: Sizes, dt):
    """k, v [S, H, d] -> (k̃, ṽ) [S/c, H, d]."""
    n, heads, d = k.shape
    kc = k.astype(dt).reshape(n // s.chunk, s.chunk, heads, d)
    vc = v.astype(dt).reshape(n // s.chunk, s.chunk, heads, d)
    # assumed: the paper's chunk weights with the learned φ in the sampled
    # vector's place and no −|k|²/2 term
    a = jax.nn.softmax(
        jnp.einsum("jmhd,hd->jmh", kc, phi.astype(dt)) * (d ** -0.5), axis=1)
    # assumed: μ is added to the pooled key and not to the value; RoPE came
    # before the pooling and k̃ gets none of its own
    return (jnp.einsum("jmh,jmhd->jhd", a, kc) + mu.astype(dt),
            jnp.einsum("jmh,jmhd->jhd", a, vc))


def _attention(p, x, s: Sizes, policy, dt):
    n, d, w = x.shape[0], s.hidden // s.heads, s.window
    tower = c.tower_dtype(policy)
    q = _mm(x, p["q_proj"], policy).reshape(n, s.held, d)
    k = _mm(x, p["k_proj"], policy).reshape(n, s.held, d)
    v = _mm(x, p["v_proj"], policy).reshape(n, s.held, d).astype(tower)
    q = _rope(q.astype(dt), s, dt).astype(tower)
    k = _rope(k.astype(dt), s, dt).astype(tower)
    kt, vt = (a.astype(tower) for a in _pool(k, v, p["phi"], p["mu"], s, dt))
    first_token = jnp.arange(kt.shape[0]) * s.chunk   # of each chunk

    # window by window: the window's queries against its own tokens (the
    # ones ahead masked) and every summary (a later or the same window's
    # masked), ONE softmax over both
    @jax.checkpoint
    def window(args):
        qw, kw, vw, index = args
        local = jnp.einsum("qhd,khd->hqk", qw, kw).astype(dt)
        far = jnp.einsum("qhd,jhd->hqj", qw, kt).astype(dt)
        at = jnp.arange(w)
        seen = jnp.concatenate([
            at[None, :] <= at[:, None],
            jnp.broadcast_to(first_token // w < index, (w, kt.shape[0]))],
            axis=1)
        scores = jnp.concatenate([local, far], axis=-1) * (d ** -0.5)
        prob = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        prob = prob.astype(tower)
        return (jnp.einsum("hqk,khd->qhd", prob[..., :w], vw)
                + jnp.einsum("hqj,jhd->qhd", prob[..., w:], vt))

    split = lambda a: a.reshape(n // w, w, s.held, d)
    out = jax.lax.map(window, (split(q), split(k), split(v),
                               jnp.arange(n // w)))
    # departure: the 24 absent heads' rows of W_o add nothing
    return _mm(out.reshape(n, s.held * d), p["o_proj"], policy)


def _swiglu(p, x, s: Sizes, policy, dt):
    @jax.checkpoint
    def rows(x):
        gate = jax.nn.silu(_mm(x, p["w1"], policy).astype(dt))
        return _mm(gate * _mm(x, p["w3"], policy).astype(dt), p["w2"], policy)

    return jax.lax.map(rows, x.reshape(-1, s.window, s.hidden)).reshape(x.shape)


def sequence_logits(params, ids, s: Sizes, policy: c.Policy):
    """Logits [S, P, vocab] of ONE sequence ids [S]."""
    dt = jnp.dtype(policy.main)

    @jax.checkpoint
    def block(x, p):
        x = x + _attention(p["attention"], _norm(x, p["attn_norm"], s, dt),
                           s, policy, dt).astype(dt)
        x = x + _swiglu(p["dense_ffn"], _norm(x, p["ffn_norm"], s, dt),
                        s, policy, dt).astype(dt)
        return x, None

    x, _ = jax.lax.scan(block, params[TABLE].astype(dt)[ids],
                        params["layers"])
    z = _mm(_norm(x, params["out_norm"], s, dt), params[HEADS], policy)
    return z.astype(dt).reshape(s.seq, s.pred_heads, s.vocab)


def sequence_loss(params, ids, s: Sizes, policy: c.Policy):
    """ONE sequence: the mean over the heads of head p's mean cross-entropy
    against byte t+1+p, over the S−1−p positions that have one.
    # assumed: the heads weigh the same; no document-boundary mask."""
    z = sequence_logits(params, ids, s, policy)
    n = s.seq
    # planted fault: the first half of the positions only, each head's sum
    # over them against the half of its count (what ``perf/control.py``'s
    # slice of the program's per-position terms leaves)
    stop = n // 2 if policy.half_batch else n
    total = 0.0
    for p in range(s.pred_heads):
        scored = min(n - 1 - p, stop)
        zp, y = z[:scored, p], ids[1 + p:1 + p + scored]
        ce = (jax.nn.logsumexp(zp, axis=-1)
              - jnp.take_along_axis(zp, y[:, None], axis=-1)[:, 0])
        total = total + jnp.sum(ce) / ((n - 1 - p) * (stop / n))
    return total / s.pred_heads


def loss(params, ids, s: Sizes, policy: c.Policy):
    """ids [B, S] -> the mean over the sequences."""
    per_seq = jax.lax.map(
        lambda one: sequence_loss(params, one, s, policy), ids)
    return jnp.mean(per_seq).astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def _programs(s: Sizes, policy: c.Policy):
    def step(params, m, v, t, ids, row_ids):
        value, g = jax.value_and_grad(
            lambda p: loss(p, ids, s, policy))(params)
        t1 = (t + 1).astype(jnp.float32)
        c1, c2 = 1.0 - s.b1 ** t1, 1.0 - s.b2 ** t1

        # assumed: Adam 1e-4 / 0.9 / 0.95 / 1e-8, no weight decay
        def upd(p, g, m, v):
            g = g.astype(jnp.float32)
            m = s.b1 * m + (1.0 - s.b1) * g
            v = s.b2 * v + (1.0 - s.b2) * g * g
            p = p - s.learning_rate * (m / c1) / (jnp.sqrt(v / c2) + s.adam_eps)
            return p, m, v

        out = jax.tree_util.tree_map(upd, params, g, m, v)
        pick = lambda i: jax.tree_util.tree_map(
            lambda x: x[i], out, is_leaf=lambda x: isinstance(x, tuple))
        named = c.flat_names(g)
        whole = {k: x for k, x in named.items() if x.size < WHOLE_LEAF_MAX}
        return (pick(0), pick(1), pick(2), value, c.leaf_norms(g), whole,
                {TABLE: named[TABLE][row_ids]})

    def start(init_key):
        params = init(init_key, s)
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        return params, zeros, zeros

    def delta(params, init_key):
        # the initial parameters once more from the seed: no copy is kept
        return c.leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a - b, params, init(init_key, s)))

    return (jax.jit(start), jax.jit(step, donate_argnums=(0, 1, 2)),
            jax.jit(delta))


def follow(config: dict, seed: int, batches: list,
           policy: c.Policy = c.Policy()) -> dict:
    """Train ``len(batches)`` Adam steps from the seed and return what the
    check compares (``_common.follow_steps``' contract): each step's loss, the
    first gradient as per-leaf norms and, under ``WHOLE_LEAF_MAX`` elements,
    whole, its rows in the byte table at the first batch's distinct ids, and
    the per-leaf norm of the parameters' change after the last step."""
    s = sizes_from_config(config)
    start, step, delta = _programs(s, policy)
    init_key, _ = jax.random.split(jax.random.PRNGKey(seed))
    with jax.default_matmul_precision("highest"):
        params, m, v = start(init_key)
        ids0 = np.unique(batches[0]["feat_ids"])
        row_ids = np.zeros(batches[0]["feat_ids"].size, np.int32)
        row_ids[:ids0.size] = ids0  # one shape whatever the seed; 0 pads
        losses = []
        for t, b in enumerate(batches):
            params, m, v, value, gn, whole, rows = step(
                params, m, v, jnp.int32(t),
                jnp.asarray(b["feat_ids"], jnp.int32), row_ids)
            losses.append(float(value))
            if t == 0:
                grad_norm = {k: float(x) for k, x in gn.items()}
                grad = {k: np.asarray(x, np.float32) for k, x in whole.items()}
                grad_rows = {k: np.asarray(x, np.float32)[:ids0.size]
                             for k, x in rows.items()}
            del whole, rows
        del m, v
        delta_norm = {k: float(x) for k, x in delta(params, init_key).items()}
    del params
    return {"loss": losses, "grad_norm": grad_norm, "grad": grad,
            "grad_rows": grad_rows, "delta_norm": delta_norm}
