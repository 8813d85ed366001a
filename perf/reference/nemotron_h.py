"""Plain reference of one Nemotron-H (``model_type: nemotron_h``) training
step on this chip's share: nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16's
config.json, its Mamba-2 mixer as mamba_ssm's ``Mamba2`` defines it (Dao &
Gu, "Transformers are SSMs", ICML 2024), its LatentMoE and its attention.
With h the hidden size, n(x) = x·rsqrt(mean(x²) + eps)·g, every projection
without bias:

    layer l:   x ← x + f_l(n_l(x)), f_l by the pattern's character l
    M:         [z | xBC | dt] = W_in x;  xBC_t = silu(Σ_j w_j ⊙ xBC_{t−3+j}
               + b), zeros before position 0;  x, B, C = split(xBC);
               Δ = softplus(dt + dt_bias);  A = −exp(A_log)
               S_t = exp(Δ_t·A)·S_{t−1} + Δ_t·x_t ⊗ B_t  (a head's B, C its
               group's), S_{−1} = 0;  y_t = S_t·C_t + D·x_t
               W_out·n_group(y ⊙ silu(z)), an RMSNorm over each group's channels
    E:         r = sigmoid(W_g x); chosen = top-k(r + b); w = r[chosen] /
               (Σ r[chosen] + 1e-6)·scale;  u = W_down x;
               W_up Σ_{e ∈ chosen ∩ held} w_e·W₂ᵉ relu(W₁ᵉu)² + W_s2 relu(W_s1 x)²
    *:         causal softmax(q·kᵀ/√d)·v over heads of W_q x, W_k x, W_v x, a
               key-value head serving heads/kv_heads query heads, no rotary
               embedding; W_o
    loss:      mean over the S−1 positions with a successor of the softmax
               cross-entropy of n_out(x)·W_head (untied) against the next
               token; dense Adam over every parameter.

Plain ``jax.numpy``, float32, ``highest``; nothing of the program.  The scan
is the recurrence itself, position by position (no chunked algebra), each
128 positions a ``jax.checkpoint`` so that its gradient fits at 8,192.  What
the published config does not fix is marked ``# assumed`` where it happens,
what leaves the published model ``# departure`` (the configuration file
lists both).  Computed so that it fits after the window: sequence by
sequence (``lax.map``), a ``jax.checkpoint`` a block, attention by query
blocks against all the keys, every held expert over every token with the
weights of the tokens that did not choose it at zero.
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

TABLE = "tok_embedding"
HEAD = "lm_head"
QUERY_BLOCK = 512
SCAN_SEGMENT = 128      # positions a checkpoint of the recurrence
# the per-position terms the program's step takes its mean over
# (``models/nemotron_h.py`` calls the byte family's with its one head):
# ``perf/control.py`` plants its fault there
PROGRAM_LOSSES = ("position_losses",)


class Sizes(NamedTuple):
    vocab: int
    seq: int
    hidden: int
    pattern: str
    heads: int            # the mixer's, held here
    head_dim: int
    groups: int           # held here
    state: int
    taps: int
    expert_width: int
    latent: int
    shared: int
    experts: int
    held: int
    top_k: int
    scale: float
    attn_heads: int
    kv_heads: int
    attn_dim: int
    eps: float
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
    if (m.get("router_score", "sigmoid") != "sigmoid"
            or not m.get("use_expert_bias", True)
            or not m.get("norm_topk_prob", True)):
        raise ValueError("the plain reference routes by a renormalised "
                         "sigmoid with a selection bias")
    return Sizes(
        vocab=int(m["feature_size"]), seq=int(m["field_size"]),
        hidden=int(m["embedding_size"]),
        pattern=m["hybrid_override_pattern"],
        heads=int(m["mamba_num_heads"]), head_dim=int(m["mamba_head_dim"]),
        groups=int(m["n_groups"]),
        state=int(m["ssm_state_size"]), taps=int(m["conv_L_cache"]),
        expert_width=int(m["moe_intermediate_size"]),
        latent=int(m["moe_latent_size"]),
        shared=int(m["moe_shared_expert_intermediate_size"]),
        experts=int(m["num_experts"]),
        held=int(m.get("experts_held") or m["num_experts"]),
        top_k=int(m["num_experts_per_tok"]),
        scale=float(m["routed_scaling_factor"]),
        attn_heads=int(m["num_attention_heads"]),
        kv_heads=int(m["num_key_value_heads"]), attn_dim=int(m["head_dim"]),
        eps=float(m["norm_eps"]),
        learning_rate=float(o["learning_rate"]), b1=float(o["adam_b1"]),
        b2=float(o["adam_b2"]), adam_eps=float(o["adam_eps"]),
    )


def init(key, s: Sizes) -> tuple:
    """(parameters, selection biases) from the seed.  # assumed: normal σ
    0.02 for every matrix, the table and the head; ones for the norm gains
    and D; A_log = log U[1, 16]; dt_bias = softplus⁻¹ of Δ drawn log-uniform
    in the published [time_step_min, time_step_max] = [0.001, 0.1], floored
    at time_step_floor 1e-4; the conv's taps and bias torch's
    default U(±1/√taps); the selection bias normal σ 0.01, frozen.  One key
    for the table, one for the head, then one a layer, split eight ways: a
    mixer's in_proj, conv taps, conv bias, A, Δ, out_proj; an expert layer's
    router, W_down, W_up, the experts' W₁ and W₂, the shared expert's two,
    the bias; attention's q, k, v, o."""
    def normal(k, shape, std=0.02):
        return std * jax.random.normal(k, shape, jnp.float32)

    h = s.hidden
    keys = jax.random.split(key, len(s.pattern) + 2)
    # departure: every eighth token of the vocabulary (this shard's slice)
    params = {TABLE: normal(keys[0], (s.vocab, h)),
              HEAD: normal(keys[1], (h, s.vocab)),
              "out_norm": jnp.ones((h,), jnp.float32)}
    bias = {}
    for l, kind in enumerate(s.pattern):
        k = jax.random.split(keys[l + 2], 8)
        p = {"norm": jnp.ones((h,), jnp.float32)}
        if kind == "M":
            # departure: only the held heads and their groups exist here
            inner = s.heads * s.head_dim
            conv = inner + 2 * s.groups * s.state
            bound = s.taps ** -0.5
            u = jax.random.uniform(k[4], (s.heads,), jnp.float32)
            delta = jnp.exp(u * (jnp.log(0.1) - jnp.log(0.001))
                            + jnp.log(0.001))
            delta = jnp.maximum(delta, 1e-4)
            p["mixer"] = {
                "in_proj": normal(k[0], (h, inner + conv + s.heads)),
                "conv": jax.random.uniform(k[1], (s.taps, conv), jnp.float32,
                                           -bound, bound),
                "conv_bias": jax.random.uniform(k[2], (conv,), jnp.float32,
                                                -bound, bound),
                "A_log": jnp.log(jax.random.uniform(
                    k[3], (s.heads,), jnp.float32, 1.0, 16.0)),
                "dt_bias": delta + jnp.log(-jnp.expm1(-delta)),
                "D": jnp.ones((s.heads,), jnp.float32),
                "norm": jnp.ones((inner,), jnp.float32),
                "out_proj": normal(k[5], (inner, h)),
            }
        elif kind == "E":
            p["router"] = {"gate": normal(k[0], (h, s.experts))}
            p["latent"] = {"down": normal(k[1], (h, s.latent)),
                           "up": normal(k[2], (s.latent, h))}
            # departure: only the held experts exist here
            p["experts"] = {
                "w1": normal(k[3], (s.held, s.latent, s.expert_width)),
                "w2": normal(k[4], (s.held, s.expert_width, s.latent))}
            p["shared_expert"] = {"w1": normal(k[5], (h, s.shared)),
                                  "w2": normal(k[6], (s.shared, h))}
            # assumed: no published update rule; drawn once, frozen
            bias[l] = normal(k[7], (s.experts,), 0.01)
        else:
            d = s.attn_dim
            p["attention"] = {
                "q_proj": normal(k[0], (h, s.attn_heads * d)),
                "k_proj": normal(k[1], (h, s.kv_heads * d)),
                "v_proj": normal(k[2], (h, s.kv_heads * d)),
                "o_proj": normal(k[3], (s.attn_heads * d, h))}
        params[f"layer_{l}"] = p
    return params, bias


def _norm(x, gain, s: Sizes, dt):
    x = x.astype(dt)
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + jnp.asarray(s.eps, dt)
    ) * gain.astype(dt)


def recurrence(x, delta, a, b, c_, dt):
    """The state-space scan position by position: x [T, H, P], delta [T, H],
    a [H] (= A, negative), b and c_ [T, H, N] (each head's group's) -> y [T,
    H, P], D's term left out; the state [H, P, N] in ``dt``, a
    ``jax.checkpoint`` every ``SCAN_SEGMENT`` positions."""
    t, h, p = x.shape
    n = b.shape[-1]
    a = a.astype(dt)

    def step(state, at):
        x_t, d_t, b_t, c_t = at
        state = (jnp.exp(d_t * a)[:, None, None] * state
                 + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    @jax.checkpoint
    def segment(state, at):
        return jax.lax.scan(step, state, at)

    seg = SCAN_SEGMENT if t % SCAN_SEGMENT == 0 else t
    at = tuple(v.astype(dt).reshape(t // seg, seg, *v.shape[1:])
               for v in (x, delta, b, c_))
    _, y = jax.lax.scan(segment, jnp.zeros((h, p, n), dt), at)
    return y.reshape(t, h, p)


def _mixer(p, x, s: Sizes, policy, dt):
    t = x.shape[0]
    inner, bc = s.heads * s.head_dim, s.groups * s.state
    proj = _mm(x, p["in_proj"], policy).astype(dt)
    z, xbc, dtr = proj[:, :inner], proj[:, inner:2 * inner + 2 * bc], proj[
        :, 2 * inner + 2 * bc:]
    padded = jnp.pad(xbc, ((s.taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(p["conv"][j].astype(dt) * padded[j:j + t]
                          for j in range(s.taps)) + p["conv_bias"].astype(dt))
    xs = xbc[:, :inner].reshape(t, s.heads, s.head_dim)
    per = s.heads // s.groups

    def by_head(v):      # [T, G·N] -> [T, H, N]: head j reads group j // per
        return jnp.repeat(v.reshape(t, s.groups, s.state), per, axis=1)

    b = by_head(xbc[:, inner:inner + bc])
    c_ = by_head(xbc[:, inner + bc:])
    delta = jax.nn.softplus(dtr + p["dt_bias"].astype(dt))
    a = -jnp.exp(p["A_log"].astype(dt))
    y = recurrence(xs, delta, a, b, c_, dt) + p["D"].astype(dt)[:, None] * xs
    y = y.reshape(t, inner) * jax.nn.silu(z)
    y = y.reshape(t, s.groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + jnp.asarray(s.eps, dt))
    y = y.reshape(t, inner) * p["norm"].astype(dt)
    return _mm(y, p["out_proj"], policy)


def _relu2(x, w1, w2, policy, dt):
    return _mm(jnp.square(jax.nn.relu(_mm(x, w1, policy).astype(dt))), w2,
               policy)


def _latent_experts(p, bias, x, s: Sizes, policy, dt):
    # assumed: the router reads the block's normalised input, not the latent
    r = jax.nn.sigmoid(x.astype(dt) @ p["router"]["gate"].astype(dt))
    _, chosen = jax.lax.top_k(r + bias.astype(dt), s.top_k)
    w = jnp.take_along_axis(r, chosen, axis=-1)
    # assumed: ε 1e-6 (the family's code has 1e-20)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + jnp.asarray(1e-6, dt))
    w = w * jnp.asarray(s.scale, dt)
    u = _mm(x, p["latent"]["down"], policy).astype(dt)

    # departure: experts held … experts−1 add nothing.  Every held expert
    # over every token, the tokens that did not choose it at weight zero
    @jax.checkpoint
    def add(y, expert):
        i, w1, w2 = expert
        w_i = jnp.sum(jnp.where(chosen == i, w, 0), axis=-1, keepdims=True)
        return y + w_i * _relu2(u, w1, w2, policy, dt).astype(dt), None

    e = p["experts"]
    routed = jax.lax.scan(add, jnp.zeros(u.shape, dt), (
        jnp.arange(s.held), e["w1"], e["w2"]))[0]
    routed = _mm(routed, p["latent"]["up"], policy).astype(dt)
    f = p["shared_expert"]
    return routed + _relu2(x, f["w1"], f["w2"], policy, dt).astype(dt)


def _attention(p, x, s: Sizes, policy, dt):
    n, d, g = x.shape[0], s.attn_dim, s.kv_heads
    tower = c.tower_dtype(policy)
    # assumed: no rotary embedding (the family's attention applies none)
    q = _mm(x, p["q_proj"], policy).reshape(n, s.attn_heads, d).astype(tower)
    k = _mm(x, p["k_proj"], policy).reshape(n, g, d).astype(tower)
    v = _mm(x, p["v_proj"], policy).reshape(n, g, d).astype(tower)
    k, v = (jnp.repeat(a, s.attn_heads // g, axis=1) for a in (k, v))
    step = QUERY_BLOCK if n % QUERY_BLOCK == 0 else n

    @jax.checkpoint
    def block(args):
        qb, start = args
        scores = jnp.einsum("qhd,khd->hqk", qb, k).astype(dt) * (d ** -0.5)
        seen = jnp.arange(n)[None, :] <= start + jnp.arange(step)[:, None]
        prob = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", prob.astype(tower), v)

    out = jax.lax.map(block, (q.reshape(n // step, step, s.attn_heads, d),
                              jnp.arange(0, n, step)))
    return _mm(out.reshape(n, s.attn_heads * d), p["o_proj"], policy)


def sequence_loss(params, bias, ids, s: Sizes, policy: c.Policy):
    """Mean next-token cross-entropy of ONE sequence ids [S]."""
    dt = jnp.dtype(policy.main)
    x = params[TABLE].astype(dt)[ids]
    for l, kind in enumerate(s.pattern):
        @jax.checkpoint
        def block(p, b, x, kind=kind):
            xn = _norm(x, p["norm"], s, dt)
            if kind == "M":
                y = _mixer(p["mixer"], xn, s, policy, dt)
            elif kind == "E":
                y = _latent_experts(p, b, xn, s, policy, dt)
            else:
                y = _attention(p["attention"], xn, s, policy, dt)
            return x + y.astype(dt)

        x = block(params[f"layer_{l}"], bias.get(l), x)
    # departure: the head over this chip's slice of the vocabulary
    z = _mm(_norm(x, params["out_norm"], s, dt), params[HEAD],
            policy).astype(dt)
    n = s.seq
    # planted fault: the first half of the positions only, against the half
    # of their count (what ``perf/control.py``'s slice of the program's
    # per-position terms leaves)
    stop = n // 2 if policy.half_batch else n
    scored = min(n - 1, stop)
    zs, y = z[:scored], ids[1:1 + scored]
    ce = (jax.nn.logsumexp(zs, axis=-1)
          - jnp.take_along_axis(zs, y[:, None], axis=-1)[:, 0])
    return jnp.sum(ce) / ((n - 1) * (stop / n))


def loss(params, bias, ids, s: Sizes, policy: c.Policy):
    """ids [B, S] -> the mean over the sequences of their means."""
    per_seq = jax.lax.map(
        lambda one: sequence_loss(params, bias, one, s, policy), ids)
    return jnp.mean(per_seq).astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def _programs(s: Sizes, policy: c.Policy):
    def step(params, m, v, bias, t, ids, row_ids):
        value, g = jax.value_and_grad(
            lambda p: loss(p, bias, ids, s, policy))(params)
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
        params, bias = init(init_key, s)
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        return params, zeros, zeros, bias

    def delta(params, init_key):
        # the initial parameters once more from the seed: no copy is kept
        return c.leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a - b, params, init(init_key, s)[0]))

    return (jax.jit(start), jax.jit(step, donate_argnums=(0, 1, 2)),
            jax.jit(delta))


def follow(config: dict, seed: int, batches: list,
           policy: c.Policy = c.Policy()) -> dict:
    """Train ``len(batches)`` Adam steps from the seed and return what the
    check compares (``_common.follow_steps``' contract): each step's loss, the
    first gradient as per-leaf norms and, under ``WHOLE_LEAF_MAX`` elements,
    whole, its rows in the token table at the first batch's distinct ids, and
    the per-leaf norm of the parameters' change after the last step."""
    s = sizes_from_config(config)
    start, step, delta = _programs(s, policy)
    init_key, _ = jax.random.split(jax.random.PRNGKey(seed))
    with jax.default_matmul_precision("highest"):
        params, m, v, bias = start(init_key)
        ids0 = np.unique(batches[0]["feat_ids"])
        row_ids = np.zeros(batches[0]["feat_ids"].size, np.int32)
        row_ids[:ids0.size] = ids0  # one shape whatever the seed; 0 pads
        losses = []
        for t, b in enumerate(batches):
            params, m, v, value, gn, whole, rows = step(
                params, m, v, bias, jnp.int32(t),
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
