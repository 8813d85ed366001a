"""Plain reference of one LFM2-MoE training step on this chip's share
(``model_type: lfm2_moe``, LiquidAI/LFM2-24B-A2B's config.json, as the
family's ``ForCausalLM`` computes it).  With h the hidden size, n(x) =
x·rsqrt(mean(x²) + eps)·g, every projection without bias:

    block l:   x ← x + op_l(n_op(x));  x ← x + ffn_l(n_ffn(x))
    conv:      [B, C, u] = split₃(W_in x);  v_t = Σ_j w[j] ⊙ (B⊙u)_{t−L+1+j},
               zeros before the sequence's start;  W_out (C ⊙ v)
    attention: q, k, v as heads of h/heads; q ← n_q(q), k ← n_k(k) per head,
               then RoPE (halves convention); a key-value head serves
               heads/kv_heads query heads; causal softmax(q·kᵀ/√d)·v; W_o
    dense ffn: W₂(silu(W₁x) ⊙ W₃x)
    experts:   r = sigmoid(W_g x); chosen = top-k(r + b); w = r[chosen] /
               (Σ r[chosen] + 1e-6)·routed_scaling_factor;
               Σ_{e ∈ chosen ∩ held} w_e·W₂ᵉ(silu(W₁ᵉx) ⊙ W₃ᵉx)
    loss:      mean over the B·(S−1) positions with a successor of the
               softmax cross-entropy of n_out(x)·Eᵀ against the next token,
               E the token table; dense Adam over every parameter.

Plain ``jax.numpy``, float32, ``highest``; nothing of the program.  Departures
from the published description, each marked ``# departure`` where it happens:
the share (experts 0…held−1 of the router's 64 and a slice of the vocabulary:
what the absent experts would add is left out), the selection bias drawn from
the seed and never updated, the tied head (the catalog row omits the key).

Computed so that it fits after the window at the published widths: sequence by
sequence (``lax.map``: the loss is a mean of per-sequence means), a
``jax.checkpoint`` a block, attention by query blocks, every held expert over
every token with the weights of the tokens that did not choose it at zero.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..check import WHOLE_LEAF_MAX
from . import _common as c

TABLE = "tok_embedding"
QUERY_BLOCK = 512


class Sizes(NamedTuple):
    vocab: int
    seq: int
    hidden: int
    layer_types: tuple
    dense_layers: int
    dense_width: int
    expert_width: int
    experts: int
    held: int
    top_k: int
    norm_topk: bool
    use_bias: bool
    scale: float
    heads: int
    kv_heads: int
    taps: int
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
        hidden=int(m["embedding_size"]), layer_types=tuple(m["layer_types"]),
        dense_layers=int(m["num_dense_layers"]),
        dense_width=int(m["intermediate_size"]),
        expert_width=int(m["moe_intermediate_size"]),
        experts=int(m["num_experts"]),
        held=int(m.get("experts_held") or m["num_experts"]),
        top_k=int(m["num_experts_per_tok"]),
        norm_topk=bool(m.get("norm_topk_prob", True)),
        use_bias=bool(m.get("use_expert_bias", True)),
        scale=float(m.get("routed_scaling_factor", 1.0)),
        heads=int(m["num_attention_heads"]),
        kv_heads=int(m["num_key_value_heads"]),
        taps=int(m.get("conv_L_cache", 3)), eps=float(m.get("norm_eps", 1e-5)),
        theta=float(m.get("rope_theta", 1e6)),
        learning_rate=float(o["learning_rate"]), b1=float(o["adam_b1"]),
        b2=float(o["adam_b2"]), adam_eps=float(o["adam_eps"]),
    )


def init(key, s: Sizes) -> tuple:
    """(parameters, selection biases) from the seed: normal σ 0.02 for every
    matrix and the table, ones for the norm gains.  One key for the table,
    then one a layer, split nine ways: the operator's four matrices (a conv
    operator uses the first, second and fourth), the feed-forward's three,
    the router, the bias."""
    def normal(k, shape, std=0.02):
        return std * jax.random.normal(k, shape, jnp.float32)

    h, d = s.hidden, s.hidden // s.heads
    keys = jax.random.split(key, len(s.layer_types) + 1)
    params = {TABLE: normal(keys[0], (s.vocab, h)),
              "out_norm": jnp.ones((h,), jnp.float32)}
    bias = {}
    for l, kind in enumerate(s.layer_types):
        k = jax.random.split(keys[l + 1], 9)
        p = {"op_norm": jnp.ones((h,), jnp.float32),
             "ffn_norm": jnp.ones((h,), jnp.float32)}
        if kind == "conv":
            p["conv"] = {"in_proj": normal(k[0], (h, 3 * h)),
                         "conv": normal(k[1], (s.taps, h)),
                         "out_proj": normal(k[3], (h, h))}
        else:
            kv = s.kv_heads * d
            p["attention"] = {"q_proj": normal(k[0], (h, h)),
                              "k_proj": normal(k[1], (h, kv)),
                              "v_proj": normal(k[2], (h, kv)),
                              "o_proj": normal(k[3], (h, h)),
                              "q_norm": jnp.ones((d,), jnp.float32),
                              "k_norm": jnp.ones((d,), jnp.float32)}
        if l < s.dense_layers:
            p["dense_ffn"] = {"w1": normal(k[4], (h, s.dense_width)),
                              "w3": normal(k[5], (h, s.dense_width)),
                              "w2": normal(k[6], (s.dense_width, h))}
        else:
            # departure: only the held experts exist here
            p["experts"] = {"w1": normal(k[4], (s.held, h, s.expert_width)),
                            "w3": normal(k[5], (s.held, h, s.expert_width)),
                            "w2": normal(k[6], (s.held, s.expert_width, h))}
            p["router"] = {"gate": normal(k[7], (h, s.experts))}
            if s.use_bias:
                # departure: no published update rule; drawn once, frozen
                bias[l] = normal(k[8], (s.experts,), 0.01)
        params[f"layer_{l}"] = p
    return params, bias


class ExpertFp8(NamedTuple):
    """A control of this family's own beside ``Policy``'s three (which
    ``perf/control.py`` drives): the fp8 half in the EXPERTS' products alone,
    everything else as the reference has it.  The expert leaves are read by
    norm only, so which number catches such a product needs a reading of its
    own (``scripts/expert_fp8_control.py``; PERF.md §2)."""
    main: str = "float32"
    mlp_fp8: bool = True
    half_batch: bool = False
    experts_only: bool = True


def _fp8(x):
    """``fp8_round`` forward, the identity backward.  Differentiated as
    written the rounding hands its cotangent back in the operand's float8
    type, unscaled: a gradient under 2⁻⁹ is flushed to zero and one over 448
    is not a number, and this model's activations' gradients are mostly the
    first.  The control lowers the products' precision, not the backward's
    range."""
    return x + jax.lax.stop_gradient(c.fp8_round(x) - x)


def _mm(x, w, policy: c.Policy):
    """A matmul the configuration states as bfloat16: float32 here; in the
    control bfloat16 with both operands through fp8."""
    dt = c.tower_dtype(policy)
    x, w = x.astype(dt), w.astype(dt)
    if policy.mlp_fp8:
        x, w = _fp8(x), _fp8(w)
    return x @ w


def _norm(x, gain, s: Sizes, dt):
    x = x.astype(dt)
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + jnp.asarray(s.eps, dt)
    ) * gain.astype(dt)


def _conv(p, x, s: Sizes, policy, dt):
    b, cg, u = jnp.split(_mm(x, p["in_proj"], policy).astype(dt), 3, axis=-1)
    bu = jnp.pad(b * u, ((s.taps - 1, 0), (0, 0)))
    n = x.shape[0]
    v = sum(p["conv"][j].astype(dt) * bu[j:j + n] for j in range(s.taps))
    return _mm(cg * v, p["out_proj"], policy)


def _rope(x, s: Sizes, dt):
    """x [S, heads, d], the whole head rotated, halves convention."""
    d = x.shape[-1]
    inv = 1.0 / (s.theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(angle).astype(dt) + turned * jnp.sin(angle).astype(dt)


def _attention(p, x, s: Sizes, policy, dt):
    n, d = x.shape[0], s.hidden // s.heads
    tower = c.tower_dtype(policy)
    q = _mm(x, p["q_proj"], policy).reshape(n, s.heads, d)
    k = _mm(x, p["k_proj"], policy).reshape(n, s.kv_heads, d)
    v = _mm(x, p["v_proj"], policy).reshape(n, s.kv_heads, d).astype(tower)
    q = _rope(_norm(q, p["q_norm"], s, dt), s, dt).astype(tower)
    k = _rope(_norm(k, p["k_norm"], s, dt), s, dt).astype(tower)
    k, v = (jnp.repeat(a, s.heads // s.kv_heads, axis=1) for a in (k, v))

    # query block by query block against all the keys, the ones ahead masked
    # (a plain square; the program pays for the triangle only)
    step = QUERY_BLOCK if n % QUERY_BLOCK == 0 else n

    @jax.checkpoint
    def block(args):
        qb, start = args
        scores = jnp.einsum("qhd,khd->hqk", qb, k).astype(dt) * (d ** -0.5)
        seen = jnp.arange(n)[None, :] <= start + jnp.arange(step)[:, None]
        prob = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", prob.astype(tower), v)

    out = jax.lax.map(block, (q.reshape(n // step, step, s.heads, d),
                              jnp.arange(0, n, step)))
    return _mm(out.reshape(n, s.hidden), p["o_proj"], policy)


def _swiglu(x, w1, w3, w2, policy, dt):
    gate = jax.nn.silu(_mm(x, w1, policy).astype(dt))
    return _mm(gate * _mm(x, w3, policy).astype(dt), w2, policy)


def _experts(p, bias, x, s: Sizes, policy, dt):
    r = jax.nn.sigmoid(x.astype(dt) @ p["router"]["gate"].astype(dt))
    _, chosen = jax.lax.top_k(r if bias is None else r + bias.astype(dt),
                              s.top_k)
    w = jnp.take_along_axis(r, chosen, axis=-1)
    if s.norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + jnp.asarray(1e-6, dt))
    w = w * jnp.asarray(s.scale, dt)
    # departure: experts held … experts−1 add nothing.  Every held expert
    # over every token, the tokens that did not choose it at weight zero
    @jax.checkpoint
    def add(y, expert):
        i, w1, w3, w2 = expert
        w_i = jnp.sum(jnp.where(chosen == i, w, 0), axis=-1, keepdims=True)
        return y + w_i * _swiglu(x, w1, w3, w2, policy, dt).astype(dt), None

    e = p["experts"]
    return jax.lax.scan(add, jnp.zeros(x.shape, dt), (
        jnp.arange(s.held), e["w1"], e["w3"], e["w2"]))[0]


def sequence_logits(params, bias, ids, s: Sizes, policy: c.Policy):
    """Logits [S, vocab] of ONE sequence ids [S]."""
    dt = jnp.dtype(policy.main)
    # the policy of everything but the experts (``ExpertFp8``)
    rest = (c.Policy(policy.main, False, policy.half_batch)
            if getattr(policy, "experts_only", False) else policy)
    x = params[TABLE].astype(dt)[ids]
    for l, kind in enumerate(s.layer_types):
        @jax.checkpoint
        def block(p, b, x, l=l, kind=kind):
            xn = _norm(x, p["op_norm"], s, dt)
            op = (_conv(p["conv"], xn, s, rest, dt) if kind == "conv"
                  else _attention(p["attention"], xn, s, rest, dt))
            x = x + op.astype(dt)
            xn = _norm(x, p["ffn_norm"], s, dt)
            if l < s.dense_layers:
                f = p["dense_ffn"]
                return x + _swiglu(xn, f["w1"], f["w3"], f["w2"], rest,
                                   dt).astype(dt)
            return x + _experts(p, b, xn, s, policy, dt)

        x = block(params[f"layer_{l}"], bias.get(l), x)
    # departure: the head is the token table (tie_embedding assumed), over
    # this chip's slice of the vocabulary
    return _mm(_norm(x, params["out_norm"], s, dt),
               params[TABLE].T, rest).astype(dt)


def sequence_loss(params, bias, ids, s: Sizes, policy: c.Policy):
    """Mean next-token cross-entropy of ONE sequence, over the S−1 positions
    that have a successor."""
    z, y = sequence_logits(params, bias, ids, s, policy)[:-1], ids[1:]
    lse = jax.nn.logsumexp(z, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(z, y[:, None], axis=-1)[:, 0])


def loss(params, bias, ids, s: Sizes, policy: c.Policy):
    """ids [B, S] -> the mean over B·(S−1) positions: sequences are of one
    length, so the mean of their means."""
    if policy.half_batch:   # planted fault: the first half of the sequences
        ids = ids[:max(1, ids.shape[0] // 2)]
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
