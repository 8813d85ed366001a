"""Plain reference of one Keye-VL-2.0 (``model_type: KeyeVL2``) training step
on this chip's share: Kwai-Keye/Keye-VL-2.0-30B-A3B's config.json, its
attention over indexer-selected keys as DeepSeek-Sparse-Attention defines it
(DeepSeek-V3.2-Exp technical report, DeepSeek-AI 2025: the lightning
indexer's score, the token-level top-k, the sparse-training stage's
objective).  With h the hidden size, d a head's size (its own key), J index
heads of size e, K = topk, n(x) = x·rsqrt(mean(x²) + eps)·g, every projection
without bias:

    block:     x ← x + attn(n₁(x));  x ← x + moe(n₂(x))
    q, k, v:   q = W_q x [heads × d], k = W_k x, v = W_v x [kv_heads × d];
               q ← n_q(q), k ← n_k(k) per head, then RoPE (halves convention);
               head j reads kv head ⌊j / (heads / kv_heads)⌋
    indexer:   x̄ = stop_gradient(n₁(x));  qᴵ_{t,j} = (Wᴵ_q x̄_t)_j ∈ R^e;
               kᴵ_s = Wᴵ_k x̄_s ∈ R^e;  w_t = Wᴵ_w x̄_t ∈ R^J;  the same RoPE
               over e on qᴵ, kᴵ
               I_{t,s} = J^-½·e^-½·Σ_j w_{t,j}·relu(qᴵ_{t,j}·kᴵ_s),   s ≤ t
    selection: S_t = the min(t+1, K) keys s ≤ t of largest I_{t,s}; of equal
               scores the lower s first
    attention: o_{t,j} = Σ_{s∈S_t} softmax_{s∈S_t}(q_{t,j}·k_s/√d)·v_s;  W_o
    moe:       r = softmax(W_g x) over all experts; chosen = top-k(r);
               w = r[chosen]/Σ r[chosen];  Σ_{e ∈ chosen ∩ held} w_e·W₂ᵉ(silu(W₁ᵉx) ⊙ W₃ᵉx)
    L_LM:      mean over the S−1 positions with a successor of the softmax
               cross-entropy of n_out(x)·W_head (untied) against the next token
    L_I:       p_{t,s} = stop_gradient(mean over the heads of the softmax
               above), s ∈ S_t;  mean over layers and t of
               Σ_{s∈S_t} p_{t,s}·(log p_{t,s} − log softmax_{s∈S_t}(I_{t,s}))
    step:      dense Adam on L_LM + L_I over every parameter.

Plain ``jax.numpy``, float32, ``highest``; nothing of the program.  What the
published config does not fix is marked ``# assumed`` where it happens, what
leaves the published model ``# departure`` (the configuration file lists both).

Computed so that it fits after the window at the published widths: sequence
by sequence (``lax.map``), a ``jax.checkpoint`` a block, attention and the
indexer by query blocks against ALL the keys, the ones ahead masked (a full
[S, S] score a head, ``QUERY_BLOCK`` rows of it at a time), every held expert
over every token with the weights of the tokens that did not choose it at
zero.
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
QUERY_BLOCK = 256
# the per-position terms the program's step takes its mean over
# (``models/keye_vl2.py`` calls the byte family's with its one head):
# ``perf/control.py`` plants its fault there
PROGRAM_LOSSES = ("position_losses",)


class Sizes(NamedTuple):
    vocab: int
    seq: int
    hidden: int
    layers: int
    expert_width: int
    experts: int
    held: int
    top_k: int
    norm_topk: bool
    heads: int
    kv_heads: int
    head_dim: int
    index_heads: int
    index_dim: int
    index_topk: int
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
    if m.get("router_score") != "softmax" or m.get("use_expert_bias", True):
        raise ValueError("the plain reference routes by a softmax, no bias")
    return Sizes(
        vocab=int(m["feature_size"]), seq=int(m["field_size"]),
        hidden=int(m["embedding_size"]), layers=len(m["layer_types"]),
        expert_width=int(m["moe_intermediate_size"]),
        experts=int(m["num_experts"]),
        held=int(m.get("experts_held") or m["num_experts"]),
        top_k=int(m["num_experts_per_tok"]),
        norm_topk=bool(m.get("norm_topk_prob", True)),
        heads=int(m["num_attention_heads"]),
        kv_heads=int(m["num_key_value_heads"]), head_dim=int(m["head_dim"]),
        index_heads=int(m["index_n_heads"]), index_dim=int(m["index_head_dim"]),
        index_topk=int(m["index_topk"]),
        eps=float(m["norm_eps"]), theta=float(m["rope_theta"]),
        learning_rate=float(o["learning_rate"]), b1=float(o["adam_b1"]),
        b2=float(o["adam_b2"]), adam_eps=float(o["adam_eps"]),
    )


def init(key, s: Sizes) -> dict:
    """Parameters from the seed.  # assumed: normal σ 0.02 for every matrix
    and the table (the family's initializer_range), ones for the norm gains.
    One key for the table, one for the head, then one a layer, split eleven
    ways: q, k, v, o, the experts' three, the router, the indexer's q, k, w."""
    def normal(k, shape):
        return 0.02 * jax.random.normal(k, shape, jnp.float32)

    h, d = s.hidden, s.head_dim
    keys = jax.random.split(key, s.layers + 2)
    # departure: every eighth token of the vocabulary (this shard's slice)
    params = {TABLE: normal(keys[0], (s.vocab, h)),
              HEAD: normal(keys[1], (h, s.vocab)),
              "out_norm": jnp.ones((h,), jnp.float32)}
    for l in range(s.layers):
        k = jax.random.split(keys[l + 2], 11)
        params[f"layer_{l}"] = {
            "op_norm": jnp.ones((h,), jnp.float32),
            "ffn_norm": jnp.ones((h,), jnp.float32),
            "attention": {"q_proj": normal(k[0], (h, s.heads * d)),
                          "k_proj": normal(k[1], (h, s.kv_heads * d)),
                          "v_proj": normal(k[2], (h, s.kv_heads * d)),
                          "o_proj": normal(k[3], (s.heads * d, h)),
                          # assumed: per-head RMSNorm on q and k (Qwen3-MoE)
                          "q_norm": jnp.ones((d,), jnp.float32),
                          "k_norm": jnp.ones((d,), jnp.float32)},
            # departure: only the held experts exist here
            "experts": {"w1": normal(k[4], (s.held, h, s.expert_width)),
                        "w3": normal(k[5], (s.held, h, s.expert_width)),
                        "w2": normal(k[6], (s.held, s.expert_width, h))},
            "router": {"gate": normal(k[7], (h, s.experts))},
            "indexer": {
                "q_proj": normal(k[8], (h, s.index_heads * s.index_dim)),
                "k_proj": normal(k[9], (h, s.index_dim)),
                "w_proj": normal(k[10], (h, s.index_heads))},
        }
    return params


def _norm(x, gain, s: Sizes, dt):
    x = x.astype(dt)
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + jnp.asarray(s.eps, dt)
    ) * gain.astype(dt)


def _rope(x, s: Sizes, dt):
    """x [S, heads, d], the whole head rotated, halves convention.
    # assumed: ``mrope_section`` with text-only positions (its three
    components equal) is this plain RoPE."""
    d = x.shape[-1]
    inv = 1.0 / (s.theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(angle).astype(dt) + turned * jnp.sin(angle).astype(dt)


def _selected(scores, seen, topk: int):
    """S_t of each row: scores [c, n], seen [c, n] (s ≤ t) -> bool [c, n].
    The K-th largest score of a row, every key above it, and of the keys AT it
    the first by position as far as K goes."""
    topk = min(topk, scores.shape[-1])
    scores = jnp.where(seen, scores, -jnp.inf)
    at = jax.lax.top_k(scores, topk)[0][:, -1:]
    above, level = scores > at, scores == at
    room = topk - jnp.sum(above, axis=-1, keepdims=True)
    return seen & (above | (level & (jnp.cumsum(level, axis=-1) <= room)))


def _attention(p, pi, x, s: Sizes, policy, dt):
    """-> (W_o·o [S, h], Σ_t of L_I's terms)."""
    n, d, g = x.shape[0], s.head_dim, s.kv_heads
    tower = c.tower_dtype(policy)
    q = _mm(x, p["q_proj"], policy).reshape(n, s.heads, d)
    k = _mm(x, p["k_proj"], policy).reshape(n, g, d)
    v = _mm(x, p["v_proj"], policy).reshape(n, g, d).astype(tower)
    q = _rope(_norm(q, p["q_norm"], s, dt), s, dt).astype(tower)
    k = _rope(_norm(k, p["k_norm"], s, dt), s, dt).astype(tower)

    # the indexer reads the block's normalised input and hands it nothing
    # back; float32 at highest as the program states it (bfloat16 in the
    # control: ``dt``).  # assumed: RoPE on qᴵ and kᴵ, no norm on kᴵ, the two
    # scale factors J^-½ and e^-½
    xi = jax.lax.stop_gradient(x).astype(dt)
    qi = _rope((xi @ pi["q_proj"].astype(dt)).reshape(
        n, s.index_heads, s.index_dim), s, dt)
    ki = _rope((xi @ pi["k_proj"].astype(dt))[:, None, :], s, dt)[:, 0]
    wi = xi @ pi["w_proj"].astype(dt)
    scale_i = jnp.asarray((s.index_heads * s.index_dim) ** -0.5, dt)

    step = QUERY_BLOCK if n % QUERY_BLOCK == 0 else n
    r = s.heads // g
    # heads first, a key-value head's query heads side by side: each block's
    # products are one matmul a key-value head, [r·step, d]·[d, n]
    k, v = jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1)         # [g, n, d]
    q = jnp.moveaxis(q.reshape(n // step, step, g, r, d), 1, 3)  # [., g, r, step, d]

    @jax.checkpoint
    def block(args):
        qb, qib, wib, start = args
        seen = jnp.arange(n)[None, :] <= start + jnp.arange(step)[:, None]
        z = (qib.reshape(step * s.index_heads, s.index_dim) @ ki.T).reshape(
            step, s.index_heads, n)
        index = (scale_i * jnp.sum(wib[:, :, None] * jax.nn.relu(z),
                                   axis=1)).astype(dt)
        live = _selected(jax.lax.stop_gradient(index), seen, s.index_topk)
        scores = jnp.einsum("gmd,gkd->gmk", qb.reshape(g, r * step, d),
                            k).astype(dt).reshape(g, r, step, n) * (d ** -0.5)
        prob = jax.nn.softmax(jnp.where(live, scores, -jnp.inf), axis=-1)
        out = jnp.einsum("gmk,gkd->gmd",
                         prob.astype(tower).reshape(g, r * step, n), v)
        target = jax.lax.stop_gradient(jnp.mean(prob, axis=(0, 1)))
        log_q = jax.nn.log_softmax(jnp.where(live, index, -jnp.inf), axis=-1)
        counted = live & (target > 0)       # 0·log 0 = 0
        kl = jnp.sum(jnp.where(
            counted,
            target * (jnp.log(jnp.where(counted, target, 1)) - log_q), 0))
        return out.reshape(g, r, step, d), kl

    out, kl = jax.lax.map(block, (
        q, qi.reshape(n // step, step, s.index_heads, s.index_dim),
        wi.reshape(n // step, step, s.index_heads),
        jnp.arange(0, n, step)))
    # [., g, r, step, d] -> [n, heads·d]
    out = jnp.moveaxis(out, 3, 1).reshape(n, s.heads * d)
    return (_mm(out, p["o_proj"], policy), jnp.sum(kl).astype(jnp.float32))


def _swiglu(x, w1, w3, w2, policy, dt):
    gate = jax.nn.silu(_mm(x, w1, policy).astype(dt))
    return _mm(gate * _mm(x, w3, policy).astype(dt), w2, policy)


def _experts(p, x, s: Sizes, policy, dt):
    r = jax.nn.softmax(x.astype(dt) @ p["router"]["gate"].astype(dt), axis=-1)
    _, chosen = jax.lax.top_k(r, s.top_k)
    w = jnp.take_along_axis(r, chosen, axis=-1)
    if s.norm_topk:     # no ε: the chosen scores of a softmax sum to > 1/E
        w = w / jnp.sum(w, axis=-1, keepdims=True)
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


def sequence_losses(params, ids, s: Sizes, policy: c.Policy):
    """(L_LM, L_I) of ONE sequence ids [S]."""
    dt = jnp.dtype(policy.main)
    x = params[TABLE].astype(dt)[ids]
    index_loss = 0.0
    for l in range(s.layers):
        @jax.checkpoint
        def block(p, x):
            xn = _norm(x, p["op_norm"], s, dt)
            y, kl = _attention(p["attention"], p["indexer"], xn, s, policy, dt)
            x = x + y.astype(dt)
            xn = _norm(x, p["ffn_norm"], s, dt)
            return x + _experts(p, xn, s, policy, dt), kl

        x, kl = block(params[f"layer_{l}"], x)
        index_loss = index_loss + kl
    # assumed: L_I's weight 1, the mean over layers and queries
    index_loss = index_loss / (s.layers * s.seq)
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
    return jnp.sum(ce) / ((n - 1) * (stop / n)), index_loss


def loss(params, ids, s: Sizes, policy: c.Policy):
    """ids [B, S] -> (L_LM + L_I, L_I), the means over the sequences."""
    lm, index = jax.lax.map(
        lambda one: sequence_losses(params, one, s, policy), ids)
    index = jnp.mean(index).astype(jnp.float32)
    return jnp.mean(lm).astype(jnp.float32) + index, index


@functools.lru_cache(maxsize=None)
def _programs(s: Sizes, policy: c.Policy):
    def step(params, m, v, t, ids, row_ids):
        (value, _), g = jax.value_and_grad(
            lambda p: loss(p, ids, s, policy), has_aux=True)(params)
        t1 = (t + 1).astype(jnp.float32)
        c1, c2 = 1.0 - s.b1 ** t1, 1.0 - s.b2 ** t1

        # assumed: Adam 1e-4 / 0.9 / 0.95 / 1e-8, no weight decay, one
        # optimizer for both terms
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
    whole, its rows in the token table at the first batch's distinct ids, and
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
