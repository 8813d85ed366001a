"""What the plain references share: the published initialisers, the loss, a
hand-written Adam and the three-step follower that the output check reads.

Plain ``jax.numpy``, float32, matmuls at ``highest`` precision.  Nothing here
imports the program (``deepfm_tpu``) or takes anything the program made: the
weights come from the seed through the published initialisers (TF1's
``glorot_normal_initializer`` for the tables, ``xavier_initializer`` for the
dense kernels), the dropout masks from the same seed, the batches from the
benchmark's own generator.

A ``Policy`` lowers the precision for the *control* (perf/control.py and
perf/tests): ``main`` is the dtype of everything the configuration states
as float32 (lookup, interaction, loss, gradients), ``mlp_fp8`` rounds every
operand of the bfloat16 tower (and CIN) through per-tensor-scaled
float8_e4m3.  The reference itself runs ``Policy()``: float32 throughout.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..check import WHOLE_LEAF_MAX

# stddev correction of a normal truncated at two sigma (TF's variance scaling)
_TRUNC = 0.87962566103423978


class Policy(NamedTuple):
    main: str = "float32"
    mlp_fp8: bool = False
    half_batch: bool = False  # planted fault: the loss's mean runs over the
    #                           first half of the rows only


class Sizes(NamedTuple):
    """The configuration's sizes, read from its JSON file (``overrides``)."""

    model_name: str
    feature_size: int
    field_size: int
    embedding_size: int
    deep_layers: tuple
    dropout_keep: tuple
    l2_reg: float
    cin_layers: tuple
    learning_rate: float
    b1: float
    b2: float
    eps: float


def sizes_from_config(config: dict) -> Sizes:
    m, o = config["overrides"]["model"], config["overrides"]["optimizer"]
    if o["name"].lower() != "adam":
        raise ValueError("the plain reference follows Adam only")
    return Sizes(
        model_name=m["model_name"],
        feature_size=int(m["feature_size"]),
        field_size=int(m["field_size"]),
        embedding_size=int(m["embedding_size"]),
        deep_layers=tuple(m["deep_layers"]),
        dropout_keep=tuple(m["dropout_keep"]),
        l2_reg=float(m["l2_reg"]),
        cin_layers=tuple(m.get("cin_layers", ())),
        learning_rate=float(o["learning_rate"]),
        b1=float(o["adam_b1"]),
        b2=float(o["adam_b2"]),
        eps=float(o["adam_eps"]),
    )


def _fans(shape):
    if len(shape) == 1:
        return float(shape[0]), float(shape[0])
    return float(shape[-2]), float(shape[-1])


def glorot_normal(key, shape):
    fan_in, fan_out = _fans(shape)
    std = (2.0 / (fan_in + fan_out)) ** 0.5 / _TRUNC
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)


def glorot_uniform(key, shape):
    fan_in, fan_out = _fans(shape)
    lim = (6.0 / (fan_in + fan_out)) ** 0.5
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim)


def init_mlp(key, in_dim: int, s: Sizes) -> dict:
    dims = [in_dim, *s.deep_layers]
    keys = jax.random.split(key, len(s.deep_layers) + 1)
    p = {}
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        p[f"layer_{i}"] = {"kernel": glorot_uniform(keys[i], (a, b)),
                           "bias": jnp.zeros((b,), jnp.float32)}
    p["out"] = {"kernel": glorot_uniform(keys[-1], (dims[-1], 1)),
                "bias": jnp.zeros((1,), jnp.float32)}
    return p


def fp8_round(x):
    """Per-tensor-scaled float8_e4m3 round trip (the usual fp8 recipe:
    scale the tensor's largest magnitude onto the format's 448)."""
    amax = jnp.max(jnp.abs(x)).astype(jnp.float32)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0).astype(x.dtype)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale


def tower_dtype(policy: Policy):
    """dtype of the tower/CIN arithmetic: float32 in the reference; the
    control keeps the configuration's bfloat16 and rounds operands to fp8."""
    return jnp.bfloat16 if policy.mlp_fp8 else jnp.float32


def mlp(p: dict, x, s: Sizes, rng, policy: Policy):
    """relu tower with TF1 keep-probability dropout; the masks are drawn as
    the configuration's seed draws them (one key per hidden layer)."""
    dt = tower_dtype(policy)
    q: Callable = fp8_round if policy.mlp_fp8 else (lambda a: a)
    h = x.astype(dt)
    n = len(s.deep_layers)
    keys = jax.random.split(rng, n)
    for i in range(n):
        lay = p[f"layer_{i}"]
        h = q(h) @ q(lay["kernel"].astype(dt)) + lay["bias"].astype(dt)
        h = jnp.where(h > 0, h, 0)  # relu, with gradient 0 at 0 (TF's)
        keep = s.dropout_keep[i]
        if keep < 1.0:
            mask = jax.random.bernoulli(keys[i], keep, h.shape)
            h = jnp.where(mask, h / keep, 0).astype(dt)
    out = p["out"]
    y = q(h) @ q(out["kernel"].astype(dt)) + out["bias"].astype(dt)
    return y[:, 0].astype(jnp.float32)


def lookup_terms(params: dict, batch: dict, s: Sizes, policy: Policy):
    """First-order term and the scaled embeddings e = V[id]·x."""
    dt = jnp.dtype(policy.main)
    ids = batch["feat_ids"].reshape(-1, s.field_size)
    vals = batch["feat_vals"].reshape(-1, s.field_size).astype(dt)
    y_w = jnp.sum(params["fm_w"].astype(dt)[ids] * vals, axis=1)
    emb = params["fm_v"].astype(dt)[ids] * vals[..., None]
    return y_w, emb


def bce_with_l2(logits, params: dict, batch: dict, s: Sizes, policy: Policy):
    """mean sigmoid cross-entropy + l2_reg·½(‖FM_W‖² + ‖FM_V‖²)."""
    dt = jnp.dtype(policy.main)
    z = logits.astype(dt)
    y = batch["label"].reshape(-1).astype(dt)
    if policy.half_batch:
        half = z.shape[0] // 2
        z, y = z[:half], y[:half]
    ce = jnp.mean(jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))
    l2 = 0.5 * (jnp.sum(jnp.square(params["fm_w"].astype(dt)))
                + jnp.sum(jnp.square(params["fm_v"].astype(dt))))
    return (ce + s.l2_reg * l2).astype(jnp.float32)


def flat_names(tree: dict, prefix: str = "") -> dict:
    """{'mlp/layer_0/kernel': leaf, ...} — the names the check compares by."""
    out = {}
    for k in sorted(tree):
        name = f"{prefix}{k}"
        if isinstance(tree[k], dict):
            out.update(flat_names(tree[k], name + "/"))
        else:
            out[name] = tree[k]
    return out


def leaf_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in flat_names(tree).items()}


@functools.lru_cache(maxsize=None)
def _programs(init_fn, loss_fn, s: Sizes, policy: Policy):
    """The jitted init, step and read-out of one (model, sizes, policy); the
    seed's keys are arguments, so every seed runs the same compiled programs
    (and finds them in the persistent cache)."""

    def step(params, m, v, t, step_key, batch, row_ids):
        rng = jax.random.fold_in(jax.random.fold_in(step_key, t), 0)
        loss, g = jax.value_and_grad(
            lambda p: loss_fn(p, batch, rng, s, policy))(params)
        t1 = (t + 1).astype(jnp.float32)
        c1, c2 = 1.0 - s.b1 ** t1, 1.0 - s.b2 ** t1

        def upd(p, g, m, v):
            g = g.astype(jnp.float32)
            m = s.b1 * m + (1.0 - s.b1) * g
            v = s.b2 * v + (1.0 - s.b2) * g * g
            p = p - s.learning_rate * (m / c1) / (jnp.sqrt(v / c2) + s.eps)
            return p, m, v

        out = jax.tree_util.tree_map(upd, params, g, m, v)
        is_triple = lambda x: isinstance(x, tuple)
        pick = lambda i: jax.tree_util.tree_map(
            lambda x: x[i], out, is_leaf=is_triple)
        whole = {k: x for k, x in flat_names(g).items()
                 if x.size < WHOLE_LEAF_MAX}
        rows = {k: x[row_ids] for k, x in flat_names(g).items()
                if x.ndim and x.shape[0] == s.feature_size}
        return pick(0), pick(1), pick(2), loss, leaf_norms(g), whole, rows

    def start(init_key):
        params = init_fn(init_key, s)
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        return params, jax.tree_util.tree_map(jnp.copy, params), zeros, zeros

    def delta(a, b):
        return leaf_norms(jax.tree_util.tree_map(lambda x, y: x - y, a, b))

    return (jax.jit(start), jax.jit(step, donate_argnums=(0, 1, 2)),
            jax.jit(delta))


def follow_steps(init_fn, loss_fn, s: Sizes, seed: int, batches: list,
                 policy: Policy = Policy()) -> dict:
    """Train ``len(batches)`` Adam steps from the seed and return what the
    check compares: each step's loss, the first gradient as per-leaf norms
    and, for the leaves under ``WHOLE_LEAF_MAX`` elements, whole, its rows
    in every table at the first batch's distinct ids, and the per-leaf norm
    of the parameters' change after the last step.

    One jitted step with donated state, so that the 10⁷-row tables, their
    moments, the initial tables and one dense gradient are all that lives on
    the chip.
    """
    start, step, delta = _programs(init_fn, loss_fn, s, policy)
    init_key, step_key = jax.random.split(jax.random.PRNGKey(seed))
    with jax.default_matmul_precision("highest"):
        params, p0, m, v = start(init_key)
        losses, grad_norm, grad, grad_rows = [], None, None, None
        ids = np.unique(batches[0]["feat_ids"])
        row_ids = np.zeros(batches[0]["feat_ids"].size, np.int32)
        row_ids[:ids.size] = ids  # one shape whatever the seed; 0 pads
        for t, b in enumerate(batches):
            dev = {"feat_ids": jnp.asarray(b["feat_ids"], jnp.int32),
                   "feat_vals": jnp.asarray(b["feat_vals"], jnp.float32),
                   "label": jnp.asarray(b["label"], jnp.float32)}
            params, m, v, loss, gn, whole, rows = step(
                params, m, v, jnp.int32(t), step_key, dev, row_ids)
            losses.append(float(loss))
            if t == 0:
                grad_norm = {k: float(x) for k, x in gn.items()}
                grad = {k: np.asarray(x, np.float32)
                        for k, x in whole.items()}
                grad_rows = {k: np.asarray(x, np.float32)[:ids.size]
                             for k, x in rows.items()}
            del whole, rows
        delta_norm = {k: float(x) for k, x in delta(params, p0).items()}
    del params, m, v, p0
    return {"loss": losses, "grad_norm": grad_norm, "grad": grad,
            "grad_rows": grad_rows, "delta_norm": delta_norm}
