"""Criteo-Kaggle-scale convergence ON DEVICE: 45M records/epoch, one chip.

BASELINE.json config #2 is "DeepFM on Criteo-Kaggle 45M (single TPU chip)".
The host-side study (benchmarks/convergence.py, docs/CONVERGENCE.md) proves
convergence parity at 5M records but is capped by host generation and the
host->device feed.  This runner removes the host from the loop entirely,
the TPU-idiomatic way:

* the SAME planted-teacher generative process as ``make_synthetic``
  (per-field log-uniform vocab sizes, Zipf-skewed categorical marginals,
  rank-8 teacher FM with the same parameter scales, bias calibrated to a
  ~25% base rate) is re-expressed as a pure JAX function of a PRNG key, so
  every batch is synthesized on-chip inside the compiled program
  (Zipf(a) via the standard inverse-CDF approximation
  ``ceil(u^(-1/(a-1)))``; the host generator uses exact zeta sampling — the
  skew shape matches, the tail constants differ slightly, so the teacher
  bias is re-calibrated against THIS sampler);
* one ``lax.scan`` jit step trains an entire epoch-chunk (thousands of
  optimizer steps) with zero per-step host dispatch — the wall-clock is
  on-chip time, not host round trips;
* eval streams fixed held-out keys through the bucketed streaming AUC
  (ops/auc.py, tf.metrics.auc semantics) for the student AND the teacher's
  own probabilities — the Bayes ceiling the student should approach.

Persists docs/BENCH_CONVERGENCE_DEVICE.json ({latest, runs}).

Run:  python benchmarks/convergence_device.py \
          --records-per-epoch 45000000 --epochs 3 --batch 16384 --persist
CPU smoke: JAX_PLATFORMS=cpu ... --records-per-epoch 200000 --epochs 2 \
          --batch 512 --eval-batches 8
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

V, FIELDS, NUM_NUMERIC = 117_581, 39, 13
TEACHER_K = 8
ZIPF_A = 1.2


def build_teacher(seed: int = 0):
    """Host-side one-time teacher sample — same recipe and scales as
    benchmarks/convergence.py make_synthetic (sizes/offsets/w/vt)."""
    rng = np.random.default_rng(seed)
    n_cat = FIELDS - NUM_NUMERIC
    remaining = V - NUM_NUMERIC - 1
    raw = np.exp(rng.uniform(np.log(10.0), np.log(remaining / 2.0), n_cat))
    sizes = np.maximum(2, (raw / raw.sum() * remaining).astype(np.int64))
    while sizes.sum() > remaining:
        sizes[np.argmax(sizes)] -= sizes.sum() - remaining
    offsets = NUM_NUMERIC + 1 + np.concatenate([[0], np.cumsum(sizes)[:-1]])
    w = rng.normal(0.0, 0.35, V).astype(np.float32)
    vt = (rng.normal(0.0, 1.0, (V, TEACHER_K)) * 0.35).astype(np.float32)
    return {
        "sizes": sizes.astype(np.int32),
        "offsets": offsets.astype(np.int32),
        "w": w,
        "vt": vt,
    }


def make_synth_fn(teacher, bias):
    """(key, batch) -> {feat_ids, feat_vals, label}, teacher_prob — pure JAX,
    jit/scan-safe."""
    import jax
    import jax.numpy as jnp

    sizes = jnp.asarray(teacher["sizes"])
    offsets = jnp.asarray(teacher["offsets"])
    w = jnp.asarray(teacher["w"])
    vt = jnp.asarray(teacher["vt"])
    n_cat = FIELDS - NUM_NUMERIC

    def synth(key, batch):
        k_u, k_nv, k_lab = jax.random.split(key, 3)
        # Zipf(a) per categorical field via inverse-CDF: X = ceil(u^(-1/(a-1)))
        u = jax.random.uniform(
            k_u, (batch, n_cat), minval=1e-6, maxval=1.0
        )
        x = jnp.exp(-jnp.log(u) / (ZIPF_A - 1.0))
        z = (jnp.minimum(x, 2.0**30).astype(jnp.int32) - 1) % sizes[None, :]
        cat_ids = offsets[None, :] + z
        num_ids = jnp.broadcast_to(
            jnp.arange(1, NUM_NUMERIC + 1, dtype=jnp.int32)[None],
            (batch, NUM_NUMERIC),
        )
        ids = jnp.concatenate([num_ids, cat_ids], axis=1)
        num_vals = jax.random.uniform(k_nv, (batch, NUM_NUMERIC))
        vals = jnp.concatenate(
            [num_vals, jnp.ones((batch, n_cat), jnp.float32)], axis=1
        )
        e = vt[ids] * vals[..., None]
        sv = jnp.sum(e, axis=1)
        fm2 = 0.5 * jnp.sum(
            jnp.square(sv) - jnp.sum(jnp.square(e), axis=1), axis=1
        )
        fm1 = jnp.sum(w[ids] * vals, axis=1)
        p = jax.nn.sigmoid(fm1 + fm2 + bias)
        label = (jax.random.uniform(k_lab, (batch,)) < p).astype(jnp.float32)
        return {"feat_ids": ids, "feat_vals": vals, "label": label}, p

    return synth


def calibrate_bias(teacher, batch: int = 8192, nb: int = 32) -> float:
    """Bisect the teacher bias to a ~25% positive rate under THIS sampler
    (the on-device Zipf approximation shifts marginals vs exact zeta)."""
    import jax
    import jax.numpy as jnp

    synth0 = make_synth_fn(teacher, 0.0)

    @jax.jit
    def logits_of(key):
        _, p = synth0(key, batch)   # bias 0: p = sigmoid(raw logit)
        return jnp.log(p) - jnp.log1p(-p)

    key = jax.random.PRNGKey(123)
    all_logits = np.concatenate(
        [np.asarray(logits_of(jax.random.fold_in(key, i))) for i in range(nb)]
    )
    lo, hi = -20.0, 20.0
    for _ in range(40):
        b0 = 0.5 * (lo + hi)
        if (1.0 / (1.0 + np.exp(-(all_logits + b0)))).mean() > 0.25:
            hi = b0
        else:
            lo = b0
    return 0.5 * (lo + hi)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--records-per-epoch", type=int, default=45_000_000)
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--batch", type=int, default=16384)
    p.add_argument("--eval-batches", type=int, default=32)
    p.add_argument("--lazy", action="store_true")
    p.add_argument("--seed", type=int, default=0,
                   help="student init + data-stream seed (teacher stays "
                        "seed-0 so every run shares the same planted task)")
    p.add_argument("--opt", default=None,
                   help="JSON optimizer-override dict (e.g. the winner of "
                        "convergence.py --dataset sweep); schedule horizon "
                        "is rescaled to THIS run's total steps")
    p.add_argument("--persist", action="store_true")
    args = p.parse_args()

    from deepfm_tpu.core.platform import configure_runtime

    configure_runtime()
    import jax
    import jax.numpy as jnp

    from deepfm_tpu.core.config import Config
    from deepfm_tpu.ops.auc import auc_init, auc_update, auc_value
    from deepfm_tpu.train import create_train_state, make_train_step

    platform = jax.devices()[0].platform
    t_setup = time.perf_counter()
    teacher = build_teacher(seed=0)
    bias = calibrate_bias(teacher)
    synth = make_synth_fn(teacher, bias)

    opt = {"learning_rate": 0.0005,
           "lazy_embedding_updates": bool(args.lazy)}
    if args.opt:
        import _bench_util as bu

        total_steps = max(1, args.records_per_epoch // args.batch) * args.epochs
        opt.update(bu.rescale_schedule(json.loads(args.opt), total_steps))
    cfg = Config.from_dict({
        "model": {
            "feature_size": V, "field_size": FIELDS, "embedding_size": 32,
            "deep_layers": (128, 64, 32), "dropout_keep": (0.5, 0.5, 0.5),
        },
        "optimizer": opt,
        "data": {"batch_size": args.batch},
    })
    import jax.random as jrandom

    state = create_train_state(
        cfg, key=jrandom.PRNGKey(1000 + args.seed)
    )
    train_step = make_train_step(cfg)

    steps_per_epoch = max(1, args.records_per_epoch // args.batch)
    data_key = jax.random.PRNGKey(7 + args.seed)
    eval_key = jax.random.PRNGKey(1009)     # disjoint from training keys

    @jax.jit
    def train_epoch(state, epoch):
        def body(st, step_i):
            key = jax.random.fold_in(
                jax.random.fold_in(data_key, epoch), step_i
            )
            batch, _ = synth(key, args.batch)
            st, metrics = train_step(st, batch)
            return st, metrics["loss"]

        return jax.lax.scan(body, state, jnp.arange(steps_per_epoch))

    from deepfm_tpu.models import get_model

    model = get_model(cfg.model)

    @jax.jit
    def eval_pass(state):
        def body(carry, i):
            st_auc, t_auc, ce_sum = carry
            batch, p_teacher = synth(jax.random.fold_in(eval_key, i),
                                     args.batch)
            logits, _ = model.apply(
                state.params, state.model_state, batch["feat_ids"],
                batch["feat_vals"], cfg=cfg.model, train=False,
            )
            pred = jax.nn.sigmoid(logits)
            lab = batch["label"]
            st_auc = auc_update(st_auc, lab, pred)
            t_auc = auc_update(t_auc, lab, p_teacher)
            ce = -jnp.mean(
                lab * jnp.log(jnp.clip(pred, 1e-7, 1.0))
                + (1 - lab) * jnp.log(jnp.clip(1 - pred, 1e-7, 1.0))
            )
            return (st_auc, t_auc, ce_sum + ce), None

        (st_auc, t_auc, ce_sum), _ = jax.lax.scan(
            body, (auc_init(), auc_init(), jnp.float32(0.0)),
            jnp.arange(args.eval_batches),
        )
        return (auc_value(st_auc), auc_value(t_auc),
                ce_sum / args.eval_batches)

    setup_s = time.perf_counter() - t_setup
    epochs_out = []
    for ep in range(args.epochs):
        t0 = time.perf_counter()
        state, losses = train_epoch(state, ep)
        jax.block_until_ready(losses)
        train_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        s_auc, t_auc, ce = map(float, eval_pass(state))
        eval_s = time.perf_counter() - t0
        row = {
            "epoch": ep,
            "records": steps_per_epoch * args.batch,
            "train_secs": round(train_s, 2),
            "examples_per_sec": round(steps_per_epoch * args.batch / train_s, 1),
            "mean_loss_last_100": round(
                float(np.asarray(losses)[-100:].mean()), 5),
            "eval_auc": round(s_auc, 5),
            "teacher_bayes_auc": round(t_auc, 5),
            "auc_gap_to_bayes": round(t_auc - s_auc, 5),
            "eval_ce": round(ce, 5),
            "eval_secs": round(eval_s, 2),
        }
        epochs_out.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)

    out = {
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "batch": args.batch,
        "steps_per_epoch": steps_per_epoch,
        "variant": "lazy_adam" if args.lazy else "dense_xla",
        "seed": args.seed,
        "optimizer": {k: v for k, v in opt.items()
                      if k != "lazy_embedding_updates"},
        "teacher_bias": round(float(bias), 4),
        "setup_secs": round(setup_s, 2),
        "eval_records": args.eval_batches * args.batch,
        "epochs": epochs_out,
        "recorded_unix_time": int(time.time()),
    }
    print(json.dumps(out))
    if args.persist:
        import _bench_util as bu

        bu.persist_latest_runs(
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "docs",
                "BENCH_CONVERGENCE_DEVICE.json"),
            out, ok=len(epochs_out), platform=platform,
        )


if __name__ == "__main__":
    main()
