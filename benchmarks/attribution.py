"""On-device cost attribution for the product-path train step.

The spmd train step's cost sits inside the compiled step, not in dispatch
(ROADMAP S1).  This bench decomposes the step into nested variants, each
scanned SCAN_K times inside ONE dispatch (no host involvement between
iterations -> every per-step number is pure device time), timed as the
slope between two dispatch counts:

    fwd         forward loss only
    grad_mlp    forward + backward with table grads stopped (MLP-only bwd)
    grad_all    full backward — adds the embedding-gradient scatter-add,
                the prime suspect (319,488 non-unique row updates/step at
                batch 8192; XLA:TPU serializes those)
    step_dense  the full dense-Adam train step (train/step.py)
    step_spmd   the actual product path (parallel/spmd.py scan loop)
    step_lazy   the touched-rows lazy-Adam step

Successive differences attribute the cost: (grad_mlp - fwd) = MLP backward,
(grad_all - grad_mlp) = table-grad scatter, (step_dense - grad_all) =
optimizer update, (step_spmd - step_dense) = shard_map machinery.

Id dtype note: there is no int64 arm — JAX's default x64-disabled mode
demotes int64 ids to int32 at device_put, so ids were ALWAYS int32 on
device (tests/test_narrow_ids.py pins this); ops/embedding.py narrow_ids
makes that invariant explicit at staging rather than changing it.

Persists docs/BENCH_ATTRIBUTION.json ({latest, runs}).

Run:  python benchmarks/attribution.py --persist
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

import _bench_util as bu

V, F, K = 117_581, 39, 32
DEEP = (128, 64, 32)
SCAN_K = 16
TABLE_KEYS = ("fm_w", "fm_v")

VARIANTS = ("fwd", "grad_mlp", "grad_all", "grad_all_segsum",
            "step_dense", "step_dense_segsum", "step_spmd",
            "step_spmd_segsum", "step_lazy")


def _cfg(batch_size: int, *, lazy: bool = False, narrow: bool = True,
         table_grad: str = "scatter"):
    from deepfm_tpu.core.config import Config

    return Config.from_dict({
        "model": {
            "feature_size": V, "field_size": F, "embedding_size": K,
            "deep_layers": DEEP, "dropout_keep": (0.5, 0.5, 0.5),
            "narrow_ids": narrow, "table_grad": table_grad,
        },
        "optimizer": {"learning_rate": 0.0005,
                      "lazy_embedding_updates": lazy},
        "data": {"batch_size": batch_size},
        "mesh": {"data_parallel": 1, "model_parallel": 1},
    })


def _stacked_host_batch(batch_size: int, ids_dtype) -> dict:
    return bu.make_host_ctr_batches(
        batch_size, 1, v=V, ids_dtype=ids_dtype, lead_shape=(SCAN_K,))[0]


def _build(variant: str, batch_size: int, narrow: bool):
    """Return (dispatch_fn, state, stacked_device_batch).

    dispatch_fn(state, stacked) -> (state, out); ONE jit dispatch running
    SCAN_K scanned iterations."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    ids_dtype = np.int32 if narrow else np.int64
    host = _stacked_host_batch(batch_size, ids_dtype)
    table_grad = "segsum" if variant.endswith("_segsum") else "scatter"
    variant = variant.removesuffix("_segsum")

    if variant == "step_spmd":
        from deepfm_tpu.core.config import MeshConfig
        from deepfm_tpu.parallel import (
            build_mesh, create_spmd_state, make_context,
            make_spmd_train_loop, shard_batch_stacked,
        )

        cfg = _cfg(batch_size, narrow=narrow, table_grad=table_grad)
        mesh = build_mesh(MeshConfig(data_parallel=1, model_parallel=1))
        ctx = make_context(cfg, mesh)
        state = create_spmd_state(ctx)
        per_step = [
            {k: v[i] for k, v in host.items()} for i in range(SCAN_K)
        ]
        staged = shard_batch_stacked(ctx, per_step, validate_ids=False)
        return make_spmd_train_loop(ctx, SCAN_K), state, staged

    from deepfm_tpu.train import create_train_state, make_train_step

    cfg = _cfg(batch_size, lazy=(variant == "step_lazy"), narrow=narrow,
               table_grad=table_grad)
    staged = {k: jax.device_put(v) for k, v in host.items()}

    if variant in ("step_dense", "step_lazy"):
        step = make_train_step(cfg)
        state = create_train_state(cfg)

        def dispatch(state, stacked):
            return lax.scan(step, state, stacked)

        return jax.jit(dispatch, donate_argnums=(0,)), state, staged

    # fwd / grad_mlp / grad_all: loss-level variants over the same model
    from deepfm_tpu.models.base import get_model
    from deepfm_tpu.train.step import make_loss_fn

    model = get_model(cfg.model)
    loss_fn = make_loss_fn(cfg, model, None)
    state = create_train_state(cfg)

    def body(carry, batch):
        params, model_state, rng, acc = carry
        step_rng = jax.random.fold_in(rng, acc.astype(jnp.int32) % 1000)
        if variant == "fwd":
            loss, _aux = loss_fn(params, model_state, batch, step_rng, True)
            acc = acc + loss
        else:
            if variant == "grad_mlp":
                def stopped_loss(p, ms, b, r, t):
                    p = {k: (lax.stop_gradient(v) if k in TABLE_KEYS else v)
                         for k, v in p.items()}
                    return loss_fn(p, ms, b, r, t)
                g_fn = jax.grad(stopped_loss, has_aux=True)
            else:
                g_fn = jax.grad(loss_fn, has_aux=True)
            grads, _aux = g_fn(params, model_state, batch, step_rng, True)
            # fold the FULL grad tree into the carried params (scaled to
            # ~no-op) so no backward output is dead code; the extra
            # read-add-write of each grad leaf is << the backward itself
            params = jax.tree_util.tree_map(
                lambda p, g: p + 1e-30 * g.astype(p.dtype), params, grads)
            acc = acc + 0.0
        return (params, model_state, rng, acc), ()

    def dispatch(carry_state, stacked):
        carry = (carry_state.params, carry_state.model_state,
                 carry_state.rng, jnp.zeros(()))
        carry, _ = lax.scan(body, carry, stacked)
        params, model_state, rng, acc = carry
        return carry_state._replace(params=params), {"loss": acc}

    return jax.jit(dispatch, donate_argnums=(0,)), state, staged


def measure(variant: str, batch_size: int, narrow: bool,
            n_lo: int = 1, n_hi: int = 4) -> dict:
    fn, state, staged = _build(variant, batch_size, narrow)
    import jax

    state, out = fn(state, staged)          # compile + warm
    jax.block_until_ready(out)

    def timed(n):
        nonlocal state
        t0 = time.perf_counter()
        for _ in range(n):
            state, out = fn(state, staged)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    t_lo, t_hi = timed(n_lo), timed(n_hi)
    per_dispatch = (t_hi - t_lo) / (n_hi - n_lo)
    return {
        "variant": variant,
        "ids_dtype": "int32" if narrow else "int64",
        "batch_size": batch_size,
        "scan_k": SCAN_K,
        "per_step_ms": round(per_dispatch / SCAN_K * 1e3, 3),
        "per_dispatch_ms": round(per_dispatch * 1e3, 2),
        "examples_per_sec": round(
            batch_size * SCAN_K / max(per_dispatch, 1e-9), 1),
        "T": {str(n_lo): round(t_lo, 4), str(n_hi): round(t_hi, 4)},
    }


def run_point(args) -> None:
    from deepfm_tpu.core.platform import configure_runtime

    configure_runtime()
    variant, bs, dt = args.point.split(",")
    r = measure(variant, int(bs), dt == "int32")
    r["platform"], r["device_kind"] = bu.backend_platform()
    print(json.dumps(r))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=8192)
    p.add_argument("--variants", default=",".join(VARIANTS))
    p.add_argument("--ids-dtypes", default="int32")
    p.add_argument("--point", default=None)
    p.add_argument("--point-timeout", type=int, default=600)
    p.add_argument("--persist", action="store_true")
    args = p.parse_args()

    if args.point:
        run_point(args)
        return

    rows, platform, device_kind = [], None, None
    consecutive_timeouts = 0
    for variant in args.variants.split(","):
        if variant not in VARIANTS:
            p.error(f"unknown variant {variant!r}; known: {VARIANTS}")
        for dt in args.ids_dtypes.split(","):
            r = bu.run_point_subprocess(
                [sys.executable, os.path.abspath(__file__),
                 "--point", f"{variant},{args.batch},{dt}",
                 "--batch", str(args.batch)],
                args.point_timeout,
                {"variant": variant, "ids_dtype": dt},
            )
            platform, device_kind = bu.capture_platform(
                r, (platform, device_kind))
            rows.append(r)
            print(json.dumps(r), file=sys.stderr, flush=True)
            if "timeout" in str(r.get("error", "")):
                consecutive_timeouts += 1
                if consecutive_timeouts >= 2:
                    print("aborting: 2 consecutive point timeouts",
                          file=sys.stderr)
                    break
            else:
                consecutive_timeouts = 0
        else:
            continue
        break

    out = {"platform": platform, "device_kind": device_kind,
           "model": {"V": V, "F": F, "K": K, "deep": DEEP},
           "batch_size": args.batch, "scan_k": SCAN_K,
           "recorded_unix_time": int(time.time()), "rows": rows}
    print(json.dumps(out))
    if args.persist:
        bu.persist_latest_runs(
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "docs",
                "BENCH_ATTRIBUTION.json"),
            out, ok=sum(1 for r in rows if "error" not in r),
            platform=platform,
        )


if __name__ == "__main__":
    main()
