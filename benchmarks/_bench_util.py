"""Shared helpers for the benchmark scripts (tpu_tune / model_zoo /
convergence_device): synthetic Criteo batch staging, the warmup+timed step
loop, the per-point subprocess driver, and the single {latest, runs}
persist policy — one place to fix, three consumers.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

V_FLAGSHIP = 117_581


def make_host_ctr_batches(batch_size: int, nb: int = 4, *,
                          v: int = V_FLAGSHIP, seed: int = 0,
                          ids_dtype=np.int64, lead_shape: tuple = ()):
    """Criteo-shaped synthetic host batches (13 numeric + 26 Zipf-skewed
    categorical) — THE synthetic distribution every harness shares.
    ``lead_shape`` prepends stacked-scan leading dims (e.g. ``(K,)``)."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(nb):
        shp = lead_shape + (batch_size,)
        numeric = rng.integers(1, 14, size=shp + (13,))
        cat = 14 + (rng.zipf(1.3, size=shp + (26,)) % (v - 14))
        out.append({
            "feat_ids": np.concatenate(
                [numeric, cat], axis=-1).astype(ids_dtype),
            "feat_vals": np.concatenate(
                [rng.random(shp + (13,), dtype=np.float32),
                 np.ones(shp + (26,), np.float32)], axis=-1),
            "label": (rng.random(shp) < 0.25).astype(np.float32),
        })
    return out


def make_ctr_batches(batch_size: int, nb: int = 4, *, v: int = V_FLAGSHIP,
                     seed: int = 0):
    """Device-staged variant of make_host_ctr_batches (step timing excludes
    the host feed)."""
    import jax

    return [
        {k: jax.device_put(vv) for k, vv in hb.items()}
        for hb in make_host_ctr_batches(batch_size, nb, v=v, seed=seed)
    ]


def time_step_loop(step_fn, state, batches, steps: int, batch_size: int):
    """3 warmup steps (compile + dispatch), then `steps` timed steps;
    blocks only at the end so async dispatch pipelines
    (``jax.block_until_ready`` waits for the device — checked on the v5e,
    CHANGES.md PR 21)."""
    import jax

    nb = len(batches)
    for i in range(3):
        state, metrics = step_fn(state, batches[i % nb])
    jax.block_until_ready(metrics)
    t0 = time.perf_counter()
    for i in range(steps):
        state, metrics = step_fn(state, batches[i % nb])
    jax.block_until_ready(metrics)
    dt = max(time.perf_counter() - t0, 1e-9)
    return {
        "examples_per_sec": round(steps * batch_size / dt, 1),
        "step_us": round(dt / steps * 1e6, 1),
        "final_loss": round(float(np.asarray(metrics["loss"]).reshape(-1)[-1]), 4),
        # unrounded, for bit-identity comparisons (the zero-sharding pair)
        "final_loss_exact": float(np.asarray(metrics["loss"]).reshape(-1)[-1]),
    }


def run_point_subprocess(cmd: list[str], timeout: int, tag: dict) -> dict:
    """Run one measurement point isolated in a subprocess; a hung point
    costs this point, not the sweep.  `tag` labels the error row."""
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout)
        if proc.returncode == 0 and proc.stdout.strip():
            return json.loads(proc.stdout.strip().splitlines()[-1])
        return dict(tag, error=(proc.stderr or "no output")[-200:])
    except subprocess.TimeoutExpired:
        return dict(tag, error=f"timeout after {timeout}s")
    except Exception as e:
        return dict(tag, error=f"{type(e).__name__}: {e}"[:200])


def capture_platform(row: dict, current: tuple[str | None, str | None]):
    """Fold a point row's platform/device_kind into the sweep-level pair
    (first success wins) and strip them from the row."""
    platform, device_kind = current
    if platform is None and "platform" in row:
        platform = row["platform"]
        device_kind = row.get("device_kind")
        print(f"platform={platform} device={device_kind}",
              file=sys.stderr, flush=True)
    row.pop("platform", None)
    row.pop("device_kind", None)
    return platform, device_kind


def backend_platform() -> tuple[str, str]:
    """(platform, device_kind) as jax reports them."""
    import jax

    d = jax.devices()[0]
    return d.platform, d.device_kind


def rescale_schedule(opt: dict, steps: int) -> dict:
    """Re-derive warmup/decay for a new training horizon, keeping the
    schedule SHAPE a sweep picked (same ~5% warmup fraction, decay to the
    end of training).  No-op for constant-lr dicts."""
    if opt.get("lr_schedule", "constant") == "constant":
        return opt
    out = dict(opt)
    out["decay_steps"] = steps
    # clamp below the horizon: for tiny benchmark horizons (steps <= 100)
    # warmup==decay would make build_lr_schedule raise
    out["warmup_steps"] = min(max(100, steps // 20), max(steps - 1, 0))
    return out


def persist_latest_runs(path: str, out: dict, *, ok: int,
                        platform: str | None) -> None:
    """The single persist policy: {latest, runs} history; keep the previous
    latest when this run has zero successful points; migrate legacy flat
    files.  Every row carries its own platform, so a CPU run is recorded
    as a CPU run — it never hides behind an older chip row."""
    latest, runs = out, []
    if os.path.exists(path):
        try:
            with open(path) as f:
                prev = json.load(f)
            runs = prev.get("runs", [])
            if "latest" in prev:
                prev_latest = prev["latest"]
            else:  # legacy flat shape: fold it into history
                prev_latest = {k: v for k, v in prev.items() if k != "runs"}
                runs = runs + [prev_latest]
            if ok == 0:
                latest = prev_latest
                print(f"keeping previous latest ({path}): ok={ok} "
                      f"platform={platform}", file=sys.stderr)
        except Exception as e:
            # an unreadable artifact must not be silently truncated:
            # preserve the bytes for forensics and start a fresh history
            backup = path + ".corrupt"
            try:
                os.replace(path, backup)
            except OSError:
                backup = "<unmovable>"
            print(f"WARNING: {path} unreadable ({type(e).__name__}: {e}); "
                  f"backed up to {backup}, starting fresh history",
                  file=sys.stderr)
            runs = []
    with open(path, "w") as f:
        json.dump({"latest": latest, "runs": runs + [out]}, f, indent=1)
    print(f"persisted {path}", file=sys.stderr)
