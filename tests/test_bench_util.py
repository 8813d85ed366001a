"""Timing-helper and persist behaviour (benchmarks/_bench_util.py), and
bench.py's refusal to measure anything but a known chip.

``jax.block_until_ready`` waits for the device (checked on the v5e, PR 21),
so timed regions end with it: there is no value-fetch barrier and no RTT
subtraction.  A row names the platform jax reports, a CPU run is persisted
as a CPU run, and bench.py fails — in its children and as a whole — when
the device is not in its peaks table."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import _bench_util as bu  # noqa: E402


def test_time_step_loop_schema_single_and_stacked():
    def step(state, batch):
        state = state + jnp.sum(batch["label"]) * 0
        return state, {"loss": jnp.mean(batch["label"]) + state * 0}

    jit_step = jax.jit(step)
    batches = [{"label": jnp.ones((8,)) * i} for i in range(3)]
    r = bu.time_step_loop(jit_step, jnp.zeros(()), batches, steps=5,
                          batch_size=8)
    assert set(r) == {"examples_per_sec", "step_us", "final_loss",
                      "final_loss_exact"}
    assert r["examples_per_sec"] > 0 and r["step_us"] > 0

    # stacked [K] metrics (scan variants): final_loss is the last sub-step
    def scan_step(state, batch):
        return state, {"loss": jnp.arange(4.0)}

    r2 = bu.time_step_loop(jax.jit(scan_step), jnp.zeros(()), batches,
                           steps=2, batch_size=32)
    assert r2["final_loss"] == 3.0


def test_timed_region_covers_the_device_work():
    """The region ends with block_until_ready, so the work of every
    dispatched step lands inside it (async dispatch alone returns early)."""
    def step(state, batch):
        x = batch["label"]
        for _ in range(20):
            x = jnp.tanh(x @ x) + 1e-3
        return state, {"loss": jnp.mean(x)}

    batches = [{"label": jnp.ones((256, 256))}]
    r = bu.time_step_loop(jax.jit(step), jnp.zeros(()), batches, steps=4,
                          batch_size=1)
    # 20 256^3 matmuls cannot finish in the microseconds a bare dispatch takes
    assert r["step_us"] > 100


def test_backend_platform_is_what_jax_reports():
    d = jax.devices()[0]
    assert bu.backend_platform() == (d.platform, d.device_kind)


def test_persist_records_a_cpu_run_as_a_cpu_run(tmp_path, capsys):
    path = str(tmp_path / "BENCH_X.json")
    tpu = {"platform": "tpu", "value": 1}
    bu.persist_latest_runs(path, tpu, ok=1, platform="tpu")
    cpu = {"platform": "cpu", "value": 2}
    bu.persist_latest_runs(path, cpu, ok=1, platform="cpu")
    with open(path) as f:
        doc = json.load(f)
    # the newer run is the latest whatever its platform; history keeps both
    assert doc["latest"] == cpu and doc["runs"] == [tpu, cpu]
    # a run with no successful point never replaces the latest
    bu.persist_latest_runs(path, {"platform": "cpu", "value": 3}, ok=0,
                           platform="cpu")
    with open(path) as f:
        doc = json.load(f)
    assert doc["latest"] == cpu and len(doc["runs"]) == 3
    # an unreadable artifact is preserved, not truncated
    with open(path, "w") as f:
        f.write("{not json")
    bu.persist_latest_runs(path, cpu, ok=1, platform="cpu")
    assert os.path.exists(path + ".corrupt")
    capsys.readouterr()


def test_bench_fails_without_a_known_chip():
    """A device kind outside the peaks table is an error in the child,
    and a failed variant is the whole run's failure: no CPU fallback, no
    re-exec, no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    child = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--variant", "xla"],
        env=env, capture_output=True, text=True, timeout=120)
    assert child.returncode != 0 and "not in HBM_GBPS" in child.stderr
    whole = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=120)
    assert whole.returncode != 0
    assert "bench variant xla failed" in whole.stderr
    assert "not in HBM_GBPS" in whole.stderr and not whole.stdout.strip()


def test_rescale_schedule_clamps_tiny_horizons():
    out = bu.rescale_schedule(
        {"lr_schedule": "cosine", "warmup_steps": 500, "decay_steps": 9999},
        steps=50)
    assert out["warmup_steps"] < out["decay_steps"] == 50
    # constant schedules pass through untouched
    const = {"lr_schedule": "constant", "learning_rate": 1.0}
    assert bu.rescale_schedule(const, steps=50) is const
