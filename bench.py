"""End-of-round benchmark: DeepFM training throughput on one chip.

Prints exactly ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Config matches the reference notebook's training job (ps notebook cell 4:
batch 1024, feature_size 117,581, field 39, K=32, deep 128/64/32, Adam 5e-4)
with bf16 MXU compute.  The reference publishes no absolute throughput
(BASELINE.md), so ``vs_baseline`` is normalized against the BASELINE.json
north-star target expressed per chip: 1M examples/sec aggregate on a v5e-64
=> 15,625 examples/sec/chip.  vs_baseline = measured / 15625 (>1.0 beats the
per-chip north-star rate).  That target is soft (it was set for a 64-chip
pod); the honest perf frame is the HBM roofline included in the artifact:
this model's dense-Adam step at V=117k moves ~90 MB of optimizer/param state
per step, so the floor on a v5e (819 GB/s) is ~110 µs/step.

One process per chip: every variant is measured in its own child process,
and the parent never initialises a jax backend — it learns platform and
device kind from the children's rows.  A device kind that is not in
``HBM_GBPS`` (the CPU included) is an error, and so is any variant that
fails or times out: the run exits non-zero instead of reporting what
survived.  What this script measures is ROADMAP S0's to replace.

Measured variants:
  xla           dense Adam, XLA gather (jit, donated)
  lazy_adam     touched-rows-only Adam (train/lazy.py)
  pallas_fused  Pallas fused gather+FM kernel (TPU only)
  spmd_xla      the PRODUCT path: shard_map train step on a 1-chip mesh
  spmd_lazy     sharded lazy-Adam step on a 1-chip mesh
  spmd_scan8    the product path with run.steps_per_loop=8: K steps fused
                into one scanned dispatch + one stacked transfer
  spmd_scan32   same with K=32 — the deep-amortization headline config
  *_segsum      same step with table_grad='segsum' (sorted-unique-write
                embedding-gradient backward, ops/embedding.py — the round-5
                candidate fix for the serialized scatter); measured right
                after its scatter twin so short windows still decide it
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmarks"))

NORTH_STAR_PER_CHIP = 1_000_000 / 64  # examples/sec/chip
V, F, K = 117_581, 39, 32
DEEP = (128, 64, 32)
# HBM bandwidth by device_kind (GB/s); an unknown kind is an error
HBM_GBPS = {
    "TPU v5 lite": 819.0,   # v5e reports this kind
    "TPU v5e": 819.0,
    "TPU v4": 1228.0,
    "TPU v5p": 2765.0,
    "TPU v6e": 1640.0,
}


def dense_adam_roofline(device_kind: str) -> dict:
    """HBM-traffic floor for the dense-Adam step: params+m+v read & write
    for the two embedding tables (the MLP is negligible), plus the batch
    gathers.  This is the honest per-chip perf frame (the model is
    bandwidth-bound, not FLOPs-bound).  ``device_kind`` must be in
    ``HBM_GBPS``.

    ``state_bytes_per_step`` carries the per-VARIANT optimizer-state
    traffic: replicated (every data shard reads+writes all of p/m/v — the
    pre-zero path) vs the ZeRO dp-sharded update
    (optimizer.zero_sharding): grads move once (reduce-scatter), moments
    never move and are read/written on the owned 1/dp window only, so
    the per-device state traffic is 1/dp of replicated; the one full-
    width write left is the all-gathered fresh params, accounted
    separately (it replaces the full param write the replicated path
    already paid inside its 6S term)."""
    bw = HBM_GBPS[device_kind]
    table_bytes = (V * K + V) * 4          # fm_v + fm_w, f32
    mlp = F * K * DEEP[0] + DEEP[0] * DEEP[1] + DEEP[1] * DEEP[2] + DEEP[2]
    param_bytes = table_bytes + mlp * 4
    state_traffic = param_bytes * 3 * 2    # p,m,v x read+write
    batch_gather = 1024 * F * (K + 1) * 4 * 2          # fwd rows + row grads
    total = state_traffic + batch_gather
    roof = {
        "dense_state_bytes_per_step": state_traffic,
        "total_bytes_per_step_est": total,
        # per-variant optimizer-state traffic, replicated vs dp-sharded
        # (~97 MB/step -> ~97/dp MB/step; measured pair: zero_sharding_pair)
        "state_bytes_per_step": {
            "replicated": state_traffic,
            **{
                f"zero_dp{d}": {
                    "state_bytes_per_step": state_traffic // d,
                    "allgather_param_write_bytes": param_bytes,
                    "moments_bytes_per_device": 2 * param_bytes // d,
                }
                for d in (2, 4, 8)
            },
            "note": (
                "replicated: every data shard reads+writes p/m/v in "
                "full; zero_dpN: each shard touches only its 1/N "
                "window (grads reduce-scatter once, moments never "
                "move), plus the all-gathered full param write"
            ),
        },
    }
    roof["hbm_bw_gbps"] = bw
    roof["roofline_step_us"] = round(total / (bw * 1e9) * 1e6, 1)
    return roof


def spmd_ici_estimate(dp: int = 2, mp: int = 4) -> dict:
    """Per-step ICI bytes for the sharded step's embedding collectives —
    psum vs alltoall (ModelConfig.shard_exchange) — from B/F/K/M plus the
    MEASURED dedup rate of the shared synthetic Criteo batch, so the
    BENCH/MULTICHIP artifacts carry the comms math, not just HBM bytes.

    psum: ring all-reduce of the dense local [B/dp, F(, K)] row tensor per
    table, forward and backward -> 2 * 2(M-1)/M * S bytes each.
    alltoall: request ids [M, C] one way, response rows [M, C, K] forward
    and summed per-unique-row grads backward -> (M-1)/M of each buffer; C
    is the static per-destination capacity (auto = ceil(N/M)), so the
    traffic scales with the batch's deduped rows, not its dense volume.
    """
    from deepfm_tpu.parallel.embedding import exchange_capacity

    import _bench_util as bu

    b_local = BATCH // dp
    n = b_local * F
    host = bu.make_host_ctr_batches(BATCH, 1, v=V)[0]
    ids = np.asarray(host["feat_ids"]).reshape(dp, -1)
    per_shard_unique = [np.unique(s).size for s in ids]
    dedup_rate = round(float(np.mean(per_shard_unique)) / n, 4)
    cap_auto = exchange_capacity(n, mp, 0.0)
    # capacity sized to the measured dedup (what the flagship bench uses;
    # benchmarks/multichip_flagship.py A2A_CAPACITY) — the worst owner
    # bucket of the unpermuted Criteo shape needs ~dedup_rate * N slots
    cap_meas = exchange_capacity(n, mp, min(1.0, dedup_rate * 1.3))
    ring = 2.0 * (mp - 1) / mp
    wire = float(mp - 1) / mp

    def psum_bytes():
        s_v, s_w = n * K * 4, n * 4
        return int(2 * ring * (s_v + s_w))  # fwd + bwd, both tables

    def a2a_bytes(cap):
        per_table_req = wire * mp * cap * 4
        resp_v = wire * mp * cap * K * 4
        resp_w = wire * mp * cap * 1 * 4
        return int(2 * per_table_req + 2 * resp_v + 2 * resp_w)

    out = {
        "mesh": [dp, mp], "batch_local": b_local, "fields": F, "k": K,
        "dedup_unique_fraction": dedup_rate,
        "psum_bytes_per_step_est": psum_bytes(),
        "alltoall_bytes_per_step_est": a2a_bytes(cap_auto),
        "alltoall_bytes_per_step_est_capacity_measured": a2a_bytes(cap_meas),
        "capacity_auto_rows": cap_auto,
        "capacity_measured_rows": cap_meas,
    }
    out["alltoall_over_psum"] = round(
        out["alltoall_bytes_per_step_est"] / out["psum_bytes_per_step_est"],
        3,
    )
    out["alltoall_over_psum_capacity_measured"] = round(
        out["alltoall_bytes_per_step_est_capacity_measured"]
        / out["psum_bytes_per_step_est"], 3,
    )
    return out


def _flagship_cfg(fused: str = "off", lazy: bool = False,
                  table_grad: str = "scatter"):
    from deepfm_tpu.core.config import Config

    return Config.from_dict(
        {
            "model": {
                "feature_size": V,
                "field_size": F,
                "embedding_size": K,
                "deep_layers": DEEP,
                "dropout_keep": (0.5, 0.5, 0.5),
                "fused_kernel": fused,
                "table_grad": table_grad,
            },
            "optimizer": {"learning_rate": 0.0005,
                          "lazy_embedding_updates": lazy},
            "data": {"batch_size": 1024},
        }
    )


def _synth_batches(batch_size: int, nb: int = 8, device_put: bool = True):
    """Synthetic Criteo-shaped batches (the shared generator in
    _bench_util), pre-staged on device so the bench isolates the
    training-step rate."""
    import _bench_util as bu

    if device_put:
        return bu.make_ctr_batches(batch_size, nb, v=V)
    return bu.make_host_ctr_batches(batch_size, nb, v=V)


STEPS = 100
BATCH = 1024


def _time_loop(step_fn, state, bs) -> tuple[float, float]:
    """Timing via the shared helper (_bench_util.time_step_loop): one
    timing policy, one implementation."""
    import _bench_util as bu

    # examples per dispatch: [B] single-step or [K, B] stacked-scan batches
    batch_size = int(np.prod(bs[0]["label"].shape))
    r = bu.time_step_loop(step_fn, state, bs, STEPS, batch_size)
    return r["examples_per_sec"], r["final_loss"]


def measure(fused: str, lazy: bool = False,
            table_grad: str = "scatter") -> tuple[float, float]:
    import jax

    from deepfm_tpu.train import create_train_state, make_train_step

    c = _flagship_cfg(fused, lazy, table_grad)
    state = create_train_state(c)
    train_step = jax.jit(make_train_step(c), donate_argnums=(0,))
    return _time_loop(train_step, state, _synth_batches(BATCH))


def measure_spmd(lazy: bool, steps_per_loop: int = 1,
                 table_grad: str = "scatter") -> tuple[float, float]:
    """The product path: shard_map step on a [1,1] mesh — measures the
    shard_map/collective overhead vs the plain jit step.  With
    ``steps_per_loop > 1``, K optimizer steps fuse into one scanned dispatch
    with one stacked transfer (run.steps_per_loop; parallel/spmd.py)."""
    from deepfm_tpu.core.config import MeshConfig
    from deepfm_tpu.parallel import (
        build_mesh, create_spmd_state, make_context, make_spmd_train_loop,
        make_spmd_train_step, shard_batch, shard_batch_stacked,
    )

    c = _flagship_cfg("off", lazy, table_grad).with_overrides(
        mesh={"data_parallel": 1, "model_parallel": 1},
    )
    mesh = build_mesh(MeshConfig(data_parallel=1, model_parallel=1))
    ctx = make_context(c, mesh)
    state = create_spmd_state(ctx)
    if steps_per_loop > 1:
        # DISTINCT stacked batches (nb*k host batches) so dispatches do not
        # replay identical data (round-3 advisor #2); nb shrinks for large K
        # to cap host staging (~62 MB at K=32)
        k = steps_per_loop
        nb = max(2, min(8, 256 // k))
        host = _synth_batches(BATCH, nb=nb * k, device_put=False)
        step_fn = make_spmd_train_loop(ctx, k)
        sb = [shard_batch_stacked(ctx, host[i * k:(i + 1) * k],
                                  validate_ids=False)
              for i in range(nb)]
        rate, loss = _time_loop(step_fn, state, sb)
        return rate, loss
    host = _synth_batches(BATCH, device_put=False)
    step_fn = make_spmd_train_step(ctx)  # donated, jitted inside
    sb = [shard_batch(ctx, hb, validate_ids=False) for hb in host]
    return _time_loop(step_fn, state, sb)


def measure_zero_pair(zero: bool) -> dict:
    """One arm of the measured before/after pair for the ZeRO dp-sharded
    weight update (optimizer.zero_sharding): the flagship config on the
    8-device virtual [2,4] mesh, replicated vs dp-sharded update.  Runs
    on the CPU virtual mesh by design (the pair measures the update
    restructure and the state-residency claim, not chip throughput); the
    parent forces the platform.  Reports the measured per-device
    optimizer-state bytes (the moments-never-move claim as a live
    artifact: replicated / dp-sharded ≈ dp for the dominant leaves) and
    final_loss, which must be BIT-IDENTICAL across the pair
    (tests/test_zero_sharding.py pins the same at step level)."""
    import jax

    from deepfm_tpu.core.config import MeshConfig
    from deepfm_tpu.parallel import (
        build_mesh, create_spmd_state, make_context, make_spmd_train_step,
        shard_batch,
    )

    dp, mp = 2, 4
    c = _flagship_cfg().with_overrides(
        mesh={"data_parallel": dp, "model_parallel": mp},
        optimizer={"zero_sharding": "on" if zero else "off"},
    )
    mesh = build_mesh(MeshConfig(data_parallel=dp, model_parallel=mp))
    ctx = make_context(c, mesh)
    state = create_spmd_state(ctx)
    opt_bytes_dev0 = int(sum(
        leaf.addressable_shards[0].data.nbytes
        for leaf in jax.tree_util.tree_leaves(state.opt_state)
        if hasattr(leaf, "addressable_shards")
    ))
    step_fn = make_spmd_train_step(ctx)
    host = _synth_batches(BATCH, device_put=False)
    sb = [shard_batch(ctx, hb, validate_ids=False) for hb in host]
    import _bench_util as bu

    r = bu.time_step_loop(step_fn, state, sb, STEPS, BATCH)
    return {
        "zero_sharding": "on" if zero else "off",
        "mesh": [dp, mp],
        "examples_per_sec": r["examples_per_sec"],
        "final_loss": r["final_loss_exact"],
        "opt_state_bytes_per_device": opt_bytes_dev0,
    }


# the measured before/after pair (run on the forced-CPU 8-device mesh by
# main(); not part of the throughput auto-tune set)
ZERO_PAIR = {
    "zero_off": lambda: measure_zero_pair(False),
    "zero_on": lambda: measure_zero_pair(True),
}


# ordered by information value under the time budget: each scatter variant
# is immediately followed by its segsum twin (ops/embedding.py segsum_lookup
# — the round-5 candidate fix for the serialized table-grad scatter), so a
# short window still yields the comparison that decides table_grad's default
VARIANTS = {
    "xla": lambda: measure("off"),
    "xla_segsum": lambda: measure("off", table_grad="segsum"),
    # the product path with deep dispatch amortization — the headline
    # run.steps_per_loop configuration (full K sweep: benchmarks/spmd_sweep.py)
    "spmd_scan32": lambda: measure_spmd(False, steps_per_loop=32),
    "spmd_scan32_segsum": lambda: measure_spmd(
        False, steps_per_loop=32, table_grad="segsum"),
    "lazy_adam": lambda: measure("off", True),
    "spmd_xla": lambda: measure_spmd(False),
    "spmd_lazy": lambda: measure_spmd(True),
    "spmd_scan8": lambda: measure_spmd(False, steps_per_loop=8),
    "pallas_fused": lambda: measure("on", False),
}


def run_variant(name: str) -> None:
    """Child mode (--variant NAME): measure one variant in THIS process and
    print its JSON row, which carries the platform and device kind this
    process got.  One variant per process: a chip belongs to one process
    at a time, and the parent stays off it."""
    from deepfm_tpu.core.platform import configure_runtime, runtime_report

    configure_runtime()
    report = runtime_report()
    where = {"platform": report["platform"],
             "device_kind": report["device_kind"]}
    if name in ZERO_PAIR:
        print(json.dumps({"variant": name, **where, **ZERO_PAIR[name]()}))
        return
    if where["device_kind"] not in HBM_GBPS:
        raise SystemExit(
            f"bench: device_kind {where['device_kind']!r} (platform "
            f"{where['platform']!r}) is not in HBM_GBPS — this benchmark "
            f"measures a chip and does not fall back"
        )
    rate, loss = VARIANTS[name]()
    print(json.dumps({"variant": name, **where, "examples_per_sec": rate,
                      "final_loss": loss}))


def _run_child(name: str, env: dict | None = None) -> dict:
    """One variant in its own process; a failure or a hang (timeout) is the
    run's failure."""
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--variant", name],
        capture_output=True, text=True, env=env,
        timeout=int(os.environ.get("DEEPFM_BENCH_VARIANT_TIMEOUT", "600")),
    )
    if r.returncode != 0 or not r.stdout.strip():
        raise RuntimeError(
            f"bench variant {name} failed (exit {r.returncode}): "
            f"{(r.stderr or 'no output')[-2000:]}"
        )
    return json.loads(r.stdout.strip().splitlines()[-1])


def main() -> None:
    if len(sys.argv) > 1 and sys.argv[1] == "--obs":
        # the observability overhead gate (benchmarks/obs_overhead.py):
        # closed-loop serve throughput at concurrency 16, full trace +
        # registry + flight pipeline vs bare, medians over interleaved
        # trials; emits docs/BENCH_OBS.json and FAILS (exit 1) when the
        # instrumented median falls more than 3% under bare.  Host-only
        # by design — the obs layer never touches lowered code
        # (audit_observability pins that), so chips are irrelevant here.
        import obs_overhead

        r = obs_overhead.main()
        sys.exit(0 if r["within_noise"] else 1)
    if len(sys.argv) > 1 and sys.argv[1] == "--multitenant":
        # the multi-tenant fleet gate (benchmarks/multitenant.py): 4
        # same-spec tenants + 1 shadow challenger on a 2-group pool —
        # per-tenant p50/p99 vs the single-tenant baseline, a mid-load
        # single-tenant swap (FAILS on any failed / mixed-version /
        # cross-tenant-contaminated response), and a paired toggled-window
        # check that shadow scoring adds no response-path latency.  Emits
        # docs/BENCH_MULTITENANT.json.  CPU virtual mesh by design — the
        # drill measures the fleet control plane, not chip throughput.
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        sys.argv = [sys.argv[0], "--persist"] + sys.argv[2:]
        import multitenant

        r = multitenant.main()
        sys.exit(0 if r["ok"] else 1)
    if len(sys.argv) > 1 and sys.argv[1] == "--multiregion":
        # the cross-region gate (benchmarks/multiregion.py): two regions
        # (pool + region store each) behind the region front, manifests
        # replicated marker-last from the home root; kills one region
        # mid-load and FAILS (exit 1) on any admitted-then-failed
        # request, a post-failover tail outside the SLO, a stale-but-
        # healthy region re-admitted before its store caught up, or
        # post-recovery traffic off the newest version / off its home
        # region.  Emits docs/BENCH_MULTIREGION.json.  CPU virtual mesh
        # by design — the drill measures the region control plane
        # (audit_region_front pins it out of the lowered predict).
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        sys.argv = [sys.argv[0], "--persist"] + sys.argv[2:]
        import multiregion

        sys.exit(multiregion.main())
    if len(sys.argv) > 1 and sys.argv[1] == "--funnel":
        # the recommendation-funnel gate (benchmarks/funnel.py): naive
        # loop vs fused engine vs pool, plus the exact/int8/int8+pallas
        # retrieval-mode comparison at flagship V AND a synthetic 2e6-row
        # corpus — FAILS (exit 1) unless the fused engine beats the naive
        # loop and, at the synthetic corpus, int8 (or int8+pallas) makes
        # >= 1.5x exact candidates/s with recall@K >= min_recall vs
        # brute_force_topk.  Emits docs/BENCH_FUNNEL.json.  CPU virtual
        # mesh by design off-TPU; on a TPU backend the int8+pallas row
        # measures the fused Pallas kernel (kernel_engaged=true).
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        sys.argv = [sys.argv[0], "--persist"] + sys.argv[2:]
        import funnel

        r = funnel.main()
        sys.exit(0 if r["ok"] else 1)
    if len(sys.argv) > 1 and sys.argv[1] == "--slo":
        # the SLO control-plane gate (benchmarks/slo_control.py): one
        # diurnal + 10x-spike trace against a static 2-group pool vs the
        # adaptive pool (deadline-aware admission + shed ladder, hedged
        # tails under a 5% budget, AutoScaler-driven 1→4 group scaling
        # through the router's add/remove_group path); emits
        # docs/BENCH_SLO.json and FAILS (exit 1) unless adaptive beats
        # static on SLO attainment with hedges inside budget, zero
        # admitted-then-failed requests, and the pool converged back to
        # min_groups after the spike.  Host-only by design — the control
        # plane is host-side policy (audit_control_plane pins it out of
        # the lowered predict), so chips are irrelevant here.
        import slo_control

        r = slo_control.main()
        sys.exit(0 if r["ok"] else 1)
    if len(sys.argv) > 1 and sys.argv[1] == "--elastic":
        # the elastic chaos drill (benchmarks/elastic_drill.py): shrink
        # [2,4]→[1,4] and grow back mid-run under serving load; emits
        # docs/BENCH_ELASTIC.json (reshard wall-time, steps lost, serving
        # error counts, loss continuity).  CPU virtual mesh by design —
        # the drill measures the robustness layer, not chip throughput.
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        import elastic_drill

        elastic_drill.main()
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--elastic-multihost":
        # the multi-host elastic drill (benchmarks/elastic_multihost.py):
        # the same [2,4]→[1,4]→[2,4] cycle under lease-fenced epoch
        # consensus, with the MPMD trainer/publisher split across real
        # processes, a scripted coordinator outage (frozen-topology
        # training), and stale-token writers refused on both the commit
        # and the publish path; emits docs/BENCH_ELASTIC_MULTIHOST.json
        # and FAILS (exit 1) on any violation.  CPU virtual mesh by
        # design — the drill measures the coordination layer, not chips.
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        import elastic_multihost

        elastic_multihost.main()
        return
    if len(sys.argv) > 2 and sys.argv[1] == "--variant":
        # child: platform was resolved by the parent and passed via env
        run_variant(sys.argv[2])
        return

    # XLA gather vs Pallas fused gather vs lazy (touched-rows) Adam vs the
    # shard_map product path — each in an isolated child; report the
    # fastest, record all.  The first row says which chip the run got.
    rates: dict[str, tuple[float, float]] = {}
    platform = device_kind = None
    for name in VARIANTS:
        row = _run_child(name)
        if platform is None:
            platform, device_kind = row["platform"], row["device_kind"]
        rates[name] = (row["examples_per_sec"], row["final_loss"])
    best = max(rates, key=lambda k: rates[k][0])
    examples_per_sec, final_loss = rates[best]
    batch_size = BATCH
    result = {
        "metric": "deepfm_train_examples_per_sec_per_chip",
        "value": round(examples_per_sec, 1),
        "unit": "examples/s",
        "vs_baseline": round(examples_per_sec / NORTH_STAR_PER_CHIP, 3),
        "platform": platform,
        "device_kind": device_kind,
        "batch_size": batch_size,
        "steps": STEPS,
        "step_ms": round(1000 * batch_size / examples_per_sec, 3),
        "final_loss": round(final_loss, 4),
        "variant": best,
        "variants": {k: round(v[0], 1) for k, v in rates.items()},
        "timing_method": "block_until_ready",
    }
    roof = dense_adam_roofline(device_kind)
    # comms math for the SPMD variants: what a [2,4] flagship mesh moves
    # over ICI per step, psum vs the deduplicated alltoall exchange
    roof["ici_bytes_per_step_est"] = spmd_ici_estimate()
    # the before/after pair for the dp-sharded weight update, on the CPU
    # 8-device virtual mesh (children that need no chip): it checks the
    # update restructure and state residency — replicated vs
    # zero_sharding=on, same batches, final_loss must be bit-identical and
    # per-device opt-state bytes must shrink ~dp-fold on the dp-sharded
    # leaves.  Its rates are CPU rates and are not device metrics.
    pair_env = dict(os.environ)
    pair_env["JAX_PLATFORMS"] = "cpu"
    pflags = pair_env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in pflags:
        pair_env["XLA_FLAGS"] = (
            pflags + " --xla_force_host_platform_device_count=8"
        ).strip()
    pair: dict = {name: _run_child(name, pair_env) for name in ZERO_PAIR}
    pair["final_loss_bit_identical"] = (
        pair["zero_off"]["final_loss"] == pair["zero_on"]["final_loss"]
    )
    off_b = pair["zero_off"]["opt_state_bytes_per_device"]
    on_b = pair["zero_on"]["opt_state_bytes_per_device"]
    pair["opt_state_bytes_ratio"] = round(off_b / max(1, on_b), 3)
    result["zero_sharding_pair"] = pair
    meas_us = 1e6 * batch_size / rates["xla"][0]
    roof["measured_xla_step_us"] = round(meas_us, 1)
    roof["hbm_utilization_xla"] = round(roof["roofline_step_us"] / meas_us, 3)
    result["roofline"] = roof
    print(json.dumps(result))


if __name__ == "__main__":
    main()
