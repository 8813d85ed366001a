#!/usr/bin/env bash
# Static-analysis gate: ruff (mechanical, skips gracefully when absent —
# scripts/lint.sh) + the JAX-aware analyzer (deepfm_tpu/analysis: AST rules
# incl. the guarded-by race lint, the interprocedural concurrency engine
# (lock-order cycles / blocking-under-lock / signal safety / thread
# lifecycle), plus the trace-time contract audit), all ratcheted against
# analysis_baseline.json — new findings exit non-zero, baselined debt does
# not (the concurrency rules ratchet at ZERO accepted debt: the baseline
# holds no entry for them).  Usage: scripts/check.sh [--json|--github]
set -euo pipefail
cd "$(dirname "$0")/.."

scripts/lint.sh

fmt="text"
if [[ "${1:-}" == "--json" ]]; then
    fmt="json"
elif [[ "${1:-}" == "--github" || -n "${GITHUB_ACTIONS:-}" ]]; then
    # workflow-command annotations: CI anchors each finding to file:line
    fmt="github"
fi

# Slow gate (CHECK_SLOW=1 or --slow): the elastic chaos drills — (1) kill
# and restore virtual-mesh devices mid-run ([2,4]→[1,4]→[2,4]) and hold the
# run to the ISSUE-9 acceptance bar: loss-curve continuity vs an
# uninterrupted baseline, exactly-once cursor lineage, 0 failed /
# 0 mixed-version predicts at the serving pool (tests/test_elastic_chaos.py
# drives tests/drills/elastic_drill.py); (2) the MULTI-HOST drill
# (tests/test_elastic_multihost.py, tests/drills/elastic_multihost.py):
# the same mesh cycle under lease-fenced epoch consensus with the MPMD
# trainer/publisher split across real processes, a FaultPlan-scripted
# coordinator outage (frozen-topology training), and a stale-token writer
# refused on both the commit and publish path; (3) the OVERLOAD drill
# (tests/test_control_chaos.py): a FaultPlan latency window stalls one
# shard-group mid-load — hedges must engage, the stalled group must NOT
# be ejected, the hedge rate must decay to zero after the heal, and zero
# admitted requests may fail; (4) the REGION-LOSS drill
# (tests/test_region_chaos.py): two regions (serving pool + region store
# each) behind the region front with manifests replicated marker-last
# from the home root — one region killed mid-load must fail over with 0
# admitted-then-failed requests and an in-SLO tail, and the restored
# region must stay OUT while its store is stale beyond the version-skew
# SLO, re-admitting only after the replicator catches it up
# (tests/drills/multiregion.py).
# Off by default: each drill trains two full runs and serves under load
# (~minutes), which does not belong in the per-commit static gate.
if [[ "${CHECK_SLOW:-0}" == "1" || "${1:-}" == "--slow" || "${2:-}" == "--slow" ]]; then
    env JAX_PLATFORMS=cpu \
        XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=8" \
        python -m pytest tests/test_elastic_chaos.py \
        tests/test_elastic_multihost.py tests/test_control_chaos.py \
        tests/test_region_chaos.py \
        -q -m slow \
        -p no:cacheprovider
fi

# the trace audit's collective contract lowers the sharded train step on an
# 8-device virtual CPU mesh (the CLI also arranges this itself when
# JAX_PLATFORMS=cpu; exported here so the gate never silently degrades).
# The same run enforces the PAGING contract (audit_paged_step): the tiered
# store's steady-state step must lower with no host transfers outside the
# designated staging arguments — and the SHARDED-PREDICT contract
# (audit_sharded_predict): the serving pool's shard-group predict must
# lower with the all_to_all exchange (no dense row tensor outside the
# fallback arm), cover every admissible per-group dispatch size with a
# precompiled bucket, and keep group swaps jit cache hits — and the
# MULTITENANT contract (audit_multitenant): two distinct same-spec tenant
# payloads must lower through ONE shard-group predict to IDENTICAL modules
# with payload leaves as lowered parameters (deepfm_tpu/fleet: N model
# variants on one pool cost N payloads and zero extra executables; a
# spec-divergent tenant claiming shared executables or a tenant payload
# baked as a constant fails the gate) — and the FUNNEL
# contract (audit_funnel): the recommendation funnel's retrieve and
# expand+rank executables must lower transfer-guard-clean with the index
# as lowered parameters (a refresh is a cache hit), per-shard top-k
# present, and no collective moving a corpus-sized operand (only the
# [B_local, K] candidate packs cross the wire); the audit lowers BOTH
# retrieval modes on both meshes, and the int8 tier carries two extra
# bandwidth checks on the lowered text — no corpus-sized f32 RESULT
# (the quantized scorer streams int8 tiles; a whole-shard
# codes.astype(f32) is the copy the tier exists to never make) and no
# corpus-sized gather result (the exact rescore may gather only the
# K*oversample shortlist) — and the ELASTIC contract
# (audit_elastic): the N→M reshard's row-adapt executables must lower
# under transfer_guard('disallow') with the table as a lowered parameter
# (no host round-trip on table leaves) and the redistribution plan must
# stay minimal-traffic (a same-width shrink plans ZERO table bytes).
# — and the ZERO-UPDATE contract (audit_zero_update): with the ZeRO
# dp-sharded weight update active the lowered SPMD step must carry one
# data-axis reduce-scatter per sharded param leaf (never a grad-sized
# data-axis all-reduce), all-gather the fresh 1/dp param windows, lower
# every flattened moment leaf with 1/dp-sized per-shard shapes, and stay
# transfer-guard-clean with the state donated.
# — and the OBSERVABILITY contract (audit_observability): the unified obs
# layer (deepfm_tpu/obs) must never enter lowered code — the serving
# predict and train step lower under transfer_guard('disallow') with no
# host-callback custom_calls in the module and lower deterministically
# across fresh builds (a host-timer value captured by the trace bakes a
# different constant per retrace).  The same audit re-lowers the serving
# predict with a LIVE flywheel impression logger (deepfm_tpu/flywheel)
# armed — worker thread running, an offer absorbed — proving the logger
# stays on the router's host response path and never inside the jitted
# predict (seeded violation: a logger call closed over the traced score).
# — and the CONTROL-PLANE contract (audit_control_plane): the SLO control
# plane (deepfm_tpu/serve/control — deadline-aware admission, the shed
# ladder, hedging, autoscaling) is host-side policy; with the full plane
# constructed and fed an observation stream, the serving predict must
# still lower transfer-guard-clean, callback-free and deterministically
# (an admission decision reading a traced value, or a scale decision
# smuggled in via io_callback, fails the gate).
# — and the REGION-FRONT contract (audit_region_front): the cross-region
# layer (deepfm_tpu/region — rendezvous home assignment, manifest
# replication lag, the staleness-SLO drain edge, budgeted failover) is
# pure control plane: statically jax-free by AST walk, runnable as plain
# host code with no device, and with a live fed region front the serving
# predict must still lower transfer-guard-clean, callback-free and
# deterministically (a staleness observation fed from a traced value, or
# a home pick smuggled in via io_callback, fails the gate).
# Seeded violations in tests/test_analysis.py (smuggled transfer,
# dense-row leak, off-bucket/indivisible shape, baked mixed-generation
# payload, spec-divergent tenants claiming one executable, baked tenant
# payload, full-corpus score gather, baked index, whole-shard int8
# dequantize, corpus-sized rescore gather, reshard host round-trip,
# baked reshard table, host timer closed over a traced value, registry
# call inside a jitted fn, admission check on a traced queue depth,
# io_callback scale decision inside jit, staleness note on a traced
# version, io_callback home pick inside jit) prove each contract
# actually catches its regression.
exec env JAX_PLATFORMS=cpu \
    XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=8" \
    python -m deepfm_tpu.analysis deepfm_tpu \
    --trace-audit --concurrency --format "$fmt" \
    --baseline analysis_baseline.json
