"""Engine 2 — trace-time contract audit.

Imports the REAL entrypoints (the jitted predict/train constructions the
serving and training stacks run) and verifies lowering-level invariants
without executing a step — everything here works on abstract
``ShapeDtypeStruct`` values, so the audit is shape/dtype/lowering truth,
not a benchmark:

* **transfer audit** — trace + lower the weight-parameterized predict
  (``serve.reload.build_predict_with``) and the canonical train step
  (``train.step.jitted_train_step``) under
  ``jax.transfer_guard("disallow")``: any implicit host→device transfer
  during tracing/lowering (a stray ``jnp.asarray(host_thing)``, an
  uncommitted constant) raises, proving the executables move data only
  through their declared arguments.
* **recompile audit** — enumerate the MicroBatcher's bucket shapes and
  prove every admissible request size maps onto a precompiled bucket
  (``serve.batcher.pick_bucket`` + the admission chunking contract):
  exactly ``len(buckets)`` executables exist and no live shape escapes
  onto the compile path.
* **swap-is-a-cache-hit audit** — lower ``predict_with`` with two
  DIFFERENT abstract payloads of identical spec and require identical
  input signatures and lowered modules: the jit cache key depends on the
  payload's shapes/dtypes only, so publishing version N+1 (same tree) can
  never recompile mid-traffic.  Also asserts the payload leaves appear as
  lowered *parameters*, not baked-in constants.
* **donation audit** — the train step's state argument must be donated
  (buffers update in place in HBM); verified from the lowered
  ``args_info``, i.e. what actually reached XLA, not what the call site
  intended.
* **dtype audit** — no float64 anywhere in the lowered signatures (a
  silent x64 upgrade doubles bytes and halves serving throughput before
  any test notices) and the predict output is exactly float32 (no
  surprise bf16 widening of the wire format).
* **paging audit** — lower the tiered store's steady-state slot-space
  train step (``tiered.step.make_paged_train_step``) under
  ``jax.transfer_guard("disallow")`` and hold it to the paging contract:
  the lowered executable contains NO host transfers outside the
  designated staging ops — i.e. every host byte enters through the
  declared arguments (translated slot ids + the pager's staged miss
  pack, which must appear as lowered PARAMETERS, never baked
  constants), the state is donated (hot-cache buffers update in place),
  and the output state specs match the input (no dtype/shape drift).
* **collective-traffic audit** — lower the REAL sharded train step on the
  8-device virtual mesh in each ``shard_exchange`` mode and hold the
  lowering to its traffic contract: in ``alltoall`` mode the program must
  contain NO all-reduce/all-gather whose operand is the full dense
  ``[B_local, F, K]`` row tensor outside the capacity-overflow fallback
  branches (``stablehlo.case`` regions — the fallback is allowed to be
  dense, the main line is not), and must actually carry the
  ``all_to_all`` pair; in ``psum`` mode the dense all-reduce must be
  PRESENT (the detector's self-check — if lowering drifts so the scanner
  goes blind, psum mode fails loudly instead of alltoall passing
  vacuously).  The per-mode expected sets live in
  :data:`EXCHANGE_CONTRACT`.

* **zero-update audit** — lower the SPMD train step with the ZeRO
  dp-sharded weight update active (``optimizer.zero_sharding``,
  train/optimizer.zero_sharded) and hold it to its traffic contract:
  dense grads REDUCE-SCATTER over the data axis (one collective per
  param leaf — the XLA-overlappable form — classified by replica
  groups, so the model-axis row-assembly psum never false-positives),
  no grad-sized data-axis all-reduce survives, the fresh 1/dp param
  windows all-gather back, every flattened moment leaf lowers with
  1/dp-sized per-shard shapes, and the step stays
  ``transfer_guard('disallow')``-clean with the state donated.

* **funnel audit** — lower the recommendation funnel's retrieval and
  expand+rank executables (``funnel/index.py``) on the audited serve
  meshes: transfer-guard-clean at every bucket, the index rides as
  lowered PARAMETERS (a refresh is a jit cache hit, never a recompile),
  per-shard ``top_k`` present, and NO collective moves a corpus-sized
  operand — only the [B_local, K] candidate packs cross the wire (a
  score-all-then-gather lowering is the seeded regression).

* **elastic-reshard audit** — lower the elastic N→M row-adapt
  executables (``checkpoint/reshard.jit_row_adapter``) for every audited
  topology move under ``jax.transfer_guard("disallow")`` and hold the
  reshard to its contract: table rows re-window device-to-device (no host
  round-trip on table leaves), the table rides as a lowered PARAMETER,
  and the planner's traffic stays minimal (a same-width shrink plans
  zero table bytes; every plan beats the gather-to-host round trip).

* **sharded-predict audit** — lower the shard-group serving pool's
  predict (``serve.pool.sharded.build_sharded_predict_with``) on the
  audited serve meshes and hold it to the pool's contract: lowers under
  ``transfer_guard('disallow')``, carries the all_to_all exchange with
  no dense row tensor outside the fallback arm, every admissible size
  per group lands on a precompiled data-axis-divisible bucket, and two
  same-spec payloads lower identically (a group swap is a cache hit —
  no mixed-generation executable can exist).

* **multitenant audit** — the fleet's executable-sharing contract
  (``deepfm_tpu/fleet``): two DISTINCT same-spec tenant payloads must
  lower through ONE shard-group predict to IDENTICAL modules with the
  payload leaves as lowered PARAMETERS — tenant selection is a payload
  pick, never a recompile, so N tenants on one pool cost N payloads and
  zero extra executables.  Catches both seeded regressions: a
  spec-divergent tenant claiming shared executables, and a tenant
  payload baked in as constants.

* **observability audit** — the unified obs layer (``deepfm_tpu/obs``)
  must never enter lowered code: the real serving predict and train step
  lower under ``transfer_guard('disallow')`` with NO host callbacks in
  the module (a registry/trace call smuggled under jit lowers as a
  ``custom_call @..callback`` the scanner catches) and lower
  deterministically across fresh builds (a host-timer value closed over
  by the trace bakes a different constant per retrace).  Timers wrap
  dispatch boundaries on the host — never traced values.

Failures are reported as the same :class:`~.findings.Finding` records as
engine 1 (rules ``trace-transfer`` / ``trace-recompile`` /
``trace-donation`` / ``trace-dtype`` / ``trace-observability``) so the
CLI, baseline, and JSON output treat both engines uniformly.
"""

from __future__ import annotations

from .findings import Finding

# small but structurally faithful: all model families keep their real
# layer stack; only the table sizes shrink so abstract lowering stays
# fast enough for a tier-1 test
_AUDIT_OVERRIDES = {"feature_size": 997, "field_size": 8}


def _default_buckets() -> tuple[int, ...]:
    """The engine's REAL default shapes (serve.batcher.DEFAULT_BUCKETS) —
    imported, not copied, so a serving-default change re-points the audit
    automatically.  Deferred import: this module must stay importable
    before jax-adjacent deps load."""
    from ..serve.batcher import DEFAULT_BUCKETS

    return DEFAULT_BUCKETS


def _finding(rule: str, message: str, hint: str = "", where: str = "",
             slug: str = "") -> Finding:
    # `slug` stands in for the source line in the fingerprint (trace
    # findings have no source line): a stable per-contract token, so two
    # different trace-dtype defects in one file never share a fingerprint
    # (and a baselined one can never mask a fresh regression)
    return Finding(
        rule=rule, path=where or "deepfm_tpu/analysis/trace_audit.py",
        line=0, col=0, message=message, hint=hint, source=slug or message,
    )


def _audit_cfg(model_name: str = "deepfm"):
    from ..core.config import Config

    return Config().with_overrides(
        model={**_AUDIT_OVERRIDES, "model_name": model_name}
    )


def _abstract_batch(cfg, rows: int):
    import jax
    import jax.numpy as jnp

    f = cfg.model.field_size
    return {
        "feat_ids": jax.ShapeDtypeStruct((rows, f), jnp.int64),
        "feat_vals": jax.ShapeDtypeStruct((rows, f), jnp.float32),
        "label": jax.ShapeDtypeStruct((rows,), jnp.float32),
    }


def _abstract_payload(cfg):
    import jax

    from ..models.base import get_model

    model = get_model(cfg.model)
    params, model_state = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), cfg.model)
    )
    return model, {"params": params, "model_state": model_state}


def audit_predict(cfg=None) -> list[Finding]:
    """Transfer + dtype + swap-cache-hit contracts on the hot-reload
    predict path."""
    import jax

    from ..serve.reload import build_predict_with

    out: list[Finding] = []
    cfg = cfg or _audit_cfg()
    where = "deepfm_tpu/serve/reload.py"
    model, payload = _abstract_payload(cfg)
    predict_with = build_predict_with(model, cfg)
    f = cfg.model.field_size
    args = lambda b: (  # noqa: E731
        jax.ShapeDtypeStruct((b, f), jax.numpy.int64),
        jax.ShapeDtypeStruct((b, f), jax.numpy.float32),
    )
    buckets = _default_buckets()
    lowered = {}
    try:
        with jax.transfer_guard("disallow"):
            for b in buckets:
                lowered[b] = predict_with.lower(payload, *args(b))
    except Exception as e:
        out.append(_finding(
            "trace-transfer",
            f"lowering predict_with under transfer_guard('disallow') "
            f"raised {type(e).__name__}: {e}",
            hint="the predict path moved host data implicitly while "
                 "tracing — route every array through the arguments",
            where=where, slug="predict-transfer-guard",
        ))
        return out
    # dtype: output exactly f32, nothing f64 in the signature
    for b, lo in lowered.items():
        flat_in = jax.tree_util.tree_leaves(lo.in_avals)
        flat_out = jax.tree_util.tree_leaves(lo.out_info)
        bad64 = [a for a in flat_in + flat_out
                 if str(getattr(a, "dtype", "")) == "float64"]
        if bad64:
            out.append(_finding(
                "trace-dtype",
                f"predict lowering at bucket {b} carries float64 avals "
                f"({len(bad64)} leaves) — silent x64 promotion",
                hint="check jax_enable_x64 and literal dtypes in the "
                     "model stack",
                where=where, slug="predict-f64",
            ))
            break
    out_dtypes = {
        str(a.dtype) for a in jax.tree_util.tree_leaves(
            lowered[buckets[0]].out_info
        )
    }
    if out_dtypes != {"float32"}:
        out.append(_finding(
            "trace-dtype",
            f"predict output dtype(s) {sorted(out_dtypes)} != "
            f"{{'float32'}} — the wire format widened or narrowed",
            hint="probabilities serve as f32; cast at the boundary",
            where=where, slug="predict-out-dtype",
        ))
    # swap == cache hit: a second, DISTINCT abstract payload of identical
    # spec must produce an identical jit signature and module
    _, payload2 = _abstract_payload(cfg)
    b0 = buckets[0]
    lo2 = predict_with.lower(payload2, *args(b0))
    if lowered[b0].in_avals != lo2.in_avals:
        out.append(_finding(
            "trace-recompile",
            "lowering predict_with with a same-spec replacement payload "
            "changed the input signature — a hot swap would MISS the jit "
            "cache and recompile mid-traffic",
            hint="keep the payload a plain argument pytree; do not bake "
                 "version-dependent values into the trace",
            where=where, slug="swap-signature-mismatch",
        ))
    elif lowered[b0].as_text() != lo2.as_text():
        out.append(_finding(
            "trace-recompile",
            "same-spec payloads lowered to different modules — payload "
            "identity leaked into the executable",
            hint="no id()/hash()/host reads of the payload inside "
                 "predict_with",
            where=where, slug="swap-module-mismatch",
        ))
    # payload leaves must be parameters of the executable, not constants
    n_payload_leaves = len(jax.tree_util.tree_leaves(payload))
    n_in_leaves = len(jax.tree_util.tree_leaves(lowered[b0].in_avals))
    if n_in_leaves != n_payload_leaves + 2:
        out.append(_finding(
            "trace-recompile",
            f"lowered predict has {n_in_leaves} input leaves, expected "
            f"{n_payload_leaves} payload leaves + ids + vals — weights "
            f"were baked in as constants (every publish would recompile)",
            hint="jit the params-as-argument form "
                 "(serve.reload.build_predict_with)",
            where=where, slug="predict-params-baked",
        ))
    return out


def audit_buckets(
    buckets=None, *, max_probe: int | None = None
) -> list[Finding]:
    """Every admissible request size must land on a precompiled bucket
    shape.  Admission chunks oversized requests to <= max(buckets) rows
    (serve/batcher.py score()), so the admissible dispatch sizes are
    1..max(buckets); each must map into the bucket set and never shrink a
    request (padding only)."""
    from ..serve.batcher import admission_starts, pick_bucket

    out: list[Finding] = []
    where = "deepfm_tpu/serve/batcher.py"
    buckets = _default_buckets() if buckets is None else buckets
    bset = set(buckets)
    cap = max(buckets)
    probe = max_probe or 2 * cap
    for n in range(1, probe + 1):
        # the engine's own admission split (same range score() slices at)
        chunks = [min(cap, n - s) for s in admission_starts(n, cap)]
        for rows in chunks:
            b = pick_bucket(tuple(sorted(bset)), rows)
            if b not in bset:
                out.append(_finding(
                    "trace-recompile",
                    f"request of {n} rows dispatches {rows} rows onto "
                    f"shape {b}, which is NOT a precompiled bucket "
                    f"{sorted(bset)} — a live request would pay a compile",
                    where=where, slug="bucket-offbucket",
                ))
                return out
            if b < rows:
                out.append(_finding(
                    "trace-recompile",
                    f"bucket {b} smaller than the {rows}-row chunk it was "
                    f"picked for — rows would be truncated",
                    where=where, slug="bucket-shrink",
                ))
                return out
    return out


def audit_train_step(cfg=None) -> list[Finding]:
    """Transfer + donation + dtype contracts on the canonical train step."""
    import jax

    from ..train.step import create_train_state, jitted_train_step

    out: list[Finding] = []
    cfg = cfg or _audit_cfg()
    where = "deepfm_tpu/train/step.py"
    state = jax.eval_shape(lambda: create_train_state(cfg))
    batch = _abstract_batch(cfg, cfg.data.batch_size)
    step = jitted_train_step(cfg)
    try:
        with jax.transfer_guard("disallow"):
            lowered = step.lower(state, batch)
    except Exception as e:
        out.append(_finding(
            "trace-transfer",
            f"lowering the train step under transfer_guard('disallow') "
            f"raised {type(e).__name__}: {e}",
            hint="hoist host-side data (schedules, constants) into traced "
                 "arguments or jnp literals",
            where=where, slug="train-transfer-guard",
        ))
        return out
    # donation: the state argument's leaves must be donated in what
    # actually reached XLA
    try:
        args_info = lowered.args_info
        state_info = args_info[0][0]
        donated = [bool(getattr(a, "donated", False))
                   for a in jax.tree_util.tree_leaves(state_info)]
    except (AttributeError, IndexError, KeyError, TypeError):
        # AOT API drift: fall through to the explicit "unverified" finding
        donated = []
    if donated and not all(donated):
        n_bad = sum(1 for d in donated if not d)
        out.append(_finding(
            "trace-donation",
            f"{n_bad}/{len(donated)} train-state leaves are NOT donated — "
            f"each step copies those parameter/optimizer buffers instead "
            f"of updating in place",
            hint="jit via train.step.jitted_train_step (donate_argnums=(0,))",
            where=where, slug="train-not-donated",
        ))
    elif not donated:
        out.append(_finding(
            "trace-donation",
            "could not read donation info from the lowered train step "
            "(args_info missing) — the donation contract is unverified",
            hint="jax upgrade changed the AOT API; update the audit",
            where=where, slug="train-donation-unverified",
        ))
    # dtype: the new state must match the old leaf-for-leaf (a widening
    # state would recompile next step and double checkpoint bytes), and
    # nothing may be float64
    new_state = lowered.out_info[0]
    old_specs = [(str(a.dtype), tuple(a.shape))
                 for a in jax.tree_util.tree_leaves(state)]
    new_specs = [(str(a.dtype), tuple(a.shape))
                 for a in jax.tree_util.tree_leaves(new_state)]
    if old_specs != new_specs:
        out.append(_finding(
            "trace-dtype",
            "train step output state specs differ from its input state — "
            "dtype/shape drift means a recompile every step and "
            "checkpoint bloat",
            hint="keep updates in the parameter dtype (check optimizer "
                 "and loss literals)",
            where=where, slug="train-state-drift",
        ))
    f64 = [a for a in jax.tree_util.tree_leaves(lowered.out_info)
           if str(getattr(a, "dtype", "")) == "float64"]
    if f64:
        out.append(_finding(
            "trace-dtype",
            f"train step emits float64 ({len(f64)} leaves) — silent x64 "
            f"promotion on this backend",
            hint="check jax_enable_x64 and python-float literals",
            where=where, slug="train-f64",
        ))
    return out


# ---------------------------------------------------------------------------
# paging contract (tiered embedding store, deepfm_tpu/tiered)

# audit shapes: small but structurally real (two tables, staging pack)
_PAGED_CAPACITY = 256
_PAGED_STAGE = 64
_PAGED_BATCH = 16


def _abstract_paged_inputs(cfg, capacity: int, stage_rows: int,
                           batch_rows: int):
    """Abstract (state, batch, stage_slots, stage) for the paged step —
    every array a ShapeDtypeStruct, nothing materializes."""
    import jax
    import jax.numpy as jnp

    from ..tiered.step import PagedState, init_hot
    from ..tiered.trainer import _rest_template, _split_rest, _widths

    template = jax.eval_shape(lambda: _rest_template(cfg))
    rest, _, rest_opt, _, keys = _split_rest(cfg, template)
    widths = _widths(cfg, keys)
    hot = jax.eval_shape(lambda: init_hot(widths, capacity))
    state = PagedState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        rest=rest,
        model_state=template.model_state,
        rest_opt=rest_opt,
        hot=hot,
        rng=template.rng,
    )
    f = cfg.model.field_size
    batch = {
        "slot_ids": jax.ShapeDtypeStruct((batch_rows, f), jnp.int32),
        "feat_vals": jax.ShapeDtypeStruct((batch_rows, f), jnp.float32),
        "label": jax.ShapeDtypeStruct((batch_rows,), jnp.float32),
    }
    stage_slots = jax.ShapeDtypeStruct((stage_rows,), jnp.int32)
    stage = {
        k: {part: jax.ShapeDtypeStruct(
            (stage_rows,) if w == 1 else (stage_rows, w), jnp.float32)
            for part in ("rows", "m", "v")}
        for k, w in widths.items()
    }
    return state, batch, stage_slots, stage


def audit_paged_step(cfg=None, step_builder=None) -> list[Finding]:
    """Paging contract on the tiered steady-state train step: the lowered
    executable moves host data ONLY through the designated staging
    arguments.  ``step_builder(cfg, capacity)`` lets the seeded-violation
    tests feed a smuggling step through the same checks."""
    import jax

    out: list[Finding] = []
    cfg = cfg or _audit_cfg()
    where = "deepfm_tpu/tiered/step.py"
    if step_builder is None:
        from ..tiered.step import make_paged_train_step

        def step_builder(c, capacity):
            return make_paged_train_step(c, capacity)

    state, batch, stage_slots, stage = _abstract_paged_inputs(
        cfg, _PAGED_CAPACITY, _PAGED_STAGE, _PAGED_BATCH
    )
    step = step_builder(cfg, _PAGED_CAPACITY)
    lowered = None
    try:
        with jax.transfer_guard("disallow"):
            try:
                lowered = step.lower(state, batch, stage_slots, stage)
            except TypeError:
                # a step that dropped the staging arguments from its
                # signature (baking the pack instead) still lowers — the
                # leaf-count contract below convicts it
                lowered = step.lower(state, batch)
    except Exception as e:
        out.append(_finding(
            "trace-transfer",
            f"lowering the paged train step under "
            f"transfer_guard('disallow') raised {type(e).__name__}: {e} — "
            f"the steady-state step performs a host transfer outside the "
            f"designated staging ops",
            hint="all host data must enter via the staged miss pack / "
                 "slot-id arguments (tiered/step.py)",
            where=where, slug="paged-transfer-guard",
        ))
        return out
    # staging pack leaves must be PARAMETERS of the executable: a pack
    # baked as constants is a host transfer smuggled past the pager
    n_expected = sum(
        len(jax.tree_util.tree_leaves(t))
        for t in (state, batch, stage_slots, stage)
    )
    n_in = len(jax.tree_util.tree_leaves(lowered.in_avals))
    if n_in != n_expected:
        out.append(_finding(
            "trace-transfer",
            f"lowered paged step has {n_in} input leaves, expected "
            f"{n_expected} (state + batch + staged miss pack) — staging "
            f"data was baked into the executable instead of arriving as "
            f"arguments (an undeclared per-step host transfer)",
            hint="pass the pager's staging pack as arguments "
                 "(tiered/step.py make_paged_train_step)",
            where=where, slug="paged-staging-baked",
        ))
    # donation: hot-cache buffers must update in place
    try:
        args_info = lowered.args_info
        state_info = args_info[0][0]
        donated = [bool(getattr(a, "donated", False))
                   for a in jax.tree_util.tree_leaves(state_info)]
    except (AttributeError, IndexError, KeyError, TypeError):
        donated = []
    if donated and not all(donated):
        n_bad = sum(1 for d in donated if not d)
        out.append(_finding(
            "trace-donation",
            f"{n_bad}/{len(donated)} paged-state leaves are NOT donated — "
            f"the hot cache (rows + moments) would copy every step "
            f"instead of updating in place in HBM",
            hint="jit with donate_argnums=(0,) "
                 "(tiered/step.py make_paged_train_step)",
            where=where, slug="paged-not-donated",
        ))
    elif not donated:
        out.append(_finding(
            "trace-donation",
            "could not read donation info from the lowered paged step "
            "(args_info missing) — the paging donation contract is "
            "unverified",
            hint="jax upgrade changed the AOT API; update the audit",
            where=where, slug="paged-donation-unverified",
        ))
    # state spec stability: drift = recompile every step + cache bloat
    new_state = lowered.out_info[0]
    old_specs = [(str(a.dtype), tuple(a.shape))
                 for a in jax.tree_util.tree_leaves(state)]
    new_specs = [(str(a.dtype), tuple(a.shape))
                 for a in jax.tree_util.tree_leaves(new_state)]
    if old_specs != new_specs:
        out.append(_finding(
            "trace-dtype",
            "paged step output state specs differ from its input state — "
            "the steady-state executable would recompile every step",
            where=where, slug="paged-state-drift",
        ))
    f64 = [a for a in jax.tree_util.tree_leaves(lowered.out_info)
           if str(getattr(a, "dtype", "")) == "float64"]
    if f64:
        out.append(_finding(
            "trace-dtype",
            f"paged step emits float64 ({len(f64)} leaves) — silent x64 "
            f"promotion",
            where=where, slug="paged-f64",
        ))
    return out


# ---------------------------------------------------------------------------
# collective-traffic contract (sharded-lookup exchange, parallel/embedding.py)

_COLLECTIVE_OPS = (
    "all_reduce", "all_gather", "all_to_all", "reduce_scatter",
    "collective_permute",
)

# per-mode expected collective sets for the sharded train step — the
# contract the audit enforces, recorded here as data so tests/docs and the
# finding messages share one source of truth
EXCHANGE_CONTRACT = {
    "psum": {
        "requires": "all_reduce over the dense [B_local, F(, K)] row "
                    "tensor (zeros-plus-psum assembly, fwd+bwd)",
        "forbids": None,
    },
    "alltoall": {
        "requires": "all_to_all request/response pair outside any "
                    "conditional region",
        "forbids": "all_reduce/all_gather of the dense [B_local, F(, K)] "
                   "row tensor outside stablehlo.case (the capacity-"
                   "overflow fallback branches)",
    },
    "alltoall_lazy": {
        "requires": "all_to_all forward exchange; all_gather only of the "
                    "capacity-bounded unique pack",
        "forbids": "all_gather of the full [B_local*F, K] occurrence-grad "
                   "stream outside stablehlo.case",
    },
}


def _replica_groups(line: str) -> list[list[int]] | None:
    """Parse a collective op's ``replica_groups = dense<[[..], ..]>``
    attribute — the device grouping that tells WHICH mesh axis the
    collective rides (the zero-update contract must tell a data-axis
    grad all-reduce from the model-axis psum of the row assembly)."""
    import re

    m = re.search(r"replica_groups\s*=\s*dense<\[\[(.*?)\]\]>", line)
    if not m:
        return None
    try:
        return [
            [int(x) for x in grp.split(",") if x.strip()]
            for grp in m.group(1).split("], [")
        ]
    except ValueError:
        return None


def collective_axis(groups, dp: int, mp: int) -> str | None:
    """Classify a collective's replica groups on a [dp, mp] mesh laid out
    data-major (parallel/mesh.build_mesh): the DATA axis groups are mp
    many, each dp devices stride mp apart; the MODEL axis groups are dp
    many, each mp consecutive devices.  None = no groups parsed;
    'other' = neither single axis (e.g. a both-axes collective)."""
    if not groups:
        return None
    sizes = {len(g) for g in groups}
    if sizes == {dp} and len(groups) == mp and all(
        g[i + 1] - g[i] == mp for g in groups for i in range(len(g) - 1)
    ):
        return "data"
    if sizes == {mp} and len(groups) == dp and all(
        g[i + 1] - g[i] == 1 for g in groups for i in range(len(g) - 1)
    ):
        return "model"
    return "other"


def _tensor_shapes(line: str) -> list[tuple[int, ...]]:
    """Operand shapes from an op's `: (tensor<AxBxDT>, ...) ->` signature."""
    import re

    m = re.search(r":\s*\(([^)]*)\)\s*->", line)
    if not m:
        return []
    shapes = []
    for dims in re.findall(r"tensor<([0-9]+(?:x[0-9]+)*)x?[a-z]", m.group(1)):
        shapes.append(tuple(int(d) for d in dims.split("x")))
    return shapes


def summarize_collectives(mlir_text: str) -> list[dict]:
    """Scan lowered StableHLO text for collective ops: kind, operand
    shapes, and WHICH conditional branch (if any) each op sits in.

    ``branch`` is ``None`` for the unconditional main line, else the
    ``(cond_id, branch_index)`` of the innermost ``stablehlo.case``/``if``
    region — the lax.cond capacity-overflow structure, whose exchange and
    dense-fallback arms the contract must tell apart.  Region-carrying ops
    (all_reduce) print their type signature on the region's closing line;
    the scanner tracks brace depth to pick it up, to advance branch
    indices at ``}, {`` separators, and to know when a region ends."""
    out: list[dict] = []
    depth = 0
    cond_id = 0
    # stack of [open_depth, cond_id, branch_index]
    cond_stack: list[list[int]] = []
    pending: tuple[dict, int] | None = None
    for line in mlir_text.splitlines():
        if cond_stack and line.strip() == "}, {" \
                and depth == cond_stack[-1][0] + 1:
            cond_stack[-1][2] += 1
        if "stablehlo.case" in line or "stablehlo.if" in line:
            cond_id += 1
            cond_stack.append([depth, cond_id, 0])
        kind = next(
            (k for k in _COLLECTIVE_OPS if f"stablehlo.{k}" in line), None
        )
        if kind is not None:
            entry = {
                "op": kind,
                "shapes": _tensor_shapes(line),
                "groups": _replica_groups(line),
                "branch": (
                    (cond_stack[-1][1], cond_stack[-1][2])
                    if cond_stack else None
                ),
            }
            out.append(entry)
            if not entry["shapes"]:
                pending = (entry, depth)
        depth += line.count("{") - line.count("}")
        if pending is not None and depth <= pending[1]:
            if not pending[0]["shapes"]:
                pending[0]["shapes"] = _tensor_shapes(line)
            pending = None
        while cond_stack and depth <= cond_stack[-1][0]:
            cond_stack.pop()
    return out


def check_exchange_collectives(
    mlir_text: str,
    dense_shapes: set[tuple[int, ...]],
    *,
    mode: str,
    variant: str = "dense",
    where: str = "deepfm_tpu/parallel/embedding.py",
) -> list[Finding]:
    """Hold one lowered train step to the per-mode collective contract
    (:data:`EXCHANGE_CONTRACT`).  Factored out of :func:`audit_spmd_exchange`
    so the seeded-violation test can feed a psum-mode lowering through the
    alltoall contract and watch it get caught."""
    cols = summarize_collectives(mlir_text)
    seen = sorted({
        (c["op"], "main" if c["branch"] is None else "cond") for c in cols
    })

    def is_dense(c):
        return (c["op"] in ("all_reduce", "all_gather")
                and any(s in dense_shapes for s in c["shapes"]))

    out: list[Finding] = []
    if mode == "psum":
        if not any(is_dense(c) for c in cols):
            out.append(_finding(
                "trace-collective",
                f"psum-mode train step lowering shows NO dense row-tensor "
                f"all-reduce/all-gather (expected {sorted(dense_shapes)}) "
                f"— the collective detector or the lowering drifted; "
                f"observed collectives: {seen}",
                hint="update the audit's shape derivation or the scanner "
                     "(summarize_collectives)",
                where=where, slug=f"{variant}-psum-detector-blind",
            ))
        return out
    # alltoall contract: the main line may never move the dense row
    # tensor; inside each lax.cond, dense collectives may live only in
    # the fallback arm — never alongside the all_to_all exchange
    contract = EXCHANGE_CONTRACT[
        "alltoall_lazy" if variant == "lazy" else "alltoall"
    ]
    main_dense = [c for c in cols if is_dense(c) and c["branch"] is None]
    if main_dense:
        out.append(_finding(
            "trace-collective",
            f"{variant} train step in shard_exchange='alltoall' still "
            f"moves the dense row tensor on the UNCONDITIONAL main line: "
            f"{[(c['op'], c['shapes']) for c in main_dense]} (dense "
            f"shapes {sorted(dense_shapes)}); contract: "
            f"{contract['forbids']}; observed "
            f"collectives: {seen}",
            hint="the exchange must dedup and route owned rows via "
                 "all_to_all; dense collectives belong only in the "
                 "lax.cond overflow fallback arm",
            where=where, slug=f"{variant}-alltoall-dense-collective",
        ))
    branches: dict = {}
    for c in cols:
        if c["branch"] is not None:
            b = branches.setdefault(c["branch"], {"a2a": False, "dense": False})
            b["a2a"] = b["a2a"] or c["op"] == "all_to_all"
            b["dense"] = b["dense"] or is_dense(c)
    leaky = [k for k, b in branches.items() if b["a2a"] and b["dense"]]
    if leaky:
        out.append(_finding(
            "trace-collective",
            f"{variant} train step in shard_exchange='alltoall' has "
            f"conditional branch(es) {leaky} carrying BOTH the all_to_all "
            f"exchange and a dense row-tensor collective — the dense "
            f"traffic leaked into the exchange arm; observed "
            f"collectives: {seen}",
            hint="only the lax.cond fallback arm may be dense",
            where=where, slug=f"{variant}-alltoall-dense-in-exchange-arm",
        ))
    if not any(c["op"] == "all_to_all" for c in cols):
        out.append(_finding(
            "trace-collective",
            f"{variant} train step in shard_exchange='alltoall' lowered "
            f"WITHOUT any all_to_all — the exchange is not in effect; "
            f"observed collectives: {seen}",
            hint="check resolve_shard_exchange wiring "
                 "(parallel/embedding.py, parallel/spmd.py)",
            where=where, slug=f"{variant}-alltoall-missing",
        ))
    return out


def audit_spmd_exchange(cfg=None) -> list[Finding]:
    """Collective-traffic contract on the real SPMD train step (lowering
    only — nothing executes, tables stay abstract).  Needs the 8-device
    virtual mesh (tests/conftest.py and scripts/check.sh arrange it);
    vacuous on smaller topologies (e.g. a single real TPU chip)."""
    import sys

    import jax

    if len(jax.devices()) < 8:
        # not silent: a --write-baseline run on a blind topology must not
        # look like a clean contract
        print(
            "trace-audit: SPMD collective contract SKIPPED — needs >= 8 "
            "devices (run under JAX_PLATFORMS=cpu with "
            "--xla_force_host_platform_device_count=8; scripts/check.sh "
            "and the analysis CLI arrange this)",
            file=sys.stderr,
        )
        return []
    from ..core.config import MeshConfig
    from ..parallel import (
        abstract_spmd_state, build_mesh, make_context, make_spmd_train_step,
    )

    base = (cfg or _audit_cfg()).with_overrides(data={"batch_size": 128})
    mesh = build_mesh(MeshConfig(data_parallel=2, model_parallel=4))

    def lowered_text(mode: str, lazy: bool) -> tuple[str, object]:
        c = base.with_overrides(
            model={"shard_exchange": mode},
            optimizer={"lazy_embedding_updates": lazy},
        )
        ctx = make_context(c, mesh)
        state = abstract_spmd_state(ctx)
        f = c.model.field_size
        b = c.data.batch_size
        batch = {
            "feat_ids": jax.ShapeDtypeStruct((b, f), jax.numpy.int32),
            "feat_vals": jax.ShapeDtypeStruct((b, f), jax.numpy.float32),
            "label": jax.ShapeDtypeStruct((b,), jax.numpy.float32),
        }
        step = make_spmd_train_step(ctx, donate=False)
        return step.lower(state, batch).as_text(), ctx

    out: list[Finding] = []
    b_local = base.data.batch_size // 2
    f = base.model.field_size
    k = base.model.embedding_size
    dense_rows = {(b_local, f, k), (b_local, f)}
    n_local = b_local * f
    lazy_dense = {(n_local, k), (n_local, 1), (n_local,)}
    for mode, lazy, shapes, variant in (
        ("psum", False, dense_rows, "dense"),
        ("alltoall", False, dense_rows, "dense"),
        ("alltoall", True, dense_rows | lazy_dense, "lazy"),
    ):
        text, _ = lowered_text(mode, lazy)
        out.extend(check_exchange_collectives(
            text, shapes, mode=mode, variant=variant,
        ))
    return out


# ---------------------------------------------------------------------------
# sharded-predict contract (shard-group serving pool, deepfm_tpu/serve/pool)

# the serve-group topologies the pool's bit-parity tests pin — both are
# audited so neither mesh orientation can regress silently
_SERVE_AUDIT_MESHES = ((2, 4), (4, 2))


def _bucket_divisibility(buckets, data_parallel: int) -> list[Finding]:
    """The per-dp half of the group recompile contract: every bucket
    must shard evenly over the group's data axis — an indivisible bucket
    would need a padded per-shard shape the engine never compiled, i.e.
    a live-request compile."""
    where = "deepfm_tpu/serve/pool/worker.py"
    dp = max(1, int(data_parallel))
    bad = sorted(int(b) for b in buckets if int(b) % dp != 0)
    if not bad:
        return []
    return [_finding(
        "trace-recompile",
        f"bucket shapes {bad} do not divide over the serve group's "
        f"data_parallel={dp} — the dispatch cannot shard evenly and "
        f"would lower a shape no group executable was compiled for",
        hint="pick bucket sizes divisible by the group mesh's data "
             "axis (GroupMember validates this at construction)",
        where=where, slug="serve-bucket-indivisible",
    )]


def audit_group_buckets(
    buckets=None, data_parallel: int = 1
) -> list[Finding]:
    """Recompile contract for ONE shard-group's engine: every admissible
    dispatch size must land on a precompiled bucket (audit_buckets) that
    shards evenly over the group's data axis (_bucket_divisibility)."""
    buckets = _default_buckets() if buckets is None else buckets
    return (list(audit_buckets(buckets))
            + _bucket_divisibility(buckets, data_parallel))


def audit_sharded_predict(cfg=None, predict_builder=None) -> list[Finding]:
    """The shard-group predict's lowering contract
    (serve/pool/sharded.py), on every audited serve mesh:

    * **transfer** — every bucket lowers under
      ``transfer_guard('disallow')``: weights and ids enter only through
      the declared arguments;
    * **collective traffic** — in ``alltoall`` mode the lowering carries
      the all_to_all request/response pair and NO dense row-tensor
      all-reduce/all-gather outside the ``stablehlo.case`` fallback arms
      (:data:`EXCHANGE_CONTRACT`); the ``psum``-mode lowering must show
      the dense all-reduce (detector self-check — a blind scanner fails
      loudly instead of passing vacuously);
    * **swap is a cache hit / no mixed-generation executable** — two
      distinct same-spec payloads lower to identical signatures and
      modules, and the payload leaves appear as lowered PARAMETERS: a
      group commit can never recompile mid-traffic, and no version- or
      generation-dependent value can be baked into an executable (which
      is what a "mixed-generation executable" would be);
    * **recompile coverage** — every admissible request size per group
      maps onto a precompiled bucket that shards evenly over the group's
      data axis (:func:`audit_group_buckets`).

    ``predict_builder(ctx)`` lets the seeded-violation tests feed a
    contract-breaking predict (baked payload, psum lowering labeled
    alltoall) through the same checks."""
    import sys

    import jax

    if len(jax.devices()) < 8:
        print(
            "trace-audit: sharded-predict contract SKIPPED — needs >= 8 "
            "devices (run under JAX_PLATFORMS=cpu with "
            "--xla_force_host_platform_device_count=8; scripts/check.sh "
            "and the analysis CLI arrange this)",
            file=sys.stderr,
        )
        return []
    from ..serve.pool.sharded import (
        abstract_serve_payload,
        build_serve_mesh,
        build_sharded_predict_with,
        make_serve_context,
    )

    base = cfg or _audit_cfg()
    where = "deepfm_tpu/serve/pool/sharded.py"
    builder = predict_builder or build_sharded_predict_with
    out: list[Finding] = []
    buckets = _default_buckets()
    for dp, mp in _SERVE_AUDIT_MESHES:
        mesh = build_serve_mesh(dp, mp)
        ctx = make_serve_context(base, mesh, exchange="alltoall")
        payload = abstract_serve_payload(ctx)
        predict_with = builder(ctx)
        f = ctx.cfg.model.field_size

        def args(b):
            return (
                jax.ShapeDtypeStruct((b, f), jax.numpy.int64),
                jax.ShapeDtypeStruct((b, f), jax.numpy.float32),
            )

        def lower_with(pay, a):
            try:
                return predict_with.lower(pay, *a)
            except TypeError:
                # a predict that dropped the payload argument (weights —
                # and therefore a generation — baked into the executable)
                # still lowers; the leaf-count contract below convicts it
                return predict_with.lower(*a)

        lowered = {}
        try:
            with jax.transfer_guard("disallow"):
                for b in buckets:
                    lowered[b] = lower_with(payload, args(b))
        except Exception as e:
            out.append(_finding(
                "trace-transfer",
                f"lowering the sharded predict on mesh [{dp},{mp}] under "
                f"transfer_guard('disallow') raised "
                f"{type(e).__name__}: {e}",
                hint="the sharded predict moved host data implicitly — "
                     "weights and ids must be arguments",
                where=where, slug=f"serve-{dp}x{mp}-transfer-guard",
            ))
            continue
        # collective traffic: the per-shard dense row tensor must not
        # ride an all-reduce/all-gather outside the fallback arm
        b0 = max(buckets)
        b_local = b0 // dp
        k = ctx.cfg.model.embedding_size
        dense = {(b_local, f, k), (b_local, f)}
        out.extend(check_exchange_collectives(
            lowered[b0].as_text(), dense, mode="alltoall",
            variant=f"serve-{dp}x{mp}", where=where,
        ))
        # swap == cache hit, and no generation can bake into the module
        payload2 = abstract_serve_payload(ctx)
        b1 = buckets[0]
        lo2 = lower_with(payload2, args(b1))
        if lowered[b1].in_avals != lo2.in_avals:
            out.append(_finding(
                "trace-recompile",
                f"sharded predict on mesh [{dp},{mp}]: a same-spec "
                f"replacement payload changed the input signature — a "
                f"group commit would MISS the jit cache and recompile "
                f"mid-traffic",
                hint="keep the payload a plain argument pytree "
                     "(serve/pool/sharded.py build_sharded_predict_with)",
                where=where, slug=f"serve-{dp}x{mp}-swap-signature",
            ))
        elif lowered[b1].as_text() != lo2.as_text():
            out.append(_finding(
                "trace-recompile",
                f"sharded predict on mesh [{dp},{mp}]: same-spec payloads "
                f"lowered to different modules — payload identity (a "
                f"version/generation) leaked into the executable",
                hint="no host reads of the payload inside the predict",
                where=where, slug=f"serve-{dp}x{mp}-swap-module",
            ))
        n_payload = len(jax.tree_util.tree_leaves(payload))
        n_in = len(jax.tree_util.tree_leaves(lowered[b1].in_avals))
        if n_in != n_payload + 2:
            out.append(_finding(
                "trace-recompile",
                f"sharded predict on mesh [{dp},{mp}] has {n_in} input "
                f"leaves, expected {n_payload} payload leaves + ids + "
                f"vals — weights were baked in as constants (every group "
                f"commit would recompile, and mid-swap the members would "
                f"serve MIXED-generation executables)",
                hint="jit the params-as-argument form "
                     "(serve/pool/sharded.py build_sharded_predict_with)",
                where=where, slug=f"serve-{dp}x{mp}-params-baked",
            ))
        # detector self-check: the psum lowering must show the dense
        # all-reduce, or the alltoall pass above proves nothing
        ctx_psum = make_serve_context(base, mesh, exchange="psum")
        psum_pw = builder(ctx_psum)
        try:
            psum_text = psum_pw.lower(
                abstract_serve_payload(ctx_psum), *args(b0)
            ).as_text()
        except TypeError:
            psum_text = psum_pw.lower(*args(b0)).as_text()
        out.extend(check_exchange_collectives(
            psum_text, dense, mode="psum",
            variant=f"serve-{dp}x{mp}", where=where,
        ))
        # per-dp recompile coverage (the mesh-independent admission map
        # is audited once by run_trace_audit's audit_buckets pass —
        # re-running it per mesh would duplicate its findings)
        out.extend(_bucket_divisibility(buckets, dp))
    return out


def audit_multitenant(cfg=None, predict_builder=None,
                      tenant_models=None) -> list[Finding]:
    """The fleet's executable-sharing contract (deepfm_tpu/fleet): N
    same-spec tenants on one pool serve from ONE precompiled executable
    set — tenant selection is a payload pick, never a recompile.

    Lower the shard-group predict ONCE (the claimed shared executable)
    and feed it two DISTINCT tenant payloads:

    * **identical modules** — every tenant payload of the pool spec must
      lower to the same input signature and the same module text: a
      divergent lowering means a tenant claimed executables it cannot
      share (each request would recompile or serve a per-tenant module);
    * **payload leaves as parameters** — the tenant's weights must appear
      as lowered PARAMETERS, not baked constants: a baked tenant payload
      is the per-tenant-module regression in disguise (every tenant swap
      compiles, and mid-swap the members serve mixed-tenant executables);
    * **transfer-guard-clean** — tenant payloads enter through the
      declared arguments only.

    ``tenant_models`` (per-tenant model-override dicts, default two
    same-spec tenants) and ``predict_builder`` let the seeded-violation
    tests (tests/test_analysis.py) feed spec-DIVERGENT tenants claiming
    one executable, and a tenant payload baked as a constant, through
    the same checks."""
    import sys

    import jax

    if len(jax.devices()) < 8:
        print(
            "trace-audit: multitenant contract SKIPPED — needs >= 8 "
            "devices (run under JAX_PLATFORMS=cpu with "
            "--xla_force_host_platform_device_count=8; scripts/check.sh "
            "and the analysis CLI arrange this)",
            file=sys.stderr,
        )
        return []
    from ..core.config import tenant_spec_divergence
    from ..serve.pool.sharded import (
        abstract_serve_payload,
        build_serve_mesh,
        build_sharded_predict_with,
        make_serve_context,
    )

    base = cfg or _audit_cfg()
    where = "deepfm_tpu/fleet/registry.py"
    out: list[Finding] = []
    overrides = list(tenant_models) if tenant_models is not None \
        else [{}, {}]
    mesh = build_serve_mesh(2, 4)
    ctx = make_serve_context(base, mesh, exchange="alltoall")
    predict_with = (predict_builder or build_sharded_predict_with)(ctx)
    f = ctx.cfg.model.field_size
    b = _default_buckets()[0]
    args = (
        jax.ShapeDtypeStruct((b, f), jax.numpy.int64),
        jax.ShapeDtypeStruct((b, f), jax.numpy.float32),
    )

    def lower_with(pay):
        try:
            return predict_with.lower(pay, *args)
        except TypeError:
            # a predict that dropped the payload argument (tenant weights
            # baked in) still lowers; the leaf-count contract convicts it
            return predict_with.lower(*args)

    import dataclasses as _dc

    base_model = _dc.asdict(base.model)
    ref = None
    for i, ov in enumerate(overrides):
        t_cfg = base.with_overrides(model=ov) if ov else base
        t_ctx = (make_serve_context(t_cfg, mesh, exchange="alltoall")
                 if ov else ctx)
        payload = abstract_serve_payload(t_ctx)
        diff = tenant_spec_divergence(base_model, ov)
        try:
            with jax.transfer_guard("disallow"):
                lo = lower_with(payload)
        except Exception as e:
            out.append(_finding(
                "trace-recompile",
                f"tenant {i}'s payload cannot lower through the pool's "
                f"shared executable ({type(e).__name__}: {e}) — a "
                f"spec-divergent tenant is claiming one executable"
                + (f" (diverging fields: {diff})" if diff else ""),
                hint="same-spec tenants only: serve a divergent spec "
                     "from its own pool (core.config."
                     "EXECUTABLE_SPEC_FIELDS)",
                where=where, slug=f"multitenant-{i}-lower",
            ))
            continue
        if ref is None:
            ref = lo
            # payload leaves as lowered parameters — the baked-tenant
            # discriminator
            n_payload = len(jax.tree_util.tree_leaves(payload))
            n_in = len(jax.tree_util.tree_leaves(lo.in_avals))
            if n_in != n_payload + 2:
                out.append(_finding(
                    "trace-recompile",
                    f"the shared predict has {n_in} input leaves, "
                    f"expected {n_payload} payload leaves + ids + vals — "
                    f"a tenant payload was baked in as constants (every "
                    f"tenant swap would compile a NEW executable and "
                    f"members would serve per-tenant modules)",
                    hint="jit the payload-as-argument form "
                         "(serve/pool/sharded.py "
                         "build_sharded_predict_with)",
                    where=where, slug="multitenant-baked",
                ))
            continue
        if lo.in_avals != ref.in_avals:
            out.append(_finding(
                "trace-recompile",
                f"tenant {i}'s payload changed the lowered input "
                f"signature — spec-divergent tenants claiming one "
                f"executable (every request mixing tenants would "
                f"recompile)"
                + (f"; diverging fields: {diff}" if diff else ""),
                hint="same-spec tenants only (core.config."
                     "EXECUTABLE_SPEC_FIELDS); the fleet registry and "
                     "config validation both refuse this at load",
                where=where, slug=f"multitenant-{i}-signature",
            ))
        elif lo.as_text() != ref.as_text():
            out.append(_finding(
                "trace-recompile",
                f"tenant {i}'s same-spec payload lowered to a DIFFERENT "
                f"module — tenant identity leaked into the executable "
                f"(the pool would serve per-tenant modules)",
                hint="no host reads of the payload inside the predict",
                where=where, slug=f"multitenant-{i}-module",
            ))
    return out


# ---------------------------------------------------------------------------
# funnel contract (recommendation funnel, deepfm_tpu/funnel)

# both serve-mesh orientations, like the sharded-predict audit
_FUNNEL_AUDIT_MESHES = ((2, 4), (4, 2))
# corpus capacity chosen so no per-shard row count (capacity/mp) or the
# capacity itself collides with any candidate-pack dimension (B_local, K,
# mp*K) on the audited meshes — the corpus-collective check keys on dims
_FUNNEL_CAPACITY = 96
_FUNNEL_K = 8
_FUNNEL_N = 4


def _funnel_audit_ctx(mesh, retrieval: str = "exact"):
    from ..funnel.index import make_funnel_context

    rank_cfg = _audit_cfg()
    query_cfg = _audit_cfg("two_tower").with_overrides(model={
        "user_vocab_size": 499, "item_vocab_size": 499,
        "user_field_size": 4, "item_field_size": 4,
        "tower_layers": (32,), "tower_dim": 16, "embedding_size": 8,
    })
    extra = {}
    if retrieval == "int8":
        # a scan tile that collides with no corpus dim (capacity 96,
        # per-shard 48/24 on the audited meshes): the per-tile dequant
        # [tile, D] f32 must be distinguishable from a whole-corpus one
        extra = dict(oversample=2, retrieval_tile=16)
    return make_funnel_context(
        rank_cfg, query_cfg, mesh,
        capacity=_FUNNEL_CAPACITY, top_k=_FUNNEL_K, return_n=_FUNNEL_N,
        retrieval=retrieval, **extra,
    )


def _op_result_types(line: str) -> list[str]:
    """The result tensor type(s) of one StableHLO op line: the types
    after the LAST ``->`` (function-type annotations), or the single
    trailing type for ops annotated ``: tensor<...>``."""
    import re

    if "->" in line:
        tail = line.rsplit("->", 1)[1]
    elif " : " in line and "=" in line:
        tail = line.rsplit(" : ", 1)[1]
    else:
        return []
    return re.findall(r"tensor<([^>]*)>", tail)


def _dims_of(tensor_type: str) -> tuple[list[int], str] | None:
    """``"24x16xf32" -> ([24, 16], "f32")``; None for non-static shapes
    (scalars have no dims and parse to ``([], dtype)``)."""
    parts = tensor_type.split("x")
    dims: list[int] = []
    for p in parts[:-1]:
        if not p.isdigit():
            return None
        dims.append(int(p))
    return dims, parts[-1]


# partitioning plumbing whose results legitimately carry full-corpus
# types: the global->per-shard reshape custom_calls and the shard_map
# argument threading
_SHARDING_MARKERS = ("@Sharding", "@SPMDFullToShardShape",
                    "@SPMDShardToFullShape")


def _corpus_f32_results(text: str, corpus_dims: set[int]) -> list[str]:
    """Lines whose op RESULT is an f32 tensor carrying a corpus-sized
    dimension.  Function signatures and the sharding custom_calls are
    exempt (the f32 item_emb legitimately ENTERS as an argument — the
    contract is that the int8 scorer never computes with it at corpus
    width, only through shortlist-sized gathers)."""
    bad = []
    for ln in text.splitlines():
        s = ln.strip()
        if (s.startswith("func.func")
                or any(m in s for m in _SHARDING_MARKERS)):
            continue
        for t in _op_result_types(s):
            parsed = _dims_of(t)
            if parsed is None:
                continue
            dims, dtype = parsed
            if dtype == "f32" and any(d in corpus_dims for d in dims):
                bad.append(s.split(" : ")[0][:100])
                break
    return bad


def _corpus_gather_results(text: str, corpus_dims: set[int]) -> list[str]:
    """Gather ops whose RESULT carries a corpus-sized dimension — the
    rescore must gather [B, K*oversample, D] shortlists, never anything
    corpus-wide."""
    bad = []
    for ln in text.splitlines():
        s = ln.strip()
        if "stablehlo.gather" not in s and "stablehlo.dynamic_gather" \
                not in s:
            continue
        for t in _op_result_types(s):
            parsed = _dims_of(t)
            if parsed is None:
                continue
            dims, _ = parsed
            if any(d in corpus_dims for d in dims):
                bad.append(s.split(" : ")[0][:100])
                break
    return bad


def audit_funnel(cfg=None, retrieve_builder=None,
                 modes=None) -> list[Finding]:
    """The recommendation funnel's lowering contract
    (funnel/index.py), on every audited serve mesh:

    * **transfer** — the retrieval executable AND the expand+rank
      executable lower under ``transfer_guard('disallow')`` at every
      bucket shape: queries, ranking rows, weights and the index enter
      only through declared arguments;
    * **index is a parameter** — every payload leaf (query tower, rank
      weights, index arrays) appears in the lowered signature: a baked
      index would turn every refresh into a recompile (and pin serving
      to one corpus snapshot forever);
    * **per-shard top-k present** — the retrieval lowering carries the
      ``top_k`` selection (per-shard ``lax.top_k``), i.e. candidate
      selection happens BEFORE any collective;
    * **no full-corpus score gather** — no collective operand carries a
      corpus-sized dimension (capacity or capacity/model_parallel): only
      the [B_local, K] candidate packs may cross the wire.  A lowering
      that gathers per-shard score tensors and top-ks globally moves
      corpus-proportional bytes per query batch — the exact failure this
      contract exists to catch;
    * **refresh is a cache hit** — two distinct same-spec payloads lower
      to identical signatures and modules: an index/weights republish
      can never recompile mid-traffic.

    The int8 retrieval mode (``funnel_retrieval``, funnel/quant.py) is
    audited alongside exact with two additional lowering checks:

    * **no corpus-sized f32 result** — no op in the int8 retrieve may
      MATERIALIZE an f32 tensor with a corpus dimension: scoring streams
      int8 tiles and dequantizes tile-by-tile, so the largest live f32
      is tile-sized (the bandwidth saving IS the contract);
    * **no corpus-sized gather** — the exact rescore gathers only the
      [B, K*oversample, D] shortlist from the f32 rows; a gather whose
      result is corpus-sized re-reads what quantization saved.

    ``retrieve_builder(ctx)`` lets the seeded-violation tests feed a
    contract-breaking retrieve (full-score gather, baked index,
    whole-corpus dequantize, corpus-wide rescore gather) through the
    same checks; ``modes`` restricts which retrieval modes are audited
    (default: exact + int8 for the real builder, exact only for a
    seeded one — violation builders target one mode's payload tree)."""
    import sys

    import jax

    if len(jax.devices()) < 8:
        print(
            "trace-audit: funnel contract SKIPPED — needs >= 8 devices "
            "(run under JAX_PLATFORMS=cpu with "
            "--xla_force_host_platform_device_count=8; scripts/check.sh "
            "and the analysis CLI arrange this)",
            file=sys.stderr,
        )
        return []
    from ..funnel.index import (
        abstract_funnel_payload,
        build_rank_topn_with,
        build_retrieve_with,
    )
    from ..serve.pool.sharded import build_serve_mesh

    where = "deepfm_tpu/funnel/index.py"
    builder = retrieve_builder or build_retrieve_with
    if modes is None:
        # a seeded violation builder targets ONE mode's payload tree;
        # default it to exact (the pre-existing seeded tests) and let
        # int8-violation tests pass modes=("int8",) explicitly
        modes = ("exact",) if retrieve_builder is not None \
            else ("exact", "int8")
    out: list[Finding] = []
    buckets = _default_buckets()
    for dp, mp in _FUNNEL_AUDIT_MESHES:
      mesh = build_serve_mesh(dp, mp)
      for mode in modes:
        ctx = _funnel_audit_ctx(mesh, mode)
        tag = f"{dp}x{mp}" if mode == "exact" else f"{dp}x{mp}-{mode}"
        payload = abstract_funnel_payload(ctx)
        retrieve_with = builder(ctx)
        rank_with = build_rank_topn_with(ctx)
        fu, f = ctx.user_fields, ctx.rank_fields
        k = ctx.top_k

        def q_args(b):
            return (
                jax.ShapeDtypeStruct((b, fu), jax.numpy.int64),
                jax.ShapeDtypeStruct((b, fu), jax.numpy.float32),
            )

        def r_args(b):
            return (
                jax.ShapeDtypeStruct((b, f), jax.numpy.int64),
                jax.ShapeDtypeStruct((b, f), jax.numpy.float32),
                jax.ShapeDtypeStruct((b, k), jax.numpy.int32),
                jax.ShapeDtypeStruct((b, k), jax.numpy.float32),
            )

        def lower_with(fn, pay, args):
            try:
                return fn.lower(pay, *args)
            except TypeError:
                # a build that dropped the payload argument (index or
                # weights baked as constants) still lowers — the
                # leaf-count contract below convicts it
                return fn.lower(*args)

        lowered_q, lowered_r = {}, {}
        try:
            with jax.transfer_guard("disallow"):
                for b in buckets:
                    lowered_q[b] = lower_with(retrieve_with, payload,
                                              q_args(b))
                    lowered_r[b] = lower_with(rank_with, payload, r_args(b))
        except Exception as e:
            out.append(_finding(
                "trace-transfer",
                f"lowering the funnel executables on mesh [{dp},{mp}] "
                f"({mode}) under transfer_guard('disallow') raised "
                f"{type(e).__name__}: {e}",
                hint="queries, ranking rows, weights and the index must "
                     "enter through arguments (funnel/index.py)",
                where=where, slug=f"funnel-{tag}-transfer-guard",
            ))
            continue
        b0 = max(buckets)
        text = lowered_q[b0].as_text()
        # per-shard top-k must exist — selection before any collective
        if "top_k" not in text:
            out.append(_finding(
                "trace-collective",
                f"funnel retrieve on mesh [{dp},{mp}] ({mode}) lowered "
                f"WITHOUT a top_k selection — candidates are not reduced "
                f"per shard before the merge",
                hint="per-shard lax.top_k then candidate-pack all_gather "
                     "(funnel/index.build_retrieve_with)",
                where=where, slug=f"funnel-{tag}-topk-missing",
            ))
        # no collective may move a corpus-sized operand
        corpus_dims = {_FUNNEL_CAPACITY, _FUNNEL_CAPACITY // mp}
        bad = [
            c for c in summarize_collectives(text)
            if any(d in corpus_dims for s in c["shapes"] for d in s)
        ]
        if bad:
            out.append(_finding(
                "trace-collective",
                f"funnel retrieve on mesh [{dp},{mp}] ({mode}) moves a "
                f"corpus-sized tensor through a collective: "
                f"{[(c['op'], c['shapes']) for c in bad]} (corpus dims "
                f"{sorted(corpus_dims)}) — only the [B_local, K] "
                f"candidate packs may cross the wire",
                hint="score and top-k per shard; gather candidate packs, "
                     "never the score tensor (funnel/index.py)",
                where=where, slug=f"funnel-{tag}-corpus-gather",
            ))
        if ctx.retrieval_mode == "int8":
            # the quantized tier's bandwidth contract: int8 streams,
            # tile-sized f32, shortlist-sized rescore gathers only
            bad_f32 = _corpus_f32_results(text, corpus_dims)
            if bad_f32:
                out.append(_finding(
                    "trace-quantized",
                    f"int8 funnel retrieve on mesh [{dp},{mp}] "
                    f"materializes corpus-sized f32 results: "
                    f"{bad_f32[:3]} (corpus dims {sorted(corpus_dims)}) "
                    f"— the quantized scorer must stream int8 tiles and "
                    f"hold only tile-sized f32",
                    hint="dequantize per scan tile "
                         "(funnel/quant.score_topk_tiles); never "
                         "codes.astype(f32) over the whole shard",
                    where=where, slug=f"funnel-{tag}-corpus-f32",
                ))
            bad_gather = _corpus_gather_results(text, corpus_dims)
            if bad_gather:
                out.append(_finding(
                    "trace-quantized",
                    f"int8 funnel retrieve on mesh [{dp},{mp}] gathers "
                    f"a corpus-sized result: {bad_gather[:3]} (corpus "
                    f"dims {sorted(corpus_dims)}) — the exact rescore "
                    f"may gather only the [B, K*oversample, D] "
                    f"shortlist",
                    hint="jnp.take the shortlist rows only "
                         "(funnel/index.build_retrieve_with int8 branch)",
                    where=where, slug=f"funnel-{tag}-rescore-gather",
                ))
        # payload leaves (incl. the index) must be lowered PARAMETERS
        n_payload = len(jax.tree_util.tree_leaves(payload))
        for name, lo, extra in (("retrieve", lowered_q[b0], 2),
                                ("rank", lowered_r[b0], 4)):
            n_in = len(jax.tree_util.tree_leaves(lo.in_avals))
            if n_in != n_payload + extra:
                out.append(_finding(
                    "trace-recompile",
                    f"funnel {name} on mesh [{dp},{mp}] ({mode}) has "
                    f"{n_in} input leaves, expected {n_payload} payload "
                    f"leaves + {extra} — weights or the index were baked "
                    f"in as constants (every index refresh would "
                    f"recompile)",
                    hint="pass the combined funnel payload as an argument "
                         "(funnel/index.py)",
                    where=where, slug=f"funnel-{tag}-{name}-baked",
                ))
        # refresh == cache hit: a same-spec replacement payload must
        # lower identically
        payload2 = abstract_funnel_payload(ctx)
        b1 = buckets[0]
        lo2 = lower_with(retrieve_with, payload2, q_args(b1))
        if lowered_q[b1].in_avals != lo2.in_avals:
            out.append(_finding(
                "trace-recompile",
                f"funnel retrieve on mesh [{dp},{mp}] ({mode}): a "
                f"same-spec replacement payload changed the input "
                f"signature — an index/weights republish would MISS the "
                f"jit cache and recompile mid-traffic",
                hint="keep the payload a plain argument pytree "
                     "(funnel/index.build_retrieve_with)",
                where=where, slug=f"funnel-{tag}-swap-signature",
            ))
        elif lowered_q[b1].as_text() != lo2.as_text():
            out.append(_finding(
                "trace-recompile",
                f"funnel retrieve on mesh [{dp},{mp}] ({mode}): "
                f"same-spec payloads lowered to different modules — "
                f"payload identity (a version) leaked into the "
                f"executable",
                hint="no host reads of the payload inside the retrieve",
                where=where, slug=f"funnel-{tag}-swap-module",
            ))
    return out


# ---------------------------------------------------------------------------
# elastic-reshard contract (elastic/plan.py + checkpoint/reshard.py)

# the N→M transitions the chaos drill exercises: same-width shrink (the
# spot-reclaim shape), the grow back, and a row-shard width change
_ELASTIC_AUDIT_MOVES = (
    ((2, 4), (1, 4)),   # shrink, width stable — plans ZERO table bytes
    ((1, 4), (2, 4)),   # grow back
    ((2, 4), (4, 2)),   # width change — windows re-cut, overlap kept
)


def audit_elastic(cfg=None, reshard_builder=None) -> list[Finding]:
    """The elastic reshard's lowering contract (``elastic/plan.py`` +
    ``checkpoint/reshard.jit_row_adapter``) on every audited N→M move:

    * **no host round-trip on table leaves** — the row-adapt executable
      that re-windows a table onto the new mesh lowers under
      ``transfer_guard('disallow')``: rows move device-to-device through
      XLA's emitted collective plan, never through a host staging buffer
      (at north-star vocabularies a host bounce would turn a sub-second
      reshard into a multi-minute outage);
    * **table is a lowered PARAMETER** — a baked table constant IS a
      smuggled host copy, and would pin every reshard to one snapshot;
    * **plan minimality** — the planner's device-to-device bytes stay
      strictly under the gather-to-host round trip, and a same-width
      shrink plans ZERO table traffic (the surviving shards already own
      their windows).

    ``reshard_builder(sharding, rows_to)`` lets the seeded-violation
    tests feed a host-round-tripping or baked adapter through the same
    checks."""
    import sys

    import jax

    if len(jax.devices()) < 8:
        print(
            "trace-audit: elastic-reshard contract SKIPPED — needs >= 8 "
            "devices (run under JAX_PLATFORMS=cpu with "
            "--xla_force_host_platform_device_count=8; scripts/check.sh "
            "and the analysis CLI arrange this)",
            file=sys.stderr,
        )
        return []
    from ..checkpoint.reshard import jit_row_adapter
    from ..core.config import MeshConfig
    from ..elastic.plan import plan_reshard
    from ..parallel import build_mesh, make_context

    base = cfg or _audit_cfg()
    where = "deepfm_tpu/elastic/plan.py"
    builder = reshard_builder or jit_row_adapter
    out: list[Finding] = []
    devs = jax.devices()
    for (dp_a, mp_a), (dp_b, mp_b) in _ELASTIC_AUDIT_MOVES:
        move = f"{dp_a}x{mp_a}->{dp_b}x{mp_b}"
        old_ctx = make_context(base, build_mesh(
            MeshConfig(data_parallel=dp_a, model_parallel=mp_a),
            devices=devs[: dp_a * mp_a],
        ))
        new_ctx = make_context(base, build_mesh(
            MeshConfig(data_parallel=dp_b, model_parallel=mp_b),
            devices=devs[: dp_b * mp_b],
        ))
        plan = plan_reshard(old_ctx, new_ctx)
        if plan.host_round_trip or plan.moved_bytes >= plan.naive_bytes:
            out.append(_finding(
                "trace-collective",
                f"elastic reshard plan {move} is not minimal-traffic: "
                f"moved {plan.moved_bytes} bytes vs gather-to-host "
                f"{plan.naive_bytes} (host_round_trip="
                f"{plan.host_round_trip})",
                hint="the planner must move only new_window - held_rows "
                     "per device (elastic/plan.plan_reshard)",
                where=where, slug=f"elastic-{move}-plan-not-minimal",
            ))
        if mp_a == mp_b and dp_b < dp_a and plan.moved_bytes != 0:
            out.append(_finding(
                "trace-collective",
                f"same-width shrink {move} plans {plan.moved_bytes} table "
                f"bytes — the surviving shards already own their row "
                f"windows; a correct plan moves ZERO",
                where=where, slug=f"elastic-{move}-shrink-moves-bytes",
            ))
        pv_old = old_ctx.cfg.model.feature_size
        pv_new = new_ctx.cfg.model.feature_size
        k = base.model.embedding_size
        for leaf, shape in (("fm_v", (pv_old, k)), ("fm_w", (pv_old,))):
            # the real restore path: the saved-shape leaf lands on the NEW
            # mesh (Orbax streams each device's chunks from disk; the live
            # path stages with device_put), then the row adapt runs
            # entirely on the new topology — one executable cannot span
            # two device sets
            new_sh = new_ctx.state_shardings.params[leaf]
            fn = builder(new_sh, pv_new)
            abstract = jax.ShapeDtypeStruct(
                shape, jax.numpy.float32, sharding=new_sh
            )
            try:
                with jax.transfer_guard("disallow"):
                    try:
                        lowered = fn.lower(abstract)
                    except TypeError:
                        # an adapter that dropped the table argument
                        # (baked snapshot) still lowers; the leaf-count
                        # contract below convicts it
                        lowered = fn.lower()
            except Exception as e:
                out.append(_finding(
                    "trace-transfer",
                    f"elastic reshard {move} of {leaf} under "
                    f"transfer_guard('disallow') raised "
                    f"{type(e).__name__}: {e} — the row adapt performs a "
                    f"host round-trip on a table leaf",
                    hint="rows must re-window on-device "
                         "(checkpoint/reshard.jit_row_adapter)",
                    where=where, slug=f"elastic-{move}-{leaf}-host-trip",
                ))
                continue
            n_in = len(jax.tree_util.tree_leaves(lowered.in_avals))
            if n_in != 1:
                out.append(_finding(
                    "trace-transfer",
                    f"elastic reshard {move} of {leaf} lowered with "
                    f"{n_in} input leaves, expected the table as the ONE "
                    f"parameter — a baked table constant is a smuggled "
                    f"host staging copy",
                    hint="the adapter must take the table as its "
                         "argument (checkpoint/reshard.jit_row_adapter)",
                    where=where, slug=f"elastic-{move}-{leaf}-baked",
                ))
    out.extend(_audit_consensus_merge(base, devs))
    return out


def _audit_consensus_merge(base, devs) -> list[Finding]:
    """The multi-host half of the elastic contract (elastic/coord.py):
    the registry-view merge that feeds the reshard planner must be
    deterministic and participant-order-independent (two processes
    deriving DIFFERENT consensus sets would build different meshes — the
    exact disagreement the coordinator exists to prevent), and a plan
    drawn on a consensus-merged shrink set must stay minimal exactly like
    a locally-detected one (zero table bytes for a same-width shrink)."""
    from ..core.config import MeshConfig
    from ..elastic.coord import merge_views
    from ..elastic.plan import plan_reshard
    from ..parallel import build_mesh, make_context

    where = "deepfm_tpu/elastic/coord.py"
    out: list[Finding] = []
    full = tuple(d.id for d in devs[:8])
    lost = tuple(d.id for d in devs[:4])  # one participant lost a slice
    views = {"p0": full, "p1": lost}
    merged = merge_views(views)
    swapped = merge_views({"p1": lost, "p0": full})
    if merged != swapped:
        out.append(_finding(
            "trace-collective",
            f"registry-view merge is participant-order-DEPENDENT: "
            f"{merged} vs {swapped} for the same views — two processes "
            f"would agree on different consensus device sets",
            hint="merge_views must be a pure order-independent function "
                 "of the views (elastic/coord.py)",
            where=where, slug="elastic-merge-order-dependent",
        ))
    if set(merged) != set(full) & set(lost):
        out.append(_finding(
            "trace-collective",
            f"registry-view merge is not the intersection: got {merged} "
            f"from views {views} — a device one participant cannot "
            f"address would enter the shared mesh",
            where=where, slug="elastic-merge-not-intersection",
        ))
    by_id = {d.id: d for d in devs}
    old_ctx = make_context(base, build_mesh(
        MeshConfig(data_parallel=2, model_parallel=4),
        devices=[by_id[i] for i in full],
    ))
    new_ctx = make_context(base, build_mesh(
        MeshConfig(data_parallel=1, model_parallel=4),
        devices=[by_id[i] for i in merged],
    ))
    plan = plan_reshard(old_ctx, new_ctx)
    if plan.moved_bytes != 0:
        out.append(_finding(
            "trace-collective",
            f"same-width shrink onto the CONSENSUS-merged device set "
            f"plans {plan.moved_bytes} table bytes — the merge must not "
            f"perturb plan minimality (surviving shards own their rows)",
            where=where, slug="elastic-consensus-shrink-moves-bytes",
        ))
    return out


# ---------------------------------------------------------------------------
# observability contract (unified obs layer, deepfm_tpu/obs)

# markers of host callbacks in lowered StableHLO: anything io_callback /
# pure_callback / debug.callback lowers to a custom_call whose target
# carries "callback" — the shape a registry/trace call smuggled under jit
# takes when it does not crash the trace outright
_CALLBACK_MARKER = "callback"


def _check_obs_lowering(name: str, texts: list[str], where: str
                        ) -> list[Finding]:
    out: list[Finding] = []
    cb_lines = [
        ln.strip()[:160] for ln in texts[0].splitlines()
        if "custom_call" in ln and _CALLBACK_MARKER in ln.lower()
    ]
    if cb_lines:
        out.append(_finding(
            "trace-observability",
            f"the jitted {name} lowers WITH a host callback "
            f"({len(cb_lines)} custom_call(s), first: {cb_lines[0]!r}) — "
            f"a registry/trace call entered the lowered graph and will "
            f"sync the device on every dispatch",
            hint="instrument AROUND the dispatch on the host "
                 "(obs/metrics.py, obs/trace.py); never inside jit",
            where=where, slug=f"obs-{name}-callback",
        ))
    if len(texts) > 1 and texts[0] != texts[1]:
        out.append(_finding(
            "trace-observability",
            f"two successive lowerings of the jitted {name} differ — a "
            f"host-side value (a wall-clock/perf_counter reading, a "
            f"sequence number) was captured into the trace, so every "
            f"retrace bakes a different executable",
            hint="host timers must wrap the dispatch boundary, never "
                 "close over traced values (obs/trace.py span discipline)",
            where=where, slug=f"obs-{name}-nondeterministic",
        ))
    return out


def audit_observability(cfg=None, predict_builder=None,
                        step_builder=None) -> list[Finding]:
    """The unified-observability contract: instrumentation NEVER enters
    lowered code.  The real serving predict
    (``serve.reload.build_predict_with`` — what the instrumented
    MicroBatcher dispatches) and the canonical train step (what the loop
    dispatches under the ``SpanRecorder``'s ``train.dispatch`` span, with
    its ``jax.named_scope``s — metadata only) must still

    * lower under ``jax.transfer_guard("disallow")`` (a registry call on
      a traced value concretizes it or forces a transfer — either way
      the lowering raises here);
    * contain **no host callbacks** in the lowered module (a
      ``debug.callback``/``io_callback`` into a metrics registry lowers
      as a ``custom_call`` the scanner catches);
    * lower **deterministically** (two successive lowerings identical):
      a host-timer value closed over by the traced function bakes a
      different constant per retrace — the classic "time the kernel from
      inside" mistake.

    ``predict_builder(model, cfg)`` / ``step_builder(cfg)`` let the
    seeded-violation tests (tests/test_analysis.py) feed an
    instrumented-inside-jit predict and a timer-baking step through the
    same checks."""
    import jax

    out: list[Finding] = []
    cfg = cfg or _audit_cfg()
    f = cfg.model.field_size
    b = _default_buckets()[0]
    args = (
        jax.ShapeDtypeStruct((b, f), jax.numpy.int64),
        jax.ShapeDtypeStruct((b, f), jax.numpy.float32),
    )
    # -- serving predict ----------------------------------------------------
    from ..serve.reload import build_predict_with

    where = "deepfm_tpu/obs/metrics.py"
    model, payload = _abstract_payload(cfg)
    build_p = predict_builder or build_predict_with
    texts: list[str] = []
    try:
        with jax.transfer_guard("disallow"):
            # TWO builder instances: jax.jit caches the trace per
            # instance, so only a fresh build re-traces — which is what
            # exposes a baked host-timer value (each trace reads a
            # different clock)
            for _ in range(2):
                texts.append(
                    build_p(model, cfg).lower(payload, *args).as_text()
                )
    except Exception as e:
        out.append(_finding(
            "trace-observability",
            f"lowering the serving predict with the observability layer "
            f"active raised {type(e).__name__}: {e} — a registry/trace "
            f"call ran under trace (concretization or implicit transfer)",
            hint="record metrics on the host around engine.score / the "
                 "dispatch boundary, never inside the jitted fn",
            where=where, slug="obs-predict-lower",
        ))
    else:
        out.extend(_check_obs_lowering("predict", texts, where))
    # -- train step ---------------------------------------------------------
    from ..train.step import create_train_state, jitted_train_step

    state = jax.eval_shape(lambda: create_train_state(cfg))
    batch = _abstract_batch(cfg, cfg.data.batch_size)
    build_s = step_builder or (lambda c: jitted_train_step(c))
    texts = []
    try:
        with jax.transfer_guard("disallow"):
            for _ in range(2):
                texts.append(
                    build_s(cfg).lower(state, batch).as_text()
                )
    except Exception as e:
        out.append(_finding(
            "trace-observability",
            f"lowering the train step with the observability layer "
            f"active raised {type(e).__name__}: {e} — a recorder span "
            f"or a registry call ran under trace",
            hint="the span recorder (obs/trace.py) wraps the dispatch on "
                 "the host (train/loop.py); nothing records inside the step",
            where=where, slug="obs-train-lower",
        ))
    else:
        out.extend(_check_obs_lowering("train_step", texts, where))
    # -- flywheel impression logger -----------------------------------------
    # The data flywheel's logger (deepfm_tpu/flywheel/impressions.py)
    # rides the router's HOST response path: a hash-stable sample of
    # answered requests is enqueued AFTER the response doc is formed
    # (serve/pool/router.py _try_group), and a background thread writes
    # the segments.  Hold the serving predict to the same lowering
    # contract with a LIVE logger — worker thread running, one scored
    # offer absorbed — so a logger call that migrates inside the jitted
    # predict (a score offered under trace, an io_callback into the
    # writer) fails the audit instead of syncing every dispatch.  The
    # seeded violation feeds a ``predict_builder`` that offers the
    # traced score to the logger (tests/test_analysis.py).
    import tempfile

    from ..flywheel.impressions import ImpressionLogger

    where_fw = "deepfm_tpu/flywheel/impressions.py"
    texts = []
    try:
        with tempfile.TemporaryDirectory() as td:
            logger = ImpressionLogger(td, sample_rate=1.0).start()
            try:
                logger.offer(
                    key="audit", trace_id="audit-trace", tenant="base",
                    model_version=0,
                    instances=[{"feat_ids": [0] * f,
                                "feat_vals": [0.0] * f}],
                    scores=[0.5], deadline_class="default")
                logger.flush()
                with jax.transfer_guard("disallow"):
                    for _ in range(2):
                        texts.append(
                            build_p(model, cfg)
                            .lower(payload, *args).as_text()
                        )
            finally:
                logger.stop()
    except Exception as e:
        out.append(_finding(
            "trace-observability",
            f"lowering the serving predict with a live flywheel "
            f"impression logger raised {type(e).__name__}: {e} — a "
            f"logger call closed over a traced value (concretization "
            f"or implicit transfer under the guard)",
            hint="offer impressions on the host AFTER the response doc "
                 "is formed (serve/pool/router.py _try_group); the "
                 "jitted predict must stay logger-free",
            where=where_fw, slug="obs-flywheel-lower",
        ))
    else:
        out.extend(
            _check_obs_lowering("flywheel_predict", texts, where_fw))
    return out


# ---------------------------------------------------------------------------
# SLO control-plane contract (adaptive serving, deepfm_tpu/serve/control)


def audit_control_plane(cfg=None, predict_builder=None) -> list[Finding]:
    """The adaptive-serving contract: every SLO decision — cost-model
    admission, the shed ladder, hedging, autoscaling — is host-side
    policy (serve/control/), and NONE of it may enter the lowered
    serving graph.  The audit builds the full control plane, feeds it a
    realistic observation stream (dispatch timings, queue-depth samples,
    sustained-breach autoscale signals — what the live pool feeds it),
    then holds the REAL serving predict to the lowering contract with
    the control plane alive:

    * lowers under ``jax.transfer_guard("disallow")`` — an admission
      decision that closed over a traced value concretizes it here;
    * no host callbacks in the lowered module — a scale/hedge decision
      smuggled into the graph via ``io_callback`` lowers as a
      ``custom_call`` the scanner catches;
    * two successive lowerings identical — a control-plane reading
      (utilization EWMA, token count, cost estimate) baked into the
      trace changes per retrace.

    ``predict_builder(model, cfg)`` lets the seeded-violation tests
    (tests/test_analysis.py) feed both failure shapes through the same
    checks."""
    import jax

    out: list[Finding] = []
    cfg = cfg or _audit_cfg()
    where = "deepfm_tpu/serve/control"
    # the control plane itself is plain host code: construct it whole
    # and feed it — if any of this needed a device or a trace, the
    # policy layer would be broken by design
    from ..serve.control.admission import (
        AdmissionController,
        DeadlineRejectedError,
        LoadShedGate,
    )
    from ..serve.control.autoscale import AutoScaler
    from ..serve.control.cost import BucketCostModel
    from ..serve.control.hedge import HedgeController, TokenBudget

    buckets = _default_buckets()
    try:
        cost = BucketCostModel(buckets)
        for bkt in buckets:
            cost.observe(bkt, 1e-3 * bkt)
        adm = AdmissionController(
            cost, deadline_ms=cfg.slo.deadline_ms or 50.0)
        adm.check(rows=buckets[0], queued_rows=0,
                  max_queue_rows=64 * buckets[-1], deadline_s=None)
        try:
            adm.check(rows=buckets[0], queued_rows=128 * buckets[-1],
                      max_queue_rows=128 * buckets[-1], deadline_s=None)
        except DeadlineRejectedError:
            pass  # the saturated-queue rejection is the designed outcome
        budget = TokenBudget(cfg.slo.retry_budget_pct / 100.0)
        budget.note_request()
        budget.try_spend()
        hedge = HedgeController(
            slo_budget_ms=cfg.slo.deadline_ms or 50.0,
            after_pct=cfg.slo.hedge_after_pct,
            budget=TokenBudget(cfg.slo.hedge_budget_pct / 100.0),
        )
        hedge.plan(200.0)
        gate = LoadShedGate()
        gate.note(True)
        gate.allow_shadow()
        scaler = AutoScaler(min_groups=cfg.slo.min_groups,
                            max_groups=cfg.slo.max_groups)
        for tick in range(10):
            scaler.observe(float(tick), groups=1, util=0.95)
    except Exception as e:
        out.append(_finding(
            "trace-control-plane",
            f"constructing/feeding the SLO control plane raised "
            f"{type(e).__name__}: {e} — the policy layer must run as "
            f"plain host code (no device, no trace, no jax)",
            hint="serve/control/ holds pure host policy; keep jax out "
                 "of it",
            where=where, slug="ctl-host-policy",
        ))
        return out
    # with that control plane alive, the serving predict must lower
    # exactly as it would without one
    from ..serve.reload import build_predict_with

    f = cfg.model.field_size
    b = buckets[0]
    args = (
        jax.ShapeDtypeStruct((b, f), jax.numpy.int64),
        jax.ShapeDtypeStruct((b, f), jax.numpy.float32),
    )
    model, payload = _abstract_payload(cfg)
    build_p = predict_builder or build_predict_with
    texts: list[str] = []
    try:
        with jax.transfer_guard("disallow"):
            for _ in range(2):
                texts.append(
                    build_p(model, cfg).lower(payload, *args).as_text()
                )
    except Exception as e:
        out.append(_finding(
            "trace-control-plane",
            f"lowering the serving predict with the SLO control plane "
            f"active raised {type(e).__name__}: {e} — an admission or "
            f"scale decision ran under trace (closed over a traced "
            f"value, or forced an implicit transfer)",
            hint="admission prices requests BEFORE dispatch on the host "
                 "(serve/batcher.py score); decisions never read traced "
                 "values",
            where=where, slug="ctl-predict-lower",
        ))
        return out
    cb_lines = [
        ln.strip()[:160] for ln in texts[0].splitlines()
        if "custom_call" in ln and _CALLBACK_MARKER in ln.lower()
    ]
    if cb_lines:
        out.append(_finding(
            "trace-control-plane",
            f"the serving predict lowers WITH a host callback under the "
            f"SLO control plane ({len(cb_lines)} custom_call(s), first: "
            f"{cb_lines[0]!r}) — a control decision (autoscale/hedge/"
            f"admission) was smuggled into the graph via io_callback and "
            f"will sync the device on every dispatch",
            hint="the control loop reads router/engine snapshots on host "
                 "threads (serve/pool/__main__.py); nothing decides "
                 "inside jit",
            where=where, slug="ctl-predict-callback",
        ))
    if len(texts) > 1 and texts[0] != texts[1]:
        out.append(_finding(
            "trace-control-plane",
            "two successive lowerings of the serving predict differ "
            "under the live control plane — a control-plane reading "
            "(utilization EWMA, token count, cost estimate) was baked "
            "into the trace as a constant, so every retrace builds a "
            "different executable",
            hint="control state changes per request; a graph that "
                 "embeds it recompiles per decision — read it on the "
                 "host at admission time instead",
            where=where, slug="ctl-predict-nondeterministic",
        ))
    return out


# ---------------------------------------------------------------------------
# zero-update contract (ZeRO dp-sharded weight update, train/optimizer.py +
# parallel/spmd.py)

# the mesh the contract lowers on (the flagship product mesh; the
# bit-parity tests additionally cover [4,2])
_ZERO_AUDIT_MESH = (2, 4)


def check_zero_collectives(
    mlir_text: str, *, dp: int, mp: int, n_sharded_leaves: int,
    where: str = "deepfm_tpu/parallel/spmd.py",
) -> list[Finding]:
    """Hold one lowered train step to the sharded-weight-update traffic
    contract: dense grads must REDUCE-SCATTER over the data axis (one
    collective per param leaf, issued as each grad becomes available so
    XLA can overlap it with the remaining backward), the fresh 1/dp param
    windows must ALL-GATHER back, and NO >1-element all-reduce may ride
    the data axis (the replicated grad sync the sharded update exists to
    remove — metric scalars are exempt).  Model-axis collectives (the
    row-assembly psum, the window bit-stability pmean) are out of scope.
    Factored out of :func:`audit_zero_update` so the seeded-violation
    test can feed a replicated-path (zero=off) lowering through the same
    checks and watch it get caught."""
    cols = summarize_collectives(mlir_text)
    out: list[Finding] = []

    def n_elems(shapes) -> int:
        best = 0
        for s in shapes:
            n = 1
            for d in s:
                n *= d
            best = max(best, n)
        return best

    data_ar = [
        c for c in cols
        if c["op"] == "all_reduce"
        and collective_axis(c.get("groups"), dp, mp) == "data"
        and n_elems(c["shapes"]) > 1
    ]
    if data_ar:
        out.append(_finding(
            "trace-collective",
            f"zero-sharded train step still ALL-REDUCES {len(data_ar)} "
            f"grad-sized tensor(s) over the data axis "
            f"({[(c['op'], c['shapes']) for c in data_ar[:4]]}) — the "
            f"replicated update's collective survived; the sharded "
            f"update must reduce-scatter instead",
            hint="raw local grads must reach the zero wrapper "
                 "(parallel/spmd.py must not _pmean_grads when "
                 "zero_layout is on; train/optimizer.zero_sharded)",
            where=where, slug="zero-dense-allreduce",
        ))
    rs = [
        c for c in cols
        if c["op"] == "reduce_scatter"
        and collective_axis(c.get("groups"), dp, mp) == "data"
    ]
    if len(rs) < n_sharded_leaves:
        out.append(_finding(
            "trace-collective",
            f"zero-sharded train step lowers {len(rs)} data-axis "
            f"reduce-scatter(s) for {n_sharded_leaves} sharded param "
            f"leaves — grads are not reduce-scattered per leaf "
            f"(per-leaf issuance is what lets XLA overlap each "
            f"collective with the remaining backward compute)",
            hint="lax.psum_scatter per leaf in "
                 "train/optimizer.zero_sharded",
            where=where, slug="zero-reduce-scatter-missing",
        ))
    ag = [
        c for c in cols
        if c["op"] == "all_gather"
        and collective_axis(c.get("groups"), dp, mp) == "data"
    ]
    if len(ag) < n_sharded_leaves:
        out.append(_finding(
            "trace-collective",
            f"zero-sharded train step lowers {len(ag)} data-axis "
            f"all-gather(s) for {n_sharded_leaves} sharded param leaves "
            f"— the fresh 1/dp param windows are not gathered back to "
            f"full width",
            hint="lax.all_gather of the updated windows in "
                 "train/optimizer.zero_sharded",
            where=where, slug="zero-allgather-missing",
        ))
    return out


def check_zero_state_sharding(
    state_shardings, state_shapes, *, dp: int,
    where: str = "deepfm_tpu/parallel/spmd.py",
) -> list[Finding]:
    """The moment-residency half of the zero contract: the opt_state must
    carry the ``zero_dp`` layout marker (train/optimizer.ZeroDpState),
    and every flattened moment leaf must be dp-sharded — its per-shard
    dim0 at most ``global // dp``.  A replicated moment leaf (the seeded
    violation: full-size per-shard moments behind the zero flag) fails
    the per-shard sizing; a plain replicated opt_state (no marker) fails
    the marker check."""
    import jax

    out: list[Finding] = []
    shard_leaves = jax.tree_util.tree_flatten_with_path(state_shardings)[0]
    shape_leaves = jax.tree_util.tree_leaves(state_shapes)
    marked = 0
    bad: list[str] = []
    for (path, sh), sds in zip(shard_leaves, shape_leaves):
        if not any(getattr(p, "name", None) == "zero_dp"
                   or getattr(p, "key", None) == "zero_dp" for p in path):
            continue
        shape = tuple(getattr(sds, "shape", ()))
        # flat (1-D) leaves are the dp-partitioned layout by construction;
        # >1-D leaves under the marker are the rare ineligible fallback
        # (legitimately not dp-sharded) and scalars are optimizer counts
        if len(shape) != 1 or shape[0] < dp:
            continue
        marked += 1
        try:
            per_shard = sh.shard_shape(shape)[0]
        except (AttributeError, TypeError, ValueError, IndexError):
            # an unreadable sharding cannot prove dp residency: treat it
            # as replicated so the contract fails loudly below
            per_shard = shape[0]
        if per_shard * dp > shape[0]:
            bad.append(
                f"{jax.tree_util.keystr(path)}: {per_shard}/{shape[0]} "
                f"per shard"
            )
    if not marked:
        out.append(_finding(
            "trace-collective",
            "opt_state carries NO dp-partitioned (zero_dp) moment leaves "
            "— the optimizer state is fully replicated across the data "
            "axis (every shard redundantly holds and updates all "
            "moments)",
            hint="build the train context with optimizer.zero_sharding "
                 "on|auto (parallel/spmd.make_context)",
            where=where, slug="zero-moments-unsharded",
        ))
    elif bad:
        out.append(_finding(
            "trace-collective",
            f"{len(bad)} zero-layout moment leaf(s) are NOT dp-sharded "
            f"(per-shard size exceeds global/dp): {bad[:4]} — the "
            f"moments are replicated despite the sharded-update layout",
            hint="_spec_for_leaf must emit data-axis specs for zero_dp "
                 "leaves (parallel/spmd.py)",
            where=where, slug="zero-moments-replicated",
        ))
    return out


def audit_zero_update(cfg=None, context_builder=None) -> list[Finding]:
    """The ZeRO dp-sharded weight-update contract
    (train/optimizer.zero_sharded + parallel/spmd.py), lowered on the
    flagship [2,4] virtual mesh with ``optimizer.zero_sharding='on'``:

    * **reduce-scatter, not all-reduce** — the lowered SPMD step carries
      one data-axis reduce-scatter per sharded param leaf and NO
      grad-sized data-axis all-reduce (:func:`check_zero_collectives`);
      the fresh 1/dp param windows all-gather back;
    * **dp-sharded moments** — every flattened moment leaf lowers with
      1/dp-sized per-shard shapes (:func:`check_zero_state_sharding`);
    * **transfer-guard-clean, donated** — the step lowers under
      ``jax.transfer_guard('disallow')`` with the state donated, exactly
      like the replicated step (the sharded update must not smuggle a
      host staging hop or break in-place buffer reuse).

    ``context_builder(cfg, mesh)`` lets the seeded-violation tests feed
    a replicated-moments context through the same checks."""
    import sys

    import jax

    if len(jax.devices()) < 8:
        print(
            "trace-audit: zero-update contract SKIPPED — needs >= 8 "
            "devices (run under JAX_PLATFORMS=cpu with "
            "--xla_force_host_platform_device_count=8; scripts/check.sh "
            "and the analysis CLI arrange this)",
            file=sys.stderr,
        )
        return []
    from ..core.config import MeshConfig
    from ..parallel import abstract_spmd_state, build_mesh, make_context
    from ..models.base import table_keys
    from ..parallel.spmd import make_spmd_train_step
    from ..train.optimizer import zero_layout_size

    dp, mp = _ZERO_AUDIT_MESH
    where = "deepfm_tpu/parallel/spmd.py"
    base = (cfg or _audit_cfg()).with_overrides(
        data={"batch_size": 128},
        optimizer={"zero_sharding": "on"},
    )
    mesh = build_mesh(MeshConfig(data_parallel=dp, model_parallel=mp))
    ctx = (context_builder or make_context)(base, mesh)
    state = abstract_spmd_state(ctx)
    pv = ctx.cfg.model.feature_size

    def _sharded_leaf(path, leaf):
        keys = {getattr(p, "key", None) for p in path}
        shape = tuple(leaf.shape)
        shards = mp if (keys & set(table_keys()) and shape
                        and shape[0] == pv) else 1
        n = 1
        for d in shape:
            n *= int(d)
        return zero_layout_size(n, shards, dp) is not None

    n_sharded = sum(
        1 for path, leaf in
        jax.tree_util.tree_flatten_with_path(state.params)[0]
        if _sharded_leaf(path, leaf)
    )
    out: list[Finding] = []
    out.extend(check_zero_state_sharding(
        ctx.state_shardings.opt_state, state.opt_state, dp=dp, where=where,
    ))
    f = ctx.cfg.model.field_size
    b = base.data.batch_size
    batch = {
        "feat_ids": jax.ShapeDtypeStruct((b, f), jax.numpy.int32),
        "feat_vals": jax.ShapeDtypeStruct((b, f), jax.numpy.float32),
        "label": jax.ShapeDtypeStruct((b,), jax.numpy.float32),
    }
    step = make_spmd_train_step(ctx)  # donated — the contract checks it
    try:
        with jax.transfer_guard("disallow"):
            lowered = step.lower(state, batch)
    except Exception as e:
        out.append(_finding(
            "trace-transfer",
            f"lowering the zero-sharded train step under "
            f"transfer_guard('disallow') raised {type(e).__name__}: {e} "
            f"— the sharded update moved host data implicitly",
            hint="the windowed update must be pure traced code "
                 "(train/optimizer.zero_sharded)",
            where=where, slug="zero-transfer-guard",
        ))
        return out
    out.extend(check_zero_collectives(
        lowered.as_text(), dp=dp, mp=mp, n_sharded_leaves=n_sharded,
        where=where,
    ))
    try:
        args_info = lowered.args_info
        state_info = args_info[0][0]
        donated = [bool(getattr(a, "donated", False))
                   for a in jax.tree_util.tree_leaves(state_info)]
    except (AttributeError, IndexError, KeyError, TypeError):
        donated = []
    if donated and not all(donated):
        n_bad = sum(1 for d in donated if not d)
        out.append(_finding(
            "trace-donation",
            f"{n_bad}/{len(donated)} zero-sharded train-state leaves are "
            f"NOT donated — the dp-partitioned moments would copy every "
            f"step instead of updating in place",
            hint="make_spmd_train_step jits with donate_argnums=(0,)",
            where=where, slug="zero-not-donated",
        ))
    elif not donated:
        out.append(_finding(
            "trace-donation",
            "could not read donation info from the lowered zero-sharded "
            "train step (args_info missing) — the donation contract is "
            "unverified",
            hint="jax upgrade changed the AOT API; update the audit",
            where=where, slug="zero-donation-unverified",
        ))
    return out


def audit_region_front(cfg=None, predict_builder=None) -> list[Finding]:
    """The cross-region contract: the region layer (deepfm_tpu/region —
    rendezvous home assignment, replication lag tracking, the staleness
    SLO drain edge, budgeted failover) is pure control plane.  No jitted
    graph and no model bytes belong on the front path: the front
    forwards opaque payloads between pools, and every region decision
    reads host state.

    Two holds:

    * **import hygiene** — no module under ``deepfm_tpu/region`` may
      import jax (statically, by AST walk): a front that can touch
      device arrays is one refactor away from scoring on the routing
      tier;
    * **lowering** — with a live, fed region front (regions ranked,
      versions observed, a drain edge crossed, failover budget spent),
      the REAL serving predict must still lower under
      ``jax.transfer_guard("disallow")``, callback-free and
      deterministically — a routing or staleness decision that reads a
      traced value (say, a home pick keyed on the model's own score)
      concretizes here.

    ``predict_builder(model, cfg)`` lets the seeded-violation tests
    (tests/test_analysis.py) feed both failure shapes through the same
    checks."""
    import ast
    import inspect

    import jax

    out: list[Finding] = []
    cfg = cfg or _audit_cfg()
    where = "deepfm_tpu/region"
    from .. import region as _region_pkg
    from ..region import front as _front_mod
    from ..region import replicator as _repl_mod

    for mod in (_region_pkg, _front_mod, _repl_mod):
        try:
            tree = ast.parse(inspect.getsource(mod))
        except (OSError, SyntaxError):  # pragma: no cover - source gone
            continue
        for node in ast.walk(tree):
            names: list[str] = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.module \
                    and node.level == 0:
                names = [node.module]
            bad = [n for n in names
                   if n == "jax" or n.startswith("jax.")]
            if bad:
                out.append(_finding(
                    "trace-region-front",
                    f"{mod.__name__} imports {bad[0]} — the region "
                    f"layer is pure control plane and must stay "
                    f"importable (and correct) with no device runtime "
                    f"at all",
                    hint="route, replicate and drain on host state; "
                         "model bytes never touch the front path",
                    where=where, slug="region-jax-import",
                ))
    # the region machinery itself is plain host code: construct it
    # whole and walk every decision edge the live front takes
    from ..fleet.split import rendezvous_arm, rendezvous_ranking
    from ..region.front import RegionFront

    try:
        regions = {
            name: {"router_url": f"http://invalid.test:1/{name}",
                   "store_root": ""}
            for name in ("use1", "euw1", "apne1")
        }
        front = RegionFront(regions, max_version_skew=2,
                            readmit_version_skew=0)
        for i in range(16):
            key = f"user-{i}"
            ranking = rendezvous_ranking(key, sorted(regions))
            assert rendezvous_arm(key, sorted(regions)) == ranking[0]
        for name in regions:
            front.note_store_version(name, 5)
        front.note_home_version(5)
        front.plan("user-0")
        front.home("user-0")
        front.note_home_version(9)   # skew 4 > 2: the drain edge
        front.note_store_version("use1", 9)  # ...and the catch-up edge
        front.retry_budget.note_request()
        front.retry_budget.try_spend()
        front.status()
    except Exception as e:
        out.append(_finding(
            "trace-region-front",
            f"constructing/feeding the region front raised "
            f"{type(e).__name__}: {e} — the region layer must run as "
            f"plain host code (no device, no trace, no jax)",
            hint="deepfm_tpu/region holds pure host policy; keep jax "
                 "out of it",
            where=where, slug="region-host-policy",
        ))
        return out
    # with that front alive, the serving predict must lower exactly as
    # it would without one
    from ..serve.reload import build_predict_with

    f = cfg.model.field_size
    b = _default_buckets()[0]
    args = (
        jax.ShapeDtypeStruct((b, f), jax.numpy.int64),
        jax.ShapeDtypeStruct((b, f), jax.numpy.float32),
    )
    model, payload = _abstract_payload(cfg)
    build_p = predict_builder or build_predict_with
    texts: list[str] = []
    try:
        with jax.transfer_guard("disallow"):
            for _ in range(2):
                texts.append(
                    build_p(model, cfg).lower(payload, *args).as_text()
                )
    except Exception as e:
        out.append(_finding(
            "trace-region-front",
            f"lowering the serving predict with the region front "
            f"active raised {type(e).__name__}: {e} — a routing or "
            f"staleness decision ran under trace (closed over a traced "
            f"value, or forced an implicit transfer)",
            hint="home picks, drain edges and failover spends read "
                 "host state; none of them may read a traced value",
            where=where, slug="region-predict-lower",
        ))
        return out
    cb_lines = [
        ln.strip()[:160] for ln in texts[0].splitlines()
        if "custom_call" in ln and _CALLBACK_MARKER in ln.lower()
    ]
    if cb_lines:
        out.append(_finding(
            "trace-region-front",
            f"the serving predict lowers WITH a host callback under "
            f"the region front ({len(cb_lines)} custom_call(s), first: "
            f"{cb_lines[0]!r}) — a region decision was smuggled into "
            f"the graph via io_callback and will sync the device on "
            f"every dispatch",
            hint="the front forwards requests on host threads "
                 "(region/front.py); nothing decides inside jit",
            where=where, slug="region-predict-callback",
        ))
    if len(texts) > 1 and texts[0] != texts[1]:
        out.append(_finding(
            "trace-region-front",
            "two successive lowerings of the serving predict differ "
            "under the live region front — a region reading (skew "
            "gauge, budget token count, ranking) was baked into the "
            "trace as a constant, so every retrace builds a different "
            "executable",
            hint="region state changes per probe tick; read it on the "
                 "host at routing time instead",
            where=where, slug="region-predict-nondeterministic",
        ))
    return out


def run_trace_audit(cfg=None) -> list[Finding]:
    """All engine-2 audits against the real entrypoints (abstract values
    only; no step executes).  Importing jax is the price of admission —
    callers that only want engine 1 never reach this module."""
    findings: list[Finding] = []
    findings.extend(audit_predict(cfg))
    findings.extend(audit_buckets())
    findings.extend(audit_train_step(cfg))
    findings.extend(audit_paged_step(cfg))
    findings.extend(audit_spmd_exchange(cfg))
    findings.extend(audit_zero_update(cfg))
    findings.extend(audit_sharded_predict(cfg))
    findings.extend(audit_multitenant(cfg))
    findings.extend(audit_funnel(cfg))
    findings.extend(audit_elastic(cfg))
    findings.extend(audit_observability(cfg))
    findings.extend(audit_control_plane(cfg))
    findings.extend(audit_region_front(cfg))
    return findings
