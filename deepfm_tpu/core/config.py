"""Typed configuration for the TPU-native DeepFM framework.

Capability parity with the reference's three-layer flag system
(reference: 1-ps-cpu/DeepFM-dist-ps-for-multipleCPU-multiInstance.py:37-107 and
2-hvd-gpu/DeepFM-hvd-tfrecord-vectorized-map.py:36-98) collapsed into one typed
dataclass hierarchy with explicit CLI/env/dict override hooks — no import-time
environment coupling, no string-encoded topology except at the parse boundary.

Dead reference flags intentionally not replicated: ``num_threads`` / ``log_steps``
were never read (ps:49, ps:55), ``loss_type`` never branched (ps:58, ps:275),
``perform_shuffle`` had no flag definition.  ``log_steps`` IS honored here
(the reference defined-but-ignored it; we wire it to the metrics logger).
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any, Sequence


def _strip_list_wrappers(s: str) -> str:
    # accept "(8,4)" / "[8,4]" alongside the canonical "8,4" — users paste
    # python tuples into --set and the bare int() error was baffling
    return s.strip().removeprefix("(").removeprefix("[") \
            .removesuffix(")").removesuffix("]")


def _parse_int_list(s: str | Sequence[int]) -> tuple[int, ...]:
    if isinstance(s, str):
        return tuple(
            int(x) for x in _strip_list_wrappers(s).split(",") if x.strip()
        )
    return tuple(int(x) for x in s)


def _parse_float_list(s: str | Sequence[float]) -> tuple[float, ...]:
    if isinstance(s, str):
        return tuple(
            float(x) for x in _strip_list_wrappers(s).split(",") if x.strip()
        )
    return tuple(float(x) for x in s)


def _parse_str_list(s: str | Sequence[str]) -> tuple[str, ...]:
    if isinstance(s, str):    # --set model.layer_types=conv,full_attention
        return tuple(
            x.strip(" '\"") for x in _strip_list_wrappers(s).split(",")
            if x.strip()
        )
    return tuple(str(x) for x in s)


# ---- multi-tenant fleet (deepfm_tpu/fleet) --------------------------------

# ModelConfig fields that determine the serving EXECUTABLES — the payload
# avals and the lowered bucket modules.  Two tenants may share one
# precompiled executable set iff they agree on ALL of these (the
# audit_multitenant trace contract proves the sharing at lowering level);
# everything else (learning rate, l2, dropout — training-time knobs) is
# tenant-local and free to differ.
EXECUTABLE_SPEC_FIELDS = (
    "model_name", "feature_size", "field_size", "embedding_size",
    "deep_layers", "cin_layers", "cross_layers", "batch_norm",
    "tower_layers", "tower_dim", "user_vocab_size", "item_vocab_size",
    "user_field_size", "item_field_size", "compute_dtype", "table_grad",
    "shard_exchange", "shard_exchange_capacity", "tiered_embeddings",
)

# keys a fleet tenant entry may carry (core/config.py and fleet/registry.py
# share ONE schema; a typo'd key raises instead of silently doing nothing)
TENANT_ENTRY_KEYS = ("name", "source", "split_percent", "shadow_of",
                     "model")


def _spec_norm(v: Any) -> Any:
    return tuple(v) if isinstance(v, list) else v


def tenant_spec_divergence(base_model: dict, overrides: dict) -> list[str]:
    """Executable-spec fields where a tenant's ``model`` overrides diverge
    from the pool's base model section.  Non-empty means the tenant CANNOT
    share the pool's precompiled executables (its payload would lower to a
    different module) — the fleet refuses it at config load instead of
    recompiling mid-traffic."""
    return sorted(
        k for k in overrides
        if k in EXECUTABLE_SPEC_FIELDS
        and _spec_norm(overrides[k]) != _spec_norm(base_model.get(k))
    )


def validate_tenant_entries(entries) -> tuple:
    """Normalize + validate a fleet tenant list (dicts or JSON text):
    duplicate names raise, split percentages of the serving (non-shadow)
    arms must sum to 100 when any is set, shadow entries must reference an
    existing non-shadow incumbent and take no split.  Returns the
    normalized tuple of entry dicts.  Spec-compatibility against the base
    model section is the cross-section half, checked in
    ``Config.__post_init__`` (and re-checked with manifests by
    ``fleet/registry.py``)."""
    if isinstance(entries, str):
        entries = json.loads(entries) if entries.strip() else []
    norm = []
    for i, e in enumerate(entries):
        if not isinstance(e, dict):
            raise ValueError(
                f"fleet.tenants[{i}] must be an object, got {type(e).__name__}"
            )
        unknown = sorted(set(e) - set(TENANT_ENTRY_KEYS))
        if unknown:
            raise ValueError(
                f"fleet.tenants[{i}] has unknown key(s) {unknown} "
                f"(known: {list(TENANT_ENTRY_KEYS)})"
            )
        name = str(e.get("name", "")).strip()
        if not name:
            raise ValueError(f"fleet.tenants[{i}] is missing a name")
        norm.append({
            "name": name,
            "source": str(e.get("source", "")),
            "split_percent": float(e.get("split_percent", 0.0)),
            "shadow_of": str(e.get("shadow_of", "")),
            "model": dict(e.get("model") or {}),
        })
    names = [e["name"] for e in norm]
    dups = sorted({n for n in names if names.count(n) > 1})
    if dups:
        raise ValueError(f"duplicate fleet tenant name(s): {dups}")
    by_name = {e["name"]: e for e in norm}
    serving = [e for e in norm if not e["shadow_of"]]
    for e in norm:
        if e["split_percent"] < 0:
            raise ValueError(
                f"tenant {e['name']!r}: split_percent must be >= 0, got "
                f"{e['split_percent']}"
            )
        if e["shadow_of"]:
            ref = by_name.get(e["shadow_of"])
            if ref is None or ref["shadow_of"]:
                raise ValueError(
                    f"shadow tenant {e['name']!r} references "
                    f"{e['shadow_of']!r}, which is not a serving (non-"
                    f"shadow) tenant"
                )
            if e["split_percent"]:
                raise ValueError(
                    f"shadow tenant {e['name']!r} cannot take live split "
                    f"traffic (split_percent="
                    f"{e['split_percent']}); it scores the sampled stream "
                    f"off the response path"
                )
    total = sum(e["split_percent"] for e in serving)
    if any(e["split_percent"] for e in serving) and abs(total - 100.0) > 1e-6:
        raise ValueError(
            f"fleet split percentages must sum to 100, got {total:g} over "
            f"{[e['name'] for e in serving]} — every key must land on "
            f"exactly one arm"
        )
    return tuple(norm)


def packed_sort_id_bound(n: int) -> int:
    """Largest EXCLUSIVE id bound the packed single-key sort accepts for an
    ``n``-id stream (``ops/embedding.py sort_segments``): the (id,
    position) pair must fit one uint32 key, so ``bits(bound) +
    ceil(log2 n) <= 32``.  Lives here (pure int math, no jax import) so
    config-time validation and the sort share ONE definition."""
    shift = max(1, int(n - 1).bit_length()) if n > 1 else 1
    return 1 << (32 - shift)


@dataclass(frozen=True)
class ModelConfig:
    """DeepFM model hyperparameters (reference ps:50-69, notebook overrides cell 4)."""

    feature_size: int = 117_581       # vocabulary size (ps notebook cell 4)
    field_size: int = 39              # 13 numeric + 26 categorical fields
    embedding_size: int = 32          # K (ps:52)
    deep_layers: tuple[int, ...] = (256, 128, 64)   # ps:62 default; notebooks use 128,64,32
    # NOTE: the reference passes these to tf.nn.dropout as *keep_prob* (ps:245),
    # so 0.5 means "keep 50%".  We store keep probabilities to match.
    dropout_keep: tuple[float, ...] = (0.5, 0.5, 0.5)
    batch_norm: bool = False          # ps:64-66
    batch_norm_decay: float = 0.9     # ps:67-69
    l2_reg: float = 0.0001            # ps:57; applied to FM_W/FM_V only (ps:275-279)
    # deepfm | xdeepfm | dcnv2 | two_tower | lfm2_moe | evabyte | keye_vl2 |
    # nemotron_h
    model_name: str = "deepfm"
    # xDeepFM CIN layer sizes / DCN-v2 cross depth (ignored by plain deepfm)
    cin_layers: tuple[int, ...] = (128, 128)
    cross_layers: int = 3
    # two-tower retrieval (model_name="two_tower"; ignored by CTR families):
    # separate user/item vocabularies and field counts, tower MLP widths,
    # output dim, and softmax temperature for in-batch negatives
    user_vocab_size: int = 0          # 0 -> feature_size
    item_vocab_size: int = 0          # 0 -> feature_size
    user_field_size: int = 1
    item_field_size: int = 1
    tower_layers: tuple[int, ...] = (64, 32)
    tower_dim: int = 16
    temperature: float = 0.05
    # token family (model_name="lfm2_moe", models/lfm2_moe.py; ignored by the
    # others).  The hidden size rides ``embedding_size`` (a token row), the
    # sequence length ``field_size``, the vocabulary held ``feature_size``.
    # One entry a layer: "conv" (gated short convolution) | "full_attention"
    # | "eva" (the byte family's)
    layer_types: tuple[str, ...] = ()
    num_dense_layers: int = 0         # leading layers with the dense SwiGLU
    intermediate_size: int = 0        # the dense SwiGLU's width
    moe_intermediate_size: int = 0    # one expert's width
    num_experts: int = 0              # the router's outputs (all shares')
    # experts held here, ids 0..held-1 of ``num_experts`` (0 = all of them):
    # what the absent ones would add is left out (ops/experts.py)
    experts_held: int = 0
    num_experts_per_tok: int = 0
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    num_attention_heads: int = 0
    num_key_value_heads: int = 0
    # the short convolution's kernel (nemotron_h: its mixer's ``conv_kernel``)
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1_000_000.0
    # byte family (model_name="evabyte", models/evabyte.py; it also reads
    # layer_types — one "eva" a layer —, intermediate_size,
    # num_attention_heads, norm_eps and rope_theta above).  Attention heads
    # held here, heads 0..held-1 of ``num_attention_heads`` (0 = all of
    # them): what the absent ones would add to a layer's output is left out
    heads_held: int = 0
    window_size: int = 0              # EVA: tokens a window of exact keys
    # EVA: tokens a summarised chunk; nemotron_h: positions a chunk of the scan
    chunk_size: int = 0
    num_pred_heads: int = 0           # output heads: head p scores byte t+1+p
    # selected-keys family (model_name="keye_vl2", models/keye_vl2.py; it
    # also reads layer_types — one "selected_attention" a layer —, the expert
    # and attention sizes, norm_eps and rope_theta above).  A head's size
    # where the architecture states it apart from the hidden size (0 =
    # embedding_size // num_attention_heads: the token family reads it too)
    head_dim: int = 0
    index_n_heads: int = 0            # the indexer's query heads (one key head)
    index_head_dim: int = 0           # the indexer's head size
    index_topk: int = 0               # keys a query attends, by indexer score
    # what the router makes of its logits before the top-k: "sigmoid" (the
    # token family's) | "softmax" over all ``num_experts``
    router_score: str = "sigmoid"
    # hybrid family (model_name="nemotron_h", models/nemotron_h.py; it also
    # reads the expert and attention sizes, head_dim, norm_eps, conv_L_cache
    # — the mixer's convolution — and chunk_size — the scan's chunk — above).
    # One character a layer: "M" a Mamba-2 mixer, "E" a latent expert layer,
    # "*" attention
    hybrid_override_pattern: str = ""
    mamba_num_heads: int = 0          # the mixer's heads held here
    mamba_head_dim: int = 0
    n_groups: int = 0                 # groups of B and C held here
    ssm_state_size: int = 0
    moe_latent_size: int = 0          # the space the routed experts work in
    moe_shared_expert_intermediate_size: int = 0
    # compute dtype for the MLP/FM math (params stay f32; bf16 feeds the MXU)
    compute_dtype: str = "bfloat16"
    # "scatter" | "segsum": selects nothing since PR 27.  The chip decided
    # (PERF.md §6): the local row gather's backward combines duplicate ids
    # and writes each distinct row once for either value
    # (ops/embedding.py dense_lookup).  The field stays for the
    # configuration files that name it; ROADMAP D3 removes it
    table_grad: str = "scatter"
    # row-sharded lookup collective strategy (parallel/embedding.py):
    # "psum" = every shard contributes a mostly-zeros [B, F, K] dense tensor,
    # assembled by lax.psum over the model axis (the original path) |
    # "alltoall" = dedup the batch ids on-device, route only UNIQUE owner-rows
    # requests/responses through lax.all_to_all (owned-rows-only traffic;
    # capacity-bounded with a jit-stable psum fallback on overflow) |
    # "auto" = alltoall where a real interconnect exists AND the mesh
    # actually exchanges rows (model_parallel > 1, or lazy updates with
    # data_parallel > 1); psum on the CPU backend, whose shared-memory
    # virtual mesh makes the dense assembly a memcpy that the exchange's
    # sort work cannot beat (measured; parallel/embedding.py
    # resolve_shard_exchange).
    shard_exchange: str = "auto"
    # per-destination-shard request capacity for the alltoall exchange, as a
    # fraction of the flattened local id stream (B_local*F).  0 = auto:
    # ceil(N/M) per model shard for the forward exchange, 0.5*N for the lazy
    # path's per-data-shard unique pack.  Overflow falls back to the dense
    # path inside the same executable (lax.cond), so any value is safe —
    # smaller capacity = less ICI traffic but more frequent fallback.
    shard_exchange_capacity: float = 0.0
    # tiered giant-vocab embedding store (deepfm_tpu/tiered): page rows +
    # lazy-Adam moments through HBM hot cache <- pinned-host backing <-
    # object-store cold tier instead of holding the table resident.
    tiered_embeddings: bool = False
    # device-resident hot-cache slots (0 = auto: next pow2 >= 2*B*F); must
    # hold at least one batch's flattened id stream
    tiered_hot_slots: int = 0
    # staged rows per step, the miss pack's fixed shape (0 = auto: B*F)
    tiered_stage_rows: int = 0
    # pinned host-memory backing rows (0 = auto: 8*hot slots)
    tiered_host_rows: int = 0
    # rows per cold-tier page (one ranged read / one overlay write)
    tiered_page_rows: int = 1024
    # cold-tier root: object-store prefix URL or local directory
    tiered_cold_url: str = ""

    def __post_init__(self):
        object.__setattr__(self, "deep_layers", _parse_int_list(self.deep_layers))
        object.__setattr__(self, "dropout_keep", _parse_float_list(self.dropout_keep))
        object.__setattr__(self, "cin_layers", _parse_int_list(self.cin_layers))
        object.__setattr__(self, "tower_layers", _parse_int_list(self.tower_layers))
        object.__setattr__(self, "layer_types", _parse_str_list(self.layer_types))
        if len(self.dropout_keep) < len(self.deep_layers):
            raise ValueError(
                f"dropout_keep has {len(self.dropout_keep)} entries for "
                f"{len(self.deep_layers)} deep layers"
            )
        if self.router_score not in ("sigmoid", "softmax"):
            raise ValueError(
                f"router_score must be 'sigmoid' or 'softmax', "
                f"got {self.router_score!r}"
            )
        if self.table_grad not in ("scatter", "segsum"):
            raise ValueError(
                f"table_grad must be 'scatter' or 'segsum', "
                f"got {self.table_grad!r}"
            )
        if self.shard_exchange not in ("psum", "alltoall", "auto"):
            raise ValueError(
                f"shard_exchange must be 'psum', 'alltoall' or 'auto', "
                f"got {self.shard_exchange!r}"
            )
        if not 0.0 <= self.shard_exchange_capacity <= 1.0:
            raise ValueError(
                f"shard_exchange_capacity must be in [0, 1] (a fraction of "
                f"the local id stream), got {self.shard_exchange_capacity!r}"
            )
        if self.tiered_page_rows < 1:
            raise ValueError(
                f"tiered_page_rows must be >= 1, got {self.tiered_page_rows}"
            )
        for name in ("tiered_hot_slots", "tiered_stage_rows",
                     "tiered_host_rows"):
            if getattr(self, name) < 0:
                raise ValueError(
                    f"{name} must be >= 0 (0 = auto), got "
                    f"{getattr(self, name)}"
                )


@dataclass(frozen=True)
class OptimizerConfig:
    """Optimizer selection, parity with reference ps:292-305."""

    name: str = "Adam"                # Adam | Adagrad | Momentum | Ftrl
    learning_rate: float = 0.0005     # ps:56
    # Horovod path scales lr by world size (hvd:171). Explicit knob here.
    scale_lr_by_data_parallel: bool = False
    # Beyond-reference (the reference is constant-lr only, ps:292-305):
    # warmup + decay schedules over OPTIMIZER steps.  constant|cosine|linear;
    # cosine/linear need decay_steps (TOTAL horizon incl. warmup) and end at
    # learning_rate * lr_end_fraction.  Resume-safe: the schedule reads the
    # restored step count.
    lr_schedule: str = "constant"
    warmup_steps: int = 0
    decay_steps: int = 0
    lr_end_fraction: float = 0.0
    # lr split: fm_w/fm_v (the tables the reference's PS hosted) train at
    # learning_rate * this; the MLP/bias keep the base lr.  Exact lr-split
    # semantics for Adam/Adagrad/Momentum; rejected for Ftrl.
    embedding_lr_multiplier: float = 1.0
    # touched-rows-only Adam for the embedding tables (train/lazy.py): the
    # TF1 sparse_apply_adam capability; Adam-only, single-controller path
    lazy_embedding_updates: bool = False
    # ZeRO-style dp-sharded weight update (train/optimizer.zero_sharded,
    # arxiv 2004.13336): reduce-scatter grads over the data axis, each dp
    # shard owns 1/dp of the flattened params and their optimizer moments,
    # all-gather the fresh windows.  "off" = replicated moments + pmean
    # (the original path) | "on" = shard whenever data_parallel > 1 (a
    # no-op at dp == 1 — warned in Config.__post_init__) | "auto" = on
    # exactly when data_parallel > 1.  Bit-identical to the replicated
    # path (tests/test_zero_sharding.py); applies to the SPMD train steps
    # (parallel/spmd.py) — the single-device step has no data axis.
    # NOT an EXECUTABLE_SPEC_FIELD: serving executables never touch
    # opt_state, so the knob cannot change any lowered serving shape.
    zero_sharding: str = "auto"
    adam_b1: float = 0.9
    adam_b2: float = 0.999
    adam_eps: float = 1e-8
    adagrad_init_accum: float = 1e-8  # ps:297 initial_accumulator_value
    momentum: float = 0.95            # ps:301

    def __post_init__(self):
        if self.zero_sharding not in ("off", "on", "auto"):
            raise ValueError(
                f"optimizer.zero_sharding must be 'off', 'on' or 'auto', "
                f"got {self.zero_sharding!r}"
            )


@dataclass(frozen=True)
class DataConfig:
    """Input-pipeline config: file/stream modes + the 4-way shard matrix.

    Shard matrix parity: README.md:87-92 and hvd:127-149 of the reference.
    ``s3_shard`` ≡ enable_s3_shard (platform pre-sharded files per host);
    ``multi_path`` ≡ enable_data_multi_path (one stream channel per local worker).
    """

    training_data_dir: str = ""
    val_data_dir: str = ""
    test_data_dir: str = ""
    batch_size: int = 1024            # notebook cell 4 (script default was 64, ps:54)
    num_epochs: int = 10
    shuffle_files: bool = True        # reference shuffles the *file list* (ps:422)
    shuffle_buffer: int = 0           # 0 = no record-level shuffle (reference has none)
    drop_remainder: bool = True       # ps:158 batch(..., drop_remainder=True)
    stream_mode: bool = False         # pipe_mode analog: streaming reader vs file mode
    s3_shard: bool = False            # platform pre-sharded the files per host
    multi_path: bool = False          # one stream path per local worker
    training_channel_name: str = "training"
    evaluation_channel_name: str = "evaluation"
    # stream-mode eval reads the evaluation channel until EOF, or until this
    # many batches when > 0 (a live channel may never close — bound the read)
    eval_max_batches: int = 0
    prefetch_batches: int = 2         # double-buffered host->device feed
    file_patterns: tuple[str, ...] = ("tr", "train")
    # concurrent per-source C++ readers for multi-shard ingest (the
    # multi-channel/multi-shard feed capability, hvd nb cell 8); 1 =
    # sequential.  Only takes effect with the native reader and >1 source.
    parallel_readers: int = 4
    # spread Zipf-hot ids across embedding shards with a fixed bijective
    # permutation (host-side, parallel/embedding.permute_ids)
    permute_ids: bool = False


# the mesh's axis names: fixed framework-wide — they appear in every sharding
# rule and in the model contract's losses (models/base.py), so they are
# constants, not configuration (re-exported by parallel/mesh.py)
DATA_AXIS = "data"
MODEL_AXIS = "model"


@dataclass(frozen=True)
class MeshConfig:
    """Device mesh topology.  Replaces PS topology flags (ps:38-48) and
    Horovod rank plumbing (hvd:333-350) with named mesh axes
    (``DATA_AXIS`` / ``MODEL_AXIS`` above)."""

    # -1 = all remaining devices on that axis
    data_parallel: int = -1
    model_parallel: int = 1           # row-shard factor for embedding tables
    # multi-host wiring (jax.distributed). 0 processes = single-process.
    coordinator_address: str = ""
    num_processes: int = 1
    process_id: int = 0


@dataclass(frozen=True)
class ElasticConfig:
    """Elastic preemption-tolerant training (``deepfm_tpu/elastic``): mesh
    shape as a RUNTIME variable.  A device registry watches availability;
    on a shrink/grow the trainer drains the in-flight step, commits
    {weights, optimizer state, stream cursor} as one Orbax payload, plans
    a minimal-traffic N→M redistribution, rebuilds mesh/shardings/compiled
    steps for the new topology, and resumes the stream cursor exactly-once
    (online/trainer.py commit semantics).  Publishing continues across the
    reshard, so serving never observes the topology change."""

    # run the elastic controller instead of the fixed-mesh online trainer
    # (task_type=online-train only; batch training keeps the stop-the-world
    # restart path in launch/preemption.py + checkpoint/reshard.py)
    enabled: bool = False
    # preferred embedding row-shard width: the planner picks the LARGEST
    # divisor of the live device count <= this (0 = mesh.model_parallel).
    # Keeping mp stable across a shrink keeps the padded vocab — and so the
    # published artifact shapes — identical, which keeps every post-reshard
    # group swap at the serving pool a jit cache hit.
    prefer_model_parallel: int = 0
    # refuse to rebuild on fewer devices than this; wait for capacity
    min_devices: int = 1
    # registry poll cadence while waiting for capacity to return
    poll_interval_secs: float = 0.25
    # max seconds to wait for min_devices after a shrink below it
    # (0 = wait forever — the platform owns the reschedule)
    wait_for_capacity_secs: float = 0.0
    # attempt a drain+commit on the OLD mesh before resharding (virtual
    # registries and advance-notice preemptions); when the commit itself
    # fails (devices already gone) the last periodic commit is the resume
    # point — exactly-once either way, the failed window just replays
    drain_commit: bool = True
    # -- multi-host composition (elastic/coord.py) --------------------------
    # coordination service URL ("" = single-process: no leases, no
    # consensus, PR-9 behavior).  With a coordinator, every training
    # process holds a TTL lease, heartbeats its local registry view, and
    # reshards only through the coordinator's two-phase drain barrier;
    # commits and publishes carry the lease's fencing token, which the
    # checkpoint root and publish root ENFORCE (a stale-token write is
    # refused, not just discouraged).
    coordinator_url: str = ""
    # lease TTL: a process that misses heartbeats for this long is expired
    # from consensus (its devices drop out, its fencing token goes stale).
    # Sent in the acquire request; the coordinator honors it clamped to
    # its own --lease-ttl ceiling, and the granted value drives expiry.
    lease_ttl_secs: float = 10.0
    # heartbeat cadence (must leave headroom under the TTL; transitions
    # and view changes heartbeat immediately regardless)
    heartbeat_interval_secs: float = 1.0
    # LiveDeviceRegistry debounce: consecutive anomalous polls required
    # before a device-set change bumps the epoch (one transient device-
    # query hiccup must not cost a full drain/commit/reshard cycle)
    registry_debounce_polls: int = 2
    # MPMD trainer/publisher split: the trainer only COMMITS payloads;
    # a separate `--task_type publish` process tails the checkpoint root
    # and publishes asynchronously, so a publish-store outage degrades
    # freshness instead of stalling the train step
    publisher_split: bool = False
    # publisher process: cadence for polling the checkpoint root for
    # newly committed payloads
    publish_poll_secs: float = 0.5

    def __post_init__(self):
        if self.min_devices < 1:
            raise ValueError(
                f"elastic.min_devices must be >= 1, got {self.min_devices}"
            )
        if self.prefer_model_parallel < 0:
            raise ValueError(
                f"elastic.prefer_model_parallel must be >= 0 (0 = "
                f"mesh.model_parallel), got {self.prefer_model_parallel}"
            )
        import math

        # NaN slips through plain <= 0 checks and every downstream
        # min/compare — a NaN TTL would mint a never-expiring lease
        if not (self.lease_ttl_secs > 0
                and math.isfinite(self.lease_ttl_secs)):
            raise ValueError(
                f"elastic.lease_ttl_secs must be finite and > 0, got "
                f"{self.lease_ttl_secs}"
            )
        if not (self.heartbeat_interval_secs > 0
                and math.isfinite(self.heartbeat_interval_secs)):
            raise ValueError(
                f"elastic.heartbeat_interval_secs must be finite and > 0, "
                f"got {self.heartbeat_interval_secs}"
            )
        if self.heartbeat_interval_secs >= self.lease_ttl_secs / 2:
            raise ValueError(
                f"elastic.heartbeat_interval_secs="
                f"{self.heartbeat_interval_secs} leaves no headroom under "
                f"lease_ttl_secs={self.lease_ttl_secs}: one delayed "
                f"heartbeat would expire the lease and self-fence the "
                f"trainer — keep the interval under ttl/2"
            )
        if self.registry_debounce_polls < 1:
            raise ValueError(
                f"elastic.registry_debounce_polls must be >= 1, got "
                f"{self.registry_debounce_polls}"
            )
        if self.publish_poll_secs <= 0:
            raise ValueError(
                f"elastic.publish_poll_secs must be > 0, got "
                f"{self.publish_poll_secs}"
            )


@dataclass(frozen=True)
class FleetConfig:
    """Multi-tenant model fleet (``deepfm_tpu/fleet``): N model variants
    served from ONE shard-group pool's precompiled executables.  Weights
    ride the executables as jit ARGUMENTS (serve/reload.py, serve/pool/
    sharded.py), so same-spec tenants cost one payload each and ZERO extra
    executables — variant selection is a payload pick, not a recompile
    (the ``audit_multitenant`` trace contract pins this).  The router
    splits traffic hash-stably across the serving tenants, shadow tenants
    score a sampled slice of the live stream off the response path, and
    each tenant hot-swaps group-atomically without touching its
    neighbours."""

    # tenant bindings: JSON text or a list of entry objects —
    #   [{"name": "prod", "source": "<publish root>", "split_percent": 90},
    #    {"name": "exp",  "source": "...", "split_percent": 10},
    #    {"name": "challenger", "source": "...", "shadow_of": "prod"}]
    # ``model`` may carry executable-NEUTRAL overrides; a tenant whose
    # model overrides touch an executable-spec field is refused at load
    # (Config.__post_init__ names the differing fields).
    tenants: tuple = ()
    # fraction of the incumbent's live stream the shadow challenger scores
    # (hash-stable per key, like the split itself)
    shadow_sample_percent: float = 100.0
    # bounded shadow queue: offers beyond this depth are SHED (counted) —
    # the shadow path may lose samples under load, never add latency
    shadow_queue_depth: int = 128

    def __post_init__(self):
        object.__setattr__(
            self, "tenants", validate_tenant_entries(self.tenants)
        )
        if not 0.0 <= self.shadow_sample_percent <= 100.0:
            raise ValueError(
                f"fleet.shadow_sample_percent must be in [0, 100], got "
                f"{self.shadow_sample_percent}"
            )
        if self.shadow_queue_depth < 1:
            raise ValueError(
                f"fleet.shadow_queue_depth must be >= 1, got "
                f"{self.shadow_queue_depth}"
            )


@dataclass(frozen=True)
class SloConfig:
    """SLO-driven adaptive serving control plane (``deepfm_tpu/serve/
    control``): deadline-aware admission at the micro-batcher, router-
    level hedged tail requests, and elastic shard-group autoscaling.
    Everything here is HOST-side control policy — the ``audit_control_
    plane`` trace contract proves none of it enters the jitted predict.

    Graceful degradation is the invariant the knobs parameterize: shed
    the cheapest work first (shadow offers, then funnel width, then
    plain predicts), never fail work already admitted, always converge
    back (hysteresis on every edge)."""

    # request completion SLO in milliseconds — the default deadline for
    # requests that carry no ``X-Deadline-Ms`` header, AND the hedge
    # trigger budget (a group whose live p95 exceeds this is hedge-
    # eligible).  0 disables deadline admission and hedging.
    deadline_ms: float = 0.0
    # hedge delay as a percent of the first-choice group's live p95: the
    # hedge fires only after the primary has already outlived this share
    # of the typical tail (a p95-based adaptive delay — near-zero extra
    # load when the group is healthy)
    hedge_after_pct: float = 95.0
    # hedges may add at most this percent extra load (token bucket over
    # the recent request rate; an exhausted bucket suppresses hedging,
    # never the primary request)
    hedge_budget_pct: float = 5.0
    # cross-group retries share a token bucket accruing at this percent
    # of the recent request rate; beyond it the router fails fast with
    # 503 + Retry-After instead of amplifying a pool-wide brownout
    retry_budget_pct: float = 10.0
    # -- priority shed ladder (cheapest first; utilizations in [0,1] of
    # the admission queue bound, EWMA-smoothed so a single burst does
    # not flip levels) ------------------------------------------------
    # level 1: shed shadow-scoring offers (zero user impact)
    shed_shadow_util: float = 0.60
    # level 2: degrade recommend expand/rank width toward the floor
    degrade_util: float = 0.75
    # level 3: shed plain predicts at admission (503 + Retry-After)
    shed_predict_util: float = 0.90
    # recommend width floor under level-2 degradation, percent of the
    # requested top_k/return_n (100 = never degrade)
    degrade_floor_pct: float = 50.0
    # -- elastic shard-group autoscaling --------------------------------
    min_groups: int = 1
    max_groups: int = 4
    # scale up when utilization stays above this (or p95 stays over
    # deadline_ms) for scale_up_window_secs
    scale_up_util: float = 0.75
    # scale down when utilization stays below this for
    # scale_down_window_secs (strictly below scale_up_util: the gap is
    # the hysteresis band that prevents flapping)
    scale_down_util: float = 0.25
    scale_up_window_secs: float = 5.0
    scale_down_window_secs: float = 30.0
    # minimum seconds between autoscale actions (lets a fresh group's
    # load signal settle before the next decision)
    cooldown_secs: float = 10.0

    def __post_init__(self):
        import math

        for name in ("deadline_ms",):
            v = getattr(self, name)
            if not (v >= 0 and math.isfinite(v)):
                raise ValueError(
                    f"slo.{name} must be finite and >= 0, got {v}"
                )
        for name in ("hedge_after_pct", "hedge_budget_pct",
                     "retry_budget_pct", "degrade_floor_pct"):
            v = getattr(self, name)
            if not (0.0 <= v <= 100.0 and math.isfinite(v)):
                raise ValueError(
                    f"slo.{name} must be a percent in [0, 100], got {v}"
                )
        for name in ("shed_shadow_util", "degrade_util",
                     "shed_predict_util", "scale_up_util",
                     "scale_down_util"):
            v = getattr(self, name)
            if not (0.0 < v <= 1.0 and math.isfinite(v)):
                raise ValueError(
                    f"slo.{name} must be a utilization in (0, 1], got {v}"
                )
        if not (self.shed_shadow_util <= self.degrade_util
                <= self.shed_predict_util):
            raise ValueError(
                f"slo shed ladder must be ordered cheapest-first: "
                f"shed_shadow_util={self.shed_shadow_util} <= "
                f"degrade_util={self.degrade_util} <= "
                f"shed_predict_util={self.shed_predict_util} — shedding "
                f"plain predicts before shadow offers inverts graceful "
                f"degradation"
            )
        if self.min_groups < 1:
            raise ValueError(
                f"slo.min_groups must be >= 1, got {self.min_groups}"
            )
        if self.max_groups < self.min_groups:
            raise ValueError(
                f"slo.max_groups={self.max_groups} < min_groups="
                f"{self.min_groups}"
            )
        if self.scale_down_util >= self.scale_up_util:
            raise ValueError(
                f"slo.scale_down_util={self.scale_down_util} must stay "
                f"strictly below scale_up_util={self.scale_up_util}: the "
                f"gap is the hysteresis band — without it the autoscaler "
                f"flaps a group up and down on every load ripple"
            )
        for name in ("scale_up_window_secs", "scale_down_window_secs",
                     "cooldown_secs"):
            v = getattr(self, name)
            if not (v > 0 and math.isfinite(v)):
                raise ValueError(
                    f"slo.{name} must be finite and > 0, got {v}"
                )


@dataclass(frozen=True)
class FlywheelConfig:
    """Data flywheel (``deepfm_tpu/flywheel``): serve → log → join →
    train on our own traffic.  The serving pool logs a hash-stable
    sample of scored impressions; a standalone join process matches
    clicks inside an attribution window (negatives synthesized at
    expiry); ``task_type=feedback-train`` points the online trainer at
    the joined stream."""

    # arm the router-side impression logger (task_type=serve pool)
    enabled: bool = False
    # immutable-segment log roots (dirs or object URLs, stream.py)
    impression_log_url: str = ""
    # click events produced by the application (join input)
    click_log_url: str = ""
    # joined labeled stream (join output; feedback-train's input)
    join_output_url: str = ""
    # fraction of requests logged, hash-stable per impression id (the
    # trace id, else the routing key) — the join recomputes the same
    # decision, so clicks for sampled-out impressions are never orphans
    sample_rate: float = 1.0
    # how long after an impression's segment publish a click may still
    # attribute; expiry under the click watermark synthesizes a negative
    attribution_window_secs: float = 1800.0
    # impression-logger segment roll: publish when the buffered segment
    # reaches this many bytes, or when its oldest record has waited this
    # long (online/stream.py SegmentWriter)
    segment_roll_bytes: int = 1 << 20
    segment_roll_age_secs: float = 10.0
    # join durability cadence: flush output + commit {cursors, pending}
    # after this many consumed input segments (checkpoints also land at
    # every run() exit)
    join_checkpoint_every_segments: int = 8
    # bounded logger queue between the serve path and the writer thread;
    # a full queue drops the impression (counted), never blocks serving
    queue_depth: int = 1024

    def __post_init__(self):
        import math

        if not (0.0 < self.sample_rate <= 1.0
                and math.isfinite(self.sample_rate)):
            raise ValueError(
                f"flywheel.sample_rate must be in (0, 1], got "
                f"{self.sample_rate}"
            )
        if not (self.attribution_window_secs > 0
                and math.isfinite(self.attribution_window_secs)):
            raise ValueError(
                f"flywheel.attribution_window_secs must be finite and "
                f"> 0, got {self.attribution_window_secs}"
            )
        if self.segment_roll_bytes < 1:
            raise ValueError(
                f"flywheel.segment_roll_bytes must be >= 1, got "
                f"{self.segment_roll_bytes}"
            )
        if not (self.segment_roll_age_secs > 0
                and math.isfinite(self.segment_roll_age_secs)):
            raise ValueError(
                f"flywheel.segment_roll_age_secs must be finite and > 0, "
                f"got {self.segment_roll_age_secs} — an age-less roll "
                f"strands a trickle of impressions in the writer buffer"
            )
        if self.join_checkpoint_every_segments < 1:
            raise ValueError(
                f"flywheel.join_checkpoint_every_segments must be >= 1, "
                f"got {self.join_checkpoint_every_segments}"
            )
        if self.queue_depth < 1:
            raise ValueError(
                f"flywheel.queue_depth must be >= 1, got "
                f"{self.queue_depth}"
            )
        if self.enabled and not self.impression_log_url:
            raise ValueError(
                "flywheel.enabled needs flywheel.impression_log_url — "
                "the logger has nowhere to publish segments"
            )


@dataclass(frozen=True)
class RegionsConfig:
    """Cross-region active-active serving (``deepfm_tpu/region``): one
    pool + one model store per region, an async manifest replicator
    keeping every region store behind-but-never-torn (marker-last order
    preserved per region), and a front tier routing each user to a
    hash-stable home region with staleness-SLO-gated failover.  All
    host-side control plane — ``audit_region_front`` proves none of it
    enters the jitted predict."""

    # arm the region layer (task_type=region-front)
    enabled: bool = False
    # region cells: each entry {"name", "router_url", "store_root"} —
    # the region pool's router endpoint and the region-local publish
    # root its hot-reload tails (dir or object URL)
    regions: tuple = ()
    # the home publish root the replicator mirrors into region stores
    home_root: str = ""
    # front tier bind address
    front_host: str = "127.0.0.1"
    front_port: int = 8400
    # replicator tail cadence over the home root
    replication_poll_secs: float = 1.0
    # whole-region health probe cadence and consecutive failures before
    # ejection (traffic-observed failures count toward the same bar)
    probe_interval_secs: float = 1.0
    eject_after: int = 2
    # -- staleness SLO (model-version skew, in committed versions) ------
    # a region whose store is more than this many versions behind the
    # home root flips to drain-and-catch-up instead of serving
    # stale-beyond-SLO scores
    max_version_skew: int = 2
    # re-admission bar (hysteresis): a drained or ejected region takes
    # traffic again only once its skew is back at or below this
    readmit_version_skew: int = 0
    # cross-region failover token budget, percent of the recent request
    # rate — beyond it the front fails fast (503 + Retry-After) so a
    # region brownout cannot cascade into a retry storm
    failover_budget_pct: float = 10.0
    # retention floor at the home root: the publisher keeps at least
    # this many versions (max with run.keep_checkpoints) so a region
    # lagging inside the SLO can still fetch what it is catching up to
    # (0 = no widening)
    publish_keep_window: int = 0

    def __post_init__(self):
        import math

        if self.enabled:
            if not self.regions:
                raise ValueError(
                    "regions.enabled needs at least one region entry"
                )
            if not self.home_root:
                raise ValueError(
                    "regions.enabled needs regions.home_root — the "
                    "replicator has nothing to tail"
                )
        names = []
        for entry in self.regions:
            if not isinstance(entry, dict) or not entry.get("name") \
                    or not entry.get("router_url"):
                raise ValueError(
                    f"each regions.regions entry needs 'name' and "
                    f"'router_url' (got {entry!r})"
                )
            names.append(entry["name"])
        if len(names) != len(set(names)):
            raise ValueError(
                f"regions.regions names must be unique, got {names}"
            )
        if self.max_version_skew < 0 or self.readmit_version_skew < 0:
            raise ValueError(
                "regions version-skew bounds must be >= 0"
            )
        if self.readmit_version_skew > self.max_version_skew:
            raise ValueError(
                f"regions.readmit_version_skew="
                f"{self.readmit_version_skew} must not exceed "
                f"max_version_skew={self.max_version_skew} — the "
                f"re-admit bar cannot be laxer than the drain bar"
            )
        if not (0.0 <= self.failover_budget_pct <= 100.0
                and math.isfinite(self.failover_budget_pct)):
            raise ValueError(
                f"regions.failover_budget_pct must be a percent in "
                f"[0, 100], got {self.failover_budget_pct}"
            )
        for name in ("replication_poll_secs", "probe_interval_secs"):
            v = getattr(self, name)
            if not (v > 0 and math.isfinite(v)):
                raise ValueError(
                    f"regions.{name} must be finite and > 0, got {v}"
                )
        if self.eject_after < 1:
            raise ValueError(
                f"regions.eject_after must be >= 1, got "
                f"{self.eject_after}"
            )
        if self.publish_keep_window < 0:
            raise ValueError(
                f"regions.publish_keep_window must be >= 0, got "
                f"{self.publish_keep_window}"
            )


@dataclass(frozen=True)
class RunConfig:
    """Run/driver config: task dispatch + paths (ps:70-79) + cluster identity
    (SM_HOSTS/SM_CURRENT_HOST analogs, ps:80-95)."""

    task_type: str = "train"          # train | eval | infer | export | serve
                                      # | online-train | feedback-train
                                      # (ps:77-79; serve = online scoring
                                      # over the exported servable,
                                      # serve/server.py; online-train =
                                      # continuous training from an event
                                      # log, online/trainer.py; feedback-
                                      # train = online-train over the
                                      # flywheel's joined stream,
                                      # deepfm_tpu/flywheel)
    model_dir: str = "./model_dir"
    servable_model_dir: str = "./servable"
    clear_existing_model: bool = False  # hvd:66-68
    hosts: tuple[str, ...] = ("localhost",)
    current_host: str = "localhost"
    workers_per_host: int = 1         # hvd:80-82 worker_per_host
    log_steps: int = 100
    # optimizer steps fused into ONE compiled dispatch (lax.scan inside the
    # sharded step) with ONE stacked host->device transfer: the standard TPU
    # host-loop design.  Amortizes per-step dispatch/transfer overhead —
    # worth ~2x at reference batch sizes where dispatch latency rivals the
    # 135 us on-chip step.  1 = step-per-dispatch (reference-equivalent
    # cadence).  Checkpoint/eval/logging granularity becomes K steps.
    # Applies to the CTR train task (train/loop.run_train); the retrieval
    # family keeps step-per-dispatch.  On a live FIFO (pipe-mode) feed, K
    # host batches buffer before each dispatch, so a slow producer adds up
    # to K-1 batches of latency and a partial tail chunk only drains at
    # stream close — prefer 1 for latency-sensitive streaming.
    steps_per_loop: int = 1
    eval_start_delay_secs: int = 0    # reference: 1000 (ps:517); 0 = eval immediately
    eval_throttle_secs: int = 0       # reference: 1200 (ps:519)
    checkpoint_every_steps: int = 1000
    keep_checkpoints: int = 3
    seed: int = 0
    profile_dir: str = ""             # jax.profiler trace of 20 steps after the first logged window ("" = off)
    serve_port: int = 8501            # task_type=serve bind port
    serve_host: str = "127.0.0.1"     # bind address (0.0.0.0 for remote clients)
    serve_item_corpus: str = ""       # two-tower: JSONL corpus for :retrieve
    serve_workers: int = 1            # >1: SO_REUSEPORT process pool (the
                                      # TF-Serving worker-pool analog,
                                      # serve/server.py serve_pool)
    # micro-batching engine (serve/batcher.py): coalesced requests pad to
    # the smallest of these bucket sizes that fits — each bucket is one
    # precompiled XLA executable
    serve_buckets: str = "8,32,128,512"
    # admission timeout: max ms a request waits for bucket-mates on an
    # IDLE engine (under load the running dispatch is the coalescing
    # window and no extra wait happens)
    serve_max_wait_ms: float = 2.0
    # hot weight reload (serve/reload.py): publish root (dir or object URL,
    # online/publisher.py) polled for new versions; "" = static weights.
    # New versions swap under the precompiled bucket executables after a
    # canary probe, with in-flight dispatches drained across the swap.
    serve_reload_url: str = ""
    serve_reload_interval_secs: float = 2.0
    # router-fronted shard-group serving pool (serve/pool/): >0 runs the
    # serve task as `serve_groups` shard-group member processes (tables
    # row-sharded over each group's mesh, the alltoall exchange on the
    # predict path) behind the consistent-hashing router
    serve_groups: int = 0
    # per-group mesh shape: batch sharding x table row sharding.
    # model_parallel 0 = auto (the member host's devices / data_parallel)
    serve_group_data_parallel: int = 1
    serve_group_model_parallel: int = 0
    # router front: bind port, max extra shard-groups tried per request,
    # health-probe cadence, consecutive probe failures before ejection
    serve_router_port: int = 8500
    serve_retry_limit: int = 2
    serve_health_interval_secs: float = 1.0
    serve_eject_after: int = 2
    # recommendation funnel (deepfm_tpu/funnel; task_type=serve over a
    # funnel servable — sharded top-K retrieval into live-weight ranking):
    # candidates retrieved per user and ranked items returned per user
    # (0 = the servable's funnel.json defaults).  funnel_top_k > 0 also
    # engages the funnel geometry validation in Config.__post_init__.
    funnel_top_k: int = 0
    funnel_return_n: int = 0
    # quantized retrieval tier (funnel/quant.py): "exact" scores the f32
    # corpus bit-exactly; "int8" streams per-row symmetric int8 codes and
    # exactly rescores an oversampled shortlist in f32; "auto" picks int8
    # once the index CAPACITY crosses funnel/quant.AUTO_INT8_MIN_ROWS.
    # Not an executable-spec field, but part of the published funnel
    # manifest — publish and serving modes must agree (stage_version
    # refuses skew).
    funnel_retrieval: str = "exact"
    # int8 shortlist width multiplier: K*oversample candidates survive the
    # quantized pass into the exact f32 rescore
    funnel_oversample: int = 4
    # publish-time recall gate (funnel/recall.py): an int8 publish whose
    # measured recall@top_k falls under this is refused
    funnel_min_recall: float = 0.95
    # online continuous training (task_type=online-train, online/trainer.py):
    # publish a servable version every N optimizer steps (0 = only at
    # stream end); stop after N batches (0 = unbounded); stop after N
    # seconds without new events (0 = tail forever)
    online_publish_every_steps: int = 100
    online_max_batches: int = 0
    online_idle_timeout_secs: float = 0.0
    # in-process crash retries with resume-from-checkpoint (the spot-retry
    # analog of use_spot_instances/max_wait, both notebooks cell 4)
    max_restarts: int = 0
    restart_backoff_secs: float = 5.0

    @property
    def host_rank(self) -> int:
        try:
            return list(self.hosts).index(self.current_host)
        except ValueError:
            raise ValueError(
                f"current_host {self.current_host!r} is not in hosts "
                f"{list(self.hosts)!r} — check SM_CURRENT_HOST/SM_HOSTS or "
                f"DEEPFM_CURRENT_HOST/DEEPFM_HOSTS consistency"
            ) from None

    @property
    def num_hosts(self) -> int:
        return len(self.hosts)


@dataclass(frozen=True)
class Config:
    model: ModelConfig = field(default_factory=ModelConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    data: DataConfig = field(default_factory=DataConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    run: RunConfig = field(default_factory=RunConfig)
    elastic: ElasticConfig = field(default_factory=ElasticConfig)
    fleet: FleetConfig = field(default_factory=FleetConfig)
    slo: SloConfig = field(default_factory=SloConfig)
    flywheel: FlywheelConfig = field(default_factory=FlywheelConfig)
    regions: RegionsConfig = field(default_factory=RegionsConfig)

    def __post_init__(self):
        """Cross-section contracts no single section can check.

        A mis-sized exchange capacity or an unpackable sort bound does
        not produce a wrong answer — it produces a SLOW one (permanent
        psum fallback, variadic argsort), which nothing downstream would
        ever flag.  Validate at config time: degenerate-by-construction
        shapes raise, merely-suspicious ones warn loudly."""
        import math
        import warnings

        m, o, d, mesh = self.model, self.optimizer, self.data, self.mesh
        mp, dp = mesh.model_parallel, mesh.data_parallel
        # 1. alltoall request capacity vs the batch shape: a fraction so
        # small that one example's field_size distinct ids cannot fit even
        # when spread perfectly across owners means the overflow psum
        # fallback engages on essentially EVERY batch — the exchange would
        # silently run as (slower-than-)psum forever.
        if m.shard_exchange_capacity > 0 and m.shard_exchange != "psum" \
                and mp > 1:
            n_local = -(-d.batch_size // max(1, dp)) * m.field_size
            cap = max(1, min(
                math.ceil(m.shard_exchange_capacity * n_local), n_local))
            if cap * mp < m.field_size:
                raise ValueError(
                    f"shard_exchange_capacity={m.shard_exchange_capacity} "
                    f"gives {cap} request slots/owner x {mp} owners < "
                    f"field_size={m.field_size}: one example's distinct "
                    f"ids cannot fit, so the overflow psum fallback would "
                    f"engage on every batch — raise the capacity (0 = "
                    f"auto: ceil(N/M))"
                )
            even = -(-n_local // mp)
            if cap < -(-even // 2):
                warnings.warn(
                    f"shard_exchange_capacity={m.shard_exchange_capacity} "
                    f"({cap} slots/owner) is under half the even-spread "
                    f"requirement ceil(N/M)={even} for "
                    f"N={n_local} local ids on {mp} owners — expect "
                    f"frequent overflow fallback to the dense psum path "
                    f"(parallel/embedding.py)", stacklevel=2,
                )
        # 1b. zero_sharding='on' with a declared single-replica data axis
        # is a silent no-op (there is nothing to shard the update across);
        # warn so a flag meant for the pod doesn't quietly do nothing on a
        # one-replica debug mesh.  dp == -1 (auto) is resolved at mesh
        # build time and stays quiet here.
        if o.zero_sharding == "on" and dp == 1:
            warnings.warn(
                "optimizer.zero_sharding='on' with mesh.data_parallel=1 "
                "is a no-op: the weight update shards across the data "
                "axis, and there is only one data shard "
                "(train/optimizer.zero_sharded)", stacklevel=2,
            )
        # 2. packed-sort id bound: the dedup paths (exchange plan, lazy
        # pack) sort (id, position) packed into ONE uint32 key; a vocab
        # too large for the local stream length sorts two operands
        # instead.  On a v5e that is 1.16 against 1.06 ms at 319,488 ids
        # (PERF.md §6, PR 27); on XLA:CPU about 4x.  Correct — say so.
        exchanges = mp > 1 or (o.lazy_embedding_updates and dp > 1)
        if exchanges and dp > 0:
            n_local = -(-d.batch_size // dp) * m.field_size
            bound = m.feature_size + 1  # +1: the out-of-range sentinel
            if bound > packed_sort_id_bound(n_local):
                warnings.warn(
                    f"feature_size={m.feature_size} exceeds the packed-"
                    f"sort id bound {packed_sort_id_bound(n_local)} for "
                    f"{n_local} local ids/shard: dedup sorts fall back to "
                    f"the two-operand sort (ops/embedding.py sort_segments; "
                    f"about 4x on XLA:CPU, 1.1x on a v5e).  Tiered embeddings "
                    f"(model.tiered_embeddings) probe in SLOT space and "
                    f"keep the packed sort at any vocabulary.",
                    stacklevel=2,
                )
        # 3. tiered cache geometry vs the batch's id stream
        if m.tiered_embeddings:
            bf = d.batch_size * m.field_size
            if 0 < m.tiered_hot_slots < bf:
                raise ValueError(
                    f"tiered_hot_slots={m.tiered_hot_slots} cannot hold "
                    f"one batch's id stream (batch_size*field_size={bf})"
                )
            if 0 < m.tiered_stage_rows < bf:
                warnings.warn(
                    f"tiered_stage_rows={m.tiered_stage_rows} < "
                    f"batch_size*field_size={bf}: a cache-cold batch can "
                    f"miss on every id and overflow the staging pack "
                    f"(the pager raises at run time)", stacklevel=2,
                )
            h = m.tiered_host_rows
            if h and h - max(1, h // 16) < bf:
                # one fill must fit inside the host tier's serviceable
                # window (capacity minus one eviction chunk) or a cold
                # batch's miss fetch cannot be satisfied (HostTier
                # raises rather than thrash)
                raise ValueError(
                    f"tiered_host_rows={h} cannot service one batch's "
                    f"miss fetch (window {h - max(1, h // 16)} < "
                    f"batch_size*field_size={bf})"
                )
        # 4. recommendation funnel geometry (deepfm_tpu/funnel): lax.top_k
        # cannot select more rows than one index shard holds (the retrieve
        # executable would be unbuildable), and a user's K-candidate rank
        # fan-out must land on a precompiled serving bucket — K over the
        # largest bucket means even a lone recommend row cannot dispatch
        # through any single rank executable (the pigeonhole), while a
        # bucket padding to >= 2x K halves the rank throughput silently
        # (the wasteful case).  Runtime re-validates against the actual
        # serve mesh (funnel/index.make_funnel_context); this is the
        # config-time gate on the declared topology.
        r = self.run
        # the quantized-tier knobs validate even without funnel_top_k —
        # a typo'd mode string must fail the config load, not the serve
        # boot hours later.  The literal mirrors funnel/quant.py
        # RETRIEVAL_MODES (config stays import-light; a sync test pins
        # the two)
        retrieval_modes = ("exact", "int8", "auto")
        if r.funnel_retrieval not in retrieval_modes:
            raise ValueError(
                f"run.funnel_retrieval={r.funnel_retrieval!r} is not one "
                f"of {retrieval_modes}"
            )
        if r.funnel_oversample < 1:
            raise ValueError(
                f"run.funnel_oversample={r.funnel_oversample} must be "
                f">= 1 (1 = no oversampling, shortlist width == top_k)"
            )
        if not 0.0 < r.funnel_min_recall <= 1.0:
            raise ValueError(
                f"run.funnel_min_recall={r.funnel_min_recall} must lie "
                f"in (0, 1] — it gates int8 publishes"
            )
        if r.funnel_top_k > 0:
            k = r.funnel_top_k
            if r.funnel_return_n > k:
                raise ValueError(
                    f"funnel_return_n={r.funnel_return_n} exceeds "
                    f"funnel_top_k={k} — cannot return more ranked items "
                    f"than candidates retrieved"
                )
            item_vocab = m.item_vocab_size or m.feature_size
            mp_serve = (r.serve_group_model_parallel if r.serve_groups > 0
                        else mp)
            if mp_serve > 0:
                per_shard = -(-item_vocab // mp_serve)
                if k > per_shard:
                    raise ValueError(
                        f"funnel_top_k={k} exceeds the (padded) per-shard "
                        f"item vocab {per_shard} (item vocab {item_vocab} "
                        f"row-sharded over model_parallel={mp_serve}) — "
                        f"per-shard lax.top_k cannot select more rows than "
                        f"a shard holds"
                    )
                # the int8 shortlist widens the per-shard selection to
                # K*oversample — the same pigeonhole, scaled ("auto" is
                # checked at runtime where the capacity is known)
                if (r.funnel_retrieval == "int8"
                        and k * r.funnel_oversample > per_shard):
                    raise ValueError(
                        f"funnel_top_k*funnel_oversample = "
                        f"{k}*{r.funnel_oversample} = "
                        f"{k * r.funnel_oversample} exceeds the (padded) "
                        f"per-shard item vocab {per_shard} — the int8 "
                        f"shortlist's per-shard lax.top_k cannot select "
                        f"more rows than a shard holds; lower "
                        f"funnel_oversample or funnel_top_k"
                    )
            buckets = _parse_int_list(r.serve_buckets)
            if buckets:
                if k > max(buckets):
                    raise ValueError(
                        f"funnel_top_k={k} exceeds the largest serve "
                        f"bucket {max(buckets)}: one user's K ranking rows "
                        f"cannot fit any precompiled dispatch "
                        f"(run.serve_buckets={r.serve_buckets!r}) — raise "
                        f"the bucket set or lower funnel_top_k"
                    )
                fit = min(b for b in buckets if b >= k)
                if fit >= 2 * k:
                    warnings.warn(
                        f"funnel_top_k={k} pads to serve bucket {fit} "
                        f"(>= 2x): every user's candidate set fills under "
                        f"half a rank dispatch — add a ~{k}-row bucket to "
                        f"run.serve_buckets or raise funnel_top_k",
                        stacklevel=2,
                    )
        # 5. multi-tenant fleet spec compatibility: every tenant on the
        # pool must share the pool's executable spec (weights ride as jit
        # arguments, so same-spec tenants serve from ONE precompiled
        # executable set — audit_multitenant proves it at lowering level).
        # A tenant whose model overrides touch an executable-spec field
        # would force per-tenant modules: refuse at load, naming the
        # fields, instead of recompiling mid-traffic.
        base_model = dataclasses.asdict(m)
        for t in self.fleet.tenants:
            diff = tenant_spec_divergence(base_model, t["model"])
            if diff:
                raise ValueError(
                    f"fleet tenant {t['name']!r} diverges from its "
                    f"executable-sharing group on {diff}: same-spec "
                    f"tenants must share ONE precompiled executable set "
                    f"(EXECUTABLE_SPEC_FIELDS) — serve a divergent spec "
                    f"from its own pool instead"
                )
        # 6. data flywheel cross-section contracts: feedback-train is the
        # online trainer pointed at the JOIN's output — without a join
        # output URL there is nothing to cursor over; and when a shadow
        # challenger is armed alongside impression logging, mismatched
        # sampling rates mean the offline join replays a different slice
        # of traffic than shadow scoring measured — legal, but the two
        # reads are then not comparable, so say so once at config time.
        fw = self.flywheel
        if r.task_type in ("feedback-train", "feedback_train") \
                and not fw.join_output_url:
            raise ValueError(
                "task_type=feedback-train needs flywheel.join_output_url "
                "— the joined labeled stream the online trainer tails "
                "(run `python -m deepfm_tpu.flywheel.join` to produce it)"
            )
        if fw.enabled and any(
                t.get("shadow_of") for t in self.fleet.tenants):
            shadow_rate = self.fleet.shadow_sample_percent / 100.0
            if abs(shadow_rate - fw.sample_rate) > 1e-9:
                warnings.warn(
                    f"flywheel.sample_rate={fw.sample_rate} differs from "
                    f"fleet.shadow_sample_percent="
                    f"{self.fleet.shadow_sample_percent} while a shadow "
                    f"challenger is armed: the flywheel join and shadow "
                    f"scoring will read different traffic slices — align "
                    f"the rates if the joined labels should explain the "
                    f"shadow's divergence", stacklevel=2,
                )
        # 7. cross-region serving: the home root's retention window must
        # cover the staleness SLO — a region allowed to run
        # max_version_skew versions behind will FETCH those versions
        # from the home root while catching up, so retaining fewer than
        # skew+1 versions can delete a version a still-inside-SLO region
        # is mid-fetch on (region/replicator.py).
        rg = self.regions
        if rg.enabled:
            window = max(self.run.keep_checkpoints,
                         rg.publish_keep_window)
            if window < rg.max_version_skew + 1:
                warnings.warn(
                    f"regions.publish_keep_window={rg.publish_keep_window}"
                    f" (effective retention {window} with "
                    f"run.keep_checkpoints={self.run.keep_checkpoints}) "
                    f"is under max_version_skew+1="
                    f"{rg.max_version_skew + 1}: home retention can "
                    f"delete a version a lagging-but-inside-SLO region "
                    f"is still catching up to — widen the keep window",
                    stacklevel=2,
                )

    # ---- overrides ------------------------------------------------------

    def with_overrides(self, **sections: dict[str, Any]) -> "Config":
        """Return a new Config with per-section field overrides:
        ``cfg.with_overrides(model={'embedding_size': 64})``."""
        updates = {}
        for section, fields in sections.items():
            cur = getattr(self, section)
            updates[section] = dataclasses.replace(cur, **fields)
        return dataclasses.replace(self, **updates)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "Config":
        """Build from a nested dict (the config.json schema).

        Unknown keys are dropped with a warning rather than raising: saved
        configs (servables, checkpoints) must keep loading across framework
        versions that add or retire fields.  CLI ``--set`` overrides go
        through ``with_overrides`` instead, which still rejects typos."""

        def known(section_cls, section: dict, name: str) -> dict:
            fields = {f.name for f in dataclasses.fields(section_cls)}
            out = {}
            for k, v in section.items():
                if k not in fields:
                    import logging

                    logging.getLogger(__name__).warning(
                        "config: ignoring unknown field %s.%s "
                        "(saved by a different framework version?)", name, k
                    )
                    continue
                out[k] = tuple(v) if isinstance(v, list) else v
            return out

        return cls(
            model=ModelConfig(**known(ModelConfig, d.get("model", {}), "model")),
            optimizer=OptimizerConfig(
                **known(OptimizerConfig, d.get("optimizer", {}), "optimizer")
            ),
            data=DataConfig(**known(DataConfig, d.get("data", {}), "data")),
            mesh=MeshConfig(**known(MeshConfig, d.get("mesh", {}), "mesh")),
            run=RunConfig(**known(RunConfig, d.get("run", {}), "run")),
            elastic=ElasticConfig(
                **known(ElasticConfig, d.get("elastic", {}), "elastic")
            ),
            fleet=FleetConfig(
                **known(FleetConfig, d.get("fleet", {}), "fleet")
            ),
            slo=SloConfig(**known(SloConfig, d.get("slo", {}), "slo")),
            flywheel=FlywheelConfig(
                **known(FlywheelConfig, d.get("flywheel", {}), "flywheel")
            ),
            regions=RegionsConfig(
                **known(RegionsConfig, d.get("regions", {}), "regions")
            ),
        )

    @classmethod
    def from_json(cls, path: str) -> "Config":
        with open(path) as f:
            return cls.from_dict(json.load(f))

    @classmethod
    def from_env(cls, base: "Config | None" = None) -> "Config":
        """Fold platform environment into a config — the SM_HOSTS /
        SM_CURRENT_HOST / SM_CHANNELS capability (ps:80-95, ps:391) done at an
        explicit call site instead of import time."""
        cfg = base or cls()
        run_fields: dict[str, Any] = {}
        if os.environ.get("SM_HOSTS"):
            run_fields["hosts"] = tuple(json.loads(os.environ["SM_HOSTS"]))
        elif os.environ.get("DEEPFM_HOSTS"):
            run_fields["hosts"] = tuple(os.environ["DEEPFM_HOSTS"].split(","))
        if os.environ.get("SM_CURRENT_HOST"):
            run_fields["current_host"] = os.environ["SM_CURRENT_HOST"]
        elif os.environ.get("DEEPFM_CURRENT_HOST"):
            run_fields["current_host"] = os.environ["DEEPFM_CURRENT_HOST"]
        mesh_fields: dict[str, Any] = {}
        if os.environ.get("DEEPFM_COORDINATOR"):
            mesh_fields["coordinator_address"] = os.environ["DEEPFM_COORDINATOR"]
            mesh_fields["num_processes"] = int(os.environ.get("DEEPFM_NUM_PROCESSES", "1"))
            mesh_fields["process_id"] = int(os.environ.get("DEEPFM_PROCESS_ID", "0"))
        out = cfg
        if run_fields:
            out = out.with_overrides(run=run_fields)
        if mesh_fields:
            out = out.with_overrides(mesh=mesh_fields)
        return out
