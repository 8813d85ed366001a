"""Launcher CLI — the notebook/SageMaker-Estimator capability (SURVEY §2a
rows 11-12) as a command line.

The reference's launch stack was: notebook hyperparameters dict -> SageMaker
serializes to CLI args -> tf.app.flags (ps:37-107) with env-derived defaults.
Here: one CLI with (1) a JSON config file, (2) dotted ``--set section.key=
value`` overrides, (3) platform env folding (SM_HOSTS/SM_CURRENT_HOST or
DEEPFM_* — Config.from_env), applied in that order, then task dispatch.

Multi-host: run one process per host with DEEPFM_COORDINATOR /
DEEPFM_NUM_PROCESSES / DEEPFM_PROCESS_ID set (the mpirun analog, §2b row 5).

Usage:
    python -m deepfm_tpu.launch.cli --task_type train \
        --training_data_dir data/ --val_data_dir data/ \
        --model_dir /tmp/model --set model.embedding_size=32
"""

from __future__ import annotations

import argparse
import json
import sys

from ..core.config import Config
from ..core.platform import configure_runtime


def _coerce(value: str):
    try:
        return json.loads(value)
    except json.JSONDecodeError:
        return value


def parse_set_pairs(pairs: list[str],
                    sections: dict[str, dict] | None = None) -> dict:
    """``section.key=value`` pairs folded into a ``with_overrides``
    sections dict (merging into ``sections`` when given)."""
    out: dict[str, dict] = sections if sections is not None else {}
    for pair in pairs:
        if "=" not in pair or "." not in pair.split("=", 1)[0]:
            raise SystemExit(
                f"--set expects section.key=value, got {pair!r} "
                f"(sections: model, optimizer, data, mesh, run, elastic)"
            )
        key, value = pair.split("=", 1)
        section, field = key.split(".", 1)
        out.setdefault(section, {})[field] = _coerce(value)
    return out


def apply_set_overrides(cfg: Config, pairs: list[str]) -> Config:
    try:
        return cfg.with_overrides(**parse_set_pairs(pairs))
    except TypeError as e:
        raise SystemExit(f"bad --set override: {e}") from None


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="deepfm-tpu",
        description="TPU-native DeepFM distributed training launcher",
    )
    p.add_argument("--config", help="JSON config file (Config.to_dict schema)")
    p.add_argument(
        "--task_type",
        choices=["train", "eval", "infer", "export", "serve",
                 "online-train", "online_train",
                 "feedback-train", "feedback_train", "publish"],
        help="task dispatch (reference ps:77-79; serve = online scoring "
             "over the exported servable; online-train = continuous "
             "training from an event log with versioned publishes the "
             "serving engine hot-reloads; feedback-train = online-train "
             "over the data flywheel's joined impression/click stream "
             "(flywheel.join_output_url, deepfm_tpu/flywheel); publish = "
             "the MPMD publisher half of the elastic trainer/publisher "
             "split — tails committed payloads in model_dir and "
             "publishes versioned servables asynchronously, "
             "elastic/mpmd.py)",
    )
    # the high-traffic flags get first-class spellings (parity with the
    # reference's most-used hyperparameters, ps nb cell 4)
    p.add_argument("--training_data_dir")
    p.add_argument("--val_data_dir")
    p.add_argument("--test_data_dir")
    p.add_argument("--model_dir")
    p.add_argument("--servable_model_dir")
    p.add_argument("--batch_size", type=int)
    p.add_argument("--num_epochs", type=int)
    p.add_argument("--learning_rate", type=float)
    p.add_argument("--feature_size", type=int)
    p.add_argument("--field_size", type=int)
    p.add_argument("--embedding_size", type=int)
    p.add_argument("--deep_layers", help='e.g. "128,64,32"')
    p.add_argument("--dropout", help='keep probabilities, e.g. "0.5,0.5,0.5"')
    p.add_argument("--optimizer", help="Adam|Adagrad|Momentum|Ftrl")
    p.add_argument("--model_name", help="deepfm|xdeepfm|dcnv2|two_tower")
    p.add_argument("--data_parallel", type=int)
    p.add_argument("--model_parallel", type=int)
    p.add_argument(
        "--serve_groups", type=int,
        help="task_type=serve: run the router-fronted shard-group pool "
             "with this many groups (tables row-sharded per group, "
             "group-atomic hot swap; serve/pool/)",
    )
    p.add_argument(
        "--serve_group_mp", type=int,
        help="row-shard degree inside each serve group's mesh "
             "(0 = auto: member host devices / group data_parallel)",
    )
    p.add_argument(
        "--funnel_top_k", type=int,
        help="task_type=serve over a funnel servable (deepfm_tpu/funnel): "
             "candidates retrieved per user before ranking "
             "(0 = the servable's funnel.json default)",
    )
    p.add_argument(
        "--funnel_return_n", type=int,
        help="funnel serving: ranked items returned per user "
             "(0 = the servable's funnel.json default)",
    )
    p.add_argument(
        "--funnel_retrieval", choices=("exact", "int8", "auto"),
        help="funnel retrieval tier (funnel/quant.py): exact f32 "
             "scoring, int8 quantized scoring with exact f32 rescore of "
             "the oversampled shortlist, or auto (int8 at large index "
             "capacity)",
    )
    p.add_argument(
        "--funnel_oversample", type=int,
        help="int8 shortlist width multiplier: K*oversample candidates "
             "survive the quantized pass into the exact rescore",
    )
    p.add_argument(
        "--funnel_min_recall", type=float,
        help="publish-time recall gate for int8 funnel versions "
             "(funnel/recall.py; in (0, 1])",
    )
    p.add_argument(
        "--coordinator_url",
        help="multi-host elastic coordination service "
             "(deepfm_tpu/elastic/coord.py; run one with `python -m "
             "deepfm_tpu.elastic.coord`): training processes hold TTL "
             "leases, agree on membership epochs, and fence every "
             "commit/publish with the lease's monotone token",
    )
    p.add_argument(
        "--lease_ttl_secs", type=float,
        help="coordination lease TTL requested at acquire — a process "
             "silent this long is expired from consensus and its fencing "
             "token goes stale; the coordinator grants it clamped to its "
             "own --lease-ttl ceiling",
    )
    p.add_argument(
        "--serve_tenants",
        help="task_type=serve with --serve_groups: multi-tenant fleet "
             "bindings as JSON (deepfm_tpu/fleet) — "
             '[{"name","source","split_percent","shadow_of"}...]; N '
             "variants share one pool's executables, the router splits "
             "traffic hash-stably and runs shadow challengers",
    )
    p.add_argument(
        "--set",
        action="append",
        default=[],
        metavar="SECTION.KEY=VALUE",
        help="override any config field, e.g. --set model.batch_norm=true",
    )
    p.add_argument("--no_env", action="store_true", help="skip platform env folding")
    p.add_argument(
        "--print_config", action="store_true", help="print resolved config and exit"
    )
    return p


_FLAG_MAP = {
    "task_type": ("run", "task_type"),
    "training_data_dir": ("data", "training_data_dir"),
    "val_data_dir": ("data", "val_data_dir"),
    "test_data_dir": ("data", "test_data_dir"),
    "model_dir": ("run", "model_dir"),
    "servable_model_dir": ("run", "servable_model_dir"),
    "batch_size": ("data", "batch_size"),
    "num_epochs": ("data", "num_epochs"),
    "learning_rate": ("optimizer", "learning_rate"),
    "feature_size": ("model", "feature_size"),
    "field_size": ("model", "field_size"),
    "embedding_size": ("model", "embedding_size"),
    "deep_layers": ("model", "deep_layers"),
    "dropout": ("model", "dropout_keep"),
    "optimizer": ("optimizer", "name"),
    "model_name": ("model", "model_name"),
    "data_parallel": ("mesh", "data_parallel"),
    "model_parallel": ("mesh", "model_parallel"),
    "serve_groups": ("run", "serve_groups"),
    "serve_group_mp": ("run", "serve_group_model_parallel"),
    "funnel_top_k": ("run", "funnel_top_k"),
    "funnel_return_n": ("run", "funnel_return_n"),
    "funnel_retrieval": ("run", "funnel_retrieval"),
    "funnel_oversample": ("run", "funnel_oversample"),
    "funnel_min_recall": ("run", "funnel_min_recall"),
    "serve_tenants": ("fleet", "tenants"),
    "coordinator_url": ("elastic", "coordinator_url"),
    "lease_ttl_secs": ("elastic", "lease_ttl_secs"),
}


def resolve_config(argv: list[str] | None = None) -> tuple[Config, argparse.Namespace]:
    args = build_parser().parse_args(argv)
    cfg = Config.from_json(args.config) if args.config else Config()
    sections: dict[str, dict] = {}
    for flag, (section, field) in _FLAG_MAP.items():
        value = getattr(args, flag)
        if value is not None:
            sections.setdefault(section, {})[field] = value
    # --set pairs fold into the SAME with_overrides pass as the
    # first-class flags: cross-section validation (e.g. feedback-train
    # needs flywheel.join_output_url) must judge the fully-resolved
    # config, never an intermediate state where only half the flags
    # have landed
    parse_set_pairs(args.set, sections)
    if sections:
        try:
            cfg = cfg.with_overrides(**sections)
        except TypeError as e:
            raise SystemExit(f"bad --set override: {e}") from None
    if not args.no_env:
        cfg = Config.from_env(cfg)
    return cfg, args


def main(argv: list[str] | None = None) -> int:
    cfg, args = resolve_config(argv)
    if args.print_config:
        print(json.dumps(cfg.to_dict(), indent=2))
        return 0
    if cfg.run.task_type == "train":
        # catch spot/maintenance signals from here on — the heavy imports
        # below plus model setup take many seconds, and before round 4 a
        # SIGTERM in that window killed the process uncleanly (verdict r03
        # weak #1).  Train only: serve/eval/infer keep default semantics so
        # SIGTERM still terminates them.
        from .preemption import install_early_handler

        install_early_handler()
    configure_runtime()
    from ..checkpoint import maybe_clear
    from ..train.loop import run_task
    from ..utils import MetricLogger
    from .preemption import PreemptedError, run_with_restarts

    # clear ONCE, before the supervisor loop: a crash retry must resume from
    # the latest checkpoint, not re-wipe the model_dir it needs to resume
    # from.  Train only — eval/infer/export READ the model_dir (hvd:372-378
    # clears in the training path only)
    if cfg.run.task_type == "train":
        maybe_clear(cfg.run.model_dir, cfg.run.clear_existing_model)
    cfg = cfg.with_overrides(run={"clear_existing_model": False})
    try:
        run_with_restarts(
            lambda: run_task(cfg),
            max_restarts=cfg.run.max_restarts,
            backoff_secs=cfg.run.restart_backoff_secs,
            on_restart=lambda attempt, e: MetricLogger().event(
                "restart", attempt=attempt, error=f"{type(e).__name__}: {e}"[:200]
            ),
        )
    except PreemptedError:
        # checkpointed and ready to resume; exit 0 so the platform's
        # reschedule (not a crash handler) brings the job back
        return 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
