"""Launcher CLI + training-driver tests: config resolution and the full
train -> checkpoint -> resume -> eval -> export -> infer lifecycle on the
virtual mesh (the reference's notebook-driven flow, SURVEY §3.1/§3.4)."""

import json
import os

import numpy as np
import pytest

from deepfm_tpu.core.config import Config
from deepfm_tpu.data import generate_synthetic_ctr
from deepfm_tpu.launch.cli import apply_set_overrides, main, resolve_config

FEATURE, FIELD = 300, 6


@pytest.fixture
def data_dir(tmp_path):
    generate_synthetic_ctr(
        tmp_path / "tr-0.tfrecords", num_records=256, feature_size=FEATURE,
        field_size=FIELD, seed=1,
    )
    generate_synthetic_ctr(
        tmp_path / "va-0.tfrecords", num_records=64, feature_size=FEATURE,
        field_size=FIELD, seed=2,
    )
    return tmp_path


def _common_args(data_dir, tmp_path):
    return [
        "--training_data_dir", str(data_dir),
        "--val_data_dir", str(data_dir),
        "--model_dir", str(tmp_path / "model"),
        "--feature_size", str(FEATURE),
        "--field_size", str(FIELD),
        "--embedding_size", "4",
        "--deep_layers", "8,4",
        "--batch_size", "32",
        "--num_epochs", "2",
        "--no_env",
        "--set", "model.dropout_keep=[1.0,1.0]",
        "--set", "model.compute_dtype=float32",
        "--set", "run.log_steps=4",
        "--set", "run.checkpoint_every_steps=0",
        "--set", "mesh.data_parallel=4", "--set", "mesh.model_parallel=2",
    ]


def test_resolve_config_flags_and_sets(tmp_path):
    cfg, _ = resolve_config(
        ["--feature_size", "123", "--deep_layers", "64,32", "--no_env",
         "--set", "optimizer.name=Adagrad", "--set", "model.batch_norm=true"]
    )
    assert cfg.model.feature_size == 123
    assert cfg.model.deep_layers == (64, 32)
    assert cfg.optimizer.name == "Adagrad"
    assert cfg.model.batch_norm is True


def test_list_values_accept_tuple_and_bracket_spellings():
    """Users paste python tuples into --set; "(8,4)" and "[8,4]" must parse
    like the canonical "8,4" (both int and float lists)."""
    cfg, _ = resolve_config(
        ["--no_env", "--set", "model.deep_layers=(8,4)",
         "--set", "model.dropout_keep=[0.9,0.8]"]
    )
    assert cfg.model.deep_layers == (8, 4)
    assert cfg.model.dropout_keep == (0.9, 0.8)


def test_resolve_config_from_json_file(tmp_path):
    path = tmp_path / "cfg.json"
    path.write_text(json.dumps({"model": {"embedding_size": 16}}))
    cfg, _ = resolve_config(["--config", str(path), "--no_env"])
    assert cfg.model.embedding_size == 16
    # CLI flag beats file
    cfg, _ = resolve_config(["--config", str(path), "--embedding_size", "8", "--no_env"])
    assert cfg.model.embedding_size == 8


def test_env_folding(tmp_path, monkeypatch):
    monkeypatch.setenv("SM_HOSTS", json.dumps(["algo-1", "algo-2"]))
    monkeypatch.setenv("SM_CURRENT_HOST", "algo-2")
    cfg, _ = resolve_config([])
    assert cfg.run.hosts == ("algo-1", "algo-2")
    assert cfg.run.host_rank == 1


def test_bad_set_override():
    with pytest.raises(SystemExit, match="section.key"):
        apply_set_overrides(Config(), ["nodots"])
    with pytest.raises(SystemExit, match="bad --set override"):
        apply_set_overrides(Config(), ["model.not_a_field=1"])


def test_print_config(capsys):
    rc = main(["--print_config", "--feature_size", "42", "--no_env"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["model"]["feature_size"] == 42


def test_serve_task_dispatch(monkeypatch):
    """task_type=serve routes to serve/server.serve_forever with the
    RunConfig serving knobs (the TF-Serving step of the workflow)."""
    from deepfm_tpu.serve import server as srv
    from deepfm_tpu.train.loop import run_task

    calls = {}

    def fake_serve(servable_dir, **kw):
        calls["dir"] = servable_dir
        calls.update(kw)

    monkeypatch.setattr(srv, "serve_forever", fake_serve)
    cfg = Config.from_dict(
        {
            "run": {
                "task_type": "serve",
                "servable_model_dir": "/x/servable",
                "serve_port": 1234,
                "serve_host": "0.0.0.0",
            }
        }
    )
    assert run_task(cfg) is None
    assert calls == {
        "dir": "/x/servable",
        "port": 1234,
        "host": "0.0.0.0",
        "buckets": "8,32,128,512",
        "max_wait_ms": 2.0,
        "item_corpus": None,
        "reload_url": None,  # run.serve_reload_url="" -> hot reload off
        "reload_interval_secs": 2.0,
        "funnel_top_k": 0,   # 0 = the servable's funnel.json defaults
        "funnel_return_n": 0,
        # ""/0 = the servable's published retrieval section; config
        # defaults are not operator overrides
        "funnel_retrieval": "",
        "funnel_oversample": 0,
    }


def test_full_lifecycle_train_eval_export_infer(data_dir, tmp_path, capsys):
    """End-to-end: train 2 epochs on the 4x2 mesh, checkpoint, eval, export,
    then resume more training and run infer to pred.txt."""
    servable = tmp_path / "servable"
    rc = main(
        _common_args(data_dir, tmp_path)
        + ["--task_type", "train", "--servable_model_dir", str(servable)]
    )
    assert rc == 0
    out_lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    kinds = [l["kind"] for l in out_lines]
    assert "train" in kinds and "eval" in kinds and "export" in kinds
    evals = [l for l in out_lines if l["kind"] == "eval"]
    assert 0.0 <= evals[-1]["auc"] <= 1.0
    assert os.path.exists(servable / "config.json")

    # rerun of the completed job: input-position resume skips the already-
    # consumed stream, so no extra training happens (planned work runs once)
    rc = main(_common_args(data_dir, tmp_path) + ["--task_type", "train"])
    assert rc == 0
    out_lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    resume = [l for l in out_lines if l["kind"] == "resume"]
    assert resume and resume[0]["step"] == 16
    assert not [l for l in out_lines if l["kind"] == "train"]

    # extending the plan (num_epochs 2 -> 4) resumes at 16 and trains to 32
    rc = main(
        _common_args(data_dir, tmp_path)
        + ["--task_type", "train", "--num_epochs", "4"]
    )
    assert rc == 0
    out_lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    steps = [l["step"] for l in out_lines if l["kind"] == "train"]
    assert max(steps) == 32

    # eval task standalone
    rc = main(_common_args(data_dir, tmp_path) + ["--task_type", "eval"])
    assert rc == 0

    # infer: writes one probability per line for every test record
    rc = main(
        _common_args(data_dir, tmp_path)
        + ["--task_type", "infer", "--test_data_dir", str(data_dir)]
    )
    assert rc == 0
    pred = data_dir / "pred.txt"
    assert pred.exists()
    probs = [float(x) for x in pred.read_text().splitlines()]
    # no te* files exist, so infer falls back to the va* set (64 records)
    assert len(probs) == 64
    assert all(0.0 <= p <= 1.0 for p in probs)


def test_periodic_eval_cadence(data_dir, tmp_path, capsys):
    """In-training eval fires on the throttle clock (ps:510-520 semantics)."""
    rc = main(
        _common_args(data_dir, tmp_path)
        + ["--task_type", "train",
           "--set", "run.eval_throttle_secs=1",
           "--set", "run.eval_start_delay_secs=0",
           "--set", "data.num_epochs=60"]
    )
    assert rc == 0
    out_lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    evals = [l for l in out_lines if l["kind"] == "eval"]
    # at least one periodic eval fired before the end-of-training eval
    assert len(evals) >= 2
    assert all(0.0 <= e["auc"] <= 1.0 for e in evals)
