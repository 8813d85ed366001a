"""Ratings loader + two-tower CLI lifecycle tests."""

import os

import numpy as np
import pytest

from deepfm_tpu.data.ratings import RatingsDataset, load_ratings, parse_ratings_line
from deepfm_tpu.launch.cli import main as cli_main


def test_parse_ratings_line_formats():
    assert parse_ratings_line("1::31::2.5::1260759144") == (1, 31, 2.5)
    assert parse_ratings_line("1,31,2.5,1260759144") == (1, 31, 2.5)
    assert parse_ratings_line("1 31 2.5") == (1, 31, 2.5)
    assert parse_ratings_line("7\t9") == (7, 9, 1.0)
    assert parse_ratings_line("userId,movieId,rating") is None  # header
    assert parse_ratings_line("") is None
    assert parse_ratings_line("# comment") is None


def test_load_ratings_min_rating(tmp_path):
    p = tmp_path / "ratings.csv"
    p.write_text("userId,movieId,rating\n1,10,5.0\n2,20,1.0\n3,30,4.0\n")
    users, items = load_ratings(p)
    np.testing.assert_array_equal(users, [1, 2, 3])
    users, items = load_ratings(p, min_rating=3.5)
    np.testing.assert_array_equal(users, [1, 3])
    np.testing.assert_array_equal(items, [10, 30])


def test_ratings_dataset_batches(tmp_path):
    p = tmp_path / "ratings.dat"
    p.write_text("".join(f"{u}::{u * 2}::5::0\n" for u in range(10)))
    ds = RatingsDataset.from_path(p)
    assert len(ds) == 10
    assert ds.max_ids() == (9, 18)
    batches = list(ds.batches(4, num_epochs=2, shuffle=False))
    assert len(batches) == 4  # 2 per epoch, remainder dropped
    b = batches[0]
    assert b["user_ids"].shape == (4, 1)
    assert b["user_vals"].dtype == np.float32
    # shuffle=True across epochs produces different orders
    b1, b2 = list(ds.batches(8, num_epochs=2, shuffle=True, seed=1))
    assert not np.array_equal(b1["user_ids"], b2["user_ids"])


@pytest.fixture
def ratings_dir(tmp_path):
    rng = np.random.default_rng(0)
    train = tmp_path / "train"
    val = tmp_path / "val"
    train.mkdir()
    val.mkdir()
    # learnable structure: user u prefers item u % 50
    lines = [f"{u},{u % 50},5.0\n" for u in rng.integers(0, 80, size=600)]
    (train / "ratings.csv").write_text("userId,movieId,rating\n" + "".join(lines))
    vlines = [f"{u},{u % 50},5.0\n" for u in rng.integers(0, 80, size=128)]
    (val / "ratings.csv").write_text("".join(vlines))
    return tmp_path


def test_two_tower_cli_train_eval(ratings_dir, tmp_path, capsys):
    model_dir = str(tmp_path / "model")
    servable = str(tmp_path / "servable")
    args = [
        "--task_type", "train",
        "--training_data_dir", str(ratings_dir / "train"),
        "--val_data_dir", str(ratings_dir / "val"),
        "--model_dir", model_dir,
        "--model_name", "two_tower",
        "--batch_size", "32",
        "--num_epochs", "2",
        "--set", "model.user_vocab_size=80",
        "--set", "model.item_vocab_size=50",
        "--set", "model.embedding_size=8",
        "--set", 'model.tower_layers="16"',
        "--set", "model.tower_dim=8",
        "--set", "run.log_steps=8",
        "--set", f"run.servable_model_dir={servable}",
        "--no_env",
    ]
    assert cli_main(args) == 0
    out = capsys.readouterr().out
    assert '"kind": "eval"' in out
    assert "top1_acc" in out
    assert os.path.exists(os.path.join(servable, "config.json"))
    # eval task restores the checkpoint written by train
    args_eval = [a for a in args]
    args_eval[1] = "eval"
    assert cli_main(args_eval) == 0
    out = capsys.readouterr().out
    assert '"kind": "eval"' in out


def test_two_tower_cli_rejects_small_vocab(ratings_dir, tmp_path):
    args = [
        "--task_type", "train",
        "--training_data_dir", str(ratings_dir / "train"),
        "--model_dir", str(tmp_path / "m"),
        "--model_name", "two_tower",
        "--batch_size", "16",
        "--set", "model.user_vocab_size=10",  # ids go up to 79
        "--set", "model.item_vocab_size=50",
        "--no_env",
    ]
    with pytest.raises(ValueError, match="exceed configured vocabs"):
        cli_main(args)


def test_two_tower_cli_rejects_infer(ratings_dir, tmp_path):
    args = [
        "--task_type", "infer",
        "--training_data_dir", str(ratings_dir / "train"),
        "--model_dir", str(tmp_path / "m"),
        "--model_name", "two_tower",
        "--no_env",
    ]
    with pytest.raises(ValueError, match="predict.*two_tower"):
        cli_main(args)
