"""Constants the perf tests share (kept out of conftest.py so that no test
imports a module named ``conftest``, which ``tests/`` has too)."""

from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
TINY_CELLS = ("tiny-deepfm-train", "tiny-xdeepfm-train")
