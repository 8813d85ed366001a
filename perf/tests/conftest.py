"""CPU, tiny sizes, no persistent compile cache, no topology call at import."""

import json
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def fixture_manifest() -> dict:
    return json.loads((ROOT / "perf" / "tests" / "fixture_manifest.json")
                      .read_text())


@pytest.fixture(scope="session")
def tiny_cell(fixture_manifest):
    from perf import manifest

    return lambda name: manifest.Cell(fixture_manifest, name,
                                      manifest.PERF_DIR)
