"""The benchmark's seam with the package, guarded from tier-1.

``perf/`` (BENCHMARK.json's command) calls the package by name —
``Config().with_overrides``, ``spmd.make_context``, ``create_spmd_state``,
``make_spmd_train_step``, ``shard_batch``, ``DevicePrefetcher`` — and
``perf/control.py`` swaps ``spmd.sigmoid_cross_entropy`` by name.  Its own
tests (``perf/tests/``) are run by hand, so a rename or a deleted config field
would otherwise show first on the chip.  Here, on the CPU: every configuration
the benchmark and its fixture name builds its ``Config``; each ``tiny-*``
fixture cell runs a window end to end and is ``correct``; the planted
half-batch fault comes out not ``correct``; every name ``perf/`` imports from
the package resolves.  Reads ``perf/``, edits nothing there.  The feed's
readers are ``tests/test_train_trace.py``'s.
"""

import ast
import importlib
import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

MANIFESTS = {
    "benchmark": ROOT / "BENCHMARK.json",
    "fixture": ROOT / "perf" / "tests" / "fixture_manifest.json",
}
# what perf/entries/train.py and perf/control.py call on parallel/spmd.py
SPMD_SEAM = {"make_context", "create_spmd_state", "make_spmd_train_step",
             "shard_batch", "sigmoid_cross_entropy"}


def _manifest(which: str) -> dict:
    return json.loads(MANIFESTS[which].read_text())


TINY_CELLS = [w["name"] for w in _manifest("fixture")["workloads"]]


def _cell(which: str, workload: str):
    from perf import manifest

    return manifest.Cell(_manifest(which), workload, manifest.PERF_DIR)


def _configurations():
    return [(which, c["name"]) for which in MANIFESTS
            for c in _manifest(which)["configs"]]


@pytest.mark.parametrize("which,config", _configurations())
def test_every_configuration_builds_its_config(which, config):
    """``build_config`` is strict (``dataclasses.replace`` raises on a field
    the program no longer has): a PR that deletes a field a configuration file
    names fails here, not on the chip."""
    from perf.entries import train

    workload = next(w["name"] for w in _manifest(which)["workloads"]
                    if w["config"] == config)
    cell = _cell(which, workload)
    cfg = train.build_config(cell, seed=7)
    for section, fields in cell.config["overrides"].items():
        for key, value in fields.items():
            want = tuple(value) if isinstance(value, list) else value
            assert getattr(getattr(cfg, section), key) == want, (section, key)
    assert cfg.data.batch_size == cell.traffic["params"]["batch_size"]
    assert cfg.run.seed == 7


def _run(cell):
    from perf.entries import train

    return train.run(cell, seed=2**31 + 11, seconds=0.3, trace=False,
                     t0=time.perf_counter(), require_chip=False)


@pytest.mark.parametrize("name", TINY_CELLS)
def test_tiny_cell_window_is_correct_with_the_contracts_keys(name):
    result = _run(_cell("fixture", name))
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 3
    assert set(result["metrics"]) == {"train_examples_per_s", "setup_s"}
    assert set(result["checks"]) == {"loss_gap", "grad_gap", "grad_diff",
                                     "row_diff", "delta_gap"}
    json.dumps(result)


@pytest.mark.parametrize("name", TINY_CELLS)
def test_planted_half_batch_comes_out_not_correct(name):
    from deepfm_tpu.parallel import spmd

    from perf import control

    real = spmd.sigmoid_cross_entropy
    unplant = control.plant_half_batch_in_program()
    try:
        assert spmd.sigmoid_cross_entropy is not real
        result = _run(_cell("fixture", name))
    finally:
        unplant()
    assert spmd.sigmoid_cross_entropy is real
    assert result["correct"] is False
    failed = {k for k, r in result["checks"].items() if r["value"] > r["limit"]}
    assert {"grad_diff", "row_diff"} <= failed


def test_every_package_name_the_benchmark_uses_resolves():
    """By ``ast`` over ``perf/**/*.py`` outside ``perf/tests/``: every
    ``from deepfm_tpu.<module> import <name>`` resolves, and every
    ``spmd.<attr>`` is an attribute of ``parallel/spmd.py``."""
    imported, spmd_attrs = set(), set()
    for path in sorted((ROOT / "perf").rglob("*.py")):
        if "tests" in path.relative_to(ROOT / "perf").parts:
            continue
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.ImportFrom) and node.level == 0
                    and (node.module or "").split(".")[0] == "deepfm_tpu"):
                imported |= {(node.module, a.name) for a in node.names}
            elif (isinstance(node, ast.Attribute)
                  and isinstance(node.value, ast.Name)
                  and node.value.id == "spmd"):
                spmd_attrs.add(node.attr)
    assert ("deepfm_tpu.parallel", "spmd") in imported
    assert ("deepfm_tpu.data.pipeline", "DevicePrefetcher") in imported
    for module, name in sorted(imported):
        mod = importlib.import_module(module)
        if not hasattr(mod, name):
            importlib.import_module(f"{module}.{name}")
    assert SPMD_SEAM <= spmd_attrs
    spmd = importlib.import_module("deepfm_tpu.parallel.spmd")
    missing = sorted(a for a in spmd_attrs if not hasattr(spmd, a))
    assert not missing, missing
