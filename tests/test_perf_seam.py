"""The benchmark's seam with the package, guarded from tier-1.

``perf/`` (BENCHMARK.json's command) calls the package by name —
``Config().with_overrides``, ``spmd.make_context``, ``create_spmd_state``,
``make_spmd_train_step``, ``shard_batch``, ``DevicePrefetcher`` — and
``perf/control.py`` swaps ``sigmoid_cross_entropy`` by name in every loaded
module of the package that binds it.  Its own tests (``perf/tests/``) are run
by hand, so a rename or a deleted config field would otherwise show first on
the chip.  Here, on the CPU: every configuration the benchmark and its fixture
name builds its ``Config``; each ``tiny-*`` fixture cell runs a window end to
end and is ``correct``; the planted half-batch fault comes out not
``correct``; every name ``perf/`` imports from the package resolves; each
benchmark cell's step lowers with the metric names and the state tree the
benchmark's reference and readers know.  Reads ``perf/``, edits nothing there.
The feed's readers are ``tests/test_train_trace.py``'s.
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
# what perf/entries/train.py calls on parallel/spmd.py
SPMD_SEAM = {"make_context", "create_spmd_state", "make_spmd_train_step",
             "shard_batch"}


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
    """The fault is planted in every loaded ``deepfm_tpu`` module that binds
    the loss by name — the one whose global the shared click-through loss
    calls through (``models/click_through.py``) among them — halves what
    each returns, and every binding is put back."""
    import numpy as np

    from deepfm_tpu.models import click_through

    from perf import control

    binders = {n: mod for n, mod in sys.modules.items()
               if n.split(".")[0] == "deepfm_tpu"
               and callable(getattr(mod, control.LOSS, None))}
    assert click_through.__name__ in binders
    real = {n: getattr(mod, control.LOSS) for n, mod in binders.items()}
    logits, labels = np.zeros(8, np.float32), np.ones(8, np.float32)
    unplant = control.plant_half_batch_in_program()
    try:
        for n, mod in binders.items():
            planted = getattr(mod, control.LOSS)
            assert planted is not real[n], n
            assert planted(logits, labels).shape == (4,), n
        result = _run(_cell("fixture", name))
    finally:
        unplant()
    for n, mod in binders.items():
        assert getattr(mod, control.LOSS) is real[n], n
    assert result["correct"] is False
    failed = {k for k, r in result["checks"].items() if r["value"] > r["limit"]}
    assert {"grad_diff", "row_diff"} <= failed


def _spmd_reads(tree: ast.AST) -> tuple[set, set]:
    """``spmd.<attr>`` reads in ``tree``: (those read for real, those read
    only inside a test that itself holds a ``hasattr(spmd, …)`` call — what
    ``perf/`` reads only where the attribute is there)."""
    def is_guard(n):
        return (isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
                and n.func.id == "hasattr" and n.args
                and isinstance(n.args[0], ast.Name) and n.args[0].id == "spmd")

    under_guard = set()
    for node in ast.walk(tree):
        if (isinstance(node, (ast.If, ast.IfExp, ast.While))
                and any(is_guard(n) for n in ast.walk(node.test))):
            under_guard |= {id(n) for n in ast.walk(node.test)}
    required, guarded = set(), set()
    for n in ast.walk(tree):
        if (isinstance(n, ast.Attribute) and isinstance(n.value, ast.Name)
                and n.value.id == "spmd"):
            (guarded if id(n) in under_guard else required).add(n.attr)
    return required, guarded - required


def test_every_package_name_the_benchmark_uses_resolves():
    """By ``ast`` over ``perf/**/*.py`` outside ``perf/tests/``: every
    ``from deepfm_tpu.<module> import <name>`` resolves, and every
    ``spmd.<attr>`` is an attribute of ``parallel/spmd.py`` — but for one
    that ``perf/`` reads only inside a test guarded by ``hasattr(spmd, …)``
    (``perf/control.py``'s post-condition on a name ``spmd`` may no longer
    bind), found by the guard, not by a list of names."""
    imported, spmd_attrs, guarded = set(), set(), set()
    for path in sorted((ROOT / "perf").rglob("*.py")):
        if "tests" in path.relative_to(ROOT / "perf").parts:
            continue
        tree = ast.parse(path.read_text())
        required, only_guarded = _spmd_reads(tree)
        spmd_attrs |= required
        guarded |= only_guarded
        for node in ast.walk(tree):
            if (isinstance(node, ast.ImportFrom) and node.level == 0
                    and (node.module or "").split(".")[0] == "deepfm_tpu"):
                imported |= {(node.module, a.name) for a in node.names}
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
    # the excuse is as narrow as the guard: an attribute read anywhere
    # without one is required above, and the guarded one is the loss
    assert guarded - spmd_attrs == {"sigmoid_cross_entropy"}


def test_the_hasattr_excuse_is_found_by_its_guard_not_by_name():
    tree = ast.parse(
        "if hasattr(spmd, NAME) and spmd.maybe_gone is real:\n"
        "    spmd.used_in_the_body()\n"
        "x = spmd.plain_read\n"
        "if other and spmd.not_guarded:\n    pass\n"
        "y = spmd.both if hasattr(spmd, 'both') else spmd.both_fallback\n"
        "z = spmd.both\n")
    required, guarded = _spmd_reads(tree)
    assert guarded == {"maybe_gone"}
    assert required == {"used_in_the_body", "plain_read", "not_guarded",
                        "both", "both_fallback"}


BENCHMARK_CELLS = [w["name"] for w in _manifest("benchmark")["workloads"]]
# the parent's: what perf/reference/ rebuilds from the seed and compares by
# leaf name, and what the benchmark's readers and the loop's log lines read
STEP_METRICS = {"loss", "ce", "pred_mean", "label_mean", "loss_per_shard"}
PARAM_LEAVES = {
    "deepfm": {
        "fm_b": (1,), "fm_v": (12_500_000, 32), "fm_w": (12_500_000,),
        "mlp/layer_0/bias": (128,), "mlp/layer_0/kernel": (1248, 128),
        "mlp/layer_1/bias": (64,), "mlp/layer_1/kernel": (128, 64),
        "mlp/layer_2/bias": (32,), "mlp/layer_2/kernel": (64, 32),
        "mlp/out/bias": (1,), "mlp/out/kernel": (32, 1),
    },
    "xdeepfm": {
        "cin/filter_0": (39, 39, 200), "cin/filter_1": (200, 39, 200),
        "cin/filter_2": (200, 39, 200), "cin/out/bias": (1,),
        "cin/out/kernel": (600, 1),
        "fm_b": (1,), "fm_v": (12_500_000, 10), "fm_w": (12_500_000,),
        "mlp/layer_0/bias": (400,), "mlp/layer_0/kernel": (390, 400),
        "mlp/layer_1/bias": (400,), "mlp/layer_1/kernel": (400, 400),
        "mlp/out/bias": (1,), "mlp/out/kernel": (400, 1),
    },
}


def _lfm2_leaves() -> dict:
    """The contract between ``models/lfm2_moe.py`` and
    ``perf/reference/lfm2_moe.py`` at the published widths: published layers
    1–5 as layer_0 … layer_4."""
    norms = {"op_norm": (2048,), "ffn_norm": (2048,)}
    conv = {"conv/in_proj": (2048, 6144), "conv/conv": (3, 2048),
            "conv/out_proj": (2048, 2048)}
    experts = {"experts/w1": (8, 2048, 1536), "experts/w3": (8, 2048, 1536),
               "experts/w2": (8, 1536, 2048), "router/gate": (2048, 64)}
    layers = [
        {**norms, **conv, "dense_ffn/w1": (2048, 11776),
         "dense_ffn/w3": (2048, 11776), "dense_ffn/w2": (11776, 2048)},
        {**norms, **experts,
         "attention/q_proj": (2048, 2048), "attention/k_proj": (2048, 512),
         "attention/v_proj": (2048, 512), "attention/o_proj": (2048, 2048),
         "attention/q_norm": (64,), "attention/k_norm": (64,)},
    ] + [{**norms, **conv, **experts}] * 3
    return {"tok_embedding": (8192, 2048), "out_norm": (2048,),
            **{f"layer_{l}/{k}": shape for l, layer in enumerate(layers)
               for k, shape in layer.items()}}


def _evabyte_leaves() -> dict:
    """The contract between ``models/evabyte.py`` and
    ``perf/reference/evabyte.py`` at the published widths: published layers
    0–3 as one tree of stacked leaves, heads 0–7 of 32."""
    layer = {"attn_norm": (4096,), "ffn_norm": (4096,),
             "attention/q_proj": (4096, 1024), "attention/k_proj": (4096, 1024),
             "attention/v_proj": (4096, 1024), "attention/o_proj": (1024, 4096),
             "attention/phi": (8, 128), "attention/mu": (8, 128),
             "dense_ffn/w1": (4096, 11008), "dense_ffn/w3": (4096, 11008),
             "dense_ffn/w2": (11008, 4096)}
    return {"byte_embedding": (320, 4096), "heads": (4096, 2560),
            "out_norm": (4096,),
            **{f"layers/{k}": (4, *shape) for k, shape in layer.items()}}


def _keye_vl2_leaves() -> dict:
    """The contract between ``models/keye_vl2.py`` and
    ``perf/reference/keye_vl2.py`` at the published widths: published layers
    0–3, experts 0–15 of 128, a head's size (128) apart from the hidden
    size, the indexer whole, the head untied."""
    layer = {"op_norm": (2048,), "ffn_norm": (2048,),
             "attention/q_proj": (2048, 4096), "attention/k_proj": (2048, 512),
             "attention/v_proj": (2048, 512), "attention/o_proj": (4096, 2048),
             "attention/q_norm": (128,), "attention/k_norm": (128,),
             "indexer/q_proj": (2048, 1024), "indexer/k_proj": (2048, 64),
             "indexer/w_proj": (2048, 16),
             "experts/w1": (16, 2048, 768), "experts/w3": (16, 2048, 768),
             "experts/w2": (16, 768, 2048), "router/gate": (2048, 128)}
    return {"tok_embedding": (18992, 2048), "lm_head": (2048, 18992),
            "out_norm": (2048,),
            **{f"layer_{l}/{k}": shape for l in range(4)
               for k, shape in layer.items()}}


# per family: the TRUE rows of its table share, its parameter leaves, the
# non-trainable state beside them, its placed batch ((b, f) the traffic's
# batch and the configuration's field_size) and its step's metrics
CONTRACTS = {
    **{name: {
        "true_feature_size": 12_500_000,
        "leaves": PARAM_LEAVES[name],
        "model_state": {},
        "batch": lambda b, f: {"feat_ids": ((b, f), "int32"),
                               "feat_vals": ((b, f), "float32"),
                               "label": ((b,), "float32")},
        "metrics": STEP_METRICS,
    } for name in PARAM_LEAVES},
    "lfm2_moe": {
        "true_feature_size": 8192,
        "leaves": _lfm2_leaves(),
        # the routers' selection biases: drawn from the seed, never stepped
        "model_state": {f"layer_{l}/expert_bias": (64,) for l in (1, 2, 3, 4)},
        "batch": lambda b, f: {"feat_ids": ((b, f), "int32")},
        "metrics": {"loss", "ce", "rows_held_share", "expert_load_max_share",
                    "experts_compact_share", "blocks_products_kept_share",
                    "loss_per_shard"},
    },
    "evabyte": {
        "true_feature_size": 320,
        "leaves": _evabyte_leaves(),
        "model_state": {},
        "batch": lambda b, f: {"feat_ids": ((b, f), "int32")},
        "metrics": {"loss", "ce", "heads_held_share", "eva_summary_key_share",
                    "blocks_products_kept_share", "loss_per_shard"},
    },
    "keye_vl2": {
        "true_feature_size": 18992,
        "leaves": _keye_vl2_leaves(),
        "model_state": {},
        "batch": lambda b, f: {"feat_ids": ((b, f), "int32")},
        "metrics": {"loss", "ce", "rows_held_share", "expert_load_max_share",
                    "experts_compact_share", "index_loss",
                    "index_selected_share", "index_kernel_share",
                    "blocks_products_kept_share", "loss_per_shard"},
    },
}


@pytest.mark.parametrize("name", BENCHMARK_CELLS)
def test_benchmark_cell_step_lowers_with_the_parents_names_and_state(name):
    """At the cell's own size, abstract state, on the CPU: the jitted
    function is still ``local_step`` (``reduce_xplane`` finds the step by
    it), its metrics are its family's (the click-through families' five are
    the parent's), and the state is the tree the family's plain reference
    rebuilds — parameter leaves by name and shape, Adam's ``mu`` / ``nu``
    mirroring them, the family's non-trainable state, nothing else."""
    import jax
    from jax.sharding import NamedSharding

    from deepfm_tpu.parallel import spmd
    from deepfm_tpu.parallel.mesh import build_mesh
    from perf.entries import train

    cfg = train.build_config(_cell("benchmark", name), seed=1)
    want = CONTRACTS[cfg.model.model_name]
    mesh = build_mesh(cfg.mesh, devices=jax.devices()[:1])
    ctx = spmd.make_context(cfg, mesh)
    assert (ctx.true_feature_size == want["true_feature_size"]
            == ctx.cfg.model.feature_size)
    abstract = spmd.abstract_spmd_state(ctx)

    def leaves(tree):
        return {"/".join(str(k.key) for k in path): leaf.shape for path, leaf
                in jax.tree_util.tree_flatten_with_path(tree)[0]}

    assert leaves(abstract.params) == want["leaves"]
    (adam, _empty), = [abstract.opt_state]
    assert leaves(adam.mu) == want["leaves"] == leaves(adam.nu)
    assert leaves(abstract.model_state) == want["model_state"]
    assert abstract.step.shape == ()
    assert len(jax.tree_util.tree_leaves(abstract)) == (
        3 * len(want["leaves"]) + 3 + len(want["model_state"]))

    state = jax.tree_util.tree_map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        abstract, ctx.state_shardings)
    batch = {k: jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(
        mesh, ctx.batch_specs[k])) for k, (shape, dtype) in want["batch"](
            cfg.data.batch_size, cfg.model.field_size).items()}
    assert set(ctx.batch_specs) == set(batch)
    lowered = spmd.make_spmd_train_step(ctx).lower(state, batch)
    assert "jit_local_step" in lowered.as_text()[:400]
    new_state, metrics = lowered.out_info
    assert set(metrics) == want["metrics"]
    assert metrics["loss_per_shard"].shape == (1,)
    assert (jax.tree_util.tree_structure(new_state)
            == jax.tree_util.tree_structure(abstract))
