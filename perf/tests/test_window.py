"""The window driver end to end at a tiny size on the CPU (the look for a chip
skipped), the faults it has to catch, the control, and the proof that a later
PR adds a configuration, a traffic mix and a metric with files of its own."""

import json
import shutil
import subprocess
import sys
import time

import pytest
from perf_test_util import ROOT, TINY_CELLS


def _run(cell, trace=False, seed=2**31 + 11, seconds=0.3):
    from perf.entries import train

    return train.run(cell, seed=seed, seconds=seconds, trace=trace,
                     t0=time.perf_counter(), require_chip=False)


@pytest.mark.parametrize("name", TINY_CELLS)
def test_run_is_correct_and_reports_the_contracts_keys(tiny_cell, name):
    result = _run(tiny_cell(name))
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 3
    assert set(result["metrics"]) == {"train_examples_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert set(result["checks"]) == {"loss_gap", "grad_gap", "grad_diff",
                                     "row_diff", "delta_gap"}
    assert list(result)[-1] == "checks"
    for row in result["checks"].values():
        assert row["value"] <= row["limit"]
    json.dumps(result)


def test_traced_run_reports_host_spans_and_leaves_out_what_it_cannot_read(
        tiny_cell):
    result = _run(tiny_cell("tiny-deepfm-train"), trace=True)
    # no TPU plane on the CPU: the device readers return nothing and the
    # harness leaves their metrics out instead of printing a 0
    assert set(result["metrics"]) == {"feed_wait_share", "step_dispatch_ms"}
    assert "busy_s" not in result["device"]
    assert result["correct"] is True
    assert not (ROOT / ".perf_trace" / "tiny-deepfm-train").exists()


def _break_timed_path(monkeypatch, kind):
    """Break the step the window drives, underneath the harness."""
    from deepfm_tpu.parallel import spmd

    from perf import control

    if kind == "half_batch_in_loss":
        # inside the step's loss; label_mean and every other metric still
        # see the whole batch
        return control.plant_half_batch_in_program()
    real_make = spmd.make_spmd_train_step

    def make(ctx, **kw):
        step = real_make(ctx, donate=False)

        def unchanged(state, batch):
            _, metrics = step(state, batch)
            return state, metrics

        def half_batch_fed(state, batch):
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return step(state, half)

        return {"unchanged": unchanged, "half_batch_fed": half_batch_fed}[kind]

    monkeypatch.setattr(spmd, "make_spmd_train_step", make)
    return lambda: None


def test_the_planted_fault_follows_the_loss_into_every_module_that_binds_it():
    """``from ..train.step import sigmoid_cross_entropy`` copies the binding,
    and each step builder calls its module's own: the fault is planted in
    every loaded ``deepfm_tpu`` module that binds the name (today
    ``train.step``, ``train``, ``parallel.spmd``, ``tiered.step``), halves
    what each returns, and every binding is put back."""
    import numpy as np

    from deepfm_tpu.parallel import spmd
    from deepfm_tpu.tiered import step as tiered_step
    from deepfm_tpu.train import step as train_step

    from perf import control

    binders = {name: mod for name, mod in sys.modules.items()
               if name.split(".")[0] == "deepfm_tpu"
               and hasattr(mod, control.LOSS)}
    assert {spmd.__name__, train_step.__name__,
            tiered_step.__name__} <= set(binders)
    real = {name: getattr(mod, control.LOSS) for name, mod in binders.items()}
    logits, labels = np.zeros(8, np.float32), np.ones(8, np.float32)
    unplant = control.plant_half_batch_in_program()
    try:
        for name, mod in binders.items():
            planted = getattr(mod, control.LOSS)
            assert planted is not real[name], name
            assert planted(logits, labels).shape == (4,), name
    finally:
        unplant()
    for name, mod in binders.items():
        assert getattr(mod, control.LOSS) is real[name], name


@pytest.mark.parametrize("kind", ["unchanged", "half_batch_in_loss",
                                  "half_batch_fed"])
@pytest.mark.parametrize("name", TINY_CELLS)
def test_a_broken_timed_path_comes_out_not_correct(tiny_cell, monkeypatch,
                                                   name, kind):
    """The step the window drives is broken underneath the harness: it
    returns its state unchanged, or leaves half of the batch out (of the
    loss's mean alone, its metrics whole; or of the whole step) and takes the
    mean over the rest."""
    unplant = _break_timed_path(monkeypatch, kind)
    try:
        result = _run(tiny_cell(name))
    finally:
        unplant()
    assert result["correct"] is False
    failed = {k for k, r in result["checks"].items() if r["value"] > r["limit"]}
    if kind == "unchanged":
        assert {"grad_gap", "grad_diff", "row_diff", "delta_gap"} <= failed
    else:
        assert {"grad_diff", "row_diff"} <= failed


@pytest.mark.parametrize("name", TINY_CELLS)
def test_the_control_one_precision_down_is_not_correct(tiny_cell, name):
    """The reference in the program's place, float32 → bfloat16 and the
    bfloat16 tower/CIN → fp8, and the fp8 half alone, against the reference
    itself; and the reference with half the batch left out of its loss."""
    from perf import check
    from perf.reference import _common as c

    cell = tiny_cell(name)
    model = cell.config["overrides"]["model"]["model_name"]
    ref_mod = cell.module("reference", model)
    gen = cell.module("generators", cell.traffic["generator"])
    limits = json.loads((cell.perf_dir / "limits" / f"{name}.json").read_text())
    for seed in (1, 2, 3):
        pool = gen.make_pool(cell.traffic["params"], rows=5000, fields=39,
                             seed=seed)[:3]
        ref = ref_mod.follow(cell.config, seed, pool)
        for policy in (c.Policy(main="bfloat16", mlp_fp8=True),
                       c.Policy(mlp_fp8=True), c.Policy(half_batch=True)):
            low = ref_mod.follow(cell.config, seed, pool, policy)
            ok, rows = check.verdict(check.compare(low, ref), limits)
            assert not ok, (policy, rows)
            assert rows["grad_diff"]["value"] > rows["grad_diff"]["limit"]
        same, _ = check.verdict(check.compare(ref, ref), limits)
        assert same


def test_check_reads_a_missing_or_nan_number_as_a_failure():
    from perf import check

    import numpy as np

    ref = {"loss": [0.7, 0.69, 0.68],
           "grad_norm": {"a": 1.0, "b": 2.0, "c": 3.0},
           "grad": {"a": np.array([0.6, 0.8]), "b": np.array([2.0, 0.0])},
           "grad_rows": {"c": np.array([[3.0, 0.0], [1e-3, 0.0], [0.0, 1e-3]])},
           "delta_norm": {"a": 0.1, "b": 0.2, "c": 0.3}}
    limits = {"loss_gap": 1e-3, "grad_gap": 1e-2, "grad_diff": 1e-2,
              "row_diff": 1e-2, "delta_gap": 1e-2}
    assert check.verdict(check.compare(ref, ref), limits)[0]
    nan = dict(ref, loss=[0.7, float("nan"), 0.68])
    assert not check.verdict(check.compare(nan, ref), limits)[0]
    lost = dict(ref, grad_norm={"a": 1.0, "b": 2.0})
    assert not check.verdict(check.compare(lost, ref), limits)[0]
    # a gradient that keeps its length and turns: only grad_diff sees it
    turned = dict(ref, grad=dict(ref["grad"], a=np.array([0.8, 0.6])))
    ok, rows = check.verdict(check.compare(turned, ref), limits)
    assert not ok
    assert [k for k, r in rows.items() if r["value"] > r["limit"]] == [
        "grad_diff"]
    # two cold rows lost beside a hot one that carries the table's norm
    lost_rows = dict(ref, grad_rows={"c": np.array([[3.0, 0.0], [0.0, 0.0],
                                                    [0.0, 0.0]])})
    ok, rows = check.verdict(check.compare(lost_rows, ref), limits)
    assert not ok and rows["row_diff"]["value"] > 0.5
    for bad in (dict(ref, grad_rows={}),
                dict(ref, grad={"a": ref["grad"]["a"]}),
                dict(ref, grad=dict(ref["grad"], b=np.zeros(3)))):
        assert not check.verdict(check.compare(bad, ref), limits)[0]
    # a leaf whose reference gradient is nought moves by round-off alone
    still = {"loss": ref["loss"], "grad": ref["grad"],
             "grad_rows": ref["grad_rows"],
             "grad_norm": dict(ref["grad_norm"], z=1e-9),
             "delta_norm": dict(ref["delta_norm"], z=0.05)}
    prog = dict(still, delta_norm=dict(still["delta_norm"], z=0.0))
    assert check.verdict(check.compare(prog, still), limits)[0]


def test_a_later_pr_adds_a_config_a_traffic_mix_and_a_metric_as_files(
        tmp_path, fixture_manifest):
    """In a temporary copy of the benchmark: three new files and three new
    manifest entries, no edit to a file that was there, and the new cell runs
    and reports the new metric."""
    perf = tmp_path / "perf"
    shutil.copytree(ROOT / "perf", perf,
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    before = {p: p.read_bytes() for p in perf.rglob("*") if p.is_file()}

    conf = json.loads((perf / "configs" / "tiny-deepfm.json").read_text())
    conf["name"] = "added-deepfm"
    conf["overrides"]["model"]["deep_layers"] = [8, 8]
    (perf / "configs" / "added-deepfm.json").write_text(json.dumps(conf))
    traffic = json.loads((perf / "traffic" / "tiny-fields-b64.json").read_text())
    traffic["params"]["batch_size"] = 32
    (perf / "traffic" / "added-zipf-b32.json").write_text(json.dumps(traffic))
    (perf / "metrics" / "added_steps.py").write_text(
        "def read(run):\n    return float(run['spans']['steps'])\n")
    shutil.copy(perf / "limits" / "tiny-deepfm-train.json",
                perf / "limits" / "added-cell.json")

    man = json.loads(json.dumps(fixture_manifest))
    man["configs"].append({"name": "added-deepfm", "source": "test",
                           "file": "perf/configs/added-deepfm.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "added-cell", "config": "added-deepfm",
                             "traffic": "added-zipf-b32", "chips": 1,
                             "why": "test"})
    man["per_layer"].append({"name": "added_steps", "unit": "steps",
                             "better": "higher", "source": "program_counter",
                             "layer": "whole step",
                             "moves": "train_examples_per_s",
                             "workloads": ["added-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))

    code = (
        "import json, sys, time\n"
        f"sys.path[:0] = [{str(tmp_path)!r}, {str(ROOT)!r}]\n"
        "from perf import manifest\n"
        f"assert str(manifest.ROOT) == {str(tmp_path)!r}\n"
        "man = manifest.load()\n"
        "assert manifest.lint(man) == [], manifest.lint(man)\n"
        "cell = manifest.Cell(man, 'added-cell', manifest.PERF_DIR)\n"
        "entry = cell.module('entries', cell.traffic['entry'])\n"
        "r = entry.run(cell, seed=5, seconds=0.2, trace=True,\n"
        "              t0=time.perf_counter(), require_chip=False)\n"
        "print(json.dumps(r))\n")
    env = {"JAX_PLATFORMS": "cpu", "JAX_ENABLE_COMPILATION_CACHE": "false",
           "PATH": "/usr/bin:/bin", "HOME": str(tmp_path)}
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    assert result["metrics"]["added_steps"]["value"] == result["attempted"]
    assert "added_steps" not in {m["name"] for m in fixture_manifest["per_layer"]}
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_no_chip_means_no_result():
    """The command itself, on this machine without a TPU: non-zero exit in
    seconds and nothing on standard output."""
    t = time.perf_counter()
    out = subprocess.run(
        [sys.executable, str(ROOT / "perf" / "run.py"), "--workload",
         "deepfm-criteo1tb-train-b8192", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "JAX_ENABLE_COMPILATION_CACHE": "false"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr
    assert time.perf_counter() - t < 60
