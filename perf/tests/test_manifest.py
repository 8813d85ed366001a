"""Lint of BENCHMARK.json (and of the tests' own manifest): names, units,
files, and the contract's key sets."""

import json

from perf_test_util import ROOT

from perf import check, manifest

TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}


def test_benchmark_json_lints_clean():
    man = manifest.load(ROOT)
    assert set(man) == TOP
    assert manifest.lint(man, ROOT) == []


def test_fixture_manifest_lints_clean(fixture_manifest):
    assert manifest.lint(fixture_manifest, ROOT) == []


def test_entries_have_only_the_contracts_keys():
    man = manifest.load(ROOT)
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in man["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                         "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in man["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                         "layer", "moves"}
    assert len(json.dumps(man)) < 64 * 1024


def test_every_cell_has_its_files_and_limits():
    man = manifest.load(ROOT)
    for w in man["workloads"]:
        cell = manifest.Cell(man, w["name"], manifest.PERF_DIR)
        model = cell.config["overrides"]["model"]["model_name"]
        for kind, name in (("entries", cell.traffic["entry"]),
                           ("generators", cell.traffic["generator"]),
                           ("work", model), ("reference", model)):
            assert (manifest.PERF_DIR / kind / f"{name}.py").is_file()
        limits = json.loads((manifest.PERF_DIR / "limits" /
                             f"{w['name']}.json").read_text())
        assert set(limits) == set(check.NUMBERS)
        # reduced names no width, and the file states what was cut
        assert cell.config_entry["reduced"] == cell.config["reduced"]
        assert cell.config_entry["reduced"] == ["feature_size"]


def test_lint_catches_a_bad_name_and_a_missing_reader():
    man = manifest.load(ROOT)
    man["per_layer"].append(dict(man["per_layer"][0], name="no such reader"))
    bad = manifest.lint(man, ROOT)
    assert any("not a name" in b for b in bad)
    assert any("reader" in b for b in bad)
