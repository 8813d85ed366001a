"""BENCHMARK.json and the data files its entries name.

Everything that belongs to one configuration, one traffic mix, one metric or
one entry kind is a file of its own, found here by the name the manifest gives:
``configs/<config>.json``, ``traffic/<traffic>.json``, ``metrics/<metric>.py``,
``work/<model_name>.py``, ``reference/<model_name>.py``, ``entries/<kind>.py``,
``generators/<generator>.py``.  Adding one never edits a file that is there.
"""

from __future__ import annotations

import importlib
import json
import re
from pathlib import Path

PERF_DIR = Path(__file__).resolve().parent
ROOT = PERF_DIR.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class Cell:
    """One ``workloads`` entry with its configuration and traffic loaded."""

    def __init__(self, manifest: dict, name: str, perf_dir: Path):
        cells = {w["name"]: w for w in manifest["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
        self.perf_dir = perf_dir
        self.workload = cells[name]
        self.name = name
        self.chips = int(self.workload["chips"])
        configs = {c["name"]: c for c in manifest["configs"]}
        self.config_entry = configs[self.workload["config"]]
        self.config = json.loads(
            (perf_dir.parent / self.config_entry["file"]).read_text())
        self.traffic = json.loads(
            (perf_dir / "traffic" / f"{self.workload['traffic']}.json")
            .read_text())
        self.end_to_end = [m for m in manifest["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in manifest["per_layer"]
                          if name in m.get("workloads", [name])]

    def module(self, kind: str, name: str):
        """Import ``perf/<kind>/<name>.py``, the file a cell's data names."""
        path = self.perf_dir / kind / f"{name}.py"
        if not path.is_file():
            raise SystemExit(f"{path} is missing")
        return importlib.import_module(f"perf.{kind}.{name}")


def load(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def lint(manifest: dict, root: Path = ROOT) -> list:
    """Faults of the manifest a run would trip over: bad names and units,
    files that are missing, metrics that no cell reports."""
    bad = []
    perf_dir = root / manifest["paths"][0]

    def name_ok(n, what):
        if not NAME_RE.match(str(n)):
            bad.append(f"{what} {n!r} is not a name")

    names = set()
    for c in manifest["configs"]:
        name_ok(c["name"], "config")
        for k in c["reduced"]:
            name_ok(k, "reduced key")
        if not (root / c["file"]).is_file():
            bad.append(f"config file {c['file']} is missing")
    for w in manifest["workloads"]:
        name_ok(w["name"], "workload")
        name_ok(w["traffic"], "traffic")
        names.add(w["name"])
        if w["config"] not in {c["name"] for c in manifest["configs"]}:
            bad.append(f"workload {w['name']} names no configuration")
        if not (perf_dir / "traffic" / f"{w['traffic']}.json").is_file():
            bad.append(f"traffic file of {w['name']} is missing")
        if len(w["why"]) > 200 or "\n" in w["why"]:
            bad.append(f"why of {w['name']} is too long")
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        name_ok(m["name"], "metric")
        if not UNIT_RE.match(m["unit"]):
            bad.append(f"unit {m['unit']!r} of {m['name']}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"better of {m['name']}")
        for w in m.get("workloads", []):
            if w not in names:
                bad.append(f"{m['name']} lists unknown workload {w}")
    for m in manifest["per_layer"]:
        if m["moves"] not in e2e:
            bad.append(f"{m['name']} moves unknown {m['moves']}")
        if not (perf_dir / "metrics" / f"{m['name']}.py").is_file():
            bad.append(f"reader of {m['name']} is missing")
    if "setup_s" not in e2e:
        bad.append("no setup_s")
    return bad
