"""The comparison that decides ``correct`` for a training cell.

The program's first three steps (taken in set-up through the window's own
call and feed) against the plain reference's, by the numbers below; each has
a limit of its own in ``perf/limits/<workload>.json``, set from chip readings
as PERF.md records.

* ``loss_gap``: widest relative gap of a step's loss over the three steps.
* ``grad_gap``: worst leaf's gap between the program's and the reference's
  norm of the first gradient (the program's worked out from Adam's first
  moment after one step), against the reference's norm of that leaf or of the
  median leaf, whichever is larger.
* ``grad_diff``: over the leaves small enough to read back whole (fewer than
  ``WHOLE_LEAF_MAX`` elements: everything but the embedding table), the worst
  leaf's norm of the *difference* of the two first gradients, against the
  reference's norm of that leaf or of the median of those leaves.  A gap of
  norms cannot see a gradient that keeps its length and turns (half of the
  batch left out; a tower computed in fp8): this one does.
* ``row_diff``: over the distinct rows that the first batch touches, each
  table's first-gradient rows read back whole: the root mean square, over
  those rows, of ‖row_prog − row_ref‖ against the reference's norm of that
  row or of the median row; the worst table.  Every row weighs the same, so
  the few hot rows that carry a table's norm cannot hide the many cold ones:
  a row whose examples were left out, counted twice or lost in a scatter
  reads 1.
* ``delta_gap``: as ``grad_gap`` for the norm of the parameters' change after
  the three steps, over the leaves whose reference gradient is at least a
  thousandth of the median leaf's (the others move by round-off alone).
"""

from __future__ import annotations

import math
import statistics

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "grad_diff", "row_diff", "delta_gap")
WHOLE_LEAF_MAX = 1 << 24


def _worst_leaf(gaps: dict, ref: dict) -> tuple:
    """Worst of ``gaps[k] / max(ref[k], median of ref over the same leaves)``."""
    med = statistics.median(ref[k] for k in gaps)
    worst, name = 0.0, ""
    for k in sorted(gaps):
        gap = gaps[k] / max(ref[k], med, 1e-30)
        if not math.isfinite(gap):
            gap = math.inf
        if gap >= worst:
            worst, name = gap, k
    return worst, name


def _norm_gaps(prog: dict, ref: dict, leaves: list) -> dict:
    return {k: abs(prog.get(k, math.nan) - ref[k]) for k in leaves}


def diff_norms(prog: dict, ref: dict) -> dict:
    """‖g_prog − g_ref‖ of each whole leaf the reference read back; a leaf
    the program lacks, or of another shape, reads as infinite."""
    out = {}
    for k, r in ref.items():
        p = prog.get(k)
        if p is None or np.shape(p) != np.shape(r):
            out[k] = math.inf
        else:
            out[k] = float(np.linalg.norm(
                np.asarray(p, np.float64).ravel()
                - np.asarray(r, np.float64).ravel()))
    return out


def row_diffs(prog: dict, ref: dict) -> dict:
    """Per table: rms over its touched rows of ‖p_row − r_row‖ / max(‖r_row‖,
    median ‖r_row‖); a table the program lacks reads as infinite."""
    out = {}
    for k, r in ref.items():
        r = np.asarray(r, np.float64).reshape(len(r), -1)
        p = prog.get(k)
        if p is None or np.size(p) != r.size:
            out[k] = math.inf
            continue
        p = np.asarray(p, np.float64).reshape(r.shape)
        norm = np.linalg.norm(r, axis=1)
        rel = np.linalg.norm(p - r, axis=1) / np.maximum(
            np.maximum(norm, np.median(norm)), 1e-30)
        out[k] = float(np.sqrt(np.mean(np.square(rel))))
    return out


def _step_gaps(prog: list, ref: list) -> list:
    gaps = [(abs(p - r) / abs(r) if math.isfinite(p) else math.inf, i + 1)
            for i, (p, r) in enumerate(zip(prog, ref))]
    if len(prog) != len(ref):
        gaps.append((math.inf, 0))
    return gaps


def compare(prog: dict, ref: dict) -> dict:
    """-> {number: (value, worst leaf or step)}; nan or a missing leaf on the
    program's side reads as an infinite gap."""
    out = {"loss_gap": max(_step_gaps(prog["loss"], ref["loss"]))}
    norms = ref["grad_norm"]
    leaves = sorted(norms)
    out["grad_gap"] = _worst_leaf(
        _norm_gaps(prog["grad_norm"], norms, leaves), norms)
    out["grad_diff"] = _worst_leaf(
        diff_norms(prog.get("grad", {}), ref["grad"]), norms)
    rows = row_diffs(prog.get("grad_rows", {}), ref["grad_rows"])
    out["row_diff"] = max((v if math.isfinite(v) else math.inf, k)
                          for k, v in rows.items())
    med = statistics.median(norms.values())
    moved = [k for k in leaves if norms[k] >= 1e-3 * med]
    out["delta_gap"] = _worst_leaf(
        _norm_gaps(prog["delta_norm"], ref["delta_norm"], moved),
        ref["delta_norm"])
    return out


def verdict(numbers: dict, limits: dict) -> tuple:
    """-> (correct, {name: {"value", "limit", "at"}})."""
    rows, ok = {}, True
    for name in NUMBERS:
        value, at = numbers[name]
        limit = float(limits[name])
        ok = ok and value <= limit
        rows[name] = {"value": value if math.isfinite(value) else 1e30,
                      "limit": limit, "at": str(at)}
    return ok, rows
