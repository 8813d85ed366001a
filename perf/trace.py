"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

Read with ``jax.profiler.ProfileData`` and nothing else.  A TPU plane
(``/device:TPU:<n>``) carries a line ``XLA Ops`` with one event per executed
HLO op and a line ``XLA Modules`` with one event per executed program.  Busy
time is the union of the op intervals; the traced window runs from the first
op's start to the last op's end, which over the seconds traced differs from
the host's window by one dispatch latency.  The benchmark's own host spans
(``perf.*`` TraceAnnotations) lie on the host plane on the same clock and give
each idle gap its label.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "perf."


def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def union_seconds(intervals: list) -> float:
    """Length of the union of [start, end) intervals (any unit in, same out)."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(intervals: list) -> list:
    out, end = [], None
    for s, e in sorted(intervals):
        if end is not None and s > end:
            out.append((end, s))
        end = e if end is None else max(end, e)
    return out


def _label(gap, spans) -> str:
    """Name of the host span that covers most of the gap ('' if none)."""
    best, best_cov = "untracked", 0.0
    for name, s, e in spans:
        cov = min(e, gap[1]) - max(s, gap[0])
        if cov > best_cov:
            best, best_cov = name, cov
    return best


_CONTAINER = re.compile(r" = .*? (?:while|conditional|call)\(")


def leaf_ops(ops: list) -> list:
    """The ``(start, end, name)`` op events without a ``while`` (or
    ``conditional`` or ``call``) that has other ops' events inside its
    interval: those are its body's, its time is theirs, and listing both
    gives one time twice.  One whose body has no event of its own stays."""
    ordered = sorted(ops, key=lambda o: (o[0], -o[1]))
    out = []
    for i, op in enumerate(ordered):
        holds_another = i + 1 < len(ordered) and ordered[i + 1][1] <= op[1]
        if not (holds_another and _CONTAINER.search(op[2])):
            out.append(op)
    return out


_KIND = re.compile(r"kind=(\w+)")
_OPCODE = re.compile(r"\b([a-z][a-z\-]*)\(")


def short_op(text: str) -> str:
    """An XLA Ops event carries the whole HLO instruction as its name:
    ``%fusion.2 = f32[12500000,32]{0,1:T(8,128)} fusion(...), kind=kCustom``
    -> ``fusion.2 fusion:kCustom f32[12500000,32]``."""
    if " = " not in text:
        return text[:120]
    op, rest = text.split(" = ", 1)
    if rest.startswith("("):  # tuple-shaped output
        shape, tail = "tuple", rest[rest.find(")") + 1:]
    else:
        shape, tail = rest.split("{", 1)[0].split(" ", 1)[0], rest
    m = _OPCODE.search(tail)
    kind = _KIND.search(text)
    opcode = (m.group(1) if m else "op") + (":" + kind.group(1) if kind else "")
    return f"{op.lstrip('%')} {opcode} {shape}"[:120]


def reduce_xplane(path: str, step_module: str = "") -> dict:
    """-> {devices, busy_s, window_s, steps, step_device_s, device_ops,
    idle_gaps}, averaged over the device planes.  ``device_ops`` lists a
    loop's body ops and not the loop (``leaf_ops``); everything else is a
    union of intervals and counts each moment once.  ``step_module`` (a
    substring of the train step's module name) picks which module events are
    counted as steps; empty counts the most frequent module."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans, devices = [], []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
        if not plane.name.startswith("/device:TPU:"):
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                       for ev in line.events]
            elif line.name == MODULES_LINE:
                modules = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                            ev.name) for ev in line.events]
        if ops:
            devices.append((plane.name, ops, modules))
    if not devices:
        return {"devices": 0}

    n = len(devices)
    busy = window = steps = step_busy = 0.0
    by_op, gaps_all = {}, []
    for _, ops, modules in devices:
        iv = [(s, e) for s, e, _ in ops]
        busy += union_seconds(iv) / 1e9
        window += (max(e for _, e in iv) - min(s for s, _ in iv)) / 1e9
        counts = {}
        for _, _, name in modules:
            base = name.split("(")[0]
            counts[base] = counts.get(base, 0) + 1
        if step_module:
            picked = [b for b in counts if step_module in b]
        else:
            picked = sorted(counts, key=counts.get)[-1:]
        mods = [(s, e) for s, e, name in modules
                if name.split("(")[0] in picked]
        steps += len(mods)
        # device time of the steps: op intervals inside step modules
        if mods:
            lo, hi = min(s for s, _ in mods), max(e for _, e in mods)
            step_busy += union_seconds(
                [(s, e) for s, e in iv if s >= lo and e <= hi]) / 1e9
        for s, e, name in leaf_ops(ops):
            by_op[name] = by_op.get(name, 0.0) + (e - s) / 1e9
        gaps_all += _gaps(iv)
    by_label = {}
    for g in gaps_all:
        lab = _label(g, spans)
        by_label[lab] = by_label.get(lab, 0.0) + (g[1] - g[0]) / 1e9
    top = lambda d: [[short_op(k), v / n] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "devices": n,
        "busy_s": busy / n,
        "window_s": window / n,
        "steps": steps / n,
        "step_device_s": step_busy / n,
        "device_ops": top(by_op),
        "idle_gaps": top(by_label),
    }
