"""Device time of a benchmark cell's train step by named scope (on the chip).

    chiprun -- python scripts/step_scopes.py --workload <cell> --seed <n>

Builds the cell as ``perf/entries/train.py`` does, takes the compiled step's
HLO text (``env.step.lower(state, batch).compile().as_text()``), traces a few
seconds of steps, and joins each ``XLA Ops`` event of the TPU plane — the bare
HLO instruction, which carries no ``op_name`` — to its ``op_name`` by
instruction name, and that to the step's named scope
(``deepfm_tpu/obs/trace.scope_of``: of a scope inside a scope, the byte
family's ``attention/eva_pool``, the inner one).  A fusion spans scopes; the name XLA
keeps on it is its root's; a ``while`` is left out of the sums, since the ops of
its body have events of their own.  Prints the 40 longest ops with their scope, the
time per scope and per Pallas kernel (a custom call, by its name), and the
share of the step's device time under no scope; with ``--scope NAME`` (as
often as wanted) also EVERY op of that scope, whatever the transforms around
it, with its time (``ops_by_scope``: what a cut inside one scope is planned
and checked on); the
same goes to ``chiprun_out/step_scopes/<cell>.json``.  What ``PERF.md`` §5's
scope column is made with, until a reader under ``perf/`` can do it (§7).
"""

from __future__ import annotations

import argparse
import json
import re
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CONTAINER = re.compile(r" = .*? (?:while|conditional|call)\(")
# a Pallas call's instruction carries the kernel's name and no ``op_name``
KERNEL_SCOPES = {"splash_mha_fwd_residuals": "attention kernel",
                 "splash_mha_fwd_no_residuals": "attention kernel",
                 "splash_mha_dkv_no_residuals": "attention kernel (backward)",
                 "ssd_scan_fwd": "ssd scan kernel",
                 "ssd_scan_bwd": "ssd scan kernel (backward)"}


def op_names(hlo_text: str) -> dict:
    """{instruction name: op_name} over every computation of the module."""
    out = {}
    for line in hlo_text.splitlines():
        m, name = _INSTR.match(line), _OP_NAME.search(line)
        if m and name:
            out[m.group(1)] = name.group(1)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--scope", action="append", default=[],
                    help="print every op of this scope (repeatable)")
    args = ap.parse_args()

    from perf import manifest
    from perf import trace as perf_trace
    from perf.entries import train

    cell = manifest.Cell(manifest.load(), args.workload, manifest.PERF_DIR)
    env = train.build(cell, args.seed)
    import jax
    from jax.profiler import ProfileData

    from deepfm_tpu.obs.trace import scope_of

    batch = next(env.feed)
    state = env.state
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        (state, batch))
    for _ in range(train.WARM_STEPS):
        state, _ = env.step(state, next(env.feed))
    jax.block_until_ready(state)
    trace_dir = ROOT / ".perf_trace" / f"scopes-{cell.name}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < args.seconds:
        state, _ = env.step(state, next(env.feed))
    jax.block_until_ready(state)
    jax.profiler.stop_trace()
    env.close()
    # the names come from a compile of this process's own: the compile cache
    # keys on the module without its metadata, so a cache written before a
    # scope existed hands back an executable that does not name it (same
    # instructions, older op_names)
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()      # ... and this process's own copy of it
    names = op_names(env.step.lower(*abstract).compile().as_text())

    by_op, steps, host_lines = {}, 0, {}
    for plane in ProfileData.from_file(
            perf_trace.newest_xplane(str(trace_dir))).planes:
        for i, line in enumerate(plane.lines):
            if plane.name.startswith("/host:"):
                for ev in line.events:
                    if ev.name.startswith(("feed.", "train.")):
                        host_lines.setdefault(   # one line per thread
                            f"{plane.name} line {i} {line.name!r}",
                            set()).add(ev.name)
            elif plane.name.startswith("/device:TPU:"):
                if line.name == perf_trace.MODULES_LINE:
                    steps += sum("local_step" in ev.name for ev in line.events)
                elif line.name == perf_trace.OPS_LINE:
                    for ev in line.events:
                        # a loop's event spans its body's ops, which have
                        # events of their own: count the ops, not the span
                        if _CONTAINER.search(ev.name):
                            continue
                        by_op[ev.name] = by_op.get(ev.name, 0) + ev.duration_ns
    shutil.rmtree(trace_dir, ignore_errors=True)

    if not steps:
        raise SystemExit("no step of a TPU plane in the trace")
    total = sum(by_op.values())
    by_scope, by_kernel, rows = {}, {}, []
    ops_by_scope = {name: [] for name in args.scope}
    for text, ns in sorted(by_op.items(), key=lambda kv: -kv[1]):
        instr = text.split(" = ", 1)[0].strip().lstrip("%")
        if " custom-call(" in text:     # a Pallas call: the kernel's name
            kernel = instr.split(".")[0]
            by_kernel[kernel] = by_kernel.get(kernel, 0) + ns
        op_name = names.get(instr, "")
        bare, scope = scope_of(op_name)
        written = scope or KERNEL_SCOPES.get(instr.split(".")[0], "(none)")
        # inside a block's jax.checkpoint the transforms wrap the checkpoint,
        # not the scope: its recomputation and its backward read the bare
        # scope.  A checkpoint UNDER the scope (the expert layer's branches)
        # is the scope's own: what it recomputes is part of its backward
        around = op_name[:op_name.index(scope)] if scope else op_name
        if "/rematted_computation/" in around:
            written += " (recomputed)"
        elif "/checkpoint/" in around:
            written += " (backward)"
        by_scope[written] = by_scope.get(written, 0) + ns
        rows.append({"op": perf_trace.short_op(text), "scope": written,
                     "op_name": op_name, "ms_per_step": ns / 1e6 / steps})
        if bare in ops_by_scope:
            ops_by_scope[bare].append(rows[-1])
    out = {
        "workload": cell.name, "steps": steps,
        "step_device_ms_sum_of_ops": total / 1e6 / steps,
        "top_ops": rows[:40],
        "ms_per_step_by_scope": {k: v / 1e6 / steps for k, v in sorted(
            by_scope.items(), key=lambda kv: -kv[1])},
        "ms_per_step_by_kernel": {k: v / 1e6 / steps
                                  for k, v in by_kernel.items()},
        "ops_by_scope": ops_by_scope,
        "unscoped_share_pct": 100.0 * by_scope.get("(none)", 0) / total,
        "unscoped_ops": [r for r in rows if r["scope"] == "(none)"][:10],
        "ops_with_no_op_name": sum(not r["op_name"] for r in rows),
        "host_lines": {k: sorted(v) for k, v in host_lines.items()},
    }
    dest = ROOT / "chiprun_out" / "step_scopes"
    dest.mkdir(parents=True, exist_ok=True)
    (dest / f"{cell.name}.json").write_text(json.dumps(out, indent=1))
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
