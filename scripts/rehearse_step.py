"""Compile a benchmark cell's train step at its real size for a described TPU
topology, without a chip, from the family's DECLARED batch:

    JAX_PLATFORMS=cpu python scripts/rehearse_step.py --workload <cell> [--hlo FILE]

What ``perf/rehearse_compile.py`` does (mesh from the described devices →
``make_context`` → ``abstract_spmd_state`` → ``make_spmd_train_step(...)
.lower().compile()`` → ``memory_analysis()``), but the batch's shapes come from
``ModelDef.batch(cfg)`` and ``ctx.batch_specs``, so any family's cell can be
rehearsed; that file builds the click-through batch by hand and only a
``benchmark`` PR may edit it (PERF.md §7).  Prints the compiler's bytes with
and without the benchmark's ``p0`` copy of the parameters (4 B a parameter,
``perf/entries/train.py first_steps``), and what the outermost
``jax.checkpoint``s (the token family's blocks) run again in their backward:
the instructions under their recomputation by the last part of their
``op_name``, and those of them that are a product, a sort, a top-k, a gather
or a kernel (``ops/kept.py``'s rule: none where everything fits).  Nothing runs: no time,
no result.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--hlo", default="", help="write the compiled HLO here")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding

    from deepfm_tpu.models.base import get_model
    from deepfm_tpu.obs.trace import NOT_ELEMENT_WISE, recomputed_part
    from deepfm_tpu.parallel import spmd
    from deepfm_tpu.parallel.mesh import build_mesh
    from perf import manifest
    from perf.entries import train

    jax.config.update("jax_enable_compilation_cache", False)
    cell = manifest.Cell(manifest.load(), args.workload, manifest.PERF_DIR)
    cfg = train.build_config(cell, seed=0)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    mesh = build_mesh(cfg.mesh, devices=topo.devices[:cell.chips])
    ctx = spmd.make_context(cfg, mesh)
    abstract = spmd.abstract_spmd_state(ctx)
    state = jax.tree_util.tree_map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        abstract, ctx.state_shardings)
    # the placer narrows a declared int64 id field to int32 on the host
    fields = get_model(ctx.cfg.model).batch(ctx.cfg.model)
    batch = {k: jax.ShapeDtypeStruct(
        (cfg.data.batch_size, *f.shape),
        np.dtype("int32" if f.table else f.dtype),
        sharding=NamedSharding(mesh, ctx.batch_specs[k]))
        for k, f in fields.items()}
    t = time.perf_counter()
    compiled = spmd.make_spmd_train_step(ctx).lower(state, batch).compile()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    if args.hlo:
        Path(args.hlo).write_text(hlo)
    # one entry an instruction: a fusion's name is its root's
    again = collections.Counter(
        part.rsplit("/", 1)[-1]
        for part in map(recomputed_part,
                        re.findall(r'op_name="([^"]*)"', hlo)) if part)
    p0 = sum(4 * int(np.prod(x.shape))
             for x in jax.tree_util.tree_leaves(abstract.params))
    step = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(json.dumps({
        "workload": cell.name, "topology": args.topology, "chips": cell.chips,
        "compile_s": time.perf_counter() - t,
        "argument_bytes": mem.argument_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "argument_plus_temp_bytes": step,
        "p0_bytes": p0,
        "with_p0_bytes": step + p0,
        "share_of_16GB": step / 16e9,
        "share_of_16GB_with_p0": (step + p0) / 16e9,
        "blocks_recompute_instructions": sum(again.values()),
        "blocks_recompute_not_element_wise": {
            k: n for k, n in again.items() if k in NOT_ELEMENT_WISE},
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
