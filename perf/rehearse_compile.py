"""Compile a cell's train step at its real size for a described TPU topology,
without a chip, and print what the compiler says it needs:

    JAX_PLATFORMS=cpu python perf/rehearse_compile.py --workload <name>

``build_mesh(cfg.mesh, devices=topo.devices[:chips])`` → ``make_context`` →
``abstract_spmd_state`` → ``make_spmd_train_step(...).lower().compile()``, then
``memory_analysis()``: so a later cell's bytes are reckoned before chip time is
spent on it.  Nothing runs, so this gives no time and no result; a compile
that passes is not a chip run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--topology", default="v5e:2x2")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding

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
    state = jax.tree_util.tree_map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        spmd.abstract_spmd_state(ctx), ctx.state_shardings)
    b, f = cfg.data.batch_size, cfg.model.field_size
    shapes = {"feat_ids": ((b, f), jnp.int32), "feat_vals": ((b, f), jnp.float32),
              "label": ((b,), jnp.float32)}
    batch = {k: jax.ShapeDtypeStruct(s, d, sharding=NamedSharding(
        mesh, ctx.batch_specs[k])) for k, (s, d) in shapes.items()}
    t = time.perf_counter()
    compiled = spmd.make_spmd_train_step(ctx).lower(state, batch).compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    args_b, temp_b = mem.argument_size_in_bytes, mem.temp_size_in_bytes
    print(json.dumps({
        "workload": cell.name, "topology": args.topology, "chips": cell.chips,
        "compile_s": time.perf_counter() - t,
        "argument_bytes": args_b, "temp_bytes": temp_b,
        "output_bytes": mem.output_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "argument_plus_temp_bytes": args_b + temp_b,
        "share_of_16GB": (args_b + temp_b) / 16e9,
        "xla_flops": cost.get("flops"),
        "xla_bytes_accessed": cost.get("bytes accessed"),
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
