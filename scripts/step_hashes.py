"""sha256 of each benchmark cell's lowered train step, on the CPU, from
abstract state at the cell's own size:

    JAX_PLATFORMS=cpu python scripts/step_hashes.py [--workload <cell> ...]

``make_context`` → ``abstract_spmd_state`` → ``make_spmd_train_step(...)
.lower(state, batch).as_text()``, the batch from the family's declared batch
(what ``tests/test_perf_seam.py`` lowers).  The text carries no source
locations, so a PR that moves code and changes no arithmetic prints the
parent's hashes: run it in a copy of the parent (``git archive`` into a
git-ignored directory, this file copied beside its ``scripts/``) and here,
and compare.  Nothing compiles and nothing runs.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", action="append", default=[])
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))

    import jax
    import numpy as np
    from jax.sharding import NamedSharding

    from deepfm_tpu.models.base import get_model
    from deepfm_tpu.parallel import spmd
    from deepfm_tpu.parallel.mesh import build_mesh
    from perf import manifest
    from perf.entries import train

    bench = manifest.load(ROOT)
    out = {}
    for name in args.workload or [w["name"] for w in bench["workloads"]]:
        cell = manifest.Cell(bench, name, ROOT / "perf")
        cfg = train.build_config(cell, seed=0)
        mesh = build_mesh(cfg.mesh, devices=jax.devices()[:cell.chips])
        ctx = spmd.make_context(cfg, mesh)
        state = jax.tree_util.tree_map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            spmd.abstract_spmd_state(ctx), ctx.state_shardings)
        fields = get_model(ctx.cfg.model).batch(ctx.cfg.model)
        batch = {k: jax.ShapeDtypeStruct(
            (cfg.data.batch_size, *f.shape),
            np.dtype("int32" if f.table else f.dtype),
            sharding=NamedSharding(mesh, ctx.batch_specs[k]))
            for k, f in fields.items()}
        text = spmd.make_spmd_train_step(ctx).lower(state, batch).as_text()
        out[name] = hashlib.sha256(text.encode()).hexdigest()
        print(json.dumps({name: out[name]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
