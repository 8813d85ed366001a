"""Device-mesh construction — the topology layer.

Replaces the reference's two cluster-wiring mechanisms — TF_CONFIG parameter-
server topology (ps:461-481, set_dist_env ps:341-386) and MPI/Horovod rank
plumbing (hvd:333-350) — with a named ``jax.sharding.Mesh``:

* ``data`` axis — batch (data-parallel) dimension; gradient reduction rides
  this axis as XLA ``psum`` (the Horovod-allreduce capability, hvd:296).
* ``model`` axis — embedding-table row sharding (the parameter-server
  capability: tables living off-worker, README.md:15,63).

Multi-host: ``jax.distributed.initialize`` + the same mesh over all
processes' devices; collectives ride ICI within a slice and DCN across
slices with no user-level transport code (SURVEY §5 comm backend).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

from ..core.config import DATA_AXIS, MODEL_AXIS, MeshConfig  # noqa: F401
from ..obs.trace import get_span_recorder


def initialize_distributed(cfg: MeshConfig) -> None:
    """Multi-host bootstrap (the mpirun/TF_CONFIG analog).  No-op for
    single-process runs."""
    if cfg.coordinator_address and cfg.num_processes > 1:
        with get_span_recorder().span("setup.distributed"):
            jax.distributed.initialize(
                coordinator_address=cfg.coordinator_address,
                num_processes=cfg.num_processes,
                process_id=cfg.process_id,
            )


def build_mesh(cfg: MeshConfig, devices=None) -> Mesh:
    """Lay out devices as [data, model].

    ``data_parallel == -1`` takes every device not claimed by the model axis.
    The model (row-shard) axis is placed innermost so table shards of one
    data replica sit on ICI-adjacent chips — embedding all-to-all/psum
    traffic stays on the fastest links, gradient psum spans the outer axis.
    """
    # with no devices handed in, this is where the backend opens
    with get_span_recorder().span("setup.mesh"):
        devices = jax.devices() if devices is None else devices
    n = len(devices)
    mp = max(1, cfg.model_parallel)
    if n % mp != 0:
        raise ValueError(f"model_parallel={mp} does not divide device count {n}")
    dp = cfg.data_parallel if cfg.data_parallel > 0 else n // mp
    if dp * mp != n:
        raise ValueError(
            f"data_parallel({dp}) × model_parallel({mp}) != device count {n}"
        )
    arr = np.asarray(devices).reshape(dp, mp)
    return Mesh(arr, (DATA_AXIS, MODEL_AXIS))


def mesh_shape(mesh: Mesh) -> tuple[int, int]:
    return mesh.shape[DATA_AXIS], mesh.shape[MODEL_AXIS]
