"""Adam's table pass over the step's distinct rows in one Pallas kernel
(``ops/adam_rows.py``), and the Pallas import that waits behind the chip's
opening (``core/platform.configure_runtime``).

The kernel, in interpret mode, against what it replaces: ``_pre_add_rows``
then the configured optax chain, at K = 32 and 10, rows on the edges of the
kernel's tiles (row 0 and row V−1 among them), rows all in one tile, only
fillers, with and without the L2 term, for one step and for three (bias
correction), on meshes [1, 1] and [1, 2].  The kernel compiled for a
described v5e at the benchmark's widths: the table and its moments enter and
leave it as bitcasts, with no copy or transpose of their size.  The import
thread: none on the CPU, one elsewhere, joined by the kernel's first trace,
its failure raised there.
"""

import builtins
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from deepfm_tpu.core import platform
from deepfm_tpu.core.config import Config
from deepfm_tpu.ops import adam_rows
from deepfm_tpu.ops.embedding import DistinctRows
from deepfm_tpu.parallel import build_mesh, spmd
from deepfm_tpu.parallel.mesh import MODEL_AXIS
from deepfm_tpu.train.optimizer import adam_step_scalars, build_optimizer

LR = 5e-4
N = 384          # the compact buffer's rows a shard: distinct rows + fillers
CHUNK = 128      # the pre-add loop's chunk in the reference


def _rows(case: str, v: int, k: int, rng) -> np.ndarray:
    """A shard's distinct rows, ascending, for a table of ``v`` rows."""
    cols = adam_rows.tile_columns(v, k)
    if case == "tile_edges":
        edges = [0, 1, cols - 1, cols, cols + 1, 2 * cols - 1, 2 * cols,
                 v - 2, v - 1]
        rest = rng.choice(v, 60, replace=False)
        return np.unique(np.concatenate([edges, rest]))
    if case == "one_tile":
        # three chunks of the compact buffer reach the tile
        return np.sort(cols + rng.choice(cols, 300, replace=False))
    if case == "only_fillers":
        return np.zeros(0, np.int64)
    raise AssertionError(case)


@pytest.mark.parametrize("k,case,l2,steps,mp", [
    (32, "tile_edges", 1e-2, 3, 1),
    (10, "tile_edges", 1e-2, 3, 2),
    (32, "tile_edges", 0.0, 1, 2),
    (10, "one_tile", 0.0, 3, 1),
    (32, "one_tile", 1e-2, 1, 1),
    (32, "only_fillers", 1e-2, 3, 2),
    (10, "only_fillers", 0.0, 1, 1),
], ids=["k32_edges_l2_3steps_1x1", "k10_edges_l2_3steps_1x2",
        "k32_edges_no_l2_1step_1x2", "k10_one_tile_no_l2_3steps_1x1",
        "k32_one_tile_l2_1step_1x1", "k32_fillers_l2_3steps_1x2",
        "k10_fillers_no_l2_1step_1x1"])
def test_kernel_is_pre_add_then_the_chain(k, case, l2, steps, mp):
    """A shard holds three tiles of columns, the last one ragged; the
    compact buffer is the shard's distinct rows then fillers past its end,
    with a fresh gradient every step.  Both of Adam's moments agree to 1e-5
    of themselves (or 2e-6 of the leaf's largest entry), the table to 1e-5
    of itself or a hundredth of ``lr``; a row's gradient lands in its
    moment and nowhere else; the kernel's sum of the squares of the table
    it read is the table's own, its ragged last tile's columns past the
    table's end left out."""
    cfg = Config().with_overrides(
        model={"l2_reg": l2},
        optimizer={"name": "Adam", "learning_rate": LR},
        mesh={"data_parallel": 1, "model_parallel": mp})
    v = 2 * adam_rows.tile_columns(1 << 30, k) + 1000    # a shard's rows
    rng = np.random.default_rng(k + mp)
    rows = [_rows(case, v, k, rng) for _ in range(mp)]
    row_of = np.concatenate([
        np.concatenate([r, v + np.arange(len(r), N)]) for r in rows
    ]).astype(np.int32)
    grads = rng.normal(0, 1e-2, (steps, mp * N, k)).astype(np.float32)
    p0 = rng.normal(0, 1e-2, (mp * v, k)).astype(np.float32)
    tx = build_optimizer(cfg.optimizer)
    rows_spec, table_spec = P(MODEL_AXIS), P(MODEL_AXIS, None)

    def run(p, row_of, grads):
        params = {"fm_v": p}
        kernel = ref = tx.init(params)
        kernel_p = p
        c = spmd._penalty_coefficient(cfg)
        distinct = jnp.sum(row_of < v, dtype=jnp.int32)
        sums = []
        for g in grads:
            # the reference: today's pre-add, then the chain
            taken = [DistinctRows(("fm_v",), ((k,),), CHUNK, row_of, distinct,
                                  params["fm_v"][jnp.clip(row_of, 0, v - 1)])]
            state, to_chain = spmd._pre_add_rows(
                cfg, ref, params, {"fm_v": c * params["fm_v"]}, taken, [g])
            updates, ref = tx.update(to_chain, state, params)
            params = optax.apply_updates(params, updates)
            # the kernel
            adam = kernel[0]
            bc1, bc2, step, mult = adam_step_scalars(
                cfg.optimizer, adam.count, "fm_v")
            read = kernel_p
            kernel_p, mu, nu, squares = adam_rows.adam_rows(
                kernel_p, adam.mu["fm_v"], adam.nu["fm_v"], row_of, g,
                jnp.stack([c, bc1, bc2, step]), b1=cfg.optimizer.adam_b1,
                b2=cfg.optimizer.adam_b2, eps=cfg.optimizer.adam_eps,
                mult=mult)
            sums.append((squares, jnp.sum(jnp.square(read))))
            kernel = (adam._replace(count=optax.safe_increment(adam.count),
                                    mu={"fm_v": mu}, nu={"fm_v": nu}),
                      *kernel[1:])
        return (kernel_p, kernel[0].mu["fm_v"], kernel[0].nu["fm_v"],
                params["fm_v"], ref[0].mu["fm_v"], ref[0].nu["fm_v"],
                jnp.stack([jnp.stack(pair) for pair in sums])[None])

    mesh = build_mesh(cfg.mesh, devices=jax.devices()[:mp])
    out = jax.jit(shard_map(
        run, mesh=mesh, in_specs=(table_spec, rows_spec, P(None, MODEL_AXIS)),
        out_specs=(table_spec,) * 6 + (P(MODEL_AXIS),),
        check_vma=False))(p0, row_of, grads)
    kp, kmu, knu, rp, rmu, rnu, sums = map(np.asarray, out)
    # the squares of the table each step read, as the kernel summed them
    np.testing.assert_allclose(sums[..., 0], sums[..., 1], rtol=1e-5)
    for x, y in ((kmu, rmu), (knu, rnu)):
        np.testing.assert_allclose(
            x, y, rtol=1e-5, atol=2e-6 * max(float(np.max(np.abs(y))), 1e-30))
    off = np.abs(kp - rp) > 1e-5 * np.abs(rp) + 1e-2 * LR
    assert np.count_nonzero(off) <= 3e-4 * kp.size
    assert np.all(np.abs(kp - rp) <= 1.001 * LR * steps)
    # the rows' gradients moved their rows' moments and no other
    touched = np.zeros(mp * v, bool)
    for shard, r in enumerate(rows):
        touched[shard * v + r] = True
    penalty = np.abs(knu[~touched])
    assert np.all(knu[touched].max(axis=1) > 0)
    if l2 == 0:
        assert np.all(penalty == 0) and np.all(kp[~touched] == p0[~touched])
    else:
        assert np.all(penalty > 0)


@pytest.fixture(scope="module")
def chip():
    """One chip of a described v5e host: no chip attached, the process's
    backend the CPU."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.parametrize("k", [32, 10])
def test_kernel_compiles_for_the_chip_in_place(k, chip):
    """The benchmark's FM_V (12.5M rows, K = 32 and 10) under a donated
    step at b8192's compact buffer: Mosaic takes the kernel, and the table
    and its moments enter and leave it as bitcasts of the column-major
    leaves, aliased: no copy, transpose or fill of a table-sized array."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import Mesh, NamedSharding

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    v, n = 12_500_000, 319_488
    mesh = Mesh(np.array([chip]), ("m",))
    sh = NamedSharding(mesh, P())
    shape = lambda s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt, sharding=sh)

    def step(p, mu, nu, row_of, grads, scalars):
        return adam_rows.adam_rows(p, mu, nu, row_of, grads, scalars,
                                   b1=0.9, b2=0.999, eps=1e-8)

    compiled = jax.jit(shard_map(
        step, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False),
        donate_argnums=(0, 1, 2)).lower(
        shape((v, k)), shape((v, k)), shape((v, k)),
        shape((n,), jnp.int32), shape((n, k)), shape((4,))).compile()
    text = compiled.as_text()
    kernel = [line for line in text.splitlines()
              if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernel) == 1 and "adam_rows" in kernel[0]
    ops = re.findall(rf"= f32\[(?:{v},{k}|{k},{v})\]\S* ([\w-]+)\(", text)
    assert set(ops) == {"parameter", "bitcast", "get-tuple-element"}, ops
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


def _traced_kernel():
    """Trace the kernel once at a tiny size."""
    v, k, n = 300, 8, 128
    mesh = build_mesh(Config().mesh, devices=jax.devices()[:1])
    f = shard_map(
        lambda p, r, g: adam_rows.adam_rows(
            p, p, p, r, g, jnp.ones(4), b1=0.9, b2=0.999, eps=1e-8)[0],
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
    return jax.jit(f).lower(jnp.zeros((v, k)), jnp.full(n, v, jnp.int32),
                            jnp.zeros((n, k)))


@pytest.fixture
def no_import(monkeypatch):
    monkeypatch.setattr(platform, "_pallas_import", None)
    monkeypatch.setenv("XLA_FLAGS", "")


def test_no_import_thread_on_the_cpu(no_import, monkeypatch):
    monkeypatch.setattr(platform, "_requested_platform", lambda: "cpu")
    platform.configure_runtime()
    assert platform._pallas_import is None


def test_import_thread_elsewhere_once_joined_by_the_kernel(no_import,
                                                           monkeypatch):
    monkeypatch.setattr(platform, "_requested_platform", lambda: "tpu")
    platform.configure_runtime()
    started = platform._pallas_import
    assert started is not None and started[0].daemon
    platform.configure_runtime()
    assert platform._pallas_import is started
    joined, real = [], platform.await_pallas

    def await_pallas():
        real()
        joined.append(started[0].is_alive())

    monkeypatch.setattr(platform, "await_pallas", await_pallas)
    _traced_kernel()
    assert joined == [False]


def test_import_failure_surfaces_at_the_join(no_import, monkeypatch):
    real = builtins.__import__

    def no_pallas(name, globals=None, locals=None, fromlist=(), level=0):
        if name.startswith("jax.experimental.pallas") or (
                name == "jax.experimental" and "pallas" in (fromlist or ())):
            raise ImportError("no Pallas here")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_pallas)
    monkeypatch.setattr(platform, "_requested_platform", lambda: "tpu")
    platform.configure_runtime()
    with pytest.raises(ImportError) as raised:
        _traced_kernel()
    assert raised.value is platform._pallas_import[1][0]
