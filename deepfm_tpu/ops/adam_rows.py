"""Adam's table pass over a table whose gradient is the step's distinct rows,
in one Pallas kernel.

The dense SPMD step hands a table of rows to the optimizer as its distinct
rows (``parallel/spmd.py _rows_into_moments``): ``row_of`` ascending, then
fillers past the table's end, and the rows' gradients ``s`` beside them.
The table's whole gradient is ``g = c·p + s``: the L2 penalty's ``c·p``
everywhere, ``s`` on those rows only.  For a table whose row is narrower
than one 128-lane tile (FM_V at K = 32 and 10) this kernel is the table's
whole Adam step:

    mu' = b1·mu + (1−b1)·g        nu' = b2·nu + (1−b2)·g²
    p'  = p + step·(mu'/bc1) / (√(nu'/bc2) + eps)·mult

with optax's bias corrections ``bc1``, ``bc2`` at the step's count, its
schedule's ``step = −lr`` and the embedding lr split ``mult``
(``train/optimizer.adam_step_scalars``), every row decayed and penalised,
all in float32.  The chip holds such a table column-major, so the kernel
sees ``p``, ``mu`` and ``nu`` as ``[K, V]`` (a bitcast of the ``[V, K]``
leaf), streams tiles of ``TILE_BYTES`` of columns through VMEM and writes
each back in place (``input_output_aliases`` on the donated state).  A
tile's distinct rows are the run ``[starts[j], starts[j+1])`` of ``row_of``
(a ``searchsorted`` of the tile's bounds, by scalar prefetch), ``ROWS`` a
chunk: the first two chunks that reach a tile are fetched beside it by the
pipeline (no tile of the cells' traffic is reached by more), any further
one by the kernel itself.  A chunk's rows land on the tile's columns by a
one-hot product on the MXU, the gradient as its three bfloat16 parts, so
that every column takes its ``s`` exactly.  No table-shaped gradient, no
scatter into a table-sized operand: the table and its moments cross HBM
once each way, which is the whole of the step's optimizer for that table.
The kernel also sums the squares of the table it reads, the loss's L2 term
of that table, which would otherwise cost a read of the table of its own.

On a TPU the kernel is compiled by Mosaic; elsewhere it runs in Pallas's
interpret mode (observed from the devices the step is traced for, as
``ops/ssd.scan_kernel`` observes them).  Its first trace joins the Pallas
import that ``core/platform.configure_runtime`` started beside the chip's
opening.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
LANES = 128
# bytes of one operand's tile of columns: three in and three out, each
# double-buffered, are 24 MiB of VMEM.  Of the tiles tried on a TPU v5e
# (12.5M rows, b8192's distinct rows) 1 MiB read 15.24 ms at K = 32 and 7.66
# at K = 10, 2 MiB 14.99 and 7.54, 0.5 MiB 15.97 and 7.96 (PERF.md §6)
TILE_BYTES = 2 << 20
# distinct rows one trip of a tile's loop places (256 reads as 128 on the
# chip, and each trip's one-hot is ROWS × the tile's columns)
ROWS = 128
# columns a pass of the kernel's loops over a tile works on: Mosaic unrolls
# a sub-block's vector work, not a whole tile's (0.44 MB of code at K = 32
# against 1.03 MB, a compile for a described v5e)
SUB = 2048
# what the kernel may hold of a v5e core's 128 MiB of VMEM
VMEM_BYTES = 64 * 2**20


def engages(table) -> bool:
    """Whether a table of the pre-add path takes this kernel: rows narrower
    than one 128-lane tile (a table of rows, rank 2)."""
    return table.ndim == 2 and table.shape[1] < LANES


def tile_columns(rows: int, width: int) -> int:
    """The columns of a tile for a ``[rows, width]`` table: ``TILE_BYTES`` of
    float32 at the width the chip pads to eight sublanes, in whole lanes, and
    no more than the table."""
    cols = TILE_BYTES // (4 * _round_up(width, 8)) // LANES * LANES
    return max(LANES, min(cols, _round_up(rows, LANES)))


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _interpret() -> bool:
    device = jax.sharding.get_abstract_mesh().abstract_device
    return device is None or not device.device_kind.startswith("TPU")


def _kernel(starts, scalars, p_ref, mu_ref, nu_ref, ids_a, g_a, ids_b, g_b,
            ids_hbm, g_hbm, p_out, mu_out, nu_out, sq_out, acc, ids_buf, g_buf,
            sems, *, rows, width, b1, b2, eps, mult):
    """One tile of columns: its distinct rows' gradients into ``acc`` [Kp,
    T], then Adam over the tile, and the sum of the squares of the tile's
    ``p`` (its columns under the table's ``rows``) in every lane of
    ``sq_out``.  The first two chunks of rows that reach the tile come
    pipelined with it (``ids_a``/``g_a``, ``ids_b``/``g_b``); a tile that
    more chunks reach fetches the rest itself.  ``scalars`` = (c, bc1, bc2,
    step)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    j = pl.program_id(0)
    kp, cols = acc.shape
    sub = math.gcd(cols, SUB)
    first = starts[j] // ROWS
    last = (starts[j + 1] + ROWS - 1) // ROWS

    def place(ids, g):
        # the chunk's rows as a column, less the tile's first: a row of
        # another tile, or a filler, meets no column of this one
        at = jnp.transpose(jnp.broadcast_to(ids, (8, ROWS)))[:, :1] - j * cols
        hi = g.astype(jnp.bfloat16)
        rest = g - hi.astype(F32)
        mid = rest.astype(jnp.bfloat16)
        lo = (rest - mid.astype(F32)).astype(jnp.bfloat16)
        parts = jnp.concatenate([hi, mid, lo], axis=0)

        def columns(i, carry):
            at_sub = pl.multiple_of(i * sub, sub)
            one_hot = (lax.broadcasted_iota(jnp.int32, (ROWS, sub), 1)
                       == at - at_sub).astype(jnp.bfloat16)
            # each column meets one row at most: the three parts' products
            # are exact, and so is their sum
            part = jnp.dot(parts, one_hot, preferred_element_type=F32)
            acc[:, pl.ds(at_sub, sub)] += (part[:kp] + part[kp:2 * kp]
                                          + part[2 * kp:])
            return carry

        lax.fori_loop(0, cols // sub, columns, 0)

    def trip(chunk, carry):
        @pl.when(chunk == first)
        def _():
            ids_buf[...] = ids_a[...]
            g_buf[...] = g_a[...]

        @pl.when(chunk == first + 1)
        def _():
            ids_buf[...] = ids_b[...]
            g_buf[...] = g_b[...]

        @pl.when(chunk > first + 1)
        def _():
            ids = pltpu.make_async_copy(ids_hbm.at[chunk], ids_buf, sems.at[0])
            grad = pltpu.make_async_copy(g_hbm.at[chunk], g_buf, sems.at[1])
            ids.start()
            grad.start()
            ids.wait()
            grad.wait()

        place(ids_buf[...], g_buf[...])
        return carry

    acc[...] = jnp.zeros(acc.shape, F32)
    lax.fori_loop(first, last, trip, 0)

    c, bc1, bc2, step = (scalars[i] for i in range(4))

    def adam(i, squares):
        at_sub = pl.multiple_of(i * sub, sub)
        here = pl.ds(at_sub, sub)
        p = p_ref[:, here]
        g = c * p + acc[:width, here]
        mu = (1 - b1) * g + b1 * mu_ref[:, here]
        nu = (1 - b2) * (g * g) + b2 * nu_ref[:, here]
        u = step * ((mu / bc1) / (jnp.sqrt(nu / bc2) + eps))
        if mult != 1.0:
            u = u * mult
        p_out[:, here] = p + u
        mu_out[:, here] = mu
        nu_out[:, here] = nu
        # the last tile runs past the table: its columns there hold no rows
        inside = (lax.broadcasted_iota(jnp.int32, (1, sub), 1)
                  < rows - j * cols - at_sub)
        return squares + jnp.sum(jnp.where(inside, p * p, 0.0), axis=0,
                                 keepdims=True)

    squares = lax.fori_loop(0, cols // sub, adam, jnp.zeros((1, sub), F32))
    sq_out[...] = jnp.broadcast_to(
        jnp.sum(squares, axis=1, keepdims=True), sq_out.shape)


@jax.named_scope("adam_rows")
def adam_rows(p, mu, nu, row_of, grads, scalars, *, b1: float, b2: float,
              eps: float, mult: float = 1.0):
    """One Adam step of a ``[V, K]`` table ``p`` with moments ``mu`` and
    ``nu`` whose gradient is ``c·p`` plus ``grads`` [n, K] on the rows
    ``row_of`` [n] (ascending, then fillers past ``V``): ``(p', mu', nu',
    Σp²)``, the first three in place of ``p``, ``mu`` and ``nu``, the last
    the sum of the squares of ``p`` as the kernel read it.  ``scalars`` =
    f32[4] (c, bc1, bc2, step); the hyperparameters are static."""
    from ..core.platform import await_pallas

    await_pallas()
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    v, k = p.shape
    cols = tile_columns(v, k)
    tiles = pl.cdiv(v, cols)
    kp = _round_up(k, 16)
    n = _round_up(row_of.shape[0], ROWS)
    pad = n - row_of.shape[0]
    row_of = jnp.pad(row_of.astype(jnp.int32), (0, pad),
                     constant_values=jnp.iinfo(jnp.int32).max)
    starts = jnp.searchsorted(
        row_of, jnp.minimum(jnp.arange(tiles + 1, dtype=jnp.int32) * cols, v)
    ).astype(jnp.int32)
    grads = jnp.pad(grads.astype(F32), ((0, pad), (0, kp - k)))
    grads = grads.reshape(n // ROWS, ROWS, kp).swapaxes(1, 2)
    tile = pl.BlockSpec((k, cols), lambda j, starts: (0, j))
    chunks = n // ROWS

    def chunk(ahead):
        at = lambda j, starts: (
            jnp.minimum(starts[j] // ROWS + ahead, chunks - 1), 0, 0)
        return [pl.BlockSpec((None, 1, ROWS), at),
                pl.BlockSpec((None, kp, ROWS), at)]

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    ids = row_of.reshape(chunks, 1, ROWS)
    table_bytes = 4 * _round_up(k, 8) * v
    outs = pl.pallas_call(
        functools.partial(_kernel, rows=v, width=k, b1=b1, b2=b2, eps=eps,
                          mult=mult),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      tile, tile, tile, *chunk(0), *chunk(1), hbm, hbm],
            out_specs=[tile, tile, tile,
                       pl.BlockSpec((None, 1, LANES),
                                    lambda j, starts: (j, 0, 0))],
            scratch_shapes=[pltpu.VMEM((kp, cols), F32),
                            pltpu.VMEM((1, ROWS), jnp.int32),
                            pltpu.VMEM((kp, ROWS), F32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct((k, v), F32)] * 3
        + [jax.ShapeDtypeStruct((tiles, 1, LANES), F32)],
        input_output_aliases={2: 0, 3: 1, 4: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=16 * k * v + 2 * 3 * kp * ROWS * cols * tiles,
            transcendentals=k * v,
            bytes_accessed=6 * table_bytes + 4 * n * (kp + 1)),
        name="adam_rows", interpret=_interpret(),
    )(starts, scalars, p.T, mu.T, nu.T, ids, grads, ids, grads, ids, grads)
    *tables, squares = outs
    return (*(out.T for out in tables), jnp.sum(squares[:, 0, 0]))
