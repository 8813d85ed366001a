"""Rows grouped by expert, and the grouped products over the experts held:
the gated SwiGLU expert and the relu² expert without a gate.

No capacity and no dropped row: the assignments are sorted so that each held
expert's rows are contiguous, the held ones first, and the caller's buffer is
a static prefix of that order — every assignment in the worst case, or the
compact prefix ``ops/experts.py`` takes while the step's held rows fit it
(``sum(sizes)`` never passes the buffer it hands over).  The grouped product
(``lax.ragged_dot``: XLA:TPU's own tiled kernel, whose trip count follows the
live rows) runs over the live prefix.  What lies past it is masked to zero on
the way in and on the way out, both ways of differentiation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def group_rows(expert_of, held: int):
    """Group a flat list of assignments by held expert.

    ``expert_of`` [A] int32: the local expert of each assignment, anything
    outside ``[0, held)`` for one that is not held here.  Returns ``(order,
    sizes, live)``: ``order`` [A] lists the assignments expert by expert, the
    ones not held last; ``sizes`` [held] the rows of each expert; ``live`` [A]
    marks the prefix of ``order`` that is held (``sum(sizes)`` rows)."""
    a = expert_of.shape[0]
    here = (expert_of >= 0) & (expert_of < held)
    key = jnp.where(here, expert_of, held).astype(jnp.int32)
    _, order = lax.sort((key, jnp.arange(a, dtype=jnp.int32)), num_keys=1)
    sizes = jnp.sum(key[:, None] == jnp.arange(held, dtype=jnp.int32)[None, :],
                    axis=0, dtype=jnp.int32)
    return order, sizes, jnp.arange(a) < jnp.sum(sizes)


def _grouped_product(x, w, sizes, keep):
    """Each live row of ``x`` times its group's ``w`` [held, in, out]."""
    # the grouped product leaves the rows past the live prefix unwritten:
    # zeroed before anything nonlinear reads them, so that neither way of
    # differentiation multiplies by what lies there
    return jnp.where(keep, lax.ragged_dot(x, w.astype(x.dtype), sizes), 0)


def grouped_swiglu(rows, w1, w3, w2, sizes, live):
    """``W₂ᵉ(silu(W₁ᵉx) ⊙ W₃ᵉx)`` of every live row ``x`` with its group's
    expert ``e``: rows [R, h] grouped as ``sizes`` says (R any prefix of the
    order that holds the ``sum(sizes)`` live rows), w1 and w3 [held, h, m],
    w2 [held, m, h] -> [R, h]; zero past the live prefix.  silu and the gate
    in float32, the products in the rows' dtype."""
    keep = live[:, None]
    rows = jnp.where(keep, rows, 0)
    a = _grouped_product(rows, w1, sizes, keep)
    b = _grouped_product(rows, w3, sizes, keep)
    h = jax.nn.silu(a.astype(jnp.float32)) * b.astype(jnp.float32)
    return _grouped_product(h.astype(rows.dtype), w2, sizes, keep)


def grouped_relu2(rows, w1, w2, sizes, live):
    """``W₂ᵉ relu(W₁ᵉx)²`` — the expert without a gate — of every live row
    ``x`` with its group's expert ``e``, grouped as ``grouped_swiglu``'s rows
    are: w1 [held, h, m], w2 [held, m, h] -> [R, h]; zero past the live
    prefix.  The square in float32, the products in the rows' dtype."""
    keep = live[:, None]
    rows = jnp.where(keep, rows, 0)
    a = _grouped_product(rows, w1, sizes, keep)
    h = jnp.square(jax.nn.relu(a.astype(jnp.float32)))
    return _grouped_product(h.astype(rows.dtype), w2, sizes, keep)
