"""What a rematerialised block keeps of its forward: the names, the rule
that chooses among them, and their bytes.

``keep(x, name)`` is ``jax.ad_checkpoint.checkpoint_name``: an identity, which
a ``jax.checkpoint`` whose policy saves ``name`` stores instead of computing
again in its backward.  The ops name what they produce (``NAMES``); the
sequence families checkpoint each block under ``block_policy``'s policy, which
follows the bytes.

The rule.  A block's recomputation should hold element-wise work only (the
block norms, gates, casts, masks and counts): no matmul, no sort, no top-k,
no gather by index and no kernel.  So where it fits, every product is kept
where it leaves the MXU, what the attention reads and writes, the router's
choice, and past that, where a traced run showed that it pays (``PERF.md``
§6, PR 39), the dense SwiGLU's operands: all of ``NAMES``.  Where it does not
fit, the names are kept in ``NAMES``' order — dearest to form again first: a
kernel and what makes its operands, a sort and a top-k, a plain product,
element-wise work — as far as the bytes go: the longest prefix whose arrays,
with the blocks' own inputs, take no more than half of what the device has
left once the state is made (the other half is the backward's: one block's
products again, their cotangents, the head's logits).  All of it observed
from the trace: the named arrays' shapes (an abstract trace of the blocks
under ``tally``), the parameters' bytes (weights, gradient and two moments
of their size: 16 B a float32 parameter), and the memory of the device the
trace is for (``device_memory``).  No option, field or environment variable;
``blocks keep: …`` at INFO says once a trace what was chosen.
"""

from __future__ import annotations

import contextlib
import contextvars
import logging
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# checkpoint names, in the order a block keeps them: what the attention
# kernel's backward needs of its forward (``ops/attention.py``); the router's
# logits, choice and chosen scores, the grouping's order and sizes
# (``ops/experts.py``); a projection's product, in the dtype it was computed
# in, and the dense SwiGLU's two operands in ``compute_dtype``
# (``ops/dense.py``)
ATTENTION_RESIDUALS = "attention_residuals"
ROUTING_RESIDUALS = "routing_residuals"
PROJECTIONS = "projections"
SWIGLU_OPERANDS = "swiglu_operands"
NAMES = (ATTENTION_RESIDUALS, ROUTING_RESIDUALS, PROJECTIONS, SWIGLU_OPERANDS)

# a described chip reports no ``memory_stats()``: what an attached one of its
# kind reads as ``bytes_limit`` (PERF.md §7 (j)), so that a rehearsal compile
# chooses what the chip will
DESCRIBED_MEMORY = {"TPU v5 lite": 16_909_336_064}

_TALLY: contextvars.ContextVar = contextvars.ContextVar("kept", default=None)


def count(name: str, shape, dtype) -> None:
    """Add an array of ``shape`` and ``dtype`` that carries ``name`` by other
    hands (a kernel's own residuals) to the open tally."""
    tally = _TALLY.get()
    if tally is not None:
        tally[name] = (tally.get(name, 0)
                       + math.prod(shape) * jnp.dtype(dtype).itemsize)


def keep(x, name: str):
    count(name, x.shape, x.dtype)
    return checkpoint_name(x, name)


@contextlib.contextmanager
def tally():
    """-> {name: bytes} of what was named while it was open."""
    token = _TALLY.set({})
    try:
        yield _TALLY.get()
    finally:
        _TALLY.reset(token)


def device_memory() -> int | None:
    """Bytes of memory of one device of the kind the trace runs under (the
    mesh of the step builders' ``shard_map``): ``memory_stats()`` of an
    attached device of that kind, the described chip's in a rehearsal
    compile, None where neither says (the CPU, no mesh)."""
    device = jax.sharding.get_abstract_mesh().abstract_device
    if device is None:
        return None
    for d in jax.local_devices():
        if d.device_kind == device.device_kind:
            limit = (d.memory_stats() or {}).get("bytes_limit")
            if limit:
                return int(limit)
    return DESCRIBED_MEMORY.get(device.device_kind)


def names_that_fit(named: dict, inputs: int, state: int,
                   memory: int | None) -> tuple:
    """The longest prefix of ``NAMES`` whose ``named`` bytes a step, with the
    blocks' ``inputs``, fit half of ``memory − state``; every name where the
    memory is unknown.  Names that no array carries are left out."""
    names, used = [], inputs
    for name in NAMES:
        used += named.get(name, 0)
        if memory is not None and 2 * used > memory - state:
            break
        if name in named:
            names.append(name)
    return tuple(names)


def block_policy(blocks, x, params, layers: int, log: logging.Logger):
    """The ``jax.checkpoint`` policy of a stack of blocks.  ``blocks(x)``
    runs them all, not checkpointed (traced here abstractly, once more than
    the step needs, for the named arrays' shapes); ``x`` is the first block's
    input, one of ``layers`` of its size that the backward holds whatever the
    policy; ``params`` the parameters the step trains (the state they bring:
    weights, gradient, two moments).  ``log`` is the family's: it says what
    was chosen."""
    with tally() as named:
        jax.eval_shape(blocks, x)
    state = 4 * sum(p.size * p.dtype.itemsize
                    for p in jax.tree_util.tree_leaves(params))
    memory = device_memory()
    names = names_that_fit(named, layers * x.size * x.dtype.itemsize, state,
                           memory)
    again = sorted(set(named) - set(names))
    log.info(
        "blocks keep: %s, %.3f MB a step%s", ", ".join(sorted(names)),
        sum(named[k] for k in names) / 1e6,
        "" if not again else "; run again: %s, %.3f MB (%.3f MB left of "
        "%.3f once the state is made)" % (
            ", ".join(again), sum(named[k] for k in again) / 1e6,
            (memory - state) / 1e6, memory / 1e6))
    return jax.checkpoint_policies.save_only_these_names(*names)
