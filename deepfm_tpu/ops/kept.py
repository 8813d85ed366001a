"""What a rematerialised block keeps of its forward: the names, the rule
that chooses among them, and their bytes.

``keep(x, name)`` is ``jax.ad_checkpoint.checkpoint_name``: an identity, which
a ``jax.checkpoint`` whose policy saves ``name`` stores instead of computing
again in its backward.  The ops name what they produce (``NAMES``); the
sequence families checkpoint each block under its policy of
``block_policy``'s, which follows the bytes and the block's place in the
stack.

The rule.  A block's recomputation should hold element-wise work only (the
block norms, gates, casts, masks and counts): no matmul, no sort, no top-k,
no gather by index and no kernel.  So where it fits, every product is kept
where it leaves the MXU, what the attention reads and writes, the router's
choice, and past that, where a traced run showed that it pays (``PERF.md``
§6, PR 39), the dense SwiGLU's operands: all of ``NAMES``.  Where it does not
fit, the rule chooses a block at a time (``names_by_block``):

1. Every block keeps the names in ``NAMES``' order — dearest to form again
   first: a kernel and what makes its operands, a sort and a top-k, a plain
   product, element-wise work — as far as the bytes go: the longest prefix
   whose arrays over all blocks, with the blocks' own inputs, take no more
   than half of what the device has left once the state is made (the other
   half is the backward's: one block's products again, their cotangents, the
   head's logits).
2. The last block of the stack, the first that the backward reaches, also
   keeps every product it carries (``PRODUCTS``), whatever the bytes: only
   the head and the loss lie between its forward and its backward, and that
   backward holds those arrays anyway — kept or formed again — at what is
   the step's fullest moment, so keeping them adds nothing to the peak
   (``PERF.md`` §6, PR 42).  The SwiGLU's operands are element-wise to form
   and not alive there: they stay under 1 in the last block as in any other.
3. Where the memory is unknown (the CPU, no mesh) every block keeps every
   name.

All of it observed from the trace: the named arrays' shapes, block by block
(an abstract trace of the blocks, each under a ``tally``), the parameters'
bytes (weights, gradient and two moments of their size: 16 B a float32
parameter), and the memory of the device the trace is for
(``device_memory``).  No option, field or environment variable; ``blocks
keep: …`` at INFO says once a trace what every block keeps, what the last
also keeps and what which blocks run again, and ``block_policy`` hands the
families the share of their blocks that keep every product they carry (their
step's ``blocks_products_kept_share``).
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import logging
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# checkpoint names, in the order a block keeps them: what the attention
# kernel's backward needs of its forward (``ops/attention.py``; where the keys
# are selected from data, the selection, as bits, and the gradient of its own
# loss, formed where the score blocks are: ``ops/indexer.py``); the router's
# logits, choice and chosen scores, the grouping's order and sizes
# (``ops/experts.py``); a projection's product, in the dtype it was computed
# in, and the dense SwiGLU's two operands in ``compute_dtype``
# (``ops/dense.py``)
ATTENTION_RESIDUALS = "attention_residuals"
ROUTING_RESIDUALS = "routing_residuals"
PROJECTIONS = "projections"
SWIGLU_OPERANDS = "swiglu_operands"
NAMES = (ATTENTION_RESIDUALS, ROUTING_RESIDUALS, PROJECTIONS, SWIGLU_OPERANDS)
# those that mark a product: what leaves the MXU, a kernel, a sort or a top-k
# (the dense SwiGLU's operands are element-wise to form)
PRODUCTS = (ATTENTION_RESIDUALS, ROUTING_RESIDUALS, PROJECTIONS)

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


def names_by_block(named: list, inputs: int, state: int,
                   memory: int | None) -> list:
    """-> for each block of a stack, in the stack's order, the names its
    policy saves; ``named`` is each block's {name: bytes}.  Every block keeps
    what ``names_that_fit`` gives the stack's sum; the last block, the first
    that the backward reaches, also every name of ``PRODUCTS`` it carries,
    whatever the bytes."""
    total = collections.Counter()
    for block in named:
        total.update(block)
    fit = names_that_fit(total, inputs, state, memory)
    last = tuple(k for k in NAMES
                 if k in fit or (k in PRODUCTS and k in named[-1]))
    return [fit] * (len(named) - 1) + [last]


def block_policy(blocks, x, params, log: logging.Logger):
    """-> (the ``jax.checkpoint`` policy of each block of a stack, the share
    of the blocks that keep every product they carry).  ``blocks(x, wrap)``
    runs them all, block i as ``wrap(run, i)(…)`` (traced here abstractly,
    once more than the step needs, each block under a ``tally`` of its own
    for the named arrays' shapes); ``x`` is the first block's input, one a
    block of its size that the backward holds whatever the policies;
    ``params`` the parameters the step trains (the state they bring:
    weights, gradient, two moments).  ``log`` is the family's: it says what
    was chosen."""
    named = []

    def tallied(run, i):
        def call(*args):
            with tally() as block:
                out = run(*args)
            named.append(block)
            return out
        return call

    jax.eval_shape(lambda x: blocks(x, tallied), x)
    state = 4 * sum(p.size * p.dtype.itemsize
                    for p in jax.tree_util.tree_leaves(params))
    memory = device_memory()
    names = names_by_block(named, len(named) * x.size * x.dtype.itemsize,
                           state, memory)

    def megabytes(keys, blocks):
        return sum(b.get(k, 0) for b in blocks for k in keys) / 1e6

    every = set(names[0])
    also = set(names[-1]) - every
    again = [set(block) - set(kept) for block, kept in zip(named, names)]
    said = "blocks keep: %s, %.3f MB a step" % (
        ", ".join(sorted(every)), megabytes(every, named))
    if also:
        said += "; the last block also: %s, %.3f MB" % (
            ", ".join(sorted(also)), megabytes(also, named[-1:]))
    if any(again):
        blocks_of = collections.Counter(k for keys in again for k in keys)
        said += ("; run again: %s, %.3f MB (%.3f MB left of %.3f once the "
                 "state is made)") % (
            ", ".join("%s in %d block%s" % (k, n, "s" * (n > 1))
                      for k, n in sorted(blocks_of.items())),
            sum(megabytes(keys, [b]) for keys, b in zip(again, named)),
            (memory - state) / 1e6, memory / 1e6)
    log.info(said)
    whole = sum(keys.isdisjoint(PRODUCTS) for keys in again)
    # one policy object for the blocks that keep the same names: jax caches
    # what it makes of a block's inner functions by it, and lowers them once
    policy = {kept: jax.checkpoint_policies.save_only_these_names(*kept)
              for kept in set(names)}
    return [policy[kept] for kept in names], whole / len(named)
