"""Decode attention over a cache leaf whose rows end at different
positions: ONE query position a row, keys and values read AS THE CACHE
STORES THEM and only as far as each row is live.

The leaf is ``[rows, groups, length, width]`` (models/decoder_hybrid.py:
the shared plane, pair-major, ``groups`` = key/value pairs, ``width`` =
128), the queries ``[rows, groups, maps, width]`` (every group's query
maps meet that group's keys), the rows' positions ``at`` ``[rows]``
int32. A plain XLA contraction reads every row to ``length`` and masks
afterwards; reading only the live blocks there means gathering them,
which writes and re-reads more bytes than the whole read. Here the grid
is (rows, key blocks), ``at`` is prefetched into scalar memory, and the
key blocks' index map CLAMPS the block index to the row's last live
block: the pipeline sees the same block index again on every step past
the row's end and fetches nothing, and ``pl.when`` skips the step's
work (the pattern of jax's paged-attention kernels). A row whose
position has run past ``length`` (a retired slot decodes on) is clamped
to the leaf's last position: nothing is read out of bounds.

The precision is the plain contraction's (``_pair_attention``): stored
dtype into float32 scores, float32 softmax weights (here an online
softmax, float32 running maximum and sum), float32 accumulation of the
weighted values at HIGHEST. For a bfloat16 leaf HIGHEST is made by
hand: a float32 weight is the exact sum of three bfloat16 numbers, so
the query rows ride three times through the scores (``PARTS``), each
copy keeps one of the three parts of its weights, ONE bfloat16
matmul against the values as stored gives three partial sums in
float32 and their sum is the product with the float32 weights to the
last bit the accumulation keeps. The values then pass the matrix unit
once, not once a pass of a float32 product: a decode step's attention
is bound by what moves, and each tile of keys and values is loaded
into the matrix unit exactly once.

Off the chip (the CPU test mesh has no Mosaic target) the kernel
interprets, resolved as ops/flash.py does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash
from .attention import NEG_INF

F32 = jnp.float32
#: positions of a key block, where the leaf's length is a multiple of
#: it (else the next smaller power of two that divides the length, else
#: the whole length). A block of 256 positions x 10 groups x 128 is
#: 0.66 MB of keys and as much of values, twice each in the pipeline;
#: on the v5e (PERF.md, PR 46) 256 read a pool of ragged rows faster
#: than 512 or 1,024, which read further past each row's end.
BLOCK_LEN = 256
#: a bfloat16 leaf's query rows ride this many times (the module's note)
PARTS = 3


def block_len(length: int) -> int:
    """Positions of one key block of a leaf of ``length`` positions."""
    size = BLOCK_LEN
    while size >= 8:
        if length % size == 0:
            return size
        size //= 2
    return length


def _last_block(at, length: int, size: int):
    """The block that holds a row's last live position: ``at``, or the
    leaf's last position for a row that has run past it."""
    return jnp.minimum(at, length - 1) // size


def positions_covered(at: jax.Array, length: int) -> jax.Array:
    """Positions the kernel's blocks cover for rows at ``at`` [rows]:
    every block up to the one that holds ``min(at, length - 1)``, whole.
    int32, one number."""
    size = block_len(length)
    return jnp.sum((_last_block(at, length, size) + 1) * size,
                   dtype=jnp.int32)


def _weighted(p, v, maps: int, parts: int):
    """p [groups, lanes, block] float32 times v [groups, block, width]
    as stored, accumulated in float32 at HIGHEST (the module's note)."""
    if parts == 1:
        return jnp.einsum("gjk,gkd->gjd", p, v.astype(F32),
                          preferred_element_type=F32,
                          precision=lax.Precision.HIGHEST)
    # copy c of the query rows keeps part c of its weights
    copy = lax.broadcasted_iota(jnp.int32, p.shape, 1) // maps
    rest, kept = p, jnp.zeros_like(p)
    for c in range(parts):
        part = rest.astype(v.dtype).astype(F32)
        kept = jnp.where(copy == c, part, kept)
        rest = rest - part
    return jnp.einsum("gjk,gkd->gjd", kept.astype(v.dtype), v,
                      preferred_element_type=F32)


def _kernel(at_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            size: int, length: int, scale: float, maps: int, parts: int):
    row, step = pl.program_id(0), pl.program_id(1)
    last = jnp.minimum(at_ref[row], length - 1)

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(step * size <= last)
    def _block():
        scores = jnp.einsum("gjd,gkd->gjk", q_ref[0], k_ref[0],
                            preferred_element_type=F32) * scale
        cols = step * size + lax.broadcasted_iota(jnp.int32, scores.shape, 2)
        scores = jnp.where(cols <= last, scores, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + _weighted(
            p, v_ref[0], maps, parts)

    @pl.when(step == pl.num_programs(1) - 1)
    def _finish():
        acc = acc_ref[...]
        out = acc[:, :maps]
        for c in range(1, parts):
            out = out + acc[:, c * maps:(c + 1) * maps]
        o_ref[0] = out / l_ref[...][:, :maps]


def ragged_decode_attention(q: jax.Array, keys: jax.Array, values: jax.Array,
                            at: jax.Array, *, scale: float) -> jax.Array:
    """Attention of q [rows, groups, maps, width] over keys / values
    [rows, groups, length, width] as stored, row r over positions ``0 ..
    min(at[r], length - 1)``. Returns float32 [rows, groups, maps,
    width] (the module's note has the precision)."""
    rows, groups, length, width = keys.shape
    maps = q.shape[2]
    size = block_len(length)
    parts = PARTS if values.dtype == jnp.bfloat16 else 1
    # the query rows: ``parts`` copies, padded to whole sublane tiles
    tile = 8 * 4 // jnp.dtype(q.dtype).itemsize
    lanes = -(-maps * parts // tile) * tile
    q = jnp.concatenate(
        [q] * parts + [jnp.zeros(
            (rows, groups, lanes - maps * parts, width), q.dtype)], axis=2)

    def block_of(row, step, at_ref):
        live = _last_block(at_ref[row], length, size)
        return (row, 0, jnp.minimum(step, live), 0)

    by_row = lambda row, step, at_ref: (row, 0, 0, 0)
    kernel = functools.partial(_kernel, size=size, length=length, scale=scale,
                               maps=maps, parts=parts)
    return pl.pallas_call(
        kernel,
        # a stable kernel name: a profiler trace finds it by it
        name="ragged_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, length // size),
            in_specs=[
                pl.BlockSpec((1, groups, lanes, width), by_row),
                pl.BlockSpec((1, groups, size, width), block_of),
                pl.BlockSpec((1, groups, size, width), block_of),
            ],
            out_specs=pl.BlockSpec((1, groups, maps, width), by_row),
            scratch_shapes=[
                pltpu.VMEM((groups, lanes, 1), F32),      # running maximum
                pltpu.VMEM((groups, lanes, 1), F32),      # running sum
                pltpu.VMEM((groups, lanes, width), F32),  # weighted values
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, groups, maps, width), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=flash._resolve_interpret(None),
    )(at.astype(jnp.int32), q, keys, values)
