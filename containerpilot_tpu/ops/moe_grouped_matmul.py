"""A layer's routed experts as ONE pipelined grouped kernel: every row
tile through the SwiGLU of the expert it belongs to, each touched
expert's weights read once, the next tile's expert fetched while this
one multiplies.

The caller (models/moe.py ``sparse_experts``) sorts the (token, expert)
assignments by expert and lays them out in a TILE-PADDED order: an
expert's run starts on a tile boundary, so a tile of ``tile_rows`` rows
belongs to one expert. Four small tables are prefetched into scalar
memory: ``tile_expert`` [tiles_max] (a tile's expert), ``tiles`` [1]
(how many tiles exist), ``tile_live`` [tiles_max] (a tile's rows that
exist) and ``token`` [tiles_max * tile_rows] (a row's token). The grid
is (row tiles, tiles of ``f``). The index maps of the three weight
stacks select ``tile_expert[j]``: they are read AS STORED (``[held, d,
f]``, ``[held, f, d]``), no concatenated or re-laid-out copy. Two
consecutive tiles of one expert name the same block, which the pipeline
does not fetch again (where ``f`` is one tile). Steps past ``tiles``
clamp to the last live step's blocks and ``pl.when`` skips them: they
fetch nothing and compute nothing (the pattern of
ops/ragged_decode.py). An expert nobody chose is in no tile and is
never read; only a call with NO tile at all still fetches the one block
its first step names.

A tile's first step copies its token rows out of ``h`` where it lies
(one small copy a row that exists, all in flight at once; ``h`` comes
as float32 ``[n, 1, d]``, a row a tile of its own, because a copy
moves whole tiles and a bfloat16 tile holds two rows). Every step
computes ``silu(rows @ w_gate) * (rows @ w_up)`` in float32, rounds it
to the compute dtype, multiplies by ``w_down`` into the tile's float32
result, which stays in fast memory while the tiles of ``f`` pass; the
last step applies the rows' float32 gates and adds each row onto its
token's row of the float32 sums ``[n, d]``, which live in fast memory
for the whole call and are written out once, by the last step. No
gathered copy of the rows and no per-assignment result ever reaches
the chip's main memory. Nothing narrower than the plain loop's
products appears anywhere; a token's experts are added in the order of
their ids.

The sizes follow the shapes alone. ``f`` is tiled only where one
expert's three matrices, twice (the pipeline's two buffers), do not
fit their share of the chip's fast memory beside the sums (``f_tile``);
a call with more tokens than ``rows_bound`` is the caller's to cut.

Off the chip (the CPU test mesh has no Mosaic target) the kernel
interprets, resolved as ops/flash.py does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash

F32 = jnp.float32
#: fast memory one call may ask for (``vmem_limit_bytes``): the v5e's
#: TensorCore has 128 MiB, the compiler keeps some for itself
VMEM_BUDGET = 100 * 2 ** 20
#: the part of it the weights' blocks may take, both of the pipeline's
#: buffers counted; the tokens' sums, a tile's rows and results and the
#: step's float32 intermediates live in the rest
WEIGHT_BUDGET = 48 * 2 ** 20
#: asked for beyond what the blocks and scratch of a call add up to.
#: Small on purpose: what a kernel reserves the compiler takes from ALL
#: of a program's own placements (with 16 MiB more, block-decode's 100 MB
#: of attention scores a layer fell out of fast memory and its attention
#: ran 2.5 ms a forward longer: PERF.md, PR 47)
MARGIN = 4 * 2 ** 20
LANES = 128


def f_tile(d: int, f: int, itemsize: int, rows_bytes: int = 0) -> int:
    """Columns of ``f`` one step multiplies: all of ``f`` where an
    expert's three [d, f] matrices fit ``WEIGHT_BUDGET`` twice over
    (and, with the ``rows_bytes`` the call keeps in fast memory beside
    them, ``VMEM_BUDGET``), else the largest divisor of ``f`` in whole
    lanes that does."""
    budget = min(WEIGHT_BUDGET, VMEM_BUDGET - rows_bytes - 2 * MARGIN)

    def fits(tile):
        return 2 * 3 * d * tile * itemsize <= budget

    if fits(f) or f % LANES:
        return f
    tiles = [t for t in range(f - LANES, 0, -LANES) if f % t == 0]
    return next((t for t in tiles if fits(t)), LANES)


def rows_bound(d: int) -> int:
    """The most token rows one call may take: their float32 sums
    [rows, d] stay in fast memory for the whole call and may fill half
    of ``VMEM_BUDGET``."""
    return VMEM_BUDGET // 2 // (4 * d) // 8 * 8


def tiles_bound(n: int, k: int, held: int, tile_rows: int) -> int:
    """The most row tiles ``n`` tokens of ``k`` experts each can fill
    among ``held`` experts: every assignment in a full tile plus one
    ragged tile an expert, and no expert more than ``n`` rows."""
    return min(-(-n * k // tile_rows) + held, held * -(-n // tile_rows))


def _kernel(tile_expert_ref, tiles_ref, live_ref, token_ref,
            h_ref, g_ref, wg_ref, wu_ref, wd_ref, o_ref,
            x_ref, y_ref, acc_ref, sem, *,
            tile_rows: int, f_steps: int, dt):
    j, s = pl.program_id(0), pl.program_id(1)
    first = (j == 0) & (s == 0)
    last = (j == pl.num_programs(0) - 1) & (s == f_steps - 1)

    @pl.when(first)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < tiles_ref[0])
    def _tile():
        base = j * tile_rows
        rows = live_ref[j]

        @pl.when(s == 0)
        def _gather():
            # the tile's token rows out of h where it lies: one copy a
            # row that exists, all in flight at once
            def row(i):
                return pltpu.make_async_copy(
                    h_ref.at[token_ref[base + i]], x_ref.at[i], sem)

            def start(i, carry):
                row(i).start()
                return carry

            def wait(i, carry):
                row(i).wait()
                return carry

            jax.lax.fori_loop(0, rows, start, 0)
            jax.lax.fori_loop(0, rows, wait, 0)

        x = x_ref[...].reshape(tile_rows, -1).astype(dt)
        up = jnp.dot(x, wu_ref[0].astype(dt), preferred_element_type=F32)
        act = jax.nn.silu(jnp.dot(
            x, wg_ref[0].astype(dt), preferred_element_type=F32)) * up
        y = jnp.dot(act.astype(dt), wd_ref[0].astype(dt),
                    preferred_element_type=F32)
        if f_steps == 1:
            y_ref[...] = y * g_ref[...]
        else:
            @pl.when(s == 0)
            def _first():
                y_ref[...] = y

            @pl.when(s > 0)
            def _further():
                y_ref[...] += y

            @pl.when(s == f_steps - 1)
            def _gated():
                y_ref[...] *= g_ref[...]

        @pl.when(s == f_steps - 1)
        def _scatter():
            # each gated row onto its token's row of the float32 sum
            def add(i, c):
                at = pl.ds(token_ref[base + i], 1)
                acc_ref[at, :] += y_ref[pl.ds(i, 1), :]
                return c

            jax.lax.fori_loop(0, rows, add, 0)

    @pl.when(last)
    def _out():
        copy = pltpu.make_async_copy(acc_ref, o_ref, sem)
        copy.start()
        copy.wait()


def grouped_swiglu(
    h: jax.Array,            # [n, 1, d] float32: the tokens' rows
    token: jax.Array,        # [tiles_max * tile_rows] int32: a row's token
    gates: jax.Array,        # [tiles_max * tile_rows, 1] float32
    tile_expert: jax.Array,  # [tiles_max] int32: the held expert of a tile
    tile_live: jax.Array,    # [tiles_max] int32: rows of a tile that exist
    tiles: jax.Array,        # [1] int32: tiles that exist
    w_gate: jax.Array,       # [held, d, f]
    w_up: jax.Array,         # [held, d, f]
    w_down: jax.Array,       # [held, f, d]
    *,
    tile_rows: int,
    dtype,
) -> jax.Array:
    """``sum over a token's rows of gates * SwiGLU_e(h[token])``, e the
    row's tile's expert, over the rows of the tiles that exist: float32
    [n, d]. ``dtype`` is the compute dtype the rows and the activation
    are rounded to."""
    n, _one, d = h.shape
    held, _d, f = w_gate.shape
    tiles_max = tile_expert.shape[0]
    itemsize = jnp.dtype(w_gate.dtype).itemsize
    rows_bytes = 4 * tile_rows * d * 4 + n * d * 4
    tf = f_tile(d, f, itemsize, rows_bytes)
    f_steps = f // tf

    def live(j, tiles_ref):
        return jnp.maximum(jnp.minimum(j, tiles_ref[0] - 1), 0)

    def step(j, s, tiles_ref):
        # past the last tile: the last live step's blocks again
        return jnp.where(j < tiles_ref[0], s, f_steps - 1)

    def rows_of(j, s, tile_expert_ref, tiles_ref, *_):
        return (live(j, tiles_ref), 0)

    def in_proj(j, s, tile_expert_ref, tiles_ref, *_):
        return (tile_expert_ref[live(j, tiles_ref)], 0, step(j, s, tiles_ref))

    def out_proj(j, s, tile_expert_ref, tiles_ref, *_):
        return (tile_expert_ref[live(j, tiles_ref)], step(j, s, tiles_ref), 0)

    need = (
        # the weights' two buffers, and a copy where they are cast
        (2 + (w_gate.dtype != dtype)) * 3 * d * tf * itemsize
        + rows_bytes + 4 * tile_rows * 3 * tf * 4)
    return pl.pallas_call(
        functools.partial(_kernel, tile_rows=tile_rows, f_steps=f_steps,
                          dt=dtype),
        # a stable kernel name: a profiler trace finds it by it
        name="moe_grouped_matmul",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(tiles_max, f_steps),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((tile_rows, 1), rows_of),
                pl.BlockSpec((1, d, tf), in_proj),
                pl.BlockSpec((1, d, tf), in_proj),
                pl.BlockSpec((1, tf, d), out_proj),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((tile_rows, 1, d), F32),  # the tile's rows
                pltpu.VMEM((tile_rows, d), F32),     # their results
                pltpu.VMEM((n, d), F32),             # the tokens' sums
                pltpu.SemaphoreType.DMA,
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n, d), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(VMEM_BUDGET, need + MARGIN)),
        interpret=flash._resolve_interpret(None),
    )(tile_expert, tiles, tile_live, token, h, gates, w_gate, w_up, w_down)
