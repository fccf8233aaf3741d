"""Routed experts for the serving families of ``models/mla_moe.py``,
``models/block_diffusion.py`` and ``models/hybrid_ssm.py``.

``route_topk``: sigmoid scores over ALL experts in float32, top k,
renormalise, scale. ``route_softmax``: the same with a softmax over all
experts for the scores and no scale. ``sparse_experts``: the part of
the result that the experts HELD here give: assignments sorted by
expert and laid out in row tiles of one expert each, ONE grouped
SwiGLU kernel a call over the tiles that exist
(ops/moe_grouped_matmul.py: the grid's steps are the tiles, the next
tile's expert is fetched while this one multiplies); no capacity, no
token dropped, an expert nobody chose is never read.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..ops import moe_grouped_matmul as grouped


def route_topk(
    h: jax.Array,         # [n, d_model]
    router_w: jax.Array,  # [d_model, all experts]
    k: int,
    scale: float,
    normalise: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """DeepSeek-V3's router without groups or bias (arXiv:2412.19437,
    eq. 12-15): ``s_e = sigmoid(h . w_e)`` in float32 over every
    expert, the ``k`` largest, gates ``scale * s_e / sum of the k``.
    Returns (expert ids [n, k] int32, gates [n, k] float32). The sum
    is over all ``k`` chosen experts, held here or not."""
    with jax.named_scope("mlp.router"):
        scores = jax.nn.sigmoid(jnp.einsum(
            "nd,de->ne", h.astype(jnp.float32),
            router_w.astype(jnp.float32),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        ))
        top, idx = jax.lax.top_k(scores, k)
        if normalise:
            top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), top * scale


def route_softmax(
    h: jax.Array,         # [n, d_model]
    router_w: jax.Array,  # [d_model, all experts]
    k: int,
    normalise: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Qwen3-MoE's router (``norm_topk_prob``): ``p = softmax(h . w)``
    in float32 over every expert, the ``k`` largest, gates ``p_e / sum
    of the k``. Returns (expert ids [n, k] int32, gates [n, k]
    float32)."""
    with jax.named_scope("mlp.router"):
        scores = jax.nn.softmax(jnp.einsum(
            "nd,de->ne", h.astype(jnp.float32),
            router_w.astype(jnp.float32),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        ), axis=-1)
        top, idx = jax.lax.top_k(scores, k)
        if normalise:
            top = top / jnp.sum(top, axis=-1, keepdims=True)
        return idx.astype(jnp.int32), top


def expert_block(n_tokens: int, k: int, n_experts: int) -> int:
    """Rows of one tile of ``sparse_experts``: about twice what an
    expert expects of ``n_tokens`` (so that most experts fill one
    tile), a power of two from 16 (a bf16 tile's rows) to 128."""
    expected = 2.0 * n_tokens * k / n_experts
    block = 16
    while block < expected and block < 128:
        block *= 2
    return block


def expert_tiles(counts: jax.Array, block: int) -> jax.Array:
    """Row tiles of ``block`` rows that assignment ``counts`` (any
    shape, one number a held expert) fill, an expert's last one ragged:
    what ``sparse_experts``' kernel runs (a call cut into row chunks
    runs up to a ragged tile more an expert and chunk). int32, one
    number."""
    return jnp.sum((counts + block - 1) // block, dtype=jnp.int32)


def sparse_experts(
    h: jax.Array,       # [n, d_model], compute dtype
    idx: jax.Array,     # [n, k] chosen experts, global ids
    gate: jax.Array,    # [n, k] float32
    w_gate: jax.Array,  # [held, d_model, f]
    w_up: jax.Array,    # [held, d_model, f]
    w_down: jax.Array,  # [held, f, d_model]
    held_lo: int,
    n_experts: int,
) -> Tuple[jax.Array, jax.Array]:
    """``sum_e gate_e * SwiGLU_e(h)`` over the chosen experts that are
    HELD here, ``held_lo <= e < held_lo + held``. Returns (the sum
    [n, d_model] float32, assignments per held expert [held] int32).

    The (token, expert) assignments are sorted by expert, the ones for
    experts held elsewhere last, and each held expert's run is padded
    to whole tiles of ``expert_block`` rows: a tile belongs to ONE
    expert. ``mlp.dispatch`` (XLA) makes the tables: each tile's
    expert and the rows of it that exist, the count of tiles that
    exist, each row's token and gate. ``mlp.experts`` is ONE kernel
    whose grid walks the tiles (``ops.moe_grouped_matmul
    .grouped_swiglu``): a step copies its tile's token rows out of
    ``h``, runs them through the tile's expert as stored and adds the
    gated rows onto their tokens' float32 sums, while the pipeline
    fetches the next tile's expert; steps past the last tile fetch and
    compute nothing (the tile count is bounded statically by
    ``tiles_bound``). Work and weight bytes follow the
    assignments: nothing is computed for an expert nobody chose, no
    token is dropped whatever the imbalance, and all shapes are
    static."""
    n, d = h.shape
    k = idx.shape[1]
    held = w_gate.shape[0]
    total = n * k
    most = grouped.rows_bound(d)
    if n > most:
        # a prompt whose float32 sums do not fit the fast memory: row
        # chunks, each a call of its own (the weights are read again)
        chunks = -(-n // most)
        size = -(-n // chunks)
        parts = [
            sparse_experts(h[at:at + size], idx[at:at + size],
                           gate[at:at + size], w_gate, w_up, w_down,
                           held_lo, n_experts)
            for at in range(0, n, size)]
        return (jnp.concatenate([part for part, _ in parts]),
                sum(counts for _, counts in parts))
    block = expert_block(n, k, n_experts)
    tiles_max = grouped.tiles_bound(n, k, held, block)
    with jax.named_scope("mlp.dispatch"):
        local = idx.reshape(total) - held_lo
        key = jnp.where((local >= 0) & (local < held), local, held)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        counts = jnp.sum(
            key[:, None] == jnp.arange(held)[None, :], axis=0,
            dtype=jnp.int32)
        starts = jnp.cumsum(counts) - counts
        tiles = (counts + block - 1) // block
        tile_end = jnp.cumsum(tiles)
        # the padded order: tile j is the ``nth`` of its expert e
        j = jnp.arange(tiles_max, dtype=jnp.int32)
        e = jnp.minimum(jnp.sum(
            j[:, None] >= tile_end[None, :], axis=1, dtype=jnp.int32),
            held - 1)
        nth = j - (tile_end[e] - tiles[e])
        # (0 past the last tile: there e is the last expert, nth past it)
        tile_live = jnp.clip(counts[e] - nth * block, 0, block)
        rank = (starts[e] + nth * block)[:, None] + jnp.arange(
            block, dtype=jnp.int32)[None, :]
        # a tile's rows past ``tile_live`` name some assignment: unread
        assignment = order[jnp.minimum(rank, total - 1)].reshape(-1)
        token = assignment // k
        weight = gate.reshape(total)[assignment][:, None]
        # (a row of its own tile each: the kernel copies single rows)
        rows = h.astype(jnp.float32)[:, None]
    with jax.named_scope("mlp.experts"):
        out = grouped.grouped_swiglu(
            rows, token, weight, e, tile_live, tile_end[-1:],
            w_gate, w_up, w_down, tile_rows=block, dtype=h.dtype)
    return out, counts
