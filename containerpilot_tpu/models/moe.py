"""Routed experts for the serving families of ``models/mla_moe.py``
and ``models/block_diffusion.py``.

``route_topk``: sigmoid scores over ALL experts in float32, top k,
renormalise, scale. ``route_softmax``: the same with a softmax over all
experts for the scores and no scale. ``sparse_experts``: the part of
the result that the experts HELD here give: assignments sorted by
expert, cut into blocks of one expert each, one grouped SwiGLU per
block that exists; no capacity, no token dropped, an expert nobody
chose is never read.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


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
    """Rows of one block of ``sparse_experts``: about twice what an
    expert expects of ``n_tokens`` (so that most experts fill one
    block), a power of two from 16 (a bf16 tile's rows) to 128."""
    expected = 2.0 * n_tokens * k / n_experts
    block = 16
    while block < expected and block < 128:
        block *= 2
    return block


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

    The (token, expert) assignments are sorted by expert, the ones
    for experts held elsewhere last. Each held expert's run is cut
    into blocks of ``expert_block`` rows; a loop over the blocks THAT
    EXIST (a dynamic trip count) gathers a block's token rows, runs
    them through that one expert's three matrices and adds the gated
    result to its tokens' rows. Work and weight bytes follow the
    assignments: nothing is computed for an expert nobody chose, no
    token is dropped whatever the imbalance, and all shapes are
    static."""
    n, d = h.shape
    k = idx.shape[1]
    held = w_gate.shape[0]
    total = n * k
    block = expert_block(n, k, n_experts)
    dt = h.dtype
    with jax.named_scope("mlp.dispatch"):
        local = idx.reshape(total) - held_lo
        key = jnp.where((local >= 0) & (local < held), local, held)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        counts = jnp.sum(
            key[:, None] == jnp.arange(held)[None, :], axis=0,
            dtype=jnp.int32)
        starts = jnp.cumsum(counts) - counts
        blocks = (counts + block - 1) // block
        block_end = jnp.cumsum(blocks)
        gates = gate.reshape(total)

    def body(j, out):
        with jax.named_scope("mlp.dispatch"):
            e = jnp.sum(j >= block_end).astype(jnp.int32)
            first = starts[e] + (j - (block_end[e] - blocks[e])) * block
            offs = first + jnp.arange(block)
            live = offs < starts[e] + counts[e]
            assignment = order[jnp.minimum(offs, total - 1)]
            token = assignment // k
            rows = h[token]
        with jax.named_scope("mlp.experts"):
            def pick(w):
                return jax.lax.dynamic_index_in_dim(w, e, 0, keepdims=False)

            up = jnp.einsum("bd,df->bf", rows, pick(w_up).astype(dt),
                            preferred_element_type=jnp.float32)
            act = jax.nn.silu(jnp.einsum(
                "bd,df->bf", rows, pick(w_gate).astype(dt),
                preferred_element_type=jnp.float32)) * up
            y = jnp.einsum("bf,fd->bd", act.astype(dt),
                           pick(w_down).astype(dt),
                           preferred_element_type=jnp.float32)
        with jax.named_scope("mlp.combine"):
            weight = jnp.where(live, gates[assignment], 0.0)
            return out.at[token].add(y * weight[:, None])

    out = jax.lax.fori_loop(
        0, block_end[-1], body, jnp.zeros((n, d), jnp.float32))
    return out, counts
