"""Mixture-of-Experts: switch-style routing with expert parallelism.

TPU-first formulation: top-1 (switch) routing expressed entirely as
one-hot einsums — dispatch and combine are batched matmuls the MXU
eats, no gathers/scatters, fully static shapes. Routing is per-token
and drop-free (see moe_layer). Expert weights carry a leading expert
axis sharded over the mesh's ``model`` axis (expert parallelism); XLA
inserts the all-to-alls at the dispatch and combine einsums.

Aux load-balancing loss is the standard switch formulation: E *
sum_e(fraction_of_tokens_e * mean_router_prob_e), minimized at uniform
routing.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def _route(x: jax.Array, router_w: jax.Array):
    """Top-1 switch routing shared by the drop-free and capacity
    layers: returns (probs, gate, onehot, aux_loss)."""
    n_experts = router_w.shape[-1]
    # callers sit under the ``mlp`` scope (transformer._ffn)
    with jax.named_scope("mlp.router"):
        router_logits = jnp.einsum(
            "bsd,de->bse", x.astype(jnp.float32),
            router_w.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        probs = jax.nn.softmax(router_logits, axis=-1)  # [b,s,E]
        expert_idx = jnp.argmax(probs, axis=-1)  # [b,s]
        gate = jnp.max(probs, axis=-1)  # [b,s]
        onehot = jax.nn.one_hot(expert_idx, n_experts, dtype=jnp.float32)
        fraction = jnp.mean(onehot, axis=(0, 1))
        router_mean = jnp.mean(probs, axis=(0, 1))
        aux_loss = n_experts * jnp.sum(fraction * router_mean)
    return probs, gate, onehot, aux_loss


def moe_layer(
    x: jax.Array,
    router_w: jax.Array,  # [d_model, n_experts]
    w_in: jax.Array,      # [n_experts, d_model, d_ff]
    w_out: jax.Array,     # [n_experts, d_ff, d_model]
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output [b,s,d], aux_loss scalar). x in compute dtype.

    Routing is per-token and drop-free (no capacity bound), so the
    result for any token depends only on that token's features — which
    is what makes incremental decoding bit-identical to the full
    forward. The cost is dense dispatch (each expert processes the full
    masked sequence). For bounded expert compute during training use
    ``moe_layer_capacity``; decoding always uses this drop-free layer
    (models/decode.py rejects capacity configs).
    """
    _probs, gate, onehot, aux_loss = _route(x, router_w)

    # note: no preferred_element_type=f32 on the batched expert einsums
    # — the TPU MXU accumulates bf16 inputs in f32 internally, and the
    # CPU backend's batched dot lacks the bf16->f32 widening variant
    dt = x.dtype
    with jax.named_scope("mlp.experts"):
        expert_in = jnp.einsum("bse,bsd->besd", onehot.astype(dt), x)
        hidden = jnp.einsum("besd,edf->besf", expert_in, w_in.astype(dt))
        hidden = jax.nn.gelu(hidden.astype(jnp.float32)).astype(dt)
        expert_out = jnp.einsum(
            "besf,efd->besd", hidden, w_out.astype(dt)
        )
        combine = (onehot * gate[..., None]).astype(dt)
        out = jnp.einsum("bse,besd->bsd", combine, expert_out)
    return out, aux_loss


def moe_layer_capacity(
    x: jax.Array,
    router_w: jax.Array,  # [d_model, n_experts]
    w_in: jax.Array,      # [n_experts, d_model, d_ff]
    w_out: jax.Array,     # [n_experts, d_ff, d_model]
    capacity_factor: float,
) -> Tuple[jax.Array, jax.Array]:
    """Capacity-bounded switch MoE: each expert processes at most
    ``ceil(capacity_factor * s / E)`` tokens per batch row; overflow
    tokens drop to the residual (standard switch training).

    Dispatch is **sparse**: every token knows its queue position within
    its expert (a cumsum over the routing one-hot), so tokens scatter
    straight into static-shape ``[E, capacity, d]`` blocks and results
    gather back by the same slot index. Expert compute AND
    dispatch/combine are O(E*capacity*d) / O(s*d) — no ``[b,s,E,C]``
    one-hot dispatch tensor, no O(s*E*C*d) dispatch einsums. Shapes are
    fully static, so XLA tiles the expert GEMMs on the MXU and (with
    the expert axis sharded over ``model``) inserts all-to-alls at the
    scatter/gather boundaries.

    Inference must use the drop-free ``moe_layer`` (capacity depends on
    sequence length, so this routing cannot match incremental decode —
    models/decode.py enforces that).
    """
    import math

    b, s, d = x.shape
    n_experts = router_w.shape[-1]
    capacity = max(1, math.ceil(capacity_factor * s / n_experts))

    probs, gate, onehot, aux_loss = _route(x, router_w)
    expert_idx = jnp.argmax(probs, axis=-1)  # [b,s]

    # queue position of each token within its expert, per batch row
    pos = jnp.sum(
        (jnp.cumsum(onehot, axis=1) - 1.0) * onehot, axis=-1
    ).astype(jnp.int32)  # [b,s]
    keep = pos < capacity
    # flat slot in the [E*C] dispatch buffer; overflow tokens get an
    # out-of-range slot, which the scatter drops and the gather fills 0
    slot = jnp.where(keep, expert_idx * capacity + pos, n_experts * capacity)

    dt = x.dtype

    def dispatch_row(x_row: jax.Array, slot_row: jax.Array) -> jax.Array:
        buf = jnp.zeros((n_experts * capacity, d), dt)
        return buf.at[slot_row].set(x_row, mode="drop")

    expert_in = jax.vmap(dispatch_row)(x, slot).reshape(
        b, n_experts, capacity, d
    )
    hidden = jnp.einsum("becd,edf->becf", expert_in, w_in.astype(dt))
    hidden = jax.nn.gelu(hidden.astype(jnp.float32)).astype(dt)
    expert_out = jnp.einsum("becf,efd->becd", hidden, w_out.astype(dt))

    def gather_row(flat_row: jax.Array, slot_row: jax.Array) -> jax.Array:
        return jnp.take(
            flat_row, slot_row, axis=0, mode="fill", fill_value=0
        )

    out = jax.vmap(gather_row)(
        expert_out.reshape(b, n_experts * capacity, d), slot
    )
    out = out * (gate * keep).astype(dt)[..., None]
    return out, aux_loss
