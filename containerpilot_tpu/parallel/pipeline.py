"""Pipeline parallelism: GPipe-style microbatching over a ``pipe`` mesh
axis.

The scan-over-stacked-layers model design makes stage partitioning
natural: the layer-stacked parameter arrays ``[L, ...]`` shard their
leading axis over ``pipe`` (each device holds L/S contiguous layers),
and microbatches stream through the stages with ``lax.ppermute``
activation handoffs — the classic SPMD collective-permute pipeline
(public recipe; see the scaling-book pattern, implemented fresh here).

Schedule: S stages, M microbatches, M + S - 1 ticks. At tick t stage 0
ingests microbatch ``min(t, M-1)`` (masked once t >= M), every stage
applies its local layers, the result permutes to the next stage, and
the last stage banks its output for microbatch ``t - S + 1``. The
pipeline bubble is the standard (S-1)/(M+S-1); raise ``n_microbatches``
to amortize it.

Embedding/unembedding run replicated outside the pipelined stack, and
the final activations are broadcast off the last stage with a masked
psum, so the loss (and grads — ppermute is differentiable) compose with
data parallelism on an outer ``data`` axis.

Logits are numerically equivalent to the unpipelined forward — same
math, tolerance-level float differences from microbatched reduction
tiling.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..models.transformer import (
    Params,
    TransformerConfig,
    _layer,
    _rms_norm,
    next_token_loss,
)
from ..ops.ring_attention import shard_map  # version-compat wrapper


def _stage_fn(
    x: jax.Array, local_layers: Any, cfg: TransformerConfig
) -> jax.Array:
    """Apply this stage's layer slice: scan over local layers."""
    x, _ = lax.scan(
        lambda x, layer_params: (_layer(x, layer_params, cfg), None),
        x, local_layers,
    )
    return x


def _pipeline_body(
    layers: Any,
    x_mb: jax.Array,  # [M, mb, s, d] microbatched embeddings (replicated)
    *,
    cfg: TransformerConfig,
    axis_name: str,
    n_stages: int,
    n_microbatches: int,
):
    """Per-device body under shard_map; ``layers`` leaves are the local
    [L/S, ...] slices."""
    stage = lax.axis_index(axis_name)
    _, mb, s, d = x_mb.shape
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    ticks = n_microbatches + n_stages - 1

    def tick(t, carry):
        acts, outputs = carry
        # stage 0 ingests microbatch t (clamped; masked when t >= M)
        feed_idx = jnp.clip(t, 0, n_microbatches - 1)
        fresh = lax.dynamic_index_in_dim(x_mb, feed_idx, 0, keepdims=False)
        my_in = jnp.where(stage == 0, fresh, acts)
        y = _stage_fn(my_in, layers, cfg)
        # the last stage banks microbatch t-S+1's result once it's real
        out_idx = jnp.clip(t - (n_stages - 1), 0, n_microbatches - 1)
        is_valid = (t >= n_stages - 1) & (stage == n_stages - 1)
        banked = lax.dynamic_update_index_in_dim(
            outputs, y.astype(outputs.dtype), out_idx, 0
        )
        outputs = jnp.where(is_valid, banked, outputs)
        acts = lax.ppermute(y, axis_name, perm)
        return acts, outputs

    acts0 = jnp.zeros((mb, s, d), cfg.dtype)
    outputs0 = jnp.zeros((n_microbatches, mb, s, d), cfg.dtype)
    _acts, outputs = lax.fori_loop(0, ticks, tick, (acts0, outputs0))
    # broadcast the last stage's results to every device
    return lax.psum(
        jnp.where(stage == n_stages - 1, outputs, 0.0).astype(jnp.float32),
        axis_name,
    ).astype(cfg.dtype)


def pipeline_forward(
    params: Params,
    tokens: jax.Array,
    cfg: TransformerConfig,
    mesh: Mesh,
    n_microbatches: int = 4,
    axis_name: str = "pipe",
):
    """Forward through pipeline-sharded layers.

    tokens: [batch, seq]; batch must divide by n_microbatches; n_layers
    by the pipe axis size. Returns logits like forward.
    """
    if axis_name not in mesh.axis_names:
        raise ValueError(f"mesh has no {axis_name!r} axis: {mesh.axis_names}")
    n_stages = mesh.shape[axis_name]
    if cfg.n_layers % n_stages:
        raise ValueError(
            f"n_layers {cfg.n_layers} not divisible by {n_stages} stages"
        )
    b, s = tokens.shape
    if b % n_microbatches:
        raise ValueError(
            f"batch {b} not divisible by {n_microbatches} microbatches"
        )
    mb = b // n_microbatches
    data_size = mesh.shape.get("data", 1)
    if mb % data_size:
        raise ValueError(
            f"microbatch size {mb} not divisible by data axis {data_size}"
        )
    layer_specs = jax.tree_util.tree_map(
        lambda _: P(axis_name), params["layers"]
    )
    # compose with data parallelism: microbatch contents shard over an
    # outer "data" axis (everything in the body is per-sample)
    data_axis = "data" if "data" in mesh.axis_names else None
    # compose with tensor parallelism: any remaining mesh axes (e.g.
    # "model") stay AUTO inside the manual region, so XLA partitions
    # each stage's layer math over them and inserts the tp collectives
    # — pp x tp without hand-writing the tp collectives here. Size-1
    # axes need no partitioning at all and are kept manual(-and-
    # unused), so a trivial model axis doesn't force the auto-region
    # restrictions (no pallas flash, f32-on-CPU) onto plain dp x pp.
    auto = {
        a
        for a in mesh.axis_names
        if a != axis_name and a != data_axis and mesh.shape[a] > 1
    }
    if auto:
        import dataclasses

        if cfg.attention_fn is None and cfg.flash_min_seq:
            # pallas calls can't be partitioned by the AUTO axes inside
            # this manual region, so the auto-selected flash path must
            # stay off here: the einsum attention partitions fine over
            # the auto model axis. (pp x tp flash needs manual-tp
            # kernels — future work.)
            cfg = dataclasses.replace(cfg, flash_min_seq=0)
        if jax.default_backend() == "cpu" and cfg.dtype == jnp.bfloat16:
            # XLA CPU's AllReducePromotion pass CHECK-crashes cloning
            # the bf16 all-reduces that auto partitioning inserts
            # around this manual region; run the whole pipelined
            # forward in f32 on the CPU test/dryrun backend (TPU is
            # unaffected)
            cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    x = params["embed"].astype(cfg.dtype)[tokens]
    x_mb = x.reshape(n_microbatches, mb, s, -1)
    x_spec = P(None, data_axis, None, None)
    fn = shard_map(
        functools.partial(
            _pipeline_body,
            cfg=cfg,
            axis_name=axis_name,
            n_stages=n_stages,
            n_microbatches=n_microbatches,
        ),
        mesh=mesh,
        in_specs=(layer_specs, x_spec),
        out_specs=x_spec,
        auto=auto or None,
    )
    outputs = fn(params["layers"], x_mb)
    x = outputs.reshape(b, s, -1)
    x = _rms_norm(x, params["norm_out"])
    return jnp.einsum(
        "bsd,dv->bsv", x, params["unembed"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    )


def pipeline_loss_fn(
    params: Params,
    tokens: jax.Array,
    cfg: TransformerConfig,
    mesh: Mesh,
    n_microbatches: int = 4,
) -> jax.Array:
    """Next-token CE through the pipeline (drop-in for loss_fn)."""
    logits = pipeline_forward(
        params, tokens[:, :-1], cfg, mesh, n_microbatches
    )
    return next_token_loss(logits, tokens)


def pipeline_sharding_rules(cfg: Any = None, mesh: Mesh = None) -> Any:
    """Param specs for a ("data", "pipe"[, "model"]) mesh: layer stacks
    shard their leading layer axis over ``pipe`` while KEEPING the
    tensor-parallel ``model`` shardings inside each stage (pp x tp).
    Without a model axis on the mesh, the in-stage specs replicate."""
    from .sharding import param_sharding_rules

    rules = param_sharding_rules(cfg, mesh)
    has_model = mesh is not None and "model" in mesh.axis_names

    def stage_spec(spec: P) -> P:
        rest = tuple(spec)[1:]  # the leading dim is the layer axis
        if not has_model:
            rest = tuple(None if a == "model" else a for a in rest)
        return P("pipe", *rest)

    rules["layers"] = jax.tree_util.tree_map(
        stage_spec, rules["layers"]
    )
    if not has_model:
        rules["embed"] = P(None, None)
        rules["unembed"] = P(None, None)
    return rules
