"""Sharding rules for the flagship transformer.

Megatron-style tensor parallelism expressed as PartitionSpecs (XLA
inserts the collectives):

- attention: heads sharded over ``model`` — q/k/v projections split
  column-wise by head, the output projection row-wise, so one
  all-reduce per attention block rides ICI;
- SwiGLU: gate/up sharded column-wise on the hidden axis, down
  row-wise — one all-reduce per MLP block;
- embed/unembed: vocab sharded over ``model``;
- activations: batch over ``data`` (gradient psum over ``data`` is the
  data-parallel all-reduce).

These are *rules over the param pytree*, so new models get sharding by
writing specs, not by rewriting layers.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def param_sharding_rules(
    cfg: Optional[Any] = None, mesh: Optional[Mesh] = None
) -> Dict[str, Any]:
    """PartitionSpec pytree matching models.transformer.init_params.

    Under GQA, wk/wv's kv-head axis may be smaller than the model axis;
    when ``mesh`` is provided and kv_heads doesn't divide by it, those
    two (small) tensors replicate instead of crashing placement.
    """
    kv_spec = P(None, None, "model", None)
    if cfg is not None and mesh is not None:
        kv_heads = getattr(cfg, "kv_heads", None)
        model_size = mesh.shape.get("model", 1)
        if kv_heads is not None and kv_heads % model_size:
            kv_spec = P(None, None, None, None)
    layers: Dict[str, Any] = {
        # [L, d, heads, head_dim]: shard heads over model axis
        "wq": P(None, None, "model", None),
        "wk": kv_spec,
        "wv": kv_spec,
        # [L, heads, head_dim, d]: row-parallel output projection
        "wo": P(None, "model", None, None),
        "norm_attn": P(None, None),  # replicated
        "norm_mlp": P(None, None),
        # [L, d, ff]: column-parallel
        "w_gate": P(None, None, "model"),
        "w_up": P(None, None, "model"),
        # [L, ff, d]: row-parallel
        "w_down": P(None, "model", None),
    }
    return {
        "embed": P("model", None),  # vocab sharded
        "layers": layers,
        "norm_out": P(None),
        "unembed": P(None, "model"),
    }


def fsdp_sharding_rules(
    cfg: Any, mesh: Mesh, rules: Any = None
) -> Dict[str, Any]:
    """FSDP (ZeRO-3 analogue): the tensor-parallel rules with every
    large parameter *additionally* sharded over the ``data`` axis.

    On TPU this is purely a placement decision — under ``pjit`` XLA
    inserts the per-use all-gathers (and turns the grad all-reduce
    into reduce-scatter) so parameters, gradients, and optimizer
    moments all live 1/dp-sized per device, exactly the scaling-book
    "fully sharded" recipe. Reference analog: none (the reference is a
    supervisor); this is the workload half's answer to torch FSDP.

    Per leaf, ``data`` goes on the largest dimension that is not
    already mesh-sharded and divides by the data-axis size. The
    stacked-layer (scan) axis is never sharded: slicing a scan operand
    across devices would force a layer-N gather on every iteration of
    the compiled loop *and* break donation aliasing; sharding the
    feature dims instead gives XLA one clean all-gather per use site.
    """
    from ..models.transformer import init_params

    if rules is None:
        rules = param_sharding_rules(cfg, mesh)
    data_size = mesh.shape.get("data", 1)
    if data_size <= 1:
        return rules
    shapes = jax.eval_shape(
        lambda r: init_params(r, cfg), jax.random.PRNGKey(0)
    )

    def add_data(path, spec: P, leaf) -> P:
        shape = leaf.shape
        entries = list(spec) + [None] * (len(shape) - len(spec))
        if "data" in entries:
            return spec  # already data-sharded (idempotent re-apply)
        # skip the scan-stacked layer axis (dim 0 of "layers" leaves)
        start = 1 if any(
            getattr(k, "key", None) == "layers" for k in path
        ) else 0
        best = None
        for i in range(start, len(shape)):
            if entries[i] is None and shape[i] % data_size == 0:
                if best is None or shape[i] > shape[best]:
                    best = i
        if best is None:
            return spec
        entries[best] = "data"
        return P(*entries)

    return jax.tree_util.tree_map_with_path(
        add_data, rules, shapes,
        is_leaf=lambda x: isinstance(x, P),
    )


def batch_spec() -> P:
    """Activations/tokens: batch over the data axis."""
    return P("data", None)


def shard_params(
    params: Any, mesh: Mesh, cfg: Optional[Any] = None, rules: Any = None
) -> Any:
    """Place a param pytree onto the mesh per the rules."""
    if rules is None:
        rules = param_sharding_rules(cfg, mesh)
    return jax.tree_util.tree_map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)),
        params,
        rules,
    )
