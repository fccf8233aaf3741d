"""Model-level weight-only int8: quantize a trained param pytree and
run the same forward/decode code on it.

``quantize_model_params`` converts every large matmul weight (attention
projections, MLP, embed/unembed) to int8 with broadcast-ready
per-output-channel scales; norms stay float. Two execution paths:

- **dense dequant** (``maybe_dequant_layer``): rebuild one layer's
  bf16 weights inside the scan body — quantized and full-precision
  params flow through identical math. Used for training-size token
  counts and any non-tile-aligned model.
- **fused int8** (``fused_qkv``/``fused_attn_out``/``fused_mlp``):
  the decode step's projections run through ops/quant.py's pallas
  dequant-GEMM, so weights stream from HBM as int8 and upcast in
  VMEM — half the weight traffic in the weight-streaming-bound decode
  regime. Selected by ``can_fuse_int8`` (models/decode.py wires it).

Resident weight memory shrinks ~4x either way (int8 vs f32 masters).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

# layers-dict keys to quantize -> axes reduced for the scale (the input
# axes of the matmul; remaining axes are output channels). Leading axis
# 0 is the stacked-layer axis, never reduced.
_LAYER_QUANT_AXES: Dict[str, Tuple[int, ...]] = {
    "wq": (1,),        # [L, d, h, hd]: reduce d
    "wk": (1,),
    "wv": (1,),
    "wo": (1, 2),      # [L, h, hd, d]: reduce h, hd
    "w_gate": (1,),    # [L, d, f]
    "w_up": (1,),
    "w_down": (1,),    # [L, f, d]
}

_TOP_QUANT_AXES: Dict[str, Tuple[int, ...]] = {
    "embed": (1,),     # [vocab, d]: reduce d -> scale per vocab row
    "unembed": (0,),   # [d, vocab]: reduce d -> scale per vocab col
}


def _quantize_tensor(
    w: jax.Array, axes: Tuple[int, ...]
) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 with scales keepdims-shaped for one-multiply
    dequant (and clean slicing through the stacked-layer axis)."""
    from ..ops.quant import quantize_int8_axes

    return quantize_int8_axes(w, axes)


def quantize_model_params(params: Any) -> Any:
    """Quantize a transformer param pytree in place-shape: each listed
    weight W becomes W_q (int8) + W_s (f32 scales); others unchanged."""
    out = dict(params)
    layers = dict(params["layers"])
    for key, axes in _LAYER_QUANT_AXES.items():
        if key in layers:
            w_q, scales = _quantize_tensor(layers.pop(key), axes)
            layers[key + "_q"] = w_q
            layers[key + "_s"] = scales
    out["layers"] = layers
    for key, axes in _TOP_QUANT_AXES.items():
        if key in out:
            w_q, scales = _quantize_tensor(out.pop(key), axes)
            out[key + "_q"] = w_q
            out[key + "_s"] = scales
    return out


def is_quantized(params: Any) -> bool:
    return "wq_q" in params.get("layers", {}) or "embed_q" in params


def maybe_dequant_layer(
    layer_params: Dict[str, jax.Array], dtype: Any
) -> Dict[str, jax.Array]:
    """Rebuild a dense layer-params dict from a quantized one (no-op
    for full-precision input). Runs inside the layer scan body, so only
    one layer's weights are ever dense at a time."""
    if "wq_q" not in layer_params:
        return layer_params
    dense = dict(layer_params)
    for key in _LAYER_QUANT_AXES:
        q = dense.pop(key + "_q", None)
        s = dense.pop(key + "_s", None)
        if q is not None:
            dense[key] = (q.astype(jnp.float32) * s).astype(dtype)
    return dense


def embed_lookup(params: Any, tokens: jax.Array, dtype: Any) -> jax.Array:
    """Embedding gather that dequantizes only the gathered rows when
    the table is stored int8."""
    with jax.named_scope("embed"):
        if "embed" in params:
            return params["embed"].astype(dtype)[tokens]
        rows = params["embed_q"][tokens].astype(jnp.float32)
        scales = params["embed_s"][tokens][..., 0][..., None]  # [., 1]
        return (rows * scales).astype(dtype)


def maybe_dequant_top(params: Any, key: str, dtype: Any) -> jax.Array:
    """Fetch a top-level tensor, dequantizing if stored int8."""
    if key in params:
        return params[key].astype(dtype)
    q = params[key + "_q"]
    s = params[key + "_s"]
    return (q.astype(jnp.float32) * s).astype(dtype)


def param_bytes(params: Any) -> int:
    return sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(params)
    )


def resident_weights(params: Any) -> Dict[str, Any]:
    """``/v1/model`` ``weights``: the form the parameters are resident
    in. ``dtype`` is the one that holds most of the tree's bytes
    (``int8`` under --int8, whose scales and norms stay float32)."""
    by_dtype: Dict[str, int] = {}
    for leaf in jax.tree_util.tree_leaves(params):
        name = jnp.dtype(leaf.dtype).name
        by_dtype[name] = by_dtype.get(name, 0) + leaf.nbytes
    return {
        "dtype": max(by_dtype, key=by_dtype.get),
        "bytes": sum(by_dtype.values()),  # = param_bytes(params)
    }


# ---------------------------------------------------------------------------
# fused int8 serving path: projections through the pallas dequant-GEMM
# ---------------------------------------------------------------------------

# beyond this many rows (batch*seq tokens) the GEMMs are MXU-bound and
# bf16 wins; below it they are weight-streaming-bound and reading int8
# halves the HBM traffic — the decode regime
FUSED_MAX_ROWS = 256

_GEMM_TILE = 128


def can_fuse_int8(
    layers: Dict[str, jax.Array], cfg: Any, rows: int
) -> bool:
    """True when the decode-step projections can run through the fused
    int8 pallas GEMM: quantized weights, a
    weight-streaming-bound row count, and tile-aligned dims."""
    if "wq_q" not in layers or "w_gate_q" not in layers:
        return False
    if rows > FUSED_MAX_ROWS:
        return False
    d = cfg.d_model
    kv_out = cfg.kv_heads * cfg.head_dim
    return (
        d % _GEMM_TILE == 0
        and kv_out % _GEMM_TILE == 0
        and cfg.d_ff % _GEMM_TILE == 0
    )


def _fused_proj(
    h2d: jax.Array, layer_params: Dict[str, jax.Array], key: str
) -> jax.Array:
    """[rows, k] @ dequant(W[key]) via the pallas kernel; W's non-layer
    leading axes flatten to the GEMM's (k, n)."""
    from ..ops.quant import int8_matmul_padded

    w_q = layer_params[key + "_q"]
    k = h2d.shape[-1]
    return int8_matmul_padded(
        h2d,
        w_q.reshape(k, -1),
        layer_params[key + "_s"].reshape(-1),
    )


def fused_qkv(
    x: jax.Array, layer_params: Dict[str, jax.Array], cfg: Any, offset: Any
):
    """The _qkv contract (pre-norm, projections, RoPE) with the
    projections running int8-fused — weights stream from HBM as int8
    and dequantize in VMEM (ops/quant.py)."""
    from .transformer import _rms_norm, _rope

    b, s, d = x.shape
    hd = cfg.head_dim
    with jax.named_scope("attn"), jax.named_scope("attn.qkv"):
        h = _rms_norm(x, layer_params["norm_attn"]).reshape(b * s, d)
        q = _fused_proj(h, layer_params, "wq").reshape(
            b, s, cfg.n_heads, hd)
        k = _fused_proj(h, layer_params, "wk").reshape(
            b, s, cfg.kv_heads, hd)
        v = _fused_proj(h, layer_params, "wv").reshape(
            b, s, cfg.kv_heads, hd)
    with jax.named_scope("attn"), jax.named_scope("attn.rope"):
        q = _rope(q, cfg.rope_theta, offset)
        k = _rope(k, cfg.rope_theta, offset)
    return q, k, v


def fused_attn_out(
    x: jax.Array,
    attn: jax.Array,
    layer_params: Dict[str, jax.Array],
    cfg: Any,
) -> jax.Array:
    """Output projection + residual, int8-fused (wo is [h, hd, d]:
    the h*hd axes flatten to the GEMM's k)."""
    b, s, h, hd = attn.shape
    with jax.named_scope("attn"), jax.named_scope("attn.out"):
        out = _fused_proj(
            attn.reshape(b * s, h * hd), layer_params, "wo"
        ).reshape(b, s, -1)
        return x + out


def fused_mlp(
    x: jax.Array, layer_params: Dict[str, jax.Array], cfg: Any
) -> jax.Array:
    """SwiGLU block + residual with all three GEMMs int8-fused."""
    from .transformer import _rms_norm

    b, s, d = x.shape
    with jax.named_scope("mlp"):
        h = _rms_norm(x, layer_params["norm_mlp"]).reshape(b * s, d)
        gate = _fused_proj(h, layer_params, "w_gate").astype(jnp.float32)
        up = _fused_proj(h, layer_params, "w_up").astype(jnp.float32)
        act = (jax.nn.silu(gate) * up).astype(cfg.dtype)
        down = _fused_proj(act, layer_params, "w_down").reshape(b, s, d)
        return x + down


# ---------------------------------------------------------------------------
# step-program face: int8 weights under the slot engine
# ---------------------------------------------------------------------------

# Defined lazily (PEP 562 module __getattr__): transformer.py imports
# this module at its top, and the step-program base lives in
# stepprog.py which imports transformer — an eager subclass here
# would close that cycle against a half-initialized module.
_QUANTIZED_PROGRAM = None


def _quantized_program_class():
    global _QUANTIZED_PROGRAM
    if _QUANTIZED_PROGRAM is not None:
        return _QUANTIZED_PROGRAM
    from .stepprog import PlainStepProgram

    class QuantizedStepProgram(PlainStepProgram):
        """Weight-only-int8 step program for the slot engine
        (models/stepprog.py's protocol): the SAME chunk and
        fused-window device programs as the plain transformer — the
        forward dequantizes one layer at a time inside its scan body
        (``maybe_dequant_layer``) or runs the fused int8 GEMMs
        (``can_fuse_int8``), so quantized weights compose with
        slots/prefix-cache/kvtier/pod parity structurally rather than
        by accident. This class makes the composition EXPLICIT: it
        validates the params really are quantized at construction (a
        mis-wired full-precision pytree fails loudly at startup, not
        as 4x the expected HBM at first decode) and is what
        ``make_step_program`` returns for an int8 pytree. Everything
        else is PlainStepProgram — deliberately: one decode
        implementation, two weight layouts."""

        def __init__(self, cfg, params, max_len, slots, chunk,
                     rounds=1, out_sharding=None):
            if not is_quantized(params):
                raise ValueError(
                    "QuantizedStepProgram needs "
                    "quantize_model_params output (no *_q leaves "
                    "found)"
                )
            super().__init__(
                cfg, params, max_len, slots, chunk,
                rounds=rounds, out_sharding=out_sharding,
            )

    _QUANTIZED_PROGRAM = QuantizedStepProgram
    return _QUANTIZED_PROGRAM


def __getattr__(name: str):
    if name == "QuantizedStepProgram":
        return _quantized_program_class()
    raise AttributeError(name)
