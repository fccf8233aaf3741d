"""Weight-only int8 quantization with a fused dequant-matmul kernel.

Serving-oriented: weights stored int8 with per-output-channel float
scales (half the HBM footprint and half the weight-streaming traffic —
the bottleneck for small-batch decode). Activations stay bf16/f32.

Two implementations with identical numerics:

- ``int8_matmul`` (XLA): dequantize-and-multiply; XLA fuses the convert
  into the matmul operand read where it can.
- ``int8_matmul_pallas``: a pallas TPU kernel that tiles the GEMM,
  loads int8 weight blocks into VMEM, dequantizes there, and
  accumulates f32 over the K dimension — the int8→f32 upcast happens
  on-chip so HBM only ever sees int8 weights. Interpret mode covers it
  off-TPU (quantization pattern per the pallas guide; implemented
  fresh).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def quantize_int8_axes(
    w: jax.Array, axes: Tuple[int, ...]
) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 over the given (input) axes; scales come back
    keepdims-shaped so dequant is a single broadcast multiply. The one
    quantization formula in the codebase — model-level quantization
    (models/quantized.py) calls this too."""
    wf = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=axes, keepdims=True)
    scales = jnp.maximum(absmax, 1e-8) / 127.0
    w_q = jnp.clip(jnp.round(wf / scales), -127, 127).astype(jnp.int8)
    return w_q, scales


def quantize_int8(w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-output-channel symmetric int8 quantization.

    w: [in_features, out_features] float -> (w_q int8 same shape,
    scales f32 [out_features]); w ≈ w_q * scales.
    """
    w_q, scales = quantize_int8_axes(w, (0,))
    return w_q, scales[0, :]


def int8_matmul(
    x: jax.Array, w_q: jax.Array, scales: jax.Array
) -> jax.Array:
    """XLA reference path: x [m, k] @ (w_q [k, n] * scales [n])."""
    wf = w_q.astype(jnp.float32) * scales[None, :]
    return jnp.einsum(
        "mk,kn->mn", x.astype(jnp.float32), wf,
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)


def _int8_matmul_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, n_k: int):
    """One (m_tile, n_tile) program; iterate K blocks via the grid's
    innermost dimension, accumulating into a VMEM scratch."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x_blk = x_ref[:].astype(jnp.float32)          # [bm, bk]
    w_blk = w_ref[:].astype(jnp.float32)          # [bk, bn] (int8 -> f32)
    acc_ref[:] += jnp.dot(
        x_blk, w_blk, preferred_element_type=jnp.float32
    )

    @pl.when(ki == n_k - 1)
    def _finish():
        o_ref[:] = (acc_ref[:] * s_ref[0, :].astype(jnp.float32)[None, :]).astype(
            o_ref.dtype
        )


def int8_matmul_padded(
    x: jax.Array,
    w_q: jax.Array,
    scales: jax.Array,
    block_m: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``int8_matmul_pallas`` for arbitrary row counts: decode
    microbatches are far below the 128-row tile, so rows pad up to one
    tile and slice back — the padding rows are dead weight the MXU
    doesn't notice in the weight-streaming-bound regime this kernel
    serves."""
    m = x.shape[0]
    pad = (-m) % block_m
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    out = int8_matmul_pallas(
        x, w_q, scales, block_m=block_m, interpret=interpret
    )
    return out[:m] if pad else out


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def int8_matmul_pallas(
    x: jax.Array,
    w_q: jax.Array,
    scales: jax.Array,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused dequant GEMM: x [m, k] @ dequant(w_q [k, n]) -> [m, n].

    Dimensions must divide by their block sizes (pad upstream).
    """
    m, k = x.shape
    k2, n = w_q.shape
    if k != k2:
        raise ValueError(f"inner dims disagree: {k} vs {k2}")
    if m % block_m or n % block_n or k % block_k:
        raise ValueError(
            f"shape ({m},{k})x({k2},{n}) not divisible by blocks "
            f"({block_m},{block_k},{block_n})"
        )
    if interpret is None:
        # only the CPU test backend interprets; see ops/flash.py
        interpret = jax.default_backend() == "cpu"
    n_k = k // block_k
    kernel = functools.partial(_int8_matmul_kernel, n_k=n_k)
    return pl.pallas_call(
        kernel,
        name="int8_matmul",  # stable: read from profiler traces
        grid=(m // block_m, n // block_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, ki: (i, ki)),
            pl.BlockSpec((block_k, block_n), lambda i, j, ki: (ki, j)),
            # scales ride as a [1, block_n] tile (TPU tiles are >= 2-D)
            pl.BlockSpec((1, block_n), lambda i, j, ki: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, ki: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_m, block_n), jnp.float32)
        ],
        interpret=interpret,
    )(x, w_q, scales[None, :])
