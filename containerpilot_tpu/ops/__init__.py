"""Op library for the TPU workload: attention five ways — XLA einsum,
pallas flash (fwd+bwd, differentiable), memory-efficient XLA training
fallback (custom VJP), ring/context-parallel, and a pallas decode read
over rows of ragged length (one query a row, each row's keys only as
far as it is live); and the routed experts' grouped SwiGLU (one pallas
kernel over a layer's row tiles, each touched expert read once)."""
from .attention import causal_attention
from .flash import flash_attention, flash_attention_forward
from .flash_training import memory_efficient_attention
from .moe_grouped_matmul import grouped_swiglu
from .quant import (
    int8_matmul,
    int8_matmul_padded,
    int8_matmul_pallas,
    quantize_int8,
)
from .ragged_decode import ragged_decode_attention
from .ring_attention import ring_attention

__all__ = [
    "causal_attention",
    "flash_attention",
    "flash_attention_forward",
    "memory_efficient_attention",
    "ring_attention",
    "ragged_decode_attention",
    "grouped_swiglu",
    "quantize_int8",
    "int8_matmul",
    "int8_matmul_pallas",
    "int8_matmul_padded",
]
