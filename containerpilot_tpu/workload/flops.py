"""Model-FLOPs accounting for the trainer's reported MFU.

PaLM-style: a training step costs ~6 FLOPs per parameter per token
(fwd matmul + 2x bwd) plus the attention score/value matmuls, which
the 6N term misses because they scale with sequence length, not
parameter count: 12 * L * d_model * span per token (fwd+bwd), where
``span`` is the AVERAGE number of keys a query actually attends to —
(seq+1)/2 for full causal (the halving the flash kernels realize by
skipping the dead half), ~window for sliding-window. MFU = achieved
FLOP/s over the chip's published bf16 peak — the honest utilization
number, not a hardware counter; billing the skipped causal half would
flatter MFU ~2x on exactly the configs where the kernels skip it.
"""
from __future__ import annotations

from typing import Any

# bf16 peak FLOP/s by TPU generation (public spec sheets), matched by
# substring of jax Device.device_kind
PEAK_BF16 = [
    ("v6", 918e12),   # Trillium / v6e
    ("v5p", 459e12),
    ("v5", 197e12),   # v5e / "TPU v5 lite"
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 46e12),
]


def peak_flops(device_kind: str) -> float:
    """bf16 peak FLOP/s of one chip of ``device_kind``. A kind the
    table does not know is an error, never an assumed peak: an MFU
    against a guessed denominator is a made-up number."""
    kind = device_kind.lower()
    for key, peak in PEAK_BF16:
        if key in kind:
            return peak
    raise ValueError(
        f"no published bf16 peak for device kind {device_kind!r}; "
        "add it to workload/flops.py PEAK_BF16 with its source"
    )


def count_params(params: Any) -> int:
    import jax

    return sum(p.size for p in jax.tree_util.tree_leaves(params))


def train_flops_per_token(
    cfg: Any, n_params: int, seq: int, n_frozen: int = 0
) -> float:
    """FLOPs one training step spends per token.

    - sliding window: the attention term scales with
      min(seq, window) — the kernels skip out-of-window blocks;
    - ``n_frozen`` (LoRA base): frozen params do forward + grad
      propagation but no weight-gradient matmul — 4 FLOPs/param
      instead of 6. Without these corrections the MFU gauge reads a
      fictitious number for exactly those configs.

    The attention span is the exact mean over positions of
    min(pos+1, window): sum_{p<s} min(p+1, w) / s = w - w*(w-1)/(2s)
    with w = min(seq, window or seq). Full causal (w == s) reduces to
    (s+1)/2 — the causal halving the kernels actually realize.
    """
    w = float(seq if cfg.window <= 0 else min(seq, cfg.window))
    attn_span = w - w * (w - 1.0) / (2.0 * seq)
    frozen = min(float(n_frozen), float(n_params))
    return (
        6.0 * (n_params - frozen)
        + 4.0 * frozen
        + 12.0 * cfg.n_layers * cfg.d_model * attn_span
    )


def train_step_flops(cfg: Any, n_params: int, batch: int,
                     seq: int) -> float:
    return train_flops_per_token(cfg, n_params, seq) * batch * seq
