"""Flash-attention block tuning: measured, per-platform, persistent.

The pallas kernels' performance hinges on (block_q, block_k) — the
right choice varies with sequence length and mode (a training step
runs fwd+bwd through one custom_vjp call, so blocks are chosen per
call, not per direction). Hardcoded 128/128 left the 2k-4k training
range losing to plain XLA attention. This module holds a small tuned
table, produced by ``python -m containerpilot_tpu.ops.autotune`` on
the actual device (ops/autotune.py) and shipped per platform under
``ops/tuned/<platform>.json``:

    {"platform": "tpu-v5-lite",
     "flash_min_seq": {"train": 2048, "fwd": 1024},
     "blocks": {"train": {"2048": [256, 128], ...},
                "fwd":   {"8192": [256, 256], ...}}}

Consumers:
- ``pick_blocks(kind, seq)`` -> (block_q, block_k) for the flash call
  (exact seq entry, else the nearest tuned seq at/below, else the
  128/128 default), clamped to divisors of seq so the kernels' static
  grids stay exact.
- ``auto_min_seq(kind)`` -> the measured flash/XLA crossover:
  sequences shorter than this run faster through XLA's fused
  attention than through the pallas kernels, so the model's
  ``flash_min_seq: AUTO`` resolves here (models/transformer.py
  flash_eligible).

A platform with no shipped table runs untuned (128/128 blocks,
crossover 1024) and says so in the log, once; so does every
(kind, seq) decision between the flash kernels and XLA attention
(``log_attention_path``). Override the table path with
CONTAINERPILOT_FLASH_TABLE; ``set_table(None)`` reverts to
auto-discovery.
"""
from __future__ import annotations

import functools
import json
import logging
import os
import re
from typing import Dict, Optional, Tuple

log = logging.getLogger("containerpilot.tuning")

DEFAULT_BLOCK = 128
DEFAULT_MIN_SEQ = 1024  # pre-tuning crossover default
AUTO = -1               # TransformerConfig.flash_min_seq sentinel

_TUNED_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tuned")

# module state: the active table, and whether discovery already ran
_table: Optional[dict] = None
_loaded = False


def platform_slug() -> str:
    """Normalized device kind of the default backend, e.g.
    'tpu-v5-lite'; 'cpu' on the test mesh."""
    import jax

    kind = jax.devices()[0].device_kind
    return re.sub(r"[^a-z0-9]+", "-", kind.lower()).strip("-")


def _table_path() -> Optional[str]:
    override = os.environ.get("CONTAINERPILOT_FLASH_TABLE")
    if override:
        return override
    # a backend that cannot initialize raises here: nothing that asks
    # for flash blocks can run without one, so it is not swallowed
    slug = platform_slug()
    path = os.path.join(_TUNED_DIR, f"{slug}.json")
    if os.path.exists(path):
        return path
    log.info(
        "no flash tuning table for %s: untuned defaults (%d/%d blocks, "
        "crossover %d)", slug, DEFAULT_BLOCK, DEFAULT_BLOCK,
        DEFAULT_MIN_SEQ,
    )
    return None


def set_table(table: Optional[dict]) -> None:
    """Install a table dict directly (tests, autotune); None reverts
    to on-disk auto-discovery at the next lookup."""
    global _table, _loaded
    _table = table
    _loaded = table is not None


def _get_table() -> Optional[dict]:
    global _table, _loaded
    if not _loaded:
        _loaded = True
        path = _table_path()
        if path:
            try:
                with open(path) as fh:
                    _table = json.load(fh)
                log.info("flash tuning table: %s", path)
            except (OSError, ValueError) as exc:
                log.warning("flash tuning table unreadable (%s): %s",
                            path, exc)
                _table = None
    return _table


def _largest_divisor_block(seq: int, block: int) -> int:
    """The largest block <= ``block`` dividing seq (halving from
    ``block``, floored at DEFAULT_BLOCK — the kernels require exact
    grids). Fails loudly on seq not a multiple
    of DEFAULT_BLOCK: pick_blocks is a public helper (autotune
    calls it), and silently clamping to a non-tile block (e.g. 100, or
    a degenerate 2) would hand pallas a grid Mosaic rejects — every
    flash call site gates on seq % 128 == 0 (flash_eligible), so such
    a seq here is a caller bug, not a tuning decision."""
    if seq % DEFAULT_BLOCK != 0:
        raise ValueError(
            f"flash blocks require seq % {DEFAULT_BLOCK} == 0; got "
            f"seq={seq} (gate the call on flash_eligible)"
        )
    b = block
    while b > DEFAULT_BLOCK and seq % b != 0:
        b //= 2
    # halving an odd-multiple block can undershoot DEFAULT_BLOCK with
    # a non-divisor; the floor is always a divisor thanks to the gate
    return max(b, DEFAULT_BLOCK)


def pick_blocks(kind: str, seq: int) -> Tuple[int, int]:
    """(block_q, block_k) for a flash call of ``kind`` ('train' = the
    differentiable fwd+bwd path, 'fwd' = inference/prefill) at ``seq``."""
    bq, bk = DEFAULT_BLOCK, DEFAULT_BLOCK
    table = _get_table()
    if table is not None:
        entries: Dict[str, list] = table.get("blocks", {}).get(kind, {})
        tuned_seqs = sorted(int(s) for s in entries)
        at_or_below = [s for s in tuned_seqs if s <= seq]
        if at_or_below:
            bq, bk = entries[str(at_or_below[-1])]
    return _largest_divisor_block(seq, bq), _largest_divisor_block(seq, bk)


def auto_min_seq(kind: str = "train") -> int:
    """The measured crossover below which XLA attention wins; the
    pre-tuning default when no table is shipped for this platform."""
    table = _get_table()
    if table is not None:
        value = table.get("flash_min_seq", {}).get(kind)
        if isinstance(value, int) and value >= 0:
            return value
    return DEFAULT_MIN_SEQ


@functools.lru_cache(maxsize=None)
def log_attention_path(
    kind: str, seq: int, window: int, flash: bool
) -> None:
    """Say, once per distinct decision, which attention path a traced
    shape took: the pallas flash kernels with their blocks, or XLA's
    einsum attention. Called at trace time, so a compiled program
    costs one line, not one per step."""
    if flash:
        bq, bk = pick_blocks(kind, seq)
        log.info(
            "attention %s seq=%d window=%d: pallas flash, blocks %d/%d",
            kind, seq, window, bq, bk,
        )
    else:
        log.info(
            "attention %s seq=%d window=%d: XLA einsum",
            kind, seq, window,
        )


def resolve_min_seq(configured: int, kind: str = "train") -> int:
    """Map a TransformerConfig.flash_min_seq to an effective threshold:
    AUTO (-1) asks the tuned table; explicit values win unchanged
    (0 keeps meaning 'never use flash')."""
    return auto_min_seq(kind) if configured == AUTO else configured
