"""Flash-attention block autotuner: measure, choose, persist.

Produces the per-platform tuning table ops/tuning.py serves
(``ops/tuned/<platform>.json``). For each sequence length it times the
pallas kernels across candidate (block_q, block_k) pairs — 'train'
(one differentiable call: fwd+bwd through the custom_vjp) and 'fwd'
(inference/prefill) separately — against the XLA fused-attention
baseline, keeps the fastest blocks, and records the flash/XLA
crossover that ``TransformerConfig.flash_min_seq = AUTO`` resolves to.

    python -m containerpilot_tpu.ops.autotune \
        --seqs 1024,2048,4096,8192 --write

Timing: n back-to-back dispatches closed by
``block_until_ready``, divided by n, min over repetitions. Nothing is
subtracted.
"""
from __future__ import annotations

import argparse
import itertools
import json
import logging
import os
import time
from typing import Dict, Iterable, List, Tuple

log = logging.getLogger("containerpilot.autotune")

from .tuning import DEFAULT_BLOCK

CANDIDATE_BLOCKS = (128, 256, 512)
# the untuned baseline every accepted pair must measurably beat —
# derived, so the guard can't drift from pick_blocks' actual fallback
DEFAULT_PAIR = (DEFAULT_BLOCK, DEFAULT_BLOCK)


def time_ms(fn, *args, n: int = 5, reps: int = 3) -> float:
    """Milliseconds per call of ``fn(*args)``: n back-to-back
    dispatches (in-order execution, so waiting on the last result
    waits for all), one ``block_until_ready``, min over ``reps``
    repetitions. The first call compiles and is not timed."""
    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) * 1e3)
    return best / n


def _candidates(seq: int, blocks: Iterable[int]) -> List[Tuple[int, int]]:
    divs = [b for b in blocks if seq % b == 0]
    pairs = list(itertools.product(divs, divs))
    # build_table's honesty guard compares every pick against the
    # 128/128 baseline, so it must be measured even when --blocks
    # excludes 128 (any flash-eligible seq is a 128-multiple)
    if seq % DEFAULT_PAIR[0] == 0 and DEFAULT_PAIR not in pairs:
        pairs.insert(0, DEFAULT_PAIR)
    return pairs


def measure(
    seqs: Iterable[int],
    blocks: Iterable[int] = CANDIDATE_BLOCKS,
    batch: int = 2,
    heads: int = 8,
    head_dim: int = 128,
    n: int = 5,
    reps: int = 3,
) -> dict:
    """Raw measurements: per seq, XLA fwd/train baselines and every
    candidate block pair's flash fwd/train times (ms)."""
    import jax
    import jax.numpy as jnp

    from .attention import causal_attention
    from .flash import flash_attention

    results: dict = {}
    for seq in seqs:
        ks = jax.random.split(jax.random.PRNGKey(seq), 4)
        q, k, v = (
            jax.random.normal(kk, (batch, seq, heads, head_dim),
                              jnp.bfloat16)
            for kk in ks[:3]
        )
        cot = jax.random.normal(
            ks[3], (batch, seq, heads, head_dim), jnp.bfloat16
        )

        def train_of(attn):
            # jit created ONCE per attention variant and reused for
            # every timed dispatch — rebuilding it inside the timed
            # callable would miss jax's jit cache and time retraces
            return jax.jit(
                jax.grad(
                    lambda q, k, v: jnp.sum(
                        (attn(q, k, v) * cot).astype(jnp.float32)
                    ),
                    argnums=(0, 1, 2),
                )
            )

        xla_train = train_of(causal_attention)
        entry = {
            "xla_fwd_ms": time_ms(
                jax.jit(causal_attention), q, k, v, n=n, reps=reps
            ),
            "xla_train_ms": time_ms(
                lambda *a: xla_train(*a)[0], q, k, v, n=n, reps=reps,
            ),
            "flash": {},
        }
        for bq, bk in _candidates(seq, blocks):
            fa = lambda q, k, v, _bq=bq, _bk=bk: flash_attention(  # noqa: E731
                q, k, v, block_q=_bq, block_k=_bk
            )
            flash_train = train_of(fa)
            entry["flash"][f"{bq}x{bk}"] = {
                "fwd_ms": time_ms(jax.jit(fa), q, k, v, n=n, reps=reps),
                "train_ms": time_ms(
                    lambda *a: flash_train(*a)[0], q, k, v, n=n,
                    reps=reps,
                ),
            }
        results[str(seq)] = entry
        log.info("autotune seq %d: %s", seq, json.dumps(entry))
    return results


def build_table(results: dict, platform: str) -> dict:
    """Choose per-seq best blocks and the flash/XLA crossover per kind.

    The crossover is the smallest measured seq from which flash (at
    its best blocks) beats XLA at EVERY measured seq onward — a seq
    where XLA still wins keeps routing below-it traffic to XLA.

    Honesty guard: a non-default block pair only enters the table if
    its measured time actually beats the 128/128 default at that seq —
    a noise-level "win" must not ship as tuning. Every entry carries
    its measured ``speedup_vs_default`` (default_ms / chosen_ms, 1.0
    when the default itself is chosen) so the table is
    self-evidencing."""
    default_key = f"{DEFAULT_PAIR[0]}x{DEFAULT_PAIR[1]}"
    blocks: Dict[str, Dict[str, list]] = {"train": {}, "fwd": {}}
    speedup: Dict[str, Dict[str, float]] = {"train": {}, "fwd": {}}
    wins: Dict[str, Dict[int, bool]] = {"train": {}, "fwd": {}}
    for seq_s, entry in results.items():
        seq = int(seq_s)
        for kind, flash_key, xla_key in (
            ("train", "train_ms", "xla_train_ms"),
            ("fwd", "fwd_ms", "xla_fwd_ms"),
        ):
            best_pair, best_ms = None, float("inf")
            for pair, times in entry["flash"].items():
                if times[flash_key] < best_ms:
                    best_ms = times[flash_key]
                    best_pair = [int(x) for x in pair.split("x")]
            if best_pair is None:
                continue
            default_times = entry["flash"].get(default_key)
            if default_times is not None:
                default_ms = default_times[flash_key]
                if best_pair != list(DEFAULT_PAIR) and best_ms >= default_ms:
                    best_pair, best_ms = list(DEFAULT_PAIR), default_ms
                speedup[kind][seq_s] = round(default_ms / best_ms, 4)
            else:
                # shouldn't happen via measure() (which always includes
                # the default pair); a hand-built results dict without
                # it ships unguarded — say so rather than imply tuning
                log.warning(
                    "autotune seq %s %s: %s baseline unmeasured; "
                    "honesty guard skipped", seq_s, kind, default_key,
                )
            blocks[kind][seq_s] = best_pair
            wins[kind][seq] = best_ms <= entry[xla_key]

    min_seq: Dict[str, int] = {}
    for kind, seq_wins in wins.items():
        measured = sorted(seq_wins)
        crossover = 0
        for seq in reversed(measured):
            if seq_wins[seq]:
                crossover = seq
            else:
                break
        # 0 would mean "flash always wins, even unmeasured tiny seqs";
        # never extrapolate below the smallest measured win
        min_seq[kind] = crossover if crossover else (
            (measured[-1] + 1) if measured else 0
        )
    return {
        "platform": platform,
        "flash_min_seq": min_seq,
        "blocks": blocks,
        "speedup_vs_default": speedup,
        "measurements": results,
    }


def main(argv=None) -> int:
    from . import tuning

    parser = argparse.ArgumentParser(description="flash block autotuner")
    parser.add_argument("--seqs", default="1024,2048,4096,8192")
    parser.add_argument(
        "--blocks", default=",".join(map(str, CANDIDATE_BLOCKS))
    )
    parser.add_argument("--batch", type=int, default=2)
    parser.add_argument("--heads", type=int, default=8)
    parser.add_argument("--head-dim", type=int, default=128)
    parser.add_argument("--n", type=int, default=5)
    parser.add_argument("--reps", type=int, default=3)
    parser.add_argument(
        "--write", action="store_true",
        help="persist to ops/tuned/<platform>.json (the auto-discovery "
        "path); otherwise print the table to stdout only",
    )
    parser.add_argument("--out", default="", help="explicit output path")
    args = parser.parse_args(argv)

    logging.basicConfig(level=logging.INFO)
    seqs = [int(s) for s in args.seqs.split(",") if s]
    blocks = [int(b) for b in args.blocks.split(",") if b]

    platform = tuning.platform_slug()
    results = measure(
        seqs, blocks, batch=args.batch, heads=args.heads,
        head_dim=args.head_dim, n=args.n, reps=args.reps,
    )
    table = build_table(results, platform)
    print(json.dumps(table, indent=1))
    if args.write or args.out:
        path = args.out or os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tuned",
            f"{platform}.json",
        )
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        with open(path, "w") as fh:
            json.dump(table, fh, indent=1)
        print(f"wrote {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
