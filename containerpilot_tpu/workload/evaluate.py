"""Standalone evaluation: checkpoint + token shards -> loss/perplexity.

Completes the train/eval/serve triad: the trainer's in-loop eval
(``--eval-every``) tracks progress DURING a run; this CLI scores any
checkpoint after the fact — the raw params, the EMA shadow
(``--use-ema``), or a LoRA-adapted base (``--lora-dir``) — over a
dataset's held-out windows (or the whole stream with
``--eval-holdout 0 --max-batches N``). One JSON line on stdout so a
supervisor job or script can consume it:

    python -m containerpilot_tpu.workload.evaluate \
        --checkpoint-dir /ckpt --data-dir /data --eval-holdout 64 \
        --d-model 1024 ...   (model flags must match the checkpoint)

``--eval-holdout`` is REQUIRED and must match the trainer's value: a
larger value here would silently score trained-on windows as
"held out" (the checkpoint does not record the split).

Runs on whatever devices are visible (the same auto (data, model)
mesh the trainer uses); the loss computation is shared with the
trainer's in-loop eval (workload/modelcfg.py), so a number here is
comparable to training logs by construction.
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from .modelcfg import average_eval_loss, derive_d_ff, restore_merged_params


def main() -> int:
    from .modelcfg import enable_compile_cache

    enable_compile_cache()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--checkpoint-dir", required=True)
    parser.add_argument("--data-dir", required=True)
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--seq-len", type=int, default=256)
    parser.add_argument("--d-model", type=int, default=256)
    parser.add_argument("--n-layers", type=int, default=2)
    parser.add_argument("--n-heads", type=int, default=4)
    parser.add_argument("--n-kv-heads", type=int, default=0)
    parser.add_argument("--vocab", type=int, default=32_000)
    parser.add_argument("--window", type=int, default=0)
    parser.add_argument("--loss-chunk", type=int, default=0)
    parser.add_argument(
        "--eval-holdout", type=int, required=True,
        help="score the dataset's LAST N windows; MUST equal the "
        "trainer's --eval-holdout or trained-on windows leak into "
        "the score (0 = score the training stream from its head)",
    )
    parser.add_argument(
        "--max-batches", type=int, default=0,
        help="cap scored batches (0 = the whole selected split)",
    )
    parser.add_argument(
        "--use-ema", action="store_true",
        help="score the checkpoint's EMA shadow weights (falls back "
        "to raw params WITH a warning and \"ema\": false in the "
        "report when the checkpoint has no shadow)",
    )
    parser.add_argument("--lora-dir", default="")
    parser.add_argument("--lora-rank", type=int, default=0)
    args = parser.parse_args()

    from ..models.transformer import TransformerConfig
    from ..parallel import make_mesh
    from .data import TokenShardDataset

    cfg = TransformerConfig(
        vocab_size=args.vocab,
        d_model=args.d_model,
        n_heads=args.n_heads,
        n_kv_heads=args.n_kv_heads,
        n_layers=args.n_layers,
        d_ff=derive_d_ff(args.d_model),
        max_seq_len=args.seq_len,
        window=args.window,
        loss_chunk=args.loss_chunk,
    )
    restored = restore_merged_params(
        cfg, make_mesh(), args.checkpoint_dir, use_ema=args.use_ema,
        lora_dir=args.lora_dir, lora_rank=args.lora_rank,
    )
    if restored is None:
        raise SystemExit(f"no checkpoint in {args.checkpoint_dir}")
    params, step = restored
    # reported honestly FROM the restore: .ema says whether the shadow
    # weights are what actually came back (the restore falls back to
    # raw params, with a logged warning, when the checkpoint has none)
    ema_scored = restored.ema

    dataset = TokenShardDataset(
        args.data_dir, args.seq_len, args.batch,
        vocab_size=cfg.vocab_size,
        holdout_windows=args.eval_holdout,
    )
    if args.eval_holdout > 0:
        n = dataset.n_eval_batches
        batch_at = dataset.eval_batch
    else:
        n = dataset.n_windows // args.batch
        batch_at = dataset.batch_at
    if args.max_batches > 0:
        n = min(n, args.max_batches)
    if n < 1:
        raise SystemExit("dataset yields no full eval batch at this "
                         "batch/seq-len; shrink --batch or --seq-len")

    loss = average_eval_loss(params, cfg, n, batch_at)
    print(json.dumps({
        "checkpoint_step": int(step),
        "eval_loss": round(loss, 6),
        "perplexity": round(float(jnp.exp(loss)), 4),
        "batches": n,
        "tokens": n * args.batch * args.seq_len,
        "split": "holdout" if args.eval_holdout > 0 else "head",
        "ema": ema_scored,
        "lora": bool(args.lora_dir),
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
