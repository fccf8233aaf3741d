"""A supervised training process: the demo workload.

This is what a job's ``exec`` points at in a TPU deployment — one
training process per host, supervised by containerpilot-tpu:

- writes a progress file every step (``--progress-file``), which the
  job's health check probes (e.g. ``exec: "find /run/progress -newermt
  '-30 seconds'"``) so a hung training loop goes catalog-critical;
- posts step/loss metrics to the supervisor's control socket
  (``--control-socket``) for the Prometheus endpoint;
- trains the flagship transformer on synthetic data over the local
  (data, model) mesh;
- handles preemption gracefully: on SIGTERM (TPU maintenance events,
  the supervisor's stopTimeout window, `docker stop`) it finishes the
  in-flight step, saves a checkpoint, and exits 0 — the supervisor's
  restart brings it back at exactly that step. Single-process only:
  a multi-process pod cannot checkpoint from one signal handler
  (orbax saves hold cross-process barriers), so there the process
  exits cleanly and the pod resumes from the last periodic
  checkpoint.

Run it stand-alone:
    python -m containerpilot_tpu.workload.train --steps 20
or under the supervisor (see examples/training-pod.json5).
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import time

import jax
import jax.numpy as jnp


def main() -> int:
    from .modelcfg import enable_compile_cache

    # the supervisor collects this process's stderr: which tuning
    # table and which attention path each compiled shape took are
    # logged there, once
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(message)s",
    )
    enable_compile_cache()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--steps", type=int, default=100)
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--seq-len", type=int, default=256)
    parser.add_argument("--d-model", type=int, default=256)
    parser.add_argument("--n-layers", type=int, default=2)
    parser.add_argument("--n-heads", type=int, default=4)
    parser.add_argument("--n-kv-heads", type=int, default=0,
                        help="GQA kv heads (0 = full multi-head)")
    parser.add_argument("--window", type=int, default=0,
                        help="sliding-window attention: each position "
                        "attends the last N positions only (0 = full "
                        "causal); bounds attention FLOPs and the "
                        "serving KV cache")
    parser.add_argument("--loss-chunk", type=int, default=0,
                        help="stream the vocab projection + softmax "
                        "over sequence chunks of N instead of "
                        "materializing [batch, seq, vocab] logits "
                        "(0 = whole-logits loss)")
    parser.add_argument("--vocab", type=int, default=1024)
    parser.add_argument("--data-dir", default="",
                        help="token shards (shard_*.npy; workload/data.py)"
                        " — default is synthetic data")
    parser.add_argument("--eval-every", type=int, default=0,
                        help="report held-out loss every N steps "
                        "(requires --data-dir and --eval-holdout)")
    parser.add_argument("--eval-holdout", type=int, default=0,
                        help="windows reserved from the shard tail as "
                        "the eval split")
    parser.add_argument("--profile-dir", default="",
                        help="capture an XLA/TPU profiler trace of steps "
                        "2..2+profile-steps into this dir (view with "
                        "tensorboard or xprof)")
    parser.add_argument("--profile-steps", type=int, default=3)
    parser.add_argument("--pipeline-stages", type=int, default=0,
                        help="GPipe pipeline stages (0 = no pipeline); "
                        "n_layers must divide by it")
    parser.add_argument("--microbatches", type=int, default=4,
                        help="pipeline microbatches (batch must divide)")
    parser.add_argument("--tensor-parallel", type=int, default=0,
                        help="model-axis size of the mesh; the "
                        "remaining devices go to data (0 = 1 when "
                        "pipelining, else the default factoring: the "
                        "largest power of two up to 4)")
    parser.add_argument("--progress-file", default="")
    parser.add_argument("--control-socket", default="")
    parser.add_argument("--learning-rate", type=float, default=3e-4)
    parser.add_argument("--warmup-steps", type=int, default=0,
                        help="linear lr warmup from 0 over N steps")
    parser.add_argument("--decay-steps", type=int, default=0,
                        help="cosine-decay the lr to 10%% of peak over "
                        "N post-warmup steps (0 = constant)")
    parser.add_argument("--lora-rank", type=int, default=0,
                        help="LoRA fine-tuning: train rank-R adapters "
                        "on attention q/v with the base frozen "
                        "(0 = full training)")
    parser.add_argument("--base-checkpoint-dir", default="",
                        help="with --lora-rank: frozen base weights "
                        "from this checkpoint (params-only restore); "
                        "default is a fresh init (demo)")
    parser.add_argument("--zero1", action="store_true",
                        help="ZeRO-1: shard adam moments over the data "
                        "axis; optimizer memory per device drops by "
                        "the data-parallel factor")
    parser.add_argument("--ema-decay", type=float, default=0.0,
                        help="maintain an EMA shadow of the params "
                        "(e.g. 0.999); eval and the checkpoint carry "
                        "it; 0 = off")
    parser.add_argument("--fsdp", action="store_true",
                        help="FSDP (ZeRO-3): shard params, grads, AND "
                        "moments over the data axis; per-device model "
                        "state drops by the dp factor, XLA all-gathers "
                        "weights at each use (subsumes --zero1)")
    parser.add_argument("--accum-steps", type=int, default=1,
                        help="gradient accumulation: split each batch "
                        "into N sequential chunks inside the compiled "
                        "step (batch must divide; not with --pipeline-"
                        "stages, whose microbatching already does this)")
    parser.add_argument("--checkpoint-dir", default="")
    parser.add_argument("--checkpoint-every", type=int, default=50)
    parser.add_argument("--checkpoint-async", action="store_true",
                        help="commit checkpoints on a background "
                        "thread: the loop resumes after the "
                        "device->host copy instead of waiting for "
                        "disk")
    args = parser.parse_args()

    from ..models.transformer import TransformerConfig
    from .modelcfg import derive_d_ff
    from ..parallel import (
        MeshPlan,
        init_train_state,
        make_mesh,
        make_optimizer,
        make_pipeline_train_step,
        make_train_step,
    )

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
    rules = None
    pipe = max(args.pipeline_stages, 1)
    if pipe > 1 and args.loss_chunk:
        raise SystemExit(
            "--loss-chunk does not apply to the pipelined loss "
            "(pipeline_loss_fn computes its own whole-logits CE)"
        )
    if pipe > 1 or args.tensor_parallel > 0:
        # an explicit factoring: dp x tp, or dp x pp x tp (layers
        # shard over pipe stages, tensor parallelism stays live inside
        # each stage — parallel/pipeline.py)
        n_dev = len(jax.devices())
        tp = args.tensor_parallel or 1
        if n_dev % (pipe * tp):
            raise SystemExit(
                f"{n_dev} devices not divisible by pipeline-stages x "
                f"tensor-parallel = {pipe} x {tp}"
            )
        mesh = make_mesh(plan=MeshPlan(
            data=n_dev // (pipe * tp), model=tp, pipe=pipe,
        ))
    else:
        mesh = make_mesh()
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"on {jax.default_backend()}")
    rng = jax.random.PRNGKey(0)
    optimizer = make_optimizer(
        args.learning_rate,
        warmup_steps=args.warmup_steps,
        decay_steps=args.decay_steps,
    )
    if args.ema_decay:
        from ..parallel import with_ema

        optimizer = with_ema(optimizer, args.ema_decay)
    lora_init = lora_abstract = None
    if args.lora_rank > 0:
        if (args.pipeline_stages > 1 or args.zero1 or args.fsdp
                or args.accum_steps > 1):
            raise SystemExit(
                "--lora-rank composes with the plain trainer only "
                "(the adapter state is tiny; zero1/fsdp/accum/pipeline "
                "solve problems LoRA doesn't have)"
            )
        from ..models.transformer import init_params
        from ..parallel import make_lora_train_step, restore_params
        from ..parallel.sharding import shard_params

        if args.base_checkpoint_dir:
            from ..parallel import abstract_train_state

            restored_base = restore_params(
                args.base_checkpoint_dir,
                abstract_train_state(rng, cfg, mesh, args.learning_rate),
            )
            if restored_base is None:
                raise SystemExit(
                    f"no checkpoint in {args.base_checkpoint_dir}"
                )
            base_params, base_step = restored_base
            print(f"lora: frozen base from checkpoint step {int(base_step)}")
        else:
            base_params = shard_params(init_params(rng, cfg), mesh, cfg)
            print("lora: fresh-init frozen base (demo mode)")
        lora_init, lora_step, lora_abstract = make_lora_train_step(
            cfg, mesh, args.lora_rank, args.learning_rate,
            optimizer=optimizer,
        )
        print(f"lora: rank {args.lora_rank} adapters on attention q/v")

        def train_step(state, tokens):
            return lora_step(state, base_params, tokens)

    elif args.pipeline_stages > 1:
        from ..parallel import pipeline_sharding_rules

        if args.accum_steps > 1:
            raise SystemExit(
                "--accum-steps composes with the plain trainer only; "
                "pipeline microbatching already bounds activations"
            )
        if args.zero1 or args.fsdp:
            raise SystemExit(
                "--zero1/--fsdp compose with the plain trainer only "
                "(pipeline sharding rules already partition state over "
                "stages)"
            )
        rules = pipeline_sharding_rules(cfg, mesh)
        train_step = make_pipeline_train_step(
            cfg, mesh, args.learning_rate, args.microbatches,
            optimizer=optimizer,
        )
    else:
        if args.batch % args.accum_steps:
            raise SystemExit(
                f"--batch {args.batch} not divisible by --accum-steps "
                f"{args.accum_steps}"
            )
        if args.fsdp:
            from ..parallel import fsdp_sharding_rules

            rules = fsdp_sharding_rules(cfg, mesh)
        train_step = make_train_step(
            cfg, mesh, args.learning_rate, optimizer=optimizer,
            accum_steps=args.accum_steps, zero1=args.zero1,
            rules=rules,
        )

    state = None
    start_step = 0
    if args.checkpoint_dir:
        from ..parallel import (
            abstract_train_state,
            restore_checkpoint,
            save_checkpoint,
        )

        # restore into the eval_shape skeleton: no throwaway init, no
        # double residency of model + optimizer state during resume
        abstract = (
            lora_abstract
            if lora_abstract is not None
            else abstract_train_state(
                rng, cfg, mesh, args.learning_rate, rules=rules,
                optimizer=optimizer, zero1=args.zero1,
            )
        )
        state = restore_checkpoint(args.checkpoint_dir, abstract)
        if state is not None:
            start_step = int(state.step)
            print(f"resumed from checkpoint at step {start_step}")
    if state is None:
        state = (
            lora_init(rng)
            if lora_init is not None
            else init_train_state(
                rng, cfg, mesh, args.learning_rate, rules=rules,
                optimizer=optimizer, zero1=args.zero1,
            )
        )

    client = None
    if args.control_socket:
        from ..client import ControlClient

        client = ControlClient(args.control_socket)

    if args.eval_every > 0 and not (args.data_dir and args.eval_holdout):
        # validated before any dataset/prefetcher exists so a bad flag
        # combination can't leak the staging thread
        raise SystemExit(
            "--eval-every requires --data-dir and --eval-holdout"
        )

    # graceful preemption: the handler only sets a flag; the train
    # loop checks it at the step boundary. Installed BEFORE any
    # resource (prefetcher thread, device buffers) exists so a
    # non-main-thread caller fails here, with nothing yet to leak;
    # the train loop's finally restores the previous disposition.
    import signal as signal_mod
    import threading

    preempted = threading.Event()
    prev_term = signal_mod.signal(
        signal_mod.SIGTERM, lambda s, f: preempted.set()
    )

    prefetcher = None
    if args.data_dir:
        from jax.sharding import NamedSharding

        from ..parallel.sharding import batch_spec
        from .data import DevicePrefetcher, TokenShardDataset

        dataset = TokenShardDataset(
            args.data_dir, args.seq_len, args.batch,
            vocab_size=cfg.vocab_size,  # fail loudly on id/vocab mismatch
            holdout_windows=args.eval_holdout,
        )
        # batches stage onto the mesh from a background thread; the
        # window order is a pure function of the step, so a restarted
        # trainer replays the exact stream from its checkpoint step
        prefetcher = DevicePrefetcher(
            dataset,
            start_step=start_step,
            sharding=NamedSharding(mesh, batch_spec()),
        )
        print(f"data: {dataset.n_windows} train windows "
              f"(+{dataset.holdout_windows} held out) from {args.data_dir}")

    eval_enabled = args.eval_every > 0

    def run_eval(params) -> float:
        # the ONE eval-loss computation, shared with the standalone
        # evaluate CLI (workload/modelcfg.py) so their numbers are
        # comparable by construction
        from .modelcfg import average_eval_loss

        with jax.profiler.TraceAnnotation("train.eval"):
            return average_eval_loss(
                params, cfg, dataset.n_eval_batches, dataset.eval_batch
            )

    # profiler window: skip step 1 (compile) and capture a few steady
    # steps — the standard "pick a mesh, profile, iterate" loop
    if args.profile_dir and args.profile_steps < 1:
        raise SystemExit("--profile-steps must be >= 1")
    profile_start = start_step + 1 if args.profile_dir else -1
    profile_stop = profile_start + args.profile_steps
    if args.profile_dir and profile_start >= args.steps:
        print(
            f"warning: --profile-dir needs at least "
            f"{profile_start - start_step + 1} steps after resume to "
            "capture a steady-state window; nothing will be profiled"
        )
    profiling = False

    # throughput accounting: tokens/s from wall clock, MFU against the
    # chip generation's bf16 peak (workload/flops.py) — the numbers an
    # operator watches on the supervisor's Prometheus endpoint
    from .flops import count_params, peak_flops, train_flops_per_token

    if args.lora_rank > 0:
        # the frozen base forwards + carries grads but trains nothing
        n_base = count_params(base_params)
        n_params = n_base + count_params(state.params)
        flops_per_token = train_flops_per_token(
            cfg, n_params, args.seq_len, n_frozen=n_base
        )
    else:
        n_params = count_params(state.params)
        flops_per_token = train_flops_per_token(
            cfg, n_params, args.seq_len
        )
    # MFU exists only against a published peak: on a TPU an unknown
    # device kind is an error (peak_flops raises); off-TPU (the CPU
    # test mesh) no MFU is reported at all
    chip_peak = None
    if jax.devices()[0].platform == "tpu":
        chip_peak = peak_flops(jax.devices()[0].device_kind) * len(
            jax.devices()
        )

    data_rng = jax.random.PRNGKey(1)
    # the loop's phases are profiler annotations (a flag test when no
    # trace runs): a trace taken with --profile-dir, or by a launcher
    # around this process, shows them on this thread's line, on the
    # device events' clock (docs/90-observability.md)
    annotate = jax.profiler.TraceAnnotation
    t0 = time.monotonic()
    try:
        for step in range(start_step, args.steps):
            if preempted.is_set():
                if args.checkpoint_dir and jax.process_count() == 1:
                    from ..parallel import wait_for_checkpoints

                    wait_for_checkpoints()  # drain async saves first
                    save_checkpoint(args.checkpoint_dir, step, state)
                    print(f"preempted: checkpoint saved at step {step}; "
                          "exiting for the supervisor to resume",
                          flush=True)
                else:
                    # a multi-process pod can't checkpoint from one
                    # signal (orbax barriers span processes): exit
                    # clean, resume from the last periodic save
                    print("preempted: exiting (resume from last "
                          "periodic checkpoint)", flush=True)
                return 0
            if step == profile_start:
                jax.profiler.start_trace(args.profile_dir)
                profiling = True
            if prefetcher is not None:
                with annotate("train.next_batch"):
                    _pstep, tokens = prefetcher.next()
            else:
                # stateless per-step key: a resumed run continues the
                # data stream exactly where the crashed run left off
                k = jax.random.fold_in(data_rng, step)
                tokens = jax.random.randint(
                    k, (args.batch, args.seq_len + 1), 0, cfg.vocab_size,
                    jnp.int32,
                )
            with jax.profiler.StepTraceAnnotation(
                "train.step", step_num=step + 1
            ):
                state, loss = train_step(state, tokens)
            if step + 1 == profile_stop and profiling:
                loss.block_until_ready()  # close the window on real work
                jax.profiler.stop_trace()
                profiling = False
                print(f"profiler trace written to {args.profile_dir}")
            if args.checkpoint_dir and (step + 1) % args.checkpoint_every == 0:
                with annotate("train.checkpoint"):
                    save_checkpoint(args.checkpoint_dir, step + 1, state,
                                    wait=not args.checkpoint_async)
            if args.progress_file:
                with annotate("train.loss_sync"):
                    # the one sync a step pays: the progress file
                    # wants the loss as a number
                    loss_now = float(loss)
                tmp = args.progress_file + ".tmp"
                with open(tmp, "w") as f:
                    json.dump({"step": step + 1, "loss": loss_now,
                               "time": time.time()}, f)
                os.replace(tmp, args.progress_file)
            if (step + 1) % 10 == 0 or step == start_step:
                # one throughput computation feeds BOTH the metric
                # export and the log line, so they can never disagree
                rate = (step + 1 - start_step) / (time.monotonic() - t0)
                tokens_s = rate * args.batch * args.seq_len
                metrics = {
                    "training_steps_total": 10,
                    "training_loss": float(loss),
                    "training_tokens_per_sec": tokens_s,
                }
                mfu_note = ""
                if chip_peak is not None:
                    mfu = tokens_s * flops_per_token / chip_peak
                    metrics["training_mfu"] = mfu
                    mfu_note = f", mfu={mfu:.3f}"
                if client is not None and (step + 1) % 10 == 0:
                    try:
                        client.put_metric(metrics)
                    except Exception:  # cpcheck: disable=CP-SWALLOW supervisor may be reloading; never die
                        pass
                print(f"step {step + 1}: loss={float(loss):.4f} "
                      f"({rate:.1f} steps/s, {tokens_s:.0f} tok/s"
                      f"{mfu_note})")
                if step == start_step:
                    # where the state actually sits, per local device
                    # (None where the backend keeps no memory stats)
                    in_use = [
                        (d.memory_stats() or {}).get("bytes_in_use")
                        for d in jax.local_devices()
                    ]
                    print(f"device bytes_in_use: {in_use}")
            if eval_enabled and (step + 1) % args.eval_every == 0:
                if args.lora_rank > 0:
                    from ..models.lora import apply_lora
                    from ..parallel import ema_params

                    adapters = (
                        ema_params(state) if args.ema_decay
                        else state.params
                    )
                    eval_loss = run_eval(
                        apply_lora(base_params, adapters, cfg)
                    )
                elif args.ema_decay:
                    from ..parallel import ema_params

                    eval_loss = run_eval(ema_params(state))
                else:
                    eval_loss = run_eval(state.params)
                print(f"step {step + 1}: eval_loss={eval_loss:.4f}")
                if client is not None:
                    try:
                        client.put_metric({"training_eval_loss": eval_loss})
                    except Exception:  # cpcheck: disable=CP-SWALLOW supervisor may be reloading; never die
                        pass
    finally:
        # a failed step must not leak the staging thread (in-process
        # callers would otherwise keep a live worker + device buffers),
        # a dangling profiler window must be closed, and in-process
        # callers (tests) must get their SIGTERM disposition back
        signal_mod.signal(signal_mod.SIGTERM, prev_term)
        if prefetcher is not None:
            prefetcher.stop()
        if profiling:
            try:
                jax.profiler.stop_trace()
            except Exception:  # cpcheck: disable=CP-SWALLOW profiler may never have started
                pass
        if args.checkpoint_async and args.checkpoint_dir:
            # an in-flight background save must commit before exit —
            # but a deferred write error must not mask an exception
            # already propagating out of the train loop. On a CLEAN
            # exit the failure must surface (a swallowed commit error
            # would return 0 with the final checkpoint silently lost).
            import sys as _sys

            from ..parallel import wait_for_checkpoints

            propagating = _sys.exc_info()[0] is not None
            try:
                wait_for_checkpoints()
            except Exception:
                if not propagating:
                    raise
                logging.getLogger("containerpilot.train").exception(
                    "async checkpoint commit failed"
                )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
