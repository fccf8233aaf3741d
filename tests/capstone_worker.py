"""Supervised data-parallel training worker for the capstone e2e
(tests/test_capstone.py) — built entirely from framework pieces:

- rendezvous: parallel.distributed.initialize_from_catalog through a
  live catalog server (the supervisor's own daemon);
- training: models.transformer loss + parallel.make_optimizer under a
  multi-process pmap data-parallel step (1 CPU device per process;
  pmean spans the pod);
- checkpoint/resume: parallel.checkpoint save/restore, called in
  LOCKSTEP by every process on ONE SHARED directory (orbax is a
  global checkpointer under jax.distributed: the primary process
  writes the data, saves hold cross-process barriers, and a shared
  dir makes the resume-step decision identical everywhere — see
  parallel/checkpoint.py's module docstring);
- failure detection: parallel.StepWatchdog armed BEFORE restore with
  a startup grace — when a peer dies, the survivor blocks silently
  inside a restore barrier or a collective; the watchdog turns the
  hang into an exit the supervisor restarts, whether it strikes
  during startup or mid-run.

Fault injection: --crash-step N exits 1 after completing step N, once
(a sentinel file remembers the crash across the supervisor restart).
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_mesh(args, dog) -> int:
    """The production-path variant: a (data, model) mesh via
    parallel.train — init_train_state / make_train_step / sharded
    save+restore — so the capstone's crash/restart/resume story runs
    over cross-process TENSOR parallelism, not just pmap dp. The
    global batch is a pure function of the step on every process
    (make_array_from_callback slices it), so loss parity with a
    1-process --tp 1 baseline holds by construction."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    from containerpilot_tpu.models.transformer import TransformerConfig
    from containerpilot_tpu.parallel import (
        MeshPlan,
        abstract_train_state,
        init_train_state,
        latest_step,
        make_mesh,
        make_train_step,
        restore_checkpoint,
        save_checkpoint,
    )
    from containerpilot_tpu.parallel.sharding import batch_spec

    n_global = jax.device_count()
    assert n_global % args.tp == 0, (n_global, args.tp)
    plan = MeshPlan(data=n_global // args.tp, model=args.tp)
    mesh = make_mesh(jax.devices(), plan=plan)
    assert args.global_batch % plan.data == 0

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=128,
        max_seq_len=16, dtype=jnp.float32, flash_min_seq=0,
    )
    seq = cfg.max_seq_len
    lr = 1e-2

    rng = jax.random.PRNGKey(0)
    state = init_train_state(rng, cfg, mesh, learning_rate=lr)
    start = 0
    restored = restore_checkpoint(
        args.checkpoint_dir,
        abstract_train_state(rng, cfg, mesh, lr),
    )
    if restored is not None:
        state = restored
        start = latest_step(args.checkpoint_dir)
        print(f"worker {args.process_id}: resumed at step {start} "
              f"(mesh {plan.data}x{plan.model})", flush=True)

    step_fn = make_train_step(cfg, mesh, learning_rate=lr)
    batch_sharding = NamedSharding(mesh, batch_spec())

    def global_batch_for(step: int):
        rows = jax.device_get(
            jax.random.randint(
                jax.random.PRNGKey(10_000 + step),
                (args.global_batch, seq + 1), 0, cfg.vocab_size,
                jnp.int32,
            )
        )
        return jax.make_array_from_callback(
            rows.shape, batch_sharding, lambda idx: rows[idx]
        )

    digest_fn = jax.jit(
        lambda p: sum(
            jnp.sum(jnp.abs(x.astype(jnp.float32)))
            for x in jax.tree.leaves(p)
        )
    )

    final_loss = None
    for step in range(start, args.steps):
        state, loss = step_fn(state, global_batch_for(step))
        final_loss = float(jax.device_get(loss))
        dog.beat()
        if args.heartbeat_file:
            with open(args.heartbeat_file, "w") as fh:
                fh.write(str(step))
        # sharded save in lockstep on the pod's ONE shared directory
        save_checkpoint(args.checkpoint_dir, step + 1, state)
        dog.beat()
        print(f"worker {args.process_id}: step {step} loss "
              f"{final_loss:.5f}", flush=True)
        if step == args.crash_step and args.crash_sentinel:
            if not os.path.exists(args.crash_sentinel):
                with open(args.crash_sentinel, "w") as fh:
                    fh.write(str(step))
                print(f"worker {args.process_id}: injected crash after "
                      f"step {step}", flush=True)
                sys.stdout.flush()
                os._exit(1)
    digest = float(jax.device_get(digest_fn(state.params)))
    dog.stop()

    with open(args.out, "w") as fh:
        json.dump(
            {
                "process_id": args.process_id,
                "final_loss": final_loss,
                "params_digest": digest,
                "resumed_from": start,
            },
            fh,
        )
    print(f"worker {args.process_id}: done (loss {final_loss:.5f})",
          flush=True)
    return 0


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--process-id", type=int, required=True)
    parser.add_argument("--num-processes", type=int, default=2)
    parser.add_argument("--catalog", default="")
    parser.add_argument("--coordinator-port", type=int, default=0)
    parser.add_argument("--steps", type=int, default=6)
    parser.add_argument("--global-batch", type=int, default=8)
    parser.add_argument("--checkpoint-dir", required=True)
    parser.add_argument("--out", required=True)
    parser.add_argument("--crash-step", type=int, default=-1)
    parser.add_argument("--crash-sentinel", default="")
    parser.add_argument("--step-timeout", type=float, default=30.0)
    parser.add_argument("--startup-timeout", type=float, default=150.0)
    parser.add_argument("--heartbeat-file", default="")
    parser.add_argument("--tp", type=int, default=0,
                        help="tensor-parallel axis size: > 0 switches "
                        "from the pmap data-parallel path to the "
                        "production mesh path (parallel.train: "
                        "make_mesh + init_train_state + "
                        "make_train_step + sharded checkpointing) on "
                        "a (devices/tp, tp) dp x tp mesh — tensor "
                        "parallelism then crosses process boundaries")
    args = parser.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from containerpilot_tpu.workload.modelcfg import (
        enable_compile_cache,
    )

    # the same compile cache as the real workload CLIs
    # (JAX_COMPILATION_CACHE_DIR when the test placed one, else the
    # checkout's): a reincarnated worker re-warms from cached
    # executables, which is both the feature's purpose and what keeps
    # the crash-resume capstones' restart windows short
    enable_compile_cache()

    from containerpilot_tpu.models.transformer import (
        TransformerConfig,
        init_params,
        loss_fn,
    )
    from containerpilot_tpu.parallel import (
        StepWatchdog,
        latest_step,
        make_optimizer,
        restore_checkpoint,
        save_checkpoint,
    )

    # a reincarnation that finds training already finished must NOT
    # rendezvous (its peers may be done and gone); report and exit
    done_before = latest_step(args.checkpoint_dir)
    if done_before is not None and done_before >= args.steps:
        print(f"worker {args.process_id}: already complete "
              f"(step {done_before})", flush=True)
        return 0

    if args.num_processes > 1:
        from containerpilot_tpu.discovery.consul import ConsulBackend
        from containerpilot_tpu.parallel import initialize_from_catalog

        initialize_from_catalog(
            ConsulBackend(address=args.catalog),
            args.process_id,
            args.num_processes,
            coordinator_port=args.coordinator_port,
            advertise_address="127.0.0.1",
            timeout=180,
            poll_interval=0.2,
        )

    # armed over the WHOLE startup window (restore barriers + first
    # compile-bearing step, where a dead peer wedges us just as
    # silently as mid-run) with a generous grace; each beat tightens
    # the deadline to the steady-state step budget
    dog = StepWatchdog(args.step_timeout).start(
        grace_s=max(args.startup_timeout, args.step_timeout)
    )

    if args.tp > 0:
        return run_mesh(args, dog)

    n_global = jax.device_count()
    n_local = jax.local_device_count()
    assert args.global_batch % n_global == 0
    per_dev = args.global_batch // n_global

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=16, dtype=jnp.float32, flash_min_seq=0,
    )
    seq = cfg.max_seq_len

    params = init_params(jax.random.PRNGKey(0), cfg)
    optimizer = make_optimizer(1e-2)
    opt_state = optimizer.init(params)
    host_state = {
        "params": jax.device_get(params),
        "opt_state": jax.device_get(opt_state),
    }

    start = 0
    restored = restore_checkpoint(args.checkpoint_dir, host_state)
    if restored is not None:
        host_state = restored
        start = latest_step(args.checkpoint_dir)
        print(f"worker {args.process_id}: resumed at step {start}",
              flush=True)

    import optax

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, tokens, cfg)
        )(params)
        grads = jax.lax.pmean(grads, "b")
        loss = jax.lax.pmean(loss, "b")
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    pstep = jax.pmap(train_step, axis_name="b")

    def replicate(tree):
        return jax.tree.map(
            lambda x: jnp.broadcast_to(
                jnp.asarray(x), (n_local,) + jnp.asarray(x).shape
            ),
            tree,
        )

    r_params = replicate(host_state["params"])
    r_opt = replicate(host_state["opt_state"])

    def global_batch_for(step: int) -> np.ndarray:
        # every process derives the IDENTICAL global batch, then takes
        # its device rows — data parity with the 1-process baseline by
        # construction
        rows = jax.device_get(
            jax.random.randint(
                jax.random.PRNGKey(10_000 + step),
                (args.global_batch, seq + 1), 0, cfg.vocab_size,
                jnp.int32,
            )
        )
        first = args.process_id * n_local * per_dev
        local = rows[first:first + n_local * per_dev]
        return local.reshape(n_local, per_dev, seq + 1)

    def progress_beat() -> None:
        # the externally visible twin of dog.beat(): the supervisor's
        # health exec checks this file's freshness, so stalled-or-dead
        # training goes catalog-critical by TTL expiry (the
        # reference's health semantics) while the in-process watchdog
        # handles the exit
        if args.heartbeat_file:
            with open(args.heartbeat_file, "w") as fh:
                fh.write(str(step))

    final_loss = None
    for step in range(start, args.steps):
        r_params, r_opt, loss = pstep(
            r_params, r_opt, jnp.asarray(global_batch_for(step))
        )
        final_loss = float(jax.device_get(loss)[0])
        dog.beat()
        progress_beat()
        host_state = {
            "params": jax.device_get(
                jax.tree.map(lambda x: x[0], r_params)
            ),
            "opt_state": jax.device_get(
                jax.tree.map(lambda x: x[0], r_opt)
            ),
        }
        # EVERY process saves in lockstep on the pod's ONE shared
        # directory: orbax's barrier is global and the primary process
        # writes the data (module docstring, parallel/checkpoint.py)
        save_checkpoint(args.checkpoint_dir, step + 1, host_state)
        dog.beat()
        print(f"worker {args.process_id}: step {step} loss "
              f"{final_loss:.5f}", flush=True)
        if step == args.crash_step and args.crash_sentinel:
            if not os.path.exists(args.crash_sentinel):
                with open(args.crash_sentinel, "w") as fh:
                    fh.write(str(step))
                print(f"worker {args.process_id}: injected crash after "
                      f"step {step}", flush=True)
                sys.stdout.flush()
                os._exit(1)
    dog.stop()

    digest = float(
        sum(
            np.abs(np.asarray(x, np.float64)).sum()
            for x in jax.tree.leaves(host_state["params"])
        )
    )
    with open(args.out, "w") as fh:
        json.dump(
            {
                "process_id": args.process_id,
                "final_loss": final_loss,
                "params_digest": digest,
                "resumed_from": start,
            },
            fh,
        )
    print(f"worker {args.process_id}: done (loss {final_loss:.5f})",
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
