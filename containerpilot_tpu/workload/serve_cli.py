"""CLI for the supervised inference server: argument surface, model
loading (checkpoint / EMA / LoRA merge / int8), and the serve loop.

``python -m containerpilot_tpu.workload.serve`` lands here via
serve.main (kept there so supervisor job configs and docs keep one
import path).
"""
from __future__ import annotations

import argparse
import asyncio

import jax

from ..models.transformer import (
    TransformerConfig,
    init_params,
    serving_params,
)
from .modelcfg import (
    derive_d_ff,
    merge_lora,
    restore_params_only,
    validate_lora_flags,
)


def _slot_count(raw: str) -> int:
    """--slots: a pool size of at least 1 (0 used to mean "no slot
    engine"; every request rides one now)."""
    slots = int(raw)
    if slots < 1:
        raise argparse.ArgumentTypeError(
            "must be >= 1 (the slot pool's size; every generate "
            "request rides the slot engine)"
        )
    return slots


def build_arg_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        description="supervised inference server"
    )
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8000)
    parser.add_argument(
        "--mux", default=True, action=argparse.BooleanOptionalAction,
        help="accept cp-mux/1 upgrades (the fleet gateway's "
        "multiplexed transport); --no-mux keeps this replica plain "
        "HTTP/1.1 and gateways fall back per-replica",
    )
    parser.add_argument(
        "--model-config", default="",
        help="build the model from a file of published config.json "
        "keys (modelcfg.load_model_file) instead of the width flags "
        "below; its weights are made and held in bfloat16. Rejects "
        "the flags that only the flagship block has",
    )
    parser.add_argument("--max-len", type=int, default=512)
    parser.add_argument("--d-model", type=int, default=256)
    parser.add_argument("--n-layers", type=int, default=2)
    parser.add_argument("--n-heads", type=int, default=4)
    parser.add_argument("--n-kv-heads", type=int, default=0,
                        help="GQA kv heads (0 = full multi-head); must "
                        "match the checkpoint being served")
    parser.add_argument("--window", type=int, default=0,
                        help="sliding-window attention; must match the "
                        "checkpoint being served. Decode KV memory "
                        "becomes a ring of `window` slots")
    parser.add_argument("--vocab", type=int, default=1024)
    parser.add_argument(
        "--checkpoint-dir", default="",
        help="load trained params from the latest checkpoint",
    )
    parser.add_argument(
        "--use-ema", action="store_true",
        help="serve the EMA shadow weights from the checkpoint "
        "(trained with --ema-decay) instead of the raw params",
    )
    parser.add_argument(
        "--int8", action="store_true",
        help="weight-only int8: ~4x smaller resident params",
    )
    parser.add_argument(
        "--kv-int8", action="store_true",
        help="int8 KV cache: halves decode KV memory vs bf16 "
        "(per-token-per-head scales; composes with GQA and --window)",
    )
    parser.add_argument(
        "--lora-dir", default="",
        help="merge a trained LoRA adapter checkpoint into the base "
        "weights at startup (zero runtime overhead); requires "
        "--lora-rank to match the adapter",
    )
    parser.add_argument(
        "--lora-rank", type=int, default=0,
        help="rank of the adapter in --lora-dir",
    )
    parser.add_argument(
        "--draft-layers", type=int, default=0,
        help="self-speculative decoding: draft with the model's first "
        "N layers; greedy single-sequence requests decode several "
        "tokens per target pass with identical output (0 = off)",
    )
    parser.add_argument(
        "--speculate", type=int, default=4,
        help="draft tokens proposed per verify round",
    )
    parser.add_argument(
        "--max-batch-rows", type=int, default=16,
        help="the most rows one request may carry: token rows, `n` "
        "samples, beam width (each row is one slot-engine sequence)",
    )
    parser.add_argument(
        "--prefill-chunk", type=int, default=0,
        help="stream prompts longer than N through chunked prefill "
        "(peak prefill activations O(N) instead of O(prompt)); 0 = "
        "one-shot prefill",
    )
    parser.add_argument(
        "--prefix-cache", type=int, default=0,
        help="prefix KV reuse: keep the KV caches of the last N "
        "prompts and re-prefill only the unseen suffix of single-row "
        "requests sharing a prefix (the chat/agent regime); 0 = off",
    )
    parser.add_argument(
        "--kv-spill-mb", type=float, default=0.0,
        help="host-RAM KV spill tier budget in MiB: prefix-cache LRU "
        "evictions spill to host memory and readmit on a later match "
        "(device_put roundtrip instead of re-prefill); requires "
        "--prefix-cache; 0 = off",
    )
    parser.add_argument(
        "--text", action="store_true",
        help="enable the text surface: POST /v1/completions encodes "
        "prompts with the built-in byte-level tokenizer (requires "
        "--vocab >= 259)",
    )
    parser.add_argument(
        "--slots", type=_slot_count, default=4,
        help="the slot pool's size, a capacity (KV memory scales with "
        "it): every sampled sequence joins a running chunked decode "
        "over a pool of N slots, and sequences past N queue; at "
        "least 1. Composes with --window (per-slot ring caches), "
        "--cp (admissions ring long prompts), --prefill-chunk "
        "(piecewise admission), and --prefix-cache (admissions "
        "rewind+extend cached prefixes)",
    )
    parser.add_argument(
        "--slot-chunk", type=int, default=8,
        help="tokens decoded per slot-engine chunk between admissions",
    )
    parser.add_argument(
        "--slot-window", type=int, default=4,
        help="decode chunk-rounds fused into ONE device dispatch (a "
        "device-side loop with early exit): the host re-enters at "
        "chunk granularity only when an admission/cancel/stop "
        "decision is pending, so steady-state dispatches/token falls "
        "~K-fold; 1 = the classic one-dispatch-per-chunk loop. "
        "Trade-off: a request arriving mid-window waits up to "
        "window*slot-chunk tokens for a freed slot",
    )
    parser.add_argument(
        "--tp", type=int, default=1,
        help="tensor-parallel ways: shard the model over the first N "
        "local devices (heads/ffn/vocab partitioned, XLA inserts the "
        "collectives); 1 = single device",
    )
    parser.add_argument(
        "--cp", type=int, default=1,
        help="context-parallel prefill ways: long prompts ring "
        "their prefill over a seq axis of N local devices on "
        "admission to the slot engine; 1 = off. Composes with --tp "
        "(a seq x model mesh over cp*tp devices); rejects "
        "--draft-layers/--prefix-cache/--window",
    )
    parser.add_argument(
        "--cp-min-len", type=int, default=0,
        help="prompts at least this long take the --cp ring "
        "(default 8x the seq axis)",
    )
    # fleet membership: register this replica in the discovery
    # catalog with a TTL heartbeat so a FleetGateway
    # (python -m containerpilot_tpu.fleet) routes to it; deregisters
    # on SIGTERM, and a crash expires critical by TTL
    parser.add_argument(
        "--fleet-catalog", default="",
        help="join an inference fleet: discovery backend URI "
        "('file:/shared/catalog' or 'consul:8500'); empty = lone "
        "replica (no registration)",
    )
    parser.add_argument(
        "--fleet-service", default="inference",
        help="service name to register under",
    )
    parser.add_argument(
        "--fleet-ttl", type=int, default=10,
        help="TTL seconds on the catalog health check",
    )
    parser.add_argument(
        "--fleet-address", default="127.0.0.1",
        help="address to advertise in the catalog",
    )
    parser.add_argument(
        "--fleet-id", default="",
        help="instance id in the catalog (default: "
        "<service>-<random>)",
    )
    parser.add_argument(
        "--migrate-window", type=float, default=5.0,
        help="seconds a drain spends migrating this replica's live "
        "KV prefixes to the digest-coldest healthy survivors (the "
        "handoff wire in reverse) before deregistering; sessions "
        "reconnect warm instead of re-prefilling cold. 0 disables "
        "migration (plain drain). Timeouts, dead targets and "
        "poisoned chunks fall back to re-prefill, counted, never a "
        "client error",
    )
    # cold-start collapse knobs (fleet/standby.py, docs/60): boot as
    # promotable warm capacity, fetch weights from a warm peer
    parser.add_argument(
        "--standby", action="store_true",
        help="boot as a warm STANDBY: load weights, warmup-compile, "
        "register under role=standby (heartbeating, never routed "
        "to); POST /v3/standby/promote flips it active in "
        "milliseconds — the autoscaler's fast scale-up path",
    )
    parser.add_argument(
        "--role", default="mixed",
        choices=("mixed", "prefill", "decode"),
        help="phase specialization for a disaggregated fleet "
        "(docs/60): 'prefill' replicas take fresh prompts and ship "
        "the resulting KV prefix to a decode peer over cp-mux/1; "
        "'decode' replicas run token generation off handed-off "
        "prefixes; 'mixed' (default) serves both phases — existing "
        "fleets are untouched. Routing advice only: every role "
        "serves any request it receives. --standby wins over this",
    )
    parser.add_argument(
        "--weights-from", default="",
        help="fetch model weights from an already-warm peer replica "
        "(host:port) over cp-mux/1 instead of reading a checkpoint "
        "— digest-verified chunks with one resume redial; ANY "
        "failure falls back to the normal --checkpoint-dir/init "
        "load",
    )
    return parser


def _serving_mesh(tp: int, cp: int = 1):
    """The mesh model loading/sharding lands on: an explicit --tp N
    builds a pure tensor-parallel mesh over the first N local
    devices; --cp adds a seq axis for context-parallel prefill
    (params shard over model and replicate over seq, so the SAME
    mesh serves both the ring prefill and the tp decode). Without
    either flag a replica is ONE device, the first, whatever the host
    holds: nothing is sharded, a restored checkpoint lands where a
    fresh init does, and the other chips stay free."""
    from ..parallel import MeshPlan, make_mesh

    tp, cp = max(tp, 1), max(cp, 1)
    devices = jax.devices()
    if tp == 1 and cp == 1:
        return make_mesh(devices[:1], plan=MeshPlan(data=1, model=1))
    if tp * cp > len(devices):
        raise SystemExit(
            f"--tp {tp} x --cp {cp} exceeds the {len(devices)} "
            "local devices"
        )
    if cp > 1:
        return make_mesh(
            devices[: tp * cp],
            plan=MeshPlan(data=1, model=tp, seq=cp),
        )
    return make_mesh(devices[:tp], plan=MeshPlan(data=1, model=tp))


def _validate_tp(cfg: TransformerConfig, tp: int) -> None:
    """Every axis the partition rules put on the model axis must
    divide by tp — fail with a clean message at startup, not a raw
    ValueError deep inside device_put/orbax (sharding.py
    param_sharding_rules: heads, d_ff and vocab are model-sharded;
    GQA KV replicates when tp does not divide it)."""
    for name, size in (
        ("n_heads", cfg.n_heads),
        ("d_ff", cfg.d_ff),
        ("vocab", cfg.vocab_size),
    ):
        if size % tp:
            raise SystemExit(f"--tp {tp} must divide {name} ({size})")


def _load_model_file(args: argparse.Namespace):
    """``--model-config``: the configuration from the file, seeded
    weights from the family's own ``init_params``, one device. What
    only the flagship block implements is refused by name."""
    from .modelcfg import load_model_file

    for flag, on in (
        ("--checkpoint-dir", args.checkpoint_dir),
        ("--int8", args.int8), ("--kv-int8", args.kv_int8),
        ("--lora-dir", args.lora_dir), ("--window", args.window),
        ("--draft-layers", args.draft_layers),
        ("--tp", (getattr(args, "tp", 1) or 1) > 1),
        ("--cp", (getattr(args, "cp", 1) or 1) > 1),
        ("--weights-from", getattr(args, "weights_from", "")),
    ):
        if on:
            raise SystemExit(
                f"--model-config does not compose with {flag} yet"
            )
    cfg = load_model_file(args.model_config, args.max_len)
    params = cfg.family.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params, _serving_mesh(1, 1)


def load_model(args: argparse.Namespace):
    """Build the config and load/transform params per the flags.
    Returns (cfg, params, mesh) — the ONE mesh everything landed on
    (checkpoint restore, shard, LoRA merge, and the --cp ring must
    share a device set or cross-mesh ops are uncompilable)."""
    if getattr(args, "model_config", ""):
        return _load_model_file(args)
    cfg = TransformerConfig(
        vocab_size=args.vocab,
        d_model=args.d_model,
        n_heads=args.n_heads,
        n_kv_heads=args.n_kv_heads,
        n_layers=args.n_layers,
        d_ff=derive_d_ff(args.d_model),
        max_seq_len=args.max_len,
        window=args.window,
        kv_int8=args.kv_int8,
    )
    tp = getattr(args, "tp", 1) or 1
    cp = getattr(args, "cp", 1) or 1
    if tp > 1:
        _validate_tp(cfg, tp)
    # ONE mesh for everything loaded here: checkpoint restore, the
    # fresh-init shard, the LoRA adapter, AND the --cp ring must
    # share a device set or cross-mesh ops are uncompilable
    mesh = _serving_mesh(tp, cp)
    if tp > 1 and cp == 1:
        # a pallas kernel does not partition under automatic sharding
        # (Mosaic refuses it at compile time on the chip; the CPU
        # tests' interpreted kernels never showed it): a prompt at or
        # past the flash crossover must run the kernel under
        # shard_map over the head-sharded model axis, the same
        # binding the mesh-parallel trainer uses
        from ..parallel.context import flash_parallel_config

        cfg = flash_parallel_config(cfg, mesh)
    params = None
    if args.checkpoint_dir:
        # shared with the evaluate CLI (workload/modelcfg.py):
        # params-only restore, so the server never pays train-state
        # memory
        restored = restore_params_only(
            cfg, mesh, args.checkpoint_dir, use_ema=args.use_ema
        )
        if restored is not None:
            params, step = restored
            print(f"serving checkpoint step {step}"
                  + (" (EMA weights)" if args.use_ema else ""))
    if params is None:
        params = init_params(jax.random.PRNGKey(0), cfg)
        if tp > 1:
            from ..parallel import shard_params

            params = shard_params(params, mesh, cfg)
    validate_lora_flags(args.lora_dir, args.lora_rank)
    if args.lora_dir:
        params, lora_step_n = merge_lora(
            params, cfg, mesh, args.lora_dir, args.lora_rank
        )
        print(f"merged lora adapter (rank {args.lora_rank}, "
              f"step {lora_step_n})")
    if args.int8:
        from ..models.quantized import param_bytes, quantize_model_params

        before = param_bytes(params)
        params = quantize_model_params(params)
        print(
            f"int8: params {before} -> {param_bytes(params)} bytes "
            f"({before / param_bytes(params):.1f}x smaller)"
        )
    else:
        # the resident form is the read form (the int8 path above
        # quantizes from float32 and keeps its own)
        params = serving_params(params, cfg)
    return cfg, params, mesh


def main() -> int:
    import logging

    from ..telemetry.goodput import process_start_monotonic
    from .modelcfg import enable_compile_cache
    from .serve import InferenceServer

    # the server's operational lines (listening, warm/accepting
    # traffic, slot frees) exist for the SUPERVISOR's log collection;
    # without a handler they vanish
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(message)s",
    )
    args = build_arg_parser().parse_args()
    backend = None
    if getattr(args, "fleet_catalog", ""):
        from ..discovery.factory import new_backend

        backend = new_backend(args.fleet_catalog)
        if backend is None:
            raise SystemExit(
                "--fleet-catalog resolved to no discovery backend"
            )
    # compile cache (workload/modelcfg.py): placed from outside by
    # JAX_COMPILATION_CACHE_DIR, else the checkout's fixed directory —
    # BEFORE model load, so every compile this process does lands in it
    cache_dir = enable_compile_cache()
    # peer weight transfer (fleet/standby.py): fetch the params from
    # a warm peer over cp-mux/1 — digest-verified, one resume redial
    # — INSTEAD of paying the checkpoint restore; the init-only tree
    # (same shapes/shardings/transforms, cheap) is the template the
    # fetch lands on. Fallback chain: peer -> checkpoint -> init —
    # a failed transfer re-runs the full disk load, so the fast path
    # is never a new way to fail a boot.
    weights_from = getattr(args, "weights_from", "")
    checkpoint_dir = args.checkpoint_dir
    if weights_from:
        host, _, port_s = weights_from.rpartition(":")
        if not port_s.isdigit():
            raise SystemExit(
                f"--weights-from wants host:port, got {weights_from!r}"
            )
        args.checkpoint_dir = ""  # skip the restore the peer replaces
    cfg, params, mesh = load_model(args)
    cp = getattr(args, "cp", 1) or 1
    if weights_from:
        from ..fleet.standby import fetch_params

        fetched = asyncio.run(
            fetch_params(host or "127.0.0.1", int(port_s), params)
        )
        if fetched is not None:
            params = fetched
            print(f"weights fetched from peer {weights_from}")
        elif checkpoint_dir:
            print(
                "peer weight transfer failed; falling back to the "
                "checkpoint restore"
            )
            args.checkpoint_dir = checkpoint_dir
            cfg, params, mesh = load_model(args)
        else:
            print(
                "peer weight transfer failed; serving freshly "
                "initialized weights"
            )
    # the EXACT mesh the params loaded onto: the ring and the params
    # must share one device set (and do, structurally)
    cp_mesh = mesh if cp > 1 else None
    # role resolution: --standby wins (a standby is promotable warm
    # capacity regardless of what it will specialize into); "mixed"
    # maps to the internal "active" so fleets that never pass --role
    # emit the exact notes/registrations they always did
    if getattr(args, "standby", False):
        role = "standby"
    else:
        role = getattr(args, "role", "mixed")
        if role == "mixed":
            role = "active"
    server = InferenceServer(
        cfg, params, args.host, args.port, args.max_len,
        draft_layers=args.draft_layers, speculate=args.speculate,
        max_batch_rows=args.max_batch_rows,
        prefix_cache_entries=args.prefix_cache,
        kv_spill_bytes=int(args.kv_spill_mb * 1024 * 1024),
        prefill_chunk=args.prefill_chunk,
        slots=args.slots, slot_chunk=args.slot_chunk,
        slot_window=args.slot_window,
        text=args.text,
        cp_mesh=cp_mesh, cp_min_len=getattr(args, "cp_min_len", 0),
        mux=args.mux,
        role=role,
        compile_cache_dir=cache_dir,
        # the ledger's ``boot`` starts with the process, not here
        # after jax start and weight init
        started_at=process_start_monotonic(),
    )
    member = None
    if backend is not None:
        from ..fleet import FleetMember

        member = FleetMember(
            server, backend, args.fleet_service,
            ttl=args.fleet_ttl, address=args.fleet_address,
            instance_id=args.fleet_id,
            migrate_window=args.migrate_window,
        )

    async def serve() -> None:
        import signal as signal_mod

        await server.run()
        if member is not None:
            # after run(): a --port 0 bind has resolved, and the
            # heartbeat only fires once warmup flips ready
            await member.start()
        stop = asyncio.Event()
        loop = asyncio.get_event_loop()
        for sig in (signal_mod.SIGTERM, signal_mod.SIGINT):
            loop.add_signal_handler(sig, stop.set)
        await stop.wait()
        if member is not None:
            # SIGTERM is a DRAIN, not an eviction: migrate live KV
            # to the survivors inside --migrate-window, flush the
            # mg= landings, deregister, finish in-flight — the same
            # path an autoscaler retire takes. Any migration failure
            # inside drain() degrades to the plain deregister this
            # branch used to be.
            await member.drain(timeout=30.0)
            await member.stop(deregister=False)
        await server.stop()

    asyncio.run(serve())
    return 0
