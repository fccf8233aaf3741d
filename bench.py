"""Benchmarks: supervisor dispatch latency + TPU workload performance.

Two halves, matching what this framework is:

1. **Supervisor job-dispatch latency** (the BASELINE.md contract).
   The reference supervisor publishes no benchmarks; its documented
   perf contract is the expected 20-50ms fork/exec round trip on
   commodity container hosts (reference docs/30-configuration/
   34-jobs.md:126,137,207). Measured end-to-end through the REAL
   stack: job built, subscribed to a fresh bus, event loop started,
   GLOBAL_STARTUP published, child spawned, exit observed,
   stopping/stopped cleanup completed.

2. **TPU workload performance** (run when a TPU backend is present):
   - a flagship-model training step: tokens/sec and model FLOPs
     utilization (MFU, PaLM-style 6N + 12*L*d*s accounting against
     the chip's bf16 peak);
   - pallas flash attention (fwd+bwd) vs the XLA einsum path at
     2k/4k/8k sequence lengths;
   - int8 weight-quantized GEMM (pallas fused dequant) vs bf16;
   - KV-cache generation throughput at batch 1 vs batch 8 (the
     continuous-batching multiplier).

   Every workload bench runs in its own child process, one at a
   time, and needs the TPU: a child that finds another platform
   fails, a failed child fails the run (its whole stderr is kept),
   and no metric line is printed.

Prints ONE JSON line:
    {"metric": ..., "value": <median ms>, "unit": "ms",
     "vs_baseline": r, "extras": {...workload numbers...}}
vs_baseline = 35ms (the documented expectation's midpoint) / measured —
above 1.0 means faster dispatch than the reference's stated envelope.
The workload numbers live in "extras" on the same line so the driver
records them in BENCH_r{N}.json.
"""
from __future__ import annotations

import asyncio
import json
import logging
import statistics
import time

from containerpilot_tpu.events import EventBus, GLOBAL_STARTUP
from containerpilot_tpu.jobs import Job, JobConfig

def _time_ms(fn, *args, n: int = 5) -> float:
    """The autotuner's timing (ops/autotune.py time_ms), imported
    where a bench child calls it: the launcher process imports
    nothing that imports jax."""
    from containerpilot_tpu.ops.autotune import time_ms

    return time_ms(fn, *args, n=n)


BASELINE_MS = 35.0  # midpoint of the reference's documented 20-50ms
MFU_TARGET = 0.35   # the docs/50-workload.md "MFU target" contract
# (v5e, seq 2048 / batch 8 bench config); training_bench stamps its
# measurement with meets_target so BENCH_r{N}.json self-reports
CYCLES = 60
WARMUP = 5

# MFU denominator lives with the workload half; see
# containerpilot_tpu/workload/flops.py for the per-generation table


async def one_cycle() -> float:
    bus = EventBus()
    job = Job(JobConfig({"name": "bench", "exec": "/bin/true"}).validate(None))
    job.subscribe(bus)
    job.register(bus)
    task = job.run()
    start = time.perf_counter()
    bus.publish(GLOBAL_STARTUP)
    await bus.wait()  # full lifecycle: spawn -> exit -> cleanup
    await task
    return (time.perf_counter() - start) * 1e3


async def dispatch_bench() -> float:
    samples = []
    for i in range(CYCLES + WARMUP):
        ms = await one_cycle()
        if i >= WARMUP:
            samples.append(ms)
    return statistics.median(samples)


# ---------------------------------------------------------------------------
# TPU workload benches
# ---------------------------------------------------------------------------


def _peak_flops(device_kind: str) -> float:
    from containerpilot_tpu.workload.flops import peak_flops

    return peak_flops(device_kind)


def training_bench() -> dict:
    """One-chip flagship training step: tokens/sec + MFU."""
    import jax
    import jax.numpy as jnp

    from containerpilot_tpu.models.transformer import (
        TransformerConfig,
        init_params,
        loss_fn,
    )
    from containerpilot_tpu.parallel import (
        MeshPlan,
        init_train_state,
        make_mesh,
        make_train_step,
    )

    from containerpilot_tpu.workload.flops import train_flops_per_token

    batch, seq = 8, 2048
    base = dict(
        vocab_size=32_768,
        d_model=1024,
        n_heads=8,
        n_layers=8,
        d_ff=4096,
        max_seq_len=seq,
        # AUTO: the measured crossover decides flash vs XLA per shape,
        # and tuned blocks apply (ops/tuning.py) — the MFU recorded
        # here is the framework's best honest number, not a fixed path
        flash_min_seq=-1,
    )
    mesh = make_mesh(jax.devices()[:1], plan=MeshPlan(1, 1))
    device_kind = jax.devices()[0].device_kind

    def measure_variant(remat, loss_chunk: int = 0) -> dict:
        cfg = TransformerConfig(
            remat=remat, loss_chunk=loss_chunk, **base
        )
        state = init_train_state(jax.random.PRNGKey(0), cfg, mesh)
        step = make_train_step(cfg, mesh)
        n_params = sum(
            p.size for p in jax.tree_util.tree_leaves(state.params)
        )
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (batch, seq + 1), 0,
            cfg.vocab_size, jnp.int32,
        )
        # warm-up/compile + 2 steps, then timed steps
        for _ in range(2):
            state, loss = step(state, tokens)
        loss.block_until_ready()
        n = 5
        t0 = time.perf_counter()
        for _ in range(n):
            state, loss = step(state, tokens)
        loss.block_until_ready()
        step_s = (time.perf_counter() - t0) / n
        tokens_per_sec = batch * seq / step_s
        flops_per_token = train_flops_per_token(cfg, n_params, seq)
        return {
            "model_params": n_params,
            "step_ms": round(step_s * 1e3, 2),
            "tokens_per_sec": round(tokens_per_sec, 1),
            # model-FLOPs utilization: remat recompute is NOT billed
            # (standard MFU), so cheaper remat shows up as higher MFU
            "mfu": round(
                flops_per_token * tokens_per_sec
                / _peak_flops(device_kind), 4,
            ),
        }

    # remat policies trade HBM for recompute; measure what fits and
    # headline the best. A variant that does not fit the chip's HBM
    # is recorded as such and the sweep goes on; any other failure
    # is a fault in the program and fails the bench.
    variants: dict = {}
    for name, remat, loss_chunk in (
        ("full", True, 0),
        ("dots", "dots", 0),
        ("none", False, 0),
        # chunked cross-entropy: the 32k-vocab logits tensor is the
        # single biggest activation at this config (~2 GB f32);
        # streaming the loss head may buy more than it recomputes
        ("dots+xent512", "dots", 512),
    ):
        try:
            variants[name] = measure_variant(remat, loss_chunk)
        except Exception as exc:  # noqa: BLE001
            msg = f"{type(exc).__name__}: {exc}"
            if "RESOURCE_EXHAUSTED" not in msg:
                raise
            variants[name] = {"error": msg}
    ok = {k: v for k, v in variants.items() if "mfu" in v}
    if not ok:
        raise RuntimeError(
            f"no remat variant fit the device: {json.dumps(variants)}"
        )
    best_name = max(ok, key=lambda k: ok[k]["mfu"])
    best = ok[best_name]
    return {
        "batch": batch,
        "seq": seq,
        "remat_variants": variants,
        "best_remat": best_name,
        **best,
        # the stated perf contract (docs/50-workload.md "MFU target"):
        # the measurement carries its own verdict so the artifact is
        # self-evidencing
        "target_mfu": MFU_TARGET,
        "meets_target": best["mfu"] >= MFU_TARGET,
        "device": device_kind,
    }


def attention_bench() -> dict:
    """pallas flash (fwd + bwd) vs XLA einsum at 2k/4k/8k."""
    import jax
    import jax.numpy as jnp

    from containerpilot_tpu.ops import causal_attention, flash_attention
    from containerpilot_tpu.ops import tuning

    out: dict = {}
    b, h, hd = 2, 8, 128
    for s in (2048, 4096, 8192):
        ks = jax.random.split(jax.random.PRNGKey(s), 4)
        q, k, v = (
            jax.random.normal(kk, (b, s, h, hd), jnp.bfloat16)
            for kk in ks[:3]
        )
        cot = jax.random.normal(ks[3], (b, s, h, hd), jnp.bfloat16)

        # blocks from the platform's tuned table (ops/tuning.py;
        # 128/128 when none is shipped) — fwd and train tuned apart
        fq, fk = tuning.pick_blocks("fwd", s)
        tq, tk = tuning.pick_blocks("train", s)
        flash_f = jax.jit(
            lambda q, k, v: flash_attention(q, k, v, block_q=fq, block_k=fk)
        )
        xla_f = jax.jit(causal_attention)
        flash_g = jax.jit(
            jax.grad(
                lambda q, k, v: jnp.sum(
                    (
                        flash_attention(q, k, v, block_q=tq, block_k=tk)
                        * cot
                    ).astype(jnp.float32)
                ),
                argnums=(0, 1, 2),
            )
        )
        xla_g = jax.jit(
            jax.grad(
                lambda q, k, v: jnp.sum(
                    (causal_attention(q, k, v) * cot).astype(jnp.float32)
                ),
                argnums=(0, 1, 2),
            )
        )
        n = 5 if s < 8192 else 3
        out[str(s)] = {
            "blocks_fwd": [fq, fk],
            "blocks_train": [tq, tk],
            "flash_fwd_ms": round(_time_ms(flash_f, q, k, v, n=n), 2),
            "xla_fwd_ms": round(_time_ms(xla_f, q, k, v, n=n), 2),
            "flash_grad_ms": round(
                _time_ms(lambda *a: flash_g(*a)[0], q, k, v, n=n), 2
            ),
            "xla_grad_ms": round(
                _time_ms(lambda *a: xla_g(*a)[0], q, k, v, n=n), 2
            ),
        }
    e8k = out["8192"]
    out["fwd_speedup_8k"] = round(e8k["xla_fwd_ms"] / e8k["flash_fwd_ms"], 2)
    out["grad_speedup_8k"] = round(
        e8k["xla_grad_ms"] / e8k["flash_grad_ms"], 2
    )
    # sliding window at 8k (window 1024): the kernels' kv-grid shrinks
    # to the contributing span, so fwd+bwd cost tracks O(s*window)
    ks = jax.random.split(jax.random.PRNGKey(81920), 3)
    q, k, v = (
        jax.random.normal(kk, (b, 8192, h, hd), jnp.bfloat16)
        for kk in ks
    )
    # full-causal tuned blocks don't transfer to windows: each q
    # block's kv span is window + block_q - 1, so a big block_q
    # inflates windowed work. Sweep a few candidates and report the
    # best (the windowed answer to the tuned table).
    win_ms, win_blocks = None, None
    for wq_b, wk_b in ((128, 128), (128, 512), (256, 512), (512, 512)):
        win_f = jax.jit(
            lambda q, k, v, a=wq_b, b_=wk_b: flash_attention(
                q, k, v, a, b_, None, 1024
            )
        )
        ms = _time_ms(win_f, q, k, v, n=3)
        if win_ms is None or ms < win_ms:
            win_ms, win_blocks = ms, [wq_b, wk_b]
    out["win1024_fwd_8k_ms"] = round(win_ms, 2)
    out["win1024_blocks"] = win_blocks
    # ratio from the unrounded value: the display rounding can hit 0.0
    out["win_fwd_speedup_8k"] = round(e8k["flash_fwd_ms"] / win_ms, 2)
    return out


def int8_bench() -> dict:
    """Fused-dequant int8 pallas GEMM vs the bf16 MXU GEMM.

    Measured at a serving-decode shape (small batch, big weights):
    that regime is weight-streaming bound, which is exactly what int8
    halves. Large-batch GEMMs are MXU-bound and int8 weight-only
    quantization does not speed those up.
    """
    import jax
    import jax.numpy as jnp

    from containerpilot_tpu.ops import int8_matmul_padded, quantize_int8

    m, k, n = 64, 4096, 14336  # decode microbatch through a big FFN
    x = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
    w_q, scales = quantize_int8(w)
    w_bf = w.astype(jnp.bfloat16)

    bf16_f = jax.jit(
        lambda x, w: jnp.dot(x, w, preferred_element_type=jnp.float32)
    )
    # the padded variant is the serving path for sub-tile microbatches
    # (m=64 < the 128-row tile; rows pad up and slice back)
    int8_f = jax.jit(lambda x, wq, s: int8_matmul_padded(x, wq, s))
    bf16_ms = _time_ms(bf16_f, x, w_bf, n=20)
    int8_ms = _time_ms(int8_f, x, w_q, scales, n=20)
    return {
        "shape": f"{m}x{k}x{n}",
        "bf16_ms": round(bf16_ms, 3),
        "int8_pallas_ms": round(int8_ms, 3),
        "speedup": round(bf16_ms / int8_ms, 2),
    }


def _decode_setup(cfg):
    """(cfg, params, label) for the decode-shaped benches. The default
    is ~1.2B params, ~2.4 GB bf16: decode is weight-streaming bound,
    which is the regime both the throughput and the admission bench
    measure."""
    import jax

    from containerpilot_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )

    label = "1.2B bf16"
    if cfg is None:
        cfg = TransformerConfig(
            vocab_size=32768, d_model=2048, n_heads=16, n_layers=16,
            d_ff=8192, max_seq_len=1024,
        )
    else:
        label = "override"
    return cfg, init_params(jax.random.PRNGKey(0), cfg), label


def decode_bench(cfg=None, max_new: int = 64, prompt_len: int = 128) -> dict:
    """KV-cache generation throughput at serving shapes: batch 1 (the
    latency regime) and batch 8 (the continuous-batching regime).
    Decode streams the model's weights from HBM once per step no
    matter how many rows ride along, so the b8/b1 ratio is the
    throughput multiplier request coalescing buys. Each timed call is
    a full generate(): prefill of the 128-token prompt + 64 greedy
    decode steps through the jitted scan. ``cfg`` override exists for
    the CPU plumbing test; the default is the measured config.

    The slot-admission comparison lives in ``slot_admission_bench``
    (its own subprocess + timeout): the two together were structurally
    over one 900s budget — ~10 heavyweight compiles of the 1.2B
    program set — which timed out the whole bench and lost BOTH
    measurements."""
    import jax.numpy as jnp

    from containerpilot_tpu.models.decode import generate

    cfg, params, label = _decode_setup(cfg)
    max_len = prompt_len + max_new * 2

    def gen(prompt):
        return generate(
            params, prompt, cfg, max_new_tokens=max_new, max_len=max_len
        )

    out: dict = {
        "model": f"{label}, prompt {prompt_len}, {max_new} new tokens"
    }
    for b in (1, 8):
        prompt = jnp.ones((b, prompt_len), jnp.int32)
        ms = _time_ms(gen, prompt, n=3)
        out[f"b{b}_tok_s"] = round(b * max_new / (ms / 1e3), 1)
    out["batch_throughput_x"] = round(
        out["b8_tok_s"] / out["b1_tok_s"], 2
    )
    return out


def slot_admission_bench(cfg=None, max_new: int = 64,
                         prompt_len: int = 128) -> dict:
    """Slot-engine admission latency: a SHORT request arriving while a
    LONG one decodes. Sequentially it waits for the whole long
    generation; through the slot pool it joins at the next chunk
    boundary. Reported: the short request's completion latency both
    ways (the admission win is the ratio)."""
    import jax.numpy as jnp

    from containerpilot_tpu.models.decode import generate
    from containerpilot_tpu.workload.serve_slots import SlotEngine

    cfg, params, label = _decode_setup(cfg)
    out: dict = {"model": label}
    short_new, long_new = 16, max_new * 2
    slot_max_len = prompt_len + long_new
    engine = SlotEngine(
        cfg, params, slot_max_len, slots=2, chunk=8
    )
    try:
        # warm both prompt-length prefills and the chunk program
        engine.submit([1] * prompt_len, max_new=2).result(timeout=600)
        engine.submit([1] * 8, max_new=2).result(timeout=600)
        t0 = time.perf_counter()
        long_fut = engine.submit([1] * prompt_len, max_new=long_new)
        short_fut = engine.submit([2] * 8, max_new=short_new)
        short_fut.result(timeout=600)
        slot_short_ms = (time.perf_counter() - t0) * 1e3
        long_fut.result(timeout=600)
    finally:
        engine.stop()
    # sequential reference: the short request queued behind the long
    # generation pays the whole long run first. generate compiles one
    # program per max_new, so warm with the EXACT max_new values the
    # timed region runs — warming with any other value would leave
    # two compilations inside the timer and fabricate the speedup.
    long_prompt = jnp.ones((1, prompt_len), jnp.int32)
    short_prompt = jnp.full((1, 8), 2, jnp.int32)
    def long_then_short() -> None:
        for prompt, new in (
            (long_prompt, long_new), (short_prompt, short_new),
        ):
            generate(
                params, prompt, cfg, new, slot_max_len
            ).block_until_ready()

    long_then_short()
    t0 = time.perf_counter()
    long_then_short()
    seq_short_ms = (time.perf_counter() - t0) * 1e3
    out["short_latency_ms_sequential"] = round(seq_short_ms, 1)
    out["short_latency_ms_slots"] = round(slot_short_ms, 1)
    out["admission_speedup_x"] = round(
        seq_short_ms / max(slot_short_ms, 1e-3), 2
    )
    return out


def host_overhead_bench(rounds: int = 40) -> dict:
    """Per-round HOST overhead of the continuous-batching decode loop,
    runnable on ANY backend (tiny CPU-sized config) — the bench that
    finally puts a real number in BENCH_r{N}.json when no TPU is
    reachable.

    Three measurements share one compiled chunk program:

    - ``device``: pure ``decode_slots_chunk`` time, measured SERIALLY
      (dispatch + block per round).
    - ``legacy``: the pre-device-resident-state loop shape — every
      round re-uploads the 12 host numpy knob arrays (step_idx, temp,
      top_k, top_p, eos, pad, min_new, presence, frequency, bias_idx,
      bias_val, done) into the state dict, dispatches, SERIALLY
      fetches the tokens, advances step_idx on the host, then runs
      the append-chunk bookkeeping.
    - ``engine``: the REAL SlotEngine (device-resident state + one-
      round lookahead dispatch), measured through round_times_ms()
      over a long steady decode.

    Host overhead is measured DIRECTLY, in-round, on both sides —
    not inferred by subtracting two separately-run loops. Shared
    small hosts show 2-3x scheduler tail noise per ~100ms round;
    a cross-loop subtraction of ~1-2ms host work under +-50ms noise
    is sign-flips all the way down (observed: the legacy loop's
    median beating the pure-device loop's). Instead:

    - ``legacy_host_overhead_ms``: inside each legacy round, bracket
      the two host segments the old loop serialized with device
      compute — the 12 ``jnp.asarray`` knob uploads + op_state dict
      build before dispatch, and the step-advance + append-chunk
      bookkeeping after the serial fetch. Median of their sum.
    - ``engine_host_overhead_ms``: the engine brackets its own jax
      calls; ``round_host_ms()`` is round wall time minus the time
      inside the chunk dispatches and the token fetch (where any
      device wait lands — CPU's bounded in-flight queue blocks in
      the NEXT dispatch rather than in ``device_get``). What's left
      — queue/cancel checks, token copy-out, bookkeeping, streaming
      callbacks — is the same bracket shape as the legacy measure,
      minus the uploads the device-resident state made unnecessary.
      Median.

    The round wall medians/mins for all three loops are reported as
    context: with lookahead the engine's pipelined rounds track pure
    device time (host work hides under chunk N+1's compute), and
    ``engine_round_min_ms`` is a round whose lookahead chunk had
    already finished — fetch + bookkeeping only, no device wait.
    ``overhead_vs_legacy`` is the headline ratio — the PR's
    acceptance bar is <= 0.5.

    The ``fused`` arm sweeps the device-resident multi-round window
    (K rounds per host dispatch, ``decode_slots_window``) over
    K in {1, 4, 8} on one long steady-state decode each: per K it
    reports ms/round (window wall / K) and dispatches/token off the
    live engine counters, warm-admission dispatches excluded by
    snapshotting after the warm request. The headline is
    ``fused_k8_vs_k1_dispatch_ratio`` — the megakernel bar is
    <= 0.3 (steady-state dispatches/token must fall at least
    ~3.3x when 8 rounds fuse into one dispatch), ANDed into
    ``meets_target`` next to the legacy-vs-engine overhead bar,
    which keeps measuring the classic one-round engine
    (``window=1``) unchanged."""
    import os
    import statistics as stats_mod

    import jax

    import jax.numpy as jnp
    import numpy as np

    from containerpilot_tpu.models.decode import BIAS_SLOTS_MAX
    from containerpilot_tpu.models.slots import (
        append_chunk,
        decode_slots_chunk,
        init_slot_state,
        slot_cache,
    )
    from containerpilot_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )
    from containerpilot_tpu.workload.serve_slots import SlotEngine

    slots, chunk = 4, 16
    prompt_len = 8
    max_len = prompt_len + rounds * chunk + chunk
    cfg = TransformerConfig(
        vocab_size=256, d_model=128, n_heads=4, n_layers=2,
        d_ff=512, max_seq_len=max_len, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)

    def fresh():
        return (
            slot_cache(cfg, slots, max_len),
            init_slot_state(cfg, slots),
        )

    # --- pure device time: serial dispatch + block per round (see
    # docstring). A dead pool decodes the IDENTICAL program (done
    # only selects pad vs sampled token), so no admission is needed
    # here.
    pool, state = fresh()
    for _ in range(3):  # compile + settle
        pool, state, toks = decode_slots_chunk(
            params, pool, state, cfg, chunk
        )
    jax.block_until_ready(toks)
    dev_times: list = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        pool, state, toks = decode_slots_chunk(
            params, pool, state, cfg, chunk
        )
        jax.block_until_ready(toks)
        dev_times.append(time.perf_counter() - t0)

    # --- legacy loop: the pre-PR per-round host path, reproduced
    # faithfully against the same chunk program. last/keys/counts
    # stayed device-resident in the old loop too; the other 12 leaves
    # were host numpy re-uploaded via jnp.asarray EVERY round, the
    # token fetch was serial (no lookahead — nothing overlapped), and
    # step_idx advanced on the host.
    pool, state = fresh()
    step_idx = np.zeros((slots,), np.int32)
    temp = np.zeros((slots,), np.float32)
    top_k = np.zeros((slots,), np.int32)
    top_p = np.zeros((slots,), np.float32)
    eos = np.full((slots,), -1, np.int32)
    pad = np.zeros((slots,), np.int32)
    min_new = np.zeros((slots,), np.int32)
    presence = np.zeros((slots,), np.float32)
    frequency = np.zeros((slots,), np.float32)
    bias_idx = np.full((slots, BIAS_SLOTS_MAX), -1, np.int32)
    bias_val = np.zeros((slots, BIAS_SLOTS_MAX), np.float32)
    done = np.zeros((slots,), bool)
    emitted: list = [[] for _ in range(slots)]
    legacy_times: list = []
    legacy_host: list = []

    def legacy_round(record: bool) -> None:
        nonlocal pool, state, step_idx
        t0 = time.perf_counter()
        op_state = dict(
            state,
            step_idx=jnp.asarray(step_idx),
            temperature=jnp.asarray(temp),
            top_k=jnp.asarray(top_k),
            top_p=jnp.asarray(top_p),
            eos_id=jnp.asarray(eos),
            pad_id=jnp.asarray(pad),
            min_new=jnp.asarray(min_new),
            presence=jnp.asarray(presence),
            frequency=jnp.asarray(frequency),
            bias_idx=jnp.asarray(bias_idx),
            bias_val=jnp.asarray(bias_val),
            done=jnp.asarray(done),
        )
        t1 = time.perf_counter()  # host segment A: uploads
        pool, state, toks = decode_slots_chunk(
            params, pool, op_state, cfg, chunk
        )
        toks_host = np.asarray(jax.device_get(toks))  # serial fetch
        t2 = time.perf_counter()
        step_idx = step_idx + chunk  # host-side position bookkeeping
        for i in range(slots):
            append_chunk(
                emitted[i], toks_host[i], rounds * chunk + 1, -1
            )
        t3 = time.perf_counter()  # host segment B: bookkeeping
        if record:
            legacy_times.append(t3 - t0)
            legacy_host.append((t1 - t0) + (t3 - t2))

    for i in range(3):
        legacy_round(record=False)
    for _ in range(rounds):
        legacy_round(record=True)

    # --- the shipped engine: one long greedy request, decode-only
    # round wall times from the worker loop itself (admission rounds
    # excluded there). window=1 pins the CLASSIC one-dispatch-per-
    # round loop so the legacy-vs-engine host-overhead comparison
    # keeps measuring the same thing it always did; the fused sweep
    # below owns the multi-round story.
    engine = SlotEngine(
        cfg, params, max_len, slots=slots, chunk=chunk, window=1
    )
    try:
        # warm the prefill/admit programs so compile never lands in a
        # timed round
        engine.submit([1] * prompt_len, max_new=2).result(timeout=600)
        engine.submit(
            [1] * prompt_len, max_new=rounds * chunk
        ).result(timeout=600)
        engine_times = engine.round_times_ms()[-rounds:]
        engine_host = engine.round_host_ms()[-rounds:]
        # the dispatches/token series (ROADMAP: the megakernel work
        # must drive this DOWN — today it is ~(lookahead-doubled
        # rounds)/(chunk tokens); a device-side multi-round loop
        # collapses the numerator)
        eng_dispatches = engine.dispatches
        eng_tokens = engine.tokens_out
    finally:
        engine.stop()

    # --- fused-rounds sweep: K decode rounds per host dispatch via
    # the device-side window loop; dispatches/token is the headline
    # (ms/round rides along as context). Counters snapshot after the
    # warm request so admissions don't blur the steady-state ratio.
    fused: dict = {}
    for k_rounds in (1, 4, 8):
        eng_k = SlotEngine(
            cfg, params, max_len, slots=slots, chunk=chunk,
            window=k_rounds,
        )
        try:
            eng_k.submit([1] * prompt_len, max_new=2).result(
                timeout=600
            )
            base_d, base_t = eng_k.dispatches, eng_k.tokens_out
            eng_k.submit(
                [1] * prompt_len, max_new=rounds * chunk
            ).result(timeout=600)
            d = eng_k.dispatches - base_d
            t = eng_k.tokens_out - base_t
            window_times = eng_k.round_times_ms()[-rounds:]
            fused[f"k{k_rounds}"] = {
                "dispatches": d,
                "tokens_out": t,
                "dispatches_per_token": round(d / max(1, t), 4),
                # a steady-state window runs all K rounds; the tail
                # window may early-exit, so this slightly overstates
                # ms/round — fine for a trajectory number
                "round_ms": round(
                    stats_mod.median(window_times) / k_rounds, 3
                ),
                "window_ms": round(
                    stats_mod.median(window_times), 3
                ),
            }
        finally:
            eng_k.stop()
    fused_ratio = (
        fused["k8"]["dispatches_per_token"]
        / max(fused["k1"]["dispatches_per_token"], 1e-9)
    )

    device_ms = stats_mod.median(dev_times) * 1e3
    legacy_ms = stats_mod.median(legacy_times) * 1e3
    engine_ms = stats_mod.median(engine_times)
    legacy_over = stats_mod.median(legacy_host) * 1e3
    engine_over = stats_mod.median(engine_host)
    return {
        "backend": jax.default_backend(),
        "config": (
            f"{cfg.n_layers}L d{cfg.d_model} v{cfg.vocab_size}, "
            f"{slots} slots x {chunk}-token chunks, {rounds} rounds"
        ),
        "device_round_ms": round(device_ms, 3),
        "device_round_min_ms": round(min(dev_times) * 1e3, 3),
        "legacy_round_ms": round(legacy_ms, 3),
        "legacy_round_min_ms": round(min(legacy_times) * 1e3, 3),
        # in-round bracketed host segments (uploads + bookkeeping):
        # what the old loop serialized with device compute per round
        "legacy_host_overhead_ms": round(legacy_over, 3),
        "engine_round_ms": round(engine_ms, 3),
        # a lookahead round whose chunk already finished is fetch +
        # bookkeeping ONLY — no device wait
        "engine_round_min_ms": round(min(engine_times), 3),
        # round wall minus the engine's own bracketed jax calls:
        # the host work a shipped-engine round pays outside them
        "engine_host_overhead_ms": round(engine_over, 3),
        # host->device dispatches per emitted token over the engine's
        # whole run (warm admissions included): the megakernel
        # yardstick, recorded so BENCH_r{N}.json shows it falling
        "dispatches": eng_dispatches,
        "tokens_out": eng_tokens,
        "dispatches_per_token": round(
            eng_dispatches / max(1, eng_tokens), 4
        ),
        "overhead_vs_legacy": round(
            engine_over / max(legacy_over, 1e-9), 3
        ),
        # the device-resident multi-round sweep: K rounds fused into
        # one dispatch, dispatches/token falling ~K-fold
        "fused": fused,
        "fused_k8_vs_k1_dispatch_ratio": round(fused_ratio, 3),
        "fused_target_ratio": 0.3,
        # the PR's stated bar: the device-resident-state + lookahead
        # loop must at least halve per-round host overhead
        "target_ratio": 0.5,
        "meets_target": (
            engine_over <= 0.5 * legacy_over
            and fused_ratio <= 0.3
        ),
    }


def gateway_overhead_bench(rounds: int = 60) -> dict:
    """Per-request latency the fleet gateway adds over direct replica
    access — mux vs pooled vs per-dial, runnable on ANY backend (tiny
    CPU-sized config).

    Boots one in-process InferenceServer, registers it in a file
    catalog via a FleetMember, and fronts it with THREE gateways: one
    on the cp-mux/1 multiplexed transport (the default), one on the
    classic keep-alive connection pool (``mux=False``), one with
    reuse disabled entirely (``pool_max_idle=0``, the pre-pool
    behavior). Each round measures /v1/generate five ways,
    interleaved so scheduler drift hits every path equally:

    - direct per-dial (fresh ``Connection: close`` client per request)
    - direct keep-alive (one persistent client connection)
    - via the pool-disabled gateway over a per-dial client
    - via the pooled gateway over a keep-alive client
    - via the mux gateway over a keep-alive client
    - via an UNTRACED mux gateway (``trace=False``) over keep-alive

    ``gateway_added_pooled_ms`` vs ``gateway_added_mux_ms`` is PR 8's
    latency claim: multiplexing must cost nothing at concurrency 1.
    The burst probe after the latency rounds is its concurrency
    claim: C concurrent requests through the pooled gateway need ~C
    upstream sockets (one request per connection), while the mux
    gateway carries all C as interleaved streams on the one warm
    connection it already holds — ≥4x in-flight streams per upstream
    socket at a fixed socket count.

    The traced-vs-untraced pair is PR 9's claim: request tracing is
    ON by default (the ``gateway_mux`` path runs with it) and must be
    effectively free — the paired per-round median of traced minus
    untraced stays within 5% of the untraced median (floored at the
    0.1ms timer-noise tolerance), pinned in ``meets_target``. The
    pair isolates GATEWAY-side tracing (mint/propagate/splice/ring):
    both arms share one replica that always traces, so replica-side
    recording sits in the common baseline, not the measured delta —
    its per-request cost is a handful of float stamps plus one digest
    encode, bounded by the engine-timings no-per-token contract
    (tests) rather than by this bench."""
    import concurrent.futures
    import http.client
    import os
    import tempfile
    import urllib.request

    import jax
    import jax.numpy as jnp

    from containerpilot_tpu.discovery import FileCatalogBackend
    from containerpilot_tpu.fleet import FleetGateway, FleetMember
    from containerpilot_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = TransformerConfig(
        vocab_size=256, d_model=64, n_heads=4, n_layers=1, d_ff=256,
        max_seq_len=64, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    server = InferenceServer(cfg, params, "127.0.0.1", 0, max_len=64)
    body = json.dumps(
        {"tokens": [[1, 2, 3, 4]], "max_new_tokens": 8}
    ).encode()

    def post_dial(port: int) -> float:
        """urllib dials per request and sends Connection: close —
        exactly the pre-keep-alive client behavior."""
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/generate",
            data=body,
            headers={"Content-Type": "application/json"},
        )
        t0 = time.perf_counter()
        with urllib.request.urlopen(req, timeout=300) as resp:
            resp.read()
        return (time.perf_counter() - t0) * 1e3

    class _KeepAliveClient:
        """One persistent http.client connection, redialed at most
        once per post if the server reaped it between rounds."""

        def __init__(self, port: int) -> None:
            self.port = port
            self.conn = None

        def post(self) -> float:
            t0 = time.perf_counter()
            for _ in range(2):
                if self.conn is None:
                    self.conn = http.client.HTTPConnection(
                        "127.0.0.1", self.port, timeout=300
                    )
                try:
                    self.conn.request(
                        "POST", "/v1/generate", body,
                        {"Content-Type": "application/json"},
                    )
                    resp = self.conn.getresponse()
                    resp.read()
                    if resp.will_close:
                        self.close()
                    return (time.perf_counter() - t0) * 1e3
                except (ConnectionError, http.client.BadStatusLine):
                    self.close()
            raise RuntimeError("keep-alive post failed twice")

        def close(self) -> None:
            if self.conn is not None:
                self.conn.close()
                self.conn = None

    series: dict = {
        "direct_per_dial": [],
        "direct_keepalive": [],
        "gateway_per_dial": [],
        "gateway_pooled": [],
        "gateway_mux": [],
        "gateway_mux_untraced": [],
    }
    BURST_CONCURRENCY = 12
    burst: dict = {}
    with tempfile.TemporaryDirectory() as root:
        backend = FileCatalogBackend(root)

        async def scenario() -> None:
            loop = asyncio.get_event_loop()
            await server.run()
            member = FleetMember(
                server, backend, "bench-infer", ttl=30,
                heartbeat_interval=0.2,
            )
            await member.start()
            gw_mux = FleetGateway(
                backend, "bench-infer", "127.0.0.1", 0,
                poll_interval=0.2, hedge=False,
            )
            gw_mux_untraced = FleetGateway(
                backend, "bench-infer", "127.0.0.1", 0,
                poll_interval=0.2, hedge=False, trace=False,
            )
            gw_pooled = FleetGateway(
                backend, "bench-infer", "127.0.0.1", 0,
                poll_interval=0.2, hedge=False, mux=False,
            )
            gw_dial = FleetGateway(
                backend, "bench-infer", "127.0.0.1", 0,
                poll_interval=0.2, hedge=False, pool_max_idle=0,
                mux=False,
            )
            gateways = (gw_mux, gw_mux_untraced, gw_pooled, gw_dial)
            for gw in gateways:
                await gw.run()
            for _ in range(200):
                if all(gw.replica_count for gw in gateways):
                    break
                await asyncio.sleep(0.05)
            assert all(gw.replica_count == 1 for gw in gateways)
            ka_direct = _KeepAliveClient(server.port)
            ka_pooled = _KeepAliveClient(gw_pooled.port)
            ka_mux = _KeepAliveClient(gw_mux.port)
            ka_untraced = _KeepAliveClient(gw_mux_untraced.port)
            paths = (
                ("direct_per_dial", lambda: post_dial(server.port)),
                ("direct_keepalive", ka_direct.post),
                ("gateway_per_dial", lambda: post_dial(gw_dial.port)),
                ("gateway_pooled", ka_pooled.post),
                ("gateway_mux", ka_mux.post),
                ("gateway_mux_untraced", ka_untraced.post),
            )
            for _ in range(5):  # warm every path (compiles, routes)
                for _name, fn in paths:
                    await loop.run_in_executor(None, fn)
            for _ in range(rounds):
                for name, fn in paths:
                    series[name].append(
                        await loop.run_in_executor(None, fn)
                    )

            # concurrency probe at a FIXED socket count: fire C
            # concurrent requests per gateway and count the upstream
            # sockets the replica saw. Each gateway starts warm (one
            # mux conn / one pooled conn from the rounds above), so
            # the delta is what concurrency itself costs in sockets.
            pool = concurrent.futures.ThreadPoolExecutor(
                BURST_CONCURRENCY
            )
            try:
                http_server = server._server  # noqa: SLF001
                for name, gw in (("mux", gw_mux), ("pooled", gw_pooled)):
                    before = http_server.connections_accepted
                    await asyncio.gather(*[
                        loop.run_in_executor(
                            pool, post_dial, gw.port
                        )
                        for _ in range(BURST_CONCURRENCY)
                    ])
                    # warm conns carried over from the rounds plus
                    # whatever the burst had to dial
                    dialed = http_server.connections_accepted - before
                    sockets = max(1, dialed + 1)
                    burst[name] = {
                        "concurrency": BURST_CONCURRENCY,
                        "upstream_sockets": sockets,
                        "streams_per_socket": round(
                            BURST_CONCURRENCY / sockets, 2
                        ),
                    }
            finally:
                pool.shutdown(wait=False)
            ka_direct.close()
            ka_pooled.close()
            ka_mux.close()
            ka_untraced.close()
            for gw in gateways:
                await gw.stop()
            await member.stop()
            await server.stop()

        asyncio.run(scenario())

    med = {k: statistics.median(v) for k, v in series.items()}
    added_per_dial = med["gateway_per_dial"] - med["direct_per_dial"]
    added_pooled = med["gateway_pooled"] - med["direct_keepalive"]
    added_mux = med["gateway_mux"] - med["direct_keepalive"]
    # mux-vs-pooled at concurrency 1 is judged on PAIRED per-round
    # differences: the two paths run back-to-back inside each
    # interleaved round, so pairing cancels the scheduler drift that
    # dominates a difference of independent medians on a shared box.
    # The parity tolerance is explicit in the output: mux must sit
    # within timer-resolution noise of pooled, not beat it.
    paired = statistics.median([
        m - p
        for m, p in zip(series["gateway_mux"], series["gateway_pooled"])
    ])
    # tracing's cost, same paired discipline: the traced default-mux
    # path against the trace=False control, per interleaved round
    trace_paired = statistics.median([
        t - u
        for t, u in zip(
            series["gateway_mux"], series["gateway_mux_untraced"]
        )
    ])
    trace_tolerance = max(
        0.05 * med["gateway_mux_untraced"], 0.1
    )
    concurrency_ratio = (
        burst["mux"]["streams_per_socket"]
        / burst["pooled"]["streams_per_socket"]
        if burst.get("pooled", {}).get("streams_per_socket") else None
    )
    return {
        "backend": jax.default_backend(),
        "config": (
            f"{cfg.n_layers}L d{cfg.d_model} v{cfg.vocab_size}, "
            f"8 new tokens, {rounds} interleaved rounds"
        ),
        "direct_per_dial_ms": round(med["direct_per_dial"], 3),
        "direct_keepalive_ms": round(med["direct_keepalive"], 3),
        "gateway_per_dial_ms": round(med["gateway_per_dial"], 3),
        "gateway_pooled_ms": round(med["gateway_pooled"], 3),
        "gateway_mux_ms": round(med["gateway_mux"], 3),
        "gateway_mux_untraced_ms": round(
            med["gateway_mux_untraced"], 3
        ),
        "gateway_added_per_dial_ms": round(added_per_dial, 3),
        "gateway_added_pooled_ms": round(added_pooled, 3),
        "gateway_added_mux_ms": round(added_mux, 3),
        "gateway_added_per_dial_min_ms": round(
            min(series["gateway_per_dial"])
            - min(series["direct_per_dial"]), 3
        ),
        "gateway_added_pooled_min_ms": round(
            min(series["gateway_pooled"])
            - min(series["direct_keepalive"]), 3
        ),
        "gateway_added_mux_min_ms": round(
            min(series["gateway_mux"])
            - min(series["direct_keepalive"]), 3
        ),
        # PR 5's bar (recorded for the trajectory; its pass was
        # pinned in r05 and it is not this bench's gating claim)
        "target_ratio": 0.5,
        "pooled_over_per_dial": (
            round(added_pooled / added_per_dial, 3)
            if added_per_dial > 0 else None
        ),
        # PR 8's bars: mux adds no latency at concurrency 1 (paired
        # median within the stated parity tolerance of pooled), and
        # multiplies in-flight streams per upstream socket >= 4x
        "mux_over_pooled": (
            round(added_mux / added_pooled, 3)
            if added_pooled > 0 else None
        ),
        "mux_minus_pooled_paired_ms": round(paired, 3),
        "latency_parity_tolerance_ms": 0.1,
        # PR 9's bar: tracing is ON by default (gateway_mux runs
        # traced) and must be effectively free — paired median within
        # 5% of the untraced control (floored at timer noise)
        "traced_minus_untraced_paired_ms": round(trace_paired, 3),
        "trace_overhead_tolerance_ms": round(trace_tolerance, 3),
        "burst": burst,
        "mux_concurrency_ratio": concurrency_ratio,
        "concurrency_target_ratio": 4.0,
        "meets_target": (
            paired <= 0.1
            and trace_paired <= trace_tolerance
            and concurrency_ratio is not None
            and concurrency_ratio >= 4.0
        ),
    }


def goodput_ledger_bench(requests: int = 6, max_new: int = 96) -> dict:
    """The device-time ledger's accounting bench, runnable on ANY
    backend (tiny CPU-sized config): boot one real InferenceServer
    (slot engine on), drive a handful of buffered generations with a
    deliberate idle gap and one drain/resume cycle, and read the
    ledger back over its REAL surface (``GET /v1/goodput``). Records:

    - ``accounting_error_fraction``: |sum(per-stage seconds) -
      uptime| / uptime. The ledger closes by construction; the bench
      proves the shipped wiring (engine stamps, warmup override,
      drain override, HTTP read path) kept it closed — the
      every-device-second-attributed acceptance bar is 2%.
    - ``dispatches_per_token``: the megakernel yardstick off the
      live engine counters — fused multi-round decode (the default
      window=4 engine) must land well under the old one-dispatch-
      per-chunk floor (chunk=8 x window=4 measures ~0.04-0.1
      depending on admission mix; the pre-fusion loop sat at
      ~0.15-0.45).
    - stage sanity: compile_warmup seconds exist (stamped BEFORE
      /health flipped 200), idle covers the injected gap, drain
      covers the maintenance window, prefill+decode > 0.

    ``meets_target`` pins accounting_error_fraction <= 0.02 AND
    dispatches_per_token <= 0.2 (tightened from 0.5 when the fused
    window landed: the dispatch tax is the thing the megakernel work
    collapses, and the bar must fall with it) — the badput
    trajectory bar release-over-release (``make bench-goodput``)."""
    import asyncio
    import http.client
    import os

    import jax
    import jax.numpy as jnp

    from containerpilot_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_heads=2, n_layers=2, d_ff=128,
        max_seq_len=256, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    out: dict = {}

    async def scenario() -> None:
        server = InferenceServer(
            cfg, params, "127.0.0.1", 0, max_len=256,
            slots=4, slot_chunk=8,
        )
        await server.run()
        loop = asyncio.get_event_loop()

        def fetch(method: str, path: str, body: bytes = b"") -> bytes:
            conn = http.client.HTTPConnection(
                "127.0.0.1", server.port, timeout=120
            )
            try:
                conn.request(
                    method, path, body or None,
                    {"Content-Type": "application/json"}
                    if body else {},
                )
                resp = conn.getresponse()
                payload = resp.read()
                if resp.status != 200:
                    raise RuntimeError(
                        f"{path} -> {resp.status}: {payload[:120]!r}"
                    )
                return payload
            finally:
                conn.close()

        body = json.dumps(
            {"tokens": [[1, 2, 3, 4, 5, 6, 7, 8]],
             "max_new_tokens": max_new}
        ).encode()
        for _ in range(requests):
            await loop.run_in_executor(
                None, fetch, "POST", "/v1/generate", body
            )
        # a deliberate idle gap the ledger must attribute as idle
        await asyncio.sleep(0.5)
        # one drain/resume cycle: the maintenance window is drain
        server.enter_maintenance()
        await asyncio.sleep(0.2)
        server.exit_maintenance()
        gp = json.loads(
            await loop.run_in_executor(None, fetch, "GET", "/v1/goodput")
        )
        await server.stop()
        stages = gp["stages_s"]
        attributed = sum(stages.values())
        uptime = gp["uptime_s"]
        out.update(
            backend=jax.default_backend(),
            config=(
                f"{cfg.n_layers}L d{cfg.d_model} v{cfg.vocab_size}, "
                f"4 slots x 8-token chunks, {requests} x "
                f"{max_new}-token requests"
            ),
            uptime_s=round(uptime, 3),
            stages_s=stages,
            attributed_s=round(attributed, 3),
            accounting_error_fraction=round(
                abs(attributed - uptime) / max(uptime, 1e-9), 5
            ),
            productive_fraction=gp["productive_fraction"],
            dispatches=gp["dispatches"],
            tokens_out=gp["tokens_out"],
            dispatches_per_token=gp["dispatches_per_token"],
            scheduling_gaps=len(gp["scheduling_gaps"]),
            compile_warmup_s=stages["compile_warmup"],
            drain_s=stages["drain"],
        )

    asyncio.run(scenario())
    out["target"] = (
        "accounting_error_fraction <= 0.02 and "
        "dispatches_per_token <= 0.2 and every lifecycle stage "
        "(compile_warmup, idle, drain, prefill+decode) attributed"
    )
    out["meets_target"] = bool(
        out["accounting_error_fraction"] <= 0.02
        and out["dispatches_per_token"] is not None
        and out["dispatches_per_token"] <= 0.2
        and out["compile_warmup_s"] > 0.0
        and out["drain_s"] > 0.0
        and out["stages_s"]["idle"] >= 0.5
        and out["productive_fraction"] > 0.0
    )
    return out


def cold_start_bench(max_new: int = 16) -> dict:
    """The cold-start collapse yardstick (``make bench-coldstart``):
    time-to-first-routed-token for the three scale-up paths, with the
    per-stage attribution from each replica's ``GET /v1/goodput``:

    - **cold**: construct + boot + warmup-compile + first 200. Runs
      FIRST in a fresh interpreter, so it pays the real XLA compiles
      a production cold launch pays.
    - **promoted**: a standby (booted and warmup-compiled OUTSIDE the
      measured window — that is the warm-standby pool's whole
      premise: the compile happened BEFORE the scale event) measured
      from ``POST /v3/standby/promote`` to its first 200.
    - **peer transfer**: a launch whose weights arrive from the warm
      cold-arm replica over cp-mux/1 (``fleet.standby.fetch_params``,
      digest-verified; byte-equality asserted) instead of disk/init,
      then boot + warmup. In-process the jit caches play the shared
      XLA compile cache's role, so this arm isolates the transfer +
      boot cost the way a cache-warm same-host launch sees it.

    ``meets_target`` pins promoted TTFRT <= 0.25x cold TTFRT (the
    promoted path must dodge boot AND compile, not merely shave
    them), every arm's first request answering 200, and the
    transferred weights byte-identical to the peer's."""
    import asyncio
    import http.client
    import os
    import time as time_mod

    import jax
    import jax.numpy as jnp
    import numpy as np

    from containerpilot_tpu.fleet.standby import fetch_params
    from containerpilot_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_heads=2, n_layers=2, d_ff=128,
        max_seq_len=256, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    out: dict = {}

    async def scenario() -> None:
        loop = asyncio.get_event_loop()

        def request(port: int, method: str, path: str,
                    body: bytes = b"") -> tuple:
            conn = http.client.HTTPConnection(
                "127.0.0.1", port, timeout=120
            )
            try:
                conn.request(
                    method, path, body or None,
                    {"Content-Type": "application/json"}
                    if body else {},
                )
                resp = conn.getresponse()
                return resp.status, resp.read()
            finally:
                conn.close()

        gen_body = json.dumps(
            {"tokens": [[1, 2, 3, 4, 5, 6, 7, 8]],
             "max_new_tokens": max_new}
        ).encode()

        async def first_token(port: int) -> int:
            status, _payload = await loop.run_in_executor(
                None, request, port, "POST", "/v1/generate", gen_body
            )
            return status

        async def stages(port: int) -> dict:
            _status, payload = await loop.run_in_executor(
                None, request, port, "GET", "/v1/goodput"
            )
            gp = json.loads(payload)
            return {
                stage: round(gp["stages_s"].get(stage, 0.0), 3)
                for stage in ("boot", "compile_warmup")
            }

        def server(**kwargs) -> InferenceServer:
            return InferenceServer(
                cfg, params, "127.0.0.1", 0, max_len=256,
                slots=2, slot_chunk=8, **kwargs,
            )

        # -- arm 1: COLD (first in this interpreter: real compiles) --
        t0 = time_mod.monotonic()
        cold = server()
        await cold.run()
        cold_status = await first_token(cold.port)
        cold_ttfrt = time_mod.monotonic() - t0
        cold_stages = await stages(cold.port)

        # -- arm 2: PROMOTED (standby boots OUTSIDE the window) ------
        standby = server(role="standby")
        await standby.run()  # boot + warmup paid before the event
        t0 = time_mod.monotonic()
        promote_status, _ = await loop.run_in_executor(
            None, request, standby.port, "POST",
            "/v3/standby/promote", b"{}",
        )
        promoted_status = await first_token(standby.port)
        promoted_ttfrt = time_mod.monotonic() - t0
        promoted_stages = await stages(standby.port)

        # -- arm 3: PEER-TRANSFER launch (weights over cp-mux/1) -----
        t0 = time_mod.monotonic()
        like = init_params(jax.random.PRNGKey(1), cfg)  # same shapes,
        # different values: byte-equality below proves the transfer
        # actually replaced them
        fetched = await fetch_params("127.0.0.1", cold.port, like)
        transfer_s = time_mod.monotonic() - t0
        transfer_ok = fetched is not None and all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(
                jax.tree_util.tree_leaves(params),
                jax.tree_util.tree_leaves(fetched),
            )
        )
        xfer = InferenceServer(
            cfg, fetched if fetched is not None else params,
            "127.0.0.1", 0, max_len=256, slots=2, slot_chunk=8,
        )
        await xfer.run()
        transfer_status = await first_token(xfer.port)
        transfer_ttfrt = time_mod.monotonic() - t0
        transfer_stages = await stages(xfer.port)

        for s in (standby, xfer, cold):
            await s.stop()

        total_bytes = sum(
            np.asarray(leaf).nbytes
            for leaf in jax.tree_util.tree_leaves(params)
        )
        out.update(
            backend=jax.default_backend(),
            config=(
                f"{cfg.n_layers}L d{cfg.d_model} v{cfg.vocab_size}, "
                f"2 slots x 8-token chunks, first token = "
                f"{max_new}-token generate"
            ),
            cold={
                "ttfrt_s": round(cold_ttfrt, 3),
                "status": cold_status,
                "stages_s": cold_stages,
            },
            promoted={
                "ttfrt_s": round(promoted_ttfrt, 3),
                "promote_status": promote_status,
                "status": promoted_status,
                "stages_s": promoted_stages,
            },
            peer_transfer={
                "ttfrt_s": round(transfer_ttfrt, 3),
                "transfer_s": round(transfer_s, 3),
                "bytes": int(total_bytes),
                "verified": bool(transfer_ok),
                "status": transfer_status,
                "stages_s": transfer_stages,
            },
            promoted_over_cold=round(
                promoted_ttfrt / max(cold_ttfrt, 1e-9), 4
            ),
        )

    asyncio.run(scenario())
    out["target"] = (
        "promoted TTFRT <= 0.25x cold TTFRT, every arm's first "
        "request 200, peer-transferred weights byte-identical"
    )
    out["meets_target"] = bool(
        out["promoted_over_cold"] <= 0.25
        and out["cold"]["status"] == 200
        and out["promoted"]["status"] == 200
        and out["promoted"]["promote_status"] == 200
        and out["peer_transfer"]["status"] == 200
        and out["peer_transfer"]["verified"]
    )
    return out


def migration_bench(max_new: int = 8) -> dict:
    """The drain-migration yardstick (``make bench-migrate``):
    next-turn latency for a multi-turn session whose first turn ran
    on a replica that then drains, across the three places turn 2
    can land:

    - **warm**: turn 2 back on the SAME replica (KV resident) — the
      ceiling migration is chasing.
    - **migrated**: the drainer pushes its cached prefixes to a
      survivor over the handoff wire (``migrate_sessions`` — the
      same bytes a real drain moves), then turn 2 lands on the
      survivor and reuses the adopted KV.
    - **re-prefill**: turn 2 lands on a replica that never saw the
      session — today's drain-as-eviction behavior, paying the full
      prefill again.

    Every server carries a synthetic ``prefill_floor_s`` standing in
    for the real prefill compute a production prompt costs (CPU-sized
    prompts prefill in microseconds, which would flatten the very
    difference this bench exists to measure); a KV-reuse hit skips
    the floor exactly as real reuse skips real prefill.
    ``meets_target`` pins the migrated arm strictly below the
    re-prefill baseline, near the warm ceiling, with bytes actually
    moved and zero counted fallbacks."""
    import asyncio
    import http.client
    import os
    import time as time_mod

    import jax
    import jax.numpy as jnp

    from containerpilot_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_heads=2, n_layers=2, d_ff=128,
        max_seq_len=256, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    floor_s = 0.25
    out: dict = {}

    async def scenario() -> None:
        loop = asyncio.get_event_loop()

        def request(port: int, method: str, path: str,
                    body: bytes = b"") -> tuple:
            conn = http.client.HTTPConnection(
                "127.0.0.1", port, timeout=120
            )
            try:
                conn.request(
                    method, path, body or None,
                    {"Content-Type": "application/json"}
                    if body else {},
                )
                resp = conn.getresponse()
                return resp.status, resp.read()
            finally:
                conn.close()

        async def generate(port: int, tokens: list) -> tuple:
            body = json.dumps(
                {"tokens": [tokens], "max_new_tokens": max_new}
            ).encode()
            t0 = time_mod.monotonic()
            status, payload = await loop.run_in_executor(
                None, request, port, "POST", "/v1/generate", body
            )
            elapsed = time_mod.monotonic() - t0
            gen = (
                json.loads(payload)["tokens"][0]
                if status == 200 else []
            )
            return status, gen, elapsed

        def server() -> InferenceServer:
            return InferenceServer(
                cfg, params, "127.0.0.1", 0, max_len=128,
                slots=2, slot_chunk=8, prefix_cache_entries=8,
                kv_spill_bytes=4 << 20, prefill_floor_s=floor_s,
            )

        drainer, survivor, fresh = server(), server(), server()
        for s in (drainer, survivor, fresh):
            await s.run()

        # compile-fairness warmup: run the SAME two-turn shape flow
        # on every server with a throwaway token family, so each arm's
        # timed request pays only its floor + decode, never a stray
        # first-shape XLA compile (the floor, not the compiler, is
        # what separates the arms)
        warm_row = [int(t) for t in range(60, 84)]
        for s in (drainer, survivor, fresh):
            st, gen, _ = await generate(s.port, warm_row)
            assert st == 200, f"warmup turn 1 failed: {st}"
            st, _, _ = await generate(
                s.port, warm_row + gen + [3, 5]
            )
            assert st == 200, f"warmup turn 2 failed: {st}"
            # re-issue turn 2: the prompt now FULLY matches the
            # longer stored key, compiling the rewind+extend-1
            # program the migrated arm's reuse hit takes (its adopted
            # keys include the drainer's completed turn-2 entry)
            st, _, _ = await generate(
                s.port, warm_row + gen + [3, 5]
            )
            assert st == 200, f"warmup turn 2 retry failed: {st}"
            # a COLD prompt at turn-2 length (distinct family, no
            # reuse possible): compiles the full-length prefill the
            # re-prefill arm takes, so that arm's number is floor +
            # decode, not floor + a stray XLA compile
            cold_probe = [
                int(t) for t in
                range(90, 90 + len(warm_row) + len(gen) + 2)
            ]
            st, _, _ = await generate(s.port, cold_probe)
            assert st == 200, f"warmup cold probe failed: {st}"

        # the measured session: turn 1 on the drainer (untimed —
        # every arm's story starts from the same resident KV)
        row1 = [int(t) for t in range(1, 25)]
        st1, gen1, _ = await generate(drainer.port, row1)
        row2 = row1 + gen1 + [9, 11]

        # -- arm 1: WARM (turn 2 back on the drainer, KV resident) --
        warm_status, _, warm_s = await generate(drainer.port, row2)

        # -- arm 2: MIGRATED (drain pushes KV, turn 2 on survivor) --
        t0 = time_mod.monotonic()
        summary = await drainer.migrate_sessions(
            [("survivor", "127.0.0.1", survivor.port, frozenset())],
            window_s=30.0,
            authority=f"127.0.0.1:{drainer.port}",
        )
        migrate_wire_s = time_mod.monotonic() - t0
        mig_status, _, migrated_s = await generate(
            survivor.port, row2
        )

        # -- arm 3: RE-PREFILL (turn 2 on a never-seen replica) ------
        base_status, _, baseline_s = await generate(fresh.port, row2)

        for s in (drainer, survivor, fresh):
            await s.stop()

        out.update(
            backend=jax.default_backend(),
            config=(
                f"{cfg.n_layers}L d{cfg.d_model} v{cfg.vocab_size}, "
                f"{len(row2)}-token turn-2 prompt, {max_new} new "
                f"tokens, prefill floor {floor_s}s"
            ),
            warm={
                "next_turn_s": round(warm_s, 3),
                "status": warm_status,
            },
            migrated={
                "next_turn_s": round(migrated_s, 3),
                "status": mig_status,
                "wire_s": round(migrate_wire_s, 3),
                "entries_moved": summary["done"],
                "bytes": summary["bytes"],
                "failed": summary["failed"],
                "timeout": summary["timeout"],
            },
            reprefill={
                "next_turn_s": round(baseline_s, 3),
                "status": base_status,
            },
            seed_status=st1,
            migrated_over_reprefill=round(
                migrated_s / max(baseline_s, 1e-9), 4
            ),
            migrated_over_warm=round(
                migrated_s / max(warm_s, 1e-9), 4
            ),
        )

    asyncio.run(scenario())
    out["target"] = (
        "migrated next-turn latency strictly below the re-prefill "
        "baseline and near the warm ceiling (<= max(2.5x warm, "
        "warm + 0.1s)), bytes moved > 0, zero failed/timed-out "
        "entries, every request 200"
    )
    out["meets_target"] = bool(
        out["seed_status"] == 200
        and out["warm"]["status"] == 200
        and out["migrated"]["status"] == 200
        and out["reprefill"]["status"] == 200
        and out["migrated"]["entries_moved"] >= 1
        and out["migrated"]["bytes"] > 0
        and out["migrated"]["failed"] == 0
        and out["migrated"]["timeout"] == 0
        and out["migrated"]["next_turn_s"]
        < out["reprefill"]["next_turn_s"]
        and out["migrated"]["next_turn_s"]
        <= max(
            2.5 * out["warm"]["next_turn_s"],
            out["warm"]["next_turn_s"] + 0.1,
        )
    )
    return out


def chaos_goodput_bench(seed: int = 0) -> dict:
    """The robustness trajectory: run the QUICK chaos scenarios (a
    real multi-replica fleet + gateway replaying a seeded trace while
    faults fire — replica SIGKILL, wedged health, catalog flap, slow
    replica, and the burst suite: a 10x overload shed by admission
    control, and a kill-under-burst the autoscaler scales through)
    and record each run's SLO-goodput, TTFT/TPOT percentiles, 5xx
    count, shed counts, scale events, and per-fault counts. Host-side
    and CPU-sized, so every bench round records real under-fire (and
    goodput-under-burst) numbers even TPU-less. ``meets_target`` is
    every scenario clearing its invariants (zero client-visible 5xx
    included — sheds are honest 429/504, counted separately) — the
    bar the ROADMAP's multiplexed-transport work will be judged
    against. See docs/80-chaos.md."""
    import logging as logging_mod
    import os
    import tempfile

    import jax

    logging_mod.disable(logging_mod.CRITICAL)

    from containerpilot_tpu.chaos import quick_scenarios, run_scenario

    scenarios: dict = {}
    all_passed = True
    for name in quick_scenarios():
        with tempfile.TemporaryDirectory(prefix="chaos-bench-") as d:
            report = run_scenario(name, d, seed=seed)
        score = report["score"]
        scenarios[name] = {
            "passed": report["passed"],
            "requests": score["requests"],
            "goodput_rps": score["goodput_rps"],
            "goodput_fraction": score["goodput_fraction"],
            "goodput_fraction_admitted": (
                score["goodput_fraction_admitted"]
            ),
            "sheds": score["sheds"],
            "shed_429": score["shed_429"],
            "shed_504": score["shed_504"],
            "client_retries": score["client_retries"],
            "ttft_p50_ms": score["ttft_ms"]["p50"],
            "ttft_p99_ms": score["ttft_ms"]["p99"],
            "tpot_p95_ms": score["tpot_ms"]["p95"],
            "count_5xx": score["count_5xx"],
            "truncated_streams": score["truncated_streams"],
            # event-loop health (analysis/loopcheck.py): the named
            # form of "the loop hiccuped", tracked release-over-release
            "loop_lag_max_ms": report["loop_lag_max_ms"],
            "loop_task_exceptions": len(
                report["loop"]["task_exceptions"]
            ),
            # device-time ledger (telemetry/goodput.py): the badput
            # trajectory per scenario, tracked release-over-release
            "productive_fraction": (
                report["goodput_ledger"]["productive_fraction"]
            ),
            # per-role cut of the same ledger (disaggregated
            # scenarios split prefill/decode; mixed fleets report
            # one "active" pool) — tracked release-over-release
            "productive_fraction_by_role": {
                role: stats["productive_fraction"]
                for role, stats in report["goodput_ledger"]
                .get("per_role", {}).items()
            },
            "dispatches_per_token": (
                report["goodput_ledger"]["dispatches_per_token"]
            ),
            "scale_up_ttfrt_s": min(
                (
                    e["ttfrt_s"]
                    for e in report["goodput_ledger"]["scale_events"]
                    if e["direction"] == "up"
                    and e.get("ttfrt_s") is not None
                ),
                default=None,
            ),
            "retried": report["gateway"]["retried"],
            "hedged": report["gateway"]["hedged"],
            "catalog_flaps_damped": (
                report["gateway"]["catalog_flaps_damped"]
            ),
            "autoscaler": (
                {
                    "scale_ups": report["autoscaler"]["scale_ups"],
                    "scale_downs": report["autoscaler"]["scale_downs"],
                    "replicas_at_end": report["autoscaler"]["replicas"],
                }
                if report.get("autoscaler") else None
            ),
            "fault_counts": report["fault_counts"],
        }
        all_passed = all_passed and report["passed"]
    return {
        "backend": jax.default_backend(),
        "seed": seed,
        "scenarios": scenarios,
        # the bar: every quick scenario's invariants hold under fire
        "meets_target": all_passed,
    }


def prefix_reuse_bench(seeds: tuple = (0, 1, 2)) -> dict:
    """Fleet-wide KV reuse vs. the session-sticky baseline: replay
    the SAME multi-turn chat trace (growing shared-prefix
    conversations + a replica draining mid-conversation, from
    chaos/trace.py) through two fleets that differ only in routing —
    ``multiturn_rebalance`` (cache-contents-aware ``_pick`` + the
    host-RAM KV spill tier earning readmissions) and
    ``multiturn_sticky_baseline`` (cache_routing off: re-pins land by
    load, blind to where the KV lives). Records fleet-wide
    tokens_reused per prompt token (the ML-goodput yardstick for
    reuse) and shed-free TTFT p50 for both arms, POOLED over the
    seeds (each seed is a different conversation schedule; pooling
    keeps one lucky tie-break concentration from deciding the
    verdict). Every scenario runs in its OWN interpreter — exactly
    the ``python -m containerpilot_tpu.chaos --scenario`` regime the
    tier-1 tests gate on: a shared warm process would amortize every
    jit compile, collapse request latencies to the point where
    conversations never overlap, and hand the blind baseline an
    idle-fleet concentration the policies are not separable under.
    ``meets_target`` = the aware arm clears its strict invariants at
    every seed (zero 5xx, drain absorbed, hint hits, spill
    readmissions) AND reuses STRICTLY more prefix tokens per prompt
    token than the baseline — cache-aware routing must pay for
    itself on the workload it exists for. Host-side and CPU-sized;
    see docs/80-chaos.md."""
    import logging as logging_mod
    import os
    import subprocess
    import sys
    import tempfile

    import jax

    logging_mod.disable(logging_mod.CRITICAL)

    def run_cold(name: str, seed: int) -> dict:
        with tempfile.TemporaryDirectory(prefix="reuse-bench-") as d:
            out = os.path.join(d, "report.json")
            proc = subprocess.run(
                [
                    sys.executable, "-m", "containerpilot_tpu.chaos",
                    "--scenario", name, "--seed", str(seed),
                    "--json", out,
                ],
                capture_output=True, text=True, timeout=240,
                cwd=os.path.dirname(os.path.abspath(__file__)),
                env=dict(os.environ, JAX_PLATFORMS="cpu"),
            )
            try:
                with open(out, encoding="utf-8") as f:
                    return json.load(f)["scenarios"][0]
            except (OSError, ValueError, KeyError, IndexError):
                raise RuntimeError(
                    f"{name} seed {seed} produced no report "
                    f"(exit {proc.returncode}): {proc.stderr}"
                ) from None

    arms: dict = {}
    for arm, name in (
        ("cache_aware", "multiturn_rebalance"),
        ("session_sticky", "multiturn_sticky_baseline"),
    ):
        runs = []
        for seed in seeds:
            report = run_cold(name, seed)
            score = report["score"]
            kv = report["kv"]
            runs.append({
                "seed": seed,
                "passed": report["passed"],
                "requests": score["requests"],
                "goodput_fraction": score["goodput_fraction"],
                # sheds carry no TTFT sample, so these are shed-free
                "ttft_p50_ms": score["ttft_ms"]["p50"],
                "ttft_p99_ms": score["ttft_ms"]["p99"],
                "count_5xx": score["count_5xx"],
                "tokens_reused": kv["tokens_reused"],
                "prompt_tokens": kv["prompt_tokens"],
                "tokens_reused_per_prompt_token": (
                    kv["tokens_reused_per_prompt_token"]
                ),
                "cache_hint_hits": kv["cache_hint_hits"],
                "cache_hint_misses": kv["cache_hint_misses"],
                "spilled": kv["spilled"],
                "readmitted": kv["readmitted"],
                "sticky_evicted": (
                    report["gateway"]["sticky"]["evicted"]
                ),
            })
        reused = sum(r["tokens_reused"] for r in runs)
        prompts = sum(r["prompt_tokens"] for r in runs)
        arms[arm] = {
            "scenario": name,
            "passed": all(r["passed"] for r in runs),
            "tokens_reused": reused,
            "prompt_tokens": prompts,
            "tokens_reused_per_prompt_token": round(
                reused / max(1, prompts), 4
            ),
            "ttft_p50_ms": round(
                sum(r["ttft_p50_ms"] for r in runs) / len(runs), 2
            ),
            "runs": runs,
        }
    aware = arms["cache_aware"]
    base = arms["session_sticky"]
    return {
        "backend": jax.default_backend(),
        "seeds": list(seeds),
        "arms": arms,
        "reuse_advantage_per_prompt_token": round(
            aware["tokens_reused_per_prompt_token"]
            - base["tokens_reused_per_prompt_token"], 4
        ),
        "ttft_p50_delta_ms": round(
            aware["ttft_p50_ms"] - base["ttft_p50_ms"], 2
        ),
        # the bar: the aware arm holds its invariants at every seed
        # AND reuses strictly more than blind session-sticky on the
        # same pooled traces
        "meets_target": bool(
            aware["passed"]
            and aware["tokens_reused_per_prompt_token"]
            > base["tokens_reused_per_prompt_token"]
        ),
    }


def disagg_bench(seeds: tuple = (0, 1)) -> dict:
    """Disaggregated prefill/decode vs the mixed fleet: replay the
    SAME multi-turn streaming trace (chaos/scenarios.py's
    ``_DISAGG_TRACE``, every cold prefill paying a synthetic
    admission floor that stands in for a production-sized prompt
    occupying the slot worker) through two fleets of the SAME size —
    ``disagg_mixed_baseline`` (3 mixed replicas; cold prefills block
    decode windows) and ``disagg_split`` (1 prefill + 2 decode
    replicas; fresh prompts prefill on the prefill pool and the KV
    prefix ships replica-to-replica over the cp-mux/1 handoff
    stream, readmitted through the same ``reuse_admission`` path a
    local spill takes). Each scenario runs in its OWN interpreter
    (the cold-process regime the tier-1 tests gate on, same as
    prefix_reuse_bench). ``meets_target`` = both arms clear their
    invariants at every seed AND the split arm's TPOT p99 (its
    streams all ride the decode pool) is STRICTLY under the mixed
    arm's AND every split seed completed handoffs with per-transfer
    wall ms recorded AND the decode pool's driven-window productive
    fraction (PR 12 ledger, per-role cut) is >= the mixed fleet's —
    phase specialization must buy tail decode latency without
    idling the pool it carved out. Host-side and CPU-sized; see
    docs/80-chaos.md."""
    import logging as logging_mod
    import os
    import subprocess
    import sys
    import tempfile

    import jax

    logging_mod.disable(logging_mod.CRITICAL)

    def run_cold(name: str, seed: int) -> dict:
        with tempfile.TemporaryDirectory(prefix="disagg-bench-") as d:
            out = os.path.join(d, "report.json")
            proc = subprocess.run(
                [
                    sys.executable, "-m", "containerpilot_tpu.chaos",
                    "--scenario", name, "--seed", str(seed),
                    "--json", out,
                ],
                capture_output=True, text=True, timeout=240,
                cwd=os.path.dirname(os.path.abspath(__file__)),
                env=dict(os.environ, JAX_PLATFORMS="cpu"),
            )
            try:
                with open(out, encoding="utf-8") as f:
                    return json.load(f)["scenarios"][0]
            except (OSError, ValueError, KeyError, IndexError):
                raise RuntimeError(
                    f"{name} seed {seed} produced no report "
                    f"(exit {proc.returncode}): {proc.stderr}"
                ) from None

    arms: dict = {}
    for arm, name in (
        ("mixed", "disagg_mixed_baseline"),
        ("disagg", "disagg_split"),
    ):
        runs = []
        for seed in seeds:
            report = run_cold(name, seed)
            score = report["score"]
            handoff = report["gateway"]["handoff"]
            per_role = report["goodput_ledger"].get("per_role", {})
            runs.append({
                "seed": seed,
                "passed": report["passed"],
                "requests": score["requests"],
                "goodput_fraction": score["goodput_fraction"],
                "count_5xx": score["count_5xx"],
                "ttft_p50_ms": score["ttft_ms"]["p50"],
                "ttft_p99_ms": score["ttft_ms"]["p99"],
                # the headline: every stream in the split arm decodes
                # on the decode pool, so the arm's TPOT p99 IS the
                # decode pool's under concurrent cold-prefill pressure
                "tpot_p50_ms": score["tpot_ms"]["p50"],
                "tpot_p99_ms": score["tpot_ms"]["p99"],
                "handoffs": handoff["total"],
                "handoff_failed": handoff["failed"],
                "handoff_skipped_warm": handoff["skipped_warm"],
                "handoff_bytes": handoff["bytes"],
                "handoff_mean_ms": round(
                    handoff["ms_sum"] / handoff["total"], 2
                ) if handoff["total"] else None,
                "productive_fraction": (
                    report["goodput_ledger"]["productive_fraction"]
                ),
                "productive_fraction_by_role": {
                    role: stats["productive_fraction"]
                    for role, stats in per_role.items()
                },
                "tokens_reused": report["kv"]["tokens_reused"],
                "readmitted": report["kv"]["readmitted"],
            })
        arms[arm] = {
            "scenario": name,
            "passed": all(r["passed"] for r in runs),
            "tpot_p99_ms": round(
                sum(r["tpot_p99_ms"] for r in runs) / len(runs), 2
            ),
            "ttft_p99_ms": round(
                sum(r["ttft_p99_ms"] for r in runs) / len(runs), 2
            ),
            "runs": runs,
        }
    mixed = arms["mixed"]
    split = arms["disagg"]
    decode_pf = [
        r["productive_fraction_by_role"].get("decode")
        for r in split["runs"]
    ]
    mixed_pf = [r["productive_fraction"] for r in mixed["runs"]]
    split["decode_productive_fraction"] = round(
        sum(decode_pf) / len(decode_pf), 4
    ) if all(f is not None for f in decode_pf) else None
    mixed["productive_fraction"] = round(
        sum(mixed_pf) / len(mixed_pf), 4
    )
    handoffs_every_seed = all(
        r["handoffs"] >= 1 and r["handoff_mean_ms"] is not None
        for r in split["runs"]
    )
    return {
        "backend": jax.default_backend(),
        "seeds": list(seeds),
        "arms": arms,
        "tpot_p99_advantage_ms": round(
            mixed["tpot_p99_ms"] - split["tpot_p99_ms"], 2
        ),
        # the handoff tax, stated next to the win it buys
        "ttft_p99_cost_ms": round(
            split["ttft_p99_ms"] - mixed["ttft_p99_ms"], 2
        ),
        # the bar: both arms hold their invariants at every seed,
        # the decode pool's tail beats the mixed fleet's STRICTLY,
        # KV actually moved (with its cost on the ledger), and the
        # carved-out decode pool out-produces the mixed fleet
        "meets_target": bool(
            mixed["passed"] and split["passed"]
            and split["tpot_p99_ms"] < mixed["tpot_p99_ms"]
            and handoffs_every_seed
            and split["decode_productive_fraction"] is not None
            and split["decode_productive_fraction"]
            >= mixed["productive_fraction"]
        ),
    }


class BenchFailed(RuntimeError):
    """A workload bench child failed; carries its whole stderr."""


#: every workload bench, in run order: (extras key, function, child
#: time limit in seconds). Each runs in its own child process, one
#: at a time — a chip belongs to one process at a time, and the
#: launcher itself never imports jax.
WORKLOAD_BENCHES = (
    # slot engine per-round host overhead + fused-window dispatches
    ("host_overhead", "host_overhead_bench", 900),
    # the fleet gateway's added per-request latency
    ("gateway_overhead", "gateway_overhead_bench", 600),
    # device-time ledger accounting + dispatches/token trajectory
    ("goodput_ledger", "goodput_ledger_bench", 600),
    # quick chaos scenarios' SLO-goodput under injected faults
    ("chaos_goodput", "chaos_goodput_bench", 900),
    # cache-aware routing + host-RAM spill tier vs session-sticky
    ("prefix_reuse", "prefix_reuse_bench", 900),
    # cold vs promoted vs peer-transfer time to first token
    ("cold_start", "cold_start_bench", 600),
    # decode-pool TPOT p99 + handoff cost vs the mixed fleet
    ("disagg", "disagg_bench", 900),
    # migrated next-turn latency vs warm and re-prefill
    ("migration", "migration_bench", 600),
    ("attention", "attention_bench", 900),
    ("int8_gemm", "int8_bench", 600),
    # four remat variants = four compiles; budget accordingly
    ("training", "training_bench", 2700),
    ("decode", "decode_bench", 1500),
    ("slot_admission", "slot_admission_bench", 1200),
)


def _bench_subprocess(fn_name: str, timeout_s: int) -> dict:
    """Run one workload bench in its own interpreter with a hard
    time limit and return its result. The child first checks that
    jax's first device is a TPU and fails otherwise: a device bench
    never runs on another platform. Any failure (no chip, a crash, a
    timeout, no result line) raises BenchFailed with the child's
    whole stderr."""
    import os
    import subprocess
    import sys

    code = (
        "import json, logging, sys, jax, bench; "
        "logging.disable(logging.CRITICAL); "
        "dev = jax.devices()[0]; "
        f"dev.platform == 'tpu' or sys.exit('{fn_name} needs a tpu, "
        "jax found ' + dev.platform); "
        f"print('BENCH_RESULT ' + json.dumps(bench.{fn_name}()))"
    )
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, timeout=timeout_s,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except subprocess.TimeoutExpired as exc:
        stderr = exc.stderr or ""
        if isinstance(stderr, bytes):
            stderr = stderr.decode(errors="replace")
        raise BenchFailed(
            f"{fn_name}: timeout after {timeout_s}s\n{stderr}"
        ) from None
    if proc.returncode == 0:
        for line in reversed(proc.stdout.splitlines()):
            if line.startswith("BENCH_RESULT "):
                return json.loads(line[len("BENCH_RESULT "):])
    raise BenchFailed(
        f"{fn_name}: exit {proc.returncode}\n{proc.stderr}"
    )


def workload_benches() -> dict:
    """Every workload bench, each in its own child, strictly one
    after another (subprocess.run returns only once the child has
    exited, so two children never hold the chip together). The first
    failure ends the run: BenchFailed propagates."""
    return {
        name: _bench_subprocess(fn_name, timeout_s)
        for name, fn_name, timeout_s in WORKLOAD_BENCHES
    }


async def main() -> int:
    # silence the supervisor's logging for the timed cycles — set here
    # (not at import) so importing bench for tests has no global
    # side effect on the host process's logging
    import sys

    logging.disable(logging.CRITICAL)
    median = await dispatch_bench()
    try:
        extras = workload_benches()
    except BenchFailed as exc:
        # no metric line at all: a run with a failed phase has no
        # result, and the failed child's whole stderr is the output
        print(f"bench failed: {exc}", file=sys.stderr)
        return 1
    print(
        json.dumps(
            {
                "metric": "supervisor_job_dispatch_latency_p50",
                "value": round(median, 3),
                "unit": "ms",
                "vs_baseline": round(BASELINE_MS / median, 2),
                "extras": extras,
            }
        )
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(asyncio.run(main()))
