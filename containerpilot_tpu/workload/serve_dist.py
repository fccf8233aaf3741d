"""Multi-host serving: one HTTP frontend, a slot-pool decode spanning
the pod.

Models too large for one host's devices serve across hosts the same
way they train: every process joins the pod through the supervisor's
catalog (``parallel.distributed.initialize_from_catalog`` — the exact
rendezvous the training capstone uses), params shard over a GLOBAL
mesh with the training partition rules, and XLA's collectives carry
the decode over ICI within a host and DCN between hosts.

The pod runs the SAME continuous-batching slot engine the single-host
server does (``models/slots.py``), made SPMD: a fixed pool of
``--slots`` per-request cache rows decodes in ``--stream-chunk``-token
lockstep chunks, and between chunks process 0 broadcasts one
fixed-shape ROUND payload (``multihost_utils.broadcast_one_to_all``)
carrying this round's admission (at most one new request row: prompt,
knobs, key), the per-slot active mask, and whether to run a chunk.
Every process — frontend included — replays the identical device ops
(`_SlotMirror`: prefill+insert for the admission, then the one
compiled chunk program); process 0 alone keeps the HTTP bookkeeping
(emitted tokens, retirement, SSE deltas). Requests therefore JOIN a
running decode at the next chunk boundary instead of queueing behind
another request's whole generation — N concurrent requests, streamed
and non-streamed, with per-request output byte-identical to a solo
single-host ``generate`` (the engine's tested invariant).

Frontend surface (process 0): ``/health``, ``/metrics``, ``/v1/model``,
``POST /v1/generate`` (token-level; the single-host server's knobs
including ``n``, ``stop``, ``logprobs``, ``beam_width``, ``stream``),
``POST /v1/score``, and behind ``--text`` ``POST /v1/completions``
(byte tokenizer, streamed or not, with UTF-8 holdback). ``logprobs``
echoes ride extra lockstep score rounds after a request retires; beams
run as a one-shot lockstep round. Followers run the broadcast-follow
loop with no HTTP surface (their supervisor job health-checks process
liveness, e.g. ``kill -0 $CONTAINERPILOT_<JOB>_PID``).

Shutdown: SIGTERM on process 0 broadcasts a shutdown op so followers
exit cleanly.

Failure detection (``--watchdog``): serving gets the same
decode-progress deadline training has (parallel/watchdog.py). The
frontend broadcasts OP_HEARTBEAT whenever the pod is idle, and every
ROUND is bounded by one chunk of decode — so every process completes
a broadcast(+device) cycle at least every watchdog/4 seconds and
beat()s its StepWatchdog. A follower that wedges mid-decode (or dies)
stalls the NEXT cycle pod-wide: every peer's watchdog turns its silent
collective hang into a hard exit (code 86) the supervisor's restart
budgets absorb, and the reincarnated pod re-rendezvouses through the
catalog. Because ALL generation (streamed or not) now rides chunked
rounds, no legitimate long request can outlast the deadline — only
one-shot ops (a beam round, a score round, an unwarmed-shape compile)
must individually finish inside it; size ``--watchdog`` above the
slowest of those.

Parallelism: ``--dp`` splits the global device count into a
(data, model) mesh — ``--dp 2`` over 4 processes serves on a 2x2
dp x tp mesh (params sharded over model, replicated over data), so
tensor parallelism crosses process boundaries exactly as a real pod's
does. ``--kv-int8`` serves with the int8 KV cache (half the KV bytes;
identical quantized numerics on every process); ``--window`` serves
sliding-window attention over per-slot ring caches (KV memory bounded
by the window, not --max-len) — both are static model config, so
every process's lockstep dispatch is unchanged. ``--sp`` adds a seq
axis (dp x sp x tp mesh): prompts at least ``--cp-min-len`` long ring
their prefill over it (parallel/context.py — per-device activation
memory bounded by prompt/sp), then decode on the replicated slot
pool; the cp decision reads only static flags plus the broadcast
plen, so it is lockstep by construction.

    python -m containerpilot_tpu.workload.serve_dist \
        --process-id 0 --num-processes 2 --catalog 127.0.0.1:8500 \
        --port 8000 --d-model 1024 ...

Request sampling reproduces the single-host server's key convention
(row i of a request draws from fold_in(PRNGKey(seed), i)), so answers
are byte-identical to a single-host server of the same config (tested
with real OS processes on the CPU backend, including co-batched
traffic).
"""
from __future__ import annotations

import argparse
import functools
import json
import logging
import os
import queue
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

log = logging.getLogger("containerpilot.serve_dist")

from ..models.decode import BIAS_SLOTS_MAX

OP_SHUTDOWN = 0
OP_ROUND = 1      # slot-engine round: optional admission + one chunk
OP_HEARTBEAT = 2  # idle liveness tick: bounds every broadcast wait
OP_SCORE = 3      # teacher-forced logprobs over the broadcast row
OP_BEAM = 4       # one-shot lockstep beam search
OP_SPEC = 5       # one-shot lockstep speculative (draft-and-verify)

WATCHDOG_EXIT = 86  # parallel.watchdog.EXIT_CODE — same semantics


def _payload_zeros(max_len: int, slots: int) -> Dict[str, np.ndarray]:
    """The ONE broadcast structure every round uses (a collective
    broadcast needs identical pytrees on every process, so heartbeat,
    score, beam, shutdown, and slot rounds all ship this shape)."""
    return {
        "op": np.zeros((), np.int32),
        # the single row a round can carry: a score/beam request's
        # tokens, or this round's admission prompt
        "prompt": np.zeros((max_len,), np.int32),
        "plen": np.zeros((), np.int32),
        # admission (admit_slot -1 = none this round); row_idx is the
        # row's index within its request — the key schedule
        # fold_in(PRNGKey(seed), row_idx) is the server convention
        "admit_slot": np.full((), -1, np.int32),
        "row_idx": np.zeros((), np.int32),
        "max_new_req": np.zeros((), np.int32),
        "temperature": np.zeros((), np.float32),
        "top_k": np.zeros((), np.int32),
        "top_p": np.zeros((), np.float32),
        "eos_id": np.full((), -1, np.int32),
        "seed": np.zeros((), np.int32),
        "min_new": np.zeros((), np.int32),
        "presence": np.zeros((), np.float32),
        "frequency": np.zeros((), np.float32),
        "bias_idx": np.full((BIAS_SLOTS_MAX,), -1, np.int32),
        "bias_val": np.zeros((BIAS_SLOTS_MAX,), np.float32),
        # beam round operands
        "beam_width": np.zeros((), np.int32),
        "length_penalty": np.zeros((), np.float32),
        # chunk control: run the (slots, chunk) program this round,
        # with this pre-chunk inactive mask (1 = slot is dead; evicted
        # slots — disconnects, stop matches — flip to 1 here)
        "run_chunk": np.zeros((), np.int32),
        "done": np.ones((slots,), np.int32),
        # fused decode: run this many chunk-rounds in ONE device
        # dispatch (the (S, chunk, K) window program, early-exiting
        # when every slot is done or out of ``budget`` tokens);
        # 1 = the classic single-chunk round. The frontend fuses only
        # pure-decode rounds — admissions, queued work, cancels and
        # stop-sequence watches keep chunk granularity — so followers
        # replay the identical program by construction.
        "rounds": np.ones((), np.int32),
        "budget": np.zeros((slots,), np.int32),
    }


def _fill_admission(payload, work: Dict[str, Any], row_idx: int,
                    slot: int) -> None:
    """Pack one request row's admission into the round payload."""
    tokens = work["tokens"]
    payload["prompt"][: len(tokens)] = np.asarray(tokens, np.int32)
    payload["plen"] = np.asarray(len(tokens), np.int32)
    payload["admit_slot"] = np.asarray(slot, np.int32)
    payload["row_idx"] = np.asarray(row_idx, np.int32)
    payload["max_new_req"] = np.asarray(work["max_new"], np.int32)
    payload["temperature"] = np.asarray(work["temperature"], np.float32)
    payload["top_k"] = np.asarray(work["top_k"], np.int32)
    payload["top_p"] = np.asarray(work["top_p"], np.float32)
    payload["eos_id"] = np.asarray(work["eos_id"], np.int32)
    payload["seed"] = np.asarray(work["seed"], np.int32)
    payload["min_new"] = np.asarray(work["min_new"], np.int32)
    payload["presence"] = np.asarray(work["presence"], np.float32)
    payload["frequency"] = np.asarray(work["frequency"], np.float32)
    # parse_logit_bias upstream coerces keys and caps at
    # BIAS_SLOTS_MAX; the slice is a defensive bound that can never
    # raise inside the pod loop (an error here would be pod-fatal)
    items = sorted((work.get("logit_bias") or {}).items())[
        :BIAS_SLOTS_MAX
    ]
    for j, (tok_id, bias) in enumerate(items):
        payload["bias_idx"][j] = tok_id
        payload["bias_val"][j] = bias


class _SlotMirror:
    """The device half of the slot engine, replayed identically on
    every process: a fixed pool of single-row caches plus the host
    knob arrays the chunk program reads. All mutations are driven by
    broadcast ROUND payloads, so frontend and followers hold
    bit-identical state without ever exchanging it.

    ``mesh`` (the pod's global mesh) pins EVERY device buffer the
    mirror owns to an explicit fully-replicated sharding: without the
    pin, each jitted update leaves the pool in whatever output
    sharding GSPMD picks for that program, and a pool drifting
    between layouts across donating programs corrupted decodes
    (observed as deterministic wrong tokens in the 2-process pod).
    Replication is also the honest layout — every process must hold
    the whole pool to keep lockstep admission/retirement purely
    host-side."""

    def __init__(self, cfg, params, max_len: int, slots: int,
                 chunk: int, mesh=None, sp: int = 1,
                 cp_min_len: int = 0, prefix_entries: int = 0,
                 prefill_chunk: int = 0, window: int = 1) -> None:
        from ..models.slots import init_slot_state, slot_cache

        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.slots = slots
        self.chunk = chunk
        # fused window size K: the frontend may broadcast rounds=K on
        # pure-decode rounds; every process compiles the same
        # (S, chunk, K) window program at warmup
        self.window = max(1, int(window))
        self.mesh = mesh
        # context-parallel admission (``--sp``): prompts at least
        # cp_min_len long ring a STARTUP-COMPILED head bucket over the
        # mesh's seq axis and extend the remainder locally
        # (parallel/context.py — ring programs are the pod's only
        # cross-process collectives outside the broadcast, and a
        # first-use collective's communicator init has a hard ~30s
        # deadline request-time compile skew can blow, so every ring
        # shape must exist before traffic; see cp_head_buckets). Both
        # knobs are static flags and plen rides the broadcast, so
        # every process picks the same path — lockstep by
        # construction.
        self.sp = sp
        self.cp_min_len = cp_min_len
        # prefix KV reuse, lockstep by construction: every process
        # keeps an IDENTICAL PrefixCache instance whose state evolves
        # only through broadcast admissions (same prompts, same order
        # -> same matches, stores, and LRU evictions everywhere).
        # Entries are standalone buffers: extend never donates its
        # cache operand and insert_row copies the row into the
        # (donated) pool. The frontend reads .stats for /v1/model.
        # chunked admission (``--prefill-chunk``): local programs
        # with a bounded piece-length set — compile skew between
        # processes only delays the slower one, unlike collectives
        self.prefill_chunk = prefill_chunk
        self.prefix_cache = None
        self._repin = None
        if prefix_entries > 0:
            from .serve_prefix import PrefixCache

            self.prefix_cache = PrefixCache(prefix_entries)
        self.cp_buckets = ()
        if sp > 1:
            from ..parallel.context import cp_head_buckets

            self.cp_buckets = tuple(
                cp_head_buckets(cp_min_len, max_len, sp)
            )
        self.rep = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            self.rep = NamedSharding(mesh, PartitionSpec())

        if self.rep is not None and self.prefix_cache is not None:
            # stored prefix entries must stay fully replicated: a
            # GSPMD-chosen layout persisting in the cache could make
            # a later extend insert cross-process collectives — the
            # exact first-use-communicator hazard the cp buckets
            # exist to avoid (and the pool-drift lesson repeated)
            self._repin = jax.jit(
                lambda t: t, out_shardings=self.rep
            )

        def g(x):
            if self.rep is None:
                return x
            host = np.asarray(jax.device_get(x))
            return jax.make_array_from_callback(
                host.shape, self.rep, lambda idx: host[idx]
            )

        self._g = g
        self.pool = jax.tree.map(g, slot_cache(cfg, slots, max_len))
        # per-slot sampling state, ENTIRELY device-resident
        # (models/slots.py SLOT_STATE_KEYS) and pinned replicated:
        # written only at admission (one row-write dispatch), read by
        # the chunk program every round with zero host->device
        # uploads. The old mirror kept 10 host numpy knob arrays and
        # re-uploaded them every round — and host-side numpy operands
        # were exactly the zero-copy in-place-mutation hazard class
        # behind the historical torn-state bugs (step_idx now
        # advances on device inside the chunk program).
        self.state = jax.tree.map(g, init_slot_state(cfg, slots))
        # host shadow of the LAST done value written to the device
        # state (admission writes False; run_chunk uploads the
        # broadcast mask when it differs). Device-side eos flips can
        # make the device value True where this says False, but any
        # eos flip also ends the row in the frontend's bookkeeping,
        # so the next broadcast mask carries a 1 there and the
        # (redundant-but-harmless) upload converges the two. This is
        # host BOOKKEEPING, never a program operand — no zero-copy
        # hazard.
        self._done_host = np.ones((slots,), bool)

    def admit(self, payload) -> int:
        """Prefill the broadcast prompt into the named slot with the
        server key convention; returns sample 0 (every process fetches
        the same value — the computation is SPMD)."""
        from ..models.decode import _jitted_prefill
        from ..models.slots import (
            admit_slot_state,
            first_sample,
            insert_row,
        )

        slot = int(payload["admit_slot"])
        plen = int(payload["plen"])
        logits = row_cache = None
        pc = self.prefix_cache
        # prompts shorter than MIN_REUSE skip the prefix machinery
        # (never reusable; also keeps warmup's dummy admission out of
        # the cache) — same rule as the single-host engine
        use_pc = False
        if pc is not None:
            from .serve_prefix import MIN_REUSE

            use_pc = plen >= MIN_REUSE
        if use_pc:
            from .serve_prefix import reuse_admission

            row_tokens = [int(t) for t in payload["prompt"][:plen]]
            hit = reuse_admission(
                pc, row_tokens, self.cfg, self.params,
                chunk_len=self.prefill_chunk,
            )
            if hit is not None:
                logits, row_cache = hit
        # context-parallel admission: the quadratic prefill of a long
        # prompt rings over the seq axis (each device holds head/sp
        # tokens), the cache leaves the ring replicated — exactly the
        # mirror's layout — and any non-axis-divisible remainder
        # extends it with one short chunk (parallel/context.py's
        # cp_generate recipe, minus its decode half: the slot pool IS
        # the decode half here).
        if row_cache is None:
            cp_head = 0
            if self.sp > 1 and plen >= self.cp_min_len:
                from ..parallel.context import pick_cp_head

                cp_head = pick_cp_head(plen, self.cp_buckets)
            if cp_head > 0:
                from ..parallel.context import (
                    cp_prefill_with_remainder,
                )

                logits, row_cache = cp_prefill_with_remainder(
                    self.params, payload["prompt"][None, :plen],
                    self.cfg, self.mesh, self.max_len, head=cp_head,
                    prefill_chunk=self.prefill_chunk,
                )
            elif (
                self.prefill_chunk > 0
                and plen > self.prefill_chunk
            ):
                from ..models.decode import chunked_prefill

                logits, row_cache = chunked_prefill(
                    self.params,
                    jnp.asarray(payload["prompt"][None, :plen],
                                jnp.int32),
                    self.cfg, self.max_len,
                    chunk_len=self.prefill_chunk,
                )
            else:
                prompt = jnp.asarray(
                    payload["prompt"][None, :plen], jnp.int32
                )
                logits, row_cache = _jitted_prefill(
                    self.cfg, self.max_len
                )(self.params, prompt)
        if use_pc:
            stored = (
                self._repin(row_cache)
                if self._repin is not None else row_cache
            )
            pc.store(tuple(row_tokens), stored)
        row_key = jax.random.fold_in(
            jax.random.PRNGKey(int(payload["seed"])),
            int(payload["row_idx"]),
        )
        eos_id = int(payload["eos_id"])
        first = first_sample(
            logits, row_key,
            float(payload["temperature"]), int(payload["top_k"]),
            float(payload["top_p"]), self.cfg, eos_id=eos_id,
            min_new=int(payload["min_new"]),
            bias_idx=jnp.asarray(payload["bias_idx"], jnp.int32),
            bias_val=jnp.asarray(payload["bias_val"], jnp.float32),
        )
        first_host = int(jax.device_get(first))
        self.pool = insert_row(
            self.pool, row_cache, slot, self.cfg,
            out_sharding=self.rep,
        )
        # ONE dispatch writes the whole admission row into the
        # device-resident state (incl. the counts row, seeded on
        # device from the first sample). The barrier that used to sit
        # here guarded in-flight donated updates against the host
        # mutating zero-copied numpy operands (step_idx/knob arrays);
        # with every operand device-resident that hazard class is
        # gone by construction, device dataflow orders the donated
        # pool/state into the next chunk, and the 2-process co-batch
        # parity + torn-state tests hold without it.
        self.state = admit_slot_state(
            self.state, slot, self.cfg,
            last=first, key=row_key,
            temperature=float(payload["temperature"]),
            top_k=int(payload["top_k"]),
            top_p=float(payload["top_p"]),
            eos_id=eos_id,
            pad_id=0,  # server pad: 0
            min_new=int(payload["min_new"]),
            presence=float(payload["presence"]),
            frequency=float(payload["frequency"]),
            bias_idx=np.asarray(payload["bias_idx"], np.int32),
            bias_val=np.asarray(payload["bias_val"], np.float32),
            done=False,
            out_sharding=self.rep,
        )
        self._done_host[slot] = False
        return first_host

    # cpcheck: hotpath — the pod's per-round chunk step; one annotated
    # fetch, and the mask upload only on rounds where it changed
    def run_chunk(self, done_mask, rounds: int = 1,
                  budget=None) -> np.ndarray:
        """Advance every slot ``rounds`` chunk-rounds under the
        broadcast inactive mask — ONE device dispatch either way
        (rounds > 1 takes the fused (S, chunk, K) window program of
        models/slots.py, early-exiting on done/budget); returns the
        [slots, rounds_run*chunk] sampled tokens (fetched on every
        process — the fetch is what synchronizes device work, so a
        wedged computation stalls THIS cycle, not some later one).
        ``rounds`` and ``budget`` ride the broadcast payload, so
        every process dispatches the identical program.

        The mask rides the device-resident state: it is re-uploaded
        (one [S] bool array, pinned replicated) ONLY on rounds where
        it differs from the last value written — retirements and
        evictions — so a steady decode round ships zero host->device
        transfers (a fused window adds one [S] int32 budget upload
        per K rounds). The old full block_until_ready barrier is gone
        with its root causes: there are no zero-copied numpy operands
        left to mutate in place (step_idx advances on device), and
        the donated pool/state order into the next dispatch by device
        dataflow (the 2-process co-batch parity and torn-state tests
        hold without the barrier — they decided)."""
        from ..models.slots import (
            decode_slots_chunk,
            decode_slots_window,
        )

        mask = np.asarray(done_mask, bool)  # cpcheck: disable=CP-HOTSYNC host-side numpy only, no device operand
        if not np.array_equal(mask, self._done_host):
            self.state = dict(
                self.state, done=self._g(jnp.asarray(mask))
            )
            self._done_host = mask.copy()
        if rounds > 1:
            # the broadcast budget is already a host [S] int32 array;
            # decode_slots_window's wrapper uploads it
            self.pool, self.state, toks, run = decode_slots_window(
                self.params, self.pool, self.state,
                self.cfg, self.chunk, rounds, budget,
                out_sharding=self.rep,
            )
            toks_host, run_host = jax.device_get((toks, run))  # cpcheck: disable=CP-HOTSYNC the per-window token fetch
            return toks_host[:, : int(run_host) * self.chunk]
        self.pool, self.state, toks = decode_slots_chunk(
            self.params, self.pool, self.state,
            self.cfg, self.chunk,
            out_sharding=self.rep,
        )
        return np.asarray(jax.device_get(toks))  # cpcheck: disable=CP-HOTSYNC the per-round token fetch


def _debug_round(mirror: _SlotMirror, payload, first, toks) -> None:  # cpcheck: disable=CP-HOTREACH debug-only dump behind CONTAINERPILOT_POD_DEBUG; every sync here is the point
    """Dump one round's inputs and full device state
    (CONTAINERPILOT_POD_DEBUG only). Deliberately a separate,
    non-hot function: every fetch below is a host sync."""
    print(
        "ROUND admit=%d plen=%d seed=%d row=%d mask=%s first=%s "
        "toks=%s step=%s last=%s keys=%s"
        % (
            int(payload["admit_slot"]), int(payload["plen"]),
            int(payload["seed"]), int(payload["row_idx"]),
            np.asarray(payload["done"]).tolist(), first,
            None if toks is None else toks.tolist(),
            np.asarray(
                jax.device_get(mirror.state["step_idx"])
            ).tolist(),
            np.asarray(
                jax.device_get(mirror.state["last"])
            ).tolist(),
            np.asarray(
                jax.device_get(mirror.state["keys"])
            ).tolist(),
        ),
        flush=True,
    )


# cpcheck: hotpath — the device ops of one pod round
def _apply_round(mirror: _SlotMirror, payload):
    """The device ops of one ROUND, identical on every process:
    optional admission, then optionally one chunk. Returns (first
    token or None, [slots, chunk] tokens or None)."""
    first = toks = None
    if int(payload["admit_slot"]) >= 0:
        first = mirror.admit(payload)
    if int(payload["run_chunk"]):
        toks = mirror.run_chunk(
            payload["done"], rounds=int(payload["rounds"]),
            budget=payload["budget"],
        )
    if os.environ.get("CONTAINERPILOT_POD_DEBUG"):
        _debug_round(mirror, payload, first, toks)
    return first, toks


def shard_params_global(params: Any, mesh, cfg) -> Any:
    """Place identically-initialized host params onto a multi-host
    mesh: each process contributes exactly the shards it addresses
    (``make_array_from_callback`` slices the host copy), so no data
    moves over DCN at load time."""
    from jax.sharding import NamedSharding

    from ..parallel.sharding import param_sharding_rules

    rules = param_sharding_rules(cfg, mesh)

    def put(leaf, spec):
        host = np.asarray(leaf)
        return jax.make_array_from_callback(
            host.shape, NamedSharding(mesh, spec),
            lambda idx: host[idx],
        )

    return jax.tree_util.tree_map(put, params, rules)


@functools.lru_cache(maxsize=8)
def _jitted_score_fn(cfg):
    from .modelcfg import score_logprobs_fn

    return jax.jit(score_logprobs_fn(cfg))


def _score_pod(params, cfg, payload, max_len: int):
    """Teacher-forced per-token logprobs of the broadcast row — the
    pod twin of the single-host /v1/score (the SAME jitted function,
    modelcfg.score_logprobs_fn); every process runs it in lockstep
    like a decode. Rows pad to a 16-multiple width (capped at
    max_len) so per-request length variation can't compile a fresh
    pod-wide program inside the watchdog deadline — causal attention
    makes the pad positions free, and the result slices back.
    Returns a HOST [1, plen-1] ndarray (the device fetch lives here;
    see the slice comment below)."""
    plen = int(payload["plen"])
    width = min(-(-plen // 16) * 16, max_len)
    toks = jnp.asarray(payload["prompt"][None, :width], jnp.int32)
    out = _jitted_score_fn(cfg)(params, toks)
    if os.environ.get("CONTAINERPILOT_POD_DEBUG"):
        print("SCORE plen=%d" % plen, flush=True)
    # slice on the HOST: a device-side `out[:, :plen-1]` compiles a
    # tiny jit(dynamic_slice) per distinct plen — a post-grace
    # compile the warmup invariant forbids (the fetch is 16 floats
    # either way)
    return np.asarray(jax.device_get(out))[:, : plen - 1]


def _beam_pod(params, cfg, payload, max_len: int) -> List[int]:
    """One-shot lockstep beam search over the broadcast row: the same
    deterministic ``models.beam.beam_search`` program the single-host
    server runs, traced from broadcast scalars so every process
    executes it identically. One-shot by nature — it does not beat the
    watchdog mid-run, so the deadline must exceed the slowest beam."""
    from ..models.beam import beam_search

    plen = int(payload["plen"])
    prompt = jnp.asarray(payload["prompt"][None, :plen], jnp.int32)
    out, _score = beam_search(
        params, prompt, cfg,
        max_new_tokens=int(payload["max_new_req"]),
        max_len=max_len,
        beam_width=int(payload["beam_width"]),
        eos_id=int(payload["eos_id"]),
        length_penalty=float(payload["length_penalty"]),
    )
    if os.environ.get("CONTAINERPILOT_POD_DEBUG"):
        print("BEAM plen=%d width=%d"
              % (plen, int(payload["beam_width"])), flush=True)
    return [int(t) for t in np.asarray(jax.device_get(out))]


def _spec_pod(params, draft, cfg, payload, max_len: int) -> List[int]:
    """One-shot lockstep speculative generation: the single-host
    draft-and-verify (models/speculative.py — greedy, output
    IDENTICAL to plain generate) run identically on every process.
    The host loop's data-dependent acceptance decisions derive from
    replicated device values, so every process takes the same
    branches in the same order — all SPMD needs. Like beams, a spec
    round beats the watchdog only on completion; the deadline must
    exceed the slowest full generation."""
    from ..models.speculative import speculative_generate

    draft_params, draft_cfg, speculate = draft
    plen = int(payload["plen"])
    prompt = jnp.asarray(payload["prompt"][None, :plen], jnp.int32)
    out, stats = speculative_generate(
        params, draft_params, prompt, cfg, draft_cfg,
        max_new_tokens=int(payload["max_new_req"]), max_len=max_len,
        speculate=speculate, eos_id=int(payload["eos_id"]),
    )
    if os.environ.get("CONTAINERPILOT_POD_DEBUG"):
        print("SPEC plen=%d stats=%s" % (plen, stats), flush=True)
    return [int(t) for t in np.asarray(jax.device_get(out))[0]]


def _hit_stop(emitted: List[int], stops: List[List[int]]) -> bool:
    """Whether any stop sequence occurs anywhere in the emission —
    the frontend's early-eviction check (the stop-EXCLUSIVE trim
    happens at answer time via InferenceServer._trim_stops, so the
    response is identical to the single-host server's; the eviction
    just stops paying for tokens the trim would discard)."""
    for stop in stops:
        n = len(stop)
        for i in range(len(emitted) - n + 1):
            if emitted[i:i + n] == stop:
                return True
    return False


class _Row:
    """One decode row of a request (n > 1 fans a request into n)."""

    __slots__ = ("emitted", "finished")

    def __init__(self) -> None:
        self.emitted: List[int] = []
        self.finished = False


class _GenReq:
    """Frontend bookkeeping for one /v1/generate|completions request
    riding the slot pool."""

    def __init__(self, work: Dict[str, Any], done_q) -> None:
        self.work = work
        self.done_q = done_q
        self.rows = [_Row() for _ in range(work["n"])]
        self.stream = bool(work.get("_stream"))
        self.cancel = work.get("_cancel")
        self.answered = False

    def cancelled(self) -> bool:
        return self.cancel is not None and self.cancel.is_set()


class _Frontend:
    """Process 0's HTTP surface: requests land in a queue the pod
    loop drains; the loop owns all device work."""

    def __init__(self, host: str, port: int, max_len: int,
                 vocab: int, pod_info: Optional[Dict[str, Any]] = None,
                 text: bool = False, stream_chunk: int = 8,
                 slots: int = 4, cfg: Any = None,
                 prefix_entries: int = 0,
                 ) -> None:
        from prometheus_client import (
            CollectorRegistry,
            Counter,
            Histogram,
        )

        from ..utils.http import HTTPServer, Response

        self.max_len = max_len
        self.vocab = vocab
        self.slots = slots
        self.cfg = cfg  # model config (beam validation); optional
        self.ready = False
        # /v1/model prefix_cache schema stability: the mirror's live
        # PrefixCache is assigned only after warm_pod, but a client
        # polling during the boot window must see the SAME keys —
        # until the live cache lands, a configured cache reports
        # zeroed stats (the true counts: nothing served yet)
        self.prefix_entries = prefix_entries
        self.prefix_cache = None
        # /v1/model payload: model config + pod topology, set by main()
        self.pod_info = pod_info or {}
        self.stream_chunk = max(int(stream_chunk), 1)
        self.requests: "queue.Queue[Tuple[dict, queue.Queue]]" = (
            queue.Queue()
        )
        # observability parity with the single-host server: a private
        # registry (an in-process supervisor's metrics never collide)
        self._registry = CollectorRegistry()
        self._m_requests = Counter(
            "containerpilot_pod_requests",
            "pod frontend requests by endpoint and status",
            ["endpoint", "status"], registry=self._registry,
        )
        self._m_latency = Histogram(
            "containerpilot_pod_request_seconds",
            "pod request latency (broadcast + lockstep decode)",
            registry=self._registry,
            buckets=(.05, .1, .25, .5, 1, 2.5, 5, 10, 30, 60, 120),
        )
        self._m_tokens = Counter(
            "containerpilot_pod_generated_tokens",
            "tokens returned by the pod frontend (post-trim)",
            registry=self._registry,
        )
        from ..telemetry import tracing
        from ..telemetry.goodput import DeviceTimeLedger
        from ..utils.prom import ensure_build_info, ensure_goodput_gauges

        ensure_build_info(self._registry, "pod")
        # device-time ledger, pod-shaped: process 0's round loop is
        # the single writer for prefill/decode/idle (admission
        # boundaries only — the lockstep chunk rounds in between
        # stamp nothing), main() brackets warm_pod as compile_warmup.
        # Followers replay broadcast ops in lockstep, so the
        # frontend's ledger IS the pod's device-time story.
        self.ledger = DeviceTimeLedger()
        # the dispatches/token pair: broadcast rounds that touched
        # the device vs tokens appended — bumped by the round loop
        self.dispatches = 0
        self.tokens_out = 0
        ensure_goodput_gauges(
            self._registry, self.ledger,
            lambda: (self.dispatches, self.tokens_out),
        )
        # request tracing, the single-host server's discipline
        # pod-shaped: adopt/mint a trace id per request, span the
        # queue->pod-loop dispatch, echo id + digest back (see
        # telemetry/tracing.py and docs/90-observability.md)
        self._tracing = tracing
        self._tracer = tracing.TraceRecorder("pod")
        self._server = HTTPServer()
        self._server.route("GET", "/health", self._health)
        self._server.route("GET", "/metrics", self._metrics)
        self._server.route("GET", "/v1/traces", self._traces)
        self._server.route("GET", "/v1/goodput", self._goodput)
        self._server.route("GET", "/v1/model", self._model)
        self._server.route(
            "POST", "/v1/generate", self._traced("generate", self._generate)
        )
        self._server.route(
            "POST", "/v1/score", self._traced("score", self._score)
        )
        # text surface: byte-level tokenizer, zero external assets —
        # the single-host server's --text, pod-shaped
        self.tokenizer = None
        if text:
            from .text import ByteTokenizer

            self.tokenizer = ByteTokenizer(vocab)
            self._server.route(
                "POST", "/v1/completions",
                self._traced("completions", self._completions),
            )
        self._host, self._port = host, port
        self._Response = Response
        self._loop = None
        self._thread: Optional[threading.Thread] = None

    @property
    def port(self) -> int:
        return self._server.bound_port or self._port

    def _traced(self, endpoint: str, handler):
        """Per-request trace around one API handler: adopt the
        caller's X-CP-Trace id (or mint one), echo it on EVERY
        answer — 422s included — and hand buffered responses the
        span digest header. Streams carry only the id (the pod's
        lockstep rounds are accounted by the ``pod_dispatch`` span
        the buffered path records; per-chunk stream spans are the
        single-host server's refinement)."""
        tracing = self._tracing

        async def wrapped(req):
            trace = self._tracer.start(
                tracing.safe_id(req.headers.get("x-cp-trace")),
                endpoint,
            )
            token = tracing.activate(trace)
            try:
                resp = await handler(req)
            except Exception:
                trace.finish(500)
                raise
            finally:
                tracing.deactivate(token)
            resp.headers.setdefault(
                tracing.TRACE_HEADER, trace.trace_id
            )
            if not hasattr(resp, "chunks"):  # buffered Response
                trace.finish(resp.status)
                resp.headers.setdefault(
                    tracing.DIGEST_HEADER, trace.digest()
                )
            else:
                trace.finish(resp.status)
            return resp

        return wrapped

    async def _traces(self, req):
        return self._Response(
            200,
            self._tracer.snapshot_json(req.query),
            content_type="application/json",
        )

    async def _goodput(self, _req):
        """The pod's device-time ledger — same schema as the
        single-host replica's ``/v1/goodput`` (scheduling gaps
        included: the pod's queue->loop dispatch span plays the
        slot_queue_wait role there when the ring ever records it)."""
        from ..telemetry.goodput import goodput_payload

        payload = goodput_payload(
            self.ledger, self._tracer, self.dispatches,
            self.tokens_out, role="pod", ready=self.ready,
            draining=False,
        )
        return self._Response(
            200, json.dumps(payload).encode(),
            content_type="application/json",
        )

    async def _dispatch(self, endpoint: str, work: Dict[str, Any]):
        """queue → pod loop → result, with the latency/500 accounting
        every endpoint shares. Returns (result, None) on success or
        (None, 500 Response) on a pod-side failure."""
        import asyncio

        t0 = time.perf_counter()
        done: "queue.Queue" = queue.Queue()
        self.requests.put((work, done))
        with self._tracing.span("pod_dispatch"):
            result = await asyncio.get_event_loop().run_in_executor(
                None, done.get
            )
        self._m_latency.observe(time.perf_counter() - t0)
        if isinstance(result, Exception):
            self._m_requests.labels(endpoint, "500").inc()
            return None, self._Response(500, f"{result}\n".encode())
        self._m_requests.labels(endpoint, "200").inc()
        return result, None

    async def _health(self, _req):
        if not self.ready:
            return self._Response(503, b"warming\n")
        return self._Response(200, b"ok\n")

    async def _metrics(self, _req):
        from ..utils.prom import exposition

        body, content_type = exposition(self._registry)
        return self._Response(200, body, content_type=content_type)

    async def _model(self, _req):
        self._m_requests.labels("model", "200").inc()
        info = dict(self.pod_info)
        pc = self.prefix_cache
        if pc is not None:
            # live stats, same shape as the single-host /v1/model
            info["prefix_cache"] = {"entries": pc.entries, **pc.stats}
        elif self.prefix_entries > 0:
            # boot window: same schema, zeroed counts (spill fields
            # included — the pod runs without a spill tier, so they
            # stay zero after warm too, mirroring the single-host
            # server's tier-disabled shape)
            info["prefix_cache"] = {
                "entries": self.prefix_entries,
                "hits": 0, "misses": 0, "tokens_reused": 0,
                "spilled": 0, "readmitted": 0, "spill_bytes": 0,
            }
        return self._Response(
            200, json.dumps(info).encode(),
            content_type="application/json",
        )

    def _parse_work(self, body, tokens, default_eos: int = -1):
        """Validate the decode knobs shared by /v1/generate and the
        --text surface into a broadcastable work dict — the
        single-host server's knob set (n, stop, logprobs, beam_width
        included). Full validation HERE: a malformed value that only
        failed inside the pod loop would be pod-fatal (the loop
        deliberately re-raises collective-path errors), and an
        out-of-int32 value would crash payload packing. Raises
        ValueError for a 422."""
        max_new = int(body.get("max_new_tokens", 16))
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(tokens) + max_new > self.max_len:
            raise ValueError(
                f"prompt + max_new_tokens exceeds max_len "
                f"{self.max_len}"
            )
        temperature = float(body.get("temperature", 0.0))
        top_k = int(body.get("top_k", 0))
        top_p = float(body.get("top_p", 0.0))
        eos_id = int(body.get("eos_id", default_eos))
        seed = int(body.get("seed", 0))
        if not 0 <= top_k <= self.vocab:
            raise ValueError(f"top_k must be in [0, {self.vocab}]")
        if not 0.0 <= top_p <= 1.0:
            raise ValueError("top_p must be in [0, 1]")
        if eos_id >= self.vocab:
            raise ValueError(f"eos_id must be < {self.vocab}")
        if not -(2**31) <= seed < 2**31:
            raise ValueError("seed must fit in int32")
        min_new = int(body.get("min_new_tokens", 0))
        if not 0 <= min_new <= max_new:
            raise ValueError(
                "min_new_tokens must be in [0, max_new_tokens]"
            )
        presence = float(body.get("presence_penalty", 0.0))
        frequency = float(body.get("frequency_penalty", 0.0))
        if not (abs(presence) <= 100 and abs(frequency) <= 100):
            raise ValueError(
                "presence/frequency penalties must be in "
                "[-100, 100]"
            )
        from .modelcfg import parse_logit_bias, parse_stop_ids

        bias = parse_logit_bias(
            body.get("logit_bias"), self.vocab
        ) or {}
        stop = parse_stop_ids(body.get("stop"), self.vocab)
        logprobs = bool(body.get("logprobs", False))
        n = int(body.get("n", 1))
        if not 1 <= n <= self.slots:
            raise ValueError(
                f"n must be in [1, --slots {self.slots}] on the pod "
                "frontend (each sample occupies one slot)"
            )
        beam_width = int(body.get("beam_width", 0))
        length_penalty = float(body.get("length_penalty", 0.0))
        if beam_width:
            if n != 1:
                raise ValueError(
                    "n does not compose with beam search (beams "
                    "already return one best row)"
                )
            if temperature > 0.0 or top_k or top_p:
                raise ValueError(
                    "beam search is deterministic; drop "
                    "temperature/top_k/top_p"
                )
            if min_new:
                raise ValueError(
                    "min_new_tokens does not apply to beam search"
                )
            if presence or frequency:
                raise ValueError("penalties do not apply to beam search")
            if bias:
                raise ValueError(
                    "logit_bias does not apply to beam search"
                )
            if self.cfg is not None:
                from ..models.beam import validate_beam_args

                validate_beam_args(self.cfg, 1, beam_width)
            elif not 1 <= beam_width <= self.vocab:
                raise ValueError(
                    f"beam_width must be in [1, vocab {self.vocab}]"
                )
            if beam_width > self.slots:
                # beams tile the KV cache: one request must not exceed
                # the pod's configured device-row budget (--slots, the
                # same sizing the pool uses)
                raise ValueError(
                    f"beam_width capped at --slots ({self.slots}) "
                    "on the pod frontend"
                )
        return {
            "kind": "beam" if beam_width else "gen",
            "tokens": tokens, "max_new": max_new,
            "temperature": temperature,
            "top_k": top_k,
            "top_p": top_p,
            "eos_id": max(eos_id, -1),
            "seed": seed,
            "min_new": min_new,
            "presence": presence,
            "frequency": frequency,
            "logit_bias": bias,
            "stop": stop,
            "logprobs": logprobs,
            "n": n,
            "beam_width": beam_width,
            "length_penalty": length_penalty,
        }

    @staticmethod
    def _check_stream_composes(work) -> None:
        if work["kind"] == "beam":
            raise ValueError(
                "stream does not compose with beam_width (beams "
                "have no incremental prefix)"
            )
        if work["n"] != 1:
            raise ValueError(
                "n does not compose with stream (one SSE stream "
                "carries one row)"
            )
        for knob, why in (
            ("logprobs", "echo logprobs need the full row"),
            ("stop", "stop sequences need whole-row trimming"),
        ):
            if work[knob]:
                raise ValueError(
                    f"stream does not compose with {knob} ({why})"
                )

    def _parse_single_row(self, body, min_len: int = 1):
        rows = body.get("tokens")
        if (
            not isinstance(rows, list) or len(rows) != 1
            or not isinstance(rows[0], list)
            or len(rows[0]) < min_len
        ):
            raise ValueError(
                f"'tokens' must be one row of at least {min_len} "
                "ids (the pod frontend serves single-row requests; "
                "n is the row multiplier)"
            )
        tokens = rows[0]
        if any(
            not isinstance(t, int) or isinstance(t, bool)
            or t < 0 or t >= self.vocab
            for t in tokens
        ):
            raise ValueError(
                f"token ids must be integers in [0, {self.vocab})"
            )
        return tokens

    async def _generate(self, req):
        try:
            body = json.loads(req.body.decode() or "{}")
            work = self._parse_work(body, self._parse_single_row(body))
            if bool(body.get("stream", False)):
                self._check_stream_composes(work)
                return self._stream_request("generate", work)
        except (ValueError, KeyError, TypeError, OverflowError) as exc:
            self._m_requests.labels("generate", "422").inc()
            return self._Response(422, f"{exc}\n".encode())
        result, err = await self._dispatch("generate", work)
        if err is not None:
            return err
        rows = result["tokens"]
        self._m_tokens.inc(sum(len(r) for r in rows))
        payload: Dict[str, Any] = {"tokens": rows}
        if result.get("logprobs") is not None:
            payload["logprobs"] = result["logprobs"]
        return self._Response(
            200, json.dumps(payload).encode(),
            content_type="application/json",
        )

    async def _completions(self, req):
        """Text in/out around the same slot-pool decode /v1/generate
        uses: encode the prompt through the byte tokenizer, default
        eos to the tokenizer's EOS, decode the generated ids back —
        the single-host /v1/completions contract, pod-shaped.
        ``stop`` takes strings here (encoded to token rows before the
        shared parser); ``stream`` emits text deltas with UTF-8
        partial-byte holdback (text.stream_decoder)."""
        tok = self.tokenizer
        try:
            body = json.loads(req.body.decode() or "{}")
            prompt = body.get("prompt")
            if not isinstance(prompt, str) or not prompt:
                raise ValueError("'prompt' must be a non-empty string")
            row = tok.encode(prompt)
            if len(row) >= self.max_len:
                raise ValueError(
                    f"prompt encodes to {len(row)} ids; max_len is "
                    f"{self.max_len}"
                )
            from .modelcfg import parse_stop_strings

            stop_raw = parse_stop_strings(body.pop("stop", None))
            if stop_raw is not None:
                body["stop"] = [
                    tok.encode(s, bos=False) for s in stop_raw
                ]
            work = self._parse_work(body, row, default_eos=tok.EOS)
            # the single-host text surface ignores the logprobs knob
            # (its response carries text+ids only); mirror that
            # instead of paying echo score rounds nobody reads
            work["logprobs"] = False
            if work["n"] > 1:
                raise ValueError(
                    "n returns token rows; use /v1/generate"
                )
            if bool(body.get("stream", False)):
                self._check_stream_composes(work)
                from .text import stream_decoder

                delta_event, tail_events = stream_decoder(tok)
                return self._stream_request(
                    "completions", work, delta_event=delta_event,
                    tail_events=tail_events,
                )
        except (ValueError, KeyError, TypeError, OverflowError) as exc:
            self._m_requests.labels("completions", "422").inc()
            return self._Response(422, f"{exc}\n".encode())
        result, err = await self._dispatch("completions", work)
        if err is not None:
            return err
        row_out = result["tokens"][0]
        self._m_tokens.inc(len(row_out))
        return self._Response(
            200,
            json.dumps(
                {"text": tok.decode(row_out), "tokens": row_out}
            ).encode(),
            content_type="application/json",
        )

    def _stream_request(self, endpoint: str, work,
                        delta_event=None, tail_events=None):
        """SSE over the pod's chunked lockstep rounds: each chunk's
        delta becomes a ``data:`` event as its round lands;
        concatenated deltas equal the non-streamed answer. A client
        disconnect sets the cancel event — the frontend evicts the
        slot at the next round and the pool keeps serving everyone
        else. ``delta_event``/``tail_events`` shape events for the
        text surface (UTF-8 holdback), mirroring the single-host
        server's streaming plumbing."""
        import asyncio
        import threading as threading_mod

        from ..utils.http import StreamingResponse

        if delta_event is None:
            delta_event = lambda d: {"tokens": d}  # noqa: E731
        if tail_events is None:
            tail_events = list  # noqa: E731 — no tail

        cancel = threading_mod.Event()
        work = dict(work, _cancel=cancel, _stream=True)
        done: "queue.Queue" = queue.Queue()
        t0 = time.perf_counter()
        self.requests.put((work, done))
        sent = [0]
        status = ["200"]
        finished = [False]

        def finish() -> None:
            if finished[0]:
                return
            finished[0] = True
            cancel.set()
            self._m_latency.observe(time.perf_counter() - t0)
            self._m_tokens.inc(sent[0])
            self._m_requests.labels(endpoint, status[0]).inc()

        def sse(payload) -> bytes:
            return b"data: " + json.dumps(payload).encode() + b"\n\n"

        async def events():
            loop = asyncio.get_event_loop()
            try:
                while True:
                    item = await loop.run_in_executor(None, done.get)
                    if isinstance(item, Exception):
                        status[0] = "500"
                        yield sse({"error": str(item)})
                        break
                    kind, val = item
                    if kind == "delta":
                        sent[0] += len(val)
                        yield sse(delta_event(val))
                    else:
                        for extra in tail_events():
                            yield sse(extra)
                        yield sse({"done": True, "count": sent[0]})
                        break
            finally:
                finish()

        return StreamingResponse(events(), close=finish)

    async def _score(self, req):
        try:
            body = json.loads(req.body.decode() or "{}")
            tokens = self._parse_single_row(body, min_len=2)
            if len(tokens) > self.max_len:
                raise ValueError(
                    f"row length exceeds max_len {self.max_len}"
                )
        except (ValueError, KeyError, TypeError) as exc:
            self._m_requests.labels("score", "422").inc()
            return self._Response(422, f"{exc}\n".encode())
        result, err = await self._dispatch(
            "score", {"kind": "score", "score": tokens}
        )
        if err is not None:
            return err
        return self._Response(
            200,
            json.dumps(
                {
                    "logprobs": [[round(float(x), 6) for x in row]
                                 for row in result],
                    "sums": [round(float(sum(row)), 6)
                             for row in result],
                }
            ).encode(),
            content_type="application/json",
        )

    def start(self) -> None:
        import asyncio

        started = threading.Event()

        def run() -> None:
            loop = asyncio.new_event_loop()
            asyncio.set_event_loop(loop)
            self._loop = loop
            loop.run_until_complete(
                self._server.start_tcp(self._host, self._port)
            )
            started.set()
            loop.run_forever()

        self._thread = threading.Thread(
            target=run, name="serve-dist-http", daemon=True
        )
        self._thread.start()
        if not started.wait(timeout=30):
            raise RuntimeError("frontend never bound")

    def stop(self) -> None:
        import asyncio

        if self._loop is not None:
            async def shutdown() -> None:
                await self._server.stop()
                asyncio.get_event_loop().stop()

            self._loop.call_soon_threadsafe(
                lambda: asyncio.ensure_future(shutdown())
            )
        if self._thread is not None:
            self._thread.join(timeout=10)


def warm_pod(mirror: _SlotMirror) -> None:
    """Compile the pool's whole serve-path program set before traffic:
    prefill (plen 4), first-sample, insert, the (slots, chunk) chunk
    program, and the width-16 scorer. Every process derives the
    IDENTICAL warm payloads from its own flags (no broadcast needed —
    broadcasting identical data is identity). Requests at these shapes
    compile NOTHING afterwards (the invariant
    tests/test_serve_dist.py::test_pod_warmup_covers_serve_path holds);
    new prompt lengths, beam shapes, and wider score rows still
    compile on first use — the watchdog deadline must absorb exactly
    those."""
    warm = _payload_zeros(mirror.max_len, mirror.slots)
    warm["op"] = np.asarray(OP_ROUND, np.int32)
    _fill_admission(
        warm,
        {
            "tokens": [0, 0, 0, 0],
            "max_new": mirror.chunk + 1,
            "temperature": 0.0, "top_k": 0, "top_p": 0.0,
            "eos_id": -1, "seed": 0, "min_new": 0,
            "presence": 0.0, "frequency": 0.0, "logit_bias": {},
        },
        row_idx=0, slot=0,
    )
    warm["run_chunk"] = np.asarray(1, np.int32)
    warm["done"][0] = 0
    _apply_round(mirror, warm)
    if mirror.window > 1:
        # compile the fused (S, chunk, K) window program inside the
        # same grace: one pure-decode window over the still-admitted
        # warm slot, budget 1 so the device loop runs exactly one
        # round and exits
        warm_w = _payload_zeros(mirror.max_len, mirror.slots)
        warm_w["op"] = np.asarray(OP_ROUND, np.int32)
        warm_w["run_chunk"] = np.asarray(1, np.int32)
        warm_w["done"][0] = 0
        warm_w["rounds"] = np.asarray(mirror.window, np.int32)
        warm_w["budget"][0] = 1
        _apply_round(mirror, warm_w)
    warm_score = _payload_zeros(mirror.max_len, mirror.slots)
    warm_score["plen"] = np.asarray(5, np.int32)
    _score_pod(mirror.params, mirror.cfg, warm_score, mirror.max_len)
    # EVERY cp ring program compiles here, inside the startup grace
    # where the pod is freshly rendezvous-synchronized: ring prefills
    # are the pod's only cross-process collectives outside the
    # broadcast, and a first-use collective program's communicator
    # init has a hard ~30s deadline that request-time compile skew
    # between processes blows (observed killing a live pod). The
    # remainder extend and plain prefill stay per-length request-time
    # compiles — they are local programs, where skew only delays.
    if mirror.cp_buckets:
        from ..parallel.context import cp_prefill_with_remainder

        for head in mirror.cp_buckets:
            warm_prompt = np.zeros((1, head), np.int32)
            logits_cp, cache_cp = cp_prefill_with_remainder(
                mirror.params, warm_prompt, mirror.cfg, mirror.mesh,
                mirror.max_len, head=head,
            )
            jax.block_until_ready((logits_cp, cache_cp))


def _run_frontend_loop(args, frontend: _Frontend, mirror: _SlotMirror,
                       dog, multihost_utils, stopping,
                       draft=None) -> None:
    """Process 0's round loop: drain HTTP work, drive admissions and
    chunks via broadcast ROUNDs, keep the per-request emission
    bookkeeping, answer handlers. Every completed round beat()s the
    watchdog; idle gaps are bounded by heartbeat rounds."""
    from .serve import InferenceServer

    S = args.slots
    heartbeat_every = args.watchdog / 4 if args.watchdog > 0 else None
    pending: "deque[Tuple[_GenReq, int]]" = deque()
    owners: List[Optional[Tuple[_GenReq, int]]] = [None] * S
    open_reqs: List[_GenReq] = []

    def beat() -> None:
        if dog is not None:
            dog.beat()

    def bcast(payload):
        return multihost_utils.broadcast_one_to_all(payload)

    def run_score_round(row: List[int]) -> np.ndarray:
        """One lockstep score op; returns the [1, plen-1] logprobs."""
        p = _payload_zeros(args.max_len, S)
        p["op"] = np.asarray(OP_SCORE, np.int32)
        p["prompt"][: len(row)] = np.asarray(row, np.int32)
        p["plen"] = np.asarray(len(row), np.int32)
        bcast(p)
        out = _score_pod(mirror.params, mirror.cfg, p, args.max_len)
        beat()
        return out

    def echo_logprobs(prompt: List[int],
                      rows_out: List[List[int]]) -> List[List[float]]:
        """Per-token logprobs of the TRIMMED generated rows via
        lockstep score rounds — numerically the single-host
        _echo_logprobs (same jitted scorer, causal attention makes
        pad-width differences free)."""
        lps: List[List[float]] = []
        start = len(prompt) - 1
        for gen in rows_out:
            if not gen:
                lps.append([])
                continue
            picked = run_score_round(prompt + gen)[0]
            lps.append([
                round(float(x), 6)
                for x in picked[start:start + len(gen)]
            ])
        return lps

    def finish_req(req: _GenReq) -> None:
        req.answered = True
        w = req.work
        if req.stream:
            req.done_q.put(("end", None))
            return
        rows_out = [
            InferenceServer._trim(
                [r.emitted], w["max_new"], w["eos_id"]
            )[0]
            for r in req.rows
        ]
        rows_out = InferenceServer._trim_stops(rows_out, w["stop"])
        result: Dict[str, Any] = {"tokens": rows_out}
        if w["logprobs"]:
            result["logprobs"] = echo_logprobs(w["tokens"], rows_out)
        req.done_q.put(result)

    def row_append(req: _GenReq, row: _Row, toks) -> None:
        from ..models.slots import append_chunk

        w = req.work
        before = len(row.emitted)
        ended = append_chunk(
            row.emitted, toks, w["max_new"], w["eos_id"]
        )
        frontend.tokens_out += len(row.emitted) - before
        if w["stop"] and not ended and _hit_stop(
            row.emitted, w["stop"]
        ):
            # the whole-row trim at answer time will cut BEFORE the
            # stop; decoding past it would be paying for discarded
            # tokens — evict at this boundary
            ended = True
        if req.stream and len(row.emitted) > before:
            req.done_q.put(("delta", list(row.emitted[before:])))
        if ended:
            row.finished = True

    def run_one_shot(work, done_q, op, fill_extra, run_op) -> None:
        """The shared answer path for one-shot lockstep ops (beam,
        spec): fill the row payload, broadcast, run, trim, echo
        logprobs if asked, answer — failing pod-fatally like every
        collective path."""
        p = _payload_zeros(args.max_len, S)
        p["op"] = np.asarray(op, np.int32)
        tokens = work["tokens"]
        p["prompt"][: len(tokens)] = np.asarray(tokens, np.int32)
        p["plen"] = np.asarray(len(tokens), np.int32)
        p["max_new_req"] = np.asarray(work["max_new"], np.int32)
        p["eos_id"] = np.asarray(work["eos_id"], np.int32)
        fill_extra(p)
        bcast(p)
        # ledger: a one-shot op is a whole generation in one lockstep
        # program — coarse-attributed to decode (the slot pool's
        # admission rounds get the finer prefill/decode split)
        frontend.ledger.enter("decode")
        frontend.dispatches += 1
        try:
            row = run_op(p)
            beat()
            rows_out = InferenceServer._trim(
                [row], work["max_new"], work["eos_id"]
            )
            rows_out = InferenceServer._trim_stops(
                rows_out, work["stop"]
            )
            # one-shot rows bypass row_append: count their tokens
            # here or the dispatches/token series overstates on
            # beam/spec traffic
            frontend.tokens_out += sum(len(r) for r in rows_out)
            result: Dict[str, Any] = {"tokens": rows_out}
            if work["logprobs"]:
                result["logprobs"] = echo_logprobs(
                    work["tokens"], rows_out
                )
        except Exception as exc:  # noqa: BLE001 — pod-fatal
            done_q.put(exc)
            fail_open(exc)
            raise
        if not any(owners) and not pending:
            # only flip back when the slot pool is truly empty: a
            # beam answered between chunk rounds must not mark a
            # busy pool idle (chunk-only rounds stamp nothing)
            frontend.ledger.engine_idle()
        done_q.put(result)

    def classify(work, done_q) -> None:
        kind = work.get("kind", "gen")
        if kind == "score":
            try:
                out = run_score_round(work["score"])
            except Exception as exc:  # noqa: BLE001 — pod-fatal
                done_q.put(exc)
                fail_open(exc)
                raise
            done_q.put(out.tolist())
            return
        if kind == "beam":
            def fill_beam(p) -> None:
                p["beam_width"] = np.asarray(
                    work["beam_width"], np.int32
                )
                p["length_penalty"] = np.asarray(
                    work["length_penalty"], np.float32
                )

            run_one_shot(
                work, done_q, OP_BEAM, fill_beam,
                lambda p: _beam_pod(
                    mirror.params, mirror.cfg, p, args.max_len
                ),
            )
            return
        if (
            draft is not None
            and not work.get("_stream")
            and not any(owners) and not pending
            and work["n"] == 1
            and work["temperature"] <= 0.0
            and work["min_new"] == 0
            and not work["presence"] and not work["frequency"]
            and not work["logit_bias"]
        ):
            # greedy single request against an IDLE pool: draft-and-
            # verify, identical output, fewer target passes (the
            # single-host routing rule plus the idle condition —
            # under concurrency the slot pool already wins, and a
            # one-shot spec round would stall co-batched streams)
            run_one_shot(
                work, done_q, OP_SPEC, lambda p: None,
                lambda p: _spec_pod(
                    mirror.params, draft, mirror.cfg, p, args.max_len
                ),
            )
            return
        req = _GenReq(work, done_q)
        open_reqs.append(req)
        for i in range(work["n"]):
            pending.append((req, i))

    def fail_open(exc: Exception) -> None:
        """A collective-path failure is pod-fatal: every waiting
        handler must get an answer before the raise, or its executor
        thread blocks forever."""
        for req in open_reqs:
            if not req.answered:
                req.answered = True
                req.done_q.put(exc)
        while True:
            try:
                _w, dq = frontend.requests.get_nowait()
            except queue.Empty:
                break
            dq.put(exc)

    def do_shutdown(leftover=None) -> None:
        """``leftover``: a (work, done_q) item already dequeued when
        SIGTERM landed — it is in neither open_reqs nor the queue, so
        it must be answered explicitly or its handler thread blocks
        forever and the interpreter can't exit."""
        p = _payload_zeros(args.max_len, S)
        p["op"] = np.asarray(OP_SHUTDOWN, np.int32)
        bcast(p)
        err = RuntimeError("pod is shutting down")
        if leftover is not None:
            leftover[1].put(err)
        fail_open(err)

    while True:
        if stopping.is_set():
            do_shutdown()
            return
        if not any(owners) and not pending:
            # fully idle: block for work, heartbeating on cadence so
            # followers' broadcast waits stay bounded
            frontend.ledger.engine_idle()
            got = None
            idle_since = time.monotonic()
            while got is None and not stopping.is_set():
                try:
                    got = frontend.requests.get(timeout=0.25)
                except queue.Empty:
                    if (
                        heartbeat_every is not None
                        and time.monotonic() - idle_since
                        >= heartbeat_every
                    ):
                        break
            if stopping.is_set():
                do_shutdown(leftover=got)
                return
            if got is None:
                p = _payload_zeros(args.max_len, S)
                p["op"] = np.asarray(OP_HEARTBEAT, np.int32)
                bcast(p)
                beat()
                continue
            classify(*got)
            continue
        # busy: drain whatever queued without blocking (scores and
        # beams run as their own lockstep ops between chunk rounds)
        while True:
            try:
                classify(*frontend.requests.get_nowait())
            except queue.Empty:
                break
        # sweep cancelled streams: their rows finish NOW, their slots
        # drop out of the next mask, the pool keeps serving the rest
        for req in open_reqs:
            if req.cancelled() and not req.answered:
                for r in req.rows:
                    r.finished = True
                finish_req(req)
        open_reqs[:] = [r for r in open_reqs if not r.answered]
        for i, o in enumerate(owners):
            if o is not None and o[0].rows[o[1]].finished:
                owners[i] = None
        # admission: at most one row per round (the payload carries
        # one prompt) — a fresh request reaches the pool within one
        # chunk of arriving
        payload = _payload_zeros(args.max_len, S)
        payload["op"] = np.asarray(OP_ROUND, np.int32)
        admit: Optional[Tuple[_GenReq, int, int]] = None
        free = [i for i, o in enumerate(owners) if o is None]
        while pending and free and admit is None:
            req, ridx = pending.popleft()
            if req.answered or req.cancelled():
                continue
            slot = free[0]
            _fill_admission(payload, req.work, ridx, slot)
            owners[slot] = (req, ridx)
            admit = (req, ridx, slot)
        mask = np.ones(S, np.int32)
        for i, o in enumerate(owners):
            if o is not None and not o[0].rows[o[1]].finished:
                mask[i] = 0
        run_chunk = int((mask == 0).any())
        if admit is None and not run_chunk:
            continue  # e.g. everything was just cancelled
        payload["run_chunk"] = np.asarray(run_chunk, np.int32)
        payload["done"] = mask
        # fuse K chunk-rounds into one dispatch on pure-decode rounds
        # (the single-host engine's host-re-entry rule, pod-shaped):
        # an admission round, queued HTTP work, a PENDING row waiting
        # for a free slot, or an active row watching stop sequences
        # keeps chunk granularity — stop eviction saves real decode,
        # and a waiting request must grab the next freed slot within
        # one chunk, not one window. Budget = each row's remaining
        # max_new, the window's early-exit gate.
        rounds = 1
        budget = np.zeros(S, np.int32)
        if (
            mirror.window > 1 and run_chunk and admit is None
            and not pending
            and frontend.requests.empty()
            and not any(
                o is not None and o[0].work["stop"]
                for o in owners
            )
        ):
            rounds = mirror.window
            for i, o in enumerate(owners):
                if o is not None and not mask[i]:
                    req_o, ridx_o = o
                    budget[i] = max(
                        req_o.work["max_new"]
                        - len(req_o.rows[ridx_o].emitted), 0,
                    )
        payload["rounds"] = np.asarray(rounds, np.int32)
        payload["budget"] = budget
        # ledger stamps at ADMISSION boundaries only (the single-host
        # engine's discipline): an admission round is prefill, the
        # rounds after it decode; chunk-only rounds stamp nothing
        if admit is not None:
            frontend.ledger.enter("prefill")
        bcast(payload)
        try:
            first, toks = _apply_round(mirror, payload)
        except Exception as exc:  # noqa: BLE001 — pod-fatal
            fail_open(exc)
            raise
        frontend.dispatches += 1
        if admit is not None:
            frontend.ledger.enter("decode")
        if admit is not None:
            req, ridx, _slot = admit
            row_append(req, req.rows[ridx], [first])
        if toks is not None:
            for i, o in enumerate(owners):
                if o is None or mask[i]:
                    continue
                req, ridx = o
                row = req.rows[ridx]
                if not row.finished:
                    row_append(req, row, toks[i])
        for i, o in enumerate(owners):
            if o is not None and o[0].rows[o[1]].finished:
                owners[i] = None
        for req in open_reqs:
            if not req.answered and all(
                r.finished for r in req.rows
            ):
                finish_req(req)
        open_reqs[:] = [r for r in open_reqs if not r.answered]
        beat()


def _run_follower_loop(args, mirror: _SlotMirror, dog,
                       multihost_utils, draft=None) -> None:
    """Followers replay whatever op the frontend broadcast; their
    device state stays bit-identical to process 0's because both run
    exactly `_apply_round` on exactly the broadcast operands."""
    while True:
        if args.wedge_file and os.path.exists(args.wedge_file):
            # fault injection: consume the trigger (wedge ONCE, so
            # the reincarnation comes back healthy) and stop making
            # progress without exiting — exactly what a stuck decode
            # looks like to the rest of the pod
            try:
                os.remove(args.wedge_file)
            except OSError:
                pass
            print("follower: injected wedge", flush=True)
            while True:
                time.sleep(3600)
        payload = multihost_utils.broadcast_one_to_all(
            _payload_zeros(args.max_len, args.slots)
        )
        op = int(payload["op"])
        if op == OP_SHUTDOWN:
            return
        if op == OP_HEARTBEAT:
            pass
        elif op == OP_SCORE:
            _score_pod(
                mirror.params, mirror.cfg, payload, args.max_len
            )
        elif op == OP_BEAM:
            _beam_pod(mirror.params, mirror.cfg, payload, args.max_len)
        elif op == OP_SPEC:
            _spec_pod(
                mirror.params, draft, mirror.cfg, payload,
                args.max_len,
            )
        elif op == OP_ROUND:
            _apply_round(mirror, payload)
        if dog is not None:
            dog.beat()


def main() -> int:
    from jax.experimental import multihost_utils

    from ..discovery.consul import ConsulBackend
    from ..models.transformer import TransformerConfig, init_params
    from ..parallel import MeshPlan, initialize_from_catalog, make_mesh
    from .modelcfg import derive_d_ff, enable_compile_cache

    enable_compile_cache()

    parser = argparse.ArgumentParser(
        description="multi-host pod inference server"
    )
    parser.add_argument("--process-id", type=int, required=True)
    parser.add_argument("--num-processes", type=int, required=True)
    parser.add_argument("--catalog", required=True)
    parser.add_argument("--coordinator-port", type=int, default=0)
    parser.add_argument("--advertise-address", default="")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8000)
    parser.add_argument("--max-len", type=int, default=512)
    parser.add_argument("--d-model", type=int, default=256)
    parser.add_argument("--n-layers", type=int, default=2)
    parser.add_argument("--n-heads", type=int, default=4)
    parser.add_argument("--n-kv-heads", type=int, default=0)
    parser.add_argument("--vocab", type=int, default=1024)
    parser.add_argument("--checkpoint-dir", default="",
                        help="shared-storage checkpoint the WHOLE pod "
                        "restores in lockstep (orbax is a global "
                        "checkpointer)")
    parser.add_argument("--use-ema", action="store_true")
    parser.add_argument("--slots", type=int, default=4,
                        help="slot-pool size: how many requests decode "
                        "concurrently in lockstep (also the n / "
                        "beam_width budget); KV memory scales with it")
    parser.add_argument("--stream-chunk", type=int, default=8,
                        help="tokens per lockstep chunk round — the "
                        "admission latency, the SSE delta "
                        "granularity, and the watchdog's progress "
                        "quantum")
    parser.add_argument("--slot-window", type=int, default=4,
                        help="chunk-rounds fused into one device "
                        "dispatch on pure-decode rounds (device-side "
                        "loop, early exit on done/budget); "
                        "admissions, queued work and stop-sequence "
                        "watches keep chunk granularity. 1 = off. "
                        "The watchdog quantum grows to "
                        "window*stream-chunk tokens on fused rounds")
    parser.add_argument("--draft-layers", type=int, default=0,
                        help="self-speculative decoding: greedy "
                        "single requests against an idle pool draft "
                        "with the model's first N layers and verify "
                        "in chunks — identical output, fewer target "
                        "passes (0 = off)")
    parser.add_argument("--speculate", type=int, default=4,
                        help="draft tokens per speculative round")
    parser.add_argument("--kv-int8", action="store_true",
                        help="serve with the int8 KV cache (half the "
                        "KV bytes; every process quantizes "
                        "identically, so lockstep answers are still "
                        "deterministic)")
    parser.add_argument("--prefill-chunk", type=int, default=0,
                        help="admissions longer than N prefill in "
                        "fixed-size pieces (O(N) peak activations, "
                        "bounded piece-length set; local programs, "
                        "so compile skew between processes only "
                        "delays). 0 = one-shot admission prefill; "
                        "prompts taking the --sp ring skip this")
    parser.add_argument("--prefix-cache", type=int, default=0,
                        help="prefix KV reuse on the pod: every "
                        "process keeps an IDENTICAL LRU of the last "
                        "N admitted prompts' KV rows (admissions are "
                        "broadcast, so cache state stays lockstep by "
                        "construction); admissions sharing a cached "
                        "prefix rewind+extend instead of full "
                        "prefill. 0 = off; rejects --sp and --window")
    parser.add_argument("--window", type=int, default=0,
                        help="sliding-window attention: each slot's "
                        "KV cache is a ring of min(window, max_len) "
                        "entries, bounding decode KV memory by the "
                        "window instead of max_len (0 = full "
                        "attention). Static config, so lockstep "
                        "dispatch is unchanged; composes with "
                        "--kv-int8 but not --draft-layers")
    parser.add_argument("--int8", action="store_true",
                        help="weight-only int8: ~4x smaller resident "
                        "params on every host (each process quantizes "
                        "its shards identically in lockstep)")
    parser.add_argument("--lora-dir", default="",
                        help="merge a trained LoRA adapter checkpoint "
                        "into the base weights at load — restored "
                        "through the same orbax global barriers as "
                        "--checkpoint-dir, before any --int8")
    parser.add_argument("--lora-rank", type=int, default=0,
                        help="rank of the adapter in --lora-dir")
    parser.add_argument("--text", action="store_true",
                        help="byte-tokenizer /v1/completions on the "
                        "frontend (vocab must be >= 259)")
    parser.add_argument("--sp", type=int, default=1,
                        help="context-parallel admission: a seq axis "
                        "of this many devices rings long-prompt "
                        "prefills (ops/ring_attention.py) so prefill "
                        "activation memory is bounded by prompt/sp "
                        "per device; decode stays on the replicated "
                        "slot pool. Composes with --dp and tensor "
                        "parallelism (dp x sp x tp mesh); not with "
                        "--window or --draft-layers")
    parser.add_argument("--cp-min-len", type=int, default=0,
                        help="minimum prompt length that rings over "
                        "the seq axis (shorter prompts prefill "
                        "replicated); 0 derives the seq axis size")
    parser.add_argument("--dp", type=int, default=1,
                        help="data-parallel axis size: the global "
                        "device count factors as (dp, devices/dp) — "
                        "model shards over the inner axis")
    parser.add_argument("--watchdog", type=float, default=0.0,
                        help="decode-progress deadline in seconds "
                        "(0 = off): every process hard-exits %d when "
                        "a broadcast+decode cycle stalls past it. "
                        "Generation is chunked, so size it above one "
                        "chunk round plus the slowest ONE-SHOT op "
                        "(a beam round, a score round, or an "
                        "unwarmed-shape compile)"
                        % WATCHDOG_EXIT)
    parser.add_argument("--startup-grace", type=float, default=300.0,
                        help="first-beat grace covering rendezvous + "
                        "restore + warmup compile")
    parser.add_argument("--wedge-file", default="",
                        help="fault injection (tests): when this file "
                        "exists, a follower consumes it and wedges — "
                        "stops making progress without exiting — to "
                        "prove the watchdog path")
    args = parser.parse_args()

    # armed BEFORE rendezvous (the trainer's pattern): a peer that
    # died between catalog registration and its first collective
    # wedges our rendezvous/warmup just as silently as a mid-serve
    # death, and the grace window covers the startup compile
    dog = None
    if args.watchdog > 0:
        from ..parallel import StepWatchdog

        dog = StepWatchdog(
            args.watchdog, exit_code=WATCHDOG_EXIT
        ).start(grace_s=max(args.startup_grace, args.watchdog))

    if args.slots < 1 or args.stream_chunk < 1:
        raise SystemExit("--slots and --stream-chunk must be >= 1")
    if args.window < 0:
        raise SystemExit("--window must be >= 0")
    if args.dp < 1 or args.sp < 1:
        raise SystemExit("--dp and --sp must be >= 1")
    if args.sp > 1 and args.window > 0:
        raise SystemExit(
            "--sp does not compose with --window (ring attention "
            "rejects sliding windows)"
        )
    if args.sp > 1 and args.draft_layers > 0:
        raise SystemExit(
            "--sp does not compose with --draft-layers (speculative "
            "prefill is chunk-driven)"
        )
    if args.prefix_cache < 0:
        raise SystemExit("--prefix-cache must be >= 0")
    if args.prefill_chunk < 0:
        raise SystemExit("--prefill-chunk must be >= 0")
    if args.prefix_cache > 0 and args.sp > 1:
        raise SystemExit(
            "--prefix-cache does not compose with --sp (cached "
            "prefixes bypass the ring)"
        )
    if args.prefix_cache > 0 and args.window > 0:
        raise SystemExit(
            "--prefix-cache does not compose with --window (a ring "
            "cache's stale rows are live window context)"
        )
    cp_min_len = args.cp_min_len
    if args.sp <= 1 and cp_min_len:
        raise SystemExit("--cp-min-len requires --sp > 1")
    if args.sp > 1:
        # ONE policy for deriving/clamping/refusing the threshold,
        # shared with the single-host --cp (parallel/context.py)
        from ..parallel.context import resolve_cp_min_len

        try:
            cp_min_len = resolve_cp_min_len(
                cp_min_len, args.sp, args.max_len, flag="sp"
            )
        except ValueError as exc:
            raise SystemExit(str(exc)) from None
    if args.window > 0 and args.draft_layers > 0:
        # same composition rule as the single-host server
        # (workload/serve.py): speculative rollback cannot undo
        # ring-cache writes. Checked BEFORE rendezvous so every
        # process fails at startup, not mid-collective.
        raise SystemExit(
            "--draft-layers does not compose with --window "
            "(speculative rollback cannot undo ring-cache writes)"
        )
    if 4 + args.stream_chunk + 1 > args.max_len:
        # warmup pushes a 4-id prompt + chunk+1 tokens through the
        # pool; a legal but tiny --max-len must fail loudly HERE
        raise SystemExit(
            f"--max-len {args.max_len} too small for the warmup "
            f"request (needs >= {4 + args.stream_chunk + 1})"
        )
    kw = {}
    if args.coordinator_port:
        kw["coordinator_port"] = args.coordinator_port
    initialize_from_catalog(
        ConsulBackend(address=args.catalog),
        args.process_id,
        args.num_processes,
        advertise_address=args.advertise_address,
        **kw,
    )
    cfg = TransformerConfig(
        vocab_size=args.vocab,
        d_model=args.d_model,
        n_heads=args.n_heads,
        n_kv_heads=args.n_kv_heads,
        n_layers=args.n_layers,
        d_ff=derive_d_ff(args.d_model),
        max_seq_len=args.max_len,
        kv_int8=args.kv_int8,
        window=args.window,
    )
    if args.text:
        from .text import ByteTokenizer

        if args.vocab < ByteTokenizer.N_IDS:
            # EVERY process must fail here, not just the frontend:
            # a frontend dying after rendezvous would strand the
            # followers in their first broadcast
            raise SystemExit(
                f"--text needs vocab >= {ByteTokenizer.N_IDS}, got "
                f"{args.vocab}"
            )
    n_global = jax.device_count()
    if n_global % (args.dp * args.sp):
        raise SystemExit(
            f"--dp {args.dp} x --sp {args.sp} must divide the "
            f"{n_global} global devices"
        )
    n_model = n_global // (args.dp * args.sp)
    if cfg.n_heads % n_model:
        raise SystemExit(
            f"model axis {n_model} must divide n_heads {cfg.n_heads}"
        )
    mesh = make_mesh(
        jax.devices(),
        plan=MeshPlan(data=args.dp, model=n_model, seq=args.sp),
    )
    if args.checkpoint_dir:
        from .modelcfg import restore_params_only

        restored = restore_params_only(
            cfg, mesh, args.checkpoint_dir, use_ema=args.use_ema
        )
        if restored is None:
            raise SystemExit(f"no checkpoint in {args.checkpoint_dir}")
        params, step = restored
        if args.process_id == 0:
            print(f"pod serving checkpoint step {step}", flush=True)
    else:
        host_params = jax.tree.map(
            np.asarray, init_params(jax.random.PRNGKey(0), cfg)
        )
        params = shard_params_global(host_params, mesh, cfg)

    from .modelcfg import validate_lora_flags

    validate_lora_flags(args.lora_dir, args.lora_rank)
    if args.lora_dir:
        # merge BEFORE any quantization (int8 bases aren't
        # adaptable); the orbax restore barriers keep it lockstep
        from .modelcfg import merge_lora

        params, lora_step = merge_lora(
            params, cfg, mesh, args.lora_dir, args.lora_rank
        )
        if args.process_id == 0:
            print(
                f"pod merged lora adapter (rank {args.lora_rank}, "
                f"step {lora_step})", flush=True,
            )
    if args.int8:
        # every process quantizes its shards with the same program
        # (scales reduce over replicated-or-sharded axes under SPMD),
        # so lockstep dispatch stays identical
        from ..models.quantized import quantize_model_params

        params = quantize_model_params(params)
        if args.process_id == 0:
            print("pod int8 weight-only params", flush=True)

    draft = None
    if args.draft_layers > 0:
        if args.speculate < 1:
            raise SystemExit("--speculate must be >= 1")
        if not 0 < args.draft_layers < cfg.n_layers:
            # every process must fail here, not mid-rendezvous
            raise SystemExit(
                f"--draft-layers must be in (0, {cfg.n_layers})"
            )
        from ..models.speculative import layer_prefix_draft

        draft_params, draft_cfg = layer_prefix_draft(
            params, cfg, args.draft_layers
        )
        draft = (draft_params, draft_cfg, args.speculate)

    frontend = None
    if args.process_id == 0:
        frontend = _Frontend(
            args.host, args.port, args.max_len, cfg.vocab_size,
            text=args.text, stream_chunk=args.stream_chunk,
            slots=args.slots, cfg=cfg,
            prefix_entries=args.prefix_cache,
            pod_info={
                "vocab_size": cfg.vocab_size,
                "d_model": cfg.d_model,
                "n_heads": cfg.n_heads,
                "n_kv_heads": cfg.kv_heads,
                "n_layers": cfg.n_layers,
                "max_len": args.max_len,
                "text": args.text,
                "stream": True,
                "kv_int8": args.kv_int8,
                "window": args.window or None,
                "prefix_cache": (
                    {"entries": args.prefix_cache}
                    if args.prefix_cache > 0 else None
                ),
                "prefill_chunk": args.prefill_chunk or None,
                "int8": args.int8,
                "lora": (
                    {"rank": args.lora_rank}
                    if args.lora_dir else None
                ),
                "speculative": (
                    {
                        "draft_layers": args.draft_layers,
                        "speculate": args.speculate,
                    }
                    if draft is not None else None
                ),
                "slot_engine": {
                    "slots": args.slots,
                    "chunk": args.stream_chunk,
                    "window": max(1, args.slot_window),
                },
                "pod": {
                    "num_processes": args.num_processes,
                    "devices": n_global,
                    "mesh": {
                        "data": args.dp, "seq": args.sp,
                        "model": n_model,
                    },
                    "watchdog_s": args.watchdog or None,
                },
                # same JSON shape as the single-host /v1/model cp
                # block (workload/serve.py) so clients read one schema
                "cp": (
                    {"seq": args.sp, "min_len": cp_min_len}
                    if args.sp > 1 else None
                ),
            },
        )
        frontend.start()
        print(f"pod frontend on {args.host}:{frontend.port} "
              f"({n_global} global devices, data={args.dp} "
              f"model={n_model}, slots={args.slots})",
              flush=True)

    # warmup in lockstep before /health goes 200 (warm_pod compiles
    # the pool's whole serve-path program set; see its docstring for
    # the no-post-grace-compiles invariant)
    if frontend is not None:
        # ledger: everything until ready flips is compile_warmup —
        # stamped before /health goes 200 so the pod's first scrape
        # already shows its compile badput (the no-idle-lie rule)
        frontend.ledger.set_override("compile_warmup")
    mirror = _SlotMirror(
        cfg, params, args.max_len, args.slots, args.stream_chunk,
        mesh=mesh, sp=args.sp, cp_min_len=cp_min_len,
        prefix_entries=args.prefix_cache,
        prefill_chunk=args.prefill_chunk,
        window=max(1, args.slot_window),
    )
    warm_pod(mirror)
    if draft is not None:
        # compile the spec path's whole program set inside the grace —
        # one shared rule for both servers (models/speculative.py)
        from ..models.speculative import warm_speculative

        draft_params, draft_cfg, spec_k = draft
        warm_speculative(
            params, draft_params, cfg, draft_cfg, spec_k, args.max_len,
        )
    if dog is not None:
        dog.beat()  # startup done: tighten to the serve deadline
    if frontend is not None:
        # live prefix stats for /v1/model (the mirror owns the cache)
        frontend.prefix_cache = mirror.prefix_cache
        frontend.ledger.clear_override()
        frontend.ledger.enter("idle")
        frontend.ready = True
        print("pod warm; accepting traffic", flush=True)

    # graceful pod shutdown: TERM on the FRONTEND broadcasts
    # OP_SHUTDOWN so followers exit cleanly. Followers keep the
    # default TERM disposition — a follower can't exit mid-collective
    # anyway, so its supervisor's TERM-then-KILL handles it.
    stopping = threading.Event()
    if frontend is not None:
        import signal as signal_mod

        signal_mod.signal(
            signal_mod.SIGTERM, lambda s, f: stopping.set()
        )
        _run_frontend_loop(
            args, frontend, mirror, dog, multihost_utils, stopping,
            draft=draft,
        )
    else:
        _run_follower_loop(
            args, mirror, dog, multihost_utils, draft=draft
        )
    if dog is not None:
        dog.stop()
    if frontend is not None:
        frontend.stop()
        print("pod frontend stopped", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
