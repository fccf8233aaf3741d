"""The slot engine: continuous decode admission for serving.

Every sampled sequence a replica serves JOINS a running decode here
(workload/serve.py submits a request's rows one by one). A fixed
pool of S slots decodes in fixed-size chunks (models/slots.py — one
compiled program set, static shapes); between dispatches the engine
harvests finished rows and admits queued requests into free slots, so
a short request lands mid-flight next to a long one instead of
waiting for the whole batch generation to finish.

The engine drives a **step program** (models/stepprog.py), not a
model directly: the plain transformer, quantized weights and
speculative draft/verify all implement the same
admit/dispatch/tokens/retire protocol, so every decode strategy
inherits admission, streaming, cancel, tracing and the ledger from
ONE driver. With ``window`` K > 1 the plain program fuses K
chunk-rounds into one device-side loop per host dispatch
(``decode_slots_window``): the host's per-round loop becomes a
per-K-window loop and dispatches/token falls ~K-fold on steady-state
decode. The host re-enters at chunk granularity exactly when a
decision is pending — queued admissions, a cancel flag, or stop —
the same lookahead test that already gated pipelining, generalized
from one round to one window.

Per-request output is byte-identical to a solo ``generate`` call with
the same arguments (the key schedule is reproduced exactly; each
slot's draw depends only on its own key and step index; a fused
window runs the same per-step body as K sequential chunks) — tested
against staggered concurrent traffic at K=1 and K>1.

One engine per server process; it owns a worker thread and the step
program's device buffers (chunk/insert donate them). ``submit`` is
thread-safe and returns a concurrent.futures.Future resolving to the
generated ids (pad-trimmed after eos, capped at the request's
max_new_tokens).
"""
from __future__ import annotations

import logging
import queue
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import List, Optional

import jax.numpy as jnp
import numpy as np

from ..models.decode import (
    BIAS_SLOTS_MAX,
    _jitted_prefill,
    normalize_logit_bias,
    row_arm,
)
from ..models.slots import append_chunk
from ..models.stepprog import make_step_program
from ..models.transformer import TransformerConfig
from ..telemetry import tracing
from ..telemetry.goodput import EnginePhases, name_os_thread
from .serve_prefix import MIN_REUSE as PREFIX_MIN_REUSE

log = logging.getLogger("containerpilot.serve.slots")

#: ``stats["sampler"]``'s keys (``/v1/model`` ``slot_engine.sampler``)
#: by the index of the sampler's arm (models/decode.py SAMPLER_ARMS)
SAMPLER_ROUNDS = ("rounds_argmax", "rounds_draw", "rounds_filter")


@dataclass
class _Request:
    tokens: List[int]
    max_new: int
    temperature: float
    top_k: int
    top_p: float
    eos_id: int
    pad_id: int
    seed: int
    # this sequence's index within its HTTP request (several token
    # rows, or ``n`` samples of one): the row's key is
    # fold_in(PRNGKey(seed), row), so the rows of one request draw
    # independently and a one-row request keeps row 0's key
    row: int = 0
    min_new: int = 0
    presence: float = 0.0
    frequency: float = 0.0
    # [BIAS_SLOTS_MAX] logit_bias row (idx -1 = unused) — always
    # materialized at the engine's ONE static width so biased and
    # plain requests share every compiled program
    bias_idx: Optional[object] = None
    bias_val: Optional[object] = None
    # streaming: called from the worker thread with each newly emitted
    # token delta (already eos/max_new-capped — concatenation equals
    # the future's final result exactly)
    on_tokens: Optional[callable] = None
    # cooperative cancel (client disconnect): the worker frees the
    # slot at the next chunk boundary instead of decoding to the end
    cancel: Optional[threading.Event] = None
    # tracing (telemetry/tracing.py): a caller-owned dict the engine
    # stamps at REQUEST boundaries only — enqueued/admitted/
    # prefill_done/done (time.monotonic, tracing's clock) plus a
    # rounds count. Nothing is recorded per token or per round beyond
    # one int increment, so the hotpath decode loop stays
    # allocation-free; the caller converts the stamps to spans once,
    # after the future resolves (tracing.add_engine_spans).
    timings: Optional[dict] = None
    # the id of the trace the request was submitted under ("" = none):
    # its ``engine.admit`` event in a profiler trace carries it, so
    # the request's spans on tracing's clock and the engine's span on
    # the device's clock are the same request by name
    trace: str = ""
    # submit's perf_counter stamp (the engine's clock): one float per
    # request, read once at admission for the phases' queue_wait_s
    enqueued: float = 0.0
    future: Future = field(default_factory=Future)


@dataclass
class _Warm:
    """``warm_programs``'s place in the queue: the worker runs the
    step program's own warm-up when no slot is occupied."""
    future: Future = field(default_factory=Future)


@dataclass
class _Slot:
    req: _Request
    emitted: List[int] = field(default_factory=list)
    finished: bool = False  # eos seen (pads follow) or max_new reached
    rounds: int = 0  # decode rounds this row rode (tracing metadata)


class SlotEngine:
    def __init__(
        self,
        cfg: TransformerConfig,
        params,
        max_len: int,
        slots: int = 8,
        chunk: int = 8,
        window: int = 4,
        cp_mesh=None,
        cp_min_len: int = 0,
        prefill_chunk: int = 0,
        prefix_cache=None,
        ledger=None,
        program=None,
        prefill_floor_s: float = 0.0,
    ) -> None:
        if slots < 1 or chunk < 1:
            raise ValueError("slots and chunk must be >= 1")
        if prefill_floor_s < 0:
            raise ValueError("prefill_floor_s must be >= 0")
        # synthetic cold-admission floor (chaos/bench seam, never set
        # in production): every COLD prefill of a reusable-length
        # prompt blocks the worker thread this many extra seconds —
        # standing in for a production-sized prompt's prefill compute
        # on the toy model, the way the chaos suite's ``slow`` faults
        # stand in for decode time. Reuse hits (including handed-off
        # KV) skip it entirely, which is exactly the interference the
        # disaggregation bench measures.
        self.prefill_floor_s = prefill_floor_s
        if window < 1:
            raise ValueError("window must be >= 1")
        # context-parallel admission: prompts at least cp_min_len
        # long ring their prefill over cp_mesh's seq axis
        # (parallel/context.py cp_prefill_with_remainder — the same
        # recipe the pod's --sp path runs) before joining the pool.
        # Single-process here, so the maximal axis-divisible head
        # applies (no cross-process compile-skew hazard; see
        # cp_head_buckets for the pod's bucketed variant).
        if cp_mesh is not None and cfg.window > 0:
            raise ValueError(
                "cp does not compose with sliding windows (ring "
                "attention rejects them)"
            )
        self.cp_mesh = cp_mesh
        self.cp_min_len = cp_min_len
        if cp_mesh is not None:
            # the ONE threshold policy (derive/clamp/never-engages)
            # applies no matter who constructs the engine — a direct
            # SlotEngine(cp_mesh=...) must not silently ring every
            # prompt or accept a threshold no prompt can reach
            from ..parallel.context import resolve_cp_min_len

            self.cp_min_len = resolve_cp_min_len(
                cp_min_len, cp_mesh.shape.get("seq", 1), max_len
            )
        # chunked admission: prompts longer than prefill_chunk
        # prefill in fixed-size pieces (models/decode.chunked_prefill
        # — peak activation memory O(chunk) instead of O(prompt), a
        # bounded piece-length set so compile churn stays finite).
        # Prompts that take the cp ring skip this (the ring already
        # bounds activations; its remainder decomposes separately).
        self.prefill_chunk = prefill_chunk
        if prefill_chunk < 0:
            raise ValueError("prefill_chunk must be >= 0")
        # prefix KV reuse under continuous batching: admissions with a
        # cached prefix rewind+extend instead of full prefill, and
        # every admission's prompt cache is stored for future turns.
        # Sound because stored entries are standalone buffers: extend
        # never donates its cache operand and insert_row COPIES the
        # row into the (donated) pool, so pool churn can't touch them.
        self.prefix_cache = prefix_cache
        if prefix_cache is not None:
            if cp_mesh is not None:
                raise ValueError(
                    "prefix cache does not compose with cp (cached "
                    "prefixes bypass the ring)"
                )
            if cfg.window > 0:
                raise ValueError(
                    "prefix cache does not compose with sliding "
                    "windows (a ring cache's stale rows are live "
                    "window context)"
                )
        # sliding windows (cfg.window > 0) compose: each slot's ring
        # cache is row-local, and admission writes the freshly
        # prefilled row WHOLESALE (insert_row dynamic_update_slices
        # the entire [layers, 1, ring, kv, hd] row plus its pos), so
        # a reused slot carries zero context from its previous
        # occupant — byte parity incl. re-admission is tested in
        # tests/test_slots.py::test_window_*
        # device-time ledger (telemetry/goodput.py): the engine is
        # the authority on prefill/decode/idle, stamped at the SAME
        # request boundaries the tracing timings use — admission
        # start, admission done, fully-idle — never per round or per
        # token. None (direct engine construction, benches) costs one
        # attribute load at those boundaries.
        self.ledger = ledger
        # engine phases (telemetry/goodput.py EnginePhases): every
        # cycle of the worker thread is partitioned into named phases
        # whose seconds and counts land in the ledger's accumulator
        # (``/v1/goodput`` ``engine``) and, under a profiler trace,
        # on the ``slot-engine`` line. The boundaries are the loop's
        # own perf_counter reads; the work is O(phases) per cycle
        # (a chunk or a fused window), never per token or per slot.
        # A bare engine (no ledger) keeps its own accumulator.
        self.phases = ledger.engine if ledger is not None else EnginePhases()
        if prefix_cache is not None:
            # store and readmit happen inside admissions, the spill
            # behind them: their seconds and bytes belong to the same
            # accumulator
            prefix_cache.attach_phases(self.phases)
        # dispatch accounting for the dispatches/token series (the
        # number the ROADMAP's megakernel item must drive down): one
        # int bump per device dispatch (prefill or chunk round), one
        # add per emitted delta — same cost class as the per-slot
        # rounds counter tracing already pays.
        self.dispatches = 0
        self.tokens_out = 0
        # decode rounds (a chunk is one, a fused window as many as it
        # ran) by the sampler's arm that the live slots' knobs called
        # for at the dispatch (``_sampler_arm``): the HOST's view. The device chooses step by step from its own
        # ``done`` flags, so a row that ends inside a round can turn
        # the rest of a counted ``filter`` round into argmax steps; a
        # trace's ``sample.*`` scopes are the device's word
        self.sampler_rounds = dict.fromkeys(SAMPLER_ROUNDS, 0)
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        # the step program (models/stepprog.py): owns the pool cache
        # and the ENTIRELY device-resident per-slot sampling state
        # (written only at admission/retirement, read every dispatch
        # with zero host->device uploads beyond the window's [S]
        # budget ints — no host numpy buffers left, so the zero-copy
        # in-place-mutation hazard class is gone by construction).
        # None builds the default for the params (plain or
        # quantized); an explicit program (e.g. speculative) brings
        # its own slots/chunk geometry, which wins.
        if program is None:
            program = make_step_program(
                cfg, params, max_len, slots, chunk, rounds=window
            )
        self.program = program
        # the program opens ``engine.admit.first_token``'s children
        # where the work happens (an optional member of the contract:
        # the speculative program brings none and records nothing)
        attach = getattr(program, "attach_phases", None)
        if attach is not None:
            attach(self.phases)
        self.slots = program.slots
        self.chunk = program.chunk
        self.window = getattr(program, "rounds", 1)
        # how far a dispatch reads the pool's rows is the program's to
        # say (``ladder``, ``read_len``: optional members); one that
        # does not say reads them whole
        self.read_ladder = tuple(getattr(program, "ladder", (max_len,)))
        self._read_len = getattr(
            program, "read_len", lambda fused: max_len
        )
        self._active: List[Optional[_Slot]] = [None] * self.slots
        self._queue: "queue.Queue[Optional[_Request]]" = queue.Queue()
        self._submit_lock = threading.Lock()
        self._stopped = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="slot-engine", daemon=True
        )
        self._thread.start()

    # ------------------------------------------------------------- API

    def submit(
        self,
        tokens: List[int],
        max_new: int,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 0.0,
        eos_id: int = -1,
        pad_id: int = 0,
        seed: int = 0,
        row: int = 0,
        min_new: int = 0,
        presence_penalty: float = 0.0,
        frequency_penalty: float = 0.0,
        logit_bias=None,
        on_tokens: Optional[callable] = None,
        cancel: Optional[threading.Event] = None,
        timings: Optional[dict] = None,
    ) -> Future:
        """Queue one sequence; resolves to its generated ids.

        ``row`` is the sequence's index within its request: it draws
        from ``fold_in(PRNGKey(seed), row)``. ``logit_bias``: a
        {token_id: bias} dict (generate's contract, validated here
        so a bad request fails the submit, not the pool).
        ``on_tokens`` (worker-thread callback) streams each emitted
        delta; ``cancel`` (a threading.Event the caller sets, e.g. on
        client disconnect) frees the slot at the next chunk boundary
        — the future then resolves with whatever was emitted.
        ``timings`` (tracing) is stamped at request boundaries only —
        see _Request.timings. A caller inside an active trace
        (telemetry/tracing.py) has its trace id noted here, on its own
        thread, for the admission's event in a profiler trace."""
        if max_new < 1:
            raise ValueError("max_new must be >= 1")
        if not 0 <= min_new <= max_new:
            raise ValueError("min_new must be in [0, max_new]")
        if not tokens or len(tokens) >= self.max_len:
            raise ValueError(
                f"prompt must be 1..{self.max_len - 1} tokens"
            )
        if len(tokens) + max_new > self.max_len:
            raise ValueError(
                f"prompt {len(tokens)} + max_new {max_new} exceeds "
                f"max_len {self.max_len}"
            )
        rows_idx, rows_val = normalize_logit_bias(
            self.cfg, 1, logit_bias or None, slots=BIAS_SLOTS_MAX
        )
        bias_idx, bias_val = rows_idx[0], rows_val[0]
        req = _Request(
            tokens=list(tokens), max_new=int(max_new),
            temperature=float(temperature), top_k=int(top_k),
            top_p=float(top_p), eos_id=int(eos_id), pad_id=int(pad_id),
            seed=int(seed), row=int(row), min_new=int(min_new),
            presence=float(presence_penalty),
            frequency=float(frequency_penalty),
            bias_idx=bias_idx, bias_val=bias_val,
            on_tokens=on_tokens, cancel=cancel, timings=timings,
            trace=tracing.current_trace_id(),
        )
        validate = getattr(self.program, "validate", None)
        if validate is not None:
            validate(req)  # what this decode strategy cannot serve
        if timings is not None:
            timings["enqueued"] = time.monotonic()
        req.enqueued = time.perf_counter()
        # atomic with stop()'s drain: either this put lands before the
        # drain (and gets cancelled there) or the stopped check raises
        with self._submit_lock:
            if self._stopped.is_set():
                raise RuntimeError("engine is stopped")
            self._queue.put(req)
        return req.future

    def warm_programs(self) -> Future:
        """Have the step program run what a warm-up REQUEST does not
        reach (``warm_ladder``: every read length of its decode
        programs, models/stepprog.py) on the worker thread, the one
        owner of the donated pool, at its first cycle with no slot
        occupied. Resolves when that is done; at once for a program
        that brings no such member."""
        item = _Warm()
        if getattr(self.program, "warm_ladder", None) is None:
            item.future.set_result(None)
            return item.future
        with self._submit_lock:
            if self._stopped.is_set():
                raise RuntimeError("engine is stopped")
            self._queue.put(item)
        return item.future

    def stop(self) -> None:
        with self._submit_lock:
            self._stopped.set()
        self._queue.put(None)  # wake the worker
        self._thread.join(timeout=30)
        if self.prefix_cache is not None:
            # the evicted rows still on their way to the host land
            # before anyone reads the stopped engine's books
            self.prefix_cache.flush(timeout=30)
        for slot in self._active:
            if slot is not None and not slot.req.future.done():
                slot.req.future.cancel()
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            if req is not None and not req.future.done():
                req.future.cancel()

    @property
    def stats(self) -> dict:
        counted = dict(self.phases.read_len_dispatches)
        return {
            "slots": self.slots,
            "chunk": self.chunk,
            # decode rounds fused per host dispatch (1 = the classic
            # one-dispatch-per-chunk loop)
            "window": self.window,
            "active": sum(s is not None for s in self._active),
            "queued": self._queue.qsize(),
            # the dispatches/token pair (goodput ledger + megakernel
            # yardstick): cumulative device dispatches vs tokens out
            "dispatches": self.dispatches,
            "tokens_out": self.tokens_out,
            "sampler": dict(self.sampler_rounds),
            # how far the decode programs read each row of the pool:
            # the lengths they are compiled for and the dispatches
            # that ran each (one rung, ``max_len``, for a program that
            # reads whole rows)
            "read_len": {
                "ladder": list(self.read_ladder),
                "dispatches": {
                    str(rung): counted.get(rung, 0)
                    for rung in self.read_ladder
                },
            },
        }

    def _program_stats(self, name: str) -> Optional[dict]:
        describe = getattr(self.program, name, None)
        return describe() if describe is not None else None

    def expert_stats(self) -> Optional[dict]:
        """What the step program's expert layers routed in the decode
        rounds fetched so far (``/v1/model`` ``experts``); None for a
        model without routed experts."""
        return self._program_stats("expert_stats")

    def diffusion_stats(self) -> Optional[dict]:
        """The step program's block-diffusion routine and counters
        (``/v1/model`` ``diffusion``); None for any other family."""
        return self._program_stats("diffusion_stats")

    def state_stats(self) -> Optional[dict]:
        """A row's recurrent state and the decode rounds' steps of it
        (``/v1/model`` ``state``); None for a model without any."""
        return self._program_stats("state_stats")

    def loop_stats(self) -> Optional[dict]:
        """The passes a looped model's layers run a token and the
        decode rounds' count of them (``/v1/model`` ``loop``); None
        for a model whose layers run once."""
        return self._program_stats("loop_stats")

    def hybrid_decoder_stats(self) -> Optional[dict]:
        """A decoder-hybrid-decoder's cache shapes and the decode
        rounds' counts over them (``/v1/model`` ``hybrid_decoder``);
        None for any other model."""
        return self._program_stats("hybrid_decoder_stats")

    # ----------------------------------------------------------- worker

    def _prefill(self, req: _Request):
        """The engine's prefill POLICY, shared by every step program:
        prefix-cache rewind+extend, cp-ring, chunked, or plain — and
        the cache-seeding side effect. Returns (logits, row_cache)."""
        cfg = self.cfg
        logits = row_cache = None
        pc = self.prefix_cache
        # prompts shorter than MIN_REUSE skip the prefix machinery
        # entirely: they can never be reused (plan_reuse requires a
        # MIN_REUSE match) so storing them only pins dead LRU entries
        # — this also keeps warmup's dummy request out of the cache
        # and its stats
        use_pc = pc is not None and len(req.tokens) >= PREFIX_MIN_REUSE
        if use_pc:
            from .serve_prefix import reuse_admission

            pc.readmit_seconds = 0.0
            with self.phases.span("engine.admit.reuse"):
                hit = reuse_admission(
                    pc, req.tokens, cfg, self.params,
                    chunk_len=self.prefill_chunk,
                )
            if hit is not None:
                logits, row_cache = hit
            if pc.readmit_seconds > 0.0:
                # time spent readmitting a spilled base from host RAM
                # (device_put roundtrip) — surfaces as the trace's
                # ``kv`` stage and the ledger's ``kv_readmit``, both
                # carved out of the prefill window
                if req.timings is not None:
                    req.timings["kv"] = pc.readmit_seconds
                if self.ledger is not None:
                    self.ledger.carve("kv_readmit", pc.readmit_seconds)
        if row_cache is None:
            with self.phases.span("engine.admit.prefill"):
                logits, row_cache = self._cold_prefill(req)
        if use_pc:
            # store the completed prompt's cache for future turns
            # (standalone buffer — see the __init__ soundness note);
            # a row this evicts is only handed to the spill tier here,
            # its copy to the host runs on the tier's own thread
            with self.phases.span("engine.admit.store"):
                pc.store(tuple(req.tokens), row_cache)
        return logits, row_cache

    def _cold_prefill(self, req: _Request):
        """The prefill dispatch for a prompt with no reusable prefix:
        cp-ring, chunked, or plain (``engine.admit.prefill``)."""
        cfg = self.cfg
        if (
            self.prefill_floor_s > 0.0
            and len(req.tokens) >= PREFIX_MIN_REUSE
        ):
            # the synthetic floor: pay it on the worker thread —
            # exactly where real prefill compute would run — then
            # carve the seconds out of the ledger's prefill stage
            # so productive_fraction keeps measuring real device
            # work. The trace's prefill span (admitted ->
            # prefill_done) still carries the hit, so
            # dominant-stage attribution names it. Warmup's
            # short dummy prompt stays under the reuse floor and
            # skips this.
            time.sleep(self.prefill_floor_s)  # cpcheck: disable=CP-HOTREACH the synthetic floor IS the work; see comment above
            if self.ledger is not None:
                self.ledger.carve("idle", self.prefill_floor_s)
        if (
            self.cp_mesh is not None
            and len(req.tokens) >= self.cp_min_len
        ):
            import numpy as _np

            from ..parallel.context import cp_prefill_with_remainder

            logits, row_cache = cp_prefill_with_remainder(
                self.params,
                _np.asarray([req.tokens], _np.int32),
                cfg, self.cp_mesh, self.max_len,
                prefill_chunk=self.prefill_chunk,
            )
        elif (
            self.prefill_chunk > 0
            and len(req.tokens) > self.prefill_chunk
        ):
            from ..models.decode import chunked_prefill

            logits, row_cache = chunked_prefill(
                self.params, jnp.asarray([req.tokens], jnp.int32),
                cfg, self.max_len, chunk_len=self.prefill_chunk,
            )
        else:
            # the prompt rides with the call as a numpy row: one
            # transfer, on the path that uses it, no program of its own
            prompt = np.asarray([req.tokens], np.int32)  # cpcheck: disable=CP-HOTREACH a list of Python ints: nothing is fetched
            logits, row_cache = _jitted_prefill(
                cfg, self.max_len
            )(self.params, prompt)
        return logits, row_cache

    def _admit(self, slot_id: int, req: _Request, now: float) -> None:
        """Prefill the prompt (engine policy) and hand the result to
        the step program, which samples token 0 with generate's exact
        key schedule and writes the row and the whole admission row of
        its device-resident state in one dispatch. ``now`` is the
        worker's perf_counter read at the admission's start (the
        ``engine.admit`` boundary)."""
        if req.timings is not None:
            req.timings["admitted"] = time.monotonic()
        if self.ledger is not None:
            self.ledger.enter("prefill")
        phases = self.phases
        phases.admissions += 1
        phases.queue_wait_s += max(now - req.enqueued, 0.0)
        logits, row_cache = self._prefill(req)
        with phases.span("engine.admit.first_token"):
            first_host = self.program.admit(
                slot_id, req, logits, row_cache
            )
        # a program whose prefill yields no token (block diffusion)
        # returns None: the row's first tokens come from its dispatches
        first = [] if first_host is None else [first_host]
        state = _Slot(req=req, emitted=first)
        if first and (first_host == req.eos_id or req.max_new <= 1):
            state.finished = True
        self._active[slot_id] = state
        # an admission is two device programs (the prefill, and the
        # step program's one for the first sample, the row's insert
        # and the state's write: ``admit_device_programs_per_admission``
        # counts a harvested row's ``retire`` with them), with ONE
        # sync after them; it counts as ONE toward dispatches/token so
        # the series tracks the steady-state decode shape the
        # megakernel work targets
        self.dispatches += 1
        self.tokens_out += len(first)
        if req.timings is not None:
            # prefill stage ends here: prompt prefilled, token 0
            # sampled, row inserted — everything after is decode
            req.timings["prefill_done"] = time.monotonic()
        if self.ledger is not None:
            self.ledger.enter("decode")
        if first:
            self._notify(req, first)

    def _warm(self, item: _Warm) -> bool:
        """Run the program's warm-up if the pool is idle (True), else
        put the item back behind what is queued (False): a chunk
        program of a short rung would step live rows past its read."""
        if any(s is not None for s in self._active):
            self._queue.put(item)
            return False
        try:
            self.program.warm_ladder()
        except Exception as exc:  # noqa: BLE001
            # the failed dispatch donated the pool
            self.program.reset()
            item.future.set_exception(exc)
        else:
            item.future.set_result(None)
        return True

    def _harvest(self, slot_id: int) -> None:
        state = self._active[slot_id]
        req = state.req
        out = state.emitted[: req.max_new]
        if req.eos_id >= 0 and req.eos_id in out:
            # keep the eos, pad-trim what follows (generate's contract
            # after its own trim step)
            out = out[: out.index(req.eos_id) + 1]
        if req.timings is not None:
            req.timings["done"] = time.monotonic()
            req.timings["rounds"] = state.rounds
        self._active[slot_id] = None
        self.program.retire(slot_id)
        if not req.future.done():
            req.future.set_result(out)

    @staticmethod
    def _notify(req: _Request, delta: List[int]) -> None:
        """Deliver a streamed delta; a raising callback (e.g. the
        consumer's event loop already closed in a shutdown race) must
        never escape into _run — it would kill the worker thread and
        strand every in-flight future while /health stays 200."""
        if req.on_tokens is None:
            return
        try:
            req.on_tokens(list(delta))
        except Exception:  # noqa: BLE001
            log.exception("on_tokens callback failed; dropping delta")

    def _sweep_cancelled(self) -> None:
        """Free slots whose requests were cancelled (client gone):
        the slot returns to the pool at this window boundary — within
        ONE window of the disconnect, by the host-re-entry rule — and
        the future resolves with the partial emission (nobody is
        usually waiting — the disconnect is why we're here). The
        ``done`` stamp lands here, at the abandon instant, so decode
        is accounted up to it and no further (the tracing
        contract)."""
        for i, s in enumerate(self._active):
            if (
                s is not None
                and s.req.cancel is not None
                and s.req.cancel.is_set()
            ):
                if s.req.timings is not None:
                    s.req.timings["done"] = time.monotonic()
                    s.req.timings["rounds"] = s.rounds
                self._active[i] = None
                self.program.retire(i)
                if not s.req.future.done():
                    s.req.future.set_result(list(s.emitted))
                log.info(
                    "slot %d freed mid-generation (%d/%d tokens): "
                    "request cancelled", i, len(s.emitted), s.req.max_new,
                )

    def _fail_and_rebuild(self, exc: Exception) -> None:
        """Fail every in-flight request loudly, once, and rebuild the
        device buffers: the failed dispatch DONATED the pool and
        state, so every later admission would die on a deleted array
        while /health stays 200."""
        log.exception("slot dispatch failed")
        for i, s in enumerate(self._active):
            if s is not None and not s.req.future.done():
                s.req.future.set_exception(exc)
            self._active[i] = None
        self.program.reset()

    def _cancel_pending(self) -> bool:
        return any(
            s is not None
            and s.req.cancel is not None
            and s.req.cancel.is_set()
            for s in self._active
        )

    def _budgets(self) -> np.ndarray:
        """Per-slot remaining max_new allowance, the fused window's
        early-exit gate (models/slots.py: it never masks emission, so
        a stale-by-one-window value stays correct — budgets only
        shrink, and excess tokens are append-discarded exactly like
        the sequential engine's)."""
        budgets = np.zeros((self.slots,), np.int32)
        for i, s in enumerate(self._active):
            if s is not None:
                budgets[i] = max(s.req.max_new - len(s.emitted), 0)
        return budgets

    def _sampler_arm(self) -> int:
        """The sampler's arm the live slots' knobs call for (the
        largest of their ``row_arm``s), for ``sampler_rounds``."""
        return max(
            (
                row_arm(s.req.temperature, s.req.top_k, s.req.top_p)
                for s in self._active if s is not None
            ),
            default=0,
        )

    def _run(self) -> None:
        # the profiler names this thread's line by its OS name, taken
        # at the thread's first event: before any jax call
        name_os_thread("slot-engine")
        try:
            self._cycles()
        finally:
            self.phases.close(time.perf_counter())

    # cpcheck: hotpath — the continuous-batching decode loop; a steady
    # window must ship zero host syncs beyond the program's one fetch.
    # Every cycle is partitioned into the phases of telemetry/goodput.py
    # ENGINE_CYCLE_PHASES; a boundary is one of the loop's own
    # perf_counter reads (t0, tj) handed to ``phases``, so the phases
    # tile the thread's wall time
    def _cycles(self) -> None:
        # one-window lookahead: the step-program handle of a window
        # already dispatched for the NEXT cycle (None = serial)
        pending = None
        program = self.program
        phases = self.phases
        windowed = self.window > 1
        while not self._stopped.is_set():
            t0 = time.perf_counter()
            admitted = False
            if pending is None:
                self._sweep_cancelled()
                free = [
                    i for i, s in enumerate(self._active) if s is None
                ]
                any_active = any(
                    s is not None for s in self._active
                )
                if not any_active and self.ledger is not None:
                    # fully idle: the ledger flips to ``idle`` only
                    # out of prefill/decode (engine_idle), so this
                    # can't cut the server's boot/warmup stages short
                    self.ledger.engine_idle()
                # block for work only when fully idle; otherwise drain
                # whatever is queued into free slots and keep decoding
                try:
                    block = not any_active
                    while free:
                        if block:
                            phases.switch("engine.wait_work", t0)
                        req = self._queue.get(block=block, timeout=None)
                        if req is None:  # stop sentinel
                            return
                        if isinstance(req, _Warm):
                            if not self._warm(req):
                                break  # occupied: decode on, then again
                            t0 = time.perf_counter()
                            continue
                        block = False
                        t0 = time.perf_counter()  # exclude idle wait
                        # the span names its cause and its size: the
                        # request (by its trace, where it came with
                        # one), its prompt, the slot it takes
                        cause = {"trace": req.trace} if req.trace else {}
                        phases.switch(
                            "engine.admit", t0, prompt=len(req.tokens),
                            slot=free[0], **cause,
                        )
                        admitted = True
                        if (
                            req.cancel is not None
                            and req.cancel.is_set()
                        ):
                            req.future.cancel()  # left before admission
                            continue
                        try:
                            self._admit(free.pop(0), req, t0)
                        except Exception as exc:  # noqa: BLE001
                            if not req.future.done():
                                req.future.set_exception(exc)
                except queue.Empty:
                    pass
                # harvest admissions that finished at token 0
                for i, s in enumerate(self._active):
                    if s is not None and s.finished:
                        self._harvest(i)
                live = sum(s is not None for s in self._active)
                if not live:
                    continue
                # fuse K rounds only when no host decision can be
                # pending: an admission just landed (more queued
                # work likely) or a non-empty queue (a waiting
                # request must grab the next freed slot at chunk
                # granularity) keeps the single-chunk program — the
                # host re-enters exactly when it has something to do
                fused = (
                    not admitted
                    and self._queue.empty()
                    and not self._cancel_pending()
                )
                tj = time.perf_counter()
                phases.dispatched(
                    tj, fused and windowed, live, self._read_len(fused)
                )
                arm = self._sampler_arm()
                try:
                    handle = program.dispatch(self._budgets(), fused)
                except Exception as exc:  # noqa: BLE001
                    self._fail_and_rebuild(exc)
                    continue
                self.dispatches += program.dispatch_cost
            else:
                (handle, arm), pending = pending, None
            # one-WINDOW lookahead (the PR 1 one-round lookahead,
            # window-sized): when no admission, cancel, or stop
            # decision is pending, dispatch window N+1 BEFORE
            # fetching window N's tokens — device dataflow orders the
            # donated pool/state, so the token fetch, host
            # bookkeeping, and streaming callbacks below overlap
            # window N+1's device compute instead of serializing
            # with it. Whenever a decision IS needed the serial path
            # runs and the decision lands at the very next window
            # boundary. Budgets are stale by one window here — an
            # upper bound, see _budgets. Programs whose next dispatch
            # depends on this window's tokens (speculative
            # acceptance) opt out via supports_lookahead.
            live = sum(s is not None for s in self._active)
            if (
                program.supports_lookahead
                and live
                and self._queue.empty()
                and not self._cancel_pending()
            ):
                tj = time.perf_counter()
                phases.dispatched(
                    tj, windowed, live, self._read_len(True)
                )
                try:
                    pending = (
                        program.dispatch(self._budgets(), True),
                        self._sampler_arm(),
                    )
                except Exception as exc:  # noqa: BLE001
                    self._fail_and_rebuild(exc)
                    pending = None
                    continue
                self.dispatches += program.dispatch_cost
            tj = time.perf_counter()
            phases.switch("engine.fetch", tj)
            try:
                # the ONE deliberate sync per window lives inside
                # program.tokens; everything after it overlaps the
                # lookahead window's device compute
                toks_host, valid, rounds_run = program.tokens(handle)
            except Exception as exc:  # noqa: BLE001 — fail loud, once
                self._fail_and_rebuild(exc)
                pending = None
                continue
            self.sampler_rounds[SAMPLER_ROUNDS[arm]] += rounds_run
            tj = time.perf_counter()
            # append, notify, harvest (and the next cycle's sweep and
            # free-slot scan, up to its first boundary)
            phases.switch("engine.deliver", tj)
            for i, state in enumerate(self._active):
                if state is None:
                    continue
                # per-window tracing cost is ONE int bump per live
                # slot; the stamps themselves land only at admission/
                # harvest boundaries (batched per request, never per
                # token)
                state.rounds += rounds_run
                req = state.req
                before = len(state.emitted)
                ended = append_chunk(
                    state.emitted, toks_host[i][: valid[i]],
                    req.max_new, req.eos_id,
                )
                if len(state.emitted) > before:
                    self.tokens_out += len(state.emitted) - before
                    self._notify(req, state.emitted[before:])
                if ended:
                    self._harvest(i)
