"""The step-program interface: one slot-engine driver for every
decode strategy.

The continuous-batching engine (workload/serve_slots.py) used to call
``decode_slots_chunk`` directly, which welded it to the plain
transformer; speculative decoding lived on a legacy one-shot path and
quantized weights composed only by accident. A **step program** is the
seam: it owns the device-resident decode state for a fixed pool of S
slots and exposes five verbs with STATIC shapes per
``(config, S, chunk, K)`` — one compiled program set, no recompiles
as traffic changes:

- ``admit(slot, req, logits, row_cache)`` — write one prefilled
  request into ``slot`` and return its first sampled token (a host
  int), or None where the prefill's logits give no token (block
  diffusion: a row's first tokens come with its first whole block).
  The ENGINE computes the prefill and passes the result in:
  prefix-cache rewind+extend, cp-ring, chunked and plain prefill stay
  engine policy, shared identically by every program.
- ``dispatch(budgets, fused)`` — advance every live slot by up to
  ``rounds * chunk`` tokens (``fused=True``; one ``chunk`` otherwise)
  in ONE logical step, returning an opaque handle. Never syncs the
  host; ``dispatch_cost`` is the number of device dispatches one call
  ships (1 for the fused/plain programs, 2 for draft+verify).
  ``budgets`` is a [S] int array of remaining max_new allowances —
  the early-exit gate, never an emission mask.
- ``tokens(handle)`` — the round trip: fetch the handle's tokens (the
  one deliberate host sync per window) and return
  ``(toks [S, W], valid [S], rounds_run)`` where ``valid[i]`` bounds
  the tokens slot i actually produced, anything from 0 to W: a step
  need not yield one token per row (a speculative round yields up to
  k + 1, a block-diffusion forward a whole block or nothing), and W
  is whatever this window's fullest row produced (the engine appends
  ``toks[i, :valid[i]]`` through the shared ``append_chunk``
  convention, so eos/max_new capping stays in one place).
- ``retire(slot)`` — free one row (harvest or cancel); pads follow
  until re-admission.
- ``reset()`` — rebuild the device buffers after a failed dispatch
  (the failure died holding the donated pool/state).

``supports_lookahead`` says whether the engine may dispatch window
N+1 before fetching window N (true when the next dispatch does not
depend on host-side decisions about N's tokens — the plain programs;
false for draft/verify, whose next round needs the acceptance
result).

The protocol is a contract over (configuration, step function, cache
tree): nothing here reads a model's widths. The configuration's family
supplies the cache and the step (models/slots.py); a family whose step
counts something per round (the routed experts of models/mla_moe.py)
returns the counts with the tokens, and ``tokens`` adds them up on the
host (``expert_stats``; ``state_stats`` for the stepped rows of
models/hybrid_ssm.py's recurrent state; ``loop_stats`` for the passes
models/looped.py ran over its rows; ``hybrid_decoder_stats`` for the
four cache shapes of models/decoder_hybrid.py): no dispatch and no
sync of their own.

Implementations: :class:`PlainStepProgram` (models/slots.py's chunk +
fused-window programs), ``models.quantized.QuantizedStepProgram``
(the same programs over int8 weights — the forward dequantizes per
layer, so composition is structural) and
``models.speculative.SpeculativeStepProgram`` (draft/verify rounds:
multi-token emission per dispatch) and
``models.block_diffusion.BlockStepProgram`` (a pool forward reveals
several tokens of a row's block, or none; blocks are handed over
whole). ``make_step_program`` picks the right default for a params
pytree. A program may also bring ``validate(req)`` (refuse what it
cannot serve, at submit), ``warm_new`` (the new tokens a warm-up
request needs to reach the fused window), ``ladder`` with
``read_len(fused)`` and ``warm_ladder()`` (the read lengths its decode
programs are compiled for, the one the next dispatch will run, and a
run of each on the idle pool: :class:`PlainStepProgram`; a program
without them reads whole rows) and ``attach_phases(phases)``:
the engine hands it its ``EnginePhases`` (telemetry/goodput.py), and
``admit`` then opens the children of ``engine.admit.first_token``
where the work happens (``.sample``, ``.insert``, ``.state``,
``.sync``: which of an admission's milliseconds are the host's own
and which wait for the device). A program that was handed none, or
brings no such member, records nothing and admits as before.
"""
from __future__ import annotations

import contextlib

import jax
import numpy as np

from .slots import (
    admit_row,
    compile_decode_programs,
    decode_slots_chunk,
    decode_slots_window,
    init_slot_state,
    pack_admission,
    read_ladder,
    retire_slot,
    slot_cache,
)
from .transformer import Params, TransformerConfig


def phase_span(phases):
    """``phases.span`` (``with span(name):`` adds a child phase's
    seconds and count and annotates the trace), or a stand-in that
    records nothing for a program no engine handed its phases to."""
    return contextlib.nullcontext if phases is None else phases.span


class PlainStepProgram:
    """The plain transformer's step program: the slot pool + the
    device-resident sampling state, advanced by decode_slots_chunk
    (``fused=False``) or the K-round fused window
    (``decode_slots_window``, ``fused=True``) — one device dispatch
    either way. ``out_sharding`` pins output placement (the pod's
    mirror passes fully-replicated).

    Every dispatch reads the pool's rows only as far as the longest
    LIVE row will reach in it: ``ladder`` (models/slots.py
    ``read_ladder``) holds the read lengths the two programs are
    compiled for, and ``read_len`` picks the shortest that is enough
    from ``_reach``, one host integer a slot: an upper bound of the
    row's device ``pos``, set at ``admit`` to the prefilled prompt's
    length, advanced at every ``dispatch`` by the steps it MAY run,
    cleared at ``retire``. A fused window that exits early leaves the
    bound too large, never too small (every row that was live in such
    a window has ended, and the engine retires it at that window's
    fetch); a retired slot decodes pads on and its device ``pos`` runs
    away, but its output is discarded, so it does not count. No device
    read, no sync and no operand of its own."""

    supports_lookahead = True
    dispatch_cost = 1
    #: the engine's EnginePhases once ``attach_phases`` was called
    phases = None

    def __init__(
        self,
        cfg: TransformerConfig,
        params: Params,
        max_len: int,
        slots: int,
        chunk: int,
        rounds: int = 1,
        out_sharding=None,
    ) -> None:
        if slots < 1 or chunk < 1 or rounds < 1:
            raise ValueError("slots, chunk and rounds must be >= 1")
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.slots = slots
        self.chunk = chunk
        self.rounds = rounds
        self.out_sharding = out_sharding
        #: the pool's ``stats`` leaf summed over every fetched round
        #: (None until a round brings one)
        self.stats_total = None
        #: the read lengths the decode programs are compiled for,
        #: shortest first, the last ``max_len`` (one rung: whole rows)
        self.ladder = read_ladder(cfg, max_len)
        self.reset()

    def reset(self) -> None:
        self._pool = slot_cache(self.cfg, self.slots, self.max_len)
        self._state = init_slot_state(self.cfg, self.slots)
        # per slot, an upper bound of the row's ``pos`` on the device
        # (0: a free slot, whose row counts for nothing)
        self._reach = [0] * self.slots

    def attach_phases(self, phases) -> None:
        self.phases = phases

    def admit(self, slot: int, req, logits, row_cache) -> int:
        """Admit the prefilled request in ONE dispatch
        (models/slots.py ``admit_row``): every number of the request
        crosses as one packed host row, and the device derives the row
        key (row ``req.row`` of ``req.seed``, the server key
        convention), samples token 0, writes the row into the pool and
        the whole sampling state row, ``done`` included. Returns the
        first token. The four children of
        ``engine.admit.first_token`` tile it: ``sample`` packs the
        row (numpy alone), ``insert`` issues the program, ``state``
        is the host's own bookkeeping, and in ``sync`` the thread is
        blocked on the device (the first token's fetch waits out the
        prefill and the program)."""
        span = phase_span(self.phases)
        with span("engine.admit.first_token.sample"):
            packed = pack_admission(
                slot=slot, seed=req.seed, row=req.row,
                top_k=req.top_k, eos_id=req.eos_id, pad_id=req.pad_id,
                min_new=req.min_new, max_new=req.max_new,
                temperature=req.temperature, top_p=req.top_p,
                presence=req.presence, frequency=req.frequency,
                bias_idx=req.bias_idx, bias_val=req.bias_val,
            )
        with span("engine.admit.first_token.insert"):
            self._pool, self._state, first = admit_row(
                self._pool, self._state, logits, row_cache, packed,
                self.cfg, self.out_sharding,
            )
        with span("engine.admit.first_token.state"):
            # the prefilled row stands at the end of its whole prompt,
            # whatever part of it a reused prefix supplied
            self._reach[slot] = len(req.tokens)
        with span("engine.admit.first_token.sync"):
            return int(jax.device_get(first))

    def retire(self, slot: int) -> None:
        self._state = retire_slot(
            self._state, slot, self.out_sharding
        )
        self._reach[slot] = 0

    def _steps(self, fused: bool) -> int:
        """The steps a dispatch may run at most."""
        return self.chunk * (self.rounds if fused else 1)

    def read_len(self, fused: bool) -> int:
        """The rung a dispatch issued NOW would read: the shortest
        that the longest occupied row does not pass in the dispatch's
        own steps, ``max_len`` where none is enough (a row decoding
        past its budget behind the engine's lookahead: the leaf takes
        no write past its end)."""
        need = max(self._reach) + self._steps(fused)
        for rung in self.ladder:
            if rung >= need:
                return rung
        return self.max_len

    def warm_ladder(self) -> None:
        """Compile every rung's chunk program and fused-window program
        side by side (``compile_decode_programs``), then run each once
        on the IDLE pool (every slot retired: the chunk program steps
        dead rows, the window program exits at once), so that no rung
        compiles, or loads from the compile cache, under traffic: a
        warm-up request reaches the first rung only."""
        if any(self._reach):
            raise RuntimeError("warm_ladder needs an idle pool")
        compile_decode_programs(
            self.params, self._pool, self._state, self.cfg, self.chunk,
            self.rounds, [self._read_len(rung) for rung in self.ladder],
            self.out_sharding,
        )
        no_budget = np.zeros((self.slots,), np.int32)
        for rung in self.ladder:
            jax.device_get(self._run(no_budget, False, rung)[0])
            if self.rounds > 1:
                jax.device_get(self._run(no_budget, True, rung)[0])

    def _read_len(self, rung: int):
        """A rung as the programs' static ``read_len``: the last rung
        is the whole row, today's program to the letter (None)."""
        return None if rung == self.max_len else rung

    # cpcheck: hotpath — the fused window dispatch: one device call,
    # zero host syncs (the budgets upload is async and per-window)
    def dispatch(self, budgets, fused: bool):
        fused = fused and self.rounds > 1
        rung = self.read_len(fused)
        steps = self._steps(fused)
        self._reach = [
            reach + steps if reach else 0 for reach in self._reach
        ]
        return self._run(budgets, fused, rung)

    def _run(self, budgets, fused: bool, rung: int):
        """One dispatch of the window (``fused``) or chunk program
        that reads ``rung`` positions a row."""
        read_len = self._read_len(rung)
        if fused:
            (self._pool, self._state, toks, run,
             stats) = decode_slots_window(
                self.params, self._pool, self._state, self.cfg,
                self.chunk, self.rounds, budgets, self.out_sharding,
                with_stats=True, read_len=read_len,
            )
            return toks, run, stats
        self._pool, self._state, toks, stats = decode_slots_chunk(
            self.params, self._pool, self._state, self.cfg,
            self.chunk, self.out_sharding, with_stats=True,
            read_len=read_len,
        )
        return toks, None, stats

    # cpcheck: hotpath — the one deliberate sync per window
    def tokens(self, handle):
        toks, run, stats = handle
        if run is None:
            toks_host, stats = jax.device_get((toks, stats))  # cpcheck: disable=CP-HOTSYNC the per-window token fetch
            rounds_run = 1
        else:
            toks_host, run_host, stats = jax.device_get((toks, run, stats))  # cpcheck: disable=CP-HOTSYNC the per-window token fetch
            rounds_run = int(run_host)
            toks_host = toks_host[:, : rounds_run * self.chunk]
        if stats is not None:
            # already on the host: fetched with the tokens above
            stats = stats.astype(np.int64)
            self.stats_total = stats if self.stats_total is None \
                else self.stats_total + stats
        valid = np.full(
            (self.slots,), rounds_run * self.chunk, np.int64
        )
        return toks_host, valid, rounds_run

    def _described(self, describe: str):
        """The family's ``describe`` of the summed ``stats``; None for
        a family without it."""
        describe = getattr(
            getattr(self.cfg, "family", None), describe, None)
        if describe is None:
            return None
        return describe(self.cfg, self.stats_total)

    def expert_stats(self):
        """What the expert layers of the decode rounds fetched so far
        routed, under ``/v1/model`` ``experts``'s names; None for a
        family without routed experts."""
        return self._described("describe_stats")

    def state_stats(self):
        """What a row keeps besides keys and values and how often the
        decode rounds fetched so far stepped it (``/v1/model``
        ``state``); None for a family without recurrent state."""
        return self._described("describe_state")

    def loop_stats(self):
        """How often the layers run a token, the planes of keys and
        values that costs and the passes the decode rounds fetched so
        far ran (``/v1/model`` ``loop``); None for a family whose
        layers run once."""
        return self._described("describe_loop")

    def hybrid_decoder_stats(self):
        """The four cache shapes of a decoder-hybrid-decoder's row and
        what the decode rounds fetched so far stepped, wrapped and
        read of them (``/v1/model`` ``hybrid_decoder``); None for any
        other family."""
        return self._described("describe_hybrid_decoder")


def make_step_program(
    cfg: TransformerConfig,
    params: Params,
    max_len: int,
    slots: int,
    chunk: int,
    rounds: int = 1,
    out_sharding=None,
):
    """The default step program for a params pytree: quantized params
    get the quantized program (same device programs, the composition
    made explicit and validated), everything else the plain one. A
    family whose step is not one token per row brings its own
    (``cfg.family.make_step_program``: models/block_diffusion.py)."""
    from .quantized import QuantizedStepProgram, is_quantized

    own = getattr(getattr(cfg, "family", None), "make_step_program", None)
    if own is not None:
        return own(cfg, params, max_len, slots, chunk, rounds=rounds,
                   out_sharding=out_sharding)

    kind = (
        QuantizedStepProgram if is_quantized(params)
        else PlainStepProgram
    )
    return kind(
        cfg, params, max_len, slots, chunk,
        rounds=rounds, out_sharding=out_sharding,
    )
