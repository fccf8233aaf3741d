"""Slot-based continuous decode: the static-shape TPU analog of
in-flight batching.

``generate`` batches rows that start together; a serving system wants
rows that start WHENEVER — a new request should join the decode loop
at the next chunk boundary instead of queueing behind the current
batch's full generation. The XLA-friendly shape for that is a fixed
pool of S slots: the pool is ONE cache of S rows whose ``pos`` is one
number per row, the decode step is one ``decode_chunk`` over it
(tokens [S, 1]: the matmuls are batched, weights stream from HBM once
per step for all slots), and admission/harvest happen between
fixed-size chunks on the host. All shapes are static: one compiled
chunk program per (config, S, chunk), plus one fused-window program
per (config, S, chunk, K) that loops K chunk-rounds on device with
early exit (``decode_slots_window``) so the host pays one dispatch
per K rounds — no recompiles as traffic changes.

The pool's form (``slot_cache``) is private to this module and
``decode_chunk``: ``init_cache``'s leaves, one per LAYER instead of
stacked over layers (k/v: a list of [S, length, kv_heads, head_dim];
with ``kv_int8`` the scales beside them), and ``pos`` [S]. A step
writes each row's new keys and values into the layer's leaf at the
row's own position and attention reads the leaf where it lies; the
programs donate the pool, so nothing the size of a layer's cache is
copied, sliced out or stacked back in a step (tests/
test_tpu_compile.py pins that on the program the v5e's compiler
makes; PERF.md, PR 28, has what the stacked form cost). Rows keep
``prefill``'s format (stacked, one row): ``insert_row`` writes each
layer's part at the slot, and rows never leave the pool.

An admission is ONE program over ONE packed host row (``admit_row``,
``pack_admission``): the row key, the first sample, the row's write
and the slot's whole sampling state happen in a single dispatch, and
every per-request number crosses to the device in one int32 row built
with numpy, so the host issues nothing else and need not see the
first token before the state is written. ``first_sample``,
``insert_row`` and ``admit_slot_state`` are the same three steps as
programs of their own (the pod's mirror engine and the tests issue
them one by one); each step has ONE traceable body that both forms
trace.

Every step reads every row of the pool, dead or live, so what a
step costs is what attention does with those bytes: ``decode_chunk``
contracts the query heads, grouped as [kv_heads, group], with each
layer's keys and values as the pool stores them (bf16, kv heads only,
float32 accumulation). Nothing the size of the pool is repeated to
n_heads or written out in float32 on the way: tests/test_decode_gqa.py
pins that on the chunk program's lowered text, tests/
test_tpu_compile.py on the program the v5e's compiler makes of it.
How FAR a step reads each row is the programs' static ``read_len``,
one of a short ladder of lengths (``read_ladder``): the step program
picks, at every dispatch, the shortest rung that the longest live row
will not pass, from a count it keeps on the host (models/stepprog.py),
so a pool of 4,096-position rows whose live rows stand under 1,024
reads a quarter of its bytes. Writes go to the whole leaf whatever the
rung. A family's pool and a ring have the one rung ``max_len``.

Sampling reproduces ``generate``'s schedule exactly: per-row key =
``jax.random.split(PRNGKey(seed), 1)[0]``, sample i uses
``fold_in(row_key, i)`` with sample 0 drawn from the prefill logits —
so a request's output is byte-identical to a solo ``generate`` call
no matter what it shared the pool with (tested).

Dead slots (finished rows not yet reused) keep decoding garbage —
static shapes — but their writes are harmless: a linear cache's row
that has run past its end writes NOTHING (its scatter index is out of
range and dropped, not clamped onto the last position), a
sliding-window config's ring cache (decode.py) wraps within its own
row, and either way the row is wholesale overwritten by the next
admission (``insert_row`` replaces the full row INCLUDING its
position, so a reused slot holds nothing of its previous occupant —
what makes windows compose with the pool). Emitted tokens are masked
to pad after eos, same as ``generate``.

The programs here are a contract over (configuration, step function,
cache tree), not over one model: the step is ``decode_chunk`` over the
whole pool for every configuration. A configuration of another family
(``cfg.family``: models/mla_moe.py) brings its own ``slot_cache`` and
``insert_row`` for what differs in kind (its latents' leaves, its
counters). A pool may carry a ``stats`` leaf (what the step function
counted, e.g. routed experts): the chunk and window programs zero it
on entry and return its value as a fourth output, beside the tokens
and fetched with them; a pool without one returns ``None`` there, and
its compiled program is what it was.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .decode import (
    BIAS_SLOTS_MAX,
    Cache,
    apply_logit_bias,
    apply_token_penalties,
    count_token,
    decode_chunk,
    init_cache,
    mask_eos_before_min,
    sample_logits,
    seed_counts_row,
)
from .transformer import Params, TransformerConfig

# The device-resident per-slot sampling state both serving engines
# carry between chunk rounds (one dict = one donated jit operand):
# everything the chunk program reads besides params and the KV pool.
# It changes ONLY at admission (one row) and retirement (one done
# flag), so keeping it on device removes the ~12 host->device uploads
# the old loop paid per round AND the whole class of zero-copied-
# numpy-mutated-in-place hazards (there is no host buffer left to
# mutate). step_idx advances on device inside the chunk program for
# the same reason.
SLOT_STATE_KEYS = (
    "last", "keys", "step_idx", "temperature", "top_k", "top_p",
    "eos_id", "pad_id", "min_new", "presence", "frequency",
    "bias_idx", "bias_val", "counts", "done",
)


def append_chunk(emitted, toks, max_new: int, eos_id: int) -> bool:
    """The ONE chunk-append convention shared by the slot engine and
    the pod's streamed decode (their outputs are documented as
    byte-identical to generate, so the rules must live in one place):
    append ``toks`` into ``emitted`` capped at ``max_new``, stopping
    at eos inclusive. Returns whether the row ended."""
    for t in toks:
        if len(emitted) >= max_new:
            break
        emitted.append(int(t))
        if int(t) == eos_id:
            break
    return (
        len(emitted) >= max_new
        or (eos_id >= 0 and eos_id in emitted)
    )


def init_slot_state(cfg: TransformerConfig, slots: int) -> dict:
    """Fresh device-resident per-slot sampling state (all slots empty,
    hence done). See SLOT_STATE_KEYS for the contract."""
    return {
        "last": jnp.zeros((slots,), jnp.int32),
        "keys": jnp.zeros((slots, 2), jnp.uint32),
        "step_idx": jnp.zeros((slots,), jnp.int32),
        "temperature": jnp.zeros((slots,), jnp.float32),
        "top_k": jnp.zeros((slots,), jnp.int32),
        "top_p": jnp.zeros((slots,), jnp.float32),
        "eos_id": jnp.full((slots,), -1, jnp.int32),
        "pad_id": jnp.zeros((slots,), jnp.int32),
        "min_new": jnp.zeros((slots,), jnp.int32),
        "presence": jnp.zeros((slots,), jnp.float32),
        "frequency": jnp.zeros((slots,), jnp.float32),
        "bias_idx": jnp.full((slots, BIAS_SLOTS_MAX), -1, jnp.int32),
        "bias_val": jnp.zeros((slots, BIAS_SLOTS_MAX), jnp.float32),
        "counts": jnp.zeros((slots, cfg.vocab_size), jnp.float32),
        "done": jnp.ones((slots,), jnp.bool_),
    }


def _write_state_row(state, slot, last, key, step_idx, temperature,
                     top_k, top_p, eos_id, pad_id, min_new, presence,
                     frequency, bias_idx, bias_val, done):
    """One admission's row written into every leaf of the state dict
    (single-row .at[slot].set per leaf: with the dict donated, no
    full-array copies). The counts row seeds on device
    (seed_counts_row) from the first sample. The ONE body of the
    state's write: ``admit_slot_state``'s program and ``admit_row``'s
    both trace it."""
    vocab = state["counts"].shape[1]
    row = {
        "last": last, "keys": key, "step_idx": step_idx,
        "temperature": temperature, "top_k": top_k,
        "top_p": top_p, "eos_id": eos_id, "pad_id": pad_id,
        "min_new": min_new, "presence": presence,
        "frequency": frequency, "bias_idx": bias_idx,
        "bias_val": bias_val,
        "counts": seed_counts_row(vocab, last, eos_id),
        "done": done,
    }
    return {
        name: state[name].at[slot].set(
            row[name].astype(state[name].dtype)
        )
        for name in state
    }


@functools.lru_cache(maxsize=8)
def _jitted_admit(cfg: TransformerConfig, out_sharding=None):
    """ONE dispatch writing a whole admission's row into every state
    leaf (``_write_state_row``; the state dict is donated), so
    admission needs no extra host round trip for the counts.
    ``out_sharding`` pins the output placement exactly like
    _jitted_insert's."""
    return jax.jit(
        _write_state_row, donate_argnums=(0,),
        out_shardings=out_sharding,
    )


def admit_slot_state(
    state: dict, slot: int, cfg: TransformerConfig, *,
    last, key, temperature, top_k, top_p, eos_id, pad_id,
    min_new, presence, frequency, bias_idx, bias_val, done,
    step_idx: int = 1, out_sharding=None,
) -> dict:
    """Write one admitted request's sampling knobs into ``slot``
    across the (donated) state dict in a single dispatch. ``last`` is
    the first sampled token (device scalar or int); the slot's counts
    row seeds from it on device."""
    return _jitted_admit(cfg, out_sharding)(
        state, jnp.asarray(slot, jnp.int32),
        jnp.asarray(last, jnp.int32),
        jnp.asarray(key, jnp.uint32),
        jnp.asarray(step_idx, jnp.int32),
        jnp.asarray(temperature, jnp.float32),
        jnp.asarray(top_k, jnp.int32),
        jnp.asarray(top_p, jnp.float32),
        jnp.asarray(eos_id, jnp.int32),
        jnp.asarray(pad_id, jnp.int32),
        jnp.asarray(min_new, jnp.int32),
        jnp.asarray(presence, jnp.float32),
        jnp.asarray(frequency, jnp.float32),
        jnp.asarray(bias_idx, jnp.int32),
        jnp.asarray(bias_val, jnp.float32),
        jnp.asarray(done, jnp.bool_),
    )


@functools.lru_cache(maxsize=8)
def _jitted_retire(out_sharding=None):
    return jax.jit(
        lambda done, slot: done.at[slot].set(True),
        donate_argnums=(0,), out_shardings=out_sharding,
    )


def retire_slot(state: dict, slot: int, out_sharding=None) -> dict:
    """Mark ``slot`` done (harvested/cancelled — pads from here until
    re-admission). Only the done leaf is touched; the rest of the
    state rides along untouched until the next admission."""
    new = dict(state)
    # the index rides with the call as a numpy scalar: one transfer,
    # no put and no ``convert_element_type`` program of its own
    new["done"] = _jitted_retire(out_sharding)(
        state["done"], np.int32(slot)
    )
    return new


def slot_cache(cfg: TransformerConfig, slots: int, max_len: int) -> Cache:
    """The pool: ONE cache of ``slots`` rows, ``init_cache``'s leaves
    held one per layer (k/v: lists of [slots, length, kv_heads,
    head_dim]) and ``pos`` one number per row, [S]; a family's own
    pool where it brings one (see the module's note)."""
    family = getattr(cfg, "family", None)
    if family is not None:
        return family.slot_cache(cfg, slots, max_len)
    stacked = jax.eval_shape(lambda: init_cache(cfg, slots, max_len))
    pool = {
        name: [jnp.zeros(s.shape[1:], s.dtype) for _ in range(s.shape[0])]
        for name, s in stacked.items() if name != "pos"
    }
    pool["pos"] = jnp.zeros((slots,), jnp.int32)
    return pool


def _insert_body(cfg: TransformerConfig):
    """``(pool, row_cache, slot) -> pool`` with the row written at
    ``slot``, traceable: the family's ``insert_row`` where ``cfg``
    brings one, else the linear pool's. The ONE body of the row's
    write: ``insert_row``'s program and ``admit_row``'s both trace
    it."""

    def insert(pool: Cache, row: Cache, slot: jax.Array) -> Cache:
        new = {"pos": lax.dynamic_update_slice(
            pool["pos"], row["pos"].reshape(1).astype(jnp.int32), (slot,)
        )}
        for name, layers in row.items():
            if name != "pos":  # row[name]: [layers, 1, length, ...]
                new[name] = [
                    lax.dynamic_update_slice(
                        big, small.astype(big.dtype),
                        (slot,) + (0,) * (big.ndim - 1),
                    )
                    for big, small in zip(pool[name], layers)
                ]
        return new

    family = getattr(cfg, "family", None)
    return insert if family is None else family.insert_row


@functools.lru_cache(maxsize=8)
def _jitted_insert(cfg: TransformerConfig, out_sharding=None):
    """(pool, row_cache, slot) -> pool with the row written at slot
    (``_insert_body``). donate the pool: insertion must not copy S
    full cache rows.

    ``out_sharding`` (a NamedSharding, hashable) pins the output
    placement — multi-process serving passes fully-replicated so the
    pool NEVER drifts into whatever sharding GSPMD would pick for
    this program (a drifting pool re-enters the next donating program
    under a different layout; pinning keeps every process's copy
    bit-identical by construction)."""
    return jax.jit(
        _insert_body(cfg), donate_argnums=(0,),
        out_shardings=out_sharding,
    )


def insert_row(pool: Cache, row: Cache, slot: int,
               cfg: TransformerConfig, out_sharding=None) -> Cache:
    """Write a freshly prefilled single-row cache into the pool.
    The pool buffer is donated (in-place update)."""
    return _jitted_insert(cfg, out_sharding)(
        pool, row, jnp.asarray(slot, jnp.int32)
    )


#: the packed admission row (``pack_admission``): one int32 word per
#: name, the floats as their bits, then the two logit_bias rows
ADMIT_ROW_INTS = (
    "slot", "seed", "row", "step_idx", "top_k", "eos_id", "pad_id",
    "min_new", "max_new",
)
ADMIT_ROW_FLOATS = ("temperature", "top_p", "presence", "frequency")
_ADMIT_ROW_SCALARS = len(ADMIT_ROW_INTS) + len(ADMIT_ROW_FLOATS)
_ADMIT_ROW_BIAS_IDX = slice(
    _ADMIT_ROW_SCALARS, _ADMIT_ROW_SCALARS + BIAS_SLOTS_MAX)
_ADMIT_ROW_BIAS_VAL = slice(
    _ADMIT_ROW_BIAS_IDX.stop, _ADMIT_ROW_BIAS_IDX.stop + BIAS_SLOTS_MAX)
ADMIT_ROW_WIDTH = _ADMIT_ROW_BIAS_VAL.stop


def pack_admission(*, bias_idx=None, bias_val=None, step_idx: int = 1,
                   **scalars) -> np.ndarray:
    """Every per-request number an admission needs as ONE host row of
    ``ADMIT_ROW_WIDTH`` int32 words, built with numpy alone (nothing
    here touches the device; the row crosses in one transfer as
    ``admit_row``'s operand): ``ADMIT_ROW_INTS``, then
    ``ADMIT_ROW_FLOATS`` and after ``bias_idx`` the ``bias_val`` row
    as their float32 bits. One static width whatever the request
    carries (``bias_idx``/``bias_val``: [BIAS_SLOTS_MAX] rows, None =
    no bias), so one program serves every request.

    ``seed`` is any Python integer ``jax.random.PRNGKey`` takes: its
    low 32 bits ride, which is all ``PRNGKey`` keeps of it where
    64-bit types are off (the process's setting; tested)."""
    scalars["step_idx"] = step_idx
    packed = np.empty((ADMIT_ROW_WIDTH,), np.int32)
    floats = packed.view(np.float32)
    n_ints = len(ADMIT_ROW_INTS)
    # C-cast, as jnp.asarray(np.int64(seed)) does with 64 bits off
    packed[:n_ints] = np.asarray(
        [scalars[name] for name in ADMIT_ROW_INTS], np.int64
    ).astype(np.int32)
    floats[n_ints:_ADMIT_ROW_SCALARS] = [
        scalars[name] for name in ADMIT_ROW_FLOATS
    ]
    packed[_ADMIT_ROW_BIAS_IDX] = -1 if bias_idx is None else bias_idx
    floats[_ADMIT_ROW_BIAS_VAL] = 0.0 if bias_idx is None else bias_val
    return packed


def _unpack_admission(packed: jax.Array) -> dict:
    """``pack_admission``'s row as named device values (traceable):
    int32 scalars, float32 scalars, and the two bias rows."""
    n_ints = len(ADMIT_ROW_INTS)
    floats = lax.bitcast_convert_type(packed, jnp.float32)
    out = {name: packed[i] for i, name in enumerate(ADMIT_ROW_INTS)}
    out.update(
        (name, floats[n_ints + i])
        for i, name in enumerate(ADMIT_ROW_FLOATS)
    )
    out["bias_idx"] = packed[_ADMIT_ROW_BIAS_IDX]
    out["bias_val"] = floats[_ADMIT_ROW_BIAS_VAL]
    return out


@functools.lru_cache(maxsize=8)
def _jitted_admit_row(cfg: TransformerConfig, out_sharding=None):
    """Everything the device does with a prefilled row, as ONE program
    over one packed operand: the row key from ``seed`` and ``row``
    (``fold_in(PRNGKey(seed), row)``, the server's key convention),
    token 0 (``_first_token``), the row's write into the pool at
    ``slot`` (``_insert_body``), ``done`` from the token (so the host
    need not see it before the state is written) and the whole state
    row (``_write_state_row``). Pool and state are donated;
    ``out_sharding`` pins every output's placement like
    _jitted_insert's."""
    insert = _insert_body(cfg)

    def admit_row(pool, state, logits, row_cache, packed):
        r = _unpack_admission(packed)
        key = jax.random.fold_in(jax.random.PRNGKey(r["seed"]), r["row"])
        first = _first_token(
            logits, key, r["temperature"], r["top_k"], r["top_p"],
            r["eos_id"], r["min_new"], r["bias_idx"], r["bias_val"],
        )
        pool = insert(pool, row_cache, r["slot"])
        done = (first == r["eos_id"]) | (r["max_new"] <= 1)
        state = _write_state_row(
            state, r["slot"], first, key, r["step_idx"],
            r["temperature"], r["top_k"], r["top_p"], r["eos_id"],
            r["pad_id"], r["min_new"], r["presence"], r["frequency"],
            r["bias_idx"], r["bias_val"], done,
        )
        return pool, state, first

    return jax.jit(
        admit_row, donate_argnums=(0, 1), out_shardings=out_sharding
    )


def admit_row(pool: Cache, state: dict, logits, row_cache: Cache,
              packed, cfg: TransformerConfig, out_sharding=None):
    """Admit one prefilled request in a single dispatch: ``logits``
    [1, vocab] and ``row_cache`` as prefill returned them, ``packed``
    the request's ``pack_admission`` row. Returns ``(pool, state,
    first)``, token for token and leaf for leaf what ``first_sample``
    -> ``insert_row`` -> ``admit_slot_state`` give (tested); the pool
    and the state dict are donated, ``first`` stays on the device
    until someone fetches it."""
    return _jitted_admit_row(cfg, out_sharding)(
        pool, state, logits, row_cache, packed
    )


def _zero_stats(pool: Cache) -> Cache:
    """A pool's ``stats`` leaf counts from the program's entry."""
    if "stats" not in pool:
        return pool
    return {**pool, "stats": jnp.zeros_like(pool["stats"])}


#: the shortest read length of a linear pool's ladder, and the ratio
#: of a rung to the one below it
READ_LADDER_BASE = 1024
READ_LADDER_STEP = 4


def read_ladder(cfg: TransformerConfig, max_len: int) -> Tuple[int, ...]:
    """The read lengths a pool's decode programs are compiled for,
    shortest first, from what the configuration's CACHE FORM allows
    and ``max_len`` alone: a linear cache takes ``READ_LADDER_BASE``
    times ``READ_LADDER_STEP`` while under ``max_len``, then
    ``max_len`` itself (4,096: 1,024 and 4,096); a family's own cache
    or a ring is read whole, the one rung ``max_len``.

    Few and wide on purpose. Each rung is a chunk program and a
    fused-window program that a boot lowers (a second of the
    interpreter each on the benchmark's host, which no thread hides)
    and compiles or loads: at 512 doubling, eight programs where two
    stood, the flagship's warm set-up took 17 % longer; at these two
    rungs, 7 % (PERF.md, PR 41). What the wider rung gives
    away is small where the step is bound by what it reads beside the
    pool: 3.50 ms a step at 1,024 against 3.04 at 512 and 4.37 at
    4,096 (same place)."""
    if getattr(cfg, "family", None) is not None or cfg.window > 0:
        return (max_len,)
    rungs = []
    rung = READ_LADDER_BASE
    while rung < max_len:
        rungs.append(rung)
        rung *= READ_LADDER_STEP
    return (*rungs, max_len)


def _round_step_body(params, state, cfg, read_len=None):
    """The ONE per-token step body (scan shape) shared by the chunk
    program and the fused K-round window program: both trace exactly
    this function, so a fused window is the same computation as K
    sequential chunk rounds token for token — the parity contract
    between them holds by construction, not by numerical luck: byte
    for byte at equal ``read_len``. A window and the chunk dispatches
    it stands for may run different rungs of the ladder (the step
    program picks one a dispatch); across rungs the sums differ by
    exact zeros only, so the logits agree to the rounding of the
    compiler's own reduction tiling and the tokens agree token for
    token. The step is one ``decode_chunk`` over the pool, a cache of
    S rows each at its own ``pos``: tokens [S, 1] -> logits [S, 1, V],
    every layer's keys and values written where they lie and read as
    far as ``read_len`` (None: whole rows).
    Carry: (pool, last_token, done, step_idx, counts)."""
    row_keys = state["keys"]
    pad_id = state["pad_id"]
    eos_id = state["eos_id"]

    def body(carry, _):
        pool, tok, done, idx, counts = carry
        logits, pool = decode_chunk(  # [S, 1, V]
            params, pool, tok[:, None], cfg, read_len=read_len
        )
        with jax.named_scope("sample"):
            masked = apply_token_penalties(
                logits[:, 0, :], counts, state["presence"],
                state["frequency"],
            )
            # always-on operand (the pool program is ONE compile):
            # idx -1 rows add exactly zero, bitwise-neutral
            masked = apply_logit_bias(
                masked, state["bias_idx"], state["bias_val"]
            )
            masked = mask_eos_before_min(
                masked, idx, state["min_new"], eos_id
            )
            # the rows live at this step choose the sampler's arm: a
            # retired slot keeps its last occupant's knobs
            nxt = sample_logits(
                masked, row_keys, state["temperature"],
                state["top_k"], state["top_p"], live=~done, fold=idx,
            )
            nxt = jnp.where(done, pad_id, nxt)
            done = done | (nxt == eos_id)
            counts = count_token(counts, nxt, ~done)
        return (pool, nxt, done, idx + 1, counts), nxt

    return body


# (both caches hold a whole ladder for every configuration alive in
# the process: a rung that fell out would compile again under traffic)
def _compiler_options(cfg: TransformerConfig):
    """What a family asks of the compiler of its decode programs
    (``cfg.family.decode_compiler_options``); None for the flagship
    block and for a family that asks nothing."""
    ask = getattr(getattr(cfg, "family", None), "decode_compiler_options",
                  None)
    return ask() if ask else None


@functools.lru_cache(maxsize=64)
def _jitted_chunk(cfg: TransformerConfig, slots: int, chunk: int,
                  out_sharding=None, read_len=None):
    """One compiled program advancing every slot ``chunk`` tokens,
    attention reading each row's first ``read_len`` positions (None:
    whole rows; see ``read_ladder``).

    Operands: the pool cache and the per-slot sampling-state dict
    (SLOT_STATE_KEYS), BOTH donated — the per-round dispatch ships
    exactly three operands (params, pool, state), all already on
    device. Returns (pool, state, tokens [S, chunk]) where the state
    carries the advanced last/done/counts AND step_idx (advanced on
    device — no host buffer to mutate in place, so the historical
    torn-step-index hazard cannot recur); the untouched knob leaves
    alias straight through the donation.
    """

    def run(params, pool, state):
        pool = _zero_stats(pool)
        body = _round_step_body(params, state, cfg, read_len)
        # ``steps`` names what the step loop itself does around its
        # body (nothing the size of the pool: PERF.md s.5)
        with jax.named_scope("steps"):
            (pool, last, done, idx, counts), toks = lax.scan(
                body,
                (pool, state["last"], state["done"], state["step_idx"],
                 state["counts"]),
                None, length=chunk,
            )
        new_state = dict(
            state, last=last, done=done, counts=counts,
            step_idx=idx,
        )
        return pool, new_state, toks.T, pool.get("stats")  # [S, chunk]

    return jax.jit(
        run, donate_argnums=(1, 2), out_shardings=out_sharding,
        compiler_options=_compiler_options(cfg),
    )


@functools.lru_cache(maxsize=64)
def _jitted_window(cfg: TransformerConfig, slots: int, chunk: int,
                   rounds: int, out_sharding=None, read_len=None):
    """K = ``rounds`` chunk-rounds fused into ONE dispatched program:
    a device-side ``lax.while_loop`` whose body is the exact per-step
    scan ``_jitted_chunk`` runs (``_round_step_body``), so the tokens
    a window emits are byte-identical to K sequential chunk
    dispatches of the same ``read_len`` (token for token across
    rungs: see ``_round_step_body``). The loop exits EARLY when no
    slot is live — a slot is live while its device ``done`` flag is
    clear AND it still has window budget (``budget`` [S] int32, the host's remaining
    max_new allowance per slot). Budget gates ONLY the exit test,
    never the emission: a slot past its budget keeps decoding real
    (append-discarded) tokens exactly like the sequential engine
    whose host hadn't retired it yet, preserving bit-equality of
    the shared rounds.

    Returns (pool, state, tokens [S, rounds*chunk], rounds_run):
    rounds not executed leave their token columns at the slot's
    pad_id, and the state advances by exactly rounds_run chunks.
    Pool and state are donated like the chunk program's."""

    def run(params, pool, state, budget):
        pool = _zero_stats(pool)
        body = _round_step_body(params, state, cfg, read_len)
        pad = state["pad_id"].astype(jnp.int32)
        out0 = jnp.broadcast_to(
            pad[:, None], (slots, rounds * chunk)
        )

        def cond(carry):
            r, _pool, _last, done, _idx, _counts, _out = carry
            return (r < rounds) & jnp.any(
                ~done & (r * chunk < budget)
            )

        def round_body(carry):
            r, pool, last, done, idx, counts, out = carry
            (pool, last, done, idx, counts), toks = lax.scan(
                body, (pool, last, done, idx, counts),
                None, length=chunk,
            )
            out = lax.dynamic_update_slice(
                out, toks.T, (0, r * chunk)
            )
            return (r + 1, pool, last, done, idx, counts, out)

        with jax.named_scope("steps"):
            r, pool, last, done, idx, counts, out = lax.while_loop(
                cond, round_body,
                (jnp.int32(0), pool, state["last"], state["done"],
                 state["step_idx"], state["counts"], out0),
            )
        new_state = dict(
            state, last=last, done=done, counts=counts, step_idx=idx,
        )
        return pool, new_state, out, r, pool.get("stats")

    return jax.jit(
        run, donate_argnums=(1, 2), out_shardings=out_sharding,
        compiler_options=_compiler_options(cfg),
    )


def compile_decode_programs(
    params: Params,
    pool: Cache,
    state: dict,
    cfg: TransformerConfig,
    chunk: int,
    rounds: int,
    read_lens,
    out_sharding=None,
) -> None:
    """Compile, or load from the compile cache, the chunk program and
    (``rounds`` > 1) the fused-window program of every read length in
    ``read_lens``, SIDE BY SIDE: each is lowered here, one after
    another (the interpreter's work), and the lowered programs compile
    on a thread each (the compiler's, outside the interpreter's lock),
    so a ladder of rungs costs a boot about what one rung does. The
    arguments give shapes and placement only: nothing runs, nothing is
    donated. A jitted function's first call then finds its executable
    made (jax keeps one per lowering, whoever asked for it); where a
    jax does not, that call compiles as it always did."""
    from concurrent.futures import ThreadPoolExecutor

    slots = int(state["last"].shape[0])
    budget = jnp.zeros((slots,), jnp.int32)
    lowered = []
    for read_len in read_lens:
        lowered.append(
            _jitted_chunk(cfg, slots, chunk, out_sharding, read_len)
            .lower(params, pool, state)
        )
        if rounds > 1:
            lowered.append(
                _jitted_window(
                    cfg, slots, chunk, rounds, out_sharding, read_len
                ).lower(params, pool, state, budget)
            )
    with ThreadPoolExecutor(len(lowered)) as threads:
        for _ in threads.map(lambda low: low.compile(), lowered):
            pass


def decode_slots_chunk(
    params: Params,
    pool: Cache,
    state: dict,
    cfg: TransformerConfig,
    chunk: int,
    out_sharding=None,
    with_stats: bool = False,
    read_len=None,
):
    """Advance the whole pool ``chunk`` tokens; see _jitted_chunk.
    ``state`` is the device-resident per-slot sampling dict
    (init_slot_state / admit_slot_state); its bias_idx/bias_val are
    [S, K] per-slot logit_bias operands (-1 = unused slot; serving
    uses K = BIAS_SLOTS_MAX so one program covers every legal
    request). Returns (pool, state, tokens [S, chunk]); the pool AND
    the whole state dict are donated. ``out_sharding`` pins every
    output's placement (see _jitted_insert) — the pod passes
    fully-replicated. ``with_stats`` appends the pool's ``stats``
    (None where it has none). ``read_len``: how far attention reads
    each row, a rung of ``read_ladder`` that no live row passes in
    these ``chunk`` steps (None: whole rows)."""
    slots = int(state["last"].shape[0])
    out = _jitted_chunk(cfg, slots, chunk, out_sharding, read_len)(
        params, pool, state
    )
    return out if with_stats else out[:3]


def decode_slots_window(
    params: Params,
    pool: Cache,
    state: dict,
    cfg: TransformerConfig,
    chunk: int,
    rounds: int,
    budget,
    out_sharding=None,
    with_stats: bool = False,
    read_len=None,
):
    """Advance the whole pool up to ``rounds`` chunk-rounds in ONE
    host->device dispatch (see _jitted_window): the device loops over
    the same per-step body the chunk program runs and exits early
    once every slot is done or out of ``budget`` (a [S] int32 of
    remaining-token allowances — the one small host->device upload a
    window pays, per K rounds instead of per round). Returns
    (pool, state, tokens [S, rounds*chunk], rounds_run); pool and
    state are donated, ``out_sharding`` pins output placement and
    ``read_len`` cuts the read exactly like decode_slots_chunk's (no
    live row may pass it in ``rounds * chunk`` steps)."""
    slots = int(state["last"].shape[0])
    out = _jitted_window(
        cfg, slots, chunk, rounds, out_sharding, read_len
    )(
        params, pool, state, jnp.asarray(budget, jnp.int32)
    )
    return out if with_stats else out[:4]


@jax.named_scope("sample")
def _first_token(logits, row_key, temperature, top_k, top_p, eos_id,
                 min_new, bias_idx, bias_val):
    """Token 0 from prefill logits [1, vocab] with generate's key
    schedule (fold_in(row_key, 0)). The ONE body of the first draw:
    ``first_sample``'s program and ``admit_row``'s both trace it."""
    # counts are empty at sample 0, so penalties are a no-op here
    # by construction — identical to generate's first sample.
    # logit_bias DOES apply at sample 0 (generate biases every
    # draw), hence the operands here.
    masked = apply_logit_bias(
        logits, bias_idx[None], bias_val[None]
    )
    masked = mask_eos_before_min(
        masked, jnp.int32(0), min_new[None], eos_id[None]
    )
    return sample_logits(
        masked, row_key[None], temperature[None], top_k[None],
        top_p[None], fold=jnp.int32(0),
    )[0]


@functools.lru_cache(maxsize=8)
def _jitted_first_sample(cfg: TransformerConfig):
    """Sample token 0 from prefill logits (``_first_token``)."""
    return jax.jit(_first_token)


def first_sample(logits, row_key, temperature, top_k, top_p,
                 cfg: TransformerConfig, eos_id: int = -1,
                 min_new: int = 0, bias_idx=None,
                 bias_val=None) -> jax.Array:
    """logits: [1, vocab] from prefill -> token 0 (scalar).
    ``bias_idx``/``bias_val``: a [K] logit_bias row (None = no bias;
    the default materializes at BIAS_SLOTS_MAX — the width serving
    always passes — so biased and plain callers share one compiled
    program)."""
    if bias_idx is None:
        bias_idx = jnp.full((BIAS_SLOTS_MAX,), -1, jnp.int32)
        bias_val = jnp.zeros((BIAS_SLOTS_MAX,), jnp.float32)
    return _jitted_first_sample(cfg)(
        logits, row_key,
        jnp.asarray(temperature, jnp.float32),
        jnp.asarray(top_k, jnp.int32),
        jnp.asarray(top_p, jnp.float32),
        jnp.asarray(eos_id, jnp.int32),
        jnp.asarray(min_new, jnp.int32),
        jnp.asarray(bias_idx, jnp.int32),
        jnp.asarray(bias_val, jnp.float32),
    )
