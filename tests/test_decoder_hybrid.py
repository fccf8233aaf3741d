"""The decoder-hybrid-decoder family (models/decoder_hybrid.py: Mamba-1
and window attention in turn, one full attention layer, then gated
memory units and cross attention that read what the first half wrote;
differential attention throughout) against the benchmark's plain
reference (benchmark/configs/phi4flash_reference.py) at toy size on
the CPU: hidden 64, 8 query and 4 key/value heads of 8, a window of 8,
a state of 4, 12 layers by the published rule (Mamba 0, 2, 4, 6, window
1, 3, 5, the memory from 6, full 7, gated memory 8, 10, cross 9, 11),
seeded weights.

Comparisons are on LOGITS. The program holds bfloat16 weights; the
tests widen the SAME values to float32 and compute in float32
(``highest``), so that what is compared is the mathematics (the
blocked scan against the step-by-step recurrence, a ring against a
banded mask, one shared plane against each layer's own keys, the
trimmed prefill against every layer at every position, the padded
queries against the split heads), not bf16 rounding. The logits are
small (the head is the embedding, seeded at 0.001: within 0.05 of
zero), so agreement is asked relative to the largest logit: to
``REL`` = 2e-5 of it, float32 rounding over 12 layers (read: 3e-6),
and tight enough that the reference's own reading with the state
rounded to bfloat16 (2e-3 of the largest logit) or with int8 weights
(0.2 of it) fails, which a test below holds it to. The bf16 path's own
distance from the reference is what the benchmark's ``correct``
measures on the chip.
"""
import asyncio
import dataclasses
import importlib.util
import json
import os
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from containerpilot_tpu.models import decoder_hybrid as dh
from containerpilot_tpu.models import slots as slots_mod
from containerpilot_tpu.models.decode import _jitted_prefill, generate
from containerpilot_tpu.models.stepprog import PlainStepProgram, make_step_program
from containerpilot_tpu.ops import ragged_decode
from containerpilot_tpu.workload import modelcfg

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY_FILE = os.path.join(ROOT, "benchmark", "tests", "toy", "toy-phi4flash.json")
REAL_FILE = os.path.join(ROOT, "benchmark", "configs",
                         "phi-4-mini-flash-serve.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REL = 2e-5
MAX_LEN = 64
WINDOW = 8
#: every sequence the programs see whole has this length (it wraps a
#: ring of 8 three times), so each is compiled once
SEQ = 29
#: positions of a key block of the plane's reads here: a plane of 64
#: positions is eight blocks, so rows end in different ones
BLOCK = 8


def _reference():
    spec = importlib.util.spec_from_file_location(
        "phi4flash_reference",
        os.path.join(ROOT, "benchmark", "configs", "phi4flash_reference.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


R = _reference()

with open(TOY_FILE) as _fh:
    TOY = {k: v for k, v in json.load(_fh).items()
           if k not in ("launch", "check", "check_note", "reference")}


def widened(config, max_len=MAX_LEN):
    """(float32 configuration, the bf16-held weights widened)."""
    cfg = dh.from_published(config, max_len)
    params = dh.init_params(None, cfg)
    return (dataclasses.replace(cfg, dtype=jnp.float32),
            jax.tree.map(lambda x: x.astype(jnp.float32), params))


class Programs:
    """The toy configuration's float32 programs, jitted once."""

    def __init__(self):
        self.cfg, self.params = widened(TOY)
        cfg = self.cfg
        self.forward = jax.jit(lambda p, t: dh.forward(p, t, cfg))
        self.prefill = _jitted_prefill(cfg, MAX_LEN)
        self.step = jax.jit(lambda p, c, t: dh.decode_chunk(p, c, t, cfg))
        self._reference = jax.jit(lambda t: R.all_logits(TOY, t))

    def logits(self, toks):
        return np.asarray(self.forward(self.params, jnp.asarray(toks)[None]))[0]

    def reference(self, toks):
        row = np.zeros((max(SEQ, len(toks)),), np.int32)
        row[: len(toks)] = toks
        return np.asarray(self._reference(row))[: len(toks)]


@pytest.fixture(scope="module", autouse=True)
def small_blocks():
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ragged_decode, "BLOCK_LEN", BLOCK)
        yield


@pytest.fixture(scope="module")
def prog():
    with jax.default_matmul_precision("highest"):
        return Programs()


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def ids(n, seed=0, vocab=512):
    return np.random.default_rng(seed).integers(1, vocab, (n,)).astype(np.int32)


def close(mine, ref, rel=REL):
    """Whether ``mine`` lies within ``rel`` of the reference's largest
    value of ``ref``, everywhere."""
    mine, ref = np.asarray(mine), np.asarray(ref)
    return float(np.abs(mine - ref).max()) < rel * float(np.abs(ref).max())


# -- forward, prefill, decode ------------------------------------------------


def test_the_layer_kinds_are_the_published_rule():
    assert dh.layer_kinds(12) == (
        "mamba", "window", "mamba", "window", "mamba", "window", "mamba",
        "full", "gmu", "cross", "gmu", "cross")
    kinds = dh.layer_kinds(32)
    assert [kinds.count(k) for k in dh.KINDS] == [9, 8, 1, 7, 7]
    assert kinds[16] == "mamba" and kinds[17] == "full" and kinds[15] == "window"
    cfg = dh.from_published(TOY, MAX_LEN)
    assert cfg.memory_layer == 6 and cfg.plane_readers == 3
    with pytest.raises(ValueError, match="multiple of 4"):
        dh.DecoderHybridConfig(n_layers=10)


def test_full_forward_matches_the_reference(prog):
    toks = ids(SEQ, seed=1)
    assert close(prog.logits(toks), prog.reference(toks))


def test_the_tolerance_fails_a_lower_precision(prog):
    """``REL`` lies under what the reference's own lower-precision
    readings move: the state rounded to bfloat16 after every step, and
    int8 weights. A program computing either would fail the test
    above."""
    toks = ids(SEQ, seed=1)
    ref = prog.reference(toks)
    for mode in ("bf16-state", "int8-weights"):
        lower = np.asarray(R.all_logits(TOY, toks, mode=mode))
        assert not close(lower, ref), mode


@pytest.mark.parametrize("prompt", [5, WINDOW, 13, 21])
def test_prefill_then_decode_matches_the_full_forward(prog, prompt):
    """A prompt shorter than, equal to and longer than the window
    through ``prefill`` (the ring filled from its last 8 positions, the
    cross-decoder run for the last position only), then one-token steps
    to position 29: the rings wrap up to three times. Every logit is
    the full forward's, which runs every layer at every position and
    keeps no cache."""
    toks = ids(SEQ, seed=2)
    want = prog.reference(toks)
    logits, cache = prog.prefill(prog.params, jnp.asarray(toks[:prompt])[None])
    assert close(np.asarray(logits)[0], want[prompt - 1])
    assert int(cache["pos"]) == prompt
    assert [int(n) for n in cache["admitted"]] == [prompt, 1]
    for t in range(prompt, SEQ):
        logits, cache = prog.step(
            prog.params, cache, jnp.asarray(toks[t:t + 1])[None])
        assert close(np.asarray(logits)[0, 0], want[t]), t
    assert int(cache["pos"]) == SEQ


def test_the_trimmed_prefill_gives_the_untrimmed_forwards_last_logits(prog):
    toks = ids(SEQ, seed=3)
    logits, _cache = prog.prefill(prog.params, jnp.asarray(toks)[None])
    assert close(np.asarray(logits)[0], prog.logits(toks)[-1])


def test_a_ring_holds_the_prompts_last_window_at_position_mod_window(prog):
    """After a prompt of 21 the ring's slot j holds the key of the
    position in 13 .. 20 that is j modulo 8, and the plane holds every
    position where it stands."""
    toks = ids(21, seed=4)
    _logits, cache = prog.prefill(prog.params, jnp.asarray(toks)[None])
    _logits, longer = prog.prefill(prog.params, jnp.asarray(toks[:20])[None])
    # position 13 .. 19 are in both rings, each at its own slot
    for t in range(13, 20):
        assert np.array_equal(np.asarray(cache["ring_k"][0][0, :, t % 8]),
                              np.asarray(longer["ring_k"][0][0, :, t % 8]))
    assert not np.array_equal(np.asarray(cache["ring_k"][0][0, :, 20 % 8]),
                              np.asarray(longer["ring_k"][0][0, :, 20 % 8]))
    plane = np.asarray(cache["k"][0][0])
    assert np.abs(plane[:, :21]).min(axis=(0, 2)).all()
    assert not np.abs(plane[:, 21:]).any()


def test_more_than_one_token_a_row_is_refused(prog):
    cache = dh.init_cache(prog.cfg, 1, MAX_LEN)
    with pytest.raises(ValueError, match="one token a row"):
        dh.decode_chunk(prog.params, cache, jnp.zeros((1, 2), jnp.int32), prog.cfg)


# -- each kind of layer against its equation -----------------------------------


def _mamba_by_the_equation(u, lp, cfg):
    """One row, step by step: u [seq, d] -> (out [seq, d], y [seq, inner])."""
    u = np.asarray(u, np.float64)
    w = {k: np.asarray(v, np.float64) for k, v in lp.items()}
    seq, inner, n, rank = len(u), cfg.d_inner, cfg.ssm_state, cfg.ssm_dt_rank
    xz = u @ w["w_in"]
    x, z = xz[:, :inner], xz[:, inner:]
    padded = np.concatenate([np.zeros((3, inner)), x])
    x = sum(w["conv_w"][j] * padded[j:j + seq] for j in range(4)) + w["conv_b"]
    x = x / (1 + np.exp(-x))
    dbc = x @ w["w_x"]
    delta = np.log1p(np.exp(dbc[:, :rank] @ w["w_dt"] + w["dt_bias"]))
    b_in, c_out = dbc[:, rank:rank + n], dbc[:, rank + n:]
    rate = -np.exp(w["a_log"])                   # held [n, inner]
    state = np.zeros((n, inner))
    ys = []
    for t in range(seq):
        state = np.exp(delta[t][None, :] * rate) * state + np.outer(
            b_in[t], delta[t] * x[t])
        ys.append(c_out[t] @ state + w["d_skip"] * x[t])
    y = np.stack(ys)
    return (y * (z / (1 + np.exp(-z)))) @ w["w_out"], y


@pytest.mark.parametrize("length", [5, 16, 21])
def test_the_blocked_scan_is_the_step_by_step_recurrence(prog, length):
    """A Mamba-1 mixer over 5, 16 and 21 positions in blocks of 16 (a
    short block, a whole one, one and a padded tail) against the
    equations in float64, and against the same mixer taken one position
    at a time from the state and the tail: output, memory and state."""
    cfg, lp = prog.cfg, prog.params["layers"][0]
    u = jax.random.normal(jax.random.PRNGKey(length), (1, length, cfg.d_model))
    state = jnp.zeros((1, cfg.ssm_state, cfg.d_inner))
    tail = jnp.zeros((1, cfg.ssm_conv - 1, cfg.d_inner))
    out, memory, last, _tail = dh._mamba(u, lp, cfg, state, tail)
    want, y = _mamba_by_the_equation(u[0], lp, cfg)
    assert close(out[0], want, 1e-5) and close(memory[0], y, 1e-5)
    for t in range(length):
        step_out, step_y, state, tail = dh._mamba(
            u[:, t:t + 1], lp, cfg, state, tail)
        assert close(step_out[0, 0], want[t], 1e-5)
    assert close(state, last, 1e-5)


def _differential_by_the_equation(q, k, v, lp, layer, window, cfg):
    """q [seq, heads, hd], k and v [seq, kv_heads, hd], one row, in
    float64 with the heads split as published."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    seq, hd = len(q), cfg.head_dim
    lq1, lk1, lq2, lk2 = (np.asarray(a, np.float64) for a in lp["lambdas"])
    start = 0.8 - 0.6 * np.exp(-0.3 * layer)
    lam = np.exp(lq1 @ lk1) - np.exp(lq2 @ lk2) + start
    mask = np.tril(np.ones((seq, seq), bool))
    if window:
        mask &= ~np.tril(np.ones((seq, seq), bool), -window)
    group = (cfg.n_heads // 2) // (cfg.n_kv_heads // 2)
    heads = []
    for j in range(cfg.n_heads // 2):
        p = j // group
        value = np.concatenate([v[:, 2 * p], v[:, 2 * p + 1]], axis=-1)
        maps = []
        for which in (0, 1):
            s = q[:, 2 * j + which] @ k[:, 2 * p + which].T / np.sqrt(hd)
            s = np.where(mask, s, -np.inf)
            e = np.exp(s - s.max(-1, keepdims=True))
            maps.append((e / e.sum(-1, keepdims=True)) @ value)
        a = maps[0] - lam * maps[1]
        a = a / np.sqrt((a * a).mean(-1, keepdims=True) + 1e-5)
        heads.append(a * np.asarray(lp["subln"]) * (1 - start))
    return np.concatenate(heads, axis=-1)


@pytest.mark.parametrize("layer, window", [(1, WINDOW), (7, 0)])
def test_differential_attention_is_the_published_form(prog, layer, window):
    """The padded queries over pair-major keys and values, and the
    difference with its norm, against the split heads in float64: a
    window layer (banded to 8 positions, self included) and the full
    layer."""
    cfg, lp = prog.cfg, prog.params["layers"][layer]
    u = jax.random.normal(jax.random.PRNGKey(layer), (1, 19, cfg.d_model))
    q, k, v = dh._qkv(u, lp, cfg)
    o = dh._sequence_attention(q, k, v, window, cfg)
    mine = dh._difference(o, lp, layer, cfg)
    qkv = np.asarray(u[0]) @ np.asarray(lp["w_qkv"]) + np.asarray(lp["b_qkv"])
    hd, heads, kv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    want = _differential_by_the_equation(
        qkv[:, :heads * hd].reshape(19, heads, hd),
        qkv[:, heads * hd:(heads + kv) * hd].reshape(19, kv, hd),
        qkv[:, (heads + kv) * hd:].reshape(19, kv, hd), lp, layer, window, cfg)
    assert close(mine[0], want, 1e-5)
    assert dh.lambda_init(0) == pytest.approx(0.2) and lp["lambdas"].shape == (4, 8)


def test_a_gated_memory_unit_reads_the_memory_of_the_same_position(prog):
    cfg, lp = prog.cfg, prog.params["layers"][8]
    u = jax.random.normal(jax.random.PRNGKey(8), (1, 7, cfg.d_model))
    memory = jax.random.normal(jax.random.PRNGKey(9), (1, 7, cfg.d_inner))
    gate = np.asarray(u[0]) @ np.asarray(lp["g_in"])
    want = ((gate / (1 + np.exp(-gate))) * np.asarray(memory[0])) @ np.asarray(
        lp["g_out"])
    assert close(dh._gmu(u, memory, lp, cfg)[0], want, 1e-5)
    stale = jnp.roll(memory, 1, axis=1)
    assert not close(dh._gmu(u, stale, lp, cfg)[0], want, 1e-2)


def test_a_cross_layer_has_a_query_and_reads_the_full_layers_keys(prog):
    """A cross layer holds no key or value projection, and what it
    attends to is layer 7's plane: with the plane's keys changed after
    layer 7 ran, the cross layers' part of the logits changes."""
    lp = prog.params["layers"][9]
    assert "w_q" in lp and "w_qkv" not in lp and lp["w_q"].shape == (64, 64)
    toks = ids(12, seed=5)
    _logits, cache = prog.prefill(prog.params, jnp.asarray(toks[:11])[None])
    logits, _new = prog.step(prog.params, cache, jnp.asarray(toks[11:])[None])
    moved = dict(cache, v=[cache["v"][0].at[:, :, 3].add(1.0)])
    other, _new = prog.step(prog.params, moved, jnp.asarray(toks[11:])[None])
    assert not close(other, logits, 1e-3)


# -- the pool ------------------------------------------------------------------

SLOTS, CHUNK = 3, 4


def _admit(prog, pool, state, slot, prompt):
    logits, row = prog.prefill(prog.params, jnp.asarray(prompt)[None])
    first = int(np.argmax(np.asarray(logits)[0]))
    pool = slots_mod.insert_row(pool, row, slot, prog.cfg)
    state = slots_mod.admit_slot_state(
        state, slot, prog.cfg, last=first, key=jax.random.PRNGKey(slot),
        temperature=0.0, top_k=0, top_p=1.0, eos_id=-1, pad_id=0, min_new=0,
        presence=0.0, frequency=0.0,
        bias_idx=np.full((slots_mod.BIAS_SLOTS_MAX,), -1),
        bias_val=np.zeros((slots_mod.BIAS_SLOTS_MAX,)), done=False)
    return pool, state, first


def _covered(prompts, first, steps):
    """Positions the blocks of one reader cover over ``steps`` steps
    from the ``first``: the prompts' rows and the pool's empty slots
    (which stand at the step's number)."""
    starts = [len(p) for p in prompts] + [0] * (SLOTS - len(prompts))
    return sum(((start + step) // BLOCK + 1) * BLOCK
               for start in starts for step in range(first, first + steps))


def _served_is_the_references_best(prog, prompt, served):
    """The served tokens' logit gaps under the reference, as the
    benchmark's ``correct`` judges them: none."""
    row = np.concatenate([prompt, served])[:-1]
    ref = prog.reference(row)
    at = np.arange(len(prompt) - 1, len(row))
    gaps = ref[at].max(axis=-1) - ref[at, served]
    return float(gaps.max()) < REL * float(np.abs(ref).max())


@pytest.mark.parametrize("program", ["chunk", "window"])
def test_pool_programs_match_the_reference(prog, program):
    """Two prompts, one shorter than the window and one longer,
    prefilled, inserted into a pool of three slots (one stays empty and
    steps on pads), decoded greedily by the chunk program (two
    dispatches) or the fused window (one): rows at different positions,
    a state, a tail, eight-slot rings and a plane each, and every
    emitted token is the reference's best at its position. The counters
    of the last program call: every row of the pool steps 4 Mamba
    layers and 3 rings and reads the plane 3 times; a row counts as
    wrapped once it stands at the window or past it; the plane's reads
    cover every row to the end of the block of 8 that holds its
    position; what the two prefills ran rides with the first step."""
    cfg, rounds = prog.cfg, 2
    pool = slots_mod.slot_cache(cfg, SLOTS, MAX_LEN)
    state = slots_mod.init_slot_state(cfg, SLOTS)
    prompts = [ids(n, seed=n) for n in (5, 13)]
    firsts = []
    for slot, prompt in enumerate(prompts):
        pool, state, first = _admit(prog, pool, state, slot, prompt)
        firsts.append(first)
    assert [int(n) for n in pool["admitted"]] == [18, 2]
    if program == "chunk":
        pieces, counted = [], []
        for _ in range(rounds):
            pool, state, toks, stats = slots_mod.decode_slots_chunk(
                prog.params, pool, state, cfg, CHUNK, with_stats=True)
            pieces.append(np.asarray(toks))
            counted.append(np.asarray(stats))
        toks = np.concatenate(pieces, axis=1)
        steps = CHUNK
        # row 0 stands at 5, 6, 7, 8 and then 9 .. 12; row 1 past the
        # window throughout; the empty slot at 0 .. 7
        assert [int(c[2]) for c in counted] == [1 + 4, 4 + 4]
        assert [int(n) for n in counted[0][5:]] == [18, 2]
        assert [int(n) for n in counted[1][5:]] == [0, 0]
        assert [int(c[4]) for c in counted] == [
            3 * _covered(prompts, first, CHUNK)
            for first in (0, CHUNK)]
        stats = counted[-1]
    else:
        pool, state, toks, run, stats = slots_mod.decode_slots_window(
            prog.params, pool, state, cfg, CHUNK, rounds,
            np.full((SLOTS,), 100), with_stats=True)
        assert int(run) == rounds
        toks, stats = np.asarray(toks), np.asarray(stats)
        steps = CHUNK * rounds
        assert int(stats[2]) == 5 + 8 and [int(n) for n in stats[5:]] == [18, 2]
        assert int(stats[4]) == 3 * _covered(prompts, 0, steps)
    assert int(stats[0]) == steps * SLOTS * 4
    assert int(stats[1]) == steps * SLOTS * 3
    assert int(stats[3]) == steps * SLOTS * 3
    assert not np.asarray(pool["admitted"]).any()
    for slot, prompt in enumerate(prompts):
        served = np.asarray([firsts[slot]] + [int(t) for t in toks[slot]])
        assert _served_is_the_references_best(prog, prompt, served), slot
    assert list(np.asarray(pool["pos"])[:2]) == [
        len(p) + CHUNK * rounds for p in prompts]


def _plain_plane_attention(q, keys, values, at, cfg):
    """What ``_plane_attention`` stood in for: the plain contraction
    over every position of every row, masked by position."""
    valid = (jnp.arange(keys.shape[2])[None, :] <= at[:, None])[:, None, :]
    return dh._pair_attention(q, keys, values, valid, cfg)


@pytest.mark.parametrize("program", ["step", "chunk", "window"])
def test_decode_reads_the_plane_as_the_plain_contraction_does(
        prog, program, monkeypatch):
    """One decode step, the chunk program and the fused window over a
    pool whose rows end in different key blocks (prompts of 5 and 21, an
    empty slot), through the kernel and through the plain read of the
    whole plane (the same programs traced again with ``_pair_attention``
    in the kernel's place): the same tokens, logits within the decode
    tests' tolerance, and every leaf of the cache the plain read's to
    float32 rounding."""
    plain_cfg = dataclasses.replace(prog.cfg, source_digest="plain read")

    def run(cfg):
        pool = slots_mod.slot_cache(cfg, SLOTS, MAX_LEN)
        state = slots_mod.init_slot_state(cfg, SLOTS)
        for slot, n in enumerate((5, 21)):
            pool, state, _first = _admit(prog, pool, state, slot, ids(n, seed=n))
        if program == "step":
            tokens = jnp.asarray(ids(SLOTS, seed=3))[:, None]
            logits, cache = jax.jit(
                lambda p, c, t: dh.decode_chunk(p, c, t, cfg))(
                    prog.params, pool, tokens)
            return np.asarray(logits), cache
        if program == "chunk":
            pool, _state, toks = slots_mod.decode_slots_chunk(
                prog.params, pool, state, cfg, CHUNK)
        else:
            pool, _state, toks, _run = slots_mod.decode_slots_window(
                prog.params, pool, state, cfg, CHUNK, 2,
                np.full((SLOTS,), 100))
        return np.asarray(toks), pool

    mine, cache = run(prog.cfg)
    with monkeypatch.context() as patch:
        patch.setattr(dh, "_plane_attention", _plain_plane_attention)
        plain, plain_cache = run(plain_cfg)
    if program == "step":
        assert close(mine, plain)
    else:
        assert np.array_equal(mine, plain)
    assert np.array_equal(np.asarray(cache["pos"]), np.asarray(plain_cache["pos"]))
    for name in dh.ALL_LEAVES:
        for got, want in zip(cache[name], plain_cache[name]):
            assert close(got, want), name


def test_the_plane_positions_read_are_the_blocks_cover_times_the_readers(prog):
    """A pool of three rows at known positions (3, the last of a block;
    8, the first of the next; 200, a dead slot past the plane's end)
    steps once: ``plane_positions_read`` counts, for each of the three
    layers that read the plane, every block up to the one that holds the
    row's position (the dead slot's: the whole plane), and
    ``describe_hybrid_decoder`` publishes it beside
    ``shared_plane_reads``."""
    cfg = prog.cfg
    pool = slots_mod.slot_cache(cfg, SLOTS, MAX_LEN)
    pool["pos"] = jnp.asarray([BLOCK - 1, BLOCK, 200], jnp.int32)
    _logits, out = prog.step(prog.params, pool, jnp.ones((SLOTS, 1), jnp.int32))
    described = dh.describe_hybrid_decoder(cfg, np.asarray(out["stats"]))
    assert described["shared_plane_reads"] == SLOTS * 3
    assert described["plane_positions_read"] == 3 * (
        BLOCK + 2 * BLOCK + MAX_LEN)
    assert list(described).index("plane_positions_read") == list(
        described).index("shared_plane_reads") + 1


def test_a_row_inserted_over_a_retired_one_keeps_nothing_of_it(prog):
    """A slot decodes, is retired (it steps on, on pads: its state,
    tail, rings and plane keep moving), and takes a new, shorter prompt:
    every leaf of the slot is then the new row's own, and the new row's
    tokens are the reference's."""
    cfg = prog.cfg
    pool = slots_mod.slot_cache(cfg, SLOTS, MAX_LEN)
    state = slots_mod.init_slot_state(cfg, SLOTS)

    def decode(pool, state):
        return slots_mod.decode_slots_chunk(prog.params, pool, state, cfg, CHUNK)

    pool, state, _first = _admit(prog, pool, state, 1, ids(20, seed=1))
    pool, state, _toks = decode(pool, state)
    state = slots_mod.retire_slot(state, 1)
    pool, state, _toks = decode(pool, state)
    assert float(jnp.abs(pool["ssm"][0][1]).max()) > 0.0
    assert int(pool["pos"][1]) == 20 + 2 * CHUNK
    prompt = ids(5, seed=2)
    _logits, row = prog.prefill(prog.params, jnp.asarray(prompt)[None])
    pool, state, first = _admit(prog, pool, state, 1, prompt)
    assert int(pool["pos"][1]) == 5
    for name in dh.ALL_LEAVES:
        for mine, theirs in zip(pool[name], row[name]):
            assert np.array_equal(np.asarray(mine[1]), np.asarray(theirs[0])), name
    # the plane past the prompt and the rings past it hold nothing
    assert not np.asarray(pool["k"][0][1, :, 5:]).any()
    assert not np.asarray(pool["ring_v"][2][1, :, 5:]).any()
    pool, state, toks = decode(pool, state)
    served = np.asarray([first] + [int(t) for t in np.asarray(toks)[1]])
    assert _served_is_the_references_best(prog, prompt, served)


def _solo(prog, tokens, new):
    out = generate(prog.params, jnp.asarray([tokens], jnp.int32), prog.cfg,
                   new, MAX_LEN)
    return [int(t) for t in np.asarray(out)[0]]


def test_the_engine_serves_what_one_shot_generation_gives(prog):
    """Three requests over two slots through ``SlotEngine`` (prefill,
    ``admit_row``, chunk and fused-window dispatches, a slot reused, a
    row beside other rows): each row's tokens are a solo
    ``generate``'s, and the counters read the family's arithmetic."""
    from containerpilot_tpu.workload.serve_slots import SlotEngine

    engine = SlotEngine(prog.cfg, prog.params, MAX_LEN, slots=2, chunk=3,
                        window=2)
    try:
        prompts = [list(map(int, ids(n, seed=n))) for n in (5, 11, 8)]
        news = (9, 14, 6)
        futures = [engine.submit(p, max_new=n) for p, n in zip(prompts, news)]
        got = [f.result(timeout=300) for f in futures]
        counted = engine.hybrid_decoder_stats()
        state = engine.state_stats()
    finally:
        engine.stop()
    for prompt, new, row in zip(prompts, news, got):
        assert row == _solo(prog, prompt, new)
    steps = counted["ssm_row_steps"] // (2 * 4)
    assert steps > 0 and counted["ssm_row_steps"] == steps * 2 * 4
    assert counted["ring_row_steps"] == steps * 2 * 3
    assert counted["shared_plane_reads"] == steps * 2 * 3
    assert counted["prefill_positions_cross"] == 3
    assert counted["prefill_positions_self"] == 5 + 11 + 8
    assert state["ssm_row_steps"] == counted["ssm_row_steps"]


def test_step_program_returns_the_counters_with_the_tokens():
    cfg = dh.from_published(TOY, MAX_LEN)
    params = dh.init_params(None, cfg)
    program = make_step_program(cfg, params, MAX_LEN, slots=2, chunk=4, rounds=2)
    assert isinstance(program, PlainStepProgram)
    assert program.hybrid_decoder_stats()["shared_plane_reads"] == 0
    assert program.expert_stats() is None and program.loop_stats() is None
    program.tokens(program.dispatch(np.asarray([100, 100]), False))
    assert program.hybrid_decoder_stats() == {
        "layer_kinds": {"mamba": 4, "window": 3, "full": 1, "gmu": 2, "cross": 2},
        "window": 8, "memory_layer": 6, "plane_readers": 3,
        "state_bytes_per_slot": 4 * (4 * 128 * 4 + 3 * 128 * 2),
        "ring_bytes_per_slot": 3 * 8 * 2 * 4 * 8 * 2,
        "plane_bytes_per_position": 2 * 4 * 8 * 2,
        "ssm_row_steps": 4 * 2 * 4, "ring_row_steps": 4 * 2 * 3,
        # both rows are empty slots at positions 0 .. 3: none wrapped
        "ring_rows_wrapped": 0, "shared_plane_reads": 4 * 2 * 3,
        # ... and inside the plane's first block of 8
        "plane_positions_read": 4 * 2 * 3 * BLOCK,
        "prefill_positions_self": 0, "prefill_positions_cross": 0,
    }
    assert program.state_stats()["ssm_row_steps"] == 4 * 2 * 4


def test_the_published_pattern_reads_the_plane_eight_times_a_row_step():
    with open(REAL_FILE) as fh:
        cfg = dh.from_published(json.load(fh), 3072)
    assert cfg.plane_readers == 8 and cfg.count("mamba") == 9
    described = dh.describe_hybrid_decoder(cfg, np.asarray(
        [64 * 9, 64 * 8, 64, 64 * cfg.plane_readers, 64 * 8 * 1536, 1536, 1]))
    assert described["shared_plane_reads"] == 8 * 64
    assert described["plane_positions_read"] == 8 * 64 * 1536
    assert described["prefill_positions_self"] == 1536
    assert described["state_bytes_per_slot"] == 3_225_600
    assert described["ring_bytes_per_slot"] == 20_971_520
    assert described["plane_bytes_per_position"] == 5_120
    assert (cfg.d_inner, cfg.ssm_dt_rank, cfg.ssm_state, cfg.pair_dim) == (
        5120, 160, 16, 128)


def test_a_model_of_another_family_publishes_none():
    from containerpilot_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=1,
                            d_ff=128, max_seq_len=32)
    program = make_step_program(
        cfg, init_params(jax.random.PRNGKey(0), cfg), 32, slots=2, chunk=2)
    assert program.hybrid_decoder_stats() is None


# -- weights, the file, the CLI, the refusals ------------------------------------


def test_weights_are_held_in_bfloat16_and_follow_the_stated_recipe():
    """Matrices and biases bf16, the recurrence's and the difference's
    vectors float32 in their recipes' ranges, the embedding seeded
    small; the reference makes the same values from the same keys."""
    cfg = dh.from_published(TOY, MAX_LEN)
    params = dh.init_params(None, cfg)
    mamba, window = params["layers"][0], params["layers"][1]
    for name in ("w_in", "w_x", "w_dt", "w_out", "conv_w", "conv_b", "w_gate"):
        assert mamba[name].dtype == jnp.bfloat16, name
    for name in ("a_log", "dt_bias", "d_skip"):
        assert mamba[name].dtype == jnp.float32, name
    assert np.allclose(np.exp(np.asarray(mamba["a_log"]))[:, 0], [1, 2, 3, 4])
    step = np.log1p(np.exp(np.asarray(mamba["dt_bias"])))
    assert 0.001 <= step.min() and step.max() <= 0.1
    assert window["lambdas"].dtype == jnp.float32
    assert float(jnp.abs(params["embed"].astype(jnp.float32)).max()) < 0.006
    theirs = R.layer_weights(TOY, 1)
    for name in ("w_qkv", "b_qkv", "w_o", "lambdas", "w_gate"):
        assert np.array_equal(np.asarray(window[name].astype(jnp.float32)),
                              np.asarray(theirs[name])), name
    theirs = R.layer_weights(TOY, 0)
    assert np.array_equal(np.asarray(mamba["dt_bias"]), np.asarray(theirs["dt_bias"]))
    assert np.array_equal(np.asarray(mamba["a_log"]).T, np.asarray(theirs["a_log"]))
    assert np.array_equal(np.asarray(params["embed"].astype(jnp.float32)),
                          np.asarray(R.embedding(TOY)))


def test_the_benchmark_files_widths_are_the_catalog_rows():
    """Every key of the catalog row stands in the benchmark's file
    with the row's value, nothing is reduced, and what the row lacks is
    under ``assumed``."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    with open(REAL_FILE) as fh:
        real = json.load(fh)
    assert {k: real[k] for k in row["config"]} == row["config"]
    assert real["source"] == row["source_url"] and real["reduced"] == {}
    for key in ("mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank",
                "layer_kinds", "differential_attention", "weight_recipe",
                "precision"):
        assert key in real["assumed"], key
    cfg = modelcfg.load_model_file(REAL_FILE, 3072)
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
            cfg.attn_window, cfg.vocab_size, cfg.head_dim) == (
        2560, 32, 40, 20, 10240, 512, 200064, 64)


def test_serve_cli_builds_the_model_from_a_file():
    from containerpilot_tpu.workload import serve_cli

    args = serve_cli.build_arg_parser().parse_args(
        ["--model-config", TOY_FILE, "--max-len", "128"])
    cfg, params, _mesh = serve_cli.load_model(args)
    assert isinstance(cfg, dh.DecoderHybridConfig)
    assert (cfg.n_layers, cfg.attn_window, cfg.max_seq_len) == (12, 8, 128)
    assert cfg.window == 0 and cfg.recurrent_state
    assert params["layers"][0]["w_in"].dtype == jnp.bfloat16
    assert isinstance(make_step_program(cfg, params, 64, 2, 2), PlainStepProgram)


@pytest.mark.parametrize("flags", [["--int8"], ["--kv-int8"], ["--window", "8"],
                                   ["--draft-layers", "1"]])
def test_serve_cli_refuses_what_only_the_flagship_block_has(flags):
    from containerpilot_tpu.workload import serve_cli

    args = serve_cli.build_arg_parser().parse_args(
        ["--model-config", TOY_FILE, *flags])
    with pytest.raises(SystemExit, match="does not compose"):
        serve_cli.load_model(args)


@pytest.mark.parametrize("flag, options", [
    ("--prefix-cache", {"prefix_cache_entries": 2}),
    ("--kv-spill-mb", {"kv_spill_bytes": 1 << 20}),
    ("--prefill-chunk", {"prefill_chunk": 16}),
])
def test_the_server_refuses_what_the_cache_cannot_do_by_name(flag, options):
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = dh.from_published(TOY, MAX_LEN)
    with pytest.raises(ValueError, match=f"{flag} does not compose"):
        InferenceServer(cfg, {}, "127.0.0.1", 0, MAX_LEN, slots=2, **options)


@pytest.mark.parametrize("key, value, match", [
    ("tie_word_embeddings", False, "tie_word_embeddings"),
    ("mb_per_layer", 4, "mb_per_layer"),
    ("num_hidden_layers", 10, "multiple of 4"),
    ("num_key_value_heads", 1, "pairs the heads"),
])
def test_a_file_this_family_cannot_run_is_refused_by_name(tmp_path, key, value,
                                                          match):
    path = tmp_path / "model.json"
    path.write_text(json.dumps(dict(TOY, **{key: value})))
    with pytest.raises(SystemExit, match=match):
        modelcfg.load_model_file(str(path), 64)


def test_the_model_file_is_told_by_its_model_type(tmp_path):
    cfg = modelcfg.load_model_file(TOY_FILE, 128)
    assert isinstance(cfg, dh.DecoderHybridConfig) and cfg.source_digest
    assert (cfg.ssm_state, cfg.ssm_dt_rank, cfg.d_inner) == (4, 4, 128)
    other = tmp_path / "other.json"
    other.write_text(json.dumps(dict(TOY, model_type="phi5flash")))
    with pytest.raises(SystemExit, match="'phi5flash' has no builder"):
        modelcfg.load_model_file(str(other), 128)


def test_beams_are_refused_and_sampling_is_not():
    dh.refuse_request({"temperature": 0.7, "top_k": 5, "beam_width": 0})
    with pytest.raises(ValueError, match="beam_width"):
        dh.refuse_request({"beam_width": 2})


def test_the_server_publishes_the_block_and_refuses_beams(run):
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = dh.from_published(TOY, MAX_LEN)
    params = dh.init_params(None, cfg)

    def call(port, path, body=None):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}",
            data=json.dumps(body).encode() if body is not None else None,
            headers={"Content-Type": "application/json"} if body else {})
        with urllib.request.urlopen(req, timeout=120) as resp:
            return json.loads(resp.read())

    async def drive():
        server = InferenceServer(cfg, params, "127.0.0.1", 0, max_len=MAX_LEN,
                                 slots=2, slot_chunk=4)
        await server.run()
        loop = asyncio.get_event_loop()
        try:
            out = await loop.run_in_executor(
                None, call, server.port, "/v1/generate",
                {"tokens": [[5, 9, 2, 40, 7, 3, 8, 11, 60, 61]],
                 "max_new_tokens": 6})
            model = await loop.run_in_executor(
                None, call, server.port, "/v1/model")
            with pytest.raises(urllib.error.HTTPError):
                await loop.run_in_executor(
                    None, call, server.port, "/v1/generate",
                    {"tokens": [[5, 9]], "max_new_tokens": 4, "beam_width": 2})
            return out, model
        finally:
            await server.stop()

    out, model = run(drive(), timeout=300)
    assert len(out["tokens"][0]) == 6
    block = model["hybrid_decoder"]
    assert block["plane_readers"] == 3 and block["window"] == 8
    assert block["shared_plane_reads"] == 3 * block["ssm_row_steps"] // 4 > 0
    assert block["ring_rows_wrapped"] > 0
    # the request's prompt of 10, and a boot's warm-up admission of 4
    assert block["prefill_positions_cross"] == 2
    assert block["prefill_positions_self"] == 10 + 4
    assert model["state"]["ssm_row_steps"] == block["ssm_row_steps"]
    assert model["loop"] is None and model["experts"] is None


# -- the check: the sound program passes, the controls do not --------------------


def _served(prog, prompts, new):
    cases = []
    for i, prompt in enumerate(prompts):
        cases.append({"index": i, "prompt": [int(t) for t in prompt],
                      "tokens": _solo(prog, [int(t) for t in prompt], new)})
    return cases


def test_the_check_passes_the_program_and_fails_the_controls(prog):
    """``check_served`` (every layer at every position, no cache) over
    what the float32 program served through the trimmed prefill, the
    rings and one-token steps: gaps of float32 rounding. Each control
    proves that it took place (logits moved, tokens changed) and reads
    over limits set between the two; one that moves nothing is an
    error."""
    limits = {"max_logit_gap": 1e-6, "mean_logit_gap": 1e-8}
    cases = _served(prog, [ids(n, seed=s) for n, s in ((5, 1), (13, 2))], 16)
    assert len(set(cases[0]["tokens"])) > 4  # no token repeated for ever
    modes = ["no-window", "no-difference", "stale-memory", "int8-weights",
             "bf16-state"]
    result = R.check_served(TOY, {
        "cases": cases, "max_len": MAX_LEN, "controls": modes})
    assert result["positions"] == 32
    assert result["max_logit_gap"] <= limits["max_logit_gap"]
    assert result["mean_logit_gap"] <= limits["mean_logit_gap"]
    for mode in modes[:-1]:
        control = result["controls"][mode]
        assert control["logits_moved_max"] > 0, mode
        assert (control["max_logit_gap"] > limits["max_logit_gap"]
                or control["mean_logit_gap"] > limits["mean_logit_gap"]), mode
        assert control["tokens_changed"] > 0, mode
    # a state held in bfloat16 moves logits by 2e-3 of the largest and,
    # over 32 positions, no token: the check on TOKENS cannot see it
    # (granite's finding, PERF.md PR 37); the comparison on logits above
    # (test_the_tolerance_fails_a_lower_precision) does
    assert result["controls"]["bf16-state"]["logits_moved_max"] > 0
    with pytest.raises(ValueError, match="control 'bf16'"):
        R.check_served(TOY, {"cases": cases[:1], "max_len": MAX_LEN,
                             "controls": ["bf16"]})


def test_a_control_that_changes_nothing_is_an_error_not_a_zero():
    """A window wider than every row makes ``no-window`` the model
    itself: the control raises instead of reading 0."""
    wide = dict(TOY, sliding_window=64)
    cases = [{"index": 0, "prompt": [int(t) for t in ids(12, seed=150)],
              "tokens": [1, 2, 3]}]
    with pytest.raises(RuntimeError, match="did not take place"):
        R.check_served(wide, {"cases": cases, "max_len": MAX_LEN,
                              "controls": ["no-window"]})


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "phi4flash_reference.py")) as fh:
        source = fh.read()
    assert "import containerpilot_tpu" not in source
    assert "from containerpilot_tpu" not in source
    assert '"highest"' in source and "reduce_precision" in source
