"""The hybrid state-space family (models/hybrid_ssm.py: Mamba-2 mixers
among NoPE attention mixers in a declared pattern, routed experts with
a shared expert, four multipliers) against the benchmark's plain
reference (benchmark/configs/granite_reference.py) at toy size on the
CPU: hidden 64, 8 state-space heads of 16 with a state of 16, chunks
of 8, 4 attention heads, 8 experts top-2 of which 4 are held, layers
``m a m``, seeded weights.

Comparisons are on LOGITS. The program holds bfloat16 weights; the
tests widen the SAME values to float32 and compute in float32
(``highest``), so that what is compared is the mathematics (the chunked
scan against the step-by-step recurrence, sorted dispatch against
masked-dense experts, a pool of state and keys against none), not bf16
rounding. The logits are small (the head is the embedding, seeded at
0.001, and they are divided by 16: within 0.003 of zero), so agreement
is asked relative to the largest logit, to 1e-4 of it. The bf16 path's
own distance from the reference is what the benchmark's ``correct``
measures on the chip. Every program is jitted and kept for the tests
that share its configuration: the file stays under a minute.
"""
import dataclasses
import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from containerpilot_tpu.models import hybrid_ssm as hs
from containerpilot_tpu.models import moe
from containerpilot_tpu.models import slots as slots_mod
from containerpilot_tpu.models.decode import _jitted_prefill, chunked_prefill
from containerpilot_tpu.models.stepprog import PlainStepProgram, make_step_program
from containerpilot_tpu.workload import modelcfg

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY_FILE = os.path.join(ROOT, "benchmark", "tests", "toy", "toy-granite.json")
REAL_FILE = os.path.join(ROOT, "benchmark", "configs",
                         "granite-4-h-small-serve.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REL = 1e-4
MAX_LEN = 64
#: every sequence the programs see whole has this length, and the
#: reference is given rows padded to it (it is causal: what follows a
#: position does not reach it), so each is compiled once
SEQ = 29


def _reference():
    spec = importlib.util.spec_from_file_location(
        "granite_reference",
        os.path.join(ROOT, "benchmark", "configs", "granite_reference.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


R = _reference()

with open(TOY_FILE) as _fh:
    # the toy file's model less one of its leading mamba layers
    # (``m a m``): a quarter less to compile in every program here
    TOY = {k: v for k, v in json.load(_fh).items()
           if k not in ("launch", "check", "check_note", "reference")}
    TOY.update(layer_types=TOY["layer_types"][1:], num_hidden_layers=3)


def widened(config, max_len=MAX_LEN):
    """(float32 configuration, the bf16-held weights widened)."""
    cfg = hs.from_published(config, max_len)
    params = hs.init_params(None, cfg)
    return (dataclasses.replace(cfg, dtype=jnp.float32),
            jax.tree.map(lambda x: x.astype(jnp.float32), params))


class Programs:
    """A configuration's float32 programs, jitted once."""

    def __init__(self, config):
        self.cfg, self.params = widened(config)
        cfg = self.cfg
        self.forward = jax.jit(lambda p, t: hs.forward(p, t, cfg))
        self.prefill = _jitted_prefill(cfg, MAX_LEN)
        self.step = jax.jit(lambda p, c, t: hs.decode_chunk(p, c, t, cfg))
        self._reference = jax.jit(lambda t: R.all_logits(config, t))

    def logits(self, toks):
        return np.asarray(self.forward(self.params, jnp.asarray(toks)[None]))[0]

    def reference(self, toks):
        row = np.zeros((max(SEQ, len(toks)),), np.int32)
        row[: len(toks)] = toks
        return np.asarray(self._reference(row))[: len(toks)]


@functools.lru_cache(maxsize=None)
def _programs(key):
    with jax.default_matmul_precision("highest"):
        return Programs(json.loads(key))


def programs(config=None) -> Programs:
    return _programs(json.dumps(TOY if config is None else config,
                                sort_keys=True))


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


# -- forward, the scan, the pool -----------------------------------------


def test_full_forward_matches_the_reference():
    """Three chunks of the scan and a tail."""
    prog = programs()
    toks = ids(SEQ, seed=SEQ)
    ref = prog.reference(toks)
    assert np.abs(ref).max() > 1e-3
    assert close(prog.logits(toks), ref)


@pytest.mark.parametrize("length", [5, 16, 21])
def test_chunked_scan_is_the_step_by_step_recurrence(length):
    """``_ssm_seq`` from a state that is not zero, over a length that
    is under a chunk, two chunks, and no multiple of the chunk (8),
    against ``_ssm_step`` taken ``length`` times."""
    prog = programs()
    cfg, lp = prog.cfg, prog.params["layers"][0]
    keys = jax.random.split(jax.random.PRNGKey(length), 3)
    rows = 2
    act = jax.random.normal(keys[0], (rows, length, cfg.conv_dim))
    dt_raw = jax.random.normal(keys[1], (rows, length, cfg.ssm_heads)) - 2.0
    state = jax.random.normal(
        keys[2], (rows, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state))
    y, last = jax.jit(lambda a, d, s: hs._ssm_seq(a, d, s, lp, cfg))(
        act, dt_raw, state)
    step = jax.jit(lambda a, d, s: hs._ssm_step(a, d, s, lp, cfg))
    steps = []
    for t in range(length):
        y_t, state = step(act[:, t], dt_raw[:, t], state)
        steps.append(y_t)
    assert np.abs(np.asarray(y)).max() > 0.5
    assert close(y, np.stack(steps, axis=1), 1e-5)
    assert close(last, state, 1e-5)


def test_prefill_then_decode_matches_the_full_forward():
    """Prefill hands over each mamba layer's final state and last
    three inputs and the attention layer's keys and values; every later
    position is computed from those alone, one at a time (the
    recurrence)."""
    prog = programs()
    toks = ids(SEQ, seed=3)
    ref = prog.reference(toks)
    logits, cache = prog.prefill(prog.params, jnp.asarray(toks[:13])[None])
    assert close(np.asarray(logits)[0], ref[12])
    assert cache["ssm"][0].dtype == jnp.float32
    assert cache["conv"][0].shape == (1, 3, prog.cfg.conv_dim)
    assert [len(cache[name]) for name in ("ssm", "conv", "k", "v")] == [2, 2, 1, 1]
    for start in range(13, SEQ):
        logits, cache = prog.step(
            prog.params, cache, jnp.asarray(toks[start:start + 1])[None])
        assert close(np.asarray(logits)[0, 0], ref[start])
    assert int(cache["pos"]) == SEQ


def test_prefill_in_pieces_matches_the_prefill():
    """``--prefill-chunk``: the prompt through ``decode_chunk`` in
    pieces of 13 and 16 from an empty cache, the chunked scan from a
    state: the last logits are the reference's, and the state is the
    one that single steps leave."""
    prog = programs()
    toks = ids(SEQ, seed=3)
    logits, row = chunked_prefill(
        prog.params, jnp.asarray(toks)[None], prog.cfg, MAX_LEN, chunk_len=16)
    assert close(np.asarray(logits)[0], prog.reference(toks)[-1])
    _logits, cache = prog.prefill(prog.params, jnp.asarray(toks[:13])[None])
    for start in range(13, SEQ):
        _logits, cache = prog.step(
            prog.params, cache, jnp.asarray(toks[start:start + 1])[None])
    for mine, theirs in zip(row["ssm"], cache["ssm"]):
        assert close(mine, theirs, 1e-5)


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


def _served_is_the_references_best(prog, prompt, served):
    """The served tokens' logit gaps under the reference, as the
    benchmark's ``correct`` judges them: none."""
    row = np.concatenate([prompt, served])[:-1]
    ref = prog.reference(row)
    at = np.arange(len(prompt) - 1, len(row))
    gaps = ref[at].max(axis=-1) - ref[at, served]
    return float(gaps.max()) < REL * float(np.abs(ref).max())


@pytest.mark.parametrize("program", ["chunk", "window"])
def test_pool_programs_match_the_reference(program):
    """Two prompts of different lengths prefilled, inserted into a pool
    of three slots (one stays empty and steps on pads), decoded
    greedily by the chunk program (two dispatches) or the fused
    window (one): rows at different positions, a state and a tail each,
    and every emitted token is the reference's best at its position."""
    prog = programs()
    cfg, rounds = prog.cfg, 2
    pool = slots_mod.slot_cache(cfg, SLOTS, MAX_LEN)
    state = slots_mod.init_slot_state(cfg, SLOTS)
    prompts = [ids(n, seed=n) for n in (13, 20)]
    firsts = []
    for slot, prompt in enumerate(prompts):
        pool, state, first = _admit(prog, pool, state, slot, prompt)
        firsts.append(first)
    if program == "chunk":
        pieces = []
        for _ in range(rounds):
            pool, state, toks, stats = slots_mod.decode_slots_chunk(
                prog.params, pool, state, cfg, CHUNK, with_stats=True)
            pieces.append(np.asarray(toks))
        toks = np.concatenate(pieces, axis=1)
    else:
        pool, state, toks, run, stats = slots_mod.decode_slots_window(
            prog.params, pool, state, cfg, CHUNK, rounds,
            np.full((SLOTS,), 100), with_stats=True)
        assert int(run) == rounds
        toks = np.asarray(toks)
    # the last program call took chunk (or rounds x chunk) steps of 3
    # rows through 3 expert layers and 2 mamba layers
    steps = CHUNK if program == "chunk" else CHUNK * rounds
    stats = np.asarray(stats)
    assert int(stats[0]) == steps * SLOTS * cfg.n_layers
    head = len(hs.STATS_HEAD)
    assert int(stats[1]) == int(stats[head:-1].sum()) > 0
    assert int(stats[-1]) == steps * SLOTS * cfg.n_mamba
    tile = moe.expert_block(SLOTS, cfg.experts_per_tok, cfg.router_experts)
    assert int(stats[2]) <= int(stats[4])
    assert int(stats[1]) <= int(stats[5]) == int(stats[4]) * tile
    for slot, prompt in enumerate(prompts):
        served = np.asarray([firsts[slot]] + [int(t) for t in toks[slot]])
        assert _served_is_the_references_best(prog, prompt, served), slot
    assert list(np.asarray(pool["pos"])[:2]) == [
        len(p) + CHUNK * rounds for p in prompts]


def test_a_row_inserted_over_a_retired_one_keeps_nothing_of_it():
    """A slot decodes, is retired (it steps on, on pads: its state
    keeps moving), and takes a new, shorter prompt: the new row's
    tokens are the reference's, so neither the old state, the old
    convolution inputs nor the old keys reach it."""
    prog = programs()
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
    prompt = ids(13, seed=2)
    pool, state, first = _admit(prog, pool, state, 1, prompt)
    assert int(pool["pos"][1]) == 13
    pool, state, toks = decode(pool, state)
    served = np.asarray([first] + [int(t) for t in np.asarray(toks)[1]])
    assert _served_is_the_references_best(prog, prompt, served)


# -- the experts, the attention, the multipliers, the pattern ----------------


def test_softmax_over_the_chosen_is_route_softmax():
    """The published gate (the top scores, then a softmax over those)
    is ``moe.route_softmax`` (a softmax over all, the chosen
    renormalised)."""
    keys = jax.random.split(jax.random.PRNGKey(4), 2)
    h = jax.random.normal(keys[0], (40, 64))
    router = jax.random.normal(keys[1], (64, 8)) * 0.5
    idx, gates, _edge = R.route(h, router, TOY)
    mine_idx, mine_gates = moe.route_softmax(h, router, 2)
    assert np.array_equal(np.asarray(idx), np.asarray(mine_idx))
    assert np.abs(np.asarray(gates) - np.asarray(mine_gates)).max() < 1e-6
    assert np.allclose(np.asarray(mine_gates).sum(-1), 1.0, atol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer():
    """The share tied to the model: what the program's experts give for
    shares [0, 4) and [4, 8), plus the shared expert ONCE, is what the
    reference's expert layer gives with all 8 experts held."""
    layer = 2
    u = jax.random.normal(jax.random.PRNGKey(layer), (24, 64))
    whole = R.layer_weights(TOY, layer, experts=(0, 8))
    want, _edge = jax.jit(lambda u: R.expert_layer(u, whole, TOY, lo=0))(u)
    total = jnp.zeros_like(u)
    for lo in (0, 4):
        cfg = hs.from_published(dict(TOY, share={
            "router_experts": 8, "held_experts": [lo, lo + 4]}), MAX_LEN)
        lp = jax.tree.map(lambda x: x.astype(jnp.float32),
                          hs._layer_leaves(cfg, layer))

        @jax.jit
        def routed(u, lp):
            idx, gate = moe.route_softmax(u, lp["router"], cfg.experts_per_tok)
            return moe.sparse_experts(
                u, idx, gate, lp["e_gate"], lp["e_up"], lp["e_down"],
                cfg.held_lo, cfg.router_experts)

        part, counts = routed(u, lp)
        assert int(counts.sum()) > 0
        total = total + part
    total = total + hs._swiglu(u, lp["s_gate"], lp["s_up"], lp["s_down"],
                               jnp.float32)
    assert np.abs(np.asarray(want)).max() > 0.1
    assert close(total, want, 1e-5)


@pytest.mark.parametrize("scale", [0.0625, 0.3])
def test_attention_has_no_positions_and_the_published_scale(scale):
    """The attention mixer against the reference's at the published
    multiplier and at one that is no power of the head size; and NoPE:
    the LAST position's output is the same when the earlier positions
    are shuffled (a rotation by position would change it)."""
    config = dict(TOY, attention_multiplier=scale)
    cfg = hs.from_published(config, MAX_LEN)
    lp = jax.tree.map(lambda x: x.astype(jnp.float32), hs._layer_leaves(cfg, 1))
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 12, 64))

    def mixer(x):
        q, k, v = hs._qkv(x, lp, cfg)
        o = hs._causal_attention(q, k, v, cfg)
        return np.asarray(jnp.einsum("bmhk,hkd->bmd", o, lp["wo"]))

    h = R._rms(x, 1e-5)
    ref = np.asarray(R.attention_mixer(h, R.layer_weights(config, 1), config))
    assert close(mixer(x), ref, 1e-5)
    order = np.concatenate([np.random.default_rng(0).permutation(11), [11]])
    assert close(mixer(x[:, order])[0, -1], mixer(x)[0, -1], 1e-5)
    other = dict(TOY, attention_multiplier=2 * scale)
    assert not close(
        R.attention_mixer(h, R.layer_weights(other, 1), other), ref, 1e-3)


MULTIPLIED = dict(TOY, embedding_multiplier=3, residual_multiplier=0.5,
                  logits_scaling=4, attention_multiplier=0.2)


@pytest.mark.parametrize("what", [
    "embedding_multiplier", "residual_multiplier", "logits_scaling", "all four"])
def test_each_multiplier_is_applied_as_published(what):
    """Each multiplier where it enters, and the whole forward with all
    four changed against the reference with the same four."""
    prog = programs(MULTIPLIED)
    cfg, params = prog.cfg, prog.params
    toks = ids(SEQ, seed=11)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 20, 64))
    if what == "embedding_multiplier":
        assert close(hs._embed(params, jnp.asarray(toks)[None], cfg)[0],
                     3 * np.asarray(params["embed"])[toks], 1e-6)
    elif what == "residual_multiplier":
        assert close(hs._residual(x, 2 * x, cfg), 2 * x, 1e-6)
    elif what == "logits_scaling":
        want = R._rms(x, 1e-5) @ params["embed"].T / 4
        assert close(hs._logits(params, x, cfg), want, 1e-5)
    else:
        assert close(prog.logits(toks), prog.reference(toks))
        assert not close(prog.logits(toks), programs().reference(toks), 1e-2)


@pytest.mark.parametrize("pattern", [
    ["attention", "mamba", "attention"], ["mamba"], ["attention"]])
def test_a_pattern_of_another_order(pattern):
    """The kind of a layer is read from ``layer_types``: another order,
    only state-space layers, only attention layers. Prefill, then
    three steps from its cache."""
    prog = programs(dict(TOY, layer_types=pattern,
                         num_hidden_layers=len(pattern)))
    toks = ids(16, seed=13)
    ref = prog.reference(toks)
    logits, cache = prog.prefill(prog.params, jnp.asarray(toks[:13])[None])
    assert close(np.asarray(logits)[0], ref[12])
    assert len(cache["ssm"]) == pattern.count("mamba")
    assert len(cache["k"]) == pattern.count("attention")
    for start in range(13, 16):
        logits, cache = prog.step(
            prog.params, cache, jnp.asarray(toks[start:start + 1])[None])
        assert close(np.asarray(logits)[0, 0], ref[start])


def test_weights_are_held_in_bfloat16_and_follow_the_stated_recipe():
    cfg = hs.from_published(TOY, MAX_LEN)
    params = hs.init_params(None, cfg)
    matrices = [x for x in jax.tree.leaves(params) if x.ndim >= 2]
    assert matrices and all(x.dtype == jnp.bfloat16 for x in matrices)
    for layer, names in ((0, ("w_in", "conv_w", "conv_b", "w_out", "a_log",
                              "dt_bias", "router", "s_down", "e_up")),
                         (1, ("wq", "wk", "wo", "e_down"))):
        ref = R.layer_weights(TOY, layer)
        for name in names:
            assert np.array_equal(
                np.asarray(params["layers"][layer][name].astype(jnp.float32)),
                np.asarray(ref[name])), name
    assert "wq" not in params["layers"][0] and "w_in" not in params["layers"][1]
    assert np.array_equal(np.asarray(params["embed"].astype(jnp.float32)),
                          np.asarray(R.embedding(TOY)))
    rate = np.exp(np.asarray(params["layers"][0]["a_log"]))
    assert rate.min() >= 1.0 and rate.max() <= 16.0
    step = np.log1p(np.exp(np.asarray(params["layers"][0]["dt_bias"])))
    assert step.min() >= 0.001 - 1e-6 and step.max() <= 0.1 + 1e-6


# -- the benchmark's file, the CLI, the refusals -----------------------------


def test_the_benchmark_files_widths_are_the_catalog_rows():
    """Every number of the catalog row's ``config`` stands in the
    benchmark's file under the same key, but the three in ``reduced``;
    ``layer_types`` is the row's first period."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "granite-4.0-h-small")
    with open(REAL_FILE) as fh:
        config = json.load(fh)
    assert sorted(config["reduced"]) == [
        "num_hidden_layers", "num_local_experts", "vocab_size"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            continue
        if key == "layer_types":
            assert config[key] == value[:10]
            assert config[key].count("attention") == 1 and config[key][5] == "attention"
        else:
            assert config[key] == value, key
    assert config["source"] == row["source_url"]
    assert config["published"]["num_local_experts"] == row["config"]["num_local_experts"]
    cfg = modelcfg.load_model_file(REAL_FILE, 3072)
    assert isinstance(cfg, hs.HybridSsmConfig)
    assert (cfg.d_model, cfg.d_inner, cfg.conv_dim, cfg.ssm_state,
            cfg.ssm_chunk) == (4096, 8192, 8448, 128, 256)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.attention_multiplier) == (32, 8, 128, 1 / 128)
    assert (cfg.router_experts, cfg.held_lo, cfg.held_n, cfg.experts_per_tok,
            cfg.moe_d_ff, cfg.shared_d_ff) == (72, 0, 36, 10, 768, 1536)
    assert (cfg.n_mamba, cfg.n_attention, cfg.vocab_size) == (9, 1, 50176)
    assert "2-chip" in config["deployment"]


def test_serve_cli_builds_the_model_from_a_file():
    from containerpilot_tpu.workload import serve_cli

    args = serve_cli.build_arg_parser().parse_args(
        ["--model-config", TOY_FILE, "--max-len", "128"])
    cfg, params, _mesh = serve_cli.load_model(args)
    assert isinstance(cfg, hs.HybridSsmConfig)
    assert (cfg.layer_types, cfg.max_seq_len) == (
        ("mamba", "mamba", "attention", "mamba"), 128)
    assert params["layers"][1]["e_gate"].shape == (4, 64, 32)
    assert params["layers"][1]["w_in"].dtype == jnp.bfloat16
    assert isinstance(make_step_program(cfg, params, 64, 2, 2), PlainStepProgram)
    assert PlainStepProgram.supports_lookahead


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
    ("--kv-spill-mb", {"prefix_cache_entries": 2, "kv_spill_bytes": 1 << 20}),
])
def test_the_server_refuses_reuse_of_recurrent_state_by_name(flag, options):
    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = hs.from_published(TOY, MAX_LEN)
    names = "--prefix-cache|--kv-spill-mb" if len(options) > 1 else flag
    with pytest.raises(ValueError, match=f"({names}) does not compose.*rewound"):
        InferenceServer(cfg, {}, "127.0.0.1", 0, MAX_LEN, slots=2, **options)


@pytest.mark.parametrize("key, value, match", [
    ("position_embedding_type", "rope", "position_embedding_type"),
    ("mamba_n_groups", 8, "mamba_n_groups"),
    ("layer_types", ["mamba", "linear", "mamba"], "linear"),
    ("layer_types", ["mamba"], "names 1 layers"),
    ("mamba_d_head", 8, "mamba_expand"),
])
def test_a_file_this_family_cannot_run_is_refused_by_name(tmp_path, key, value,
                                                          match):
    path = tmp_path / "model.json"
    path.write_text(json.dumps(dict(TOY, **{key: value})))
    with pytest.raises(SystemExit, match=match):
        modelcfg.load_model_file(str(path), 64)


def test_beams_are_refused_and_sampling_is_not():
    hs.refuse_request({"temperature": 0.7, "top_k": 5, "beam_width": 0})
    with pytest.raises(ValueError, match="beam_width"):
        hs.refuse_request({"beam_width": 2})


# -- the step program's counters, /v1/model state ----------------------------


def test_step_program_returns_both_counters_with_the_tokens():
    cfg = hs.from_published(dict(TOY, share={
        "router_experts": 8, "held_experts": [4, 8]}), MAX_LEN)
    params = hs.init_params(None, cfg)
    program = make_step_program(cfg, params, MAX_LEN, slots=2, chunk=4, rounds=2)
    assert program.expert_stats()["rows"] == 0
    assert program.state_stats()["ssm_row_steps"] == 0
    toks, valid, rounds_run = program.tokens(
        program.dispatch(np.asarray([100, 100]), False))
    experts, state = program.expert_stats(), program.state_stats()
    assert experts["rows"] == 4 * 2 * cfg.n_layers
    assert experts["published"] == 8 and experts["held"] == [4, 8]
    assert experts["assignments_here"] == sum(experts["load"])
    assert state == {
        "layer_kinds": {"mamba": 2, "attention": 1},
        "state_bytes_per_slot": 2 * (8 * 16 * 16 * 4 + 3 * 160 * 2),
        "kv_bytes_per_position": 2 * 2 * 16 * 2,
        "ssm_row_steps": 4 * 2 * 2,
    }


def test_a_model_without_recurrent_state_publishes_none():
    from containerpilot_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=1,
                            d_ff=128, max_seq_len=32)
    program = make_step_program(
        cfg, init_params(jax.random.PRNGKey(0), cfg), 32, slots=2, chunk=2)
    assert program.state_stats() is None


# -- the check: the sound program passes, a lower precision does not ---------


def test_the_check_passes_the_program_and_fails_a_bf16_state():
    """Limits between the two readings, over what the sound program
    (float32 here) served greedily for 200 tokens a row: it lies under
    both; the reference read with ``S`` rounded to bfloat16 after every
    step lies over at least one, because some served token is then no
    longer the best."""
    limits = {"max_logit_gap": 1e-6, "mean_logit_gap": 1e-9}
    with open(TOY_FILE) as fh:
        config = json.load(fh)  # all four layers: three of them keep a state
    cfg, params = widened(config, 256)
    prefill = jax.jit(lambda p, t: hs.prefill(p, t, cfg, 256))
    step = jax.jit(lambda p, c, t: hs.decode_chunk(p, c, t, cfg))
    cases = []
    for index in range(3):
        prompt = [int(t) for t in ids(40, seed=140 + index)]
        logits, cache = prefill(params, jnp.asarray([prompt], jnp.int32))
        served = [int(np.argmax(np.asarray(logits)[0]))]
        for _ in range(199):
            logits, cache = step(
                params, cache, jnp.asarray([[served[-1]]], jnp.int32))
            served.append(int(np.argmax(np.asarray(logits)[0, 0])))
        assert len(set(served)) > 50  # no token repeated for ever
        cases.append({"index": index, "prompt": prompt, "tokens": served})
    result = R.check_served(config, {
        "cases": cases, "max_len": 256, "controls": ["bf16-state"]})
    assert result["positions"] == 3 * 200
    assert result["max_logit_gap"] <= limits["max_logit_gap"]
    assert result["mean_logit_gap"] <= limits["mean_logit_gap"]
    control = result["controls"]["bf16-state"]
    assert (control["max_logit_gap"] > limits["max_logit_gap"]
            or control["mean_logit_gap"] > limits["mean_logit_gap"]), control
    # the proof that the rounding took place: logits moved, tokens changed
    assert control["logits_moved_max"] > 0 and control["tokens_changed"] > 0
    with pytest.raises(ValueError, match="one of"):
        R.check_served(config, {"cases": cases[:1], "max_len": 256,
                                "controls": ["fp8"]})


def test_a_control_that_rounds_nothing_is_an_error_not_a_zero(monkeypatch):
    """What the chip made of ``astype(bfloat16).astype(float32)`` in the
    scan's body (the compiler dropped the round trip, the control read
    0 / 0 and looked like a measurement): a control whose logits ARE
    the reference's raises."""
    prompt = [int(t) for t in ids(12, seed=150)]
    cases = [{"index": 0, "prompt": prompt, "tokens": [1, 2, 3]}]
    monkeypatch.setattr(R, "_bf16_values", lambda x: x)
    with pytest.raises(RuntimeError, match="did not take place"):
        R.check_served(TOY, {"cases": cases, "max_len": 64,
                             "controls": ["bf16-state"]})


def test_rounding_to_bfloat16_values_is_the_cast_and_back():
    """``reduce_precision(x, 8, 7)`` gives, bit for bit, what a cast to
    bfloat16 and back gives: ties to even, zeros, both signs, the
    largest and the smallest normal numbers."""
    bits = np.concatenate([
        np.random.default_rng(0).integers(0, 2 ** 32, 20000, np.uint64),
        [0x3F808000, 0x3F818000, 0x3F80FFFF, 0xBF808000, 0, 0x80000000,
         0x7F7F0000, 0x00800000, 0x3F807FFF, 0x3F808001],
    ]).astype(np.uint32)
    x = bits.view(np.float32)
    x = x[np.isfinite(x) & ((np.abs(x) >= np.finfo(np.float32).tiny) | (x == 0))]
    got = np.asarray(jax.jit(R._bf16_values)(jnp.asarray(x)))
    want = np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
