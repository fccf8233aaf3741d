"""The looped family (models/looped.py: a stack of layers run several
times a token with the same weights, a norm before and after every
block, one plane of keys and values per pass and layer) at toy size on
the CPU: hidden 64, 4 heads of 16, SwiGLU width 160, 3 layers run 4
times, seeded weights.

Comparisons are on LOGITS, in float32: the program holds bfloat16
weights; the tests widen the SAME values to float32 and compute in
float32 (``highest``), so that what is compared is the mathematics (the
pass loop, the planes, prefill against one-token steps, a pool of rows
at different positions against one row), not bf16 rounding. Logits are
of order 1 (an untied head seeded at ``hidden ** -0.5`` over a normed
stream), and agreement is asked to 1e-5 of the largest, which float32
sums in another order keep. The bf16 path's own distance from the
reference is what the benchmark's ``correct`` measures on the chip.
"""
import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from containerpilot_tpu.models import looped
from containerpilot_tpu.models import slots as slots_mod
from containerpilot_tpu.models.decode import _jitted_prefill, generate
from containerpilot_tpu.models.stepprog import PlainStepProgram, make_step_program
from containerpilot_tpu.workload import modelcfg

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY_FILE = os.path.join(ROOT, "benchmark", "tests", "toy", "toy-ouro.json")
REAL_FILE = os.path.join(ROOT, "benchmark", "configs", "ouro-2.6b-serve.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REL = 1e-5
MAX_LEN = 48
SLOTS, CHUNK = 3, 4
SEQ = 24


def _reference():
    spec = importlib.util.spec_from_file_location(
        "ouro_reference",
        os.path.join(ROOT, "benchmark", "configs", "ouro_reference.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


R = _reference()

with open(TOY_FILE) as _fh:
    TOY = {k: v for k, v in json.load(_fh).items()
           if k not in ("launch", "check", "check_note", "reference")}


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


class Programs:
    """The toy configuration in float32 (the bf16-held weights
    widened) and its programs, jitted once."""

    def __init__(self):
        cfg = looped.from_published(TOY, MAX_LEN)
        params = looped.init_params(None, cfg)
        self.cfg = cfg = dataclasses.replace(cfg, dtype=jnp.float32)
        self.params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
        self.forward = jax.jit(lambda p, t: looped.forward(p, t, cfg))
        self.prefill = _jitted_prefill(cfg, MAX_LEN)
        self.step = jax.jit(lambda p, c, t: looped.decode_chunk(p, c, t, cfg))

    def logits(self, toks):
        return np.asarray(self.forward(self.params, jnp.asarray(toks)[None]))[0]


@pytest.fixture(scope="module")
def prog():
    with jax.default_matmul_precision("highest"):
        return Programs()


def ids(n, seed=0, vocab=512):
    return np.random.default_rng(seed).integers(1, vocab, (n,)).astype(np.int32)


def close(mine, ref, rel=REL):
    """Whether ``mine`` lies within ``rel`` of the reference's largest
    value of ``ref``, everywhere."""
    mine, ref = np.asarray(mine), np.asarray(ref)
    return float(np.abs(mine - ref).max()) < rel * float(np.abs(ref).max())


# -- the loop, written out -------------------------------------------------


def plain_loop(params, tokens, cfg, passes=None):
    """The equations of the module's note over ONE sequence from
    position 0, in plain numpy-like jnp: no cache, no scan, no helper
    of the program. Returns logits [seq, vocab]."""
    def rms(x):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.rms_eps)

    seq, hd = len(tokens), cfg.head_dim
    half = hd // 2
    freqs = cfg.rope_theta ** (-np.arange(half) / half)
    angles = np.arange(seq)[:, None] * freqs
    cos, sin = np.cos(angles)[:, None, :], np.sin(angles)[:, None, :]

    def rope(x):
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    h = params["embed"][jnp.asarray(tokens)]
    mask = np.tril(np.ones((seq, seq), bool))
    for _t in range(cfg.passes if passes is None else passes):
        for lp in params["layers"]:
            n = rms(h)
            q = rope((n @ lp["wq"].T).reshape(seq, cfg.n_heads, hd))
            k = rope((n @ lp["wk"].T).reshape(seq, cfg.n_kv_heads, hd))
            v = (n @ lp["wv"].T).reshape(seq, cfg.n_kv_heads, hd)
            scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
            weights = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
            a = jnp.einsum("hqk,khd->qhd", weights, v).reshape(seq, -1) @ lp["wo"]
            h = h + rms(a)
            n = rms(h)
            m = (jax.nn.silu(n @ lp["w_gate"]) * (n @ lp["w_up"])) @ lp["w_down"]
            h = h + rms(m)
        h = rms(h)
    return h @ params["unembed"]


def test_forward_equals_a_plain_loop_written_out(prog):
    toks = ids(SEQ)
    assert close(prog.logits(toks), plain_loop(prog.params, toks, prog.cfg))


def test_forward_equals_the_benchmarks_reference(prog):
    toks = ids(SEQ, seed=1)
    assert close(prog.logits(toks), R.all_logits(TOY, toks))


def test_a_pass_fewer_is_another_model(prog):
    """What the ``three-passes`` control rests on: the fourth pass
    moves the logits grossly."""
    toks = ids(SEQ, seed=2)
    full = prog.logits(toks)
    three = np.asarray(plain_loop(prog.params, toks, prog.cfg, passes=3))
    assert np.abs(three - full).max() > 0.1 * np.abs(full).max()
    assert close(three, R.all_logits(TOY, toks, mode="three-passes"))


# -- the cache: prefill, steps, planes -----------------------------------


@pytest.mark.parametrize("prompt", [1, 7, 16])
def test_prefill_then_decode_equals_the_full_forward(prog, prompt):
    """A prompt prefilled, the rest one token at a time through the
    cache's planes: the logits of EVERY position are the full
    forward's."""
    toks = ids(SEQ, seed=prompt)
    full = prog.logits(toks)
    logits, cache = prog.prefill(prog.params, jnp.asarray(toks[:prompt])[None])
    assert close(logits[0], full[prompt - 1])
    for i in range(prompt, SEQ):
        logits, cache = prog.step(prog.params, cache, jnp.asarray(toks[i:i + 1])[None])
        assert close(logits[0, 0], full[i]), i
    assert int(cache["pos"]) == SEQ


def test_several_tokens_a_step_equal_one_a_step(prog):
    """``decode_chunk`` over 5 tokens at once (an extension of a reused
    prefix) is the same mathematics as 5 steps."""
    toks = ids(SEQ, seed=3)
    full = prog.logits(toks)
    _logits, cache = prog.prefill(prog.params, jnp.asarray(toks[:9])[None])
    logits, cache = prog.step(prog.params, cache, jnp.asarray(toks[9:14])[None])
    assert close(logits[0], full[9:14])
    assert int(cache["pos"]) == 14


def test_the_cache_is_a_plane_per_pass_and_layer(prog):
    cfg = prog.cfg
    _logits, cache = prog.prefill(prog.params, jnp.asarray(ids(9))[None])
    assert len(cache["k"]) == len(cache["v"]) == cfg.n_layers == 3
    assert cache["k"][0].shape == (4, 1, MAX_LEN, 4, 16)
    pool = jax.eval_shape(lambda: looped.slot_cache(cfg, SLOTS, MAX_LEN))
    assert pool["k"][2].shape == (4, SLOTS, MAX_LEN, 4, 16)
    assert pool["pos"].shape == (SLOTS,) and pool["stats"].shape == (2,)
    assert cfg.cache_planes == 12
    held = sum(x.size * x.dtype.itemsize for name in ("k", "v")
               for x in jax.eval_shape(
                   lambda: looped.init_cache(
                       dataclasses.replace(cfg, dtype=jnp.bfloat16), 1, 1))[name])
    assert held == dataclasses.replace(
        cfg, dtype=jnp.bfloat16).cache_bytes_per_position == 12 * 2 * 4 * 16 * 2


def test_each_passes_planes_differ(prog):
    """The passes see different streams, so what they write differs:
    no two passes' keys of a layer are alike."""
    _logits, cache = prog.prefill(prog.params, jnp.asarray(ids(9))[None])
    keys = np.asarray(cache["k"][1])[:, 0, :9]
    for a in range(4):
        for b in range(a + 1, 4):
            assert np.abs(keys[a] - keys[b]).max() > 0.1 * np.abs(keys[a]).max()


def test_reading_pass_0s_plane_in_every_pass_differs_grossly(prog):
    """The variant a shared plane would be: every pass's plane
    overwritten with pass 0's before a step. Were the passes to read
    pass 0's plane anyway, nothing would move."""
    toks = ids(12, seed=4)
    _logits, cache = prog.prefill(prog.params, jnp.asarray(toks[:11])[None])
    shared = dict(cache)
    for name in ("k", "v"):
        shared[name] = [jnp.broadcast_to(leaf[:1], leaf.shape)
                        for leaf in cache[name]]
    step = jnp.asarray(toks[11:])[None]
    own, _cache = prog.step(prog.params, cache, step)
    other, _cache = prog.step(prog.params, shared, step)
    assert np.abs(np.asarray(other - own)).max() > 0.05 * np.abs(np.asarray(own)).max()


@pytest.mark.parametrize("plane", [0, 1, 2, 3])
def test_a_pass_reads_its_own_plane_of_every_layer(prog, plane):
    """One plane of one layer zeroed: the step's logits move (that pass
    reads it), and zeroing what lies beyond the row's position in every
    plane moves nothing (no pass reads there)."""
    toks = ids(12, seed=5)
    _logits, cache = prog.prefill(prog.params, jnp.asarray(toks[:11])[None])
    step = jnp.asarray(toks[11:])[None]
    own, _cache = prog.step(prog.params, cache, step)
    cut = dict(cache, k=list(cache["k"]))
    cut["k"][1] = cache["k"][1].at[plane].set(0.0)
    moved, _cache = prog.step(prog.params, cut, step)
    assert np.abs(np.asarray(moved - own)).max() > 1e-3 * np.abs(np.asarray(own)).max()
    beyond = dict(cache, v=[leaf.at[:, :, 12:].set(7.0) for leaf in cache["v"]])
    same, _cache = prog.step(prog.params, beyond, step)
    assert np.array_equal(np.asarray(same), np.asarray(own))


# -- the pool ------------------------------------------------------------


def _admit(prog, pool, state, slot, prompt):
    cfg = prog.cfg
    logits, row = prog.prefill(prog.params, jnp.asarray(prompt)[None])
    first = int(jnp.argmax(logits[0]))
    pool = slots_mod.insert_row(pool, row, slot, cfg)
    state = slots_mod.admit_slot_state(
        state, slot, cfg, last=first, key=jnp.zeros((2,), jnp.uint32),
        temperature=0.0, top_k=0, top_p=1.0, eos_id=-1, pad_id=0, min_new=0,
        presence=0.0, frequency=0.0,
        bias_idx=np.full((slots_mod.BIAS_SLOTS_MAX,), -1),
        bias_val=np.zeros((slots_mod.BIAS_SLOTS_MAX,)), done=False)
    return pool, state, first


def _alone(prog, prompt, new):
    """Greedy tokens of one row decoded by itself through a cache."""
    logits, cache = prog.prefill(prog.params, jnp.asarray(prompt)[None])
    out = [int(jnp.argmax(logits[0]))]
    for _ in range(new - 1):
        logits, cache = prog.step(
            prog.params, cache, jnp.asarray([[out[-1]]], jnp.int32))
        out.append(int(jnp.argmax(logits[0, 0])))
    return out


@pytest.mark.parametrize("program", ["chunk", "window"])
def test_rows_admitted_at_different_times_decode_as_if_alone(prog, program):
    """One prompt admitted, a chunk decoded, a second of another length
    admitted beside it, both decoded on (a third slot stays empty and
    steps on pads): every row's tokens are what it decodes alone, and
    the counters say rows x steps and four passes of each."""
    cfg, rounds = prog.cfg, 2
    pool = slots_mod.slot_cache(cfg, SLOTS, MAX_LEN)
    state = slots_mod.init_slot_state(cfg, SLOTS)
    first_prompt, second_prompt = ids(13, seed=13), ids(7, seed=7)
    pool, state, first = _admit(prog, pool, state, 0, first_prompt)
    pool, state, toks, stats = slots_mod.decode_slots_chunk(
        prog.params, pool, state, cfg, CHUNK, with_stats=True)
    assert list(np.asarray(stats)) == [CHUNK * SLOTS, 4 * CHUNK * SLOTS]
    served = {0: [first] + [int(t) for t in np.asarray(toks)[0]]}
    pool, state, first = _admit(prog, pool, state, 1, second_prompt)
    served[1] = [first]
    if program == "chunk":
        for _ in range(rounds):
            pool, state, toks, stats = slots_mod.decode_slots_chunk(
                prog.params, pool, state, cfg, CHUNK, with_stats=True)
            for slot in served:
                served[slot] += [int(t) for t in np.asarray(toks)[slot]]
        assert list(np.asarray(stats)) == [CHUNK * SLOTS, 4 * CHUNK * SLOTS]
    else:
        pool, state, toks, run, stats = slots_mod.decode_slots_window(
            prog.params, pool, state, cfg, CHUNK, rounds,
            np.full((SLOTS,), 100), with_stats=True)
        assert int(run) == rounds
        for slot in served:
            served[slot] += [int(t) for t in np.asarray(toks)[slot]]
        assert list(np.asarray(stats)) == [
            rounds * CHUNK * SLOTS, 4 * rounds * CHUNK * SLOTS]
    assert served[0] == _alone(prog, first_prompt, 1 + 3 * CHUNK)
    assert served[1] == _alone(prog, second_prompt, 1 + 2 * CHUNK)
    assert list(np.asarray(pool["pos"])[:2]) == [13 + 3 * CHUNK, 7 + 2 * CHUNK]


def test_a_row_inserted_over_a_retired_one_keeps_nothing_of_it(prog):
    cfg = prog.cfg
    pool = slots_mod.slot_cache(cfg, SLOTS, MAX_LEN)
    state = slots_mod.init_slot_state(cfg, SLOTS)
    pool, state, _first = _admit(prog, pool, state, 1, ids(20, seed=1))
    pool, state, _toks = slots_mod.decode_slots_chunk(
        prog.params, pool, state, cfg, CHUNK)
    state = slots_mod.retire_slot(state, 1)
    pool, state, _toks = slots_mod.decode_slots_chunk(
        prog.params, pool, state, cfg, CHUNK)
    prompt = ids(9, seed=2)
    pool, state, first = _admit(prog, pool, state, 1, prompt)
    assert int(pool["pos"][1]) == 9
    assert float(jnp.abs(pool["k"][0][:, 1, 9:]).max()) == 0.0
    pool, state, toks = slots_mod.decode_slots_chunk(
        prog.params, pool, state, cfg, CHUNK)
    assert [first] + [int(t) for t in np.asarray(toks)[1]] == _alone(
        prog, prompt, 1 + CHUNK)


# -- the published file, the CLI, the refusals ---------------------------------


def _catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as fh:
        return next(r for r in map(json.loads, fh) if r["name"] == "Ouro-2.6B")


def test_the_catalog_rows_keys_give_the_published_sizes():
    """``from_published`` on the catalog row's own ``config``:
    2,667,974,657 parameters (by ``jax.eval_shape``: nothing is made)
    and 1,572,864 bytes of keys and values a position."""
    cfg = looped.from_published(_catalog_row()["config"], 320)
    shapes = jax.eval_shape(lambda: looped.init_params(None, cfg))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 2_667_974_657
    assert cfg.cache_bytes_per_position == 1_572_864
    assert (cfg.passes, cfg.n_layers, cfg.cache_planes) == (4, 48, 192)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff,
            cfg.vocab_size, cfg.rope_theta, cfg.rms_eps) == (
                2048, 16, 16, 128, 5632, 49152, 1e6, 1e-6)
    matrices = [x for x in jax.tree.leaves(shapes) if x.ndim >= 2]
    assert matrices and all(x.dtype == jnp.bfloat16 for x in matrices)
    assert shapes["exit_gate"]["w"].shape == (2048,)
    assert shapes["exit_gate"]["b"].shape == (1,)


def test_the_benchmark_file_holds_every_key_of_the_catalog_row():
    row = _catalog_row()
    with open(REAL_FILE) as fh:
        config = json.load(fh)
    assert config["reduced"] == {}
    for key, value in row["config"].items():
        assert config[key] == value, key
    assert config["source"] == row["source_url"]
    for point in ("norms", "final_norm", "cache_planes", "exit_gate", "weights",
                  "precision", "torch_dtype"):
        assert point in config["assumed"], point
    cfg = modelcfg.load_model_file(REAL_FILE, 320)
    assert isinstance(cfg, looped.LoopedConfig)
    assert cfg == dataclasses.replace(
        looped.from_published(row["config"], 320),
        source_digest=cfg.source_digest)
    args = config["launch"]["replica_args"]
    assert args[args.index("--max-len") + 1] == "320"
    assert args[args.index("--slots") + 1] == "16"


def test_an_exit_threshold_under_one_is_refused_by_name():
    with pytest.raises(ValueError, match="adaptive exit per row is not served"):
        looped.from_published(dict(TOY, early_exit_threshold=0.5), 64)


def test_a_file_without_its_pass_count_is_refused_by_name(tmp_path):
    config = {k: v for k, v in TOY.items() if k != "total_ut_steps"}
    with pytest.raises(ValueError, match="total_ut_steps"):
        looped.from_published(config, 64)
    path = tmp_path / "model.json"
    path.write_text(json.dumps(config))
    with pytest.raises(SystemExit, match="'ouro' has no builder"):
        modelcfg.load_model_file(str(path), 64)


@pytest.mark.parametrize("key, value, match", [
    ("early_exit_threshold", 0.5, "adaptive exit"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("sliding_window", 128, "sliding_window"),
    ("layer_types", ["full_attention", "sliding_attention"], "sliding_attention"),
    ("total_ut_steps", 0, "total_ut_steps"),
])
def test_a_file_this_family_cannot_run_is_refused_by_name(tmp_path, key, value,
                                                          match):
    path = tmp_path / "model.json"
    path.write_text(json.dumps(dict(TOY, **{key: value})))
    with pytest.raises(SystemExit, match=match):
        modelcfg.load_model_file(str(path), 64)


def test_weights_are_held_in_bfloat16_and_follow_the_stated_recipe():
    cfg = looped.from_published(TOY, MAX_LEN)
    params = looped.init_params(None, cfg)
    matrices = [x for x in jax.tree.leaves(params) if x.ndim >= 2]
    assert matrices and all(x.dtype == jnp.bfloat16 for x in matrices)
    ref = R.layer_weights(TOY, 1)
    mine = params["layers"][1]
    for name in ("wq", "wk", "wv"):  # held output-major, heads folded
        assert np.array_equal(np.asarray(mine[name].T),
                              np.asarray(ref[name]).reshape(64, -1)), name
    assert np.array_equal(np.asarray(mine["wo"]),
                          np.asarray(ref["wo"]).reshape(-1, 64))
    for name in ("w_gate", "w_up", "w_down"):
        assert np.array_equal(np.asarray(mine[name]), np.asarray(ref[name])), name
    assert np.array_equal(np.asarray(params["embed"]),
                          np.asarray(R.vocab_leaf(TOY, "embed", 0.02)))
    assert np.array_equal(np.asarray(params["unembed"].T),
                          np.asarray(R.vocab_leaf(TOY, "unembed", 64 ** -0.5)))
    assert all(np.array_equal(np.asarray(mine[n]), np.ones(64)) for n in looped.NORMS)


def test_serve_cli_builds_the_model_from_a_file():
    from containerpilot_tpu.workload import serve_cli

    args = serve_cli.build_arg_parser().parse_args(
        ["--model-config", TOY_FILE, "--max-len", "128"])
    cfg, params, _mesh = serve_cli.load_model(args)
    assert isinstance(cfg, looped.LoopedConfig)
    assert (cfg.passes, cfg.n_layers, cfg.max_seq_len) == (4, 3, 128)
    assert params["layers"][2]["w_gate"].shape == (64, 160)
    assert params["layers"][2]["wq"].dtype == jnp.bfloat16
    assert isinstance(make_step_program(cfg, params, 64, 2, 2), PlainStepProgram)


@pytest.mark.parametrize("flags", [
    ["--int8"], ["--kv-int8"], ["--window", "8"], ["--draft-layers", "1"],
    ["--tp", "2"], ["--cp", "2"], ["--checkpoint-dir", "/nowhere"]])
def test_serve_cli_refuses_what_only_the_flagship_block_has(flags):
    from containerpilot_tpu.workload import serve_cli

    args = serve_cli.build_arg_parser().parse_args(
        ["--model-config", TOY_FILE, *flags])
    with pytest.raises(SystemExit, match="does not compose"):
        serve_cli.load_model(args)


def test_beams_are_refused_and_sampling_is_not():
    looped.refuse_request({"temperature": 0.7, "top_k": 5, "beam_width": 0})
    with pytest.raises(ValueError, match="beam_width"):
        looped.refuse_request({"beam_width": 2})


# -- the engine, the counters, /v1/model ---------------------------------------


def _solo(prog, tokens, new):
    out = generate(prog.params, jnp.asarray([tokens], jnp.int32), prog.cfg,
                   new, MAX_LEN)
    return [int(t) for t in np.asarray(out)[0]]


def test_the_engine_serves_what_one_shot_generation_gives(prog):
    """Three requests over two slots through ``SlotEngine`` (prefill,
    insert, chunk and fused-window dispatches, a slot reused): each
    row's tokens are ``generate``'s, and the counters read four passes a
    row-step."""
    from containerpilot_tpu.workload.serve_slots import SlotEngine

    engine = SlotEngine(prog.cfg, prog.params, MAX_LEN, slots=2, chunk=3,
                        window=2)
    try:
        prompts = [list(map(int, ids(n, seed=n))) for n in (5, 11, 8)]
        news = (9, 14, 6)
        futures = [engine.submit(p, max_new=n) for p, n in zip(prompts, news)]
        got = [f.result(timeout=300) for f in futures]
        loop = engine.loop_stats()
    finally:
        engine.stop()
    for prompt, new, row in zip(prompts, news, got):
        assert row == _solo(prog, prompt, new)
    assert loop["loop_row_steps"] > 0 and loop["loop_row_steps"] % 2 == 0
    assert loop["loop_row_passes"] == 4 * loop["loop_row_steps"]
    assert {k: loop[k] for k in ("passes", "layers", "cache_planes",
                                 "cache_bytes_per_position")} == {
        "passes": 4, "layers": 3, "cache_planes": 12,
        "cache_bytes_per_position": 12 * 2 * 4 * 16 * 4}


def test_a_reused_prefix_is_rewound_and_extended_like_the_flagships(prog):
    """Keys and values are addressable by position in every plane, so
    the prefix cache needs no path of its own: a second turn that
    extends a stored prompt reuses it (a hit) and gives the tokens a
    cold engine gives."""
    from containerpilot_tpu.workload.serve_prefix import PrefixCache
    from containerpilot_tpu.workload.serve_slots import SlotEngine

    turn = list(map(int, ids(20, seed=20)))
    more = turn + list(map(int, ids(9, seed=9)))
    outs = {}
    caches = {"reusing": PrefixCache(2), "cold": None}
    for name, pc in caches.items():
        engine = SlotEngine(prog.cfg, prog.params, MAX_LEN, slots=1, chunk=3,
                            prefix_cache=pc)
        try:
            outs[name] = [engine.submit(t, 6).result(timeout=300)
                          for t in (turn, more)]
        finally:
            engine.stop()
    assert outs["reusing"] == outs["cold"]
    assert outs["cold"][1] == _solo(prog, more, 6)
    stats = caches["reusing"].stats
    assert stats["hits"] == 1 and stats["tokens_reused"] >= 8, stats


def test_step_program_returns_the_counters_with_the_tokens():
    cfg = looped.from_published(TOY, MAX_LEN)
    params = looped.init_params(None, cfg)
    program = make_step_program(cfg, params, MAX_LEN, slots=2, chunk=4, rounds=2)
    assert program.loop_stats()["loop_row_passes"] == 0
    assert program.expert_stats() is None and program.state_stats() is None
    program.tokens(program.dispatch(np.asarray([100, 100]), False))
    assert program.loop_stats() == {
        "passes": 4, "layers": 3, "cache_planes": 12,
        "cache_bytes_per_position": 12 * 2 * 4 * 16 * 2,
        "loop_row_steps": 4 * 2, "loop_row_passes": 4 * 4 * 2}


def test_a_model_whose_layers_run_once_publishes_none():
    from containerpilot_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=1,
                            d_ff=128, max_seq_len=32)
    program = make_step_program(
        cfg, init_params(jax.random.PRNGKey(0), cfg), 32, slots=2, chunk=2)
    assert program.loop_stats() is None


def test_v1_model_carries_the_loop_block(run):
    import asyncio
    import urllib.request

    from containerpilot_tpu.workload.serve import InferenceServer

    cfg = looped.from_published(TOY, MAX_LEN)
    params = looped.init_params(None, cfg)

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
                {"tokens": [[5, 9, 2, 40, 7]], "max_new_tokens": 6})
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
    loop = model["loop"]
    assert (loop["passes"], loop["layers"], loop["cache_planes"]) == (4, 3, 12)
    assert loop["cache_bytes_per_position"] == 12 * 2 * 4 * 16 * 2
    assert loop["loop_row_passes"] == 4 * loop["loop_row_steps"] > 0
    assert model["state"] is None and model["experts"] is None


# -- the check: the sound program passes, the controls do not ----------------


def _served(cfg, params, prompts, new):
    cases = []
    for i, prompt in enumerate(prompts):
        out = generate(params, jnp.asarray([prompt], jnp.int32), cfg, new, MAX_LEN)
        cases.append({"index": i, "prompt": [int(t) for t in prompt],
                      "tokens": [int(t) for t in np.asarray(out)[0]]})
    return cases


def test_the_check_passes_the_program_and_fails_three_passes(prog):
    """``check_served`` over what the float32 program served: gaps of
    float32 rounding; the ``three-passes`` control (a pass skipped) and
    ``int8-weights`` prove that they took place and read far above it;
    a control that moves nothing is an error."""
    cases = _served(prog.cfg, prog.params, [ids(10, seed=s) for s in (1, 2)], 8)
    result = R.check_served(TOY, {
        "cases": cases, "max_len": SEQ,
        "controls": ["three-passes", "int8-weights"]})
    assert result["positions"] == 16
    assert result["max_logit_gap"] < 1e-4
    three, int8 = result["controls"]["three-passes"], result["controls"]["int8-weights"]
    assert three["logits_moved_max"] > 0.5 and three["tokens_changed"] >= 8
    assert three["mean_logit_gap"] > 100 * max(result["mean_logit_gap"], 1e-6)
    assert int8["logits_moved_max"] > 1e-3
    # on the CPU float32 products ARE the default: the control that
    # asks for single-pass bf16 products moves nothing here, and says so
    with pytest.raises(RuntimeError, match="did not take place"):
        R.check_served(TOY, {"cases": cases, "max_len": SEQ,
                             "controls": ["bf16-products"]})
    with pytest.raises(ValueError, match="control 'bf16'"):
        R.check_served(TOY, {"cases": cases, "max_len": SEQ, "controls": ["bf16"]})


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "configs", "ouro_reference.py")) as fh:
        source = fh.read()
    assert "import containerpilot_tpu" not in source
    assert "from containerpilot_tpu" not in source
    assert 'default_matmul_precision(precision)' in source
    assert '"highest"' in source and "reduce_precision" in source
