"""The block-diffusion family (models/block_diffusion.py,
models/moe.py ``route_softmax``) against the benchmark's plain
reference (benchmark/configs/sdar_reference.py) at toy size on the CPU:
hidden 64, 4 query / 2 key-value heads of 16, 8 experts top-2, two
layers, blocks of 4 in 2 denoising steps, seeded weights.

The program holds bfloat16 weights; the tests widen the SAME values to
float32 and compute in float32 (``highest``), so that what is compared
is the mathematics (the block mask, a cache against none, a pool of
rows each at its own phase against one sequence at a time, sorted
dispatch against masked-dense experts), not bf16 rounding: logits agree
to 1e-4 and generated tokens exactly.
"""
import dataclasses
import importlib.util
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from containerpilot_tpu.models import block_diffusion as bd
from containerpilot_tpu.models import moe
from containerpilot_tpu.models.decode import _jitted_prefill
from containerpilot_tpu.workload import modelcfg
from containerpilot_tpu.workload.serve_prefix import (
    PrefixCache,
    plan_reuse,
    reuse_admission,
)
from containerpilot_tpu.workload.serve_slots import SlotEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY_FILE = os.path.join(ROOT, "benchmark", "tests", "toy", "toy-sdar.json")
REAL_FILE = os.path.join(
    ROOT, "benchmark", "configs", "sdar-30b-a3b-serve.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TOL = 1e-4
MAX_LEN = 64


def _reference():
    spec = importlib.util.spec_from_file_location(
        "sdar_reference",
        os.path.join(ROOT, "benchmark", "configs", "sdar_reference.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


R = _reference()
with open(TOY_FILE) as _fh:
    TOY = json.load(_fh)
MASK_ID = TOY["diffusion"]["mask_token_id"]


def with_rule(rule, threshold=0.9, steps=2):
    config = dict(TOY)
    config["diffusion"] = dict(
        TOY["diffusion"], remasking=rule, confidence_threshold=threshold,
        denoising_steps=steps)
    return config


def widened(config, max_len=MAX_LEN):
    """(float32 configuration, the bf16-held weights widened)."""
    cfg = bd.from_published(config, max_len)
    params = bd.init_params(None, cfg)
    return (dataclasses.replace(cfg, dtype=jnp.float32),
            jax.tree.map(lambda x: x.astype(jnp.float32), params))


@pytest.fixture(scope="module")
def model():
    return widened(TOY)


@pytest.fixture(scope="module")
def weights():
    return R.all_weights(TOY)


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def ids(n, seed=0, vocab=500):
    return [int(t) for t in
            np.random.default_rng(seed).integers(1, vocab, (n,))]


def engine_for(cfg, params, **kw):
    kw = {"slots": 3, "chunk": 3, "window": 2, **kw}
    return SlotEngine(cfg, params, MAX_LEN, **kw)


# -- the forward under the block mask ------------------------------------


@pytest.mark.parametrize("seq", [8, 19])
def test_full_forward_under_the_block_mask_matches_the_reference(
        model, weights, seq):
    cfg, params = model
    toks = ids(seq, seed=seq)
    mine = np.asarray(bd.forward(params, jnp.asarray([toks]), cfg))[0]
    ref = np.asarray(R.all_logits(TOY, toks, weights=weights))
    assert np.abs(ref).max() > 0.5
    assert np.abs(mine - ref).max() < TOL


def test_the_mask_is_by_blocks_not_causal(model):
    """A later token of the SAME block moves an earlier position's
    logits; a token of a later block does not."""
    cfg, params = model
    toks = ids(12, seed=3)
    base = np.asarray(bd.forward(params, jnp.asarray([toks]), cfg))[0]
    same = list(toks)
    same[6] = (same[6] + 1) % 500 + 1   # block 1: positions 4..7
    later = list(toks)
    later[9] = (later[9] + 1) % 500 + 1  # block 2
    moved = np.asarray(bd.forward(params, jnp.asarray([same]), cfg))[0]
    kept = np.asarray(bd.forward(params, jnp.asarray([later]), cfg))[0]
    assert np.abs(moved[4] - base[4]).max() > 1e-3
    assert np.abs(moved[3] - base[3]).max() < 1e-6
    assert np.abs(kept[:8] - base[:8]).max() < 1e-6


def test_per_head_qk_norms_come_before_the_rotation(model, weights):
    cfg, params = model
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 8, cfg.d_model))
    lp, w = params["layers"][0], weights["layers"][0]
    q, k, _v = bd._qkv(x, lp, cfg, 0)
    h = R._rms(x[0], 1e-6)
    at = jnp.arange(8)
    for mine, name in ((q, "wq"), (k, "wk")):
        ref = R._rope(R._rms(jnp.einsum("sd,dhk->shk", h, w[name]), 1e-6),
                      at, 1e6)
        assert np.abs(np.asarray(mine[0]) - np.asarray(ref)).max() < 1e-5
        # the norm is per head: every head's vector has unit RMS
        rms = np.sqrt((np.asarray(mine[0]) ** 2).mean(axis=-1))
        assert np.abs(rms - 1.0).max() < 1e-3


def test_softmax_routing_takes_the_top_and_renormalises(model, weights):
    cfg, params = model
    h = jax.random.normal(jax.random.PRNGKey(7), (33, cfg.d_model))
    idx, gate = moe.route_softmax(
        h, params["layers"][1]["router"], cfg.experts_per_tok, True)
    ref_idx, ref_gate = R.route(h, weights["layers"][1]["router"], TOY)
    assert np.array_equal(np.asarray(idx), np.asarray(ref_idx))
    assert np.abs(np.asarray(gate) - np.asarray(ref_gate)).max() < 1e-6
    assert np.abs(np.asarray(gate).sum(axis=-1) - 1.0).max() < 1e-6
    _idx, raw = moe.route_softmax(
        h, params["layers"][1]["router"], cfg.experts_per_tok, False)
    assert (np.asarray(raw).sum(axis=-1) < 1.0).all()


def test_expert_layer_with_all_128_experts_held_matches_the_reference():
    """256 rows (64 slots x 4 positions) choose 8 of 128 experts, all
    held: the sorted dispatch against the masked dense sum."""
    config = dict(TOY, num_experts=128, num_experts_per_tok=8,
                  hidden_size=32, moe_intermediate_size=16,
                  num_attention_heads=2, num_key_value_heads=1)
    cfg, params = widened(config)
    lp = params["layers"][0]
    w = R.layer_weights(config, 0)
    x = jax.random.normal(jax.random.PRNGKey(11), (64, 4, 32))
    mine, counts = bd._sparse_ffn(x, lp, cfg)
    u = R._rms(x.reshape(256, 32), 1e-6)
    idx, gates = R.route(u, w["router"], config)
    ref = x.reshape(256, 32) + R.experts(u, idx, gates, w)
    assert np.abs(np.asarray(mine).reshape(256, 32)
                  - np.asarray(ref)).max() < TOL
    assert int(counts.sum()) == 256 * 8 and counts.shape == (128,)


# -- prefill, then block steps through the pool ---------------------------


@pytest.mark.parametrize("rule,threshold", [
    ("low_confidence_static", 0.9),
    ("low_confidence_dynamic", 0.9),    # nothing passes: the fallback
    ("low_confidence_dynamic", 0.004),  # some pass: more than 2 a step
])
@pytest.mark.parametrize("prompt_len,max_new", [(8, 12), (10, 9), (19, 13)])
def test_pool_generation_matches_the_reference(
        rule, threshold, prompt_len, max_new):
    """Prefill under the block mask, then denoising and commit
    forwards through the pool's cache, against the reference's routine
    with no cache: prompts that end on and inside a block, outputs that
    are no multiple of 4."""
    config = with_rule(rule, threshold)
    cfg, params = widened(config)
    prompt = ids(prompt_len, seed=prompt_len)
    want, states = R.generate(config, prompt, max_new,
                              weights=R.all_weights(config))
    engine = engine_for(cfg, params)
    try:
        got = engine.submit(prompt, max_new).result(timeout=600)
    finally:
        engine.stop()
    assert got == want and len(got) == max_new
    assert MASK_ID not in got
    if threshold < 0.01:
        # the dynamic rule did reveal more than the static count somewhere
        first = [s for s in states if s[2].sum() == 4]
        after = [s for s in states if 0 < s[2].sum() < 2]
        assert first and (after or len(states) < 2 * len(first))


def test_three_denoising_steps_of_an_uneven_schedule():
    """4 positions in 3 steps reveal 2, 1, 1."""
    config = with_rule("low_confidence_static", steps=3)
    cfg, params = widened(config)
    assert cfg.schedule == (2, 1, 1) == tuple(R.schedule(4, 3))
    prompt = ids(8, seed=21)
    want, _states = R.generate(config, prompt, 8,
                               weights=R.all_weights(config))
    engine = engine_for(cfg, params, chunk=4)
    try:
        assert engine.submit(prompt, 8).result(timeout=600) == want
    finally:
        engine.stop()


@pytest.mark.parametrize("chunk", [2, 3])
def test_a_row_joins_a_pool_mid_block_of_its_neighbours(model, weights, chunk):
    """With 2 forwards a dispatch the rows' phases drift apart (a block
    takes 3); a request admitted while its neighbours are inside their
    blocks still gets what it would get alone."""
    cfg, params = model
    first, second, third = ids(8, seed=31), ids(10, seed=32), ids(13, seed=33)
    engine = engine_for(cfg, params, chunk=chunk, window=1)
    started = threading.Event()
    try:
        a = engine.submit(first, 28, on_tokens=lambda d: started.set())
        assert started.wait(timeout=600)
        b = engine.submit(second, 17)
        c = engine.submit(third, 11)
        got = [f.result(timeout=600) for f in (a, b, c)]
    finally:
        engine.stop()
    for prompt, max_new, mine in ((first, 28, got[0]), (second, 17, got[1]),
                                  (third, 11, got[2])):
        want, _ = R.generate(TOY, prompt, max_new, weights=weights)
        assert mine == want


def test_the_stream_delivers_whole_blocks_in_order(model, weights):
    cfg, params = model
    prompt = ids(10, seed=41)  # ends inside a block: the first has 2 new
    deltas = []
    engine = engine_for(cfg, params)
    try:
        got = engine.submit(prompt, 13, on_tokens=deltas.append).result(
            timeout=600)
        stats = engine.stats
    finally:
        engine.stop()
    assert [t for d in deltas for t in d] == got and len(got) == 13
    assert stats["tokens_out"] == 13
    # block boundaries of the sequence: 2 tokens finish the prompt's
    # block, then whole blocks of 4, the last cut at max_new
    edges, at = [], 0
    for d in deltas:
        at += len(d)
        edges.append(at)
    assert all((10 + e) % 4 == 0 for e in edges[:-1]) and edges[-1] == 13
    want, _ = R.generate(TOY, prompt, 13, weights=weights)
    assert got == want


def test_a_prompt_holding_the_mask_id_is_not_taken_for_hidden(model, weights):
    cfg, params = model
    prompt = ids(10, seed=51)
    prompt[3] = prompt[8] = prompt[9] = MASK_ID  # 8, 9: in the first block
    want, _ = R.generate(TOY, prompt, 10, weights=weights)
    engine = engine_for(cfg, params)
    try:
        got = engine.submit(prompt, 10).result(timeout=600)
    finally:
        engine.stop()
    assert got == want and MASK_ID not in got


def test_counters_say_what_a_block_costs(model):
    cfg, params = model
    engine = engine_for(cfg, params)
    try:
        engine.submit(ids(8, seed=61), 16).result(timeout=600)
        counted = engine.diffusion_stats()
        experts = engine.expert_stats()
    finally:
        engine.stop()
    assert counted["block_length"] == 4 and counted["denoising_steps"] == 2
    assert counted["remasking"] == "low_confidence_static"
    # 2 denoising forwards and a commit a block, 4 tokens revealed
    assert counted["blocks_committed"] == counted["commit_forwards"] >= 4
    assert counted["row_forwards"] >= 3 * counted["blocks_committed"]
    assert counted["tokens_revealed"] >= 4 * counted["blocks_committed"]
    assert experts["published"] == 8 and experts["held"] == [0, 8]
    assert experts["assignments_here"] == sum(experts["load"]) > 0


# -- reuse at block boundaries only ---------------------------------------


def test_reuse_is_refused_off_a_block_boundary_and_exact_on_one(model):
    cfg, params = model
    base = ids(24, seed=71)
    row = base[:22] + ids(8, seed=72)  # shares 22 tokens: inside block 5
    pc = PrefixCache(4)
    logits, cache = _jitted_prefill(cfg, MAX_LEN)(
        params, jnp.asarray([base], jnp.int32))
    assert int(cache["pos"]) == 24
    pc.store(tuple(base), cache)
    # the flagship's plan would rewind to 14; a block's keys depend on
    # the whole block, so this family rewinds to 12
    assert plan_reuse(pc, row)[0] == 14
    assert plan_reuse(pc, row, cfg.reuse_quantum)[0] == 12
    _logits, warm = reuse_admission(pc, row, cfg, params)
    _logits, cold = _jitted_prefill(cfg, MAX_LEN)(
        params, jnp.asarray([row], jnp.int32))
    assert int(warm["pos"]) == int(cold["pos"]) == 28
    for name in ("k", "v"):
        for mine, ref in zip(warm[name], cold[name]):
            assert np.abs(np.asarray(mine[:, :28])
                          - np.asarray(ref[:, :28])).max() < 1e-5
    # rewinding INSIDE the matched block instead reads keys that were
    # computed beside tokens the new row does not hold
    wrong = dict(pc.get(tuple(base)), pos=jnp.asarray(22, jnp.int32))
    _l, bad = bd.decode_chunk(
        params, wrong, jnp.asarray([row[22:]], jnp.int32), cfg)
    assert np.abs(np.asarray(bad["k"][1][:, 20:22])
                  - np.asarray(cold["k"][1][:, 20:22])).max() > 1e-3


def test_a_second_turn_reuses_the_first_and_serves_the_same(model, weights):
    cfg, params = model
    pc = PrefixCache(4)
    first = ids(24, seed=81)
    second = first + ids(10, seed=82)  # 34: ends inside a block
    engine = SlotEngine(cfg, params, MAX_LEN, slots=2, chunk=3, window=2,
                        prefix_cache=pc)
    try:
        engine.submit(first, 4).result(timeout=600)
        got = engine.submit(second, 9).result(timeout=600)
    finally:
        engine.stop()
    assert pc.stats["hits"] == 1 and pc.stats["tokens_reused"] % 4 == 0
    assert 0 < pc.stats["tokens_reused"] <= 24
    want, _ = R.generate(TOY, second, 9, weights=weights)
    assert got == want


# -- what is refused --------------------------------------------------------


@pytest.mark.parametrize("knob,value", [
    ("temperature", 0.7), ("top_k", 5), ("top_p", 0.9), ("min_new", 2),
    ("presence", 0.5), ("frequency", 0.5), ("logit_bias", {3: 1.0}),
    ("beam_width", 2), ("logprobs", True),
])
def test_knobs_the_routine_does_not_take_are_refused(knob, value):
    bd.refuse_request({"temperature": 0.0, "top_k": 0})
    with pytest.raises(ValueError, match=knob):
        bd.refuse_request({knob: value})


def test_submit_refuses_a_sorted_sampler(model):
    cfg, params = model
    engine = engine_for(cfg, params)
    try:
        with pytest.raises(ValueError, match="top_k"):
            engine.submit(ids(8), 4, top_k=5)
        with pytest.raises(ValueError, match="logit_bias"):
            engine.submit(ids(8), 4, logit_bias={3: 1.0})
    finally:
        engine.stop()


# -- the order-free judgement ----------------------------------------------


def _cases(rows):
    return [{"index": i, "prompt": prompt, "tokens": tokens}
            for i, (prompt, tokens) in enumerate(rows)]


def test_candidates_are_the_orders_the_schedule_allows():
    six = R.candidates(0, 4, [2, 2])
    assert len(six) == 6 and all(len(chain) == 2 for chain in six)
    assert {chain[0][1] for chain in six} == {
        (0, 1), (0, 2), (0, 3), (1, 2), (1, 3), (2, 3)}
    # a prompt that shows 3 of the 4: one hidden, one step
    assert R.candidates(3, 4, [2, 2]) == [[((0, 1, 2), (3,))]]
    assert len(R.candidates(0, 4, [1, 1, 1, 1])) == 24


def test_the_order_free_check_passes_the_sound_program_and_fails_int8(
        model, weights):
    """What the engine served (float32 here) lies within rounding of
    the reference under SOME order of reveals; what an int8 reading of
    the same model serves does not, by a wide margin, and the check's
    own ``controls`` read the same."""
    cfg, params = model
    prompts = [ids(8, seed=91), ids(10, seed=92), ids(16, seed=93)]
    engine = engine_for(cfg, params)
    try:
        served = [engine.submit(p, 24).result(timeout=600) for p in prompts]
    finally:
        engine.stop()
    sound = R.check_served(TOY, {
        "cases": _cases(zip(prompts, served)), "max_len": MAX_LEN,
        "controls": ["int8"]})
    assert sound["positions"] > 0
    assert sound["max_logit_gap"] < 1e-3 and sound["mean_logit_gap"] < 1e-4
    coarse = R.all_weights(TOY, "int8")
    other = [R.generate(TOY, p, 24, weights=coarse, precision="default")[0]
             for p in prompts]
    assert other != served
    unsound = R.check_served(TOY, {
        "cases": _cases(zip(prompts, other)), "max_len": MAX_LEN})
    assert unsound["mean_logit_gap"] > 100 * max(sound["mean_logit_gap"], 1e-6)
    assert unsound["max_logit_gap"] > 0.01
    control = sound["controls"]["int8"]
    assert control["mean_logit_gap"] > 100 * max(sound["mean_logit_gap"], 1e-6)


def test_the_check_judges_only_blocks_delivered_whole():
    case = {"index": 0, "prompt": ids(10), "tokens": ids(13, seed=1)}
    # positions 10..22: blocks 2 (2 new tokens), 3, 4 whole; 5 is cut
    assert R.judged_blocks(case, 4) == [2, 3, 4]
    long = {"index": 1, "prompt": ids(8), "tokens": ids(400, seed=2)}
    picked = R.judged_blocks(long, 4)
    assert len(picked) == R.JUDGED_BLOCKS
    assert picked[0] == 2 and picked[-1] == 101


# -- the configuration files -------------------------------------------------


def test_the_model_file_is_told_by_its_keys(tmp_path):
    cfg = modelcfg.load_model_file(TOY_FILE, 128)
    assert isinstance(cfg, bd.BlockDiffusionConfig)
    assert (cfg.block_length, cfg.denoising_steps, cfg.mask_token_id) == (
        4, 2, MASK_ID)
    assert cfg.reuse_quantum == 4 and cfg.source_digest
    unknown = tmp_path / "other.json"
    unknown.write_text(json.dumps({"model_type": "granitemoehybrid",
                                   "num_experts": 8}))
    with pytest.raises(SystemExit, match="granitemoehybrid"):
        modelcfg.load_model_file(str(unknown), 128)
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(dict(TOY, mlp_only_layers=[0])))
    with pytest.raises(SystemExit, match="mlp_only_layers"):
        modelcfg.load_model_file(str(bad), 128)


def test_the_benchmark_file_holds_the_published_widths():
    with open(REAL_FILE) as fh:
        real = json.load(fh)
    with open(CATALOG) as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    published = next(r for r in rows if r["name"] == "SDAR-30B-A3B-Chat")
    assert real["source"] == published["source_url"]
    differs = [k for k, v in published["config"].items() if real.get(k, k) != v]
    assert differs == ["num_hidden_layers"] == list(real["reduced"])
    assert real["num_hidden_layers"] == 6
    assert real["published"]["num_hidden_layers"] == 48
    cfg = bd.from_published(real, 3072)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        2048, 32, 4, 128)
    assert (cfg.n_experts, cfg.experts_per_tok, cfg.moe_d_ff) == (128, 8, 768)
    assert cfg.vocab_size == 151_936 and cfg.mask_token_id == 151_669
    assert cfg.schedule == (2, 2)
    shapes = jax.eval_shape(lambda: bd.init_params(None, cfg))
    held = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)
               if s.dtype == jnp.bfloat16)
    assert held == 4_361_027_584  # 8.72 GB in bf16: the file's arithmetic
    assert all(s.dtype in (jnp.bfloat16, jnp.float32)
               for s in jax.tree.leaves(shapes))
