"""The latent-attention, routed-expert family (models/mla_moe.py,
models/moe.py ``route_topk``/``sparse_experts``) against the
benchmark's plain reference (benchmark/configs/axk1_reference.py) at
toy size on the CPU: hidden 64, 4 heads, latent 16 + rope 8, 16
experts top-4, one dense and two sparse layers, seeded weights.

Comparisons are on LOGITS. The program holds bfloat16 weights; the
tests widen the SAME values to float32 and compute in float32
(``highest``), so that what is compared is the mathematics (absorbed
against expanded attention, sorted dispatch against masked-dense
experts, a cache against none), not bf16 rounding: agreement is to
1e-4 where the logits reach 4. The bf16 path's own distance from the
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

from containerpilot_tpu.kvtier import HostSpillTier
from containerpilot_tpu.models import mla_moe, moe
from containerpilot_tpu.models import slots as slots_mod
from containerpilot_tpu.models.decode import _jitted_prefill
from containerpilot_tpu.models.stepprog import make_step_program
from containerpilot_tpu.workload import modelcfg
from containerpilot_tpu.workload.serve_prefix import (
    PrefixCache,
    reuse_admission,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY_FILE = os.path.join(ROOT, "benchmark", "tests", "toy", "toy-axk1.json")
REAL_FILE = os.path.join(ROOT, "benchmark", "configs", "ax-k1-serve.json")
TOL = 1e-4
MAX_LEN = 64


def _reference():
    spec = importlib.util.spec_from_file_location(
        "axk1_reference",
        os.path.join(ROOT, "benchmark", "configs", "axk1_reference.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


R = _reference()

TOY = {
    "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_routed_experts": 16, "n_shared_experts": 1,
    "num_experts_per_tok": 4, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "scoring_func": "sigmoid",
    "topk_method": "none", "rope_theta": 10000, "hidden_act": "silu",
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64,
                     "type": "yarn"},
    "rms_norm_eps": 1e-6, "vocab_size": 256, "moe_layer_freq": 1,
}


def widened(config, max_len=MAX_LEN):
    """(float32 configuration, the bf16-held weights widened)."""
    cfg = mla_moe.from_published(config, max_len)
    params = mla_moe.init_params(None, cfg)
    return (dataclasses.replace(cfg, dtype=jnp.float32),
            jax.tree.map(lambda x: x.astype(jnp.float32), params))


@pytest.fixture(scope="module")
def model():
    return widened(TOY)


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def ids(n, seed=0, vocab=256):
    return np.random.default_rng(seed).integers(1, vocab, (n,)).astype(np.int32)


# -- forward, absorbed against expanded, the pool -----------------------


@pytest.mark.parametrize("seq", [8, 40])
def test_full_forward_matches_the_reference(model, seq):
    cfg, params = model
    toks = ids(seq, seed=seq)
    mine = np.asarray(mla_moe.forward(params, jnp.asarray(toks)[None], cfg))[0]
    ref = np.asarray(R.all_logits(TOY, toks))
    assert np.abs(ref).max() > 1.0
    assert np.abs(mine - ref).max() < TOL


@pytest.mark.parametrize("m", [1, 4])
def test_absorbed_decode_matches_the_expanded_reference(model, m):
    """Prefill (expanded form) writes the latents; every later position
    is computed in the absorbed form from the cache alone, in chunks
    of ``m``, and has the logits the reference's full forward has."""
    cfg, params = model
    toks = ids(28, seed=3)
    ref = np.asarray(R.all_logits(TOY, toks))
    logits, cache = mla_moe.prefill(
        params, jnp.asarray(toks[:16])[None], cfg, MAX_LEN)
    assert np.abs(np.asarray(logits)[0] - ref[15]).max() < TOL
    for start in range(16, 28, m):
        logits, cache = mla_moe.decode_chunk(
            params, cache, jnp.asarray(toks[start:start + m])[None], cfg)
        assert np.abs(
            np.asarray(logits)[0] - ref[start:start + m]).max() < TOL
    assert int(cache["pos"]) == 28


@pytest.mark.parametrize("program", ["chunk", "window"])
def test_pool_programs_match_the_reference(model, program):
    """Three prompts of different lengths prefilled, inserted into a
    pool of four slots, decoded greedily by the chunk program (three
    dispatches) or the fused window (one): every emitted token is the
    reference's best at its position, judged by the logit gap as the
    benchmark's ``correct`` judges it."""
    cfg, params = model
    slots, chunk, rounds = 4, 4, 3
    pool = slots_mod.slot_cache(cfg, slots, MAX_LEN)
    state = slots_mod.init_slot_state(cfg, slots)
    prompts = [ids(n, seed=n) for n in (9, 16, 21)]
    firsts = []
    for slot, prompt in enumerate(prompts):
        logits, row = _jitted_prefill(cfg, MAX_LEN)(
            params, jnp.asarray(prompt)[None])
        first = int(np.argmax(np.asarray(logits)[0]))
        firsts.append(first)
        pool = slots_mod.insert_row(pool, row, slot, cfg)
        state = slots_mod.admit_slot_state(
            state, slot, cfg, last=first,
            key=jax.random.PRNGKey(slot), temperature=0.0, top_k=0,
            top_p=1.0, eos_id=-1, pad_id=0, min_new=0, presence=0.0,
            frequency=0.0,
            bias_idx=np.full((slots_mod.BIAS_SLOTS_MAX,), -1),
            bias_val=np.zeros((slots_mod.BIAS_SLOTS_MAX,)), done=False)
    if program == "chunk":
        pieces = []
        for _ in range(rounds):
            pool, state, toks, stats = slots_mod.decode_slots_chunk(
                params, pool, state, cfg, chunk, with_stats=True)
            pieces.append(np.asarray(toks))
        toks = np.concatenate(pieces, axis=1)
    else:
        pool, state, toks, run, stats = slots_mod.decode_slots_window(
            params, pool, state, cfg, chunk, rounds,
            np.full((slots,), 100), with_stats=True)
        assert int(run) == rounds
        toks = np.asarray(toks)
    # the last program call routed chunk (or rounds x chunk) steps of
    # 4 rows through 2 sparse layers
    steps = chunk if program == "chunk" else chunk * rounds
    assert int(stats[0]) == steps * slots * cfg.n_sparse
    head = len(mla_moe.STATS_HEAD)
    assert int(stats[1]) == int(np.asarray(stats[head:]).sum()) > 0
    assert 0 < int(stats[2]) <= int(stats[3]) == steps * cfg.n_sparse * 16
    # the experts' kernel ran whole row tiles: at least a tile a touched
    # (expert, layer, step), every assignment in one
    tile = moe.expert_block(slots, cfg.experts_per_tok, cfg.router_experts)
    assert int(stats[2]) <= int(stats[4])
    assert int(stats[1]) <= int(stats[5]) == int(stats[4]) * tile
    for slot, prompt in enumerate(prompts):
        served = [firsts[slot]] + [int(t) for t in toks[slot]]
        row = np.concatenate([prompt, served])[:-1]
        ref = np.asarray(R.all_logits(TOY, row))
        at = np.arange(len(prompt) - 1, len(row))
        gaps = ref[at].max(axis=-1) - ref[at, served]
        assert gaps.max() < TOL, (slot, gaps)
    assert list(np.asarray(pool["pos"])[:3]) == [
        len(p) + chunk * rounds for p in prompts]


# -- sorted dispatch against masked-dense experts -------------------------


def _experts(held_lo, held_n, seed=0, d=64, f=32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {
        "e_gate": jax.random.normal(keys[0], (held_n, d, f)) * d ** -0.5,
        "e_up": jax.random.normal(keys[1], (held_n, d, f)) * d ** -0.5,
        "e_down": jax.random.normal(keys[2], (held_n, f, d)) * f ** -0.5,
    }


def _routing(case, n, k, experts):
    rng = np.random.default_rng(7)
    idx = np.stack([rng.permutation(experts)[:k] for _ in range(n)])
    if case == "one_gets_all_one_gets_none":
        # expert 5 is every token's first choice; expert 6 nobody's
        idx = np.where(idx == 6, 7, idx)
        for row in idx:
            if 5 not in row:
                row[0] = 5
            # a token chooses k DISTINCT experts
            seen = set()
            for j, e in enumerate(row):
                while int(row[j]) in seen or int(row[j]) == 6:
                    row[j] = (int(row[j]) + 1) % experts
                seen.add(int(row[j]))
        assert (idx == 5).any(axis=1).all() and not (idx == 6).any()
    gates = rng.uniform(0.1, 1.0, (n, k)).astype(np.float32)
    return idx.astype(np.int32), gates


@pytest.mark.parametrize("case,n,held", [
    ("random", 24, (0, 16)),
    ("one_gets_all_one_gets_none", 24, (0, 16)),
    ("random", 5, (4, 8)),        # a share in the middle, a ragged block
    ("random", 200, (12, 16)),    # more tokens than one block holds
])
def test_sparse_dispatch_matches_masked_dense_experts(case, n, held):
    lo, hi = held
    w = _experts(lo, hi - lo)
    idx, gates = _routing(case, n, 4, 16)
    h = jax.random.normal(jax.random.PRNGKey(1), (n, 64))
    out, counts = moe.sparse_experts(
        h, jnp.asarray(idx), jnp.asarray(gates), w["e_gate"], w["e_up"],
        w["e_down"], lo, 16)
    want = R.experts_part(h, jnp.asarray(idx), jnp.asarray(gates), w, lo, hi)
    assert np.abs(np.asarray(out) - np.asarray(want)).max() < TOL
    assert list(np.asarray(counts)) == [
        int((idx == e).sum()) for e in range(lo, hi)]
    if case.startswith("one"):
        assert int(counts[5]) == n and int(counts[6]) == 0


def test_router_is_sigmoid_top_k_renormalised_and_scaled():
    h = jax.random.normal(jax.random.PRNGKey(2), (6, 64))
    w = jax.random.normal(jax.random.PRNGKey(3), (64, 16)) * 0.3
    idx, gate = moe.route_topk(h, w, 4, 2.5)
    scores = np.asarray(jax.nn.sigmoid(h @ w))
    for row, (chosen, gates) in enumerate(zip(np.asarray(idx), np.asarray(gate))):
        best = np.argsort(-scores[row])[:4]
        assert sorted(chosen) == sorted(best)
        assert np.allclose(gates.sum(), 2.5, atol=1e-5)
        assert np.allclose(gates / gates.sum(),
                           scores[row, chosen] / scores[row, chosen].sum(),
                           atol=1e-6)


# -- the share and the model ------------------------------------------------


def test_sixteen_shares_add_up_to_the_uncut_layer(model):
    """Sixteen processes that hold one expert each: their routed parts,
    with the shared expert counted once, are the reference's whole
    expert layer. The held experts' weights do not depend on the share
    (an expert's key is folded with its global index)."""
    cfg, params = model
    layer = 1
    whole = R.layer_weights(TOY, layer)
    h = jax.random.normal(jax.random.PRNGKey(5), (12, 64))
    idx_r, gates_r, _edge, _at_held = R.route(h, whole["router"], TOY)
    want = R.experts_part(h, idx_r, gates_r, whole, 0, 16) + R._swiglu(
        h, whole["s_gate"], whole["s_up"], whole["s_down"])
    total = jnp.zeros_like(h)
    for share in range(16):
        config = dict(TOY, n_routed_experts=1, share={
            "router_experts": 16, "held_experts": [share, share + 1]})
        cfg_s, params_s = widened(config)
        lp = params_s["layers"][layer]
        assert np.array_equal(np.asarray(lp["e_gate"][0]),
                              np.asarray(whole["e_gate"][share]))
        idx, gate = moe.route_topk(
            h, lp["router"], cfg_s.experts_per_tok, cfg_s.routed_scale)
        part, counts = moe.sparse_experts(
            h, idx, gate, lp["e_gate"], lp["e_up"], lp["e_down"],
            cfg_s.held_lo, cfg_s.router_experts)
        assert int(counts[0]) == int((np.asarray(idx) == share).sum())
        total = total + part
    lp = params["layers"][layer]
    total = total + mla_moe._swiglu(
        h, lp["s_gate"], lp["s_up"], lp["s_down"], jnp.float32)
    assert np.abs(np.asarray(total) - np.asarray(want)).max() < TOL


def test_a_share_leaves_out_what_absent_experts_add(model):
    """Holding 4 of 16 experts: the program and the reference, given
    the same share, agree; and both differ from the uncut model."""
    config = dict(TOY, n_routed_experts=4, share={
        "router_experts": 16, "held_experts": [0, 4]})
    cfg, params = widened(config)
    toks = ids(20, seed=11)
    mine = np.asarray(mla_moe.forward(params, jnp.asarray(toks)[None], cfg))[0]
    assert np.abs(mine - np.asarray(R.all_logits(config, toks))).max() < TOL
    assert np.abs(mine - np.asarray(R.all_logits(TOY, toks))).max() > 0.05


def test_sliced_vocabulary_has_the_uncut_logits_on_the_slice():
    big = dict(TOY, vocab_size=512)
    cfg_b, params_b = widened(big)
    cfg_s, params_s = widened(TOY)
    toks = jnp.asarray(ids(16, seed=9, vocab=256))[None]
    whole = np.asarray(mla_moe.forward(params_b, toks, cfg_b))[0]
    sliced = np.asarray(mla_moe.forward(params_s, toks, cfg_s))[0]
    assert np.abs(whole[:, :256] - sliced).max() < 1e-5


def test_weights_are_held_in_bfloat16_and_follow_the_stated_recipe():
    cfg = mla_moe.from_published(TOY, MAX_LEN)
    params = mla_moe.init_params(None, cfg)
    matrices = [x for x in jax.tree.leaves(params) if x.ndim >= 2]
    assert matrices and all(x.dtype == jnp.bfloat16 for x in matrices)
    ref = R.layer_weights(TOY, 2)
    for name in ("w_dq", "w_ukv", "router", "s_down", "e_up"):
        assert np.array_equal(
            np.asarray(params["layers"][2][name].astype(jnp.float32)),
            np.asarray(ref[name])), name
    assert np.array_equal(
        np.asarray(params["unembed"].astype(jnp.float32)),
        np.asarray(R.vocab_weights(TOY, "unembed", 64 ** -0.5)).T)


def test_published_widths_yarn_and_scale_of_the_benchmark_file():
    cfg = modelcfg.load_model_file(REAL_FILE, 3072)
    assert (cfg.d_model, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank) == (
        7168, 64, 1536, 512)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (
        128, 64, 128)
    assert (cfg.d_ff, cfg.moe_d_ff, cfg.router_experts, cfg.experts_per_tok,
            cfg.routed_scale) == (18432, 2048, 192, 8, 2.5)
    assert (cfg.held_lo, cfg.held_n, cfg.n_layers, cfg.first_dense) == (
        0, 12, 6, 1)
    assert abs(cfg.softmax_scale - 0.13086) < 1e-5
    with open(REAL_FILE) as fh:
        config = json.load(fh)
    freqs = np.asarray(mla_moe._inv_freq(cfg))
    assert np.allclose(freqs, np.asarray(R.inv_freq(config)), rtol=1e-6)
    # the fastest pairs keep the published frequency, the slowest are
    # stretched by the factor
    assert np.isclose(freqs[0], 1.0) and np.isclose(
        freqs[-1], 10000 ** (-62 / 64) / 32)


# -- the prefix cache and the spill tier over latent rows --------------------


@pytest.mark.parametrize("path", ["rewind", "extend", "spill_readmit"])
def test_cached_latent_rows_give_the_logits_of_an_uncached_run(model, path):
    cfg, params = model
    spill = HostSpillTier(1 << 20)
    cache = PrefixCache(1, spill=spill)
    base = [int(t) for t in ids(32, seed=21)]
    _logits, row = _jitted_prefill(cfg, MAX_LEN)(
        params, jnp.asarray([base], jnp.int32))
    cache.store(tuple(base), row)
    latent = sum(x.nbytes for x in row["ckv"] + row["kpe"])
    assert cache.phases.latent_store_bytes == latent
    assert cache.phases.store_bytes == latent + row["pos"].nbytes
    if path == "rewind":
        wanted = base[:24] + [int(t) for t in ids(8, seed=22)]
    else:
        wanted = base + [int(t) for t in ids(8, seed=23)]
    if path == "spill_readmit":
        other = [int(t) for t in ids(32, seed=24)]
        _l, other_row = _jitted_prefill(cfg, MAX_LEN)(
            params, jnp.asarray([other], jnp.int32))
        cache.store(tuple(other), other_row)  # pushes ``base`` to the host
        assert cache.flush(timeout=30)  # the copy runs behind the store
        assert cache.stats["spilled"] == 1
        assert cache.phases.latent_spill_bytes == latent
    hit = reuse_admission(cache, wanted, cfg, params)
    assert hit is not None and cache.stats["hits"] == 1
    if path == "spill_readmit":
        assert cache.stats["readmitted"] == 1
        assert cache.phases.latent_readmit_bytes == latent
    logits, extended = hit
    cold, cold_row = _jitted_prefill(cfg, MAX_LEN)(
        params, jnp.asarray([wanted], jnp.int32))
    assert np.abs(np.asarray(logits) - np.asarray(cold)).max() < TOL
    assert int(extended["pos"]) == len(wanted)
    n = len(wanted)
    for mine, theirs in zip(extended["ckv"], cold_row["ckv"]):
        assert np.abs(np.asarray(mine)[:, :n] - np.asarray(theirs)[:, :n]).max() < TOL


def test_rows_of_keys_and_values_count_no_latent_bytes():
    from containerpilot_tpu.kvtier.spill import latent_nbytes

    assert latent_nbytes({"k": np.zeros((2, 3)), "v": np.zeros((2, 3)),
                          "pos": np.zeros(())}) == 0


# -- the step program's counters, the CLI, the fingerprint -------------------


def test_step_program_returns_the_experts_counters_with_the_tokens():
    cfg = mla_moe.from_published(dict(TOY, n_routed_experts=4, share={
        "router_experts": 16, "held_experts": [4, 8]}), MAX_LEN)
    params = mla_moe.init_params(None, cfg)
    program = make_step_program(cfg, params, MAX_LEN, slots=2, chunk=4, rounds=2)
    assert program.expert_stats()["rows"] == 0
    for fused, steps in ((False, 4), (True, 8)):
        before = program.expert_stats()
        toks, valid, rounds_run = program.tokens(
            program.dispatch(np.asarray([100, 100]), fused))
        after = program.expert_stats()
        # every slot is empty (done): the window exits after no round
        ran = steps if not fused else rounds_run * 4
        assert after["rows"] - before["rows"] == ran * 2 * cfg.n_sparse
    stats = program.expert_stats()
    assert stats["published"] == 16 and stats["held"] == [4, 8]
    assert stats["assignments_here"] == sum(stats["load"])
    # empty slots all decode the pad token: they may touch no held expert
    assert 0 <= stats["expert_steps_touched"] <= stats["expert_steps"]
    assert stats["expert_steps"] == stats["rows"] // 2 * 4


def test_a_dense_model_has_no_experts_counters():
    from containerpilot_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=1,
                            d_ff=128, max_seq_len=32)
    program = make_step_program(
        cfg, init_params(jax.random.PRNGKey(0), cfg), 32, slots=2, chunk=2)
    program.tokens(program.dispatch(np.asarray([4, 4]), False))
    assert program.expert_stats() is None and program.stats_total is None


def test_serve_cli_builds_the_model_from_a_file():
    from containerpilot_tpu.workload import serve_cli

    args = serve_cli.build_arg_parser().parse_args(
        ["--model-config", TOY_FILE, "--max-len", "128"])
    cfg, params, _mesh = serve_cli.load_model(args)
    assert isinstance(cfg, mla_moe.MlaMoeConfig)
    assert (cfg.router_experts, cfg.held_n, cfg.max_seq_len) == (16, 4, 128)
    assert params["layers"][1]["e_gate"].shape == (4, 64, 32)
    assert params["layers"][1]["e_gate"].dtype == jnp.bfloat16


@pytest.mark.parametrize("flags", [["--int8"], ["--kv-int8"], ["--window", "8"],
                                   ["--checkpoint-dir", "/nowhere"]])
def test_serve_cli_refuses_what_only_the_flagship_block_has(flags):
    from containerpilot_tpu.workload import serve_cli

    args = serve_cli.build_arg_parser().parse_args(
        ["--model-config", TOY_FILE, *flags])
    with pytest.raises(SystemExit, match="does not compose"):
        serve_cli.load_model(args)


def test_a_file_of_another_architecture_is_refused_by_name():
    path = os.path.join(ROOT, "benchmark", "configs", "mistral-7b-serve.json")
    with pytest.raises(SystemExit, match="no builder"):
        modelcfg.load_model_file(path, 64)


def test_warmup_fingerprint_tells_shares_and_files_apart(tmp_path):
    def fingerprint(config):
        path = tmp_path / "model.json"
        path.write_text(json.dumps(config))
        cfg = modelcfg.load_model_file(str(path), 64)
        return modelcfg.warmup_fingerprint(cfg, 64, slots=2, slot_chunk=2)

    first = dict(TOY, n_routed_experts=4, share={
        "router_experts": 16, "held_experts": [0, 4]})
    other_share = dict(first, share={
        "router_experts": 16, "held_experts": [4, 8]})
    other_note = dict(first, note="edited")
    prints = {fingerprint(c) for c in (first, other_share, other_note)}
    assert len(prints) == 3
    assert fingerprint(first) in prints
