"""Step-program interface + fused K-round decode windows
(models/stepprog.py, models/slots.py::decode_slots_window,
models/speculative.py::SpeculativeStepProgram): byte parity between
fused and sequential decode at the models level AND the engine level,
speculative-as-step-program parity with speculative_generate,
cancel-mid-window retirement with the PR 9 decode-accounting
contract, honest dispatch counters under fusion, and the contract's
optional ``attach_phases`` member (a program that takes the engine's
phases opens ``engine.admit.first_token``'s children; one that brings
no such member records none)."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from containerpilot_tpu.models.decode import (
    BIAS_SLOTS_MAX,
    _jitted_prefill,
    generate,
)
from containerpilot_tpu.models.slots import (
    admit_slot_state,
    decode_slots_chunk,
    decode_slots_window,
    first_sample,
    init_slot_state,
    insert_row,
    slot_cache,
)
from containerpilot_tpu.models.transformer import (
    TransformerConfig,
    init_params,
)
from containerpilot_tpu.telemetry.goodput import FIRST_TOKEN_PHASES
from containerpilot_tpu.workload.serve_slots import SlotEngine

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
    max_seq_len=64, dtype=jnp.float32,
)
MAX_LEN = 48


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _solo(params, tokens, max_new, cfg=CFG, **kw):
    """Solo generate with the server key convention, server-trimmed."""
    seed = kw.pop("seed", 0)
    eos = kw.pop("eos_id", -1)
    out = generate(
        params, jnp.asarray([tokens], jnp.int32), cfg, max_new,
        MAX_LEN,
        rng=jnp.stack([jax.random.fold_in(jax.random.PRNGKey(seed), 0)]),
        eos_id=eos, **kw,
    )
    row = [int(t) for t in np.asarray(out)[0]]
    if eos >= 0 and eos in row:
        row = row[: row.index(eos) + 1]
    return row


def _admitted_pool(params, tokens, seed=7, temperature=0.8, top_k=12):
    """A 2-slot pool with one sampled request admitted at slot 0 —
    shared setup for the models-level window-vs-sequential tests."""
    pool = slot_cache(CFG, 2, MAX_LEN)
    state = init_slot_state(CFG, 2)
    prompt = jnp.asarray([tokens], jnp.int32)
    logits, row = _jitted_prefill(CFG, MAX_LEN)(params, prompt)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
    bias_idx = jnp.full((BIAS_SLOTS_MAX,), -1, jnp.int32)
    bias_val = jnp.zeros((BIAS_SLOTS_MAX,), jnp.float32)
    first = first_sample(
        logits, key, temperature, top_k, 0.0, CFG,
        bias_idx=bias_idx, bias_val=bias_val,
    )
    pool = insert_row(pool, row, 0, CFG)
    state = admit_slot_state(
        state, 0, CFG, last=first, key=key,
        temperature=temperature, top_k=top_k, top_p=0.0, eos_id=-1,
        pad_id=0, min_new=0, presence=0.0, frequency=0.0,
        bias_idx=bias_idx, bias_val=bias_val, done=False,
    )
    return pool, state


def test_window_matches_sequential_chunks(params):
    """The tentpole's byte-parity contract at the models level: one
    fused K-round window emits bit-identical tokens to K sequential
    decode_slots_chunk dispatches AND leaves every state leaf
    bit-identical — the window's while_loop body is the same traced
    per-step scan, so this is equality by construction, pinned."""
    chunk, k_rounds = 3, 4
    pool, state = _admitted_pool(params, [1, 2, 3, 4])
    seq_toks = []
    for _ in range(k_rounds):
        pool, state, toks = decode_slots_chunk(
            params, pool, state, CFG, chunk
        )
        seq_toks.append(np.asarray(jax.device_get(toks)))
    sequential = np.concatenate(seq_toks, axis=1)
    seq_state = {
        name: np.asarray(jax.device_get(leaf))
        for name, leaf in state.items()
    }

    pool2, state2 = _admitted_pool(params, [1, 2, 3, 4])
    budget = np.asarray([chunk * k_rounds, 0], np.int32)
    pool2, state2, toks, run = decode_slots_window(
        params, pool2, state2, CFG, chunk, k_rounds, budget
    )
    assert int(jax.device_get(run)) == k_rounds
    assert np.array_equal(
        np.asarray(jax.device_get(toks)), sequential
    )
    for name, leaf in state2.items():
        assert np.array_equal(
            np.asarray(jax.device_get(leaf)), seq_state[name]
        ), f"state leaf {name} diverged"


@pytest.mark.parametrize("window_reads,chunks_read", [
    (32, 32), (32, None), (None, 32), (16, 32),
], ids=["32-32", "32-whole", "whole-32", "16-32"])
def test_window_matches_sequential_chunks_rung_for_rung(
        params, window_reads, chunks_read):
    """The parity contract under a ladder of read lengths (ISSUE 41):
    a window and the chunk dispatches it stands for may run different
    rungs. At EQUAL ``read_len`` they are the same computation, tokens
    and every state leaf bit for bit; across rungs that all cover the
    row (4 prompt tokens + 12 steps = 16 positions) the sums differ by
    exact zeros, the sampled tokens are the same token for token and
    the penalty counts, a function of the tokens alone, with them."""
    chunk, k_rounds = 3, 4
    pool, state = _admitted_pool(params, [1, 2, 3, 4])
    seq_toks = []
    for _ in range(k_rounds):
        pool, state, toks = decode_slots_chunk(
            params, pool, state, CFG, chunk, read_len=chunks_read
        )
        seq_toks.append(np.asarray(jax.device_get(toks)))
    sequential = np.concatenate(seq_toks, axis=1)
    pool2, state2 = _admitted_pool(params, [1, 2, 3, 4])
    budget = np.asarray([chunk * k_rounds, 0], np.int32)
    pool2, state2, toks, run = decode_slots_window(
        params, pool2, state2, CFG, chunk, k_rounds, budget,
        read_len=window_reads,
    )
    assert int(jax.device_get(run)) == k_rounds
    assert np.array_equal(np.asarray(jax.device_get(toks)), sequential)
    for name, leaf in state2.items():
        assert np.array_equal(
            np.asarray(jax.device_get(leaf)),
            np.asarray(jax.device_get(state[name])),
        ), f"state leaf {name} diverged"
    if window_reads == chunks_read:
        for name in ("k", "v"):
            for a, b in zip(pool[name], pool2[name]):
                assert np.array_equal(np.asarray(a), np.asarray(b))


def test_window_early_exit_on_budget_and_done(params):
    """The device loop stops once every slot is done or out of
    budget: a 2-token budget exits after one 3-token round, and the
    skipped rounds' token columns stay at pad."""
    chunk, k_rounds = 3, 4
    pool, state = _admitted_pool(params, [1, 2, 3, 4])
    # one reference round for the executed prefix
    ref_pool, ref_state = _admitted_pool(params, [1, 2, 3, 4])
    _rp, _rs, ref = decode_slots_chunk(
        params, ref_pool, ref_state, CFG, chunk
    )
    ref = np.asarray(jax.device_get(ref))

    pool, state, toks, run = decode_slots_window(
        params, pool, state, CFG, chunk, k_rounds,
        np.asarray([2, 0], np.int32),
    )
    toks = np.asarray(jax.device_get(toks))
    assert int(jax.device_get(run)) == 1
    assert np.array_equal(toks[:, :chunk], ref)
    assert (toks[:, chunk:] == 0).all()  # pad_id 0 fill
    # an all-dead pool (budget 0 everywhere) runs zero rounds
    pool, state, toks, run = decode_slots_window(
        params, pool, state, CFG, chunk, k_rounds,
        np.zeros((2,), np.int32),
    )
    assert int(jax.device_get(run)) == 0


@pytest.mark.parametrize("window", [2, 4])
def test_engine_fused_parity_with_window_one(params, window):
    """Engine-level byte parity: the same request mix — greedy,
    sampled, eos-stopped, penalized — produces identical outputs on a
    fused engine and a window=1 engine, and both match solo
    generate."""
    reqs = [
        ([1, 2, 3, 4], dict(max_new=12)),
        ([5, 6, 7], dict(max_new=9, temperature=0.9, top_k=12,
                         top_p=0.8, seed=11)),
        ([1, 2, 3], dict(max_new=8, temperature=0.7, seed=8,
                         frequency_penalty=50.0)),
    ]
    results = {}
    for w in (1, window):
        eng = SlotEngine(CFG, params, MAX_LEN, slots=2, chunk=3,
                         window=w)
        try:
            futs = [eng.submit(list(t), **dict(kw)) for t, kw in reqs]
            results[w] = [f.result(timeout=180) for f in futs]
        finally:
            eng.stop()
    assert results[1] == results[window]
    for (tokens, kw), got in zip(reqs, results[window]):
        kw = dict(kw)
        max_new = kw.pop("max_new")
        assert got == _solo(params, tokens, max_new, **kw)


def test_engine_fused_eos_parity(params):
    """eos inside a fused window trims exactly like generate: the row
    keeps the eos, drops the pads after it."""
    tokens = [2, 4, 6]
    free = _solo(params, tokens, 9)
    eos = free[1]
    eng = SlotEngine(CFG, params, MAX_LEN, slots=2, chunk=3, window=4)
    try:
        got = eng.submit(tokens, max_new=9, eos_id=eos).result(
            timeout=120
        )
    finally:
        eng.stop()
    assert got == _solo(params, tokens, 9, eos_id=eos)
    assert got[-1] == eos


def test_fused_dispatch_counters_honest(params):
    """dispatches bumps once per DEVICE dispatch (not per fused
    round) and tokens_out counts every round's emissions: a K=4
    engine decodes the same long request with well under half the
    K=1 engine's dispatches/token."""
    dpt = {}
    for w in (1, 4):
        eng = SlotEngine(CFG, params, MAX_LEN, slots=2, chunk=3,
                         window=w)
        try:
            # warm admission programs, then snapshot
            eng.submit([1, 2], max_new=2).result(timeout=120)
            d0, t0 = eng.dispatches, eng.tokens_out
            out = eng.submit([1, 2, 3, 4], max_new=36).result(
                timeout=180
            )
            assert len(out) == 36
            d, t = eng.dispatches - d0, eng.tokens_out - t0
            assert t >= 36  # every round's emissions counted
            dpt[w] = d / t
        finally:
            eng.stop()
    assert dpt[4] <= 0.5 * dpt[1], dpt


def test_cancel_mid_window_retires_within_one_window(params):
    """A cancel lands at the NEXT window boundary, not the end of the
    generation: the slot frees with a partial emission and the
    request's engine timings carry the abandon-instant ``done`` stamp
    (decode accounted up to the abandon, the PR 9 tracing
    contract)."""
    eng = SlotEngine(CFG, params, MAX_LEN, slots=2, chunk=2, window=4)
    try:
        cancel = threading.Event()
        first = threading.Event()
        timings = {}

        def on_tokens(_delta):
            first.set()

        max_new = MAX_LEN - 3
        fut = eng.submit(
            [5, 6, 7], max_new=max_new, on_tokens=on_tokens,
            cancel=cancel, timings=timings,
        )
        assert first.wait(timeout=120), "no first token"
        abandoned_at = time.monotonic()
        cancel.set()
        got = fut.result(timeout=120)
        assert 0 < len(got) < max_new, (
            f"cancel did not stop decode early ({len(got)}/{max_new})"
        )
        # the engine stamped done at the sweep (>= the abandon
        # instant, within the one-window reaction bound) and recorded
        # the rounds the row actually rode
        assert timings["done"] >= timings["admitted"]
        assert timings["done"] >= abandoned_at
        assert timings["rounds"] >= 1
        # the slot is back; the pool keeps serving with parity
        deadline = time.monotonic() + 30
        while eng.stats["active"]:
            assert time.monotonic() < deadline
            time.sleep(0.05)
        after = eng.submit([1, 2, 3, 4], max_new=7).result(timeout=120)
        assert after == _solo(params, [1, 2, 3, 4], 7)
    finally:
        eng.stop()


# ---------------------------------------------------------- programs


def test_make_step_program_picks_quantized():
    from containerpilot_tpu.models.quantized import (
        QuantizedStepProgram,
        quantize_model_params,
    )
    from containerpilot_tpu.models.stepprog import (
        PlainStepProgram,
        make_step_program,
    )

    params = init_params(jax.random.PRNGKey(0), CFG)
    plain = make_step_program(CFG, params, MAX_LEN, 2, 3)
    assert type(plain) is PlainStepProgram
    qparams = quantize_model_params(params)
    quant = make_step_program(CFG, qparams, MAX_LEN, 2, 3, rounds=4)
    assert isinstance(quant, QuantizedStepProgram)
    assert quant.rounds == 4
    # a full-precision pytree must fail loudly, not serve 4x HBM
    with pytest.raises(ValueError, match="quantize_model_params"):
        QuantizedStepProgram(CFG, params, MAX_LEN, 2, 3)


def test_quantized_program_decodes_through_engine():
    """int8 weights under the fused engine: the engine drives the
    quantized step program end to end and output matches the
    quantized params' own solo generate (same weights, same keys)."""
    from containerpilot_tpu.models.quantized import (
        quantize_model_params,
    )

    params = init_params(jax.random.PRNGKey(0), CFG)
    qparams = quantize_model_params(params)
    eng = SlotEngine(CFG, qparams, MAX_LEN, slots=2, chunk=3,
                     window=4)
    try:
        assert type(eng.program).__name__ == "QuantizedStepProgram"
        got = eng.submit([1, 2, 3], max_new=8).result(timeout=180)
        assert got == _solo(qparams, [1, 2, 3], 8)
    finally:
        eng.stop()


def _spec_setup():
    from containerpilot_tpu.models.speculative import (
        layer_prefix_draft,
    )

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_seq_len=64, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    dparams, dcfg = layer_prefix_draft(params, cfg, 1)
    return cfg, params, dcfg, dparams


def test_speculative_program_matches_speculative_generate():
    """The speculative step program through the engine emits exactly
    what speculative_generate emits (trimmed) on the same prompts —
    greedy, eos-stopped, and max_new-capped."""
    from containerpilot_tpu.models.speculative import (
        SpeculativeStepProgram,
        speculative_generate,
    )

    cfg, params, dcfg, dparams = _spec_setup()
    eng = SlotEngine(
        cfg, params, MAX_LEN,
        program=SpeculativeStepProgram(
            cfg, dcfg, params, dparams, MAX_LEN, speculate=4
        ),
    )
    try:
        assert eng.stats["slots"] == 1
        cases = [([1, 2, 3, 4], 12, -1), ([5, 6], 10, -1)]
        # derive an eos that actually occurs mid-stream
        ref, _ = speculative_generate(
            params, dparams, jnp.asarray([[2, 4, 6]], jnp.int32),
            cfg, dcfg, max_new_tokens=16, max_len=MAX_LEN,
            speculate=4,
        )
        cases.append(([2, 4, 6], 16, int(np.asarray(ref)[0][1])))
        ref_rounds = 0
        for tokens, max_new, eos in cases:
            ref, stats = speculative_generate(
                params, dparams, jnp.asarray([tokens], jnp.int32),
                cfg, dcfg, max_new_tokens=max_new, max_len=MAX_LEN,
                speculate=4, eos_id=eos,
            )
            ref_rounds += stats["rounds"]
            ref_row = [int(t) for t in np.asarray(ref)[0]]
            if eos >= 0 and eos in ref_row:
                ref_row = ref_row[: ref_row.index(eos) + 1]
            got = eng.submit(tokens, max_new=max_new,
                             eos_id=eos).result(timeout=180)
            assert got == ref_row, (tokens, got, ref_row)
        # dispatch honesty, exactly: one dispatch per admission plus
        # dispatch_cost=2 (draft + verify) per round — and the engine
        # rode the SAME round count the standalone loop did (same k
        # clamps, same eos/max_new exits)
        assert eng.dispatches == len(cases) + 2 * ref_rounds
    finally:
        eng.stop()
    # the program brings no ``attach_phases`` (an optional member):
    # the engine records the admission's own phases and no child of
    # ``engine.admit.first_token``
    assert not hasattr(SpeculativeStepProgram, "attach_phases")
    assert eng.phases.phase_n["engine.admit.first_token"] == len(cases)
    assert not any(
        eng.phases.phase_n[child] for child in FIRST_TOKEN_PHASES)


def test_the_engine_hands_its_phases_to_a_program_that_takes_them(params):
    """The contract's optional member ``attach_phases``: the default
    program is handed the engine's ``EnginePhases`` at construction
    (the ledger's, where the engine has one), and its ``admit`` opens
    the children of ``engine.admit.first_token`` on it."""
    from containerpilot_tpu.telemetry.goodput import DeviceTimeLedger

    ledger = DeviceTimeLedger()
    eng = SlotEngine(CFG, params, MAX_LEN, slots=2, chunk=4, ledger=ledger)
    try:
        assert eng.program.phases is eng.phases is ledger.engine
        eng.submit([1, 2, 3], max_new=3).result(timeout=120)
    finally:
        eng.stop()
    assert [ledger.engine.phase_n[c] for c in FIRST_TOKEN_PHASES] == [1] * 4


def test_speculative_program_rejects_bad_shapes():
    import dataclasses

    from containerpilot_tpu.models.speculative import (
        SpeculativeStepProgram,
    )

    cfg, params, dcfg, dparams = _spec_setup()
    with pytest.raises(ValueError, match="speculate"):
        SpeculativeStepProgram(cfg, dcfg, params, dparams, MAX_LEN,
                               speculate=0)
    win = dataclasses.replace(cfg, window=8)
    with pytest.raises(ValueError, match="window"):
        SpeculativeStepProgram(win, dcfg, params, dparams, MAX_LEN)


def test_server_speculative_rides_engine(run):
    """Server-level: a greedy /v1/generate on a --draft-layers server
    routes through the speculative ENGINE (not the slot engine),
    matches plain greedy decode, and folds its dispatch/token pair
    into /v1/model + /v1/goodput."""
    import asyncio
    import json
    import urllib.request

    from containerpilot_tpu.workload.serve import InferenceServer

    cfg, params, _dcfg, _dparams = _spec_setup()
    server = InferenceServer(
        cfg, params, "127.0.0.1", 0, max_len=MAX_LEN,
        draft_layers=1, speculate=4,
    )

    def fetch(path, body=None):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}{path}",
            data=json.dumps(body).encode() if body is not None
            else None,
            headers={"Content-Type": "application/json"}
            if body else {},
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            return json.loads(resp.read().decode())

    async def scenario():
        await server.run()
        loop = asyncio.get_event_loop()
        out = await loop.run_in_executor(
            None, lambda: fetch(
                "/v1/generate",
                {"tokens": [[1, 2, 3]], "max_new_tokens": 10},
            )
        )
        info = await loop.run_in_executor(
            None, lambda: fetch("/v1/model")
        )
        gp = await loop.run_in_executor(
            None, lambda: fetch("/v1/goodput")
        )
        await server.stop()
        return out, info, gp

    out, info, gp = run(scenario())
    expect = _solo(params, [1, 2, 3], 10, cfg=cfg)
    assert out["tokens"][0] == expect
    spec = info["speculative"]
    assert spec["engine"]["slots"] == 1
    assert spec["engine"]["dispatches"] >= 1
    # the spec engine's counters fold into the goodput pair
    assert gp["dispatches"] >= spec["engine"]["dispatches"]
    assert gp["tokens_out"] >= len(expect)


def test_tiny_max_len_clamps_window(params):
    """A max_len too small for the fused warmup request clamps the
    server's engine back to window 1 instead of leaving the fused
    program to compile under a live request (the boundary the
    PR-guard test pins stays valid: 4 + chunk + 1 == max_len)."""
    from containerpilot_tpu.workload.serve import InferenceServer

    server = InferenceServer(
        CFG, params, "127.0.0.1", 0, max_len=9, slots=1, slot_chunk=4,
    )
    assert server.slot_engine.window == 1
    roomy = InferenceServer(
        CFG, params, "127.0.0.1", 0, max_len=MAX_LEN, slots=1,
        slot_chunk=4,
    )
    assert roomy.slot_engine.window == 4


def test_warmup_fingerprint_includes_window():
    from containerpilot_tpu.workload.modelcfg import warmup_fingerprint

    a = warmup_fingerprint(CFG, MAX_LEN, slots=2, slot_chunk=4,
                           slot_window=1)
    b = warmup_fingerprint(CFG, MAX_LEN, slots=2, slot_chunk=4,
                           slot_window=4)
    assert a != b


# --- an admission is one packed host row and one device program
# (ISSUE 43): counted as the benchmark's
# ``admit_device_programs_per_admission`` counts it, from a profiler
# trace; the CPU client leaves one ``PjRtCpuExecutable::Execute`` event
# on the calling thread's line per program it runs, whoever issued it
# (a jitted function, or the ``convert_element_type`` of a
# ``jnp.asarray`` of a Python scalar)

def _programs_run(trace_dir) -> int:
    import glob

    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    assert paths, f"no trace under {trace_dir}"
    return sum(
        event.name == "PjRtCpuExecutable::Execute"
        for plane in ProfileData.from_file(paths[-1]).planes
        for line in plane.lines for event in line.events
    )


def _traced(trace_dir, work) -> int:
    jax.profiler.start_trace(str(trace_dir))
    try:
        jax.block_until_ready(work())
    finally:
        jax.profiler.stop_trace()
    return _programs_run(trace_dir)


@pytest.mark.parametrize("kind", ["greedy", "sampled-biased"])
def test_an_admission_is_two_device_programs_and_a_retire_one(
        params, tmp_path, kind):
    """Prefill + ``admit_row``: every number of the request crosses in
    the packed row, so nothing else runs (no put of a scalar, no key
    built on the host), and ``retire`` is its one write. The three
    calls the program stands for, issued as they were, run an order of
    magnitude more (``_admitted_pool``): the count sees what it is
    there to see."""
    from containerpilot_tpu.models.decode import normalize_logit_bias
    from containerpilot_tpu.models.stepprog import PlainStepProgram
    from containerpilot_tpu.workload.serve_slots import _Request

    bias = {9: 4.0, 17: -3.0} if kind != "greedy" else None
    idx, val = normalize_logit_bias(CFG, 1, bias, slots=BIAS_SLOTS_MAX)
    knobs = {} if kind == "greedy" else dict(
        temperature=0.8, top_k=5, top_p=0.9, presence=0.2, min_new=1)
    program = PlainStepProgram(CFG, params, MAX_LEN, slots=2, chunk=3)
    prefill = _jitted_prefill(CFG, MAX_LEN)

    def request(seed):
        base = dict(temperature=0.0, top_k=0, top_p=0.0)
        base.update(knobs)
        return _Request(
            tokens=[5, 6, 7, 8], max_new=8, eos_id=3, pad_id=0, seed=seed,
            bias_idx=idx[0], bias_val=val[0], **base)

    def admit(slot, seed):
        req = request(seed)
        logits, row = prefill(params, np.asarray([req.tokens], np.int32))
        return program.admit(slot, req, logits, row)

    # compile everything outside the traces
    admit(0, 1), program.retire(0), _admitted_pool(params, [5, 6, 7, 8])
    assert _traced(tmp_path / "admit", lambda: admit(1, 2)) == 2
    assert _traced(
        tmp_path / "retire",
        lambda: program.retire(1) or program._state) == 1
    # slot_cache and init_slot_state run programs of their own (zeros)
    old = _traced(
        tmp_path / "old", lambda: _admitted_pool(params, [5, 6, 7, 8]))
    assert old >= 20, old


def test_the_engine_admits_with_two_programs_a_request(params, tmp_path):
    """The same through the engine: between the start of a traced
    window and the requests' ends, two requests of one token each run
    2 programs an admission and 1 a retire and NO decode program,
    whatever thread issued them."""
    eng = SlotEngine(CFG, params, MAX_LEN, slots=2, chunk=3)
    try:
        eng.submit([1, 2, 3], max_new=1).result(timeout=120)  # compile
        eng.submit([1, 2, 3, 4], max_new=1).result(timeout=120)

        def work():
            for prompt in ([4, 5, 6], [7, 8, 9, 10]):
                eng.submit(prompt, max_new=1, temperature=0.9, top_k=4,
                           seed=5).result(timeout=120)

        assert _traced(tmp_path, work) == 2 * (2 + 1)
    finally:
        eng.stop()
