"""The ladder of read lengths (ISSUE 41): a decode step reads the
flagship pool's rows only as far as the longest live row reaches.
``decode_chunk``'s static ``read_len`` cuts the read and nothing else
(same tokens, logits to float32 rounding; a cut BELOW a live row's
reach shows), the step program's host count never undercounts a live
row's position under the traffic an engine sees, the ladder follows
from ``max_len`` and the cache's form alone, and the server's warm-up
leaves every rung compiled. All on the CPU at toy widths with the
ladder's base patched down to 16, so three rungs fit in 128
positions: counts and values only, never a time."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from containerpilot_tpu.models import slots as slots_mod
from containerpilot_tpu.models.decode import (
    _jitted_prefill,
    decode_chunk,
)
from containerpilot_tpu.models.slots import (
    _jitted_chunk,
    _jitted_window,
    insert_row,
    read_ladder,
    slot_cache,
)
from containerpilot_tpu.models.stepprog import PlainStepProgram
from containerpilot_tpu.models.transformer import (
    TransformerConfig,
    init_params,
)
from containerpilot_tpu.workload.serve_prefix import PrefixCache
from containerpilot_tpu.workload.serve_slots import SlotEngine

MAX_LEN = 128
RUNGS = (16, 64, 128)
CHUNK, WINDOW = 4, 2


def _cfg(**kw):
    return TransformerConfig(**{
        **dict(vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2,
               n_layers=2, d_ff=64, max_seq_len=MAX_LEN,
               dtype=jnp.float32),
        **kw,
    })


CFG = _cfg()


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture
def short_ladder(monkeypatch):
    monkeypatch.setattr(slots_mod, "READ_LADDER_BASE", RUNGS[0])


# ---------------------------------------------------------- the read


def _pool_of(cfg, params, lengths):
    """A pool whose row i holds a prompt of ``lengths[i]`` tokens."""
    pool = slot_cache(cfg, len(lengths), MAX_LEN)
    rng = np.random.default_rng(7)
    for slot, n in enumerate(lengths):
        prompt = jnp.asarray(
            rng.integers(1, cfg.vocab_size, (1, n)), jnp.int32)
        _logits, row = _jitted_prefill(cfg, MAX_LEN)(params, prompt)
        pool = insert_row(pool, row, slot, cfg)
    return pool


def _greedy_steps(cfg, params, pool, steps, read_len):
    """``steps`` greedy steps of the whole pool: (tokens [S, steps],
    logits [S, steps, V])."""
    tok = jnp.arange(1, 1 + pool["pos"].shape[0], dtype=jnp.int32)
    toks, logits = [], []
    for _ in range(steps):
        out, pool = decode_chunk(
            params, pool, tok[:, None], cfg, read_len=read_len)
        tok = jnp.argmax(out[:, 0], axis=-1).astype(jnp.int32)
        toks.append(np.asarray(tok))
        logits.append(np.asarray(out[:, 0]))
    return np.stack(toks, 1), np.stack(logits, 1)


@pytest.mark.parametrize("kv_int8", [False, True], ids=["bf16kv", "int8kv"])
def test_a_rung_that_covers_the_rows_reads_what_the_whole_row_reads(
        kv_int8):
    """Rows at positions 5, 20 and 37, four steps each: every rung of
    at least 41 gives the whole-row read's greedy tokens and its
    logits to float32 rounding (the cut drops exact zeros from the
    softmax's sums), with the keys stored as they are or as int8 with
    the scales cut beside them."""
    cfg = _cfg(kv_int8=kv_int8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    pool = _pool_of(cfg, params, (5, 20, 37))
    whole_toks, whole_logits = _greedy_steps(cfg, params, pool, 4, None)
    for rung in (64, 128):
        toks, logits = _greedy_steps(cfg, params, pool, 4, rung)
        np.testing.assert_array_equal(toks, whole_toks)
        np.testing.assert_allclose(
            logits, whole_logits, rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("kv_int8", [False, True], ids=["bf16kv", "int8kv"])
def test_a_rung_below_a_live_rows_reach_changes_its_logits(kv_int8):
    """The check sees the fault it guards against: at ``read_len`` 32
    the row at position 37 attends to 32 of its 38 keys and its logits
    move; the rows at 5 and 20 read all they have and do not."""
    cfg = _cfg(kv_int8=kv_int8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    pool = _pool_of(cfg, params, (5, 20, 37))
    _toks, whole = _greedy_steps(cfg, params, pool, 1, None)
    _toks, cut = _greedy_steps(cfg, params, pool, 1, 32)
    np.testing.assert_allclose(cut[:2], whole[:2], rtol=2e-6, atol=2e-6)
    assert np.abs(cut[2] - whole[2]).max() > 1e-3


def test_writes_go_to_the_whole_leaf_whatever_the_read(params):
    """A row standing PAST the read length still writes its keys where
    they belong, in every layer: the cut is of the read alone. (The
    first layer's keys come before any attention, so they are the
    whole-row step's own; a later layer's follow what the row read.)"""
    pool = _pool_of(CFG, params, (40, 5))
    tok = jnp.asarray([[3], [4]], jnp.int32)
    _l, whole = decode_chunk(params, pool, tok, CFG)
    _l, cut = decode_chunk(params, pool, tok, CFG, read_len=16)
    for name in ("k", "v"):
        np.testing.assert_array_equal(  # row 0 wrote position 40
            np.asarray(whole[name][0][0, 40]),
            np.asarray(cut[name][0][0, 40]))
        for before, after in zip(pool[name], cut[name]):
            assert np.abs(np.asarray(before[0, 40])).sum() == 0
            assert np.abs(np.asarray(after[0, 40])).sum() > 0
            assert after.shape == before.shape
    np.testing.assert_array_equal(
        np.asarray(whole["pos"]), np.asarray(cut["pos"]))


@pytest.mark.parametrize("cfg,read_len,match", [
    (_cfg(window=32), 16, "ring"),
    (CFG, 0, "outside"),
    (CFG, MAX_LEN + 1, "outside"),
], ids=["ring", "zero", "past-the-leaf"])
def test_decode_chunk_refuses_a_read_len_it_cannot_honour(
        cfg, read_len, match):
    params = init_params(jax.random.PRNGKey(0), cfg)
    pool = slot_cache(cfg, 2, MAX_LEN)
    with pytest.raises(ValueError, match=match):
        decode_chunk(
            params, pool, jnp.zeros((2, 1), jnp.int32), cfg,
            read_len=read_len)


# -------------------------------------------------------- the ladder


@pytest.mark.parametrize("max_len,want", [
    (4096, (1024, 4096)),
    (3072, (1024, 3072)),
    (8192, (1024, 4096, 8192)),
    (1024, (1024,)),
    (64, (64,)),
])
def test_the_ladder_follows_from_max_len_alone(max_len, want):
    assert read_ladder(CFG, max_len) == want


def test_a_ring_or_a_family_has_the_one_rung():
    """What decides is the cache's form, which the configuration
    shows, never a model's name."""
    from containerpilot_tpu.workload.modelcfg import load_model_file

    assert read_ladder(_cfg(window=1024), 4096) == (4096,)
    for toy in ("toy-granite", "toy-axk1"):
        family = load_model_file(f"benchmark/tests/toy/{toy}.json", 3072)
        assert family.family is not None and family.window == 0
        assert read_ladder(family, 3072) == (3072,)


def test_the_programs_caches_hold_a_ladder_for_several_configurations():
    """A few rungs a configuration: a cache of eight programs would
    drop a rung of the fourth configuration alive in a process."""
    for cached in (_jitted_chunk, _jitted_window):
        assert cached.cache_info().maxsize >= 32


# ------------------------------------------------- the host's count


class _Watched(PlainStepProgram):
    """Reads the device's ``pos`` at every dispatch (a sync no serving
    program makes) and keeps what the choice has to answer for."""

    def __init__(self, *args, **kw):
        self.seen = []
        super().__init__(*args, **kw)

    def _run(self, budgets, fused, rung):
        pos = np.asarray(self._pool["pos"])
        live = [i for i, reach in enumerate(self._reach) if reach]
        self.seen.append({
            "rung": rung, "fused": fused,
            "steps": self.chunk * (self.rounds if fused else 1),
            "live_pos": max((int(pos[i]) for i in live), default=0),
            "bounds": [self._reach[i] for i in live],
            "pos": [int(pos[i]) for i in live],
        })
        return super()._run(budgets, fused, rung)


def _drive(eng):
    """Traffic with every turn the count has to survive; returns the
    rows it produced, in submission order (the cancelled one's
    excluded: it ends where the sweep finds it)."""
    rng = np.random.default_rng(41)
    shared = [int(t) for t in rng.integers(1, 64, 20)]

    def prompt(n):
        return [int(t) for t in rng.integers(1, 64, n)]

    out = []
    # two rows of different lengths, queued together: no fusion while
    # the queue holds work, the short one retires first
    a = eng.submit(shared + prompt(3), max_new=30)
    b = eng.submit(prompt(6), max_new=9)
    # a third takes the slot the short one leaves (re-admission), its
    # prompt reusing the first's stored prefix
    c = eng.submit(shared + prompt(9), max_new=40)
    out += [a.result(timeout=300), b.result(timeout=300),
            c.result(timeout=300)]
    # alone with an empty queue: fused windows, the one-window
    # lookahead, and an early exit at its 11th token of 16 a window
    out.append(eng.submit(prompt(5), max_new=11).result(timeout=300))
    # a long row that crosses every rung, and beside it one that is
    # cancelled mid-way
    gone = threading.Event()
    seen = threading.Event()
    long_row = eng.submit(prompt(10), max_new=100)
    cancelled = eng.submit(
        prompt(12), max_new=100, cancel=gone,
        on_tokens=lambda _d: seen.set())
    assert seen.wait(timeout=300)
    gone.set()
    cancelled.result(timeout=300)
    out.append(long_row.result(timeout=300))
    # and the slot the cancel freed is taken again
    out.append(eng.submit(shared + prompt(2), max_new=20)
               .result(timeout=300))
    return out


def test_the_hosts_count_never_undercounts_a_live_row(
        params, short_ladder):
    """At EVERY dispatch of a seeded engine run (admissions with and
    without a reused prefix, retire and re-admit into the same slot, a
    cancel, fused windows that exit early, the lookahead with an empty
    queue) the rung covers the live rows' device positions plus the
    steps the dispatch may run, each row's bound is at least its
    position, and the tokens equal those of a run pinned to the top
    rung."""
    watched = _Watched(CFG, params, MAX_LEN, 2, CHUNK, rounds=WINDOW)
    assert watched.ladder == RUNGS
    eng = SlotEngine(
        CFG, params, MAX_LEN, program=watched,
        prefix_cache=PrefixCache(4),
    )
    try:
        rows = _drive(eng)
        stats = eng.stats["read_len"]
    finally:
        eng.stop()
    assert len(watched.seen) >= 15
    for d in watched.seen:
        assert d["rung"] >= d["live_pos"] + d["steps"] or (
            d["rung"] == MAX_LEN), d
        assert all(b >= p for b, p in zip(d["bounds"], d["pos"])), d
    assert {d["fused"] for d in watched.seen} == {False, True}
    # the run climbed the ladder, and the engine's counts are the
    # program's dispatches
    ran = {d["rung"] for d in watched.seen}
    assert ran >= {64, 128}, ran
    assert stats["ladder"] == list(RUNGS)
    counted = {int(k): v for k, v in stats["dispatches"].items()}
    assert sum(counted.values()) == len(watched.seen)
    assert {k for k, v in counted.items() if v} == ran
    assert watched._reach == [0, 0]  # every slot retired

    pinned = PlainStepProgram(CFG, params, MAX_LEN, 2, CHUNK, rounds=WINDOW)
    pinned.ladder = (MAX_LEN,)
    ref = SlotEngine(
        CFG, params, MAX_LEN, program=pinned,
        prefix_cache=PrefixCache(4),
    )
    try:
        assert _drive(ref) == rows
        assert ref.stats["read_len"] == {
            "ladder": [MAX_LEN],
            "dispatches": {str(MAX_LEN): ref.phases.dispatches_fused
                           + ref.phases.dispatches_single},
        }
    finally:
        ref.stop()


def test_the_bound_follows_admit_dispatch_retire_and_reset(
        params, short_ladder):
    """The count by hand: set to the prompt's length at admission,
    advanced by the steps a dispatch MAY run for occupied slots only,
    cleared by ``retire`` and ``reset``; ``read_len`` is the shortest
    rung that is enough, ``max_len`` where none is."""
    from containerpilot_tpu.workload.serve_slots import _Request

    prog = PlainStepProgram(CFG, params, MAX_LEN, 2, CHUNK, rounds=WINDOW)
    assert prog.read_len(False) == 16 and prog.read_len(True) == 16

    def admit(slot, n):
        from containerpilot_tpu.models.decode import (
            BIAS_SLOTS_MAX,
            normalize_logit_bias,
        )

        idx, val = normalize_logit_bias(CFG, 1, None, slots=BIAS_SLOTS_MAX)
        req = _Request(
            tokens=list(range(1, n + 1)), max_new=100, temperature=0.0,
            top_k=0, top_p=0.0, eos_id=-1, pad_id=0, seed=0,
            bias_idx=idx[0], bias_val=val[0])
        logits, row = _jitted_prefill(CFG, MAX_LEN)(
            params, jnp.asarray([req.tokens], jnp.int32))
        prog.admit(slot, req, logits, row)

    admit(1, 10)
    assert prog._reach == [0, 10]
    assert prog.read_len(False) == 16  # 10 + 4
    assert prog.read_len(True) == 64  # 10 + 8
    prog.tokens(prog.dispatch(np.full((2,), 100, np.int32), True))
    assert prog._reach == [0, 18]
    assert int(np.asarray(prog._pool["pos"])[1]) == 18
    admit(0, 25)
    assert prog.read_len(False) == 64  # 25 + 4
    prog.tokens(prog.dispatch(np.full((2,), 100, np.int32), False))
    assert prog._reach == [29, 22]
    prog.retire(0)
    assert prog._reach == [0, 22] and prog.read_len(True) == 64
    for _ in range(12):  # 22 + 12 x 8 = 118
        prog.tokens(prog.dispatch(np.full((2,), 100, np.int32), True))
    assert prog._reach == [0, 118]
    assert prog.read_len(False) == 128  # 122
    assert prog.read_len(True) == 128  # 126
    prog.tokens(prog.dispatch(np.full((2,), 100, np.int32), True))
    assert prog.read_len(True) == MAX_LEN  # 134: no rung is enough
    with pytest.raises(RuntimeError, match="idle"):
        prog.warm_ladder()
    prog.reset()
    assert prog._reach == [0, 0] and prog.read_len(True) == 16


# ------------------------------------------------------ the warm-up


def test_warmup_leaves_every_rung_compiled(run, short_ladder):
    """``warmup()`` runs each rung's chunk and fused-window program
    once on the idle pool: a request that then climbs the whole ladder
    meets no compile (the event the benchmark's
    ``compiles_in_window.serve`` counts), and each rung's jitted
    function still holds the one executable the warm-up made."""
    from jax import monitoring

    from containerpilot_tpu.workload.serve import (
        WARMUP_PROMPT_LEN,
        InferenceServer,
    )

    # widths of this test's own: the jitted functions' caches are the
    # process's, and no other test's dispatches may have filled them
    cfg = _cfg(d_ff=96)
    params = init_params(jax.random.PRNGKey(1), cfg)
    compiles = []

    def listener(event, _duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    async def scenario():
        server = InferenceServer(
            cfg, params, "127.0.0.1", 0, max_len=MAX_LEN,
            slots=2, slot_chunk=CHUNK, slot_window=WINDOW,
        )
        await server.run()
        try:
            engine = server.slot_engine
            assert engine.read_ladder == RUNGS
            assert engine.phases.admissions == 1  # the dummy request
            warmed = len(compiles)
            assert warmed >= 2 * len(RUNGS)
            # the warm-up's own dispatches are set-up, not traffic
            assert sum(
                engine.stats["read_len"]["dispatches"].values()
            ) == engine.phases.dispatches_fused + engine.phases.dispatches_single
            # the warm-up request's own length: no new prefill program
            out = engine.submit(
                [0] * WARMUP_PROMPT_LEN,
                max_new=MAX_LEN - WARMUP_PROMPT_LEN,
            ).result(timeout=300)
            assert len(out) == MAX_LEN - WARMUP_PROMPT_LEN
            assert len(compiles) == warmed, "a rung compiled under traffic"
            ran = engine.stats["read_len"]["dispatches"]
            assert all(ran[str(rung)] >= 1 for rung in RUNGS), ran
        finally:
            await server.stop()

    monitoring.register_event_duration_secs_listener(listener)
    try:
        run(scenario(), timeout=300)
    finally:
        monitoring.unregister_event_duration_listener(listener)
    for rung in RUNGS:
        read_len = None if rung == MAX_LEN else rung
        assert _jitted_chunk(
            cfg, 2, CHUNK, None, read_len)._cache_size() == 1
        assert _jitted_window(
            cfg, 2, CHUNK, WINDOW, None, read_len)._cache_size() == 1


def test_warm_ladder_compiles_each_program_once(short_ladder):
    """``warm_ladder`` compiles the ladder's programs side by side
    ahead of their first call (``compile_decode_programs``) and then
    runs each: the runs find their executables made, so the whole
    warm-up compiles each program ONCE (were the ahead-of-time
    executables not the ones a call finds, it would be twice)."""
    from jax import monitoring

    cfg = _cfg(d_ff=80)  # this test's own programs
    params = init_params(jax.random.PRNGKey(2), cfg)
    prog = PlainStepProgram(cfg, params, MAX_LEN, 2, CHUNK, rounds=WINDOW)
    jax.block_until_ready(prog._state)
    compiles = []

    def listener(event, _duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    monitoring.register_event_duration_secs_listener(listener)
    try:
        prog.warm_ladder()
    finally:
        monitoring.unregister_event_duration_listener(listener)
    programs = 2 * len(RUNGS)
    # (beside them at most the budget's zeros and a fetch's glue)
    assert programs <= len(compiles) <= programs + 2, len(compiles)
    for rung in RUNGS:
        read_len = None if rung == MAX_LEN else rung
        assert _jitted_chunk(
            cfg, 2, CHUNK, None, read_len)._cache_size() == 1
        assert _jitted_window(
            cfg, 2, CHUNK, WINDOW, None, read_len)._cache_size() == 1


def test_warm_programs_waits_for_an_idle_pool(params, short_ladder):
    """The engine runs the program's warm-up on its own thread, the
    one owner of the donated pool, and only with no slot occupied: a
    chunk program of a short rung would step a live row past its
    read. Asked for beside traffic, it runs once the rows are done,
    and the rows come out as they would have."""
    alone = SlotEngine(CFG, params, MAX_LEN, slots=2, chunk=CHUNK,
                       window=WINDOW)
    try:
        want = alone.submit(list(range(1, 9)), max_new=60).result(
            timeout=300)
    finally:
        alone.stop()
    eng = SlotEngine(CFG, params, MAX_LEN, slots=2, chunk=CHUNK,
                     window=WINDOW)
    try:
        started = threading.Event()
        row = eng.submit(list(range(1, 9)), max_new=60,
                         on_tokens=lambda _d: started.set())
        assert started.wait(timeout=300)
        warmed = eng.warm_programs()
        assert row.result(timeout=300) == want
        assert warmed.result(timeout=300) is None
        # warm-up dispatches are no traffic: the counts are the row's
        assert sum(eng.stats["read_len"]["dispatches"].values()) == (
            eng.phases.dispatches_fused + eng.phases.dispatches_single)
    finally:
        eng.stop()
    with pytest.raises(RuntimeError, match="stopped"):
        eng.warm_programs()
