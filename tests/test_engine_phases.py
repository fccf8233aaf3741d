"""Engine phases and model scopes (ISSUE 24): the slot engine's
worker thread accounts every cycle in named phases
(telemetry/goodput.py ``EnginePhases``, the ``engine`` block of
``/v1/goodput``), the same names land on the ``slot-engine`` line of a
profiler trace, the trainer's loop annotates its steps, and the step
programs carry the layer map's names as ``jax.named_scope``s. All on
the CPU at toy widths: counts and names only, never a device time."""
import glob
import re
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from containerpilot_tpu.kvtier import HostSpillTier
from containerpilot_tpu.models.decode import (
    BIAS_SLOTS_MAX,
    _jitted_extend,
    _jitted_prefill,
    normalize_logit_bias,
)
from containerpilot_tpu.models.slots import (
    _jitted_chunk,
    _jitted_window,
    init_slot_state,
    slot_cache,
)
from containerpilot_tpu.models.transformer import (
    TransformerConfig,
    init_params,
)
from containerpilot_tpu.telemetry.goodput import (
    ENGINE_CYCLE_PHASES,
    ENGINE_PHASES,
    FIRST_TOKEN_PHASES,
    DeviceTimeLedger,
    EnginePhases,
    goodput_payload,
    process_start_monotonic,
)
from containerpilot_tpu.models.stepprog import PlainStepProgram
from containerpilot_tpu.telemetry import tracing
from containerpilot_tpu.telemetry.tracing import TraceRecorder
from containerpilot_tpu.workload.serve_prefix import PrefixCache
from containerpilot_tpu.workload.serve_slots import SlotEngine, _Request

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
    max_seq_len=512, dtype=jnp.float32,
)
CHUNK, WINDOW = 8, 4


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _engine(params, max_len=64, **kw):
    return SlotEngine(
        CFG, params, max_len, slots=2, chunk=CHUNK, window=WINDOW, **kw
    )


def _cycle_seconds(phases: EnginePhases) -> float:
    return sum(phases.phase_s[p] for p in ENGINE_CYCLE_PHASES)


def test_cycle_phases_sum_to_the_workers_wall_time(params):
    """The cycle phases tile the worker thread's life: waiting,
    admitting, dispatching, fetching and delivering add up to the
    time between the thread's start and its exit, within 2 %."""
    built = time.perf_counter()
    eng = _engine(params)  # the worker starts as this returns
    t0 = time.perf_counter()
    try:
        first = eng.submit([1, 2, 3, 4], max_new=24)
        second = eng.submit([5, 6, 7], max_new=12)
        assert len(first.result(timeout=120)) == 24
        assert len(second.result(timeout=120)) == 12
        time.sleep(0.05)  # a stretch of engine.wait_work
    finally:
        eng.stop()
    end = time.perf_counter()
    total = _cycle_seconds(eng.phases)
    assert total <= end - built
    assert total >= 0.98 * (end - t0), (total, end - t0, eng.phases.phase_s)
    for phase in ENGINE_CYCLE_PHASES:
        assert eng.phases.phase_n[phase] >= 1, phase
        assert eng.phases.phase_s[phase] > 0.0, phase


def test_counts_move_per_window_not_per_token(params):
    """A 256-token decode moves the dispatch, fetch and deliver counts
    by the number of windows it rode (chunk x window tokens each), and
    the admission counts by one: nothing is counted per token."""
    eng = _engine(params, max_len=512)
    try:
        eng.submit([1, 2, 3], max_new=2).result(timeout=120)  # compile
        before = dict(eng.phases.phase_n)
        out = eng.submit([9, 8, 7, 6], max_new=256).result(timeout=300)
        assert len(out) == 256
        time.sleep(0.05)
    finally:
        eng.stop()
    moved = {p: eng.phases.phase_n[p] - before[p] for p in ENGINE_PHASES}
    windows = -(-256 // (CHUNK * WINDOW))  # 8 fused windows
    # the admission's own single-chunk round, the fused windows, and
    # at most one lookahead window dispatched past the end
    assert windows <= moved["engine.fetch"] <= windows + 3, moved
    assert moved["engine.deliver"] == moved["engine.fetch"], moved
    assert windows <= moved["engine.dispatch"] <= windows + 4, moved
    assert moved["engine.admit"] == 1, moved
    assert moved["engine.admit.first_token"] == 1, moved
    assert moved["engine.admit.prefill"] == 1, moved
    # the step program's children of ``first_token``: a constant an
    # admission, whatever the tokens that follow
    for child in FIRST_TOKEN_PHASES:
        assert moved[child] == 1, (child, moved)
    # admit, prefill, first_token and its four children: seven counts
    # an admission, and the dispatches carry one ``live`` each
    assert sum(n for p, n in moved.items()
               if p.startswith("engine.admit")) == 7, moved


def test_admissions_and_dispatch_kinds_are_counted(params):
    eng = _engine(params)
    try:
        futures = [
            eng.submit([1 + i, 2, 3], max_new=6) for i in range(5)
        ]
        for f in futures:
            f.result(timeout=120)
    finally:
        eng.stop()
    phases = eng.phases
    assert phases.admissions == 5
    assert phases.phase_n["engine.admit"] == 5
    # five requests on two slots: the later ones waited in the queue
    assert phases.queue_wait_s > 0.0
    dispatched = phases.dispatches_fused + phases.dispatches_single
    assert dispatched == phases.phase_n["engine.dispatch"]
    # an admission keeps its round on the single-chunk program
    assert phases.dispatches_single >= 1
    snap = phases.snapshot()
    assert set(snap["phase_s"]) == set(ENGINE_PHASES)
    assert snap["admissions"] == 5


def test_sampler_rounds_are_counted_by_the_arm_the_live_slots_ask_for(
        params):
    """``stats["sampler"]`` (``/v1/model`` ``slot_engine.sampler``):
    decode rounds by the sampler's arm that the engine's own record of
    its live slots' knobs called for at the dispatch. Greedy traffic
    reads ``rounds_draw = rounds_filter = 0``; a request that filters
    puts its rounds under ``rounds_filter``, one that samples without
    a filter under ``rounds_draw``, and a slot retired with such knobs
    counts for nothing."""
    eng = _engine(params, max_len=256)
    try:
        for i in range(3):
            eng.submit([1 + i, 2, 3], max_new=40).result(timeout=120)
        greedy = dict(eng.stats["sampler"])
        assert set(greedy) == {
            "rounds_argmax", "rounds_draw", "rounds_filter"}
        assert greedy["rounds_draw"] == greedy["rounds_filter"] == 0
        # 39 tokens after the first, 8 a round: at least 5 rounds each
        assert greedy["rounds_argmax"] >= 15
        out = eng.submit(
            [4, 5, 6], max_new=40, temperature=0.9, top_k=5, seed=3,
        ).result(timeout=120)
        assert len(out) == 40
        filtered = dict(eng.stats["sampler"])
        assert filtered["rounds_filter"] >= 5
        assert filtered["rounds_draw"] == 0
        eng.submit(
            [7, 8, 9], max_new=40, temperature=0.9, seed=4,
        ).result(timeout=120)
        drawn = dict(eng.stats["sampler"])
        assert drawn["rounds_draw"] >= 5
        # the retired slots keep their knobs on the device; the
        # engine's record is of LIVE slots: greedy rounds count as such
        eng.submit([1, 2, 3], max_new=40).result(timeout=120)
        time.sleep(0.05)
    finally:
        eng.stop()
    after = eng.stats["sampler"]
    assert after["rounds_argmax"] >= drawn["rounds_argmax"] + 5
    # at most the one lookahead window dispatched while the sampling
    # request was still live is fetched after it
    assert after["rounds_filter"] <= filtered["rounds_filter"] + WINDOW
    assert after["rounds_draw"] <= drawn["rounds_draw"] + WINDOW
    assert after == eng.sampler_rounds


def test_store_spill_and_readmit_bytes_under_a_prefix_cache(params):
    """With a one-entry prefix cache over a spill tier, a second
    session's admission evicts and spills the first's row, and the
    first session's next turn readmits it: the bytes and the
    ``kvtier.*`` phases move with them."""
    pc = PrefixCache(1, spill=HostSpillTier(1 << 22))
    ledger = DeviceTimeLedger()
    eng = _engine(params, prefix_cache=pc, ledger=ledger)
    first = list(range(1, 21))
    try:
        eng.submit(first, max_new=4).result(timeout=120)
        eng.submit(list(range(21, 41)), max_new=4).result(timeout=120)
        # the first row's copy to the host runs behind the engine: let
        # it land, so the next turn readmits it by a device_put
        assert pc.flush(timeout=120)
        eng.submit(first + [5, 6, 7], max_new=4).result(timeout=120)
    finally:
        eng.stop()
    phases = ledger.engine
    assert eng.phases is phases  # the ledger's accumulator, not a copy
    assert phases.phase_n["engine.admit.store"] == 3
    assert phases.phase_n["engine.admit.reuse"] == 3
    assert phases.phase_n["kvtier.spill"] >= 2
    assert phases.phase_n["kvtier.readmit"] == 1
    assert phases.store_bytes > phases.spill_bytes > 0
    assert 0 < phases.readmit_bytes < phases.spill_bytes
    # what was spilled is still in the tier or came back
    assert phases.spill_bytes == (
        pc.spill.bytes_used + phases.readmit_bytes
    )
    # children nest inside the admission they belong to
    assert phases.phase_s["engine.admit"] >= (
        phases.phase_s["engine.admit.store"]
        + phases.phase_s["engine.admit.first_token"]
    )


def test_tokens_are_delivered_while_an_evicted_row_is_still_spilling(
    params, spill_gate
):
    """The engine's thread only HANDS an evicted row to the spill
    tier: with the copy to the host gated shut, the evicting request
    and the one after it run to their last token, the row in flight
    is readmitted from the device itself, and ``stop`` leaves nothing
    pending and ``kvtier.spill``'s books as a waited-for copy's."""
    tier = HostSpillTier(1 << 22)
    pc = PrefixCache(1, spill=tier)
    eng = _engine(params, prefix_cache=pc)
    first, second = list(range(1, 21)), list(range(21, 41))
    try:
        eng.submit(first, max_new=4).result(timeout=120)
        streamed = []
        out = eng.submit(
            second, max_new=12, on_tokens=streamed.extend
        ).result(timeout=120)  # its admission evicts ``first``'s row
        assert spill_gate.reached.wait(120)
        assert len(out) == 12 and streamed == out
        snap = tier.snapshot()
        assert (snap["pending"], snap["deferred"], snap["spilled"]) == (1, 1, 0)
        assert eng.phases.phase_n["kvtier.spill"] == 0  # still copying
        assert eng.phases.phase_n["engine.admit.store"] == 2
        # the next turn of the first session finds its row in flight
        eng.submit(first + [5, 6, 7], max_new=4).result(timeout=120)
        assert tier.stats["pending_hits"] == 1
        assert pc.stats["hits"] == 1 and pc.stats["readmitted"] == 1
        assert eng.phases.phase_n["kvtier.readmit"] == 0  # no device_put
    finally:
        spill_gate.open()
        eng.stop()
    snap = tier.snapshot()
    assert snap["pending"] == 0 and snap["failed"] == 0
    assert snap["backpressure_n"] == 0
    # handed over: ``first`` (taken back in flight), ``second``, and
    # ``first`` again when its longer turn was stored
    assert snap["deferred"] == 3
    assert snap["spilled"] == snap["entries"] == 2
    assert pc.stats["spilled"] == 2
    phases = eng.phases
    # the copy the take overtook ran too; its bytes joined no book
    assert phases.phase_n["kvtier.spill"] == 3
    assert phases.spill_bytes == tier.bytes_used > 0
    assert phases.readmit_bytes == 0
    # four rows stored (the readmit's among them), two of them landed
    assert 2 * phases.spill_bytes == phases.store_bytes


def test_profiler_trace_holds_the_spill_on_the_kv_spill_line(
    params, tmp_path
):
    """``kvtier.spill`` lies on the tier's own ``kv-spill`` line, and
    no longer under the engine's admission."""
    pc = PrefixCache(1, spill=HostSpillTier(1 << 22))
    eng = _engine(params, prefix_cache=pc)
    try:
        eng.submit(list(range(1, 21)), max_new=2).result(timeout=120)
        jax.profiler.start_trace(str(tmp_path))
        try:
            eng.submit(list(range(21, 41)), max_new=2).result(timeout=120)
            eng.submit(list(range(41, 61)), max_new=2).result(timeout=120)
            assert pc.flush(timeout=120)
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.stop()
    lines = _host_lines(str(tmp_path))
    assert "kv-spill" in lines, sorted(lines)
    assert "kvtier.spill" in lines["kv-spill"], sorted(lines["kv-spill"])
    assert "engine.admit.store" in lines["slot-engine"]
    assert "kvtier.spill" not in lines["slot-engine"]


def test_first_tokens_children_tile_it_and_count_admissions(params):
    """The step program opens four children inside
    ``engine.admit.first_token`` (sample, sync, insert, state): their
    seconds sum to the parent's within 2 %, as the cycle phases tile
    the thread, and each is counted once an admission. Prompts of 200
    tokens, so that ``sync`` has a prefill to wait out."""
    eng = _engine(params, max_len=256)
    try:
        eng.submit([1, 2, 3], max_new=2).result(timeout=120)  # compile
        eng.submit(list(range(1, 201)), max_new=2).result(timeout=120)
        warm = {p: eng.phases.phase_s[p] for p in ENGINE_PHASES}
        futures = [
            eng.submit([1 + (i + j) % 60 for j in range(200)], max_new=3)
            for i in range(6)
        ]
        for f in futures:
            f.result(timeout=300)
    finally:
        eng.stop()
    phases = eng.phases
    assert phases.admissions == 8
    # once everything is compiled an admission is a few ms here, and
    # its five span boundaries some tens of us of Python: loose on a
    # busy CPU, 2 % over the whole life (and on the chip: PERF.md)
    moved = {p: phases.phase_s[p] - warm[p] for p in ENGINE_PHASES}
    assert sum(moved[c] for c in FIRST_TOKEN_PHASES) >= (
        0.9 * moved["engine.admit.first_token"]), moved
    for child in FIRST_TOKEN_PHASES:
        assert phases.phase_n[child] == phases.admissions, child
        assert phases.phase_s[child] > 0.0, child
    whole = phases.phase_s["engine.admit.first_token"]
    parts = sum(phases.phase_s[c] for c in FIRST_TOKEN_PHASES)
    assert parts <= whole
    assert parts >= 0.98 * whole, (parts, whole, phases.phase_s)
    snap = phases.snapshot()
    assert set(FIRST_TOKEN_PHASES) <= set(snap["phase_s"])
    assert set(FIRST_TOKEN_PHASES) <= set(snap["phase_n"])


def test_a_block_diffusion_engine_records_insert_and_no_sync():
    """models/block_diffusion.py's ``admit`` fetches no first token:
    one program writes the row and its state (``insert``), nothing
    waits for the prefill, and the other three children stay at 0."""
    from test_block_diffusion import TOY, engine_for, ids, widened

    cfg, weights = widened(TOY)
    eng = engine_for(cfg, weights)
    try:
        with jax.default_matmul_precision("highest"):
            eng.submit(ids(8, seed=61), 8).result(timeout=600)
            eng.submit(ids(6, seed=62), 4).result(timeout=600)
    finally:
        eng.stop()
    phases = eng.phases
    assert phases.admissions == 2
    assert phases.phase_n["engine.admit.first_token"] == 2
    assert phases.phase_n["engine.admit.first_token.insert"] == 2
    assert phases.phase_s["engine.admit.first_token.insert"] > 0.0
    for child in ("sample", "sync", "state"):
        name = f"engine.admit.first_token.{child}"
        assert phases.phase_n[name] == 0 and phases.phase_s[name] == 0.0
    assert phases.phase_s["engine.admit.first_token.insert"] >= (
        0.9 * phases.phase_s["engine.admit.first_token"])


def test_a_program_given_no_phases_admits_as_before(params):
    """``attach_phases`` is an optional member of the step-program
    contract: a program nobody handed an ``EnginePhases`` records
    nothing, and writes the same row and returns the same token."""
    prompt = jnp.asarray([[5, 6, 7, 8]], jnp.int32)
    idx, val = normalize_logit_bias(CFG, 1, None, slots=BIAS_SLOTS_MAX)
    req = _Request(
        tokens=[5, 6, 7, 8], max_new=8, temperature=0.0, top_k=0,
        top_p=0.0, eos_id=-1, pad_id=0, seed=3,
        bias_idx=idx[0], bias_val=val[0],
    )
    firsts, pools = [], []
    watched = EnginePhases()
    for phases in (None, watched):
        program = PlainStepProgram(CFG, params, 64, slots=2, chunk=CHUNK)
        if phases is not None:
            program.attach_phases(phases)
        logits, row = _jitted_prefill(CFG, 64)(params, prompt)
        firsts.append(program.admit(1, req, logits, row))
        pools.append(jax.device_get((program._pool, program._state)))
    assert firsts[0] == firsts[1]
    same = jax.tree.map(lambda a, b: bool((a == b).all()), *pools)
    assert all(jax.tree.leaves(same))
    assert [watched.phase_n[c] for c in FIRST_TOKEN_PHASES] == [1, 1, 1, 1]
    assert PlainStepProgram.phases is None  # the class hands out none


OLD_GOODPUT_KEYS = {
    "stage", "uptime_s", "stages_s", "productive_s",
    "productive_fraction", "transitions", "first_productive_at",
    "role", "ready", "draining", "dispatches", "tokens_out",
    "dispatches_per_token", "scheduling_gaps",
}


def test_goodput_body_keeps_every_old_key_and_gains_engine():
    ledger = DeviceTimeLedger()
    ledger.engine.switch("engine.dispatch", time.perf_counter())
    ledger.engine.close(time.perf_counter())
    body = goodput_payload(
        ledger, TraceRecorder("replica"), 3, 24,
        role="replica", ready=True, draining=False,
    )
    assert OLD_GOODPUT_KEYS <= set(body)
    assert set(body) - OLD_GOODPUT_KEYS == {"engine"}
    assert set(body["stages_s"]) == {
        "boot", "compile_warmup", "idle", "prefill", "decode",
        "kv_readmit", "drain",
    }
    engine = body["engine"]
    assert set(engine) == {
        "phase_s", "phase_n", "admissions", "queue_wait_s",
        "dispatches_fused", "dispatches_single", "store_bytes",
        "spill_bytes", "readmit_bytes", "latent_store_bytes",
        "latent_spill_bytes", "latent_readmit_bytes",
        "read_len_dispatches",
    }
    assert engine["phase_n"]["engine.dispatch"] == 1
    assert engine["read_len_dispatches"] == {}  # no ``dispatched`` yet
    ledger.engine.dispatched(time.perf_counter(), False, 2, 1024)
    ledger.engine.dispatched(time.perf_counter(), True, 2, 512)
    ledger.engine.dispatched(time.perf_counter(), True, 1, 1024)
    assert ledger.engine.snapshot()["read_len_dispatches"] == {
        "512": 1, "1024": 2}


def test_ledger_boot_starts_with_the_process():
    """``serve_cli`` hands the ledger the process's start: ``boot``
    then holds interpreter start and the jax import, which a ledger
    built after them cannot see."""
    started = process_start_monotonic()
    now = time.monotonic()
    assert started < now
    assert now - started < 3600.0  # this test process is minutes old
    ledger = DeviceTimeLedger(now=started)
    snap = ledger.snapshot()
    assert snap["stage"] == "boot"
    assert snap["stages_s"]["boot"] == pytest.approx(
        snap["uptime_s"], abs=0.01
    )
    assert snap["uptime_s"] >= now - started - 0.01


def _host_planes(trace_dir):
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(
        f"{trace_dir}/plugins/profile/*/*.xplane.pb"
    ))
    assert paths, f"no trace under {trace_dir}"
    return [plane for plane in ProfileData.from_file(paths[-1]).planes
            if plane.name.startswith("/host:CPU")]


def _host_lines(trace_dir):
    """line name -> event names, over the host plane of the newest
    trace under ``trace_dir``."""
    lines = {}
    for plane in _host_planes(trace_dir):
        for line in plane.lines:
            lines.setdefault(line.name, set()).update(
                ev.name for ev in line.events
            )
    return lines


def _engine_events(trace_dir):
    """The ``slot-engine`` line's events in order of their start, as
    (name, start_ns, end_ns, {argument: value})."""
    events = []
    for plane in _host_planes(trace_dir):
        for line in plane.lines:
            if line.name != "slot-engine":
                continue
            for ev in line.events:
                events.append((
                    ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                    dict(ev.stats),
                ))
    return sorted(events, key=lambda e: (e[1], -e[2]))


def test_profiler_trace_holds_the_phases_on_the_slot_engine_line(
    params, tmp_path
):
    eng = _engine(params)
    try:
        eng.submit([1, 2, 3], max_new=2).result(timeout=120)  # compile
        jax.profiler.start_trace(str(tmp_path))
        try:
            eng.submit([4, 5, 6, 7], max_new=40).result(timeout=120)
            eng.submit([8, 9], max_new=9).result(timeout=120)
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.stop()
    lines = _host_lines(str(tmp_path))
    assert "slot-engine" in lines, sorted(lines)
    names = lines["slot-engine"]
    for phase in ("engine.admit", "engine.admit.prefill",
                  "engine.admit.first_token", "engine.dispatch",
                  "engine.fetch", "engine.deliver"):
        assert phase in names, (phase, sorted(
            n for n in names if n.startswith("engine")))


def test_profiler_trace_nests_the_children_and_carries_the_arguments(
    params, tmp_path
):
    """In a profiler trace the four children lie on the ``slot-engine``
    line inside their ``engine.admit.first_token``, back to back and
    in order; ``engine.admit`` says which request caused it (its
    prompt's tokens, its slot and, where the request was submitted
    under a trace of telemetry/tracing.py, that trace's id), and
    ``engine.dispatch`` how many rows were live: the two the test
    admitted."""
    eng = _engine(params)
    recorder = TraceRecorder("replica")
    try:
        eng.submit([1, 2, 3], max_new=2).result(timeout=120)  # compile
        jax.profiler.start_trace(str(tmp_path))
        try:
            trace = recorder.start("feedc0dedeadbeef", "/v1/generate")
            token = tracing.activate(trace)
            try:
                traced = eng.submit([4, 5, 6, 7], max_new=40)
            finally:
                tracing.deactivate(token)
            bare = eng.submit([8, 9], max_new=40)
            assert len(traced.result(timeout=120)) == 40
            assert len(bare.result(timeout=120)) == 40
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.stop()
    events = _engine_events(str(tmp_path))
    admits = [e for e in events if e[0] == "engine.admit"]
    assert [a[3]["prompt"] for a in admits] == [4, 2], admits
    assert sorted(a[3]["slot"] for a in admits) == [0, 1], admits
    assert admits[0][3]["trace"] == "feedc0dedeadbeef"
    assert "trace" not in admits[1][3]  # submitted under no trace
    parents = [e for e in events if e[0] == "engine.admit.first_token"]
    assert len(parents) == 2
    for admit, parent in zip(admits, parents):
        assert admit[1] <= parent[1] and parent[2] <= admit[2]
        inside = [e for e in events
                  if e[0] in FIRST_TOKEN_PHASES
                  and parent[1] <= e[1] and e[2] <= parent[2]]
        assert [e[0] for e in inside] == list(FIRST_TOKEN_PHASES)
        for before, after in zip(inside, inside[1:]):
            assert before[2] <= after[1]  # back to back, never nested
        assert not any(e[3] for e in inside)  # the admission names them
    dispatches = [e for e in events if e[0] == "engine.dispatch"]
    assert len(dispatches) >= 2
    assert {d[3]["fused"] for d in dispatches} <= {0, 1}
    # both requests were queued before the first dispatch and run 40
    # tokens: every dispatch but the last ones sees both rows live
    assert dispatches[0][3]["live"] == 2
    assert {d[3]["live"] for d in dispatches} <= {1, 2}
    # ...and how far it read the pool's rows: a pool of 64 positions
    # has the one rung
    assert {d[3]["read_len"] for d in dispatches} == {64}


def test_the_one_program_is_issued_in_insert_and_sync_comes_last(
    params, tmp_path
):
    """An admission's device work is ONE program (models/slots.py
    ``admit_row``), issued inside ``.insert``; ``.sample`` before it
    packs the request's numbers on the host and ``.state`` after it is
    the host's bookkeeping: neither runs a program; ``.sync``, the
    fetch of the first token, comes after all three, so nothing the
    host still has to issue waits for the device."""
    eng = _engine(params)
    try:
        eng.submit([1, 2, 3], max_new=2).result(timeout=120)  # compile
        eng.submit([4, 5, 6, 7], max_new=2).result(timeout=120)
        jax.profiler.start_trace(str(tmp_path))
        try:
            eng.submit([8, 9, 10], max_new=3, temperature=0.8, top_k=4,
                       seed=9).result(timeout=120)
            eng.submit([4, 5, 6, 7], max_new=3).result(timeout=120)
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.stop()
    events = _engine_events(str(tmp_path))
    child = {name: [e for e in events if e[0] == name]
             for name in FIRST_TOKEN_PHASES}
    sample, insert, state, sync = (child[n] for n in FIRST_TOKEN_PHASES)
    assert len(sample) == len(insert) == len(state) == len(sync) == 2
    for a, b, c, d in zip(sample, insert, state, sync):
        assert a[2] <= b[1] and b[2] <= c[1] and c[2] <= d[1]

    def inside(spans, name):
        return [e for e in events if e[0] == name
                and any(s[1] <= e[1] and e[2] <= s[2] for s in spans)]

    ran = "PjRtCpuExecutable::Execute"
    assert len(inside(insert, ran)) == 2  # one an admission
    assert len(inside(insert, "PjitFunction(admit_row)")) >= 2
    for spans in (sample, state, sync):
        assert not inside(spans, ran), spans[0][0]
    # and beside the prefill nothing else of the admission runs one
    admits = [e for e in events if e[0] == "engine.admit"]
    assert len(inside(admits, ran)) == 2 * 2


def test_read_len_rides_the_dispatch_span_and_v1_model(
        params, tmp_path, monkeypatch):
    """``engine.dispatch``'s event carries ``read_len=<rung>`` beside
    ``fused`` and ``live``, ``stats["read_len"]`` (``/v1/model``
    ``slot_engine.read_len``) the ladder and the dispatches by rung,
    and ``/v1/goodput``'s ``engine.read_len_dispatches`` the same
    counts for a reader that takes deltas: a row decoding from
    position 3 to 100 of a pool whose ladder starts at 16 climbs it."""
    from containerpilot_tpu.models import slots as slots_mod

    monkeypatch.setattr(slots_mod, "READ_LADDER_BASE", 16)
    eng = _engine(params, max_len=128)
    assert eng.stats["read_len"] == {
        "ladder": [16, 64, 128],
        "dispatches": {"16": 0, "64": 0, "128": 0},
    }
    try:
        eng.warm_programs().result(timeout=300)  # compile every rung
        before = eng.phases.snapshot()["read_len_dispatches"]
        assert before == {}  # a warm-up's dispatches are no traffic
        jax.profiler.start_trace(str(tmp_path))
        try:
            out = eng.submit([1, 2, 3], max_new=97).result(timeout=300)
        finally:
            jax.profiler.stop_trace()
        assert len(out) == 97
        stats = eng.stats["read_len"]
        counted = eng.phases.snapshot()["read_len_dispatches"]
    finally:
        eng.stop()
    dispatches = [e[3] for e in _engine_events(str(tmp_path))
                  if e[0] == "engine.dispatch"]
    rungs = [d["read_len"] for d in dispatches]
    assert rungs == sorted(rungs), rungs  # one row, only ever longer
    assert set(rungs) == {16, 64, 128}
    # the first dispatch follows an admission: the chunk program,
    # 3 + 8 positions, the first rung
    assert dispatches[0] == {"fused": 0, "live": 1, "read_len": 16}
    by_rung = {str(r): rungs.count(r) for r in (16, 64, 128)}
    assert stats == {"ladder": [16, 64, 128], "dispatches": by_rung}
    assert counted == by_rung


def test_profiler_trace_holds_train_step_for_a_two_step_trainer(tmp_path):
    from containerpilot_tpu.workload.train import main

    progress = tmp_path / "progress.json"
    argv = sys.argv
    sys.argv = [
        "train", "--steps", "2", "--batch", "2", "--seq-len", "16",
        "--d-model", "64", "--n-layers", "1", "--n-heads", "4",
        "--vocab", "64", "--progress-file", str(progress),
    ]
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        assert main() == 0
    finally:
        sys.argv = argv
        jax.profiler.stop_trace()
    names = set().union(*_host_lines(str(tmp_path / "trace")).values())
    assert "train.step" in names
    assert "train.loss_sync" in names


def _scope_tokens(text: str):
    """Every name inside the ``loc("...")`` paths of a lowered
    module: path components, and the names transforms wrap
    (``transpose(jvp(layers))`` holds ``layers``)."""
    names = set()
    for path in re.findall(r'loc\("([^"]+)"', text):
        names.update(re.findall(r"[A-Za-z_][\w.]*", path))
    return names


def _lowered(kind, params):
    slots, max_len = 2, 48
    if kind in ("chunk", "window"):
        pool = slot_cache(CFG, slots, max_len)
        state = init_slot_state(CFG, slots)
        if kind == "chunk":
            return _jitted_chunk(CFG, slots, CHUNK).lower(
                params, pool, state)
        return _jitted_window(CFG, slots, CHUNK, WINDOW).lower(
            params, pool, state, jnp.zeros((slots,), jnp.int32))
    prompt = jnp.zeros((1, 16), jnp.int32)
    if kind == "prefill":
        return _jitted_prefill(CFG, max_len).lower(params, prompt)
    if kind == "extend":
        _logits, cache = _jitted_prefill(CFG, max_len)(params, prompt)
        return _jitted_extend(CFG).lower(
            params, cache, jnp.zeros((1, 4), jnp.int32))
    from containerpilot_tpu.parallel import (
        init_train_state,
        make_mesh,
        make_train_step,
    )

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_seq_len=64, remat=True, loss_chunk=8,
    )
    mesh = make_mesh(jax.devices()[:1])
    state = init_train_state(jax.random.PRNGKey(0), cfg, mesh)
    return jax.jit(make_train_step(cfg, mesh)).lower(
        state, jnp.zeros((2, 17), jnp.int32))


@pytest.mark.parametrize("kind,wanted", [
    ("chunk", {"embed", "attn", "attn.qkv", "attn.rope", "attn.kv_write",
               "attn.scores", "attn.out", "mlp", "head", "sample",
               "sample.argmax", "sample.draw", "sample.filter", "steps"}),
    ("window", {"attn", "attn.kv_write", "attn.scores", "mlp", "head",
                "sample", "sample.argmax", "sample.draw", "sample.filter",
                "steps", "layers"}),
    ("prefill", {"embed", "attn", "attn.qkv", "attn.kv_write",
                 "attn.scores", "mlp", "head", "layers"}),
    ("extend", {"attn", "attn.kv_write", "attn.scores", "mlp", "head"}),
    ("train", {"embed", "attn", "attn.qkv", "attn.scores", "attn.out",
               "mlp", "norm", "head", "loss", "optimizer", "layers"}),
])
def test_lowered_programs_name_the_layer_maps_scopes(kind, wanted, params):
    names = _scope_tokens(_lowered(kind, params).as_text(debug_info=True))
    assert wanted <= names, sorted(wanted - names)


@pytest.mark.parametrize("kind", ["chunk", "window"])
def test_decode_programs_are_still_named_jit_run(kind, params):
    """benchmark/layer_metrics/decode_programs.py rests on two names:
    it finds the decode programs by this module name, ``jit_run``, and
    counts their token-steps by the executions of the scope ``sample``
    inside them (pinned for both programs by
    ``test_lowered_programs_name_the_layer_maps_scopes``)."""
    text = _lowered(kind, params).as_text()
    assert re.search(r"module @jit_run\b", text), text[:200]
