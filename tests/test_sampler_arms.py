"""The sampler does what its live rows ask for
(models/decode.py::sample_logits, ``sampler_arm``, ``SAMPLER_ARMS``):
with the knobs given as arrays the program holds three arms and a call
runs one of them for the whole pool, chosen on the device. Whichever
runs, every row gets the token the ONE unconditional path gave it
before there were arms (sort, masks, draw, then argmax for the greedy
rows): kept here as the reference, as it stood."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from containerpilot_tpu.models.decode import (
    BIAS_SLOTS_MAX,
    NEG_INF,
    SAMPLER_ARMS,
    _jitted_prefill,
    apply_logit_bias,
    mask_eos_before_min,
    row_arm,
    sample_logits,
    sampler_arm,
)
from containerpilot_tpu.models.slots import (
    admit_slot_state,
    decode_slots_chunk,
    decode_slots_window,
    first_sample,
    init_slot_state,
    insert_row,
    retire_slot,
    slot_cache,
)
from containerpilot_tpu.models.transformer import (
    TransformerConfig,
    init_params,
)

VOCAB = 96
ROWS = 6
ARGMAX, DRAW, FILTER = range(3)


def _unconditional(logits, keys, temperature, top_k, top_p):
    """``sample_logits`` for array knobs and per-row keys as it was
    before the arms: every call sorts, masks and draws for every row
    and then hands the greedy rows their argmax."""
    b, vocab = logits.shape
    t = jnp.asarray(temperature, jnp.float32)[:, None]
    raw = logits.astype(jnp.float32)
    x = raw / jnp.maximum(t, 1e-6)
    sorted_logits = jnp.sort(x, axis=-1)[:, ::-1]
    k = jnp.asarray(top_k, jnp.int32)[:, None]
    k = jnp.where(k > 0, k, vocab)
    keep = jnp.arange(vocab)[None, :] < k
    p = jnp.asarray(top_p, jnp.float32)[:, None]
    p = jnp.where((p > 0.0) & (p < 1.0), p, 1.0)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    keep &= (jnp.cumsum(probs, axis=-1) - probs) < p
    threshold = jnp.min(
        jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True
    )
    x = jnp.where(x < threshold, NEG_INF, x)
    sampled = jax.vmap(
        lambda key, row: jax.random.categorical(key, row)
    )(keys, x)
    return jnp.where(t[:, 0] <= 0.0, jnp.argmax(raw, axis=-1), sampled)


def _logits(seed, rows=ROWS, vocab=VOCAB):
    return 3.0 * jax.random.normal(
        jax.random.PRNGKey(seed), (rows, vocab), jnp.float32
    )


def _row_keys(seed, rows=ROWS):
    return jax.random.split(jax.random.PRNGKey(1000 + seed), rows)


def _fold(keys, step):
    return jax.vmap(jax.random.fold_in)(
        keys, jnp.full((keys.shape[0],), step, jnp.int32)
    )


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_all_greedy_pool_takes_the_argmax(seed):
    """(a) No row samples: the argmax arm, whose tokens are the
    argmax of the logits handed in and what the unconditional path
    gave the same rows, stale filters on greedy rows included."""
    logits = _logits(seed)
    keys = _row_keys(seed)
    temperature = jnp.zeros((ROWS,), jnp.float32)
    top_k = jnp.asarray([0, 5, 0, VOCAB, 1, 0], jnp.int32)
    top_p = jnp.asarray([0.0, 0.0, 0.7, 1.0, 0.2, 0.0], jnp.float32)
    assert int(sampler_arm(temperature, top_k, top_p)) == ARGMAX
    got = sample_logits(
        logits, keys, temperature, top_k, top_p,
        fold=jnp.full((ROWS,), seed, jnp.int32),
    )
    assert got.dtype == jnp.int32
    assert np.array_equal(got, jnp.argmax(logits, axis=-1))
    assert np.array_equal(got, _unconditional(
        logits, _fold(keys, seed), temperature, top_k, top_p))


ONE_LIVE_ROW = {
    # name: (temperature, top_k, top_p) of the one row that samples,
    # and the arm a pool of greedy rows around it runs
    "top_k": ((0.9, 5, 0.0), FILTER),
    "top_p": ((1.3, 0, 0.6), FILTER),
    "top_k+top_p": ((0.7, 12, 0.9), FILTER),
    "top_k=vocab": ((0.8, VOCAB, 0.0), FILTER),
    "unfiltered": ((0.9, 0, 0.0), DRAW),
    "top_p=1": ((1.1, 0, 1.0), DRAW),
    "top_k<0": ((0.6, -3, 0.0), DRAW),
}


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("kind", sorted(ONE_LIVE_ROW))
def test_one_sampling_row_among_greedy_rows(kind, seed):
    """(b) One live row that filters, or samples without a filter,
    among greedy rows: every row's token is the unconditional path's,
    bit for bit, over several steps of one pool's keys."""
    (t, k, p), arm = ONE_LIVE_ROW[kind]
    row = seed % ROWS
    temperature = jnp.zeros((ROWS,), jnp.float32).at[row].set(t)
    top_k = jnp.zeros((ROWS,), jnp.int32).at[row].set(k)
    top_p = jnp.zeros((ROWS,), jnp.float32).at[row].set(p)
    assert int(sampler_arm(temperature, top_k, top_p)) == arm
    keys = _row_keys(seed)
    sampled = []
    for step in range(1, 9):
        logits = _logits(100 * seed + step)
        fold = jnp.full((ROWS,), step, jnp.int32)
        got = sample_logits(
            logits, keys, temperature, top_k, top_p, fold=fold
        )
        want = _unconditional(
            logits, _fold(keys, step), temperature, top_k, top_p
        )
        assert np.array_equal(got, want), (kind, step)
        sampled.append(int(got[row]) != int(jnp.argmax(logits[row])))
    # the row really drew: eight argmaxes in a row would be a key or
    # a temperature that never reached the draw
    assert any(sampled) or k == 1


@pytest.mark.parametrize("seed", range(6))
def test_a_rows_draw_does_not_depend_on_the_arm(seed):
    """The per-row-key contract across arms: a row that samples
    without a filter draws the same token when a neighbour's filter
    makes the whole pool sort."""
    logits = _logits(seed)
    keys = _row_keys(seed)
    fold = jnp.full((ROWS,), 3, jnp.int32)
    temperature = jnp.asarray([0.0, 0.9, 0.0, 1.2, 0.0, 0.8])
    top_p = jnp.zeros((ROWS,), jnp.float32)
    alone = jnp.zeros((ROWS,), jnp.int32)
    beside = alone.at[5].set(4)
    assert int(sampler_arm(temperature, alone, top_p)) == DRAW
    assert int(sampler_arm(temperature, beside, top_p)) == FILTER
    a = sample_logits(logits, keys, temperature, alone, top_p, fold=fold)
    b = sample_logits(logits, keys, temperature, beside, top_p, fold=fold)
    assert np.array_equal(a[:5], b[:5])


STALE = {
    # name: (temperature, top_k, top_p, live) -> arm
    "sampled row retired": (
        [0.0, 0.9, 0.0], [0, 0, 0], [0.0, 0.0, 0.0],
        [True, False, True], ARGMAX),
    "filtered row retired": (
        [0.0, 0.9, 0.0], [0, 7, 0], [0.0, 0.5, 0.0],
        [True, False, True], ARGMAX),
    "filtered row retired beside a sampled one": (
        [0.8, 0.9, 0.0], [0, 7, 0], [0.0, 0.5, 0.0],
        [True, False, True], DRAW),
    "filtered row live": (
        [0.8, 0.9, 0.0], [0, 7, 0], [0.0, 0.0, 0.0],
        [True, True, False], FILTER),
    "top_p alone filters": (
        [0.0, 0.9, 0.0], [0, 0, 0], [0.0, 0.5, 0.0],
        [True, True, True], FILTER),
    "a greedy row's filters ask for nothing": (
        [0.0, 0.9, 0.0], [5, 0, 3], [0.4, 0.0, 0.9],
        [True, True, True], DRAW),
    "top_p outside (0, 1) is no filter": (
        [0.7, 0.9, 0.5], [0, -1, 0], [1.0, 0.0, 1.5],
        [True, True, True], DRAW),
    "every row retired": (
        [0.7, 0.9, 0.5], [3, 3, 3], [0.5, 0.5, 0.5],
        [False, False, False], ARGMAX),
    "no mask: every row counts": (
        [0.0, 0.9, 0.0], [0, 7, 0], [0.0, 0.0, 0.0], None, FILTER),
}


@pytest.mark.parametrize("case", sorted(STALE))
def test_stale_knobs_of_a_done_row_choose_nothing(case):
    """(c) ``sampler_arm`` as a pure function: a done row's
    temperature, top_k and top_p (a retired slot keeps them until
    readmission) select no arm."""
    temperature, top_k, top_p, live, arm = STALE[case]
    got = sampler_arm(
        np.asarray(temperature, np.float32),
        np.asarray(top_k, np.int32), np.asarray(top_p, np.float32),
        None if live is None else np.asarray(live),
    )
    assert int(got) == arm
    # the engine's host-side record gives the same: the largest
    # ``row_arm`` of the live rows (serve_slots.py ``_sampler_arm``)
    assert arm == max(
        (row_arm(float(t), int(k), float(p))
         for t, k, p, alive in zip(
             temperature, top_k, top_p, live or [True] * len(top_k))
         if alive),
        default=0,
    )


def _primitives(jaxpr):
    """Every primitive's name in a jaxpr, its sub-jaxprs included."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names.extend(_primitives(sub))
    return names


def test_the_sort_and_the_noise_stand_inside_their_arms():
    """The traced program: one ``cond`` of three branches; no sort
    and no random bits outside it or in the argmax arm, no sort in
    the draw arm, the sort in the filter arm."""
    knobs = (
        jnp.zeros((ROWS,), jnp.float32), jnp.zeros((ROWS,), jnp.int32),
        jnp.zeros((ROWS,), jnp.float32),
    )
    jaxpr = jax.make_jaxpr(
        lambda logits, keys, *knobs: sample_logits(
            logits, keys, *knobs, live=jnp.ones((ROWS,), bool),
            fold=jnp.arange(ROWS),
        )
    )(_logits(0), _row_keys(0), *knobs).jaxpr
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    outside = [e.primitive.name for e in jaxpr.eqns if e is not conds[0]]
    arms = [_primitives(b.jaxpr) for b in conds[0].params["branches"]]
    assert len(arms) == len(SAMPLER_ARMS)
    noise = {"random_bits", "threefry2x32", "random_wrap", "random_fold_in"}
    assert "sort" not in outside and not noise & set(outside)
    assert "sort" not in arms[ARGMAX] and not noise & set(arms[ARGMAX])
    assert "argmax" in arms[ARGMAX]
    assert "sort" not in arms[DRAW] and noise & set(arms[DRAW])
    assert "sort" in arms[FILTER] and noise & set(arms[FILTER])


def test_static_none_keeps_one_path_without_a_cond():
    """Both filters ``None``: the static form beam search and the
    speculative engine call, with no conditional in it."""
    jaxpr = jax.make_jaxpr(
        lambda logits, keys: sample_logits(
            logits, keys, jnp.full((ROWS,), 0.9))
    )(_logits(0), _row_keys(0)).jaxpr
    names = _primitives(jaxpr)
    assert "cond" not in names and "sort" not in names


# ---- the pool's programs ------------------------------------------------

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
    max_seq_len=64, dtype=jnp.float32,
)
MAX_LEN = 48
NO_BIAS = (
    jnp.full((BIAS_SLOTS_MAX,), -1, jnp.int32),
    jnp.zeros((BIAS_SLOTS_MAX,), jnp.float32),
)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _pool_of(params, knobs):
    """A pool with one request admitted per (temperature, top_k,
    top_p) of ``knobs``, slot i's prompt and seed its own."""
    slots = len(knobs)
    pool = slot_cache(CFG, slots, MAX_LEN)
    state = init_slot_state(CFG, slots)
    for slot, (t, k, p) in enumerate(knobs):
        prompt = jnp.asarray([[slot + 1, slot + 2, slot + 3]], jnp.int32)
        logits, row = _jitted_prefill(CFG, MAX_LEN)(params, prompt)
        key = jax.random.fold_in(jax.random.PRNGKey(7 + slot), 0)
        first = first_sample(
            logits, key, t, k, p, CFG,
            bias_idx=NO_BIAS[0], bias_val=NO_BIAS[1],
        )
        pool = insert_row(pool, row, slot, CFG)
        state = admit_slot_state(
            state, slot, CFG, last=first, key=key, temperature=t,
            top_k=k, top_p=p, eos_id=-1, pad_id=0, min_new=0,
            presence=0.0, frequency=0.0, bias_idx=NO_BIAS[0],
            bias_val=NO_BIAS[1], done=False,
        )
    return pool, state


MIXES = {
    "greedy+filtered+sampled": [(0.0, 0, 0.0), (0.8, 6, 0.9), (1.1, 0, 0.0)],
    "greedy+sampled": [(0.0, 0, 0.0), (0.9, 0, 0.0), (0.0, 0, 0.0)],
    "greedy": [(0.0, 0, 0.0), (0.0, 4, 0.5), (0.0, 0, 0.0)],
}


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_chunk_and_fused_window_stay_token_identical(params, mix):
    """(d) The byte-parity contract on a mixed pool: K sequential
    chunk dispatches and one fused window of K rounds give the same
    tokens and leave the same state, whichever arm the steps run."""
    chunk, rounds = 3, 4
    pool, state = _pool_of(params, MIXES[mix])
    sequential = []
    for _ in range(rounds):
        pool, state, toks = decode_slots_chunk(
            params, pool, state, CFG, chunk)
        sequential.append(np.asarray(toks))
    sequential = np.concatenate(sequential, axis=1)
    pool2, state2 = _pool_of(params, MIXES[mix])
    budget = np.full((len(MIXES[mix]),), chunk * rounds, np.int32)
    pool2, state2, toks, run = decode_slots_window(
        params, pool2, state2, CFG, chunk, rounds, budget)
    assert int(run) == rounds
    assert np.array_equal(np.asarray(toks), sequential)
    for name, leaf in state2.items():
        assert np.array_equal(np.asarray(leaf), np.asarray(state[name])), name


def test_a_retired_sampling_slot_leaves_its_neighbours_tokens_alone(params):
    """A slot retired with ``temperature > 0`` and a filter in its
    knobs keeps them; its neighbours decode what they decode beside a
    slot that never sampled (the device's arm follows ``done``, and
    no row's token depends on the arm)."""
    chunk = 6
    pool, state = _pool_of(params, [(0.0, 0, 0.0), (0.9, 5, 0.8)])
    state = retire_slot(state, 1)
    assert int(sampler_arm(
        state["temperature"], state["top_k"], state["top_p"],
        ~state["done"])) == ARGMAX
    assert int(sampler_arm(
        state["temperature"], state["top_k"], state["top_p"])) == FILTER
    _, _, toks = decode_slots_chunk(params, pool, state, CFG, chunk)
    pool, state = _pool_of(params, [(0.0, 0, 0.0), (0.0, 0, 0.0)])
    state = retire_slot(state, 1)
    _, _, want = decode_slots_chunk(params, pool, state, CFG, chunk)
    assert np.array_equal(np.asarray(toks)[0], np.asarray(want)[0])
    assert (np.asarray(toks)[1] == 0).all()  # pads from a retired slot


FIRST = {
    "greedy": (0.0, 0, 0.0),
    "greedy with stale filters": (0.0, 8, 0.5),
    "sampled": (0.9, 0, 0.0),
    "top_k": (0.9, 4, 0.0),
    "top_p": (1.2, 0, 0.7),
    "top_k+top_p": (0.7, 10, 0.9),
}


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("kind", sorted(FIRST))
def test_first_sample_gives_what_it_gave(kind, seed):
    """(e) Token 0 of an admission: bias, the eos floor, the key
    folded with 0, and the unconditional path's token for greedy,
    sampled and filtered knobs."""
    t, k, p = FIRST[kind]
    logits = _logits(50 + seed, rows=1, vocab=CFG.vocab_size)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 2)
    eos = int(jnp.argmax(logits[0]))  # floor it away: min_new 1
    bias_idx = NO_BIAS[0].at[0].set(3)
    bias_val = NO_BIAS[1].at[0].set(1.5)
    got = first_sample(
        logits, key, t, k, p, CFG, eos_id=eos, min_new=1,
        bias_idx=bias_idx, bias_val=bias_val,
    )
    masked = mask_eos_before_min(
        apply_logit_bias(logits, bias_idx[None], bias_val[None]),
        jnp.int32(0), jnp.asarray([1]), jnp.asarray([eos]),
    )
    want = _unconditional(
        masked, jax.random.fold_in(key, 0)[None],
        jnp.asarray([t]), jnp.asarray([k]), jnp.asarray([p]),
    )
    assert got.shape == () and got.dtype == jnp.int32
    assert int(got) == int(want[0]) != eos
