"""The plain reference for the granite-4.0-h-small configuration
(``model_type`` ``granitemoehybrid``).

The decoder as published (Mamba-2: arXiv:2405.21060, section 3 and HF
``modeling_granitemoehybrid.py``; the multipliers: Granite 3/4's
``config.json``). ``x_0 = embedding_multiplier * E[token]``; each layer
``a = x + r * Mixer(RMSNorm(x))``, ``y = a + r * (Routed(u) +
Shared(u))`` with ``u = RMSNorm(a)`` and ``r = residual_multiplier``;
``logits = RMSNorm(x_L) E^T / logits_scaling`` (the head is the
embedding). The mixer is the one ``layer_types`` names:

* ``mamba``: ``[z | xBC | dt] = h W_in``; ``xBC_t <- silu(b_c + sum_{j
  = 0..3} w_c[j] * xBC_{t-3+j})`` (inputs before position 0 are zero);
  ``xBC`` splits into ``x_t`` [heads, head_dim], ``B_t`` and ``C_t``
  [state]; per head ``Delta_t = softplus(dt_t + dt_bias)``; **``S_t =
  exp(-Delta_t e^{A_log}) S_{t-1} + Delta_t x_t (x) B_t``**, ``S_{-1} =
  0``; ``y_t = S_t C_t + D x_t``; the mixer gives ``RMSNorm_g(y_t *
  silu(z_t)) W_out``, the norm over all heads at once. Computed STEP BY
  STEP over time, a ``lax.scan`` of that definition: nothing chunked.
* ``attention``: ``q, k, v = h W_q, h W_k, h W_v`` (grouped: query head
  ``i`` reads key-value head ``i // group``), NO position encoding,
  ``score = q . k * attention_multiplier``, causal softmax, ``W_o``.

Experts: ``g = u W_r`` over ALL published experts, the
``num_experts_per_tok`` largest, gates = softmax over those; each HELD
expert is applied to EVERY position and masked (no sort, no dispatch);
what the experts held on other chips would add is left out, as in the
program. The shared expert is added to every position.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no kernel, no cache. It
imports nothing of the program. Weights are data made HERE by the
recipe the configuration file states under ``assumed`` (A.X-K1's: every
leaf from ``fold_in(fold_in(PRNGKey(0), layer), leaf number)``, an
expert's folded once more with its GLOBAL index, a block of 128
vocabulary rows with its block index; normal times ``fan_in ** -0.5``,
embedding 0.001, rounded once to bfloat16 and widened here; the mixer's
vectors float32, ``A_log = log U(1, 16)``, ``dt_bias = softplus^-1 U(
0.001, 0.1)``, ``D = 1``).

So that the chip holds it: ONE layer's float32 weights at a time,
every sequence through that layer before the next is made; sequences
go ``GROUP`` at a time, padded at the end to a multiple of ``PAD_TO``,
so that the steps of the scan over time are shared.
"""
from __future__ import annotations

import functools
import math
import sys
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256     # query rows of a sequence worked on at once
PAD_TO = 512      # sequences are padded at the END to a multiple
GROUP = 8         # sequences of one padded length worked on at once
VOCAB_BLOCK = 128
LEAF = {name: i for i, name in enumerate((
    "w_in", "conv_w", "conv_b", "w_out", "a_log", "dt_bias",
    "wq", "wk", "wv", "wo",
    "router", "s_gate", "s_up", "s_down", "e_gate", "e_up", "e_down",
    "embed",
))}
TOP = 1_000_000
NEAR_TIE = 1e-3
A_RANGE = (1.0, 16.0)
DT_RANGE = (0.001, 0.1)
EMBED_SCALE = 0.001
#: the lower-precision readings ``run_pass`` can make of itself
MODES = ("bf16", "int8", "bf16-state")


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    share = config.get("share", {})
    held = share.get("held_experts", [0, config["num_local_experts"]])
    heads, head_dim = config["mamba_n_heads"], config["mamba_d_head"]
    state = config["mamba_d_state"]
    return {
        "d": config["hidden_size"], "h": config["num_attention_heads"],
        "kv": config["num_key_value_heads"],
        "hd": config["hidden_size"] // config["num_attention_heads"],
        "heads": heads, "p": head_dim, "n": state,
        "inner": heads * head_dim, "conv": heads * head_dim + 2 * state,
        "taps": config["mamba_d_conv"],
        "fe": config["intermediate_size"],
        "fs": config.get("shared_intermediate_size", 0),
        "experts": share.get("router_experts", config["num_local_experts"]),
        "held": (int(held[0]), int(held[1])),
        "k": config["num_experts_per_tok"],
        "kinds": list(config["layer_types"]),
        "vocab": config["vocab_size"], "eps": config["rms_norm_eps"],
    }


# -- weights ------------------------------------------------------------


def _key(layer: int, name: str):
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(0), layer), LEAF[name])


def _bf16_values(x):
    """float32 ``x`` rounded (to nearest, ties to even) to the values
    bfloat16 holds, still float32. ``reduce_precision`` is an operation
    the compiler keeps; ``x.astype(bfloat16).astype(float32)`` inside a
    jitted float32 computation is not: on the chip XLA drops that round
    trip (``xla_allow_excess_precision``), and the rounding with it."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw(key, shape, scale):
    """float32 draw, rounded once to bfloat16's values."""
    return _bf16_values(jax.random.normal(key, shape, jnp.float32) * scale)


def layer_weights(config: Dict[str, Any], layer: int,
                  experts=None) -> Dict[str, Any]:
    """One layer's weights; ``experts`` (lo, hi) overrides the held
    range (the test that adds the shares up)."""
    z = sizes(config)
    d = z["d"]
    mamba = z["kinds"][layer] == "mamba"
    if mamba:
        shapes = {
            "w_in": ((d, z["inner"] + z["conv"] + z["heads"]), d),
            "conv_w": ((z["taps"], z["conv"]), z["taps"]),
            "conv_b": ((z["conv"],), z["taps"]),
            "w_out": ((z["inner"], d), z["inner"]),
        }
    else:
        shapes = {
            "wq": ((d, z["h"], z["hd"]), d), "wk": ((d, z["kv"], z["hd"]), d),
            "wv": ((d, z["kv"], z["hd"]), d),
            "wo": ((z["h"], z["hd"], d), z["h"] * z["hd"]),
        }
    shapes["router"] = ((d, z["experts"]), d)
    if z["fs"]:
        shapes.update({"s_gate": ((d, z["fs"]), d), "s_up": ((d, z["fs"]), d),
                       "s_down": ((z["fs"], d), z["fs"])})
    w = {name: _draw(_key(layer, name), shape, fan_in ** -0.5)
         for name, (shape, fan_in) in shapes.items()}
    lo, hi = experts or z["held"]
    for name, shape, fan_in in (("e_gate", (d, z["fe"]), d),
                                ("e_up", (d, z["fe"]), d),
                                ("e_down", (z["fe"], d), z["fe"])):
        w[name] = jnp.stack([
            _draw(jax.random.fold_in(_key(layer, name), e), shape,
                  fan_in ** -0.5) for e in range(lo, hi)])
    if mamba:
        w["a_log"] = jnp.log(jax.random.uniform(
            _key(layer, "a_log"), (z["heads"],), jnp.float32, *A_RANGE))
        w["dt_bias"] = jnp.log(jnp.expm1(jax.random.uniform(
            _key(layer, "dt_bias"), (z["heads"],), jnp.float32, *DT_RANGE)))
    return w


def embedding(config: Dict[str, Any]):
    z = sizes(config)
    key = _key(TOP, "embed")
    return jnp.concatenate([
        _draw(jax.random.fold_in(key, b), (VOCAB_BLOCK, z["d"]), EMBED_SCALE)
        for b in range(z["vocab"] // VOCAB_BLOCK)])


MATMUL_AXES = {  # name -> the axes a token's activations contract over
    "w_in": (0,), "w_out": (0,), "wq": (0,), "wk": (0,), "wv": (0,),
    "wo": (0, 1), "s_gate": (0,), "s_up": (0,), "s_down": (0,),
    "e_gate": (1,), "e_up": (1,), "e_down": (1,), "embed": (1,),
}


@functools.partial(jax.jit, static_argnums=(1,))
def _int8(w, axes):
    """8 bits a weight, one float scale per output channel (symmetric,
    absmax / 127): the nearest precision below the stated bfloat16."""
    scale = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 127.0
    return _bf16_values(jnp.clip(jnp.round(w / scale), -127, 127) * scale)


def lower_precision(weights: Dict[str, Any], mode: str) -> Dict[str, Any]:
    """``int8``: every matmul weight but the router on an int8 grid;
    any other mode leaves the weights as they are."""
    if mode != "int8":
        return weights
    return {name: _int8(w, MATMUL_AXES[name]) if name in MATMUL_AXES else w
            for name, w in weights.items()}


# -- the layers -----------------------------------------------------------


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def mamba_mixer(h, w, config, bf16_state=False):
    """h [rows, seq, d] (normed) -> the mixer's output [rows, seq, d].
    The recurrence is scanned one position at a time; ``bf16_state``
    rounds ``S`` to bfloat16's values after every step (the control of
    a pool that holds ``S`` in bfloat16)."""
    z = sizes(config)
    rows, seq, _d = h.shape
    inner, conv, taps = z["inner"], z["conv"], z["taps"]
    zxd = h @ w["w_in"]
    gate, xbc, dt = (zxd[..., :inner], zxd[..., inner:inner + conv],
                     zxd[..., inner + conv:])
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(w["conv_b"] + sum(
        w["conv_w"][j] * padded[:, j:j + seq] for j in range(taps)))
    x = xbc[..., :inner].reshape(rows, seq, z["heads"], z["p"])
    b_in = xbc[..., inner:inner + z["n"]]
    c_out = xbc[..., inner + z["n"]:]
    step = jax.nn.softplus(dt + w["dt_bias"])       # [rows, seq, heads]
    decay = jnp.exp(-step * jnp.exp(w["a_log"]))

    def one(state, inputs):
        x_t, b_t, c_t, step_t, decay_t = inputs
        state = (decay_t[:, :, None, None] * state
                 + (step_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        if bf16_state:
            state = _bf16_values(state)
        return state, jnp.sum(state * c_t[:, None, None, :], axis=-1) + x_t

    over_time = [jnp.moveaxis(v, 1, 0) for v in (x, b_in, c_out, step, decay)]
    _last, y = jax.lax.scan(
        one, jnp.zeros((rows, z["heads"], z["p"], z["n"])), over_time)
    y = jnp.moveaxis(y, 0, 1).reshape(rows, seq, inner)  # D = 1
    return _rms(y * jax.nn.silu(gate), z["eps"]) @ w["w_out"]


def attention_mixer(h, w, config):
    """h [rows, seq, d] (normed) -> the mixer's output. No position
    encoding; the published multiplier scales the scores."""
    z = sizes(config)
    rows, seq, _d = h.shape
    group = z["h"] // z["kv"]
    q = jnp.einsum("rsd,dhk->rshk", h, w["wq"])
    k = jnp.repeat(jnp.einsum("rsd,dhk->rshk", h, w["wk"]), group, axis=2)
    v = jnp.repeat(jnp.einsum("rsd,dhk->rshk", h, w["wv"]), group, axis=2)
    block = math.gcd(seq, Q_BLOCK)
    cols = jnp.arange(seq)

    def at(start):
        qs = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("rqhd,rkhd->rhqk", qs, k) * config[
            "attention_multiplier"]
        mask = cols[None, :] <= (start + jnp.arange(block))[:, None]
        weights = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
        return jnp.einsum("rhqk,rkhd->rqhd", weights, v)

    o = jax.lax.map(at, jnp.arange(0, seq, block))   # [n, rows, block, h, hd]
    o = jnp.moveaxis(o, 0, 1).reshape(rows, seq, z["h"], z["hd"])
    return jnp.einsum("rshk,hkd->rsd", o, w["wo"])


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def route(h, router, config):
    """(expert ids [..., k], gates [..., k]: a softmax over the chosen
    scores, edge [...]: how far the last chosen score lies above the
    first one left out)."""
    k = sizes(config)["k"]
    top, idx = jax.lax.top_k(h @ router, k + 1)
    return idx[..., :k], jax.nn.softmax(top[..., :k], axis=-1), (
        top[..., k - 1] - top[..., k])


def experts_part(h, idx, gates, w, lo):
    """What the experts ``lo, lo + 1, ...`` (whose weights ``w`` holds,
    in that order) add: each applied to every position, then masked."""
    def one(total, inputs):
        e, gate, up, down = inputs
        weight = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)
        return total + _swiglu(h, gate, up, down) * weight[..., None], None

    total, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (lo + jnp.arange(w["e_gate"].shape[0]), w["e_gate"], w["e_up"],
         w["e_down"]))
    return total


def expert_layer(u, w, config, lo=None, shared=True):
    """``Routed(u) + Shared(u)`` over the held experts (from ``lo``)."""
    z = sizes(config)
    idx, gates, edge = route(u, w["router"], config)
    y = experts_part(u, idx, gates, w, z["held"][0] if lo is None else lo)
    if z["fs"] and shared:
        y = y + _swiglu(u, w["s_gate"], w["s_up"], w["s_down"])
    return y, edge


def layer(x, w, config, kind, bf16_state=False):
    """One whole layer over x [rows, seq, d]. Returns (x, near-tie
    mask [rows, seq])."""
    z = sizes(config)
    r = config["residual_multiplier"]
    h = _rms(x, z["eps"])
    if kind == "mamba":
        x = x + r * mamba_mixer(h, w, config, bf16_state)
    else:
        x = x + r * attention_mixer(h, w, config)
    y, edge = expert_layer(_rms(x, z["eps"]), w, config)
    return x + r * y, edge < NEAR_TIE


def all_logits(config: Dict[str, Any], tokens, precision: str = "highest",
               mode: str = ""):
    """Logits [seq, vocab] of ONE sequence from position 0, every
    layer's weights made in turn (tests; the chip path is
    ``run_pass``)."""
    z = sizes(config)
    with jax.default_matmul_precision(precision):
        embed = embedding(config)
        x = config["embedding_multiplier"] * embed[jnp.asarray(tokens)][None]
        for i, kind in enumerate(z["kinds"]):
            w = lower_precision(layer_weights(config, i), mode)
            x, _near = layer(x, w, config, kind, mode == "bf16-state")
        if mode == "int8":
            embed = _int8(embed, MATMUL_AXES["embed"])
        return (_rms(x[0], z["eps"]) @ embed.T) / config["logits_scaling"]


# -- what the harness's child calls -------------------------------------


def _say(*words: Any) -> None:
    """Progress, to the child's log (``reference.log``)."""
    print("granite_reference:", *words, file=sys.stderr, flush=True)


def _batches(rows: List[List[int]], cap: int):
    """The rows ``GROUP`` at a time by padded length: [(indices, ids
    [GROUP, length])], short groups filled with rows of zeros."""
    by_length: Dict[int, List[int]] = {}
    for i, row in enumerate(rows):
        length = min(-(-len(row) // PAD_TO) * PAD_TO, max(cap, len(row)))
        by_length.setdefault(length, []).append(i)
    out = []
    for length, members in sorted(by_length.items()):
        for start in range(0, len(members), GROUP):
            indices = members[start:start + GROUP]
            ids = np.zeros((GROUP, length), np.int32)
            for slot, i in enumerate(indices):
                ids[slot, : len(rows[i])] = rows[i]
            out.append((indices, ids))
    return out


def run_pass(config: Dict[str, Any], rows: List[List[int]],
             keep: List[np.ndarray], cap: int, mode: str = ""):
    """Every row through the model layer by layer: one layer's weights
    at a time, all rows through it, then the next. ``mode`` "" is the
    reference (float32 products, ``highest``); "bf16" and "int8" read
    with single-pass bf16 products (and int8 weights), "bf16-state"
    with the reference's products and ``S`` rounded to bfloat16 after
    every step. Returns (per row the logits at its ``keep`` positions
    as numpy, near-tie count, routed (position, layer) pairs)."""
    z = sizes(config)
    precision = "default" if mode in ("bf16", "int8") else "highest"

    @functools.partial(jax.jit, static_argnums=(2,))
    def through(x, w, kind):
        with jax.default_matmul_precision(precision):
            return layer(x, w, config, kind, mode == "bf16-state")

    @jax.jit
    def head_at(x, embed, at):
        with jax.default_matmul_precision(precision):
            return (_rms(x[at], z["eps"]) @ embed.T) / config["logits_scaling"]

    embed = embedding(config)
    batches = _batches(rows, cap)
    hidden = [config["embedding_multiplier"] * embed[jnp.asarray(ids)]
              for _indices, ids in batches]
    near_ties = []
    for i, kind in enumerate(z["kinds"]):
        t0 = time.monotonic()
        w = lower_precision(layer_weights(config, i), mode)
        jax.block_until_ready(w)
        t1 = time.monotonic()
        for b, (indices, _ids) in enumerate(batches):
            hidden[b], near = through(hidden[b], w, kind)
            near_ties += [jnp.sum(near[slot, : len(rows[r])])
                          for slot, r in enumerate(indices)]
        del w
        jax.block_until_ready(hidden)
        _say(f"mode {mode or 'highest'!r} layer {i} ({kind}): weights "
             f"{t1 - t0:.1f} s, {len(batches)} batches "
             f"{time.monotonic() - t1:.1f} s")
    if mode == "int8":
        embed = _int8(embed, MATMUL_AXES["embed"])
    out: List[Any] = [None] * len(rows)
    for b, (indices, ids) in enumerate(batches):
        for slot, r in enumerate(indices):
            # the positions kept are padded to a multiple too: few shapes
            at = keep[r]
            padded = np.zeros(
                (min(-(-len(at) // 128) * 128, ids.shape[1]),), np.int32)
            padded[: len(at)] = at
            out[r] = np.asarray(head_at(
                hidden[b][slot], embed, jnp.asarray(padded)))[: len(at)]
        hidden[b] = None
    pairs = sum(len(r) for r in rows) * len(z["kinds"])
    return out, int(sum(int(n) for n in near_ties)), pairs


def check_served(config: Dict[str, Any], spec: Dict[str, Any]) -> Dict[str, Any]:
    """For each case (a prompt and the greedy tokens the server
    streamed for it): the reference over prompt + tokens, and at every
    generated position how far the served token's logit lies below the
    reference's best. One full forward, so it is also the statement
    that a chunked prefill followed by one-step updates through the
    pool agrees with the plain recurrence. ``near_tie_share`` is a
    diagnosis, no limit: the share of (position, layer) pairs whose
    last chosen and first unchosen router scores lie within 1e-3, where
    a bf16 program and this reference may choose different experts."""
    cap = int(spec["max_len"])
    rows, keep = [], []
    for case in spec["cases"]:
        prompt, served = case["prompt"], case["tokens"]
        row = (prompt + served)[:-1]
        rows.append(row)
        keep.append(np.arange(len(prompt) - 1, len(row)))
    logits, near_ties, pairs = run_pass(config, rows, keep, cap)
    cases = []
    worst = total = 0.0
    positions = 0
    best_of = []
    for case, got in zip(spec["cases"], logits):
        served = np.asarray(case["tokens"])
        best = got.max(axis=-1)
        gaps = best - got[np.arange(len(served)), served]
        best_of.append(best)
        cases.append({
            "index": case["index"], "prompt_len": len(case["prompt"]),
            "served": len(served), "max_gap": float(gaps.max()),
            "exact": int((gaps == 0).sum()),
            "first_divergence": int(np.argmax(gaps > 0)) if (gaps > 0).any() else -1,
            "best_logit_abs_max": float(np.abs(best).max()),
        })
        worst = max(worst, float(gaps.max()))
        total += float(gaps.sum())
        positions += len(served)
    # the controls: the reference itself in a lower precision on the
    # SAME prompts and tokens; at each position the gap, under the
    # float32 reading, of the token that variant puts first, and, as
    # the proof that the lower precision took place, how far it moved
    # any logit and how many positions it gives another token
    controls = {}
    for mode in spec.get("controls", ()):
        if mode not in MODES:
            raise ValueError(f"control {mode!r}: one of {', '.join(MODES)}")
        lower, _ties, _pairs = run_pass(config, rows, keep, cap, mode)
        c_sum = c_max = moved = 0.0
        changed = 0
        for got, theirs, best in zip(logits, lower, best_of):
            picked = theirs.argmax(axis=-1)
            gaps = best - got[np.arange(len(picked)), picked]
            c_sum += float(gaps.sum())
            c_max = max(c_max, float(gaps.max()))
            moved = max(moved, float(np.abs(theirs - got).max()))
            changed += int((gaps > 0).sum())
        if not moved > 0:
            # a control that IS the reference says nothing by reading 0
            raise RuntimeError(
                f"control {mode!r} left every logit as the reference has "
                "it: the lower precision did not take place")
        controls[mode] = {"max_logit_gap": c_max,
                          "mean_logit_gap": c_sum / max(positions, 1),
                          "logits_moved_max": moved,
                          "tokens_changed": changed}
    return {"cases": cases, "max_logit_gap": worst, "positions": positions,
            "mean_logit_gap": total / max(positions, 1),
            "near_tie_share": near_ties / max(pairs, 1),
            "controls": controls}
