"""The plain reference for the Phi-4-mini-flash-reasoning configuration
(``model_type`` ``phi4flash``: the decoder-hybrid-decoder "SambaY",
arXiv:2507.06607, with Mamba-1 mixers, arXiv:2312.00752, and
differential attention, arXiv:2410.05258).

``x_0 = E[token]``; every layer ``h = h + Mixer_i(LN(h))``, ``h = h +
W2(up * silu(gate))`` with ``[gate, up] = W1 LN(h)``; ``logits =
LN(h_L) E^T`` (the head is the embedding). ``LN`` is LayerNorm with a
weight and a bias; nothing encodes a position. For ``n`` layers the
mixer of layer ``i`` is

* ``i`` even, ``i <= n/2``: **Mamba-1**. ``[x, z] = u W_in``; ``x_t <-
  silu(b_c + sum_{j = 0..3} w_c[j] x_{t-3+j})`` (inputs before position 0
  are zero); ``[dr, B, C] = x W_x``; ``delta = softplus(dr W_dt +
  b_dt)``; ``A = -exp(A_log)`` [inner, state]; **``S_t = exp(delta_t A)
  S_{t-1} + (delta_t x_t) (x) B_t``**, ``S_{-1} = 0``; ``y_t = S_t C_t +
  D x_t``; the mixer gives ``(y * silu(z)) W_out``. Layer ``n/2`` also
  hands on ``m = y``, before the gate: the memory. Computed STEP BY STEP
  over time, a ``lax.scan`` of that definition whose step makes its own
  decay: nothing chunked, and no array of [time, inner, state] exists.
* ``i`` odd, ``i < n/2``: **window attention**, causal over the last
  ``sliding_window`` positions, self included. ``i = n/2 + 1``: **full
  attention**, the same without the window. ``i`` odd, ``i >= n/2 + 3``:
  **cross attention**: its own query projection, the keys and values of
  layer ``n/2 + 1`` at positions ``<= t``. All in the differential form:
  query heads ``2j, 2j + 1`` are ``(q1, q2)``, key heads ``2p, 2p + 1``
  are ``(k1, k2)``, value heads ``2p, 2p + 1`` joined are ``V``, query
  pair ``j`` reads key pair ``j // group``; ``a = softmax(q1 k1^T /
  sqrt(hd)) V - lambda softmax(q2 k2^T / sqrt(hd)) V``, ``lambda =
  exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 -
  0.6 exp(-0.3 i)``; ``o = RMSNorm(a) w (1 - lambda_init)``; heads joined,
  then ``W_o`` with its bias.
* ``i`` even, ``i >= n/2 + 2``: **gated memory unit**, ``(silu(u W_in) *
  m_t) W_out`` with ``m_t`` layer ``n/2``'s memory at the same position.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no kernel, no cache, no
ring, no trimmed prefill: every layer runs at every position. It
imports nothing of the program. Weights are data made HERE by the
recipe the configuration file states under ``assumed.weight_recipe``
(every leaf from ``fold_in(fold_in(PRNGKey(0), layer), leaf number)``,
a block of 128 vocabulary rows with its block index; rounded once to
bfloat16's values and held in float32).

So that the chip holds it: ONE layer's float32 weights at a time, every
sequence through that layer before the next is made; sequences go
``GROUP`` at a time, padded at the end to a multiple of ``PAD_TO``.
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
GROUP = 4         # sequences of one padded length worked on at once
VOCAB_BLOCK = 128
LEAF = {name: i for i, name in enumerate((
    "w_in", "conv_w", "conv_b", "w_x", "w_dt", "dt_bias", "w_out",
    "w_qkv", "b_qkv", "w_q", "b_q", "w_o", "b_o", "lambdas",
    "g_in", "g_out", "w_gate", "w_up", "w_down", "embed",
))}
TOP = 1_000_000
EMBED_SCALE = 0.001
BIAS_SCALE = 0.02
LAMBDA_SCALE = 0.1
DT_RANGE = (0.001, 0.1)
#: the readings ``run_pass`` can make of itself that are NOT the model:
#: a lower precision, and three mechanisms left out
MODES = ("int8-weights", "bf16-state", "no-window", "no-difference",
         "stale-memory")


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    assumed = config.get("assumed", {})
    d, heads = config["hidden_size"], config["num_attention_heads"]
    n = config["num_hidden_layers"]
    half = n // 2
    kinds = [
        ("mamba" if i <= half else "gmu") if i % 2 == 0
        else "window" if i < half
        else "full" if i == half + 1 else "cross"
        for i in range(n)]
    return {
        "d": d, "h": heads, "kv": config["num_key_value_heads"],
        "hd": d // heads, "ff": config["intermediate_size"],
        "inner": int(assumed.get("mamba_expand", 2)) * d,
        "n": int(assumed.get("mamba_d_state", 16)),
        "taps": int(assumed.get("mamba_d_conv", 4)),
        "rank": int(assumed.get("mamba_dt_rank", math.ceil(d / 16))),
        "window": config["sliding_window"], "kinds": kinds,
        "memory_layer": half, "vocab": config["vocab_size"],
        "eps": config["layer_norm_eps"],
    }


# -- weights ------------------------------------------------------------


def _key(layer: int, name: str):
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(0), layer), LEAF[name])


def _bf16_values(x):
    """float32 ``x`` rounded (to nearest, ties to even) to the values
    bfloat16 holds, still float32: ``reduce_precision`` is an operation
    the chip's compiler keeps, a cast there and back is not."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw(key, shape, scale):
    """float32 draw, rounded once to bfloat16's values."""
    return _bf16_values(jax.random.normal(key, shape, jnp.float32) * scale)


def layer_weights(config: Dict[str, Any], layer: int) -> Dict[str, Any]:
    """One layer's weights, in the published shapes."""
    z = sizes(config)
    d, inner, hd = z["d"], z["inner"], z["hd"]
    kind = z["kinds"][layer]
    q_width, kv_width = z["h"] * hd, z["kv"] * hd
    shapes = {"w_gate": ((d, z["ff"]), d), "w_up": ((d, z["ff"]), d),
              "w_down": ((z["ff"], d), z["ff"])}
    biases = {}
    if kind == "mamba":
        shapes.update({
            "w_in": ((d, 2 * inner), d),
            "conv_w": ((z["taps"], inner), z["taps"]),
            "w_x": ((inner, z["rank"] + 2 * z["n"]), inner),
            "w_dt": ((z["rank"], inner), z["rank"]),
            "w_out": ((inner, d), inner)})
        biases["conv_b"] = (inner,)
    elif kind == "gmu":
        shapes.update({"g_in": ((d, inner), d), "g_out": ((inner, d), inner)})
    else:
        if kind == "cross":
            shapes["w_q"] = ((d, q_width), d)
            biases["b_q"] = (q_width,)
        else:
            shapes["w_qkv"] = ((d, q_width + 2 * kv_width), d)
            biases["b_qkv"] = (q_width + 2 * kv_width,)
        shapes["w_o"] = ((q_width, d), q_width)
        biases["b_o"] = (d,)
    w = {name: _draw(_key(layer, name), shape, fan_in ** -0.5)
         for name, (shape, fan_in) in shapes.items()}
    for name, shape in biases.items():
        w[name] = _draw(_key(layer, name), shape, BIAS_SCALE)
    if kind == "mamba":
        w["a_log"] = jnp.broadcast_to(
            jnp.log(jnp.arange(1, z["n"] + 1, dtype=jnp.float32)),
            (inner, z["n"]))
        lo, hi = (math.log(v) for v in DT_RANGE)
        w["dt_bias"] = jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
            _key(layer, "dt_bias"), (inner,), jnp.float32, lo, hi))))
    elif kind != "gmu":
        w["lambdas"] = jax.random.normal(
            _key(layer, "lambdas"), (4, hd), jnp.float32) * LAMBDA_SCALE
    return w


def embedding(config: Dict[str, Any]):
    z = sizes(config)
    key = _key(TOP, "embed")
    return jnp.concatenate([
        _draw(jax.random.fold_in(key, b), (VOCAB_BLOCK, z["d"]), EMBED_SCALE)
        for b in range(z["vocab"] // VOCAB_BLOCK)])


#: the matrices a token's activations are multiplied by, and the axis
#: they contract over
MATMUL_AXES = {name: (0,) for name in (
    "w_in", "w_x", "w_dt", "w_out", "w_qkv", "w_q", "w_o", "g_in", "g_out",
    "w_gate", "w_up", "w_down")}
MATMUL_AXES["embed"] = (1,)


@functools.partial(jax.jit, static_argnums=(1,))
def _int8(w, axes):
    """8 bits a weight, one float scale per output channel (symmetric,
    absmax / 127): the nearest precision below the stated bfloat16."""
    scale = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 127.0
    return _bf16_values(jnp.clip(jnp.round(w / scale), -127, 127) * scale)


def lower_precision(weights: Dict[str, Any], mode: str) -> Dict[str, Any]:
    """``int8-weights``: every matrix on an int8 grid; any other mode
    leaves the weights as they are."""
    if mode != "int8-weights":
        return weights
    return {name: _int8(w, MATMUL_AXES[name]) if name in MATMUL_AXES else w
            for name, w in weights.items()}


# -- the layers -----------------------------------------------------------


def _layer_norm(x, eps):
    """LayerNorm with the recipe's weight 1 and bias 0."""
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(
        jnp.mean(centred * centred, axis=-1, keepdims=True) + eps)


def mamba_mixer(u, w, config, bf16_state=False):
    """u [rows, seq, d] (normed) -> (the mixer's output [rows, seq, d],
    y before the gate [rows, seq, inner]). The recurrence is scanned one
    position at a time; ``bf16_state`` rounds ``S`` to bfloat16's values
    after every step (the control of a pool that holds it so)."""
    z = sizes(config)
    rows, seq, _d = u.shape
    inner, taps, rank, n = z["inner"], z["taps"], z["rank"], z["n"]
    xz = u @ w["w_in"]
    x, gate = xz[..., :inner], xz[..., inner:]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    x = jax.nn.silu(w["conv_b"] + sum(
        w["conv_w"][j] * padded[:, j:j + seq] for j in range(taps)))
    dbc = x @ w["w_x"]
    delta = jax.nn.softplus(dbc[..., :rank] @ w["w_dt"] + w["dt_bias"])
    b_in, c_out = dbc[..., rank:rank + n], dbc[..., rank + n:]
    rate = -jnp.exp(w["a_log"])                       # [inner, n]

    def one(state, inputs):
        x_t, delta_t, b_t, c_t = inputs               # [rows, inner] x 2, [rows, n] x 2
        state = (jnp.exp(delta_t[:, :, None] * rate) * state
                 + (delta_t * x_t)[:, :, None] * b_t[:, None, :])
        if bf16_state:
            state = _bf16_values(state)
        return state, jnp.sum(state * c_t[:, None, :], axis=-1) + x_t  # D = 1

    over_time = [jnp.moveaxis(v, 1, 0) for v in (x, delta, b_in, c_out)]
    _last, y = jax.lax.scan(one, jnp.zeros((rows, inner, n)), over_time)
    y = jnp.moveaxis(y, 0, 1)
    return (y * jax.nn.silu(gate)) @ w["w_out"], y


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def differential_attention(q, k, v, w, config, start, window: int,
                           difference: bool = True):
    """q [rows, seq, heads, hd], k and v [rows, seq, kv_heads, hd] ->
    the heads joined [rows, seq, heads x hd]; causal, and over the last
    ``window`` positions where ``window`` > 0; ``start`` is the layer's
    ``lambda_init``. ``difference`` False leaves the second map out
    (lambda = 0)."""
    z = sizes(config)
    rows, seq = q.shape[:2]
    hd, pairs = z["hd"], z["kv"] // 2
    group = (z["h"] // 2) // pairs
    q = q.reshape(rows, seq, pairs, group, 2, hd)
    k = k.reshape(rows, seq, pairs, 2, hd)
    value = v.reshape(rows, seq, pairs, 2 * hd)       # [v1 ; v2]
    block = math.gcd(seq, Q_BLOCK)
    cols = jnp.arange(seq)

    def at(start):
        qs = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("rqpgwd,rkpwd->rpgwqk", qs, k) * hd ** -0.5
        here = (start + jnp.arange(block))[:, None]
        mask = cols[None, :] <= here
        if window:
            mask = mask & (cols[None, :] > here - window)
        maps = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("rpgwqk,rkpe->rqpgwe", maps, value)

    o = jax.lax.map(at, jnp.arange(0, seq, block))    # [n, rows, block, ...]
    o = jnp.moveaxis(o, 0, 1).reshape(rows, seq, pairs, group, 2, 2 * hd)
    lq1, lk1, lq2, lk2 = w["lambdas"]
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + start
    a = o[..., 0, :] - (lam if difference else 0.0) * o[..., 1, :]
    a = a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True) + 1e-5)
    a = a * (1.0 - start)                             # the norm's weight is 1
    return a.reshape(rows, seq, -1) @ w["w_o"] + w["b_o"]


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def layer(x, w, config, kind: str, start, carried, mode: str = "",
          hands_on: bool = False):
    """One whole layer of ``kind`` over x [rows, seq, d]; ``start`` is
    an attention layer's ``lambda_init``. ``carried`` holds what later
    layers read of earlier ones: the memory (a Mamba layer that
    ``hands_on`` puts it there) and the full layer's keys and values.
    Returns x."""
    z = sizes(config)
    rows, seq, _d = x.shape
    hd = z["hd"]
    u = _layer_norm(x, z["eps"])
    if kind == "mamba":
        out, y = mamba_mixer(u, w, config, mode == "bf16-state")
        if hands_on:
            carried["memory"] = y
    elif kind == "gmu":
        memory = carried["memory"]
        if mode == "stale-memory":                    # m_{t-1}, nothing at 0
            memory = jnp.pad(memory, ((0, 0), (1, 0), (0, 0)))[:, :-1]
        out = (jax.nn.silu(u @ w["g_in"]) * memory) @ w["g_out"]
    else:
        if kind == "cross":
            q = (u @ w["w_q"] + w["b_q"]).reshape(rows, seq, z["h"], hd)
            k, v = carried["k"], carried["v"]
        else:
            q_width, kv_width = z["h"] * hd, z["kv"] * hd
            qkv = u @ w["w_qkv"] + w["b_qkv"]
            q = qkv[..., :q_width].reshape(rows, seq, z["h"], hd)
            k = qkv[..., q_width:q_width + kv_width].reshape(
                rows, seq, z["kv"], hd)
            v = qkv[..., q_width + kv_width:].reshape(rows, seq, z["kv"], hd)
            if kind == "full":
                carried["k"], carried["v"] = k, v
        window = z["window"] if kind == "window" and mode != "no-window" else 0
        out = differential_attention(
            q, k, v, w, config, start, window, mode != "no-difference")
    x = x + out
    return x + _swiglu(_layer_norm(x, z["eps"]), w["w_gate"], w["w_up"],
                       w["w_down"])


def all_logits(config: Dict[str, Any], tokens, precision: str = "highest",
               mode: str = ""):
    """Logits [seq, vocab] of ONE sequence from position 0, every
    layer's weights made in turn (tests; the chip path is
    ``run_pass``)."""
    z = sizes(config)
    with jax.default_matmul_precision(precision):
        embed = embedding(config)
        x = embed[jnp.asarray(tokens)][None]
        carried: Dict[str, Any] = {}
        for i, kind in enumerate(z["kinds"]):
            w = lower_precision(layer_weights(config, i), mode)
            x = layer(x, w, config, kind, lambda_init(i), carried, mode,
                      i == z["memory_layer"])
        if mode == "int8-weights":
            embed = _int8(embed, MATMUL_AXES["embed"])
        return _layer_norm(x[0], z["eps"]) @ embed.T


# -- what the harness's child calls -------------------------------------


def _say(*words: Any) -> None:
    """Progress, to the child's log (``reference.log``)."""
    print("phi4flash_reference:", *words, file=sys.stderr, flush=True)


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
    reference; the others are ``MODES``. Returns per row the stream
    after the last LayerNorm at its ``keep`` positions, padded to a
    multiple of 128 positions (few shapes), float32 numpy [n, d]: the
    head is applied where the logits are judged (``_judge``), a case at
    a time, because a case's logits are 0.8 GB at this vocabulary and a
    run's would not fit the host."""
    z = sizes(config)

    @functools.partial(jax.jit, static_argnums=(2, 5), donate_argnums=(0,))
    def through(x, w, kind, start, carried, hands_on):
        """(x after a layer, what the layer hands on to later ones);
        one program a kind of layer and padded length."""
        with jax.default_matmul_precision("highest"):
            seen = dict(carried)
            x = layer(x, w, config, kind, start, seen, mode, hands_on)
            return x, {k: v for k, v in seen.items() if k not in carried}

    @jax.jit
    def normed_at(x, at):
        return _layer_norm(x[at], z["eps"])

    embed = embedding(config)
    batches = _batches(rows, cap)
    hidden = [embed[jnp.asarray(ids)] for _indices, ids in batches]
    del embed
    carried: List[Dict[str, Any]] = [{} for _ in batches]
    for i, kind in enumerate(z["kinds"]):
        t0 = time.monotonic()
        w = lower_precision(layer_weights(config, i), mode)
        jax.block_until_ready(w)
        t1 = time.monotonic()
        for b in range(len(batches)):
            hidden[b], handed = through(
                hidden[b], w, kind, jnp.float32(lambda_init(i)), carried[b],
                i == z["memory_layer"])
            carried[b].update(handed)
        del w
        jax.block_until_ready(hidden)
        _say(f"mode {mode or 'highest'!r} layer {i} ({kind}): weights "
             f"{t1 - t0:.1f} s, {len(batches)} batches "
             f"{time.monotonic() - t1:.1f} s")
    del carried
    out: List[Any] = [None] * len(rows)
    for b, (indices, ids) in enumerate(batches):
        for slot, r in enumerate(indices):
            at = keep[r]
            padded = np.zeros(
                (min(-(-len(at) // 128) * 128, ids.shape[1]),), np.int32)
            padded[: len(at)] = at
            out[r] = np.asarray(normed_at(hidden[b][slot], jnp.asarray(padded)))
        hidden[b] = None
    return out


@jax.jit
def _judge(normed, embed, tokens):
    """Logits of one case's kept positions (on the device, never
    fetched whole): per position the best logit and how far
    ``tokens``' logit lies below it."""
    with jax.default_matmul_precision("highest"):
        logits = normed @ embed.T
    best = logits.max(axis=-1)
    mine = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return best, best - mine


@jax.jit
def _judge_variant(normed, theirs, embed, their_embed):
    """One case under the reference and under a variant of it: per
    position the gap, under the reference's reading, of the token the
    variant puts first, and how far the variant moved any logit."""
    with jax.default_matmul_precision("highest"):
        logits = normed @ embed.T
        other = theirs @ their_embed.T
    picked = jnp.argmax(other, axis=-1)
    gaps = logits.max(axis=-1) - jnp.take_along_axis(
        logits, picked[:, None], axis=-1)[:, 0]
    return gaps, jnp.max(jnp.abs(other - logits), axis=-1)


def check_served(config: Dict[str, Any], spec: Dict[str, Any]) -> Dict[str, Any]:
    """For each case (a prompt and the greedy tokens the server
    streamed for it): the reference over prompt + tokens, and at every
    generated position how far the served token's logit lies below the
    reference's best. One full forward of every layer at every
    position, so it is also the statement that the trimmed prefill, the
    chunked scan, the rings and one-step updates through the pool agree
    with the plain model."""
    cap = int(spec["max_len"])
    rows, keep = [], []
    for case in spec["cases"]:
        prompt, served = case["prompt"], case["tokens"]
        row = (prompt + served)[:-1]
        rows.append(row)
        keep.append(np.arange(len(prompt) - 1, len(row)))
    normed = run_pass(config, rows, keep, cap)
    embed = embedding(config)
    cases = []
    worst = total = 0.0
    positions = 0
    for case, got in zip(spec["cases"], normed):
        n = len(case["tokens"])
        served = np.zeros((len(got),), np.int32)
        served[:n] = case["tokens"]
        best, gaps = (np.asarray(v)[:n] for v in _judge(
            jnp.asarray(got), embed, jnp.asarray(served)))
        cases.append({
            "index": case["index"], "prompt_len": len(case["prompt"]),
            "served": n, "max_gap": float(gaps.max()),
            "exact": int((gaps == 0).sum()),
            "first_divergence": int(np.argmax(gaps > 0)) if (gaps > 0).any() else -1,
            "best_logit_abs_max": float(np.abs(best).max()),
        })
        worst = max(worst, float(gaps.max()))
        total += float(gaps.sum())
        positions += n
    # the controls: the reference itself in a lower precision, or with a
    # mechanism left out, on the SAME prompts and tokens; at each
    # position the gap, under the reference's reading, of the token
    # that variant puts first, and, as the proof that the variant took
    # place, how far it moved any logit and how many positions it gives
    # another token
    controls = {}
    for mode in spec.get("controls", ()):
        if mode not in MODES:
            raise ValueError(f"control {mode!r}: one of {', '.join(MODES)}")
        lower = run_pass(config, rows, keep, cap, mode)
        their_embed = (_int8(embed, MATMUL_AXES["embed"])
                       if mode == "int8-weights" else embed)
        c_sum = c_max = moved = 0.0
        changed = 0
        for case, got, theirs in zip(spec["cases"], normed, lower):
            n = len(case["tokens"])
            gaps, shift = (np.asarray(v)[:n] for v in _judge_variant(
                jnp.asarray(got), jnp.asarray(theirs), embed, their_embed))
            c_sum += float(gaps.sum())
            c_max = max(c_max, float(gaps.max()))
            moved = max(moved, float(shift.max()))
            changed += int((gaps > 0).sum())
        del their_embed
        if not moved > 0:
            # a control that IS the reference says nothing by reading 0
            raise RuntimeError(
                f"control {mode!r} left every logit as the reference has "
                "it: the variant did not take place")
        controls[mode] = {"max_logit_gap": c_max,
                          "mean_logit_gap": c_sum / max(positions, 1),
                          "logits_moved_max": moved,
                          "tokens_changed": changed}
    return {"cases": cases, "max_logit_gap": worst, "positions": positions,
            "mean_logit_gap": total / max(positions, 1),
            "controls": controls}
