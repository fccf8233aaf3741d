"""The plain reference for the Ouro-2.6B configuration (``model_type``
``ouro``: a looped language model, a stack of layers run several times
a token).

Tokens ``x -> h = E[x]``. For pass ``t = 0 .. total_ut_steps - 1``, for
layer ``l = 0 .. num_hidden_layers - 1``, with the SAME weights in
every pass::

    q, k, v = N1_l(h) Wq, N1_l(h) Wk, N1_l(h) Wv   rotate-half RoPE on q, k
    a = softmax(q k^T / sqrt(head_dim), causal) v Wo
    h = h + N2_l(a)
    m = (silu(N3_l(h) W_gate) * (N3_l(h) W_up)) W_down
    h = h + N4_l(m)

after the last layer of each pass ``h = N_out(h)``, which is the next
pass's input; ``logits = h W_head`` after the last pass. Every ``N`` is
an RMSNorm (eps ``rms_norm_eps``) whose learned scale is 1 in a seeded
model, so no scale is held here; no biases. Computed over a WHOLE
sequence from position 0 in every pass, so pass ``t`` of layer ``l``
attends to exactly what pass ``t`` of layer ``l`` made of the earlier
positions: there is no cache here and nothing to share between passes.
``early_exit_threshold`` 1: every position takes every pass; the exit
gate is not computed.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no kernel, no cache, no
batching of requests beyond rows of one padded length. It imports
nothing of the program. Weights are data made HERE by the recipe the
configuration file states under ``assumed`` (every leaf from
``fold_in(fold_in(PRNGKey(0), layer), leaf number)``, a block of 128
vocabulary rows folded once more with its block index; normal times
``fan_in ** -0.5``, embedding 0.02, drawn in float32 and rounded ONCE to
bfloat16's values with ``jax.lax.reduce_precision``: a cast to bfloat16
and back inside one jitted computation is not a rounding on the chip).

So that the chip holds it: the rounded values are KEPT in bfloat16 (5.3
GB, exactly the values) and ONE layer is widened to float32 at a time,
inside the layer's own computation; no float32 copy of the model is
ever resident.
"""
from __future__ import annotations

import functools
import sys
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

GROUP = 16        # sequences worked on at once, all padded to one length
VOCAB_BLOCK = 128
LEAF = {name: i for i, name in enumerate((
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
    "embed", "unembed", "exit_gate",
))}
TOP = 1_000_000
EMBED_SCALE = 0.02
#: the lower-precision (or shorter) readings ``run_pass`` can make of
#: itself: int8 weights, single-pass bf16 products, one pass fewer
MODES = ("int8-weights", "bf16-products", "three-passes")


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    heads = config["num_attention_heads"]
    return {
        "d": config["hidden_size"], "h": heads,
        "kv": config.get("num_key_value_heads", heads),
        "hd": config.get("head_dim", config["hidden_size"] // heads),
        "f": config["intermediate_size"],
        "layers": config["num_hidden_layers"],
        "passes": config["total_ut_steps"],
        "vocab": config["vocab_size"], "eps": config["rms_norm_eps"],
        "theta": config["rope_theta"],
    }


# -- weights ------------------------------------------------------------


def _key(layer: int, name: str):
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(0), layer), LEAF[name])


def _bf16_values(x):
    """float32 ``x`` rounded (to nearest, ties to even) to the values
    bfloat16 holds, still float32: an operation the compiler keeps."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw(key, shape, scale):
    """float32 draw, rounded once to bfloat16's values and KEPT in
    bfloat16 (which holds exactly those values)."""
    return _bf16_values(
        jax.random.normal(key, shape, jnp.float32) * scale
    ).astype(jnp.bfloat16)


def layer_weights(config: Dict[str, Any], layer: int) -> Dict[str, Any]:
    """One layer's seven matrices, the rounded values in bfloat16."""
    z = sizes(config)
    d, h, kv, hd, f = z["d"], z["h"], z["kv"], z["hd"], z["f"]
    shapes = {
        "wq": ((d, h, hd), d), "wk": ((d, kv, hd), d),
        "wv": ((d, kv, hd), d), "wo": ((h, hd, d), h * hd),
        "w_gate": ((d, f), d), "w_up": ((d, f), d), "w_down": ((f, d), f),
    }
    return {name: _draw(_key(layer, name), shape, fan_in ** -0.5)
            for name, (shape, fan_in) in shapes.items()}


def vocab_leaf(config: Dict[str, Any], name: str, scale: float):
    """[vocab, d] block by block of 128 rows: ``embed`` or ``unembed``."""
    z = sizes(config)
    key = _key(TOP, name)
    return jnp.concatenate([
        _draw(jax.random.fold_in(key, b), (VOCAB_BLOCK, z["d"]), scale)
        for b in range(z["vocab"] // VOCAB_BLOCK)])


MATMUL_AXES = {  # name -> the axes a token's activations contract over
    "wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
    "w_gate": (0,), "w_up": (0,), "w_down": (0,), "unembed": (1,),
}


@functools.partial(jax.jit, static_argnums=(1,))
def _int8(w, axes):
    """8 bits a weight, one float scale per output channel (symmetric,
    absmax / 127): the nearest precision below the stated bfloat16.
    The grid's values, rounded to bfloat16's, in bfloat16."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 127.0
    return _bf16_values(
        jnp.clip(jnp.round(w / scale), -127, 127) * scale
    ).astype(jnp.bfloat16)


def lower_precision(weights: Dict[str, Any], mode: str) -> Dict[str, Any]:
    """``int8-weights``: every matmul weight on an int8 grid; any other
    mode leaves the weights as they are."""
    if mode != "int8-weights":
        return weights
    return {name: _int8(w, MATMUL_AXES[name]) if name in MATMUL_AXES else w
            for name, w in weights.items()}


# -- the layers -----------------------------------------------------------


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """Rotate-half rotary embedding of x [rows, seq, heads, head_dim]
    at positions 0 .. seq - 1."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(x, w, config):
    """One layer of one pass over x [rows, seq, d] float32; ``w`` is the
    layer's matrices in bfloat16, widened here."""
    z = sizes(config)
    w = {name: leaf.astype(jnp.float32) for name, leaf in w.items()}
    seq, group = x.shape[1], z["h"] // z["kv"]
    h = _rms(x, z["eps"])
    q = _rope(jnp.einsum("rsd,dhk->rshk", h, w["wq"]), z["theta"])
    k = _rope(jnp.einsum("rsd,dhk->rshk", h, w["wk"]), z["theta"])
    v = jnp.einsum("rsd,dhk->rshk", h, w["wv"])
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("rqhd,rkhd->rhqk", q, k) * z["hd"] ** -0.5
    causal = jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None]
    weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("rhqk,rkhd->rqhd", weights, v)
    x = x + _rms(jnp.einsum("rshk,hkd->rsd", o, w["wo"]), z["eps"])
    h = _rms(x, z["eps"])
    m = (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
    return x + _rms(m, z["eps"])


def passes_of(config: Dict[str, Any], mode: str) -> int:
    """How often the layers run: the published count, or one fewer in
    the ``three-passes`` control (which shows that the check sees the
    loop)."""
    return sizes(config)["passes"] - (1 if mode == "three-passes" else 0)


def all_logits(config: Dict[str, Any], tokens, precision: str = "highest",
               mode: str = ""):
    """Logits [seq, vocab] of ONE sequence from position 0 (tests; the
    chip path is ``run_pass``)."""
    z = sizes(config)
    with jax.default_matmul_precision(precision):
        embed = vocab_leaf(config, "embed", EMBED_SCALE)
        x = embed[jnp.asarray(tokens)].astype(jnp.float32)[None]
        weights = [lower_precision(layer_weights(config, i), mode)
                   for i in range(z["layers"])]
        for _t in range(passes_of(config, mode)):
            for w in weights:
                x = layer(x, w, config)
            x = _rms(x, z["eps"])
        head = lower_precision(
            {"unembed": vocab_leaf(config, "unembed", z["d"] ** -0.5)}, mode)
        return x[0] @ head["unembed"].astype(jnp.float32).T


# -- what the harness's child calls -------------------------------------


def _say(*words: Any) -> None:
    """Progress, to the child's log (``reference.log``)."""
    print("ouro_reference:", *words, file=sys.stderr, flush=True)


def _batches(rows: List[List[int]], cap: int):
    """The rows ``GROUP`` at a time, every one padded at the END to one
    length (the longest row's, at least ``cap``: one compiled shape):
    [(indices, ids [GROUP, length])], short groups filled with rows of
    zeros. The model is causal: what follows a position does not reach
    it."""
    length = max(cap, max(len(row) for row in rows))
    out = []
    for start in range(0, len(rows), GROUP):
        indices = list(range(start, min(start + GROUP, len(rows))))
        ids = np.zeros((GROUP, length), np.int32)
        for slot, i in enumerate(indices):
            ids[slot, : len(rows[i])] = rows[i]
        out.append((indices, ids))
    return out


def run_pass(config: Dict[str, Any], rows: List[List[int]],
             keep: List[np.ndarray], cap: int, mode: str = ""):
    """Every row through the model: the weights made once (bfloat16,
    the rounded values), then pass by pass, layer by layer, all rows
    through a layer before the next. ``mode`` "" is the reference
    (float32 products, ``highest``); ``bf16-products`` and
    ``int8-weights`` read with single-pass bf16 products (and int8
    weights), ``three-passes`` with the reference's products and one
    pass fewer. Returns per row the logits at its ``keep`` positions as
    numpy."""
    z = sizes(config)
    single = mode in ("bf16-products", "int8-weights")
    precision = "default" if single else "highest"

    @jax.jit
    def through(x, w):
        with jax.default_matmul_precision(precision):
            return layer(x, w, config)

    @jax.jit
    def norm_out(x):
        return _rms(x, z["eps"])

    @jax.jit
    def head_at(x, unembed, at):
        with jax.default_matmul_precision(precision):
            return x[at] @ unembed.astype(jnp.float32).T

    t0 = time.monotonic()
    weights = [lower_precision(layer_weights(config, i), mode)
               for i in range(z["layers"])]
    embed = vocab_leaf(config, "embed", EMBED_SCALE)
    unembed = lower_precision(
        {"unembed": vocab_leaf(config, "unembed", z["d"] ** -0.5)},
        mode)["unembed"]
    jax.block_until_ready((weights, embed, unembed))
    _say(f"mode {mode or 'highest'!r}: weights {time.monotonic() - t0:.1f} s")
    batches = _batches(rows, cap)
    hidden = [embed[jnp.asarray(ids)].astype(jnp.float32)
              for _indices, ids in batches]
    for t in range(passes_of(config, mode)):
        t1 = time.monotonic()
        for w in weights:
            hidden = [through(x, w) for x in hidden]
        hidden = [norm_out(x) for x in hidden]
        jax.block_until_ready(hidden)
        _say(f"mode {mode or 'highest'!r} pass {t}: {len(batches)} batches "
             f"{time.monotonic() - t1:.1f} s")
    out: List[Any] = [None] * len(rows)
    for b, (indices, ids) in enumerate(batches):
        for slot, r in enumerate(indices):
            # the positions kept are padded to a multiple too: few shapes
            at = keep[r]
            padded = np.zeros(
                (min(-(-len(at) // 64) * 64, ids.shape[1]),), np.int32)
            padded[: len(at)] = at
            out[r] = np.asarray(head_at(
                hidden[b][slot], unembed, jnp.asarray(padded)))[: len(at)]
        hidden[b] = None
    return out


def check_served(config: Dict[str, Any], spec: Dict[str, Any]) -> Dict[str, Any]:
    """For each case (a prompt and the greedy tokens the server
    streamed for it): the reference over prompt + tokens, and at every
    generated position how far the served token's logit lies below the
    reference's best. One full forward of every pass, so it is also the
    statement that a prefill followed by one-token steps through the
    pool's planes agrees with the plain loop."""
    cap = int(spec["max_len"])
    rows, keep = [], []
    for case in spec["cases"]:
        prompt, served = case["prompt"], case["tokens"]
        row = (prompt + served)[:-1]
        rows.append(row)
        keep.append(np.arange(len(prompt) - 1, len(row)))
    logits = run_pass(config, rows, keep, cap)
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
    # the controls: the reference itself in a lower precision (or with
    # a pass fewer) on the SAME prompts and tokens; at each position the
    # gap, under the float32 reading, of the token that variant puts
    # first, and, as the proof that the variant took place, how far it
    # moved any logit and how many positions it gives another token
    controls = {}
    for mode in spec.get("controls", ()):
        if mode not in MODES:
            raise ValueError(f"control {mode!r}: one of {', '.join(MODES)}")
        lower = run_pass(config, rows, keep, cap, mode)
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
                "it: the variant did not take place")
        controls[mode] = {"max_logit_gap": c_max,
                          "mean_logit_gap": c_sum / max(positions, 1),
                          "logits_moved_max": moved,
                          "tokens_changed": changed}
    return {"cases": cases, "max_logit_gap": worst, "positions": positions,
            "mean_logit_gap": total / max(positions, 1),
            "controls": controls}
