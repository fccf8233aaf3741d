"""The plain reference for the A.X-K1 configuration (DeepSeek-V3's
keys: ``model_type`` ``axk1``).

The decoder as the papers publish it (MLA: DeepSeek-V2,
arXiv:2405.04434, section 2.1; routing: DeepSeek-V3, arXiv:2412.19437,
eq. 12-15; yarn: arXiv:2309.00071 as HF ``modeling_deepseek.py``
computes it). With ``h = RMSNorm(x)``:

* attention: ``c_q = RMSNorm(h W_dq)``, ``q = c_q W_uq`` split per
  head into ``[q_n | q_r]``; ``[c_kv | k_r] = h W_dkv``, ``c_kv <-
  RMSNorm(c_kv)``, ``k_r`` ONE vector for all heads; RoPE at yarn
  frequencies on ``q_r`` and ``k_r`` only; ``[k_n | v] = c_kv W_ukv``
  per head; ``score = (q_n . k_n + q_r . k_r) * s``, ``s = (dn + dr) **
  -0.5 * (0.1 mscale_all_dim ln(factor) + 1) ** 2``; causal softmax;
  ``x <- x + concat_heads(sum w v) W_o``. EXPANDED form only: no cache,
  nothing absorbed.
* layer < ``first_k_dense_replace``: ``x <- x + W_down(silu(W_gate h)
  * W_up h)``.
* later layers: ``s_e = sigmoid(h W_r)`` over ALL published experts,
  ``T`` the ``num_experts_per_tok`` largest, ``g_e = scale * s_e /
  (sum_{j in T} s_j + 1e-20)``, ``y = sum_{e in T, e held} g_e E_e(h) +
  E_shared(h)``: every held expert is applied to EVERY position and
  masked; no sort, no dispatch. What the experts held on other chips
  would add is left out, as in the program: that partial result is what
  goes on to the next layer.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; one sequence at a time;
no kernel. It imports nothing of the program. Weights are data made
HERE by the recipe the configuration file states under ``assumed``:
every leaf from ``fold_in(fold_in(PRNGKey(0), layer), leaf number)``
(an expert's folded once more with its GLOBAL index, a block of 128
vocabulary rows with its block index), normal times ``fan_in ** -0.5``
(embedding 0.02) in float32, rounded once to bfloat16: those rounded
values ARE the model, widened here to float32.

So that the chip holds it: ONE layer's float32 weights at a time (2.7
GB), every case through that layer before the next is made.
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

Q_BLOCK = 512     # query rows of a sequence worked on at once
PAD_TO = 512      # sequences are padded at the END to a multiple
VOCAB_BLOCK = 128
LEAF = {name: i for i, name in enumerate((
    "w_dq", "w_uq", "w_dkv", "w_ukv", "w_o", "w_gate", "w_up", "w_down",
    "router", "s_gate", "s_up", "s_down", "e_gate", "e_up", "e_down",
    "embed", "unembed",
))}
TOP = 1_000_000
NEAR_TIE = 1e-3
#: ``clear``: the gaps over positions whose choice of experts was not
#: in doubt by this much (see check_served)
CLEAR_MARGINS = (1e-3, 3e-3, 1e-2, 3e-2)


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    share = config.get("share", {})
    held = share.get("held_experts", [0, config["n_routed_experts"]])
    return {
        "d": config["hidden_size"], "h": config["num_attention_heads"],
        "dn": config["qk_nope_head_dim"], "dr": config["qk_rope_head_dim"],
        "dv": config["v_head_dim"], "rq": config["q_lora_rank"],
        "rkv": config["kv_lora_rank"], "f": config["intermediate_size"],
        "fe": config["moe_intermediate_size"],
        "shared": config.get("n_shared_experts", 0),
        "experts": share.get("router_experts", config["n_routed_experts"]),
        "held": (int(held[0]), int(held[1])),
        "k": config["num_experts_per_tok"],
        "scale": config.get("routed_scaling_factor", 1.0),
        "norm": config.get("norm_topk_prob", True),
        "layers": config["num_hidden_layers"],
        "dense": config["first_k_dense_replace"],
        "vocab": config["vocab_size"], "eps": config["rms_norm_eps"],
    }


# -- weights ------------------------------------------------------------


def _key(layer: int, name: str):
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(0), layer), LEAF[name])


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw(key, shape, scale):
    """float32 draw, rounded once to bfloat16, widened again."""
    drawn = jax.random.normal(key, shape, jnp.float32) * scale
    return drawn.astype(jnp.bfloat16).astype(jnp.float32)


def layer_weights(config: Dict[str, Any], layer: int) -> Dict[str, Any]:
    z = sizes(config)
    d, h = z["d"], z["h"]
    shapes = {
        "w_dq": ((d, z["rq"]), d),
        "w_uq": ((z["rq"], h, z["dn"] + z["dr"]), z["rq"]),
        "w_dkv": ((d, z["rkv"] + z["dr"]), d),
        "w_ukv": ((z["rkv"], h, z["dn"] + z["dv"]), z["rkv"]),
        "w_o": ((h, z["dv"], d), h * z["dv"]),
    }
    sparse = layer >= z["dense"]
    if sparse:
        fs = z["fe"] * max(z["shared"], 1)
        shapes.update({"router": ((d, z["experts"]), d),
                       "s_gate": ((d, fs), d), "s_up": ((d, fs), d),
                       "s_down": ((fs, d), fs)})
    else:
        shapes.update({"w_gate": ((d, z["f"]), d), "w_up": ((d, z["f"]), d),
                       "w_down": ((z["f"], d), z["f"])})
    w = {name: _draw(_key(layer, name), shape, fan_in ** -0.5)
         for name, (shape, fan_in) in shapes.items()}
    if sparse:
        lo, hi = z["held"]
        for name, shape, fan_in in (("e_gate", (d, z["fe"]), d),
                                    ("e_up", (d, z["fe"]), d),
                                    ("e_down", (z["fe"], d), z["fe"])):
            w[name] = jnp.stack([
                _draw(jax.random.fold_in(_key(layer, name), e), shape,
                      fan_in ** -0.5) for e in range(lo, hi)])
    return w


def vocab_weights(config: Dict[str, Any], name: str, scale: float):
    z = sizes(config)
    key = _key(TOP, name)
    return jnp.concatenate([
        _draw(jax.random.fold_in(key, b), (VOCAB_BLOCK, z["d"]), scale)
        for b in range(z["vocab"] // VOCAB_BLOCK)])


MATMUL_AXES = {  # name -> the axes a token's activations contract over
    "w_dq": (0,), "w_uq": (0,), "w_dkv": (0,), "w_ukv": (0,), "w_o": (0, 1),
    "w_gate": (0,), "w_up": (0,), "w_down": (0,), "s_gate": (0,),
    "s_up": (0,), "s_down": (0,), "e_gate": (1,), "e_up": (1,),
    "e_down": (1,), "unembed": (1,),
}


@functools.partial(jax.jit, static_argnums=(1,))
def _int8(w, axes):
    """8 bits a weight, one float scale per output channel (symmetric,
    absmax / 127): the nearest precision below the stated bfloat16."""
    scale = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 127.0
    q = jnp.clip(jnp.round(w / scale), -127, 127) * scale
    return q.astype(jnp.bfloat16).astype(jnp.float32)


def lower_precision(weights: Dict[str, Any], mode: str) -> Dict[str, Any]:
    """``bf16``: the weights as they are (they are bfloat16 values),
    read by single-pass bf16 products. ``int8``: every matmul weight
    but the router on an int8 grid, same products."""
    if mode != "int8":
        return weights
    return {name: _int8(w, MATMUL_AXES[name]) if name in MATMUL_AXES else w
            for name, w in weights.items()}


# -- the layers -----------------------------------------------------------


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def inv_freq(config: Dict[str, Any]):
    dim = config["qk_rope_head_dim"]
    i = np.arange(dim // 2, dtype=np.float64)
    extra = float(config["rope_theta"]) ** (-2.0 * i / dim)
    rope = config.get("rope_scaling")
    if not rope:
        return jnp.asarray(extra, jnp.float32)

    def correction(beta):
        return dim * math.log(rope["original_max_position_embeddings"]
                              / (beta * 2 * math.pi)) / (
            2 * math.log(config["rope_theta"]))

    low = max(math.floor(correction(rope["beta_fast"])), 0)
    high = min(math.ceil(correction(rope["beta_slow"])), dim - 1)
    mask = 1.0 - np.clip((i - low) / ((high - low) or 0.001), 0.0, 1.0)
    inter = extra / rope["factor"]
    return jnp.asarray(inter * (1 - mask) + extra * mask, jnp.float32)


def softmax_scale(config: Dict[str, Any]) -> float:
    scale = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]) ** -0.5
    rope = config.get("rope_scaling")
    if rope and rope.get("mscale_all_dim"):
        m = 0.1 * rope["mscale_all_dim"] * math.log(rope["factor"]) + 1.0
        scale *= m * m
    return scale


def _rope(x, freqs):
    """x [seq, ..., dr] at positions 0..seq-1; pairs (i, i + dr/2)."""
    half = x.shape[-1] // 2
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    while angles.ndim < x.ndim:
        angles = angles[:, None]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(x, w, config):
    z = sizes(config)
    dn, rkv, eps = z["dn"], z["rkv"], z["eps"]
    seq = x.shape[0]
    freqs = inv_freq(config)
    h = _rms(x, eps)
    q = jnp.einsum("sr,rhk->shk", _rms(h @ w["w_dq"], eps), w["w_uq"])
    ckr = h @ w["w_dkv"]
    c_kv = _rms(ckr[:, :rkv], eps)
    k_r = _rope(ckr[:, rkv:], freqs)
    q_n, q_r = q[..., :dn], _rope(q[..., dn:], freqs)
    kv = jnp.einsum("sr,rhk->shk", c_kv, w["w_ukv"])
    k_n, v = kv[..., :dn], kv[..., dn:]
    block = math.gcd(seq, Q_BLOCK)
    cols = jnp.arange(seq)

    def rows(start):
        qn = jax.lax.dynamic_slice_in_dim(q_n, start, block, axis=0)
        qr = jax.lax.dynamic_slice_in_dim(q_r, start, block, axis=0)
        scores = (jnp.einsum("qhd,khd->hqk", qn, k_n)
                  + jnp.einsum("qhd,kd->hqk", qr, k_r)) * softmax_scale(config)
        mask = cols[None, :] <= (start + jnp.arange(block))[:, None]
        weights = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", weights, v)

    o = jax.lax.map(rows, jnp.arange(0, seq, block)).reshape(
        seq, z["h"], z["dv"])
    return x + jnp.einsum("shv,hvd->sd", o, w["w_o"])


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def route(h, router, config):
    """(expert ids [seq, k], gates [seq, k], edge [seq]: how far the
    last chosen score lies above the first one left out, at_held [seq]
    bool: one of those two experts is held here, so that swapping them
    changes what this share computes)."""
    z = sizes(config)
    scores = jax.nn.sigmoid(h @ router)
    top, idx = jax.lax.top_k(scores, z["k"] + 1)
    edge = top[:, z["k"] - 1] - top[:, z["k"]]
    lo, hi = z["held"]
    pair = idx[:, z["k"] - 1:]
    at_held = jnp.any((pair >= lo) & (pair < hi), axis=-1)
    top, idx = top[:, :z["k"]], idx[:, :z["k"]]
    if z["norm"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return idx, top * z["scale"], edge, at_held


def experts_part(h, idx, gates, w, lo, hi):
    """What the experts ``lo..hi-1`` (whose weights ``w`` holds, in
    that order) add: each applied to every position, then masked."""
    def one(total, inputs):
        e, gate, up, down = inputs
        weight = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)
        return total + _swiglu(h, gate, up, down) * weight[:, None], None

    total, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (jnp.arange(lo, hi), w["e_gate"], w["e_up"], w["e_down"]))
    return total


def dense_layer(x, w, config):
    x = attention(x, w, config)
    h = _rms(x, sizes(config)["eps"])
    return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"])


def sparse_layer(x, w, config):
    z = sizes(config)
    x = attention(x, w, config)
    h = _rms(x, z["eps"])
    idx, gates, edge, at_held = route(h, w["router"], config)
    y = experts_part(h, idx, gates, w, *z["held"])
    if z["shared"]:
        y = y + _swiglu(h, w["s_gate"], w["s_up"], w["s_down"])
    return x + y, edge, at_held


def all_logits(config: Dict[str, Any], tokens, precision: str = "highest",
               mode: str = ""):
    """Logits [seq, vocab] of ONE sequence from position 0, every
    layer's weights made in turn (tests; the chip path is
    ``run_pass``)."""
    z = sizes(config)
    with jax.default_matmul_precision(precision):
        x = vocab_weights(config, "embed", 0.02)[jnp.asarray(tokens)]
        for layer in range(z["layers"]):
            w = lower_precision(layer_weights(config, layer), mode)
            if layer < z["dense"]:
                x = dense_layer(x, w, config)
            else:
                x, _edge, _at_held = sparse_layer(x, w, config)
        head = vocab_weights(config, "unembed", z["d"] ** -0.5)
        if mode == "int8":
            head = _int8(head, MATMUL_AXES["unembed"])
        return _rms(x, z["eps"]) @ head.T


# -- what the harness's child calls -------------------------------------


def _padded(n: int, cap: int, quantum: int = PAD_TO) -> int:
    return min(-(-n // quantum) * quantum, max(cap, n))


def _say(*words: Any) -> None:
    """Progress, to the child's log (``reference.log``)."""
    print("axk1_reference:", *words, file=sys.stderr, flush=True)


def run_pass(config: Dict[str, Any], rows: List[List[int]],
             keep: List[np.ndarray], cap: int, mode: str = ""):
    """Every row through the model layer by layer: one layer's weights
    at a time, all rows through it, then the next. ``mode`` "" is the
    reference (float32 products, ``highest``); "bf16" and "int8" are
    the lower-precision readings (single-pass bf16 products). Returns
    (per row the logits at its ``keep`` positions as numpy, per row
    each position's least edge over the layers in which a held expert
    sat at it, near-tie count, routed (position, layer) pairs)."""
    z = sizes(config)
    precision = "highest" if not mode else "default"

    @jax.jit
    def dense(x, w):
        with jax.default_matmul_precision(precision):
            return dense_layer(x, w, config)

    @jax.jit
    def sparse(x, w, margin):
        with jax.default_matmul_precision(precision):
            x, edge, at_held = sparse_layer(x, w, config)
            margin = jnp.minimum(margin, jnp.where(at_held, edge, jnp.inf))
            return x, margin, edge < NEAR_TIE

    @jax.jit
    def head_at(x, head, at):
        with jax.default_matmul_precision(precision):
            return _rms(x[at], z["eps"]) @ head.T

    embed = vocab_weights(config, "embed", 0.02)
    hidden = []
    for row in rows:
        ids = np.zeros((_padded(len(row), cap),), np.int32)
        ids[: len(row)] = row
        hidden.append(embed[jnp.asarray(ids)])
    del embed
    margin = [jnp.full((len(x),), jnp.inf) for x in hidden]
    near_ties = []
    for layer in range(z["layers"]):
        t0 = time.monotonic()
        w = lower_precision(layer_weights(config, layer), mode)
        jax.block_until_ready(w)
        t1 = time.monotonic()
        for i, row in enumerate(rows):
            if layer < z["dense"]:
                hidden[i] = dense(hidden[i], w)
            else:
                hidden[i], margin[i], near = sparse(hidden[i], w, margin[i])
                near_ties.append(jnp.sum(near[: len(row)]))
        del w
        jax.block_until_ready(hidden)
        _say(f"mode {mode or 'highest'!r} layer {layer}: weights "
             f"{t1 - t0:.1f} s, {len(rows)} rows {time.monotonic() - t1:.1f} s")
    near_ties = int(sum(int(n) for n in near_ties))
    head = vocab_weights(config, "unembed", z["d"] ** -0.5)
    if mode == "int8":
        head = _int8(head, MATMUL_AXES["unembed"])
    out = []
    for i, at in enumerate(keep):
        # the positions kept are padded to a multiple too: few shapes
        padded = np.zeros((_padded(len(at), len(hidden[i]), 128),), np.int32)
        padded[: len(at)] = at
        out.append(np.asarray(
            head_at(hidden[i], head, jnp.asarray(padded)))[: len(at)])
        hidden[i] = None
    pairs = sum(len(r) for r in rows) * (z["layers"] - z["dense"])
    return out, [np.asarray(m) for m in margin], near_ties, pairs


def check_served(config: Dict[str, Any], spec: Dict[str, Any]) -> Dict[str, Any]:
    """For each case (a prompt and the greedy tokens the server
    streamed for it): the reference over prompt + tokens, and at every
    generated position how far the served token's logit lies below the
    reference's best. ``near_tie_share`` is a diagnosis, no limit: the
    share of (position, sparse layer) pairs whose 8th and 9th largest
    router scores lie within 1e-3, where a bf16 program and this
    reference may choose different experts. ``clear`` is the same two
    gaps over the positions where, in every layer, no HELD expert sat
    at the edge of the choice by less than each of ``CLEAR_MARGINS``:
    what is left of the gaps where this share's part of the result is
    not in doubt (earlier positions' choices still reach such a
    position through attention)."""
    cap = int(spec["max_len"])
    rows, keep = [], []
    for case in spec["cases"]:
        prompt, served = case["prompt"], case["tokens"]
        row = (prompt + served)[:-1]
        rows.append(row)
        keep.append(np.arange(len(prompt) - 1, len(row)))
    logits, margins, near_ties, pairs = run_pass(config, rows, keep, cap)
    cases = []
    worst = total = 0.0
    positions = 0
    best_of = []
    clear = [{"margin_over": t, "positions": 0, "max_logit_gap": 0.0,
              "mean_logit_gap": 0.0} for t in CLEAR_MARGINS]
    for case, got, at, margin in zip(spec["cases"], logits, keep, margins):
        served = np.asarray(case["tokens"])
        best = got.max(axis=-1)
        gaps = best - got[np.arange(len(served)), served]
        best_of.append(best)
        for part in clear:
            sure = gaps[margin[at] > part["margin_over"]]
            if len(sure):
                part["positions"] += len(sure)
                part["mean_logit_gap"] += float(sure.sum())
                part["max_logit_gap"] = max(part["max_logit_gap"], float(sure.max()))
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
    # float32 reading, of the token that variant puts first
    controls = {}
    for mode in spec.get("controls", ()):
        lower, _margins, _ties, _pairs = run_pass(config, rows, keep, cap, mode)
        c_sum = c_max = 0.0
        for got, theirs, best in zip(logits, lower, best_of):
            picked = theirs.argmax(axis=-1)
            gaps = best - got[np.arange(len(picked)), picked]
            c_sum += float(gaps.sum())
            c_max = max(c_max, float(gaps.max()))
        controls[mode] = {"max_logit_gap": c_max,
                          "mean_logit_gap": c_sum / max(positions, 1)}
    for part in clear:
        part["mean_logit_gap"] /= max(part["positions"], 1)
    return {"cases": cases, "max_logit_gap": worst, "positions": positions,
            "mean_logit_gap": total / max(positions, 1),
            "near_tie_share": near_ties / max(pairs, 1), "clear": clear,
            "controls": controls}
