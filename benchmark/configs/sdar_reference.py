"""The plain reference for the SDAR configuration (``model_type``
``sdar_moe``: Qwen3-MoE's keys, generation by diffusion over blocks).

The decoder as published. With ``h = RMSNorm(x)``:

* attention: ``q = RoPE(RMSNorm_hd(h W_q))``, ``k = RoPE(RMSNorm_hd(h
  W_k))`` (a learned RMS norm over each head's ``head_dim`` dimensions,
  BEFORE the rotation; pairs ``(i, i + head_dim / 2)``), ``v = h W_v``,
  scores ``q . k / sqrt(head_dim)``, 8 query heads to a key-value head,
  ``x <- x + concat_heads(sum w v) W_o``. The mask is by blocks of ``B``
  positions: ``i`` sees ``j`` iff ``j // B <= i // B``.
* experts: ``u = RMSNorm(x)``, ``p = softmax(u W_r)`` over ALL experts,
  ``T`` the ``num_experts_per_tok`` largest, ``g_e = p_e / sum_T p``,
  ``x <- x + sum_{e in T} g_e E_e(u)``: every expert applied to EVERY
  position and masked; no sort, no dispatch.
* generation (``generate``): the next block starts as ``B`` mask
  tokens (a prompt that ends inside a block shows its part of it); a
  denoising step is one forward of everything so far; a hidden
  position's own logits (the mask token's excluded) give its token and
  its confidence, the softmax probability of that token;
  ``low_confidence_static`` reveals the ``B / steps`` hidden positions
  of highest confidence (ties to the lower position, never more than
  are hidden), ``low_confidence_dynamic`` every hidden position above
  the threshold and never fewer than that.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no kernel, no cache. It
imports nothing of the program. Weights are data made HERE by the
recipe the configuration file states under ``assumed``; the rounded
bfloat16 values ARE the model, widened here to float32, ONE layer at a
time on the chip (2.5 GB), every case through that layer before the
next is made.

**What ``check_served`` judges, and why without the order.** The
harness hands over a prompt and the final tokens, nothing about which
positions were revealed first. So one forward runs over [the finished
sequence ; noised copies of the judged blocks]: a copy stands at its
block's positions, sees the clean blocks before it and itself (the
block-diffusion training mask), and shows the served tokens at some
positions and the mask token at the rest. One copy per state a block
can have passed through gives the reference's logits in every such
state: at 2 reveals of 4, the all-mask state and the six states with
two served tokens shown. A CANDIDATE is an order of reveals the
schedule allows (a pair, there). Its gaps: at each position it reveals
in a step, the served token's logit below the reference's best in the
state before that step; and, where the step had a choice, how far the
lowest log-confidence of what it revealed lies below the reference's
own ``count``-th highest among the hidden positions of that state. The
block is judged by the candidate whose largest gap is smallest, and
all of that candidate's gaps enter ``max_logit_gap`` and
``mean_logit_gap``.

This is as tight as teacher forcing: every token is still compared,
in logit units, with the reference's best in a state built from the
SERVED tokens alone, and the true order is one of the candidates, so
a sound program's reading can only be equal or lower than under its
true order, while a program that computes in int8 or drops an expert
moves the logits of EVERY state, so no candidate escapes (the
``controls`` show it: the reference itself in a lower precision,
judged as if it had served what it puts first). The clean part of the
same forward is the statement that prefill, then decoding through the
cache, agree with a full forward: the copies read the clean blocks'
keys as the full forward makes them.
"""
from __future__ import annotations

import functools
import itertools
import random
import sys
import time
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512     # query rows worked on at once
PAD_TO = 512      # a case's positions are padded at the END to a multiple
KEEP_PAD = 128    # ... and its judged positions to a multiple of this
VOCAB_BLOCK = 128
LEAF = {name: i for i, name in enumerate((
    "wq", "wk", "wv", "wo", "router", "e_gate", "e_up", "e_down",
    "embed", "unembed",
))}
TOP = 1_000_000
#: blocks judged per case: the first, the last, and a seeded draw
JUDGED_BLOCKS = 32


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    diffusion = config["diffusion"]
    return {
        "d": config["hidden_size"], "h": config["num_attention_heads"],
        "kv": config["num_key_value_heads"], "hd": config["head_dim"],
        "fe": config["moe_intermediate_size"],
        "experts": config["num_experts"], "k": config["num_experts_per_tok"],
        "norm": config.get("norm_topk_prob", True),
        "layers": config["num_hidden_layers"], "vocab": config["vocab_size"],
        "eps": config["rms_norm_eps"], "theta": float(config["rope_theta"]),
        "block": int(diffusion["block_length"]),
        "steps": int(diffusion["denoising_steps"]),
        "rule": diffusion["remasking"],
        "threshold": float(diffusion["confidence_threshold"]),
        "mask_id": int(diffusion["mask_token_id"]),
    }


def schedule(block: int, steps: int) -> List[int]:
    """Positions revealed by step: the published
    ``get_num_transfer_tokens``."""
    base, extra = divmod(block, steps)
    return [base + (i < extra) for i in range(steps)]


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
    d, h, kv, hd, f = z["d"], z["h"], z["kv"], z["hd"], z["fe"]
    shapes = {
        "wq": ((d, h, hd), d), "wk": ((d, kv, hd), d), "wv": ((d, kv, hd), d),
        "wo": ((h, hd, d), h * hd), "router": ((d, z["experts"]), d),
    }
    w = {name: _draw(_key(layer, name), shape, fan_in ** -0.5)
         for name, (shape, fan_in) in shapes.items()}
    for name, shape, fan_in in (("e_gate", (d, f), d), ("e_up", (d, f), d),
                                ("e_down", (f, d), f)):
        w[name] = jnp.stack([
            _draw(jax.random.fold_in(_key(layer, name), e), shape,
                  fan_in ** -0.5) for e in range(z["experts"])])
    return w


def vocab_weights(config: Dict[str, Any], name: str, scale: float):
    z = sizes(config)
    key = _key(TOP, name)
    return jnp.concatenate([
        _draw(jax.random.fold_in(key, b), (VOCAB_BLOCK, z["d"]), scale)
        for b in range(z["vocab"] // VOCAB_BLOCK)])


MATMUL_AXES = {  # name -> the axes a token's activations contract over
    "wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1), "e_gate": (1,),
    "e_up": (1,), "e_down": (1,), "unembed": (1,),
}


@functools.partial(jax.jit, static_argnums=(1,))
def _int8(w, axes):
    """8 bits a weight, one float scale per output channel (symmetric,
    absmax / 127): the nearest precision below the stated bfloat16."""
    scale = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 127.0
    q = jnp.clip(jnp.round(w / scale), -127, 127) * scale
    return q.astype(jnp.bfloat16).astype(jnp.float32)


def lower_precision(weights: Dict[str, Any], mode: str) -> Dict[str, Any]:
    """``bf16``: the weights as they are, read by single-pass bf16
    products. ``int8``: every matmul weight but the router on an int8
    grid, same products."""
    if mode != "int8":
        return weights
    return {name: _int8(w, MATMUL_AXES[name]) if name in MATMUL_AXES else w
            for name, w in weights.items()}


# -- the layer ------------------------------------------------------------


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, positions, theta):
    """x [seq, heads, hd] at ``positions`` [seq]; pairs (i, i + hd/2)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(x, w, config, positions, mask):
    """x [seq, d]; ``mask`` [seq, seq] says which row sees which."""
    z = sizes(config)
    seq = x.shape[0]
    h = _rms(x, z["eps"])
    q = _rope(_rms(jnp.einsum("sd,dhk->shk", h, w["wq"]), z["eps"]),
              positions, z["theta"])
    k = _rope(_rms(jnp.einsum("sd,dhk->shk", h, w["wk"]), z["eps"]),
              positions, z["theta"])
    v = jnp.einsum("sd,dhk->shk", h, w["wv"])
    group = z["h"] // z["kv"]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    step = Q_BLOCK if seq % Q_BLOCK == 0 else seq

    def rows(start):
        qs = jax.lax.dynamic_slice_in_dim(q, start, step, axis=0)
        sees = jax.lax.dynamic_slice_in_dim(mask, start, step, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qs, k) * z["hd"] ** -0.5
        weights = jax.nn.softmax(jnp.where(sees[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", weights, v)

    o = jax.lax.map(rows, jnp.arange(0, seq, step)).reshape(
        seq, z["h"], z["hd"])
    return x + jnp.einsum("shk,hkd->sd", o, w["wo"])


def route(u, router, config):
    """(expert ids [seq, k], gates [seq, k])."""
    z = sizes(config)
    top, idx = jax.lax.top_k(jax.nn.softmax(u @ router, axis=-1), z["k"])
    if z["norm"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return idx, top


def experts(u, idx, gates, w):
    """Every expert applied to every position, then masked."""
    def one(total, inputs):
        e, gate, up, down = inputs
        weight = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)
        y = (jax.nn.silu(u @ gate) * (u @ up)) @ down
        return total + y * weight[:, None], None

    total, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (jnp.arange(w["e_gate"].shape[0]), w["e_gate"], w["e_up"],
         w["e_down"]))
    return total


def layer(x, w, config, positions, mask):
    x = attention(x, w, config, positions, mask)
    u = _rms(x, sizes(config)["eps"])
    idx, gates = route(u, w["router"], config)
    return x + experts(u, idx, gates, w)


def block_mask(seq: int, block: int) -> np.ndarray:
    at = np.arange(seq) // block
    return at[None, :] <= at[:, None]


def all_weights(config: Dict[str, Any], mode: str = "") -> Dict[str, Any]:
    """The whole model (tests at toy size; the chip path makes one
    layer at a time, ``run_pass``)."""
    z = sizes(config)
    head = vocab_weights(config, "unembed", z["d"] ** -0.5)
    return {
        "embed": vocab_weights(config, "embed", 0.02),
        "layers": [lower_precision(layer_weights(config, i), mode)
                   for i in range(z["layers"])],
        "unembed": _int8(head, MATMUL_AXES["unembed"]) if mode == "int8"
        else head,
    }


def all_logits(config: Dict[str, Any], tokens, precision: str = "highest",
               mode: str = "", weights=None):
    """Logits [seq, vocab] of ONE sequence from position 0 under the
    block mask."""
    z = sizes(config)
    weights = weights or all_weights(config, mode)
    seq = len(tokens)
    positions = jnp.arange(seq)
    mask = jnp.asarray(block_mask(seq, z["block"]))
    with jax.default_matmul_precision(precision):
        x = weights["embed"][jnp.asarray(tokens)]
        for w in weights["layers"]:
            x = layer(x, w, config, positions, mask)
        return _rms(x, z["eps"]) @ weights["unembed"].T


def reveal(conf: np.ndarray, hidden: np.ndarray, count: int, rule: str,
           threshold: float) -> np.ndarray:
    """Which hidden positions a step reveals: by confidence, ties to
    the lower position; at most what is hidden."""
    order = sorted(np.nonzero(hidden)[0], key=lambda p: (-conf[p], p))
    chosen = np.zeros_like(hidden)
    chosen[order[:count]] = True
    if rule == "low_confidence_dynamic":
        high = hidden & (conf > threshold)
        if high.sum() >= min(count, hidden.sum()):
            chosen = high
    return chosen


def generate(config: Dict[str, Any], prompt: Sequence[int], max_new: int,
             weights=None, precision: str = "highest"):
    """The published routine, with no cache: every step is a full
    forward of the sequence so far with the current block at its end.
    Returns (the ``max_new`` tokens, the states: per forward the block
    index, the block as it went in and which positions were hidden)."""
    z = sizes(config)
    size, mask_id = z["block"], z["mask_id"]
    weights = weights or all_weights(config)
    counts = schedule(size, z["steps"])
    seq = list(prompt)
    start = len(seq) - len(seq) % size
    total = len(prompt) + max_new
    states = []
    while start < total:
        blk = np.full((size,), mask_id, np.int64)
        shown = seq[start:]
        blk[: len(shown)] = shown
        hidden = np.arange(size) >= len(shown)
        step = 0
        while hidden.any():
            states.append((start // size, blk.copy(), hidden.copy()))
            logits = np.array(all_logits(
                config, seq[:start] + blk.tolist(), precision,
                weights=weights)[start:], np.float64)
            logits[:, mask_id] = -np.inf
            picked = logits.argmax(axis=-1)
            top = logits.max(axis=-1)
            conf = 1.0 / np.exp(logits - top[:, None]).sum(axis=-1)
            count = counts[step] if step < len(counts) else size
            chosen = reveal(conf, hidden, count, z["rule"], z["threshold"])
            blk[chosen] = picked[chosen]
            hidden &= ~chosen
            step += 1
        seq = seq[:start] + blk.tolist()
        start += size
    return seq[len(prompt):total], states


# -- the order-free judgement ----------------------------------------------


def candidates(given: int, block: int, counts: List[int]):
    """Every order of reveals the static schedule allows for a block
    whose first ``given`` positions are shown: lists of (shown before
    the step, revealed by it) as tuples of positions."""
    def walk(shown: Tuple[int, ...], step: int):
        hidden = [p for p in range(block) if p not in shown]
        if not hidden:
            yield []
            return
        count = min(counts[step] if step < len(counts) else block,
                    len(hidden))
        for picked in itertools.combinations(hidden, count):
            after = tuple(sorted(shown + picked))
            for rest in walk(after, step + 1):
                yield [(shown, picked)] + rest

    return list(walk(tuple(range(given)), 0))


def judged_blocks(case: Dict[str, Any], block: int) -> List[int]:
    """The generated blocks of a case that were delivered whole, cut to
    ``JUDGED_BLOCKS``: first, last, and a draw seeded by the case."""
    first = len(case["prompt"]) // block
    last = (len(case["prompt"]) + len(case["tokens"])) // block  # exclusive
    blocks = list(range(first, last))
    if len(blocks) <= JUDGED_BLOCKS:
        return blocks
    inner = blocks[1:-1]
    random.Random(f"blocks:{case['index']}").shuffle(inner)
    return sorted([blocks[0], blocks[-1]] + inner[: JUDGED_BLOCKS - 2])


def lay_out(case: Dict[str, Any], config: Dict[str, Any]):
    """One case as [the finished sequence ; noised copies]: token ids,
    positions, the mask, and per copy (block, shown positions, offset
    of its first row)."""
    z = sizes(config)
    size, mask_id = z["block"], z["mask_id"]
    counts = schedule(size, z["steps"])
    clean = list(case["prompt"]) + list(case["tokens"])
    ids, positions = list(clean), list(range(len(clean)))
    copies = []
    plans = {}
    for b in judged_blocks(case, size):
        given = max(len(case["prompt"]) - b * size, 0)
        plan = candidates(given, size, counts)
        plans[b] = plan
        for shown in sorted({shown for chain in plan for shown, _ in chain}):
            copies.append((b, shown, len(ids)))
            served = clean[b * size:(b + 1) * size]
            ids += [served[p] if p in shown else mask_id for p in range(size)]
            positions += range(b * size, (b + 1) * size)
    rows = -(-len(ids) // PAD_TO) * PAD_TO
    mask = np.eye(rows, dtype=bool)  # a pad row sees itself
    n = len(clean)
    mask[:n, :n] = block_mask(n, size)
    for b, _shown, at in copies:
        mask[at:at + size, :min(b * size, n)] = True
        mask[at:at + size, at:at + size] = True
    ids += [0] * (rows - len(ids))
    positions += [0] * (rows - len(positions))
    return (np.asarray(ids, np.int32), np.asarray(positions, np.int32), mask,
            copies, plans)


def _say(*words: Any) -> None:
    """Progress, to the child's log (``reference.log``)."""
    print("sdar_reference:", *words, file=sys.stderr, flush=True)


def run_pass(config: Dict[str, Any], laid: List[Any], look_up: List[np.ndarray],
             mode: str = ""):
    """Every laid-out case through the model layer by layer: one
    layer's weights at a time, all cases through it, then the next.
    ``mode`` "" is the reference (float32 products, ``highest``);
    "bf16" and "int8" are the lower-precision readings. ``look_up[i]``
    [copies * B, n] holds token ids whose logits are wanted at each
    copy position. Per case, at every copy position: the best logit
    (the mask token's excluded), its token, the log of the softmax's
    normaliser, and the logits looked up."""
    z = sizes(config)
    precision = "highest" if not mode else "default"

    @jax.jit
    def through(x, w, positions, mask):
        with jax.default_matmul_precision(precision):
            return layer(x, w, config, positions, mask)

    @jax.jit
    def head_at(x, head, at, wanted):
        with jax.default_matmul_precision(precision):
            logits = _rms(x[at], z["eps"]) @ head.T
        logits = jnp.where(jnp.arange(z["vocab"]) == z["mask_id"], -jnp.inf,
                           logits)
        return (jnp.max(logits, axis=-1), jnp.argmax(logits, axis=-1),
                jax.nn.logsumexp(logits, axis=-1),
                jnp.take_along_axis(logits, wanted, axis=-1))

    embed = vocab_weights(config, "embed", 0.02)
    hidden = [embed[jnp.asarray(ids)] for ids, *_ in laid]
    del embed
    where = [(jnp.asarray(positions), jnp.asarray(mask))
             for _ids, positions, mask, *_ in laid]
    for index in range(z["layers"]):
        t0 = time.monotonic()
        w = lower_precision(layer_weights(config, index), mode)
        jax.block_until_ready(w)
        t1 = time.monotonic()
        for i, (positions, mask) in enumerate(where):
            hidden[i] = through(hidden[i], w, positions, mask)
        del w
        jax.block_until_ready(hidden)
        _say(f"mode {mode or 'highest'!r} layer {index}: weights "
             f"{t1 - t0:.1f} s, {len(laid)} cases {time.monotonic() - t1:.1f} s")
    head = vocab_weights(config, "unembed", z["d"] ** -0.5)
    if mode == "int8":
        head = _int8(head, MATMUL_AXES["unembed"])
    out = []
    for i, (_ids, _positions, _mask, copies, _plans) in enumerate(laid):
        at = np.concatenate([np.arange(first, first + z["block"])
                             for _b, _shown, first in copies])
        n = len(at)
        rows = -(-n // KEEP_PAD) * KEEP_PAD
        padded = np.zeros((rows,), np.int32)
        padded[:n] = at
        wanted = np.zeros((rows, look_up[i].shape[1]), np.int32)
        wanted[:n] = look_up[i]
        best, token, lse, logits = head_at(
            hidden[i], head, jnp.asarray(padded), jnp.asarray(wanted))
        out.append((np.asarray(best, np.float64)[:n], np.asarray(token)[:n],
                    np.asarray(lse, np.float64)[:n],
                    np.asarray(logits, np.float64)[:n]))
        hidden[i] = None
    return out


def _chain_gaps(chain, state_row, best, lse, token_logit, size):
    """The gaps of one order of reveals: ``state_row[shown]`` is the
    first row of that state's copy; ``token_logit`` the logit of the
    token judged at every copy position."""
    gaps = []
    for shown, picked in chain:
        row = state_row[shown]
        for p in picked:
            gaps.append(best[row + p] - token_logit[row + p])
        hidden = [p for p in range(size) if p not in shown]
        if len(picked) < len(hidden):
            own = sorted((best[row + p] - lse[row + p] for p in hidden),
                         reverse=True)
            lowest = min(token_logit[row + p] - lse[row + p] for p in picked)
            gaps.append(max(own[len(picked) - 1] - lowest, 0.0))
    return gaps


def _judge_case(copies, plans, best, lse, token_logit, size):
    """Per judged block, the gaps of the candidate whose largest gap
    is smallest (then whose sum is)."""
    first_copy = {}
    for n, (b, shown, _at) in enumerate(copies):
        first_copy.setdefault(b, {})[shown] = n * size
    gaps: List[float] = []
    for b, plan in plans.items():
        tried = [_chain_gaps(chain, first_copy[b], best, lse, token_logit, size)
                 for chain in plan]
        gaps += min(tried, key=lambda g: (max(g), sum(g)))
    return gaps


def _control_chain(copies, b, given, counts, own_conf, size):
    """The order a lower-precision reading would itself have revealed
    block ``b`` in, by ITS confidences in the states of the served
    tokens."""
    first_copy = {shown: n * size for n, (blk, shown, _at) in enumerate(copies)
                  if blk == b}
    shown = tuple(range(given))
    chain = []
    step = 0
    while len(shown) < size:
        hidden = [p for p in range(size) if p not in shown]
        count = min(counts[step] if step < len(counts) else size, len(hidden))
        row = first_copy[shown]
        picked = tuple(sorted(sorted(
            hidden, key=lambda p: (-own_conf[row + p], p))[:count]))
        chain.append((shown, picked))
        shown = tuple(sorted(shown + picked))
        step += 1
    return chain, first_copy


def check_served(config: Dict[str, Any], spec: Dict[str, Any]) -> Dict[str, Any]:
    """For each case (a prompt and the tokens the server streamed for
    it), the order-free judgement of the module's note over its judged
    blocks. ``positions`` counts the gaps that entered the two judged
    numbers. ``controls``: the reference itself in a lower precision on
    the SAME states; what that reading puts first, in the order its own
    confidences give, judged under the float32 reading."""
    z = sizes(config)
    if z["rule"] != "low_confidence_static":
        raise ValueError("the order-free judgement enumerates a fixed "
                         "schedule: low_confidence_static only")
    size = z["block"]
    counts = schedule(size, z["steps"])
    laid = [lay_out(case, config) for case in spec["cases"]]
    # a copy shows the mask token where a position is hidden: the token
    # judged at a copy's row is the served one, from the clean sequence
    served = [np.concatenate([ids[b * size:(b + 1) * size]
                              for b, _shown, _at in copies])
              for ids, _p, _m, copies, _plans in laid]
    modes = list(spec.get("controls", ()))
    lower = {mode: run_pass(config, laid, [s[:, None] for s in served], mode)
             for mode in modes}
    look_up = [np.stack([served[i]] + [lower[m][i][1] for m in modes], axis=1)
               for i in range(len(laid))]
    got = run_pass(config, laid, look_up)
    cases = []
    worst = total = 0.0
    positions = 0
    for case, (_ids, _p, _m, copies, plans), (best, _tok, lse, logit) in zip(
            spec["cases"], laid, got):
        gaps = _judge_case(copies, plans, best, lse, logit[:, 0], size)
        cases.append({
            "index": case["index"], "prompt_len": len(case["prompt"]),
            "served": len(case["tokens"]), "blocks_judged": len(plans),
            "gaps": len(gaps), "max_gap": max(gaps, default=0.0),
            "exact": int(sum(g == 0 for g in gaps)),
            "best_logit_abs_max": float(np.abs(best).max()) if len(best) else 0.0,
        })
        worst = max(worst, max(gaps, default=0.0))
        total += float(sum(gaps))
        positions += len(gaps)
    controls = {}
    for n, mode in enumerate(modes):
        c_gaps: List[float] = []
        for case, (_ids, _p, _m, copies, plans), (best, _tok, lse, logit), theirs in zip(
                spec["cases"], laid, got, lower[mode]):
            own_conf = theirs[0] - theirs[2]
            for b in plans:
                given = max(len(case["prompt"]) - b * size, 0)
                chain, first_copy = _control_chain(
                    copies, b, given, counts, own_conf, size)
                c_gaps += _chain_gaps(chain, first_copy, best, lse,
                                      logit[:, 1 + n], size)
        controls[mode] = {
            "max_logit_gap": max(c_gaps, default=0.0),
            "mean_logit_gap": float(sum(c_gaps)) / max(len(c_gaps), 1),
            "positions": len(c_gaps),
        }
    return {"cases": cases, "max_logit_gap": worst, "positions": positions,
            "mean_logit_gap": total / max(positions, 1), "controls": controls}
