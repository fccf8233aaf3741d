"""The plain reference for the Mistral-7B configurations.

Mistral-7B-v0.1's decoder as published (Jiang et al. 2023,
arXiv:2310.06825; HF ``modeling_mistral.py``): token embedding, then
per block RMSNorm -> q/k/v projections -> rotary embedding on q and k
(half-split pairing, theta from the config) -> grouped-query causal
attention limited to the last ``sliding_window`` keys -> output
projection -> residual; RMSNorm -> SwiGLU (silu(gate) * up, down) ->
residual; final RMSNorm; untied output head. For training the loss is
the mean next-token cross-entropy.

Straightforward ``jax.numpy`` in float32 with
``jax.default_matmul_precision("highest")``; no kernel, no cache, no
batching, one sequence at a time, attention in blocks of query rows so
that a long sequence fits. It imports nothing of the program and takes
nothing the program made. The weights are data made here from the same
recipe the program documents for a fresh model (``PRNGKey(0)`` split
four ways: embedding N(0, 0.02^2); every projection N(0, 1/fan_in);
norm scales 1), with jax's counter-based generator, which gives the
same numbers wherever it runs.

Departures from the published model, each because the program's block
fixes it (listed under ``assumed`` in the configuration file): the
norm's epsilon is the file's ``rms_norm_eps``.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512  # rows of a sequence worked on at once, so that 8192 fit


def init_weights(config: Dict[str, Any]) -> Dict[str, Any]:
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    kv = config["num_key_value_heads"]
    hd = d // h
    f = config["intermediate_size"]
    layers = config["num_hidden_layers"]
    vocab = config["vocab_size"]

    @jax.jit
    def make():
        k_emb, k_attn, k_mlp, k_out = jax.random.split(jax.random.PRNGKey(0), 4)
        ka = jax.random.split(k_attn, 4)
        km = jax.random.split(k_mlp, 3)

        def dense(key, shape, fan_in):
            return jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)

        return {
            "embed": jax.random.normal(k_emb, (vocab, d), jnp.float32) * 0.02,
            "wq": dense(ka[0], (layers, d, h, hd), d),
            "wk": dense(ka[1], (layers, d, kv, hd), d),
            "wv": dense(ka[2], (layers, d, kv, hd), d),
            "wo": dense(ka[3], (layers, h, hd, d), h * hd),
            "w_gate": dense(km[0], (layers, d, f), d),
            "w_up": dense(km[1], (layers, d, f), d),
            "w_down": dense(km[2], (layers, f, d), f),
            "norm_attn": jnp.ones((layers, d), jnp.float32),
            "norm_mlp": jnp.ones((layers, d), jnp.float32),
            "norm_out": jnp.ones((d,), jnp.float32),
            "unembed": dense(k_out, (d, vocab), d),
        }

    return make()


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: [seq, heads, head_dim]; pairs (i, i + head_dim/2)."""
    seq, _heads, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _attention(q, k, v, window):
    """q: [seq, h, hd]; k, v: [seq, kv, hd]. Query i sees key j iff
    i - window < j <= i. Computed in blocks of query rows, each over
    the slice of keys its rows can see; a block's scores are worked
    out again in the backward pass and not kept."""
    seq, h, hd = q.shape
    groups = h // k.shape[1]
    k = jnp.repeat(k, groups, axis=1)
    v = jnp.repeat(v, groups, axis=1)
    block = math.gcd(seq, Q_BLOCK)
    span = seq if window <= 0 else min(seq, window + block)

    @jax.checkpoint
    def one(start):
        first = jnp.clip(start + block - span, 0, seq - span)
        rows = start + jnp.arange(block)
        cols = first + jnp.arange(span)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        kb = jax.lax.dynamic_slice_in_dim(k, first, span, axis=0)
        vb = jax.lax.dynamic_slice_in_dim(v, first, span, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, kb) * (hd ** -0.5)
        mask = cols[None, :] <= rows[:, None]
        if window > 0:
            mask &= cols[None, :] > rows[:, None] - window
        scores = jnp.where(mask[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), vb)

    out = jax.lax.map(one, jnp.arange(0, seq, block))
    return out.reshape(seq, h, hd)


def hidden(weights, tokens, config, window):
    """tokens [seq] -> final normed hidden [seq, d]."""
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    x = weights["embed"][tokens]
    per_layer = {k: weights[k] for k in (
        "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
        "norm_attn", "norm_mlp")}

    def block(x, w):
        hn = _rms_norm(x, w["norm_attn"], eps)
        q = _rope(jnp.einsum("sd,dhk->shk", hn, w["wq"]), theta)
        k = _rope(jnp.einsum("sd,dhk->shk", hn, w["wk"]), theta)
        v = jnp.einsum("sd,dhk->shk", hn, w["wv"])
        x = x + jnp.einsum("shk,hkd->sd", _attention(q, k, v, window), w["wo"])
        hn = _rms_norm(x, w["norm_mlp"], eps)

        @jax.checkpoint
        def mlp(rows):
            return (jax.nn.silu(rows @ w["w_gate"]) * (rows @ w["w_up"])) @ w["w_down"]

        rows = math.gcd(x.shape[0], Q_BLOCK)
        out = jax.lax.map(mlp, hn.reshape(-1, rows, hn.shape[-1]))
        return x + out.reshape(x.shape), None

    # a layer's inner values are worked out again in the backward pass
    x, _ = jax.lax.scan(jax.checkpoint(block), x, per_layer)
    return _rms_norm(x, weights["norm_out"], eps)


def make_logits_fn(config, window, precision="highest"):
    @jax.jit
    def logits(weights, tokens):
        with jax.default_matmul_precision(precision):
            return hidden(weights, tokens, config, window) @ weights["unembed"]
    return logits


MATMUL_WEIGHTS = {  # name -> the axes a token's activations contract over
    "wq": (1,), "wk": (1,), "wv": (1,), "wo": (1, 2),
    "w_gate": (1,), "w_up": (1,), "w_down": (1,), "unembed": (0,),
}


def lower_precision(weights, mode):
    """The reference's weights as a lower precision would hold them:
    ``bf16`` rounds every matmul weight to bfloat16 (the precision the
    configurations state for compute); ``int8`` keeps 8 bits a weight
    with one float scale per output channel (symmetric, absmax / 127),
    the nearest precision below it."""
    out = dict(weights)
    for name, axes in MATMUL_WEIGHTS.items():
        w = weights[name]
        if mode == "int8":
            scale = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 127.0
            w = jnp.clip(jnp.round(w / scale), -127, 127) * scale
        out[name] = w.astype(jnp.bfloat16).astype(jnp.float32)
    return out


def _row_loss(weights, row, config, window):
    """Mean next-token cross-entropy of one row [seq + 1]."""
    x = hidden(weights, row[:-1], config, window)
    block = math.gcd(x.shape[0], Q_BLOCK)

    @jax.checkpoint
    def piece(start):
        xs = jax.lax.dynamic_slice_in_dim(x, start, block, axis=0)
        ts = jax.lax.dynamic_slice_in_dim(row, start + 1, block)
        logp = jax.nn.log_softmax(xs @ weights["unembed"], axis=-1)
        return -jnp.take_along_axis(logp, ts[:, None], axis=-1)[:, 0]

    return jnp.mean(jax.lax.map(piece, jnp.arange(0, x.shape[0], block)))


def make_loss_fn(config, window):
    @jax.jit
    def loss(weights, row):
        with jax.default_matmul_precision("highest"):
            return _row_loss(weights, row, config, window)
    return loss


def make_grad_fn(config, window, mode=""):
    """(weights, rows [batch, seq + 1]) -> (mean loss, its gradient),
    one row after another. ``mode`` "" is the reference: float32,
    ``highest``. "int8" is the CONTROL: every matmul weight held in 8
    bits (lower_precision; the gradient passes straight through the
    rounding) and single-pass bfloat16 products."""
    precision = "default" if mode else "highest"

    def row_loss(weights, row):
        if mode:
            lower = lower_precision(weights, mode)
            weights = jax.tree.map(
                lambda w, l: w + jax.lax.stop_gradient(l - w), weights, lower)
        with jax.default_matmul_precision(precision):
            return _row_loss(weights, row, config, window)

    row_grad = jax.jit(jax.value_and_grad(row_loss))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)

    def grads(weights, rows):
        loss, grad = row_grad(weights, rows[0])
        for row in rows[1:]:
            more, other = row_grad(weights, row)
            loss, grad = loss + more, add(grad, other)
        return loss / len(rows), _scaled(grad, 1.0 / len(rows))

    grads.row_grad = row_grad  # for a compile without the chip
    return grads


@functools.partial(jax.jit, donate_argnums=0)
def _scaled(tree, factor):
    return jax.tree.map(lambda g: g * factor, tree)


STACKED = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "norm_attn", "norm_mlp")


def _norms(tree, sampled=False, sums=False) -> Dict[str, List[float]]:
    """One 2-norm (``sums``: the plain sum of the elements) per leaf,
    and per layer of a leaf stacked over layers, under the program's
    names for its leaves. ``sampled``: of a leaf above 2**20 elements
    only its leading 1/16 along the first axis that is not the
    layer's, the part of the parameters that the harness reads back
    from the trainer."""
    @jax.jit
    def reduce(tree):
        out = {}
        for name, leaf in tree.items():
            stacked = name in STACKED
            if sampled and leaf.size > 1 << 20:
                keep = max(leaf.shape[1 if stacked else 0] // 16, 1)
                leaf = leaf[:, :keep] if stacked else leaf[:keep]
            rows = leaf.reshape(leaf.shape[0], -1) if stacked else leaf.reshape(1, -1)
            out[("layers/" if stacked else "") + name] = (
                jnp.sum(rows, axis=1) if sums
                else jnp.sqrt(jnp.sum(jnp.square(rows), axis=1)))
        return out

    return {name: [float(v) for v in values]
            for name, values in jax.device_get(reduce(tree)).items()}


@jax.jit
def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(tree)))


def adamw_update(weights, history, opt):
    """AdamW (Loshchilov & Hutter 2019) as the configuration states it,
    decay on every leaf, after the t-th gradient. ``history`` holds
    the t clipped gradients, oldest first: the moments are their
    weighted sums, so after the first step one tree stands for both.
    The weights are updated in place."""
    t = len(history)
    b1, b2 = opt["b1"], opt["b2"]

    def leaf(p, *gs):
        m = sum((1 - b1) * b1 ** (t - 1 - i) * g for i, g in enumerate(gs))
        v = sum((1 - b2) * b2 ** (t - 1 - i) * g * g for i, g in enumerate(gs))
        m_hat, v_hat = m / (1 - b1 ** t), v / (1 - b2 ** t)
        step = m_hat / (jnp.sqrt(v_hat) + opt["eps"]) + opt["weight_decay"] * p
        return p - opt["learning_rate"] * step

    update = jax.jit(lambda w, *hs: jax.tree.map(leaf, w, *hs), donate_argnums=0)
    return update(weights, *history)


def train_steps(config, spec, mode=""):
    """Follow the trainer through its first steps from the seeded
    weights: each step's loss, the first moment AdamW holds after
    step 1 and the parameters' change after the last, as norms per
    leaf and layer. Two steps fit one chip: float32 weights, the
    first step's gradient, and a gradient being summed over rows."""
    window = int(spec.get("window", 0))
    opt = config["optimizer"]
    weights = init_weights(config)
    grad_fn = make_grad_fn(config, window, mode)
    width = int(spec["seq_len"]) + 1
    data = np.concatenate([np.load(p) for p in spec["shards"]])
    out = {"losses": [], "grad_norms": [], "clip": []}
    history: List[Any] = []
    for rows in spec["steps"]:
        batch = np.stack([data[r * width:(r + 1) * width] for r in rows])
        loss, grad = grad_fn(weights, jnp.asarray(batch))
        norm = float(_global_norm(grad))
        clip = min(1.0, opt["clip_norm"] / max(norm, 1e-30))
        grad = _scaled(grad, clip)
        out["losses"].append(float(loss))
        out["grad_norms"].append(norm)
        out["clip"].append(clip)
        if not history:
            for key, sums in (("first_moment_norms", False), ("first_moment_sums", True)):
                out[key] = {k: [(1 - opt["b1"]) * v for v in vs]
                            for k, vs in _norms(grad, sums=sums).items()}
        history.append(grad)
        weights = adamw_update(weights, history, opt)
        del grad
    del history
    seeded = init_weights(config)
    out["change_norms"] = _norms(
        jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b), donate_argnums=0)(
            weights, seeded), sampled=True)
    return out


# -- what the harness's child calls -------------------------------------


def _bucket(n: int, quantum: int, cap: int) -> int:
    return min(-(-n // quantum) * quantum, cap)


def check_served(config: Dict[str, Any], spec: Dict[str, Any]) -> Dict[str, Any]:
    """For each case (a prompt and the greedy tokens the server
    streamed for it): run the reference once over prompt + tokens and
    read, at every generated position, how far the served token's
    logit lies below the reference's best. Rows are padded at the END
    to a multiple of 256 (causal attention: padding cannot reach an
    earlier position), so few shapes compile."""
    window = int(spec.get("window", 0))
    weights = init_weights(config)
    fn = make_logits_fn(config, window)
    cap = int(spec["max_len"])
    # the control, read in every run beside the program's own numbers:
    # the reference itself in the stated compute precision (bf16
    # weights and single-pass bf16 products) and in the nearest
    # precision below (int8 weights). At each position of the SAME
    # prompts and tokens, the gap of the token that variant puts first.
    variants = {}
    for mode in spec.get("controls", ()):
        variants[mode] = {"weights": jax.jit(lower_precision, static_argnums=1)(
            weights, mode), "fn": make_logits_fn(config, window, "default"),
            "sum": 0.0, "max": 0.0}
    cases = []
    worst = 0.0
    total = 0.0
    positions = 0
    for case in spec["cases"]:
        prompt, served = case["prompt"], case["tokens"]
        row = (prompt + served)[:-1]
        width = _bucket(len(row), 256, cap)
        padded = np.zeros((width,), np.int32)
        padded[: len(row)] = row
        logits = np.asarray(fn(weights, jnp.asarray(padded)))
        at = np.arange(len(prompt) - 1, len(row))
        picked = logits[at, np.asarray(served)]
        best = logits[at].max(axis=-1)
        gaps = best - picked
        for variant in variants.values():
            lower = np.asarray(variant["fn"](variant["weights"], jnp.asarray(padded)))
            theirs = lower[at].argmax(axis=-1)
            vgaps = best - logits[at, theirs]
            variant["sum"] += float(vgaps.sum())
            variant["max"] = max(variant["max"], float(vgaps.max()))
        cases.append({
            "index": case["index"], "prompt_len": len(prompt),
            "served": len(served), "max_gap": float(gaps.max()),
            "exact": int((gaps == 0).sum()),
            "first_divergence": int(np.argmax(gaps > 0)) if (gaps > 0).any() else -1,
            "best_logit_abs_max": float(np.abs(best).max()),
        })
        worst = max(worst, float(gaps.max()))
        total += float(gaps.sum())
        positions += len(served)
    # the widest gap swings from seed to seed by its nature and grows
    # with the error; the MEAN gap over all positions grows with the
    # error's square (a near-tie is both likelier and wider), so it is
    # the number that separates a lower precision
    return {"cases": cases, "max_logit_gap": worst, "positions": positions,
            "mean_logit_gap": total / max(positions, 1),
            "controls": {mode: {"max_logit_gap": v["max"],
                                "mean_logit_gap": v["sum"] / max(positions, 1)}
                         for mode, v in variants.items()}}


def check_trained(config: Dict[str, Any], spec: Dict[str, Any]) -> Dict[str, Any]:
    """The trainer's first steps followed from the seeded weights on
    the rows the loader served (same shards, the loader's documented
    order), and the reference's loss AT the seeded weights on further
    batches (``seeded_batches``). ``mode`` asks for the control
    instead of the reference."""
    window = int(spec.get("window", 0))
    out = train_steps(config, spec, spec.get("mode", ""))
    weights = init_weights(config)
    fn = make_loss_fn(config, window)
    width = int(spec["seq_len"]) + 1
    data = np.concatenate([np.load(p) for p in spec["shards"]])
    out["seeded_losses"] = [
        float(np.mean([float(fn(weights, jnp.asarray(data[r * width:(r + 1) * width])))
                       for r in rows]))
        for rows in spec.get("seeded_batches", ())
    ]
    return out
