"""Incremental decoding: prefill + single-token steps with a KV cache.

TPU-first inference for the flagship transformer:

- static shapes throughout — the cache is allocated at ``max_len`` and
  masked by position, so XLA compiles exactly two programs (prefill and
  decode step) regardless of generation length;
- the decode loop is a ``lax.scan`` over steps; prefill's layer stack
  is a ``lax.scan`` over stacked layer params (same as training),
  a decode step's is unrolled so that each layer writes and reads its
  own part of the cache where it lies (``decode_chunk``);
- greedy or temperature sampling.

Numerics are identical to the full forward: the parity test asserts
incremental logits match ``forward``'s per-position logits.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .quantized import (
    can_fuse_int8,
    embed_lookup,
    fused_attn_out,
    fused_mlp,
    fused_qkv,
    maybe_dequant_layer,
    maybe_dequant_top,
)
from .transformer import (
    Params,
    TransformerConfig,
    _attn_out,
    _mlp,
    _qkv,
    _rms_norm,
    flash_eligible,
    repeat_kv,
)
from ..ops.attention import NEG_INF, causal_attention
from ..ops.flash import flash_attention_forward

Cache = Dict[str, jax.Array]


def init_cache(
    cfg: TransformerConfig, batch: int, max_len: int
) -> Cache:
    """Zeroed KV cache: k/v are [layers, batch, length, kv_heads,
    head_dim] — under GQA the cache holds only the kv heads, which is
    the whole point (n_heads/kv_heads smaller cache).

    With sliding-window attention (cfg.window > 0) the cache is a RING
    of ``min(window, max_len)`` entries — position p lives at slot
    ``p % length`` and old entries are overwritten as the window
    slides, so decode KV memory is bounded by the window, not the
    generation length.

    With ``cfg.kv_int8`` k/v store as int8 with a per-(token, head)
    scale over the head_dim axis — KV memory halves vs bf16,
    composing with both levers above.

    ``pos`` is one number: every row stands at the same position. The
    slot pool (models/slots.py ``slot_cache``) keeps the same leaves
    one per layer, with ``pos`` one number per row, and
    ``decode_chunk`` takes either.

    A configuration of another family (``cfg.family``, e.g.
    models/mla_moe.py's latent cache) makes its own tree; ``prefill``
    and ``decode_chunk`` hand over the same way, so everything built
    on these three (generate, the prefix cache's extend, the slot
    pool) serves either family."""
    family = getattr(cfg, "family", None)
    if family is not None:
        return family.init_cache(cfg, batch, max_len)
    length = max_len if cfg.window <= 0 else min(cfg.window, max_len)
    shape = (cfg.n_layers, batch, length, cfg.kv_heads, cfg.head_dim)
    cache: Cache = {
        "pos": jnp.zeros((), jnp.int32),  # number of tokens cached
    }
    if cfg.kv_int8:
        cache["k"] = jnp.zeros(shape, jnp.int8)
        cache["v"] = jnp.zeros(shape, jnp.int8)
        cache["k_scale"] = jnp.zeros(shape[:-1], jnp.float32)
        cache["v_scale"] = jnp.zeros(shape[:-1], jnp.float32)
    else:
        cache["k"] = jnp.zeros(shape, cfg.dtype)
        cache["v"] = jnp.zeros(shape, cfg.dtype)
    return cache


def _kv_quant(x: jax.Array):
    """Symmetric int8 over the head_dim axis via the codebase's one
    quantization formula (ops/quant.py); returns (q int8, scale f32
    without the trailing axis)."""
    from ..ops.quant import quantize_int8_axes

    q, scale = quantize_int8_axes(x, (-1,))
    return q, scale[..., 0]


def _kv_dequant(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _logits(params: Params, x: jax.Array, cfg: TransformerConfig) -> jax.Array:
    with jax.named_scope("head"):
        x = _rms_norm(x, params["norm_out"])
        return jnp.einsum(
            "bsd,dv->bsv", x,
            maybe_dequant_top(params, "unembed", cfg.dtype),
            preferred_element_type=jnp.float32,
        )


def prefill(
    params: Params, tokens: jax.Array, cfg: TransformerConfig, max_len: int
) -> Tuple[jax.Array, Cache]:
    """Process the prompt; returns (logits for the last position, cache).

    tokens: [batch, prompt_len] int32; prompt_len <= max_len.
    """
    family = getattr(cfg, "family", None)
    if family is not None:
        return family.prefill(params, tokens, cfg, max_len)
    b, s = tokens.shape
    x = embed_lookup(params, tokens, cfg.dtype)

    # long prompts go through the pallas flash kernels, same threshold
    # as training; short prompts stay einsum. The flash path is
    # GQA-native: it reads the unrepeated kv heads straight from the
    # cache layout, skipping the repeat_kv copy. Sliding windows ride
    # both paths (the flash kernels block-skip old KV; the einsum path
    # masks).
    gqa_flash = cfg.attention_fn is None and flash_eligible(
        cfg, s, kind="fwd"
    )
    if cfg.attention_fn is not None:
        attn_fn = cfg.attention_fn
    elif cfg.window > 0:
        import functools as _ft

        attn_fn = _ft.partial(causal_attention, window=cfg.window)
    else:
        attn_fn = causal_attention

    def attend(q, k, v):
        if cfg.kv_int8:
            # attention reads the quantization roundtrip, exactly what
            # any later decode reads from the cache — prefill,
            # chunked_prefill, and decode stay numerically consistent
            k = _kv_dequant(*_kv_quant(k), cfg.dtype)
            v = _kv_dequant(*_kv_quant(v), cfg.dtype)
        if gqa_flash:
            from ..ops import tuning as _tuning

            fq, fk = _tuning.pick_blocks("fwd", s)
            attn = flash_attention_forward(
                q, k, v, block_q=fq, block_k=fk, window=cfg.window
            )
        elif getattr(attn_fn, "gqa_native", False):
            # ring attention (context-parallel prefill): the ring
            # rotates the SMALL grouped K/V over ICI — repeating
            # first would ship n_heads/kv_heads x more bytes per hop
            # (transformer.py honors the same flag)
            attn = attn_fn(q, k, v)
        else:
            attn = attn_fn(
                q, repeat_kv(k, cfg.n_heads), repeat_kv(v, cfg.n_heads)
            )
        return attn, k, v

    def body(carry, layer_params):
        layer_params = maybe_dequant_layer(layer_params, cfg.dtype)
        q, k, v = _qkv(carry, layer_params, cfg)
        with jax.named_scope("attn"), jax.named_scope("attn.scores"):
            attn, k, v = attend(q, k, v)
        out = _mlp(
            _attn_out(carry, attn, layer_params, cfg), layer_params, cfg
        )
        return out, (k, v)  # cache stores the unrepeated kv heads

    with jax.named_scope("layers"):
        x, (ks, vs) = lax.scan(body, x, params["layers"])
    with jax.named_scope("attn"), jax.named_scope("attn.kv_write"):
        cache = init_cache(cfg, b, max_len)
        length = cache["k"].shape[2]
        writes = {"k": ks, "v": vs}
        if cfg.kv_int8:
            writes["k"], writes["k_scale"] = _kv_quant(ks)
            writes["v"], writes["v_scale"] = _kv_quant(vs)
        if s > length:
            # ring cache smaller than the prompt: keep the last `length`
            # positions, each at its slot p % length (static scatter)
            import numpy as _np

            slots = _np.arange(s - length, s) % length
            for name, arr in writes.items():
                cache[name] = cache[name].at[:, :, slots].set(
                    arr[:, :, s - length:]
                )
        else:
            for name, arr in writes.items():
                cache[name] = lax.dynamic_update_slice(
                    cache[name], arr, (0,) * cache[name].ndim
                )
    cache["pos"] = jnp.asarray(s, jnp.int32)
    logits = _logits(params, x[:, -1:, :], cfg)
    return logits[:, 0, :], cache


def chunked_prefill(
    params: Params,
    tokens: jax.Array,
    cfg: TransformerConfig,
    max_len: int,
    chunk_len: int = 512,
) -> Tuple[jax.Array, Cache]:
    """``prefill`` in fixed-size pieces: the prompt streams through
    ``decode_chunk`` ``chunk_len`` tokens at a time, so peak
    activation memory is O(chunk) instead of O(prompt) — the serving
    answer to prompts long enough that one-shot prefill attention
    blows HBM. Numerics match ``prefill`` (same masked paths).

    Compile churn is bounded by construction: the ragged remainder is
    processed first as (at most) one sub-16 piece plus 16-token
    pieces, then full chunks — so piece lengths come from
    {1..15, 16, chunk_len} regardless of prompt length, instead of a
    fresh program per distinct ``prompt_len % chunk_len``. With a
    sliding window, pieces are capped at the ring length.
    """
    b, s = tokens.shape
    if chunk_len < 1:
        raise ValueError("chunk_len must be >= 1")
    cache = init_cache(cfg, b, max_len)
    if cfg.window > 0:
        chunk_len = min(chunk_len, cache["k"].shape[2])
    return extend_pieces(params, cache, tokens, cfg, chunk_len)


def extend_pieces(
    params: Params,
    cache: Cache,
    tokens: jax.Array,
    cfg: TransformerConfig,
    chunk_len: int,
) -> Tuple[jax.Array, Cache]:
    """Extend ``tokens`` into ``cache`` in bounded pieces — the
    chunked_prefill piece plan ({1..15, 16, chunk_len} lengths), also
    applied by the slot engine's prefix-hit path so a huge cached-hit
    suffix honors the same O(chunk) activation bound as a cold
    prompt. Returns (last logits, cache)."""
    s = tokens.shape[1]
    bucket = min(16, chunk_len)
    lead = s % chunk_len
    plan = []
    if lead % bucket:
        plan.append(lead % bucket)
    plan += [bucket] * (lead // bucket)
    plan += [chunk_len] * (s // chunk_len)
    extend = _jitted_extend(cfg)
    logits = None
    start = 0
    for piece in plan:
        logits, cache = extend(
            params, cache, tokens[:, start:start + piece]
        )
        start += piece
    return logits, cache


def decode_step(
    params: Params, cache: Cache, token: jax.Array, cfg: TransformerConfig
) -> Tuple[jax.Array, Cache]:
    """One autoregressive step. token: [batch] int32 (the token at
    position cache['pos']); returns (logits [batch, vocab], new cache).
    The m=1 case of decode_chunk — one shared implementation keeps
    single-step and speculative-verify numerics identical by
    construction."""
    logits, new_cache = decode_chunk(params, cache, token[:, None], cfg)
    return logits[:, 0, :], new_cache


def _grouped_attention(
    q: jax.Array, keys: jax.Array, values: jax.Array, valid: jax.Array,
    dtype, scale: Optional[float] = None,
) -> jax.Array:
    """Masked attention of q [b, m, n_heads, d] over keys/values
    [b, length, kv_heads, d] AS THE CACHE STORES THEM; valid is
    [m, length], or [b or 1, m, length] where rows stand at different
    positions. Returns [b, m, n_heads, d] in ``dtype``.

    The n_heads axis is viewed as [kv_heads, group], so query head
    h = kv * group + g reads kv head h // group (``repeat_kv``'s
    order) and the cache is never repeated to n_heads. The keys enter
    the score contraction in their own dtype with float32 accumulation
    (bf16 x bf16 products are exact in float32) and the
    head_dim ** -0.5 scale (or a family's own ``scale``,
    models/hybrid_ssm.py) is applied to the float32 scores, so they
    are never widened. The softmax weights stay float32 and meet the
    stored values at HIGHEST precision: on the TPU the values' widening
    folds into the contraction's fusion (no float32 copy reaches
    memory, tests/test_tpu_compile.py) and the extra MXU passes hide
    behind the read of the cache, while weights rounded to bf16 first
    cost half as much error again in a decode step's output (PERF.md,
    PR 26). A decode step reads each stored key and value once."""
    b, m, n_heads, d = q.shape
    kv_heads = keys.shape[2]
    qg = q.reshape(b, m, kv_heads, n_heads // kv_heads, d)
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, keys,
        preferred_element_type=jnp.float32,
    ) * (d ** -0.5 if scale is None else scale)
    # [b, kv_heads, group, m, length]
    scores = jnp.where(
        jnp.expand_dims(valid, (-3, -4)), scores, NEG_INF
    )
    weights = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum(
        "bhgqk,bkhd->bqhgd", weights, values,
        preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST,
    )
    return attn.astype(dtype).reshape(b, m, n_heads, d)


def decode_chunk(
    params: Params, cache: Cache, tokens: jax.Array, cfg: TransformerConfig,
    read_len: Optional[int] = None,
) -> Tuple[jax.Array, Cache]:
    """Process m tokens against the cache in ONE forward — the verify
    step of speculative decoding (m = speculate+1), and the general
    multi-token incremental step.

    ``tokens[:, i]`` sits at position ``pos + i`` of its row;
    ``logits[:, i]`` predicts position ``pos + i + 1``. ``pos`` is one
    number, or one per row ([batch], the slot pool: a row past the end
    of its cache writes nothing, a ring row wraps). Within the chunk
    attention is causal; everything already cached is visible.
    Numerics match m sequential ``decode_step`` calls (and therefore
    the full forward).

    The layers are unrolled, and each writes the chunk's keys and
    values INTO its leaf of the cache and reads the leaf where it lies.
    A ``lax.scan`` that took the cache as ``xs`` and gave it back as
    ``ys`` copied every layer's slice out of the stack and a whole new
    stack back on every step; with the stack in the scan's carry the
    write is in place, but the slice at a traced layer index is still
    copied out, because the contraction changes the keys' layout inside
    its fusion and the chip's compiler will not fuse the slice on top
    of that (PERF.md, PR 28; tests/test_tpu_compile.py pins the
    program). A cache stacked over layers (``init_cache``) is split
    into its layers on the way in and stacked again on the way out;
    the slot pool's leaves, one per layer, pass straight through.

    Attention is ``_grouped_attention``: the query heads are viewed as
    [kv_heads, group] and contracted with the layer's keys and values
    in the layout the cache stores them in (the ring concatenated with
    the chunk, or the ``_kv_dequant`` output, on those paths),
    accumulating in float32. A decode step is bound by the bytes it
    reads, and the cache is the only operand that grows with
    ``max_len``: repeating it to n_heads and widening it to float32 in
    memory first made every step write and re-read about twenty times
    the cache's size (PERF.md, PR 26), so neither copy is ever built.

    ``read_len`` (static; a linear cache only) cuts what attention
    READS to each leaf's first ``read_len`` positions; the chunk's
    keys and values are still written into the whole leaf, in place.
    The caller promises that no row whose output it keeps reaches past
    it (``pos + m <= read_len``: the slot pool's step program counts
    that on the host, models/stepprog.py). A masked position weighs
    exactly 0.0 in the float32 softmax, so the cut drops exact zeros
    from the sums and nothing else: the same mathematics at the same
    precision, and a step that reads a pool of 4,096-position rows to
    the 1,024th reads a quarter of the bytes. None (every other
    caller) reads whole leaves: the program it always was.
    """
    family = getattr(cfg, "family", None)
    if read_len is not None and (family is not None or cfg.window > 0):
        raise ValueError(
            "read_len cuts a linear cache's read: this configuration's "
            "cache is its family's own or a ring"
        )
    if family is not None:
        return family.decode_chunk(params, cache, tokens, cfg)
    pos = cache["pos"]
    b, m = tokens.shape
    # a cache is stacked over layers (init_cache, prefill); the slot
    # pool holds one leaf per layer (models/slots.py), and each layer
    # below writes and reads its own leaf where it lies
    stacked = not isinstance(cache["k"], (list, tuple))
    kv = {
        name: list(leaves) for name, leaves in cache.items()
        if name != "pos"
    }
    length = kv["k"][0].shape[1]
    ring = cfg.window > 0
    if ring and m > length:
        raise ValueError(
            f"decode chunk of {m} tokens exceeds the {length}-slot "
            "window ring; chunk at most `window` tokens"
        )
    # how far attention reads a leaf: all of it, or ``read_len``
    reach = length if read_len is None else read_len
    if not 0 < reach <= length:
        raise ValueError(
            f"read_len {read_len} outside the cache's {length} positions"
        )
    x = embed_lookup(params, tokens, cfg.dtype)  # [b, m, d]
    # one position for every row, or one per row: [1 or b, m]
    q_idx = jnp.arange(m)
    q_pos = pos.reshape(-1, 1) + q_idx
    if ring:
        # ring slot j holds the newest position p < pos with
        # p % length == j (negative = never written); a query at
        # pos+i sees ring entries inside its window plus the chunk's
        # own causal prefix — the chunk k/v are CONCATENATED after the
        # ring so in-chunk keys are never read from slots they are
        # about to overwrite
        before = pos.reshape(-1, 1) - 1
        ring_pos = before - jnp.mod(before - jnp.arange(length), length)
        ring_ok = (
            (ring_pos[:, None, :] >= 0)
            & (ring_pos[:, None, :] > q_pos[:, :, None] - cfg.window)
        )
        chunk_ok = (
            (q_idx[None, :] <= q_idx[:, None])
            & (q_idx[:, None] - q_idx[None, :] < cfg.window)
        )
        valid = jnp.concatenate(
            [ring_ok, jnp.broadcast_to(chunk_ok, (len(ring_ok), m, m))],
            axis=2,
        )
        write_idx = jnp.mod(q_pos, length)
    else:
        valid = jnp.arange(reach) <= q_pos[:, :, None]  # [1 or b, m, reach]
        write_idx = q_pos
    rows = jnp.arange(b)[:, None]
    # int8-quantized dense models run their projections through the
    # fused dequant pallas GEMM: decode is weight-streaming bound, so
    # reading int8 instead of dequantized bf16 halves the HBM traffic
    fused = can_fuse_int8(params["layers"], cfg, rows=b * m)

    kv_int8 = cfg.kv_int8

    def write(leaf, new):
        """``new`` [b, m, ...] into one layer's leaf [b, length, ...],
        in place. One position for every row is one block; positions
        per row scatter, a ring wraps, and a row past the end of a
        linear cache (a dead slot of the pool) writes nothing."""
        if pos.ndim == 0 and not ring:
            return lax.dynamic_update_slice(
                leaf, new, (0, pos) + (0,) * (new.ndim - 2)
            )
        return leaf.at[rows, write_idx].set(new, mode="drop")

    def cut(leaf):
        """A leaf as far as attention reads it (``read_len``)."""
        return leaf if reach == length else leaf[:, :reach]

    def read(layer, name):
        """The layer's keys or values as attention contracts them."""
        if not kv_int8:
            return cut(kv[name][layer])
        return _kv_dequant(
            cut(kv[name][layer]), cut(kv[name + "_scale"][layer]),
            cfg.dtype,
        )

    with jax.named_scope("layers"):
        for layer in range(cfg.n_layers):
            layer_params = jax.tree.map(
                lambda w: w[layer], params["layers"]
            )
            if fused:
                q, k, v = fused_qkv(x, layer_params, cfg, offset=pos)
            else:
                layer_params = maybe_dequant_layer(layer_params, cfg.dtype)
                q, k, v = _qkv(x, layer_params, cfg, offset=pos)
            with jax.named_scope("attn"), jax.named_scope("attn.kv_write"):
                new = {"k": k, "v": v}
                if kv_int8:
                    new["k"], new["k_scale"] = _kv_quant(k)
                    new["v"], new["v_scale"] = _kv_quant(v)
                if ring:
                    # the chunk's own k/v also read through the
                    # quantization roundtrip, so chunked decode matches
                    # sequential steps (which read their keys back from
                    # the quantized ring)
                    if kv_int8:
                        k = _kv_dequant(new["k"], new["k_scale"], cfg.dtype)
                        v = _kv_dequant(new["v"], new["v_scale"], cfg.dtype)
                    keys = jnp.concatenate([read(layer, "k"), k], axis=1)
                    values = jnp.concatenate([read(layer, "v"), v], axis=1)
                for name in kv:
                    kv[name][layer] = write(kv[name][layer], new[name])
                if not ring:
                    keys, values = read(layer, "k"), read(layer, "v")
            with jax.named_scope("attn"), jax.named_scope("attn.scores"):
                attn = _grouped_attention(q, keys, values, valid, cfg.dtype)
            if fused:
                x = fused_attn_out(x, attn, layer_params, cfg)
                x = fused_mlp(x, layer_params, cfg)
            else:
                x = _attn_out(x, attn, layer_params, cfg)
                x = _mlp(x, layer_params, cfg)
    logits = _logits(params, x, cfg)  # [b, m, vocab]
    if stacked:
        kv = {name: jnp.stack(leaves) for name, leaves in kv.items()}
    return logits, {**kv, "pos": pos + m}


import functools


#: the sampler's arms, by the index ``sampler_arm`` gives: the named
#: scope each runs under (in a device trace an operation's path says
#: which arm it belongs to, and an arm that did not run left no event)
SAMPLER_ARMS = ("sample.argmax", "sample.draw", "sample.filter")


def row_arm(temperature: float, top_k: int, top_p: float) -> int:
    """What ONE row's knobs ask of the sampler, as an index into
    ``SAMPLER_ARMS``, from Python numbers (the engine's own record of
    a request): 0 for a greedy row whatever its filters hold (its
    token is the argmax), 2 for a row that samples under a filter
    (``top_k > 0`` or ``0 < top_p < 1``), 1 for one that samples
    without."""
    if not temperature > 0.0:
        return 0
    return 2 if top_k > 0 or 0.0 < top_p < 1.0 else 1


def sampler_arm(temperature, top_k=None, top_p=None, live=None):
    """What a pool's LIVE rows ask of the sampler: the largest of
    their ``row_arm``s, on the device. ``live`` ([batch] bool, default
    all rows) masks rows whose knobs are stale: a retired slot keeps
    its last occupant's until readmission."""
    sampling = jnp.asarray(temperature, jnp.float32) > 0.0
    filters = jnp.zeros((), bool)
    if top_k is not None:
        filters |= jnp.asarray(top_k, jnp.int32) > 0
    if top_p is not None:
        p = jnp.asarray(top_p, jnp.float32)
        filters |= (p > 0.0) & (p < 1.0)
    arms = sampling * (1 + filters.astype(jnp.int32))
    if live is not None:
        arms = jnp.where(live, arms, 0)
    return jnp.max(arms)


def sample_logits(
    logits: jax.Array,
    key: jax.Array,
    temperature: jax.Array,
    top_k=None,
    top_p=None,
    live=None,
    fold=None,
) -> jax.Array:
    """Sample token ids from [batch, vocab] logits.

    Every sampling knob may be a traced scalar OR a per-row [batch]
    array — both filters are static-shape masks over one shared sorted
    copy of the logits, so arbitrary per-request values run in a single
    compiled program, and co-batched requests can each carry their own
    settings. A row whose temperature is <= 0 decodes greedily
    (argmax). top-k keeps the k highest logits (k <= 0 keeps all; ties
    at the k-th value all survive); nucleus keeps the smallest set of
    tokens whose probability mass reaches p (the top token always
    survives; p outside (0,1) keeps all). ``None`` disables a filter
    statically; with both ``None`` there is no sort in the program.

    With a filter given, the program holds three arms
    (``SAMPLER_ARMS``) and each call runs ONE of them for the whole
    batch, chosen on the device by ``sampler_arm`` from what the
    ``live`` rows ask for: the argmax alone (no sort, no softmax, no
    key, no noise) where none of them samples; the categorical draw
    without the sort where some sample and none of those filters; the
    sort of the whole vocabulary, the masks and the draw only where a
    live sampling row filters. A row's token does not depend on the
    arm: a greedy row gets ``argmax`` of the same float32 logits in
    all three, and a row without filters has nothing masked in the
    third (its threshold is its own minimum), so it draws there what
    it draws in the second.

    ``key`` is one PRNG key shared by the batch, or [batch] stacked
    per-row keys (``jax.random.split`` output) — per-row keys make each
    row's draw independent of what it is batched with. ``fold``
    ([batch] int) is folded into the per-row keys inside the arms that
    draw, so a call that draws nothing folds nothing.
    """
    b, vocab = logits.shape
    t = jnp.broadcast_to(
        jnp.asarray(temperature, jnp.float32), (b,)
    )[:, None]
    raw = logits.astype(jnp.float32)

    def draw(filtered: bool):
        x = raw / jnp.maximum(t, 1e-6)
        if filtered:
            x = _mask_filtered(x, top_k, top_p)
        keys = key
        if fold is not None:
            keys = jax.vmap(jax.random.fold_in)(
                key, jnp.broadcast_to(fold, (b,))
            )
        if keys.ndim > 1:  # stacked per-row keys
            sampled = jax.vmap(
                lambda k, row: jax.random.categorical(k, row)
            )(keys, x)
        else:
            sampled = jax.random.categorical(keys, x, axis=-1)
        return jnp.where(
            t[:, 0] <= 0.0, jnp.argmax(raw, axis=-1), sampled
        ).astype(jnp.int32)

    if top_k is None and top_p is None:
        return draw(False)
    arms = (
        lambda: jnp.argmax(raw, axis=-1).astype(jnp.int32),
        functools.partial(draw, False),
        functools.partial(draw, True),
    )
    return lax.switch(
        sampler_arm(temperature, top_k, top_p, live),
        [jax.named_scope(name)(arm)
         for name, arm in zip(SAMPLER_ARMS, arms)],
    )


def _mask_filtered(x: jax.Array, top_k, top_p) -> jax.Array:
    """NEG_INF every logit of [batch, vocab] ``x`` that its row's
    top-k / nucleus filter drops (``sample_logits`` has the rules):
    one descending sort of the whole vocabulary, both masks over it,
    and the smallest kept value as the row's threshold."""
    b, vocab = x.shape
    sorted_logits = jnp.sort(x, axis=-1)[:, ::-1]
    keep = jnp.ones(sorted_logits.shape, bool)
    if top_k is not None:
        k = jnp.broadcast_to(
            jnp.asarray(top_k, jnp.int32), (b,)
        )[:, None]
        k = jnp.where(k > 0, k, vocab)
        keep &= jnp.arange(vocab)[None, :] < k
    if top_p is not None:
        p = jnp.broadcast_to(
            jnp.asarray(top_p, jnp.float32), (b,)
        )[:, None]
        # off is +inf, not 1.0: a float32 cumulative sum can pass 1
        # before the row's end, and a row that asks for no filter must
        # have NOTHING masked, as in the arm that does not sort
        p = jnp.where((p > 0.0) & (p < 1.0), p, jnp.inf)
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        keep &= (jnp.cumsum(probs, axis=-1) - probs) < p
    threshold = jnp.min(
        jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(x < threshold, NEG_INF, x)


def mask_eos_before_min(
    logits: jax.Array, step_idx, min_new, eos_id
) -> jax.Array:
    """NEG_INF the eos logit for rows still under their min_new
    floor — sample i honors `min_new_tokens` by construction on every
    decode path (sampled AND greedy draw from the same masked logits).
    eos_id < 0 (disabled) indexes nothing thanks to the suppress
    gate."""
    b, vocab = logits.shape
    eos_row = jnp.broadcast_to(jnp.asarray(eos_id, jnp.int32), (b,))
    min_row = jnp.broadcast_to(jnp.asarray(min_new, jnp.int32), (b,))
    suppress = (step_idx < min_row) & (eos_row >= 0)
    eos_onehot = (
        jnp.arange(vocab)[None, :] == jnp.clip(eos_row, 0)[:, None]
    )
    return jnp.where(
        suppress[:, None] & eos_onehot, NEG_INF, logits
    )


def apply_token_penalties(
    logits: jax.Array,
    counts: jax.Array,
    presence_penalty,
    frequency_penalty,
) -> jax.Array:
    """OpenAI-style repetition control over the GENERATED tokens so
    far (counts: [batch, vocab]): logit -= presence * (count > 0)
    + frequency * count. Generated-only (not the prompt) keeps ONE
    semantic on every decode path — the slot engine and the
    prefix-cache path have no prompt in scope at sampling time. Both
    penalties 0 leave logits bitwise-unchanged."""
    b = logits.shape[0]
    pres = jnp.broadcast_to(
        jnp.asarray(presence_penalty, jnp.float32), (b,)
    )[:, None]
    freq = jnp.broadcast_to(
        jnp.asarray(frequency_penalty, jnp.float32), (b,)
    )[:, None]
    return logits - pres * (counts > 0) - freq * counts


BIAS_SLOTS = 16  # fast-path static per-row logit_bias capacity:
# almost every real request carries a handful of entries, and a
# static K keeps ONE compiled program for all of them
BIAS_SLOTS_MAX = 300  # OpenAI's documented logit_bias cap; a request
# with more than BIAS_SLOTS entries selects this wider static table
# at normalize time (one extra program keyed by the operand shape)
# instead of being rejected


def apply_logit_bias(
    logits: jax.Array, bias_idx: jax.Array, bias_val: jax.Array
) -> jax.Array:
    """OpenAI-style logit_bias: add ``bias_val[b, j]`` to token
    ``bias_idx[b, j]``'s logit before temperature/filters. Sparse and
    static-shape: idx/val are [batch, K] with -1 marking unused slots,
    so arbitrary per-request bias sets run in one compiled program.
    Applied BEFORE the min_new eos mask, so a positive eos bias can
    never break the min_new_tokens floor."""
    b, vocab = logits.shape
    valid = bias_idx >= 0
    idx = jnp.where(valid, bias_idx, 0)
    add = jnp.zeros_like(logits, shape=(b, vocab)).at[
        jnp.arange(b)[:, None], idx
    ].add(jnp.where(valid, bias_val, 0.0).astype(logits.dtype))
    return logits + add


def count_token(
    counts: jax.Array, token: jax.Array, alive
) -> jax.Array:
    """counts[b, token[b]] += 1 for rows still alive (a done row's
    pad filler must not be penalized)."""
    b, vocab = counts.shape
    onehot = (
        jnp.arange(vocab)[None, :] == token[:, None]
    ).astype(counts.dtype)
    return counts + onehot * jnp.asarray(alive, counts.dtype)[:, None]


def seed_counts_row(vocab_size: int, first, eos_id) -> jax.Array:
    """The generated-token counts row right after sample 0 — the
    just-drawn token counts once unless it ended the row, matching
    generate's scan exactly. Lives here with count_token so the whole
    penalty-counts convention has one home; runs INSIDE the slot
    admission program (traceable), so seeding costs no host round
    trip."""
    row = jnp.zeros((vocab_size,), jnp.float32)
    return row.at[first].set(
        jnp.where(first == eos_id, 0.0, 1.0)
    )


def _sampling_scan(cfg, max_new_tokens: int, greedy: bool,
                   filtered: bool, penalized: bool = False,
                   biased: bool = False):
    """The shared decode loop: from (cache, next-token logits) sample
    max_new_tokens with eos/pad handling. Used by the prefill-fused
    generate program and the prefix-cache extend path.

    ``penalized``/``biased`` are static compile-key flags (like
    greedy/filtered): only requests that actually set
    presence/frequency penalties pay the [batch, vocab] counts carry,
    and only requests carrying a logit_bias pay the per-step
    scatter-add — the common plain program is unchanged."""

    def scan(params, cache, logits, row_keys, temperature, top_k,
             top_p, eos_id, pad_id, min_new, presence, frequency,
             bias_idx, bias_val):
        @jax.named_scope("sample")
        def sample(logits, step_idx, counts):
            if penalized:
                logits = apply_token_penalties(
                    logits, counts, presence, frequency
                )
            if biased:
                logits = apply_logit_bias(logits, bias_idx, bias_val)
            logits = mask_eos_before_min(
                logits, step_idx, min_new, eos_id
            )
            if greedy:
                return jnp.argmax(logits, axis=-1)
            keys = jax.vmap(
                lambda k: jax.random.fold_in(k, step_idx)
            )(row_keys)
            return sample_logits(
                logits, keys, temperature,
                top_k if filtered else None,
                top_p if filtered else None,
            )

        counts = (
            jnp.zeros(logits.shape, jnp.float32) if penalized else None
        )
        first = sample(logits, jnp.int32(0), counts).astype(jnp.int32)
        # rows that have emitted eos keep decoding (static shapes) but
        # emit pad from then on; eos_id == -1 disables the early stop
        # dynamically (token ids are non-negative, so it never matches)
        done = first == eos_id
        if penalized:
            counts = count_token(counts, first, ~done)

        def step(carry, step_idx):
            if penalized:
                cache, token, done, counts = carry
            else:
                cache, token, done = carry
                counts = None
            logits, cache = decode_step(params, cache, token, cfg)
            next_token = sample(
                logits, step_idx, counts
            ).astype(jnp.int32)
            next_token = jnp.where(done, pad_id, next_token)
            done = done | (next_token == eos_id)
            if penalized:
                counts = count_token(counts, next_token, ~done)
                return (cache, next_token, done, counts), next_token
            return (cache, next_token, done), next_token

        init = (
            (cache, first, done, counts) if penalized
            else (cache, first, done)
        )
        _final, rest = lax.scan(
            step, init, jnp.arange(1, max_new_tokens, dtype=jnp.int32),
        )
        return jnp.concatenate([first[:, None], rest.T], axis=1)

    return scan


@functools.lru_cache(maxsize=32)
def _jitted_generate(cfg: TransformerConfig, max_new_tokens: int,
                     max_len: int, greedy: bool, filtered: bool,
                     penalized: bool = False, biased: bool = False):
    """One compiled program per (config, lengths, sampling mode); jit's
    own cache covers distinct prompt lengths and batch sizes.
    Everything request-controlled that doesn't change shapes
    (temperature, top_k, top_p, eos_id, pad_id — all per-row arrays)
    is a traced operand, so per-request variation can't churn this
    cache, and co-batched requests keep independent settings. Each row
    samples from its own key (fold_in per step), so a row's output
    never depends on what it was batched with."""
    scan = _sampling_scan(cfg, max_new_tokens, greedy, filtered,
                          penalized, biased)

    def fn(params, prompt, row_keys, temperature, top_k, top_p, eos_id,
           pad_id, min_new, presence, frequency, bias_idx, bias_val):
        logits, cache = prefill(params, prompt, cfg, max_len)
        return scan(params, cache, logits, row_keys, temperature,
                    top_k, top_p, eos_id, pad_id, min_new, presence,
                    frequency, bias_idx, bias_val)

    return jax.jit(fn)


@functools.lru_cache(maxsize=8)
def _jitted_prefill(cfg: TransformerConfig, max_len: int):
    """Standalone jitted prefill returning (last logits, cache) — the
    prefix-cache entry point (generate's fused program never exposes
    its cache)."""
    return jax.jit(lambda p, t: prefill(p, t, cfg, max_len))


@functools.lru_cache(maxsize=8)
def _jitted_extend(cfg: TransformerConfig):
    """Jitted cache extension: consume a token chunk against a cache
    (decode_chunk) and return (last logits, cache). jit re-specializes
    per chunk length; serving buckets those."""

    def fn(params, cache, chunk):
        logits, cache = decode_chunk(params, cache, chunk, cfg)
        return logits[:, -1, :], cache

    return jax.jit(fn)


@functools.lru_cache(maxsize=32)
def _jitted_decode_from_cache(cfg: TransformerConfig,
                              max_new_tokens: int, greedy: bool,
                              filtered: bool, penalized: bool = False,
                              biased: bool = False):
    return jax.jit(
        _sampling_scan(cfg, max_new_tokens, greedy, filtered,
                       penalized, biased)
    )


def generate(
    params: Params,
    prompt: jax.Array,
    cfg: TransformerConfig,
    max_new_tokens: int,
    max_len: int,
    temperature=0.0,
    rng: jax.Array = None,
    top_k=0,
    top_p=0.0,
    eos_id=-1,
    pad_id=0,
    min_new_tokens=0,
    presence_penalty=0.0,
    frequency_penalty=0.0,
    logit_bias=None,
) -> jax.Array:
    """Autoregressive generation. prompt: [batch, prompt_len] int32;
    returns [batch, max_new_tokens] int32.

    Every sampling knob accepts a scalar or a per-row [batch] sequence
    (so a serving batcher can coalesce requests with different
    settings). ``top_k``/``top_p`` filter the sampling distribution
    (0 disables either; both compose). A row with temperature <= 0
    decodes greedily. ``eos_id >= 0`` enables early stop: once a row
    samples eos, the rest of that row is ``pad_id``;
    ``min_new_tokens`` suppresses eos for a row's first N samples so
    short answers can be floored. ``presence_penalty`` /
    ``frequency_penalty`` subtract from the logits of tokens already
    GENERATED this call (OpenAI semantics over the output, prompt
    excluded — one semantic across every decode path).
    ``logit_bias`` adds per-token offsets to the logits before
    temperature/filters (OpenAI semantics: -100 effectively bans a
    token, +100 effectively forces it) — one ``{token_id: bias}``
    dict for the whole batch or a per-row list of dicts, at most
    BIAS_SLOTS_MAX (= OpenAI's 300) entries per row — rows within
    BIAS_SLOTS ride the fast-path program; applied before the min_new
    eos mask
    so a positive eos bias cannot break the floor. ``rng`` is one
    key (split per row internally) or [batch] stacked per-row keys —
    per-row keys keep each row's output independent of co-batched
    rows.
    """
    operands = _normalize_sampling(
        cfg, prompt.shape[0], max_new_tokens, temperature, rng, top_k,
        top_p, eos_id, pad_id, min_new_tokens, presence_penalty,
        frequency_penalty, logit_bias,
    )
    if prompt.shape[1] + max_new_tokens > max_len:
        # an overflowing decode would silently clamp cache writes onto
        # the last slot and return garbage — fail loudly instead
        raise ValueError(
            f"prompt_len {prompt.shape[1]} + max_new_tokens "
            f"{max_new_tokens} exceeds max_len {max_len}"
        )
    greedy, filtered, penalized, biased, op_arrays = operands
    fn = _jitted_generate(
        cfg, max_new_tokens, max_len, greedy, filtered, penalized,
        biased,
    )
    return fn(params, prompt, *op_arrays)


def _normalize_sampling(cfg, b, max_new_tokens, temperature, rng,
                        top_k, top_p, eos_id, pad_id,
                        min_new_tokens=0, presence_penalty=0.0,
                        frequency_penalty=0.0, logit_bias=None):
    """Validate/broadcast the per-row sampling knobs exactly as
    ``generate`` documents; returns (greedy, filtered, penalized,
    biased, operand arrays in _sampling_scan order after the
    cache/logits)."""
    import numpy as np

    def row(v, dtype, name):
        arr = np.asarray(jax.device_get(v), dtype)
        if arr.ndim == 0:
            arr = np.full((b,), arr)
        if arr.shape != (b,):
            raise ValueError(f"{name} must be a scalar or [batch] array")
        return arr

    t = row(temperature, np.float32, "temperature")
    k_arr = row(top_k, np.int64, "top_k")
    p_arr = row(top_p, np.float64, "top_p")
    eos_arr = row(eos_id, np.int64, "eos_id")
    pad_arr = row(pad_id, np.int64, "pad_id")
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    if (
        (k_arr < 0).any() or (k_arr > cfg.vocab_size).any()
        or (p_arr < 0.0).any() or (p_arr > 1.0).any()
    ):
        raise ValueError(
            f"top_k must be in [0, vocab {cfg.vocab_size}] and "
            "top_p in [0, 1]"
        )
    if (eos_arr >= cfg.vocab_size).any() or (
        (pad_arr < 0) | (pad_arr >= cfg.vocab_size)
    ).any():
        raise ValueError(
            f"eos_id (< 0 disables) and pad_id must be < vocab "
            f"{cfg.vocab_size}, pad_id non-negative"
        )
    if rng is None:
        rng = jax.random.PRNGKey(0)
    row_keys = rng if rng.ndim > 1 else jax.random.split(rng, b)
    if row_keys.shape[0] != b:
        raise ValueError(f"rng must be one key or {b} stacked keys")
    min_arr = row(min_new_tokens, np.int64, "min_new_tokens")
    if (min_arr < 0).any() or (min_arr > max_new_tokens).any():
        raise ValueError(
            f"min_new_tokens must be in [0, max_new_tokens "
            f"{max_new_tokens}]"
        )
    pres_arr = row(presence_penalty, np.float32, "presence_penalty")
    freq_arr = row(frequency_penalty, np.float32, "frequency_penalty")
    if (np.abs(pres_arr) > 100).any() or (np.abs(freq_arr) > 100).any():
        raise ValueError(
            "presence/frequency penalties must be in [-100, 100]"
        )
    bias_idx, bias_val = normalize_logit_bias(cfg, b, logit_bias)
    greedy = bool((t <= 0.0).all())
    if greedy:
        # dead under argmax; normalize so the compile key can't churn
        k_arr = np.zeros_like(k_arr)
        p_arr = np.zeros_like(p_arr)
    filtered = bool(
        ((k_arr > 0) | ((p_arr > 0.0) & (p_arr < 1.0))).any()
    )
    penalized = bool(pres_arr.any() or freq_arr.any())
    biased = bool((bias_idx >= 0).any())
    return greedy, filtered, penalized, biased, (
        row_keys,
        jnp.asarray(t, jnp.float32), jnp.asarray(k_arr, jnp.int32),
        jnp.asarray(p_arr, jnp.float32),
        jnp.asarray(np.maximum(eos_arr, -1), jnp.int32),
        jnp.asarray(pad_arr, jnp.int32),
        jnp.asarray(min_arr, jnp.int32),
        jnp.asarray(pres_arr, jnp.float32),
        jnp.asarray(freq_arr, jnp.float32),
        jnp.asarray(bias_idx, jnp.int32),
        jnp.asarray(bias_val, jnp.float32),
    )


def normalize_logit_bias(cfg, b: int, logit_bias, slots: int = None):
    """[b, K] (idx, val) arrays from None, one {token: bias} dict
    applied to every row, or a per-row list of such dicts (None
    entries allowed). Unused slots carry idx -1. Validates ids, |bias|
    <= 100 (OpenAI's range), and the per-row entry cap
    (BIAS_SLOTS_MAX = OpenAI's 300).

    ``slots`` pins the static capacity K (fixed-width callers: the
    slot engine and the pod payload). When None, K is chosen per
    request: BIAS_SLOTS while every row fits it (the common fast
    path keeps its one compiled program), else BIAS_SLOTS_MAX — the
    operand shape keys the one extra program big requests compile."""
    import numpy as np

    # parse/validate FIRST so capacity can be picked from the real
    # row sizes; int-coerce keys BEFORE sorting (a dict mixing int
    # and str ids — str is OpenAI's JSON wire form — must fail the
    # documented ValueError way, not a raw TypeError from sorted)
    rows = []
    if logit_bias is not None:
        raw_rows = (
            logit_bias if isinstance(logit_bias, (list, tuple))
            else [logit_bias] * b
        )
        if len(raw_rows) != b:
            raise ValueError(f"logit_bias must be one dict or {b} rows")
        for entry in raw_rows:
            if entry is None:
                rows.append([])
                continue
            if not isinstance(entry, dict):
                raise ValueError("logit_bias rows must be dicts or None")
            try:
                # dict-dedup AFTER coercion (last wins, matching
                # parse_logit_bias): {"5": 100, 5: 100} must not
                # occupy two slots whose scatter-adds SUM past the
                # validated per-entry +/-100 bound
                items = sorted(
                    {int(t): float(v) for t, v in entry.items()}
                    .items()
                )
            except (TypeError, ValueError):
                raise ValueError(
                    "logit_bias keys must be token ids and values "
                    "numbers"
                ) from None
            for tok, bias in items:
                if not 0 <= tok < cfg.vocab_size:
                    raise ValueError(
                        f"logit_bias token ids must be in "
                        f"[0, {cfg.vocab_size})"
                    )
                if not abs(bias) <= 100:
                    raise ValueError(
                        "logit_bias values must be in [-100, 100]"
                    )
            rows.append(items)
    need = max((len(r) for r in rows), default=0)
    if slots is None:
        slots = BIAS_SLOTS if need <= BIAS_SLOTS else BIAS_SLOTS_MAX
    if need > slots:
        raise ValueError(
            f"logit_bias is capped at {slots} tokens per row"
        )
    idx = np.full((b, slots), -1, np.int32)
    val = np.zeros((b, slots), np.float32)
    for r, items in enumerate(rows):
        for j, (tok, bias) in enumerate(items):
            idx[r, j] = tok
            val[r, j] = bias
    return idx, val


def generate_from_cache(
    params: Params,
    cache: Cache,
    logits: jax.Array,
    cfg: TransformerConfig,
    max_new_tokens: int,
    temperature=0.0,
    rng: jax.Array = None,
    top_k=0,
    top_p=0.0,
    eos_id=-1,
    pad_id=0,
    pos: int = None,
    min_new_tokens=0,
    presence_penalty=0.0,
    frequency_penalty=0.0,
    logit_bias=None,
) -> jax.Array:
    """``generate`` starting from an existing (cache, next-token
    logits) pair — the prefix-cache serving path: the caller restored
    or extended a cached prompt prefix (prefill/_jitted_extend) and
    only the new tokens decode here. Same sampling contract as
    ``generate``.

    ``pos`` is the host-known value of cache['pos'] (tokens already
    cached); pass it to get the same loud overflow check ``generate``
    does without a device fetch. When omitted, the scalar is fetched —
    correctness over latency."""
    length = cache["k"].shape[2]
    if cfg.window <= 0 or length < cfg.window:
        # a FULL ring cache (length == window) legally decodes past
        # its length: positions wrap by design and every overwritten
        # slot is already outside the attention window. A linear cache
        # overflows, and so does a TRUNCATED ring (window > max_len at
        # init_cache shrinks the ring to max_len slots): wrapping there
        # overwrites keys still inside the window — in-window context
        # silently dropped.
        if pos is None:
            pos = int(jax.device_get(cache["pos"]))
        if pos + max_new_tokens > length:
            # an overflowing decode would silently clamp cache writes
            # onto the last slot and return garbage — same contract as
            # generate
            raise ValueError(
                f"cache pos {pos} + max_new_tokens {max_new_tokens} "
                f"exceeds cache length {length}"
            )
    greedy, filtered, penalized, biased, op_arrays = (
        _normalize_sampling(
            cfg, logits.shape[0], max_new_tokens, temperature, rng,
            top_k, top_p, eos_id, pad_id, min_new_tokens,
            presence_penalty, frequency_penalty, logit_bias,
        )
    )
    fn = _jitted_decode_from_cache(
        cfg, max_new_tokens, greedy, filtered, penalized, biased
    )
    return fn(params, cache, logits, *op_arrays)
