"""decode_chunk's grouped-query contraction (models/decode.py
_grouped_attention) against the plain formula written out here:
explicit jnp.repeat of keys and values to n_heads, float32 einsum,
float32 softmax. The program contracts the cache as stored (no head
repeat, no float32 copy of the keys, the values widened only as the
contraction's operand); the plain formula builds every copy, so the
two share nothing but the projections around them.

Also pinned: head h reads kv head h // group, and the slot engine's
chunk program lowers without the two tensors the rewrite removed (the
cache repeated to n_heads, the cache widened to float32) under the
module name the benchmark's trace reader looks for.
"""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from containerpilot_tpu.models.decode import (
    _grouped_attention,
    _kv_dequant,
    _kv_quant,
    _logits,
    decode_chunk,
    prefill,
)
from containerpilot_tpu.models.quantized import embed_lookup
from containerpilot_tpu.models.slots import (
    _jitted_chunk,
    init_slot_state,
    slot_cache,
)
from containerpilot_tpu.models.transformer import (
    TransformerConfig,
    _attn_out,
    _mlp,
    _qkv,
    init_params,
)

HEADS = [(4, 4), (4, 2), (8, 2), (4, 1)]
PROMPT, MAX_LEN, WINDOW = 11, 24, 8
NEG = -1e30


def _plain_attention(q, keys, values, valid):
    """The formula the program used to run: every kv head repeated to
    its group of query heads, everything widened to float32."""
    n_heads = q.shape[2]
    group = n_heads // keys.shape[2]
    k_full = jnp.repeat(keys, group, axis=2).astype(jnp.float32)
    v_full = jnp.repeat(values, group, axis=2).astype(jnp.float32)
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k_full,
        precision="highest",
    ) * q.shape[-1] ** -0.5
    scores = jnp.where(valid[None, None], scores, NEG)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", weights, v_full, precision="highest"
    )


def _plain_decode_chunk(params, cache, tokens, cfg):
    """decode_chunk's logits by a python loop over layers: the cache's
    entries (dequantized where int8) followed by the chunk's own keys,
    each key with its absolute position, masked by position alone."""
    pos = int(cache["pos"])
    b, m = tokens.shape
    length = cache["k"].shape[2]
    if cfg.window > 0:  # ring: slot j holds the newest p < pos, p % length == j
        key_pos = np.array([
            max([p for p in range(pos) if p % length == j], default=-1)
            for j in range(length)
        ])
    else:  # linear: slot j holds position j, written only below pos
        key_pos = np.where(np.arange(length) < pos, np.arange(length), -1)
    key_pos = np.concatenate([key_pos, pos + np.arange(m)])
    q_pos = pos + np.arange(m)
    valid = (key_pos[None, :] >= 0) & (key_pos[None, :] <= q_pos[:, None])
    if cfg.window > 0:
        valid &= key_pos[None, :] > q_pos[:, None] - cfg.window
    valid = jnp.asarray(valid)
    x = embed_lookup(params, tokens, cfg.dtype)
    for layer in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[layer], params["layers"])
        q, k, v = _qkv(x, lp, cfg, offset=pos)
        cached_k, cached_v = cache["k"][layer], cache["v"][layer]
        if cfg.kv_int8:  # the chunk's keys are read back quantized too
            cached_k = _kv_dequant(cached_k, cache["k_scale"][layer], cfg.dtype)
            cached_v = _kv_dequant(cached_v, cache["v_scale"][layer], cfg.dtype)
            k = _kv_dequant(*_kv_quant(k), cfg.dtype)
            v = _kv_dequant(*_kv_quant(v), cfg.dtype)
        attn = _plain_attention(
            q, jnp.concatenate([cached_k, k], axis=1),
            jnp.concatenate([cached_v, v], axis=1), valid,
        ).astype(cfg.dtype)
        x = _attn_out(x, attn, lp, cfg)
        x = _mlp(x, lp, cfg)
    return _logits(params, x, cfg)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("mode", ["linear", "ring", "kv_int8"])
@pytest.mark.parametrize("m", [1, 5])
@pytest.mark.parametrize("n_heads,kv_heads", HEADS)
def test_decode_chunk_matches_plain_formula(n_heads, kv_heads, m, mode, dtype):
    cfg = TransformerConfig(
        vocab_size=64, d_model=64, n_heads=n_heads, n_kv_heads=kv_heads,
        n_layers=2, d_ff=128, max_seq_len=32, dtype=dtype, flash_min_seq=0,
        window=WINDOW if mode == "ring" else 0, kv_int8=mode == "kv_int8",
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, PROMPT + m), 0, cfg.vocab_size, jnp.int32
    )
    _, cache = prefill(params, tokens[:, :PROMPT], cfg, MAX_LEN)
    assert cache["k"].shape[2:] == (
        WINDOW if mode == "ring" else MAX_LEN, kv_heads, cfg.head_dim
    )
    want = _plain_decode_chunk(params, cache, tokens[:, PROMPT:], cfg)
    got, new_cache = decode_chunk(params, cache, tokens[:, PROMPT:], cfg)
    assert got.shape == (2, m, cfg.vocab_size)
    assert int(new_cache["pos"]) == PROMPT + m
    # float32: the tolerance of the incremental-decode parity tests.
    # bf16: the int8-KV parity test's; what differs is not attention
    # (equal to one bf16 rounding, next test) but where the compiled
    # scan and this eager loop round the projections' activations
    tol = 2e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("m", [1, 5])
@pytest.mark.parametrize("n_heads,kv_heads", HEADS)
def test_grouped_attention_matches_plain_formula(n_heads, kv_heads, m, dtype):
    """The contraction alone, random inputs under a random mask: equal
    to the plain formula to one rounding of the output dtype."""
    b, length, d = 2, 24, 16
    keys = jax.random.split(jax.random.PRNGKey(4), 4)
    q = jax.random.normal(keys[0], (b, m, n_heads, d), dtype)
    k = jax.random.normal(keys[1], (b, length, kv_heads, d), dtype)
    v = jax.random.normal(keys[2], (b, length, kv_heads, d), dtype)
    valid = jax.random.bernoulli(keys[3], 0.7, (m, length)).at[:, 0].set(True)
    got = _grouped_attention(q, k, v, valid, dtype)
    want = _plain_attention(q, k, v, valid)
    tol = 1e-5 if dtype == jnp.float32 else 2 ** -7
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), rtol=tol, atol=tol
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("n_heads,kv_heads", HEADS)
def test_head_h_reads_kv_head_h_over_group(n_heads, kv_heads, dtype):
    """Values: kv head j holds the constant j + 1, so whatever the
    weights, head h must come out as h // group + 1. Keys: kv head j
    is large at position j alone while every head's values hold
    position + 1, so head h must come out as h // group + 1 again."""
    b, m, length, d = 2, 3, 8, 16
    group = n_heads // kv_heads
    want = np.broadcast_to(
        (np.arange(n_heads) // group + 1.0)[None, None, :, None],
        (b, m, n_heads, d),
    )
    valid = jnp.ones((m, length), bool)
    q = jax.random.normal(jax.random.PRNGKey(2), (b, m, n_heads, d), dtype)
    keys = jax.random.normal(
        jax.random.PRNGKey(3), (b, length, kv_heads, d), dtype
    )
    head_const = jnp.broadcast_to(
        (jnp.arange(kv_heads) + 1.0)[None, None, :, None],
        (b, length, kv_heads, d),
    ).astype(dtype)
    got = _grouped_attention(q, keys, head_const, valid, dtype)
    assert got.dtype == dtype and got.shape == (b, m, n_heads, d)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), want, rtol=1e-2, atol=0
    )

    peaked = jnp.zeros((b, length, kv_heads, d), dtype)
    for j in range(kv_heads):
        peaked = peaked.at[:, j, j, :].set(8.0)
    pos_const = jnp.broadcast_to(
        (jnp.arange(length) + 1.0)[None, :, None, None],
        (b, length, kv_heads, d),
    ).astype(dtype)
    got = _grouped_attention(
        jnp.ones((b, m, n_heads, d), dtype), peaked, pos_const, valid, dtype
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), want, rtol=1e-2, atol=0
    )


def _cache_like(text, length, head_dim):
    """(element count, element type, dims) of every tensor type in
    StableHLO text that holds the cache's length and head_dim."""
    out = []
    for dims, elem in re.findall(r"tensor<((?:\d+x)+)(\w+)>", text):
        dims = [int(n) for n in dims.split("x") if n]
        if length in dims and head_dim in dims:
            out.append((math.prod(dims), elem, dims))
    return out


@pytest.mark.parametrize("kv_int8", [False, True], ids=["bf16kv", "int8kv"])
def test_chunk_program_holds_no_repeated_or_widened_cache(kv_int8):
    """The slot engine's chunk program for a bf16 GQA configuration:
    no tensor with the cache's length and head_dim that is as large
    as the cache repeated to n_heads, and ONE float32 tensor a layer
    (the layers are unrolled) as large as the layer's cache: the
    values as the float32 softmax weights' operand, which jax writes as a convert before the contraction and
    the chip's compiler folds into it (tests/test_tpu_compile.py pins
    that no float32 copy is left in the optimised program); the keys
    are never widened. int8 KV dequantizes through float32 by design,
    so only the repeat is looked for there. Module still `jit_run`."""
    slots, chunk, length = 3, 2, 48
    cfg = TransformerConfig(
        vocab_size=64, d_model=128, n_heads=8, n_kv_heads=2, n_layers=2,
        d_ff=256, max_seq_len=length, kv_int8=kv_int8,
    )
    assert cfg.dtype == jnp.bfloat16 and cfg.head_dim == 16
    shapes = jax.eval_shape(
        lambda: (
            init_params(jax.random.PRNGKey(0), cfg),
            slot_cache(cfg, slots, length),
            init_slot_state(cfg, slots),
        )
    )
    text = _jitted_chunk(cfg, slots, chunk).lower(*shapes).as_text()
    assert re.search(r"module @jit_run\b", text), text[:200]
    layer_cache = slots * length * cfg.kv_heads * cfg.head_dim
    repeated = layer_cache * (cfg.n_heads // cfg.kv_heads)
    cache_like = _cache_like(text, length, cfg.head_dim)
    assert cache_like, "the pool is not in the program's text"
    for size, elem, dims in cache_like:
        assert size < repeated, f"cache repeated to n_heads: {dims} {elem}"
    if not kv_int8:
        widened = re.findall(
            r"stablehlo\.convert[^\n]*-> tensor<((?:\d+x)+)f32>", text
        )
        widened = [
            dims for dims in widened
            if math.prod(int(n) for n in dims.split("x") if n) >= layer_cache
            and str(length) in dims.split("x")
        ]
        assert len(widened) == cfg.n_layers, (
            f"float32 copies of the cache: {widened}"
        )


def test_chunk_program_check_sees_the_old_form():
    """The same scan of a program written the old way finds both
    tensors, so the test above cannot pass by looking past them."""
    b, length, kv_heads, group, d = 3, 48, 2, 4, 16

    def old(q, keys):
        k_full = jnp.repeat(keys, group, axis=2).astype(jnp.float32)
        return jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k_full)

    text = jax.jit(old).lower(
        jax.ShapeDtypeStruct((b, 1, kv_heads * group, d), jnp.bfloat16),
        jax.ShapeDtypeStruct((b, length, kv_heads, d), jnp.bfloat16),
    ).as_text()
    layer_cache = b * length * kv_heads * d
    sizes = _cache_like(text, length, d)
    assert any(size >= layer_cache * group for size, _, _ in sizes)
    assert any(
        elem == "f32" and size >= layer_cache for size, elem, _ in sizes
    )
