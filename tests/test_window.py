"""Sliding-window attention: masks, flash kernels, ring KV cache.

Mistral-style local attention (TransformerConfig.window): position i
attends j iff i - window < j <= i. The decode cache becomes a ring of
`window` slots, so KV memory is bounded by the window, not the
generation length. No reference analog (the reference is a supervisor,
SURVEY.md §2); this is workload-half model-family coverage.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from containerpilot_tpu.models.decode import (
    decode_chunk,
    decode_step,
    generate,
    init_cache,
    prefill,
)
from containerpilot_tpu.models.transformer import (
    TransformerConfig,
    forward,
    init_params,
)
from containerpilot_tpu.ops.attention import causal_attention
from containerpilot_tpu.ops.flash import flash_attention


def _cfg(window, **kw):
    base = dict(
        vocab_size=128, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_seq_len=256, dtype=jnp.float32, flash_min_seq=0,
        window=window,
    )
    base.update(kw)
    return TransformerConfig(**base)


def test_windowed_mask_matches_bruteforce():
    """causal_attention(window=W) == explicit mask reference."""
    rng = jax.random.PRNGKey(0)
    q, k, v = (
        jax.random.normal(kk, (2, 48, 4, 16), jnp.float32)
        for kk in jax.random.split(rng, 3)
    )
    W = 16
    got = causal_attention(q, k, v, window=W)
    s = q.shape[1]
    idx = np.arange(s)
    mask = (idx[None, :] <= idx[:, None]) & (idx[None, :] > idx[:, None] - W)
    scores = np.einsum("bqhk,bshk->bhqs", np.asarray(q), np.asarray(k))
    scores = scores * (16 ** -0.5)
    scores = np.where(mask[None, None], scores, -1e30)
    w = np.exp(scores - scores.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    ref = np.einsum("bhqs,bshk->bqhk", w, np.asarray(v))
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-4, atol=1e-5)


def test_window_geq_seq_equals_full():
    rng = jax.random.PRNGKey(1)
    q, k, v = (
        jax.random.normal(kk, (1, 32, 2, 16), jnp.float32)
        for kk in jax.random.split(rng, 3)
    )
    full = causal_attention(q, k, v)
    win = causal_attention(q, k, v, window=32)
    np.testing.assert_allclose(
        np.asarray(full), np.asarray(win), rtol=0, atol=0
    )


def test_windowed_flash_matches_xla_fwd_and_grads():
    """The pallas kernels' block-skip + in-block window mask agree with
    the einsum path for value and all three gradients, including
    mismatched block sizes and a window that skips whole blocks."""
    rng = jax.random.PRNGKey(2)
    q, k, v = (
        jax.random.normal(kk, (2, 512, 4, 64), jnp.float32)
        for kk in jax.random.split(rng, 3)
    )
    W = 128
    ref = causal_attention(q, k, v, window=W)
    got = flash_attention(q, k, v, block_q=128, block_k=64, window=W)
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(got), rtol=1e-4, atol=1e-5
    )
    for argi in range(3):
        def lf(x, fn, argi=argi):
            args = [q, k, v]
            args[argi] = x
            return (fn(*args) ** 2).sum()

        ga = jax.grad(
            lambda x: lf(x, lambda *a: causal_attention(*a, window=W))
        )([q, k, v][argi])
        gb = jax.grad(
            lambda x: lf(x, lambda *a: flash_attention(*a, window=W))
        )([q, k, v][argi])
        np.testing.assert_allclose(
            np.asarray(ga), np.asarray(gb), rtol=2e-4, atol=2e-4
        )


def test_windowed_forward_trains():
    """Training through the windowed model: finite loss, finite grads,
    and the windowed forward differs from full attention once seq >
    window (the mask is actually live)."""
    from containerpilot_tpu.models.transformer import loss_fn

    cfg = _cfg(window=8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 33), 0, cfg.vocab_size, jnp.int32
    )
    loss, grads = jax.value_and_grad(loss_fn)(params, tokens, cfg)
    assert np.isfinite(float(loss))
    for g in jax.tree_util.tree_leaves(grads):
        assert np.isfinite(np.asarray(g)).all()
    full = forward(params, tokens[:, :-1], _cfg(window=0))
    win = forward(params, tokens[:, :-1], cfg)
    assert not np.allclose(np.asarray(full), np.asarray(win))


@pytest.mark.parametrize("prompt_len", [4, 24])
def test_windowed_incremental_decode_matches_forward(prompt_len):
    """Ring-cache decode == windowed full forward at every position,
    with the prompt shorter AND longer than the window, decoding far
    enough that the ring wraps several times."""
    cfg = _cfg(window=8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    b, total = 2, 40
    tokens = jax.random.randint(
        jax.random.PRNGKey(3), (b, total), 0, cfg.vocab_size, jnp.int32
    )
    ref_logits = forward(params, tokens, cfg)  # [b, total, vocab]

    logits, cache = prefill(params, tokens[:, :prompt_len], cfg, total)
    assert cache["k"].shape[2] == 8  # ring, not max_len
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits[:, prompt_len - 1]),
        rtol=2e-3, atol=2e-3,
    )
    for i in range(prompt_len, total):
        logits, cache = decode_step(params, cache, tokens[:, i], cfg)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(ref_logits[:, i]),
            rtol=2e-3, atol=2e-3,
            err_msg=f"position {i}",
        )


def test_windowed_decode_chunk_matches_steps():
    """Multi-token chunks through the ring equal single steps."""
    cfg = _cfg(window=8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(4), (2, 30), 0, cfg.vocab_size, jnp.int32
    )
    _, cache_a = prefill(params, tokens[:, :6], cfg, 64)
    _, cache_b = prefill(params, tokens[:, :6], cfg, 64)
    # chunk of 5 (crosses the ring boundary at pos 6+5 > 8)
    chunk = tokens[:, 6:11]
    logits_a, cache_a = decode_chunk(params, cache_a, chunk, cfg)
    for i in range(5):
        logits_b, cache_b = decode_step(
            params, cache_b, chunk[:, i], cfg
        )
        np.testing.assert_allclose(
            np.asarray(logits_a[:, i]), np.asarray(logits_b),
            rtol=2e-3, atol=2e-3, err_msg=f"chunk index {i}",
        )
    np.testing.assert_allclose(
        np.asarray(cache_a["k"]), np.asarray(cache_b["k"]),
        rtol=1e-5, atol=1e-6,
    )
    with pytest.raises(ValueError, match="window ring"):
        decode_chunk(params, cache_a, tokens[:, :9], cfg)


def test_windowed_generate_greedy_matches_bruteforce():
    """End-to-end generate with a window: greedy tokens equal the
    brute-force argmax loop over the windowed full forward."""
    cfg = _cfg(window=8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(
        jax.random.PRNGKey(5), (2, 12), 0, cfg.vocab_size, jnp.int32
    )
    out = generate(params, prompt, cfg, max_new_tokens=10, max_len=64)
    seq = np.asarray(prompt)
    for _ in range(10):
        logits = forward(params, jnp.asarray(seq), cfg)
        nxt = np.argmax(np.asarray(logits[:, -1]), axis=-1)
        seq = np.concatenate([seq, nxt[:, None].astype(np.int32)], axis=1)
    np.testing.assert_array_equal(np.asarray(out), seq[:, 12:])


def test_windowed_gqa_and_cache_shape():
    """GQA + window: the ring holds only kv heads x window slots."""
    cfg = _cfg(window=8, n_kv_heads=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(6), (1, 20), 0, cfg.vocab_size, jnp.int32
    )
    ref = forward(params, tokens, cfg)
    logits, cache = prefill(params, tokens[:, :10], cfg, 64)
    assert cache["k"].shape == (2, 1, 8, 2, 16)
    for i in range(10, 20):
        logits, cache = decode_step(params, cache, tokens[:, i], cfg)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(ref[:, i]),
            rtol=2e-3, atol=2e-3, err_msg=f"position {i}",
        )


def test_window_rejects_speculative_and_ring_contexts():
    """Destructive ring writes can't be rolled back, so speculative
    decoding (and ring attention) refuse windowed configs."""
    from containerpilot_tpu.models.speculative import (
        layer_prefix_draft,
        speculative_generate,
    )

    cfg = _cfg(window=8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    draft_params, draft_cfg = layer_prefix_draft(params, cfg, 1)
    prompt = jnp.ones((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="sliding-window"):
        speculative_generate(
            params, draft_params, prompt, cfg, draft_cfg,
            max_new_tokens=4, max_len=32,
        )


@pytest.mark.parametrize("bq,bk,W", [(64, 128, 300), (128, 64, 300)])
def test_windowed_flash_mismatched_blocks_span_coverage(bq, bk, W):
    """Unequal block sizes with a window that is not block-aligned:
    the visited-block span must still cover every contributing block
    (regression: the original span formulas undercounted here,
    silently dropping in-window kv blocks)."""
    rng = jax.random.PRNGKey(7)
    q, k, v = (
        jax.random.normal(kk, (1, 1024, 2, 64), jnp.float32)
        for kk in jax.random.split(rng, 3)
    )
    ref = causal_attention(q, k, v, window=W)
    got = flash_attention(q, k, v, block_q=bq, block_k=bk, window=W)
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(got), rtol=1e-4, atol=1e-5
    )
    ga = jax.grad(
        lambda k_: (causal_attention(q, k_, v, window=W) ** 2).sum()
    )(k)
    gb = jax.grad(
        lambda k_: (
            flash_attention(q, k_, v, block_q=bq, block_k=bk, window=W)
            ** 2
        ).sum()
    )(k)
    np.testing.assert_allclose(
        np.asarray(ga), np.asarray(gb), rtol=2e-4, atol=2e-4
    )


def test_truncated_ring_overflow_rejected():
    """window > max_len truncates the ring to max_len slots; wrapping
    such a ring would overwrite keys still inside the attention
    window, so generate_from_cache must apply the linear-cache
    overflow guard instead of the full-ring wrap exemption."""
    from containerpilot_tpu.models.decode import generate_from_cache

    cfg = _cfg(window=128)  # window wider than the serving max_len
    params = init_params(jax.random.PRNGKey(0), cfg)
    max_len = 16  # ring truncated: length = min(window, max_len) = 16
    prompt = jnp.ones((1, 8), jnp.int32)
    logits, cache = prefill(params, prompt, cfg, max_len)
    assert cache["k"].shape[2] == max_len  # truncated ring
    with pytest.raises(ValueError, match="exceeds cache length"):
        generate_from_cache(
            params, cache, logits, cfg, max_new_tokens=12, pos=8
        )
    # in-bounds decode still works
    out = generate_from_cache(
        params, cache, logits, cfg, max_new_tokens=4, pos=8
    )
    assert out.shape == (1, 4)


def test_full_ring_decodes_past_length():
    """A FULL ring (length == window) legally wraps: every overwritten
    slot is already outside the window."""
    from containerpilot_tpu.models.decode import generate_from_cache

    cfg = _cfg(window=8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    max_len = 32  # ring length = window = 8 (full ring)
    prompt = jnp.ones((1, 4), jnp.int32)
    logits, cache = prefill(params, prompt, cfg, max_len)
    assert cache["k"].shape[2] == 8
    out = generate_from_cache(
        params, cache, logits, cfg, max_new_tokens=16, pos=4
    )
    assert out.shape == (1, 16)


def test_a_ring_pool_is_read_whole_at_every_dispatch(monkeypatch):
    """A sliding-window configuration's slot pool is a ring per row:
    live context wraps, so no head of it can be cut off. Its step
    program has the one rung ``max_len`` whatever the ladder's base
    (models/slots.py ``read_ladder``) and dispatches the programs it
    always did: a long row through a pool of ring rows decodes what a
    solo ``generate`` does."""
    from containerpilot_tpu.models import slots as slots_mod
    from containerpilot_tpu.models.stepprog import PlainStepProgram
    from containerpilot_tpu.workload.serve_slots import SlotEngine

    monkeypatch.setattr(slots_mod, "READ_LADDER_BASE", 8)
    cfg = _cfg(window=16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    max_len = 64
    program = PlainStepProgram(cfg, params, max_len, 2, 4, rounds=2)
    assert program.ladder == (max_len,)
    assert program.read_len(True) == max_len
    eng = SlotEngine(cfg, params, max_len, program=program)
    try:
        out = eng.submit([5, 6, 7], max_new=40).result(timeout=300)
        stats = eng.stats["read_len"]
    finally:
        eng.stop()
    solo = generate(
        params, jnp.asarray([[5, 6, 7]], jnp.int32), cfg, 40, max_len,
        rng=jnp.stack([jax.random.fold_in(jax.random.PRNGKey(0), 0)]),
    )
    assert out == [int(t) for t in np.asarray(solo)[0]]
    assert stats["ladder"] == [max_len]
    assert stats["dispatches"][str(max_len)] >= 5
