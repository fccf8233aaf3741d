"""Flagship model: a decoder-only transformer, TPU-first.

Design choices for the MXU/XLA (not a port of anything):

- all matmuls run in bfloat16 with float32 accumulation
  (``preferred_element_type``), params kept in float32;
- static shapes everywhere; the layer stack is a ``lax.scan`` over
  stacked per-layer parameters, so XLA compiles ONE layer body
  regardless of depth (fast compiles, perfect for pjit);
- RMSNorm + rotary embeddings + SwiGLU — all bandwidth-light
  elementwise ops that XLA fuses into the surrounding matmuls;
- head dim and hidden dims sized to multiples of 128 (lane width);
- attention is causal with an optional pallas flash kernel
  (ops/attention.py) for long sequences.

Parameters are a plain pytree (dict), so sharding rules are just
PartitionSpecs over the tree (parallel/sharding.py).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import tuning
from ..ops.attention import causal_attention
from .quantized import embed_lookup, maybe_dequant_layer, maybe_dequant_top


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32_000
    d_model: int = 512
    n_heads: int = 8
    # grouped-query attention: fewer K/V heads than query heads shrinks
    # the KV cache by n_heads/n_kv_heads; 0 means full multi-head
    n_kv_heads: int = 0
    n_layers: int = 4
    d_ff: int = 1408  # SwiGLU hidden (multiple of 128)
    max_seq_len: int = 2048
    rope_theta: float = 10_000.0
    dtype: Any = jnp.bfloat16  # compute dtype
    # attention override: None = auto (pallas flash at/after
    # flash_min_seq, XLA causal below it); set to e.g. a mesh-bound
    # ring_attention for context parallelism (parallel/context.py)
    attention_fn: Any = None
    # sequences at/above this length (and 128-aligned) run the pallas
    # flash kernels — fwd AND bwd (ops/flash.py); 0 disables auto-flash;
    # -1 (AUTO) takes the measured flash/XLA crossover from the
    # platform's tuned table (ops/tuning.py), falling back to 1024
    # when none is shipped. Block sizes come from the same table.
    # Mesh-parallel trainers bind the shard_map-wrapped equivalent via
    # parallel.context.flash_parallel_config (pallas calls don't
    # partition under automatic pjit sharding).
    flash_min_seq: int = tuning.AUTO
    # rematerialize each layer in the backward pass instead of saving
    # its activations: the standard TPU trade of MXU FLOPs (~1/3 extra)
    # for HBM. Without it the scan-over-layers saves every layer's MLP
    # hiddens ([L, b, s, d_ff]) and real model sizes blow the 16GB HBM.
    # True/"full" = discard everything per layer; "dots" = keep matmul
    # outputs, recompute only elementwise (less HBM saved, almost no
    # recompute FLOPs); False = save everything.
    remat: Any = True
    # >0: the training loss streams the unembed projection +
    # log-softmax over sequence chunks of this size instead of
    # materializing [batch, seq, vocab] logits (gigabytes at real
    # vocab sizes); the backward recomputes each chunk's logits.
    # 0 = whole-logits loss.
    loss_chunk: int = 0
    # int8 KV cache for serving (models/decode.py): k/v quantize
    # per-(token, head) on write and dequantize on read — KV memory
    # halves vs bf16, composing with GQA and the window ring. Training
    # is unaffected (no cache there).
    kv_int8: bool = False
    # sliding-window attention (Mistral-style): each position attends
    # only the last `window` positions. 0 = full causal. Bounds the
    # decode KV cache to a ring of `window` entries (models/decode.py)
    # and the attention FLOPs to O(s*window).
    window: int = 0

    def __post_init__(self) -> None:
        if self.remat not in (True, False, "full", "dots", "none"):
            raise ValueError(
                f"remat must be True/False/'full'/'dots'/'none', "
                f"got {self.remat!r}"
            )
        if self.loss_chunk < 0:
            raise ValueError(
                f"loss_chunk must be >= 0, got {self.loss_chunk}"
            )

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        kv = self.n_kv_heads or self.n_heads
        assert self.n_heads % kv == 0, "n_heads must divide by n_kv_heads"
        return kv


Params = Dict[str, Any]

FLASH_BLOCK = 128


def flash_eligible(
    cfg: "TransformerConfig", seq: int, kind: str = "train"
) -> bool:
    """True when the auto-selected attention should be the pallas flash
    path: at/above the (possibly table-resolved) threshold and
    block-aligned. ``kind`` picks which measured crossover an AUTO
    threshold resolves through — 'train' for the differentiable path,
    'fwd' for inference prefill. A sliding window must itself be
    block-aligned for the kernels' block-skip logic."""
    min_seq = tuning.resolve_min_seq(cfg.flash_min_seq, kind=kind)
    flash = (
        min_seq > 0
        and seq >= min_seq
        and seq % FLASH_BLOCK == 0
        and (cfg.window == 0 or cfg.window % FLASH_BLOCK == 0)
    )
    tuning.log_attention_path(kind, seq, cfg.window, flash)
    return flash


def _auto_attention(cfg: "TransformerConfig", seq: int) -> Any:
    import functools

    if flash_eligible(cfg, seq):
        from ..ops.flash import flash_attention

        # 'train' blocks: forward() is the differentiable path, so one
        # custom_vjp call carries fwd AND bwd through these blocks
        bq, bk = tuning.pick_blocks("train", seq)
        return functools.partial(
            flash_attention, block_q=bq, block_k=bk, window=cfg.window
        )
    if cfg.window > 0:
        return functools.partial(causal_attention, window=cfg.window)
    return causal_attention


def init_params(rng: jax.Array, cfg: TransformerConfig) -> Params:
    """Initialize parameters as stacked-per-layer arrays (leading axis =
    layer), ready for the scan-based forward."""
    k_emb, k_attn, k_mlp, k_out = jax.random.split(rng, 4)
    d, h, hd, f, L = (
        cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff, cfg.n_layers,
    )
    kv = cfg.kv_heads

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5))

    ks = jax.random.split(k_attn, 4)
    km = jax.random.split(k_mlp, 3)
    layers = {
        # attention projections, stacked over layers
        "wq": dense(ks[0], (L, d, h, hd), d),
        "wk": dense(ks[1], (L, d, kv, hd), d),
        "wv": dense(ks[2], (L, d, kv, hd), d),
        "wo": dense(ks[3], (L, h, hd, d), h * hd),
        "norm_attn": jnp.ones((L, d), jnp.float32),
        "norm_mlp": jnp.ones((L, d), jnp.float32),
        # SwiGLU
        "w_gate": dense(km[0], (L, d, f), d),
        "w_up": dense(km[1], (L, d, f), d),
        "w_down": dense(km[2], (L, f, d), f),
    }
    return {
        "embed": jax.random.normal(k_emb, (cfg.vocab_size, d), jnp.float32)
        * 0.02,
        "layers": layers,
        "norm_out": jnp.ones((d,), jnp.float32),
        "unembed": dense(k_out, (d, cfg.vocab_size), d),
    }


def serving_params(params: Params, cfg: TransformerConfig) -> Params:
    """The tree a replica serves from: every floating leaf rounded to
    ``cfg.dtype`` once, where it lies (sharding kept). Every serving
    program reads a weight as ``w.astype(cfg.dtype)``, so the matmuls
    get the very bits they got from the float32 tree, without each
    program's own copy of the weights at every dispatch.

    CONSUMES ``params``: a float32 leaf is deleted as soon as its
    rounded copy exists, so the device holds the tree plus one leaf,
    never both trees. Trainer, evaluation and tests keep the float32
    tree of ``init_params``."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    for i, leaf in enumerate(leaves):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            continue
        cast = leaf.astype(cfg.dtype)
        if cast is not leaf:
            cast.block_until_ready()
            leaf.delete()
        leaves[i] = cast
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _rms_norm(x: jax.Array, scale: jax.Array,
              eps: float = 1e-6) -> jax.Array:
    # named scopes (here and below) are the layer map's names in the
    # compiled programs' op metadata: a profiler trace attributes
    # device time by them (docs/90-observability.md). Metadata only.
    with jax.named_scope("norm"):
        var = jnp.mean(
            jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True
        )
        return (
            (x * lax.rsqrt(var + eps).astype(x.dtype))
            * scale.astype(x.dtype)
        )


def _rope(x: jax.Array, theta: float, offset: Any = 0) -> jax.Array:
    """Rotary position embedding over the last (head_dim) axis.
    x: [batch, seq, heads, head_dim]; ``offset`` shifts the absolute
    positions (needed by incremental decoding — models/decode.py): one
    number, or one per row ([batch]: the slot pool's rows each stand
    at their own position)."""
    b, s, h, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    positions = jnp.asarray(offset)[..., None] + jnp.arange(
        s, dtype=jnp.float32
    )  # [s] or [batch, s]
    angles = positions[..., None] * freqs
    cos = jnp.cos(angles)[..., None, :].astype(x.dtype)
    sin = jnp.sin(angles)[..., None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _qkv(
    x: jax.Array,
    layer_params: Dict[str, jax.Array],
    cfg: TransformerConfig,
    offset: Any = 0,
):
    """Pre-norm + q/k/v projections with RoPE applied at ``offset``.

    Under GQA, k/v come back with ``cfg.kv_heads`` heads — callers
    either store them that way (the KV cache, which is the point of
    GQA) or broadcast to full heads via ``repeat_kv`` for attention.
    """
    dt = cfg.dtype
    with jax.named_scope("attn"), jax.named_scope("attn.qkv"):
        h = _rms_norm(x, layer_params["norm_attn"])
        q = jnp.einsum("bsd,dhk->bshk", h, layer_params["wq"].astype(dt),
                       preferred_element_type=jnp.float32).astype(dt)
        k = jnp.einsum("bsd,dhk->bshk", h, layer_params["wk"].astype(dt),
                       preferred_element_type=jnp.float32).astype(dt)
        v = jnp.einsum("bsd,dhk->bshk", h, layer_params["wv"].astype(dt),
                       preferred_element_type=jnp.float32).astype(dt)
    with jax.named_scope("attn"), jax.named_scope("attn.rope"):
        q = _rope(q, cfg.rope_theta, offset)
        k = _rope(k, cfg.rope_theta, offset)
    return q, k, v


def repeat_kv(x: jax.Array, n_heads: int) -> jax.Array:
    """Broadcast GQA k/v [b,s,kv,hd] to [b,s,n_heads,hd]."""
    kv = x.shape[2]
    if kv == n_heads:
        return x
    return jnp.repeat(x, n_heads // kv, axis=2)


def _attn_out(
    x: jax.Array,
    attn: jax.Array,
    layer_params: Dict[str, jax.Array],
    cfg: TransformerConfig,
) -> jax.Array:
    """Output projection + residual."""
    dt = cfg.dtype
    with jax.named_scope("attn"), jax.named_scope("attn.out"):
        attn_out = jnp.einsum("bshk,hkd->bsd", attn,
                              layer_params["wo"].astype(dt),
                              preferred_element_type=jnp.float32).astype(dt)
        return x + attn_out


def _mlp(
    x: jax.Array, layer_params: Dict[str, jax.Array], cfg: TransformerConfig
) -> jax.Array:
    """SwiGLU block + residual."""
    dt = cfg.dtype
    with jax.named_scope("mlp"):
        h = _rms_norm(x, layer_params["norm_mlp"])
        gate = jnp.einsum("bsd,df->bsf", h,
                          layer_params["w_gate"].astype(dt),
                          preferred_element_type=jnp.float32)
        up = jnp.einsum("bsd,df->bsf", h, layer_params["w_up"].astype(dt),
                        preferred_element_type=jnp.float32)
        act = (jax.nn.silu(gate) * up).astype(dt)
        down = jnp.einsum("bsf,fd->bsd", act,
                          layer_params["w_down"].astype(dt),
                          preferred_element_type=jnp.float32).astype(dt)
        return x + down


def _layer(
    x: jax.Array, layer_params: Dict[str, jax.Array], cfg: TransformerConfig
):
    """One transformer block. x: [batch, seq, d_model] in compute dtype."""
    layer_params = maybe_dequant_layer(layer_params, cfg.dtype)
    q, k, v = _qkv(x, layer_params, cfg)
    attn_fn = cfg.attention_fn or _auto_attention(cfg, q.shape[1])
    if not getattr(attn_fn, "gqa_native", False):
        # fns that handle grouped kv themselves (e.g. ring attention)
        # get the small K/V — rotating the unrepeated heads over ICI is
        # the point of GQA; everything else gets full heads
        k = repeat_kv(k, cfg.n_heads)
        v = repeat_kv(v, cfg.n_heads)
    with jax.named_scope("attn"), jax.named_scope("attn.scores"):
        attn = attn_fn(q, k, v)
    x = _attn_out(x, attn, layer_params, cfg)
    return _mlp(x, layer_params, cfg)


def forward_hidden(
    params: Params, tokens: jax.Array, cfg: TransformerConfig
) -> jax.Array:
    """tokens: [batch, seq] int32 -> final normed hidden
    [batch, seq, d_model] — everything up to (not including) the
    unembed projection, so losses may stream the vocab projection in
    pieces (chunked cross-entropy) instead of materializing
    [batch, seq, vocab] logits.

    The layer stack is a lax.scan over stacked layer params: one
    compiled block body, L iterations, rematerialization-friendly.
    """
    x = embed_lookup(params, tokens, cfg.dtype)

    def body(x, layer_params):
        return _layer(x, layer_params, cfg), None

    if cfg.remat and cfg.remat != "none":
        # remat="dots" keeps the MXU outputs (the expensive matmuls)
        # and recomputes only elementwise work in the backward pass —
        # most of full remat's memory win at a fraction of its ~1/3
        # recompute FLOPs. True/"full" discards everything per layer.
        if cfg.remat == "dots":
            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies
                .dots_with_no_batch_dims_saveable,
            )
        else:
            body = jax.checkpoint(body)
    # ``layers`` names what the scan itself does around the blocks
    # (slicing the stacked weights, stacking the backward's residuals)
    with jax.named_scope("layers"):
        x, _ = lax.scan(body, x, params["layers"])
    return _rms_norm(x, params["norm_out"])


def forward(
    params: Params, tokens: jax.Array, cfg: TransformerConfig
) -> jax.Array:
    """tokens: [batch, seq] int32 -> logits [batch, seq, vocab] f32."""
    family = getattr(cfg, "family", None)
    if family is not None:  # another family's own forward (serving only)
        return family.forward(params, tokens, cfg)
    x = forward_hidden(params, tokens, cfg)
    with jax.named_scope("head"):
        return jnp.einsum(
            "bsd,dv->bsv", x,
            maybe_dequant_top(params, "unembed", cfg.dtype),
            preferred_element_type=jnp.float32,
        )


def _ce_nll(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Per-position negative log-likelihood — the ONE cross-entropy
    core shared by the whole-logits and chunked losses, so a change
    to the objective (z-loss, label smoothing, soft-capping) cannot
    silently apply to only one path."""
    with jax.named_scope("loss"):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(
            logp, targets[..., None], axis=-1
        )[..., 0]


def next_token_loss(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Next-token CE over logits for tokens[:, :-1] — shared by the
    plain and pipelined losses."""
    return jnp.mean(_ce_nll(logits, tokens[:, 1:]))


def _chunked_next_token_loss(
    params: Params, tokens: jax.Array, cfg: TransformerConfig
) -> jax.Array:
    """CE without ever materializing the full [b, s, vocab] logits:
    the unembed projection + log-softmax + gather run over sequence
    chunks inside a remat'd scan, so peak activation memory for the
    loss head is [b, loss_chunk, vocab] (the backward recomputes each
    chunk's logits — one extra unembed matmul, a few percent of step
    FLOPs, against gigabytes of saved HBM at real vocab sizes)."""
    x = forward_hidden(params, tokens[:, :-1], cfg)
    targets = tokens[:, 1:]
    b, s, d = x.shape
    chunk = min(cfg.loss_chunk, s)
    n = -(-s // chunk)
    pad = n * chunk - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
    mask = (jnp.arange(n * chunk) < s)[None, :]  # [1, n*chunk]
    with jax.named_scope("head"):
        unembed = maybe_dequant_top(params, "unembed", cfg.dtype)

    x_chunks = x.reshape(b, n, chunk, d).swapaxes(0, 1)  # [n,b,c,d]
    t_chunks = targets.reshape(b, n, chunk).swapaxes(0, 1)
    m_chunks = mask.reshape(1, n, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def piece(total, inputs):
        xc, tc, mc = inputs
        with jax.named_scope("head"):
            logits = jnp.einsum(
                "bcd,dv->bcv", xc, unembed,
                preferred_element_type=jnp.float32,
            )
        with jax.named_scope("loss"):
            return total + jnp.sum(_ce_nll(logits, tc) * mc), None

    with jax.named_scope("loss.chunks"):
        total, _ = lax.scan(
            piece, jnp.zeros((), jnp.float32),
            (x_chunks, t_chunks, m_chunks),
        )
    return total / (b * s)


def loss_fn(
    params: Params, tokens: jax.Array, cfg: TransformerConfig
) -> jax.Array:
    """Next-token cross-entropy. ``cfg.loss_chunk > 0`` streams the
    vocab projection in sequence chunks instead of materializing full
    logits."""
    if cfg.loss_chunk > 0:
        return _chunked_next_token_loss(params, tokens, cfg)
    return next_token_loss(forward(params, tokens[:, :-1], cfg), tokens)
