"""A third model family: generation by diffusion over blocks (SDAR,
``model_type`` ``sdar_moe``; the layer is Qwen3-MoE's). Serving only.

What differs from the two families beside it, and why it is a module
of its own:

- **the mask is by blocks.** Position ``i`` sees ``j`` iff
  ``j // B <= i // B`` (``B`` = ``block_length``): causal between
  blocks, both ways inside one. Prompts are prefilled under it, and a
  cached position's keys depend on its WHOLE block, so everything that
  cuts a cache (reuse, rewind-and-extend, readmission) cuts at
  multiples of ``B`` (``reuse_quantum``);
- **a step is not a token.** The next block starts as ``B`` mask
  tokens. A denoising forward runs the block over the cache of the
  finished blocks; a hidden position's OWN logits give its token (no
  shift) and its confidence, the float32 softmax probability of that
  token; ``low_confidence_static`` reveals the ``B / steps`` hidden
  positions of highest confidence, ``low_confidence_dynamic`` every
  hidden position whose confidence passes the threshold and never
  fewer than the static count. When nothing is hidden, one more
  forward (the commit) writes the keys and values of the REVEALED
  block and the row moves on. So a pool forward yields 0 to ``B``
  tokens a row, and :class:`BlockStepProgram` is the step program
  (models/stepprog.py) that says so;
- **per-head q/k norms.** A learned RMS norm over each head's
  ``head_dim`` dimensions, before the rotation;
- **softmax routing, every expert held.** ``moe.route_softmax`` scores
  all experts by a float32 softmax and renormalises the chosen;
  ``moe.sparse_experts`` then sees every row of the pool's blocks
  (``S * B`` a forward).

Every forward writes its block's keys and values at the row's own
``pos .. pos + B`` and reads the cache where it lies; ``pos`` (the
committed length, a multiple of ``B``) moves only on a commit, so what
a denoising forward wrote is overwritten by the commit and never read
as context. Hidden positions are a boolean mask in the step's state,
never found by comparing ids (a prompt may hold the mask token's id),
and the mask token's logit is excluded from every choice, so it is
never emitted.

A row whose prompt ends inside a block starts with that block's
prompt tokens shown and the rest hidden (the published routine). Where
fewer positions are hidden than a step would reveal, only the hidden
ones are revealed. Ties in confidence go to the lower position.

Weights follow models/mla_moe.py's recipe (made and held in bf16, a
key per leaf, expert and vocabulary block).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import moe
from .decode import _grouped_attention
from .mla_moe import (
    STATS_HEAD, TOP, VOCAB_BLOCK, _count, _draw, named_stats)
from .quantized import embed_lookup
from .slots import retire_slot
from .stepprog import phase_span
from .transformer import _rms_norm, _rope

Params = Dict[str, Any]
Cache = Dict[str, Any]

#: query rows of a prefill worked on at once (scores are
#: [kv_heads, group, Q_BLOCK, seq] in float32)
Q_BLOCK = 512
REMASKING = ("low_confidence_static", "low_confidence_dynamic")
#: a pool's ``diffusion`` counters, in the order of its leaf
DIFFUSION_COUNTERS = ("row_forwards", "tokens_revealed",
                      "blocks_committed", "commit_forwards")


@dataclass(frozen=True)
class BlockDiffusionConfig:
    vocab_size: int = 1024
    d_model: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    n_layers: int = 2
    moe_d_ff: int = 32
    n_experts: int = 8
    experts_per_tok: int = 2
    norm_topk: bool = True
    rope_theta: float = 1_000_000.0
    block_length: int = 4
    denoising_steps: int = 2
    remasking: str = "low_confidence_static"
    confidence_threshold: float = 0.9
    mask_token_id: int = 1023
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    #: digest of the file the configuration was read from (part of a
    #: server's warm-up fingerprint, workload/modelcfg.py)
    source_digest: str = ""

    def __post_init__(self) -> None:
        if self.n_heads % self.n_kv_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even")
        if not 1 <= self.experts_per_tok <= self.n_experts:
            raise ValueError("num_experts_per_tok lies outside the experts")
        if self.block_length < 1 or not (
                1 <= self.denoising_steps <= self.block_length):
            raise ValueError(
                f"denoising_steps {self.denoising_steps} must lie in "
                f"1..block_length {self.block_length}")
        if self.remasking not in REMASKING:
            raise ValueError(f"remasking {self.remasking!r}: one of "
                             f"{', '.join(REMASKING)}")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError("mask_token_id lies outside the vocabulary")

    # what the serving code asks of any configuration
    window = 0
    kv_int8 = False

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads

    @property
    def family(self):
        import sys

        return sys.modules[__name__]

    @property
    def reuse_quantum(self) -> int:
        """A cache may be cut only at multiples of this."""
        return self.block_length

    @property
    def schedule(self) -> Tuple[int, ...]:
        """Positions a denoising step reveals, by step (the published
        ``get_num_transfer_tokens``): ``B // steps`` each, the
        remainder one each to the first steps."""
        base, extra = divmod(self.block_length, self.denoising_steps)
        return tuple(base + (i < extra)
                     for i in range(self.denoising_steps))


def from_published(config: Dict[str, Any], max_seq_len: int,
                   source_digest: str = "") -> BlockDiffusionConfig:
    """The configuration from a published ``config.json``'s own keys
    (Qwen3-MoE's, which ``sdar_moe`` shares letter for letter). What
    the published file does not give (block length, schedule, rule,
    threshold, mask token) is read from a ``diffusion`` group."""
    if abs(float(config.get("rms_norm_eps", 1e-6)) - 1e-6) > 1e-12:
        raise ValueError("this block's RMSNorm fixes eps 1e-6")
    for key, want in (
        ("hidden_act", "silu"), ("attention_bias", False),
        ("decoder_sparse_step", 1), ("mlp_only_layers", []),
        ("rope_scaling", None), ("use_sliding_window", False),
        ("tie_word_embeddings", False),
    ):
        if config.get(key, want) != want:
            raise ValueError(f"{key} {config[key]!r}: only {want!r}")
    diffusion = config.get("diffusion")
    if not isinstance(diffusion, dict):
        raise ValueError("a block-diffusion file needs a 'diffusion' group "
                         "(block_length, denoising_steps, remasking, "
                         "confidence_threshold, mask_token_id)")
    return BlockDiffusionConfig(
        vocab_size=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        n_layers=int(config["num_hidden_layers"]),
        moe_d_ff=int(config["moe_intermediate_size"]),
        n_experts=int(config["num_experts"]),
        experts_per_tok=int(config["num_experts_per_tok"]),
        norm_topk=bool(config.get("norm_topk_prob", True)),
        rope_theta=float(config.get("rope_theta", 1_000_000.0)),
        block_length=int(diffusion["block_length"]),
        denoising_steps=int(diffusion["denoising_steps"]),
        remasking=str(diffusion["remasking"]),
        confidence_threshold=float(diffusion["confidence_threshold"]),
        mask_token_id=int(diffusion["mask_token_id"]),
        max_seq_len=max_seq_len, source_digest=source_digest,
    )


# -- weights ------------------------------------------------------------

#: a leaf's key is PRNGKey(0) folded with its layer (TOP for the
#: embedding and the head) and then with its number here; an expert's
#: with its index after that, a vocabulary block's with its block index
LEAF = {name: i for i, name in enumerate((
    "wq", "wk", "wv", "wo", "router", "e_gate", "e_up", "e_down",
    "embed", "unembed",
))}


def _leaf_key(layer: int, name: str):
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(0), layer), LEAF[name])


def _layer_leaves(cfg: BlockDiffusionConfig, layer: int) -> Dict[str, Any]:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    f, dt = cfg.moe_d_ff, cfg.dtype
    shapes = {
        "wq": ((d, h, hd), d), "wk": ((d, kv, hd), d),
        "wv": ((d, kv, hd), d), "wo": ((h, hd, d), h * hd),
        "router": ((d, cfg.n_experts), d),
    }
    out = {
        name: _draw(_leaf_key(layer, name), shape, fan_in ** -0.5, dt)
        for name, (shape, fan_in) in shapes.items()
    }
    experts = jnp.arange(cfg.n_experts)
    for name, shape, fan_in in (("e_gate", (d, f), d), ("e_up", (d, f), d),
                                ("e_down", (f, d), f)):
        keys = jax.vmap(
            lambda e, name=name: jax.random.fold_in(
                _leaf_key(layer, name), e))(experts)
        out[name] = jax.vmap(
            lambda k, shape=shape, fan_in=fan_in: _draw(
                k, shape, fan_in ** -0.5, dt))(keys)
    out["norm_attn"] = jnp.ones((d,), jnp.float32)
    out["norm_mlp"] = jnp.ones((d,), jnp.float32)
    out["norm_q"] = jnp.ones((hd,), jnp.float32)
    out["norm_k"] = jnp.ones((hd,), jnp.float32)
    return out


def _vocab_leaf(cfg: BlockDiffusionConfig, name: str, scale: float):
    """[vocab, d] drawn block by block of VOCAB_BLOCK rows."""
    if cfg.vocab_size % VOCAB_BLOCK:
        raise ValueError(f"vocab_size must be a multiple of {VOCAB_BLOCK}")
    blocks = jnp.arange(cfg.vocab_size // VOCAB_BLOCK)
    key = _leaf_key(TOP, name)
    rows = jax.vmap(lambda b: _draw(
        jax.random.fold_in(key, b), (VOCAB_BLOCK, cfg.d_model), scale,
        cfg.dtype))(blocks)
    return rows.reshape(cfg.vocab_size, cfg.d_model)


def init_params(rng: Any, cfg: BlockDiffusionConfig) -> Params:
    """Seeded weights, made leaf by leaf and held in ``cfg.dtype``
    (``rng`` is unused: see models/mla_moe.py ``init_params``)."""
    del rng
    return {
        "embed": _vocab_leaf(cfg, "embed", 0.02),
        "layers": [_layer_leaves(cfg, i) for i in range(cfg.n_layers)],
        "norm_out": jnp.ones((cfg.d_model,), jnp.float32),
        "unembed": _vocab_leaf(cfg, "unembed", cfg.d_model ** -0.5).T,
    }


# -- pieces of a layer ----------------------------------------------------


def _qkv(x, lp, cfg: BlockDiffusionConfig, offset):
    """Pre-norm, the three projections, the per-head norms of q and k
    and then the rotation at ``offset`` (one number, or one per row).
    Returns q [b, m, H, hd], k and v [b, m, KV, hd]."""
    dt = cfg.dtype
    h = _rms_norm(x, lp["norm_attn"])
    with jax.named_scope("attn"), jax.named_scope("attn.qkv"):
        q, k, v = (
            jnp.einsum("bmd,dhk->bmhk", h, lp[name].astype(dt),
                       preferred_element_type=jnp.float32).astype(dt)
            for name in ("wq", "wk", "wv"))
    with jax.named_scope("attn"), jax.named_scope("attn.qk_norm"):
        q = _rms_norm(q, lp["norm_q"])
        k = _rms_norm(k, lp["norm_k"])
    with jax.named_scope("attn"), jax.named_scope("attn.rope"):
        q = _rope(q, cfg.rope_theta, offset)
        k = _rope(k, cfg.rope_theta, offset)
    return q, k, v


def _attn_out(x, o, lp, cfg: BlockDiffusionConfig):
    with jax.named_scope("attn"), jax.named_scope("attn.out"):
        out = jnp.einsum("bmhk,hkd->bmd", o, lp["wo"].astype(cfg.dtype),
                         preferred_element_type=jnp.float32)
        return x + out.astype(cfg.dtype)


def _sparse_ffn(x, lp, cfg: BlockDiffusionConfig):
    """The expert layer + residual. Returns (x, assignments per expert
    [n_experts] int32)."""
    dt = cfg.dtype
    b, m, d = x.shape
    with jax.named_scope("mlp"):
        h = _rms_norm(x, lp["norm_mlp"]).reshape(b * m, d)
        idx, gate = moe.route_softmax(
            h, lp["router"], cfg.experts_per_tok, cfg.norm_topk)
        routed, counts = moe.sparse_experts(
            h, idx, gate, lp["e_gate"], lp["e_up"], lp["e_down"],
            0, cfg.n_experts)
        return x + routed.astype(dt).reshape(b, m, d), counts


def _logits(params: Params, x: jax.Array, cfg: BlockDiffusionConfig):
    with jax.named_scope("head"):
        x = _rms_norm(x, params["norm_out"])
        return jnp.einsum("bsd,dv->bsv", x,
                          params["unembed"].astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


def _prefill_attention(q, k, v, cfg: BlockDiffusionConfig):
    """Attention of a whole sequence from position 0 under the block
    mask, ``Q_BLOCK`` query rows at a time where the sequence is long."""
    b, s = q.shape[:2]
    size = cfg.block_length
    step = Q_BLOCK if s > Q_BLOCK and s % Q_BLOCK == 0 else s
    col_block = jnp.arange(s) // size

    def rows(start):
        qs = lax.dynamic_slice_in_dim(q, start, step, axis=1)
        row_block = (start + jnp.arange(step)) // size
        valid = col_block[None, :] <= row_block[:, None]
        return _grouped_attention(qs, k, v, valid, cfg.dtype)

    if step == s:
        return rows(0)
    out = lax.map(rows, jnp.arange(0, s, step))  # [n, b, step, H, hd]
    return jnp.moveaxis(out, 0, 1).reshape(b, s, *out.shape[3:])


# -- the cache ------------------------------------------------------------


def init_cache(cfg: BlockDiffusionConfig, batch: int, max_len: int) -> Cache:
    """Zeroed cache: per layer ``k[l]`` and ``v[l]`` [batch, length,
    kv_heads, head_dim]; ``pos`` is the committed length, one number
    until a pool makes it one per row."""
    if max_len % cfg.block_length:
        raise ValueError(f"max_len {max_len} must be a multiple of "
                         f"block_length {cfg.block_length}")
    shape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {
        "pos": jnp.zeros((), jnp.int32),
        "k": [jnp.zeros(shape, cfg.dtype) for _ in range(cfg.n_layers)],
        "v": [jnp.zeros(shape, cfg.dtype) for _ in range(cfg.n_layers)],
    }


def slot_cache(cfg: BlockDiffusionConfig, slots: int, max_len: int) -> Cache:
    """The serving pool: one row and one position per slot, the expert
    layers' counters and the diffusion counters."""
    pool = init_cache(cfg, slots, max_len)
    pool["pos"] = jnp.zeros((slots,), jnp.int32)
    pool["stats"] = jnp.zeros((len(STATS_HEAD) + cfg.n_experts,), jnp.int32)
    pool["diffusion"] = jnp.zeros((len(DIFFUSION_COUNTERS),), jnp.int32)
    return pool


def insert_row(pool: Cache, row: Cache, slot: jax.Array) -> Cache:
    """Write a one-row cache (``prefill``'s) into ``slot``: the whole
    row and its position."""
    new = dict(pool)
    for name in ("k", "v"):
        new[name] = [
            lax.dynamic_update_slice(big, small.astype(big.dtype),
                                     (slot, 0, 0, 0))
            for big, small in zip(pool[name], row[name])]
    new["pos"] = lax.dynamic_update_slice(
        pool["pos"], jnp.reshape(row["pos"], (1,)).astype(jnp.int32), (slot,))
    return new


# -- forward, prefill, extension -----------------------------------------


def _hidden(params: Params, tokens: jax.Array, cfg: BlockDiffusionConfig):
    """tokens [b, s] from position 0 under the block mask -> (hidden
    [b, s, d], every layer's keys and values)."""
    x = embed_lookup(params, tokens, cfg.dtype)
    kept_k, kept_v = [], []
    with jax.named_scope("layers"):
        for lp in params["layers"]:
            q, k, v = _qkv(x, lp, cfg, 0)
            with jax.named_scope("attn"), jax.named_scope("attn.scores"):
                o = _prefill_attention(q, k, v, cfg)
            x = _attn_out(x, o, lp, cfg)
            x, _counts = _sparse_ffn(x, lp, cfg)
            kept_k.append(k)
            kept_v.append(v)
    return x, (kept_k, kept_v)


def forward(params: Params, tokens: jax.Array, cfg: BlockDiffusionConfig):
    """tokens [b, s] -> logits [b, s, vocab] float32, block mask."""
    x, _kept = _hidden(params, tokens, cfg)
    return _logits(params, x, cfg)


def prefill(params: Params, tokens: jax.Array, cfg: BlockDiffusionConfig,
            max_len: int) -> Tuple[jax.Array, Cache]:
    """Process the prompt under the block mask; returns (logits of the
    last position, the cache). The cache's ``pos`` is the prompt's
    length cut to whole blocks: what a trailing part of a block wrote
    lies beyond it and is overwritten when that block is denoised."""
    b, s = tokens.shape
    x, (keys, values) = _hidden(params, tokens, cfg)
    with jax.named_scope("attn"), jax.named_scope("attn.kv_write"):
        cache = init_cache(cfg, b, max_len)
        cache["k"] = [lax.dynamic_update_slice(big, new, (0, 0, 0, 0))
                      for big, new in zip(cache["k"], keys)]
        cache["v"] = [lax.dynamic_update_slice(big, new, (0, 0, 0, 0))
                      for big, new in zip(cache["v"], values)]
    cache["pos"] = jnp.asarray(s - s % cfg.block_length, jnp.int32)
    return _logits(params, x[:, -1:, :], cfg)[:, 0, :], cache


def _cached_forward(params: Params, cache: Cache, tokens: jax.Array,
                    cfg: BlockDiffusionConfig):
    """m tokens per row at ``pos .. pos + m`` over the cache in one
    forward: each layer's keys and values written in place at the
    row's own positions and read where they lie, under the block mask.
    ``pos`` is NOT moved. Returns (logits [b, m, V], cache)."""
    pos = cache["pos"]
    b, m = tokens.shape
    length = cache["k"][0].shape[1]
    size = cfg.block_length
    rows = jnp.arange(b)[:, None]
    start = jnp.broadcast_to(pos, (b,))
    q_pos = start[:, None] + jnp.arange(m)  # [b, m]
    cols = jnp.arange(length)
    valid = (cols[None, None, :] // size <= q_pos[:, :, None] // size) & (
        cols[None, None, :] < (start + m)[:, None, None])
    x = embed_lookup(params, tokens, cfg.dtype)
    new_k, new_v, counts = [], [], []
    with jax.named_scope("layers"):
        for layer, lp in enumerate(params["layers"]):
            keys, values = cache["k"][layer], cache["v"][layer]
            q, k, v = _qkv(x, lp, cfg, pos)
            with jax.named_scope("attn"), jax.named_scope("attn.kv_write"):
                if pos.ndim == 0:
                    keys = lax.dynamic_update_slice(keys, k, (0, pos, 0, 0))
                    values = lax.dynamic_update_slice(values, v, (0, pos, 0, 0))
                else:
                    # a dead slot decodes on past the end: dropped there
                    keys = keys.at[rows, q_pos].set(k, mode="drop")
                    values = values.at[rows, q_pos].set(v, mode="drop")
            with jax.named_scope("attn"), jax.named_scope("attn.block"):
                o = _grouped_attention(q, keys, values, valid, cfg.dtype)
            x = _attn_out(x, o, lp, cfg)
            x, layer_counts = _sparse_ffn(x, lp, cfg)
            counts.append(layer_counts)
            new_k.append(keys)
            new_v.append(values)
    new = {**cache, "k": new_k, "v": new_v}
    if "stats" in cache:
        new["stats"] = _count(
            cache["stats"], b * m, counts,
            moe.expert_block(b * m, cfg.experts_per_tok, cfg.n_experts))
    return _logits(params, x, cfg), new


def decode_chunk(params: Params, cache: Cache, tokens: jax.Array,
                 cfg: BlockDiffusionConfig) -> Tuple[jax.Array, Cache]:
    """Extend a cache by m KNOWN tokens (a prompt's suffix after a
    reused prefix) under the block mask. ``pos`` must be a multiple of
    the block length and moves on to the new length cut to whole
    blocks, as ``prefill`` leaves it."""
    logits, new = _cached_forward(params, cache, tokens, cfg)
    end = cache["pos"] + tokens.shape[1]
    new["pos"] = end - end % cfg.block_length
    return logits, new


# -- the step program ------------------------------------------------------


def _reveal(conf, hidden, step, cfg: BlockDiffusionConfig):
    """Which hidden positions a denoising step reveals: conf [S, B]
    float32, hidden [S, B] bool, step [S] -> [S, B] bool."""
    size = cfg.block_length
    schedule = jnp.asarray(cfg.schedule + (size,), jnp.int32)
    hidden_n = jnp.sum(hidden, axis=1)
    # past the schedule's end (only the dynamic rule's fallback can
    # fall behind it) everything left is revealed
    count = jnp.minimum(
        schedule[jnp.minimum(step, cfg.denoising_steps)], hidden_n)
    masked = jnp.where(hidden, conf, -jnp.inf)
    at = jnp.arange(size)
    # rank by confidence, ties to the lower position
    ahead = (masked[:, None, :] > masked[:, :, None]) | (
        (masked[:, None, :] == masked[:, :, None])
        & (at[None, None, :] < at[None, :, None]))
    rank = jnp.sum(ahead, axis=2)
    chosen = hidden & (rank < count[:, None])
    if cfg.remasking == "low_confidence_dynamic":
        high = hidden & (masked > cfg.confidence_threshold)
        enough = jnp.sum(high, axis=1) >= count
        chosen = jnp.where(enough[:, None], high, chosen)
    return chosen


def _pool_forward(params, cfg: BlockDiffusionConfig):
    """The ONE pool forward both programs trace: every row's block
    over its cache, then per row a reveal (something is hidden) or a
    commit (nothing is). Carry: (pool, blk [S, B], hidden [S, B],
    step [S], done [S]); yields (the block after the reveal, whether
    it became whole in this forward)."""
    size = cfg.block_length
    mask_id = cfg.mask_token_id

    def body(carry, _):
        pool, blk, hidden, step, done = carry
        logits, pool = _cached_forward(params, pool, blk, cfg)
        with jax.named_scope("sample"):
            with jax.named_scope("sample.confidence"):
                # the mask token is no answer: it is never emitted
                logits = jnp.where(
                    jnp.arange(cfg.vocab_size) == mask_id, -jnp.inf, logits)
                top = jnp.max(logits, axis=-1)
                picked = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                conf = 1.0 / jnp.sum(
                    jnp.exp(logits - top[..., None]), axis=-1)
            with jax.named_scope("sample.reveal"):
                commit = ~jnp.any(hidden, axis=1)
                chosen = _reveal(conf, hidden, step, cfg) & ~commit[:, None]
                blk = jnp.where(chosen, picked, blk)
                hidden = hidden & ~chosen
                whole = ~commit & ~jnp.any(hidden, axis=1)
                live = ~done
                committed = jnp.sum(live & commit)
                # a commit is a forward of its own: both counters move
                counted = jnp.stack([
                    jnp.sum(live), jnp.sum(chosen & live[:, None]),
                    committed, committed,
                ]).astype(jnp.int32)
                out = blk
                # a commit moves the row on to its next block
                pool = {**pool,
                        "pos": pool["pos"] + size * commit.astype(jnp.int32),
                        "diffusion": pool["diffusion"] + counted}
                blk = jnp.where(commit[:, None], mask_id, blk)
                hidden = hidden | commit[:, None]
                step = jnp.where(commit, 0, step + 1)
        return (pool, blk, hidden, step, done), (out, whole)

    return body


def _zero_counters(pool: Cache) -> Cache:
    return {**pool, "stats": jnp.zeros_like(pool["stats"]),
            "diffusion": jnp.zeros_like(pool["diffusion"])}


@functools.lru_cache(maxsize=8)
def _jitted_chunk(cfg: BlockDiffusionConfig, slots: int, chunk: int):
    """One compiled program running ``chunk`` pool forwards. Pool and
    state are donated. Returns (pool, state, blocks [chunk, S, B],
    whole [chunk, S], forwards run, expert counters, diffusion
    counters)."""

    def run(params, pool, state):
        pool = _zero_counters(pool)
        body = _pool_forward(params, cfg)
        with jax.named_scope("steps"):
            (pool, blk, hidden, step, done), (out, whole) = lax.scan(
                body, (pool, state["blk"], state["hidden"], state["step"],
                       state["done"]), None, length=chunk)
        state = dict(state, blk=blk, hidden=hidden, step=step)
        return (pool, state, out, whole, jnp.int32(chunk), pool["stats"],
                pool["diffusion"])

    return jax.jit(run, donate_argnums=(1, 2))


@functools.lru_cache(maxsize=8)
def _jitted_window(cfg: BlockDiffusionConfig, slots: int, chunk: int,
                   rounds: int):
    """Up to ``rounds`` rounds of ``chunk`` pool forwards in ONE
    dispatched program (the body ``_jitted_chunk`` scans), leaving
    early once no live row is still owed tokens: ``budget`` [S] is the
    host's remaining allowance in TOKENS, less what each row yielded
    inside this window. It gates the exit only."""
    size = cfg.block_length
    total = rounds * chunk

    def run(params, pool, state, budget):
        pool = _zero_counters(pool)
        body = _pool_forward(params, cfg)
        out0 = jnp.zeros((total, slots, size), jnp.int32)
        whole0 = jnp.zeros((total, slots), jnp.bool_)

        def cond(carry):
            r, _pool, _blk, _hidden, _step, done, owed, _out, _whole = carry
            return (r < rounds) & jnp.any(~done & (owed > 0))

        def round_body(carry):
            r, pool, blk, hidden, step, done, owed, out, whole = carry
            (pool, blk, hidden, step, done), (toks, became) = lax.scan(
                body, (pool, blk, hidden, step, done), None, length=chunk)
            out = lax.dynamic_update_slice(out, toks, (r * chunk, 0, 0))
            whole = lax.dynamic_update_slice(whole, became, (r * chunk, 0))
            owed = owed - size * jnp.sum(became, axis=0, dtype=jnp.int32)
            return (r + 1, pool, blk, hidden, step, done, owed, out, whole)

        with jax.named_scope("steps"):
            (r, pool, blk, hidden, step, _done, _owed, out,
             whole) = lax.while_loop(
                cond, round_body,
                (jnp.int32(0), pool, state["blk"], state["hidden"],
                 state["step"], state["done"], budget, out0, whole0))
        state = dict(state, blk=blk, hidden=hidden, step=step)
        return (pool, state, out, whole, r * chunk, pool["stats"],
                pool["diffusion"])

    return jax.jit(run, donate_argnums=(1, 2))


@functools.lru_cache(maxsize=8)
def _jitted_admit(cfg: BlockDiffusionConfig):
    """Write one admitted row: its prefilled cache into the pool and
    its first block (the prompt's trailing part shown, the rest
    hidden) into the state. Both donated."""

    def admit(pool, state, row, slot, blk, hidden):
        pool = insert_row(pool, row, slot)
        state = {
            "blk": state["blk"].at[slot].set(blk),
            "hidden": state["hidden"].at[slot].set(hidden),
            "step": state["step"].at[slot].set(0),
            "done": state["done"].at[slot].set(False),
        }
        return pool, state

    return jax.jit(admit, donate_argnums=(0, 1))


class BlockStepProgram:
    """The family's step program (models/stepprog.py's five verbs): a
    pool of S rows, each at its own phase inside its own block. One
    ``dispatch`` runs ``chunk`` pool forwards (``rounds * chunk`` at
    most when fused); ``tokens`` hands back, per row and in order, the
    blocks that became whole in them. Nothing the next dispatch needs
    comes from the host, so the engine may look one window ahead."""

    supports_lookahead = True
    dispatch_cost = 1
    #: the engine's EnginePhases once ``attach_phases`` was called
    phases = None

    def __init__(self, cfg: BlockDiffusionConfig, params: Params,
                 max_len: int, slots: int, chunk: int, rounds: int = 1,
                 out_sharding=None) -> None:
        if slots < 1 or chunk < 1 or rounds < 1:
            raise ValueError("slots, chunk and rounds must be >= 1")
        if out_sharding is not None:
            raise ValueError("the block-diffusion program runs on one device")
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.slots = slots
        self.chunk = chunk
        self.rounds = rounds
        #: the pool's counters summed over every fetched window
        self.stats_total = None
        self.diffusion_total = np.zeros((len(DIFFUSION_COUNTERS),), np.int64)
        self.reset()

    @property
    def warm_new(self) -> int:
        """New tokens of a warm-up request that outlasts the first
        (single-chunk) dispatch, so that the fused program compiles
        before traffic: a forward pair can yield a whole block."""
        return -(-self.chunk // 2) * self.cfg.block_length + 1

    def reset(self) -> None:
        size = self.cfg.block_length
        self._pool = slot_cache(self.cfg, self.slots, self.max_len)
        self._state = {
            "blk": jnp.full((self.slots, size), self.cfg.mask_token_id,
                            jnp.int32),
            "hidden": jnp.ones((self.slots, size), jnp.bool_),
            "step": jnp.zeros((self.slots,), jnp.int32),
            "done": jnp.ones((self.slots,), jnp.bool_),
        }
        # prompt tokens at the head of each row's first block: shown
        # from the start, and no part of what the row generates
        self._given = [0] * self.slots

    def validate(self, req) -> None:
        """Refuse at submit what ``refuse_request`` refuses."""
        refuse_request({
            "temperature": req.temperature, "top_k": req.top_k,
            "top_p": req.top_p, "min_new": req.min_new,
            "presence": req.presence, "frequency": req.frequency,
            "logit_bias": req.bias_idx is not None and bool(
                np.any(np.asarray(req.bias_idx) >= 0)),
        })

    def attach_phases(self, phases) -> None:
        self.phases = phases

    def admit(self, slot: int, req, logits, row_cache) -> Optional[int]:
        """Write the prefilled row and its first block; a request's
        first tokens come with its first whole block, so there is no
        token to return, nothing is fetched, and of the children of
        ``engine.admit.first_token`` (models/stepprog.py) only
        ``insert`` opens: ONE program writes the row and its state."""
        del logits
        cfg = self.cfg
        size = cfg.block_length
        with phase_span(self.phases)("engine.admit.first_token.insert"):
            given = len(req.tokens) % size
            blk = np.full((size,), cfg.mask_token_id, np.int32)
            blk[:given] = req.tokens[len(req.tokens) - given:]
            hidden = np.arange(size) >= given
            self._pool, self._state = _jitted_admit(cfg)(
                self._pool, self._state, row_cache,
                jnp.asarray(slot, jnp.int32), jnp.asarray(blk),
                jnp.asarray(hidden))
        self._given[slot] = given
        return None

    def retire(self, slot: int) -> None:
        self._state = retire_slot(self._state, slot)

    # cpcheck: hotpath — one device call, zero host syncs
    def dispatch(self, budgets, fused: bool):
        if fused and self.rounds > 1:
            out = _jitted_window(
                self.cfg, self.slots, self.chunk, self.rounds)(
                self.params, self._pool, self._state,
                jnp.asarray(budgets, jnp.int32))
        else:
            out = _jitted_chunk(self.cfg, self.slots, self.chunk)(
                self.params, self._pool, self._state)
        self._pool, self._state = out[:2]
        return out[2:]

    # cpcheck: hotpath — the one deliberate sync per window
    def tokens(self, handle):
        out, whole, ran, stats, diffusion = jax.device_get(handle)  # cpcheck: disable=CP-HOTSYNC the per-window token fetch
        ran = int(ran)
        stats = stats.astype(np.int64)
        self.stats_total = stats if self.stats_total is None \
            else self.stats_total + stats
        self.diffusion_total += diffusion
        size = self.cfg.block_length
        whole = whole[:ran]
        valid = size * whole.sum(axis=0).astype(np.int64)
        toks = np.zeros((self.slots, max(int(valid.max()), 1)), np.int64)
        filled = [0] * self.slots
        for forward, slot in zip(*np.nonzero(whole)):
            at = filled[slot]
            toks[slot, at:at + size] = out[forward, slot]
            filled[slot] = at + size
        for slot, given in enumerate(self._given):
            if given and valid[slot]:
                # the row's first block: its prompt tokens are not output
                toks[slot, :-given] = toks[slot, given:]
                valid[slot] -= given
                self._given[slot] = 0
        return toks, valid, -(-ran // self.chunk)

    def expert_stats(self):
        return describe_stats(self.cfg, self.stats_total)

    def diffusion_stats(self) -> Dict[str, Any]:
        """``/v1/model`` ``diffusion``: the configuration's routine and
        what the fetched forwards counted, by LIVE row."""
        cfg = self.cfg
        out: Dict[str, Any] = {
            "block_length": cfg.block_length,
            "denoising_steps": cfg.denoising_steps,
            "remasking": cfg.remasking,
            "confidence_threshold": cfg.confidence_threshold,
        }
        out.update(zip(DIFFUSION_COUNTERS,
                       (int(v) for v in self.diffusion_total)))
        return out


def refuse_request(knobs: Dict[str, Any]) -> None:
    """What this family's routine does not take, under the server's
    names for a request's knobs: a token is the argmax of its own
    position's logits and is revealed by confidence, so nothing here
    sorts the vocabulary (top_k, top_p), draws (temperature), reshapes
    the logits or scores beams. Raises ValueError (the server's 422)."""
    for name in ("temperature", "top_k", "top_p", "min_new", "presence",
                 "frequency", "logit_bias", "beam_width", "logprobs"):
        if knobs.get(name):
            raise ValueError(
                f"generation by diffusion over blocks decodes greedily "
                f"by confidence: {name} is refused")


def make_step_program(cfg, params, max_len, slots, chunk, rounds=1,
                      out_sharding=None) -> BlockStepProgram:
    return BlockStepProgram(cfg, params, max_len, slots, chunk,
                            rounds=rounds, out_sharding=out_sharding)


def describe_stats(cfg: BlockDiffusionConfig, total) -> Dict[str, Any]:
    """A pool's summed ``stats`` under the names ``/v1/model``
    ``experts`` publishes (models/mla_moe.py's schema; every expert is
    held here)."""
    return {
        "published": cfg.n_experts, "held": [0, cfg.n_experts],
        "per_token": cfg.experts_per_tok,
        **named_stats(total, cfg.n_experts),
    }
