"""A second model family beside ``transformer.py``: latent attention
(MLA, DeepSeek-V2, arXiv:2405.04434) over a leading dense layer and
sparse expert layers with sigmoid routing and a shared expert
(DeepSeek-V3, arXiv:2412.19437) -- the block the A.X-K1, DeepSeek-V3
and Kimi-K2 configurations publish. Serving only.

What differs from the flagship block, and why it is a module of its
own and not flags on ``TransformerConfig``:

- **two layer kinds.** ``first_dense`` dense SwiGLU layers run first,
  then the sparse layers. ``params["layers"]`` is a LIST of per-layer
  trees and the layers are unrolled, not scanned over stacked leaves:
  on the chip a scan copies each layer's slice of every stacked leaf
  out before it is used (the 1.06 GB of a layer's held experts, every
  step; seen in the program compiled for the v5e, PERF.md PR 27),
  and a step is the read of exactly those bytes;
- **a latent cache.** A position stores ``c_kv`` (``kv_lora_rank``
  wide, after its norm) and ``k_r`` (``qk_rope_head_dim`` wide, after
  RoPE, ONE vector shared by all heads): per layer a leaf ``ckv[l]
  [batch, length, r]`` and ``kpe[l] [batch, length, dr]``, written in
  place and read where they lie;
- **two attention forms over the same numbers.** Prefill expands
  keys and values per head from the latent (``W_ukv``); decode, and
  every extension of a cache, runs the absorbed form: ``W_uk`` is
  folded into the query, the weighted sum is taken over the latents as
  stored, and ``W_uv`` is applied after it. The absorbed form is the
  only one that reads the cache as stored;
- **RoPE on a slice.** Only the ``dr`` rope dimensions of the query
  (per head) and of ``k_r`` rotate, at yarn frequencies, and the
  softmax scale is ``(dn + dr) ** -0.5 * mscale ** 2``;
- **a share of the experts.** The layer is TOLD which routed experts
  it holds (``held_lo``, ``held_n``): it scores all
  ``router_experts``, takes the top ``experts_per_tok``, normalises
  over ALL of them, and computes the part of the result its own
  experts give (``moe.sparse_experts``). On one chip the layer runs
  without its exchange; nothing stands in for the absent chips.

Weights are MADE and HELD in the compute dtype (bf16): each leaf is
drawn in float32 from its own key and rounded once, so no float32
copy of the model is ever resident (``init_params``).

The cache is batched, not stacked: the batch axis of ``ckv``/``kpe``
is the slot axis of the serving pool and ``pos`` may be one position
per row, so the slot engine's step is ONE ``decode_chunk`` over the
pool (models/slots.py) and the expert layer sees
all rows of a step at once. A pool carries one more leaf, ``stats``:
what the expert layers routed since the step program zeroed it.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.attention import NEG_INF
from . import moe
from .quantized import embed_lookup
from .transformer import _mlp, _rms_norm

Params = Dict[str, Any]
Cache = Dict[str, jax.Array]

#: rows of the vocabulary drawn from one key: a slice of the
#: vocabulary then holds the same rows as the whole
VOCAB_BLOCK = 128
#: query rows of a prefill worked on at once (scores are
#: [heads, Q_BLOCK, seq] in float32)
Q_BLOCK = 512
#: counters ahead of the per-expert loads in a pool's ``stats`` leaf
STATS_HEAD = ("rows", "assignments_here", "expert_steps_touched",
              "expert_steps", "expert_tiles", "expert_tile_rows")


@dataclass(frozen=True)
class MlaMoeConfig:
    vocab_size: int = 1024
    d_model: int = 64
    n_heads: int = 4
    q_lora_rank: int = 32
    kv_lora_rank: int = 16
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    n_layers: int = 3
    first_dense: int = 1
    d_ff: int = 128            # the dense layers' SwiGLU width
    moe_d_ff: int = 32         # one expert's SwiGLU width
    router_experts: int = 16   # the router's width: all published experts
    experts_per_tok: int = 4
    held_lo: int = 0           # this process holds experts
    held_n: int = 16           # [held_lo, held_lo + held_n)
    n_shared: int = 1
    routed_scale: float = 1.0
    norm_topk: bool = True
    rope_theta: float = 10_000.0
    # yarn (rope_scaling); factor 1 = plain RoPE
    rope_factor: float = 1.0
    rope_orig_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    #: digest of the file the configuration was read from (part of a
    #: server's warm-up fingerprint, workload/modelcfg.py)
    source_digest: str = ""

    def __post_init__(self) -> None:
        if not 0 < self.first_dense < self.n_layers:
            raise ValueError(
                "need at least one dense and one sparse layer, got "
                f"first_dense {self.first_dense} of {self.n_layers}"
            )
        if self.held_lo < 0 or self.held_n < 1 or (
            self.held_lo + self.held_n > self.router_experts
        ):
            raise ValueError(
                f"held experts [{self.held_lo}, "
                f"{self.held_lo + self.held_n}) lie outside the "
                f"router's {self.router_experts}"
            )
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")

    # what the serving code asks of any configuration
    window = 0
    kv_int8 = False

    @property
    def kv_heads(self) -> int:
        return self.n_heads

    @property
    def head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def n_sparse(self) -> int:
        return self.n_layers - self.first_dense

    @property
    def family(self):
        import sys

        return sys.modules[__name__]

    @property
    def softmax_scale(self) -> float:
        scale = self.head_dim ** -0.5
        if self.rope_factor > 1.0 and self.rope_mscale_all_dim:
            m = 0.1 * self.rope_mscale_all_dim * math.log(
                self.rope_factor) + 1.0
            scale *= m * m
        return scale


def from_published(config: Dict[str, Any], max_seq_len: int,
                   source_digest: str = "") -> MlaMoeConfig:
    """The configuration from a published ``config.json``'s own keys
    (DeepSeek-V3's, which A.X-K1 shares letter for letter). A
    ``share`` group says which of the routed experts this process
    holds and how wide the router is; without it all are held."""
    share = config.get("share", {})
    held = share.get("held_experts", [0, config["n_routed_experts"]])
    rope = config.get("rope_scaling") or {}
    if rope and rope.get("type") != "yarn":
        raise ValueError(f"rope_scaling type {rope.get('type')!r} is "
                         "not supported (yarn or none)")
    if abs(float(config.get("rms_norm_eps", 1e-6)) - 1e-6) > 1e-12:
        raise ValueError("this block's RMSNorm fixes eps 1e-6")
    for key, want in (("scoring_func", "sigmoid"), ("hidden_act", "silu")):
        if config.get(key, want) != want:
            raise ValueError(f"{key} {config[key]!r}: only {want!r}")
    if config.get("topk_method", "none") not in ("none", "greedy"):
        raise ValueError("only plain top-k routing (topk_method none)")
    if int(config.get("moe_layer_freq", 1)) != 1:
        raise ValueError("moe_layer_freq must be 1")
    mscale = float(rope.get("mscale", 1.0))
    mscale_all = float(rope.get("mscale_all_dim", 0.0))
    if rope and mscale != mscale_all:
        raise ValueError("cos and sin are unscaled only where mscale "
                         "equals mscale_all_dim")
    return MlaMoeConfig(
        vocab_size=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        q_lora_rank=int(config["q_lora_rank"]),
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_head_dim=int(config["qk_nope_head_dim"]),
        qk_rope_head_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]),
        n_layers=int(config["num_hidden_layers"]),
        first_dense=int(config["first_k_dense_replace"]),
        d_ff=int(config["intermediate_size"]),
        moe_d_ff=int(config["moe_intermediate_size"]),
        router_experts=int(share.get("router_experts",
                                     config["n_routed_experts"])),
        experts_per_tok=int(config["num_experts_per_tok"]),
        held_lo=int(held[0]), held_n=int(held[1]) - int(held[0]),
        n_shared=int(config.get("n_shared_experts", 0)),
        routed_scale=float(config.get("routed_scaling_factor", 1.0)),
        norm_topk=bool(config.get("norm_topk_prob", True)),
        rope_theta=float(config.get("rope_theta", 10_000.0)),
        rope_factor=float(rope.get("factor", 1.0)),
        rope_orig_max=int(rope.get("original_max_position_embeddings",
                                   4096)),
        rope_beta_fast=float(rope.get("beta_fast", 32.0)),
        rope_beta_slow=float(rope.get("beta_slow", 1.0)),
        rope_mscale=mscale, rope_mscale_all_dim=mscale_all,
        max_seq_len=max_seq_len, source_digest=source_digest,
    )


# -- weights ------------------------------------------------------------

#: a leaf's key is PRNGKey(0) folded with its layer (TOP for the
#: embedding and the head) and then with its number here; an expert's
#: with its global index after that, a vocabulary block's with its
#: block index: the same numbers whatever share is held
LEAF = {name: i for i, name in enumerate((
    "w_dq", "w_uq", "w_dkv", "w_ukv", "w_o", "w_gate", "w_up", "w_down",
    "router", "s_gate", "s_up", "s_down", "e_gate", "e_up", "e_down",
    "embed", "unembed",
))}
TOP = 1_000_000


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, fan_in_scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * fan_in_scale).astype(dtype)


def _leaf_key(layer: int, name: str):
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(0), layer), LEAF[name])


def _attention_shapes(cfg: MlaMoeConfig):
    d, h = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    return {
        "w_dq": ((d, rq), d), "w_uq": ((rq, h, dn + dr), rq),
        "w_dkv": ((d, rkv + dr), d), "w_ukv": ((rkv, h, dn + dv), rkv),
        "w_o": ((h, dv, d), h * dv),
    }


def _layer_leaves(cfg: MlaMoeConfig, layer: int) -> Dict[str, jax.Array]:
    """One layer's leaves, each drawn in float32 inside a program of
    its own and rounded there: what stays on the device is bf16."""
    d, dt = cfg.d_model, cfg.dtype
    shapes = dict(_attention_shapes(cfg))
    sparse = layer >= cfg.first_dense
    if sparse:
        fs = cfg.moe_d_ff * max(cfg.n_shared, 1)
        shapes.update({
            "router": ((d, cfg.router_experts), d),
            "s_gate": ((d, fs), d), "s_up": ((d, fs), d),
            "s_down": ((fs, d), fs),
        })
    else:
        f = cfg.d_ff
        shapes.update({"w_gate": ((d, f), d), "w_up": ((d, f), d),
                       "w_down": ((f, d), f)})
    out = {
        name: _draw(_leaf_key(layer, name), shape, fan_in ** -0.5, dt)
        for name, (shape, fan_in) in shapes.items()
    }
    if sparse:
        f = cfg.moe_d_ff
        experts = cfg.held_lo + jnp.arange(cfg.held_n)
        for name, shape, fan_in in (("e_gate", (d, f), d),
                                    ("e_up", (d, f), d),
                                    ("e_down", (f, d), f)):
            keys = jax.vmap(
                lambda e, name=name: jax.random.fold_in(
                    _leaf_key(layer, name), e))(experts)
            out[name] = jax.vmap(
                lambda k, shape=shape, fan_in=fan_in: _draw(
                    k, shape, fan_in ** -0.5, dt))(keys)
    out["norm_attn"] = jnp.ones((d,), jnp.float32)
    out["norm_mlp"] = jnp.ones((d,), jnp.float32)
    out["norm_q"] = jnp.ones((cfg.q_lora_rank,), jnp.float32)
    out["norm_kv"] = jnp.ones((cfg.kv_lora_rank,), jnp.float32)
    return out


def _vocab_leaf(cfg: MlaMoeConfig, name: str, scale: float) -> jax.Array:
    """[vocab, d] drawn block by block of VOCAB_BLOCK rows."""
    if cfg.vocab_size % VOCAB_BLOCK:
        raise ValueError(f"vocab_size must be a multiple of {VOCAB_BLOCK}")
    blocks = jnp.arange(cfg.vocab_size // VOCAB_BLOCK)
    key = _leaf_key(TOP, name)
    rows = jax.vmap(lambda b: _draw(
        jax.random.fold_in(key, b), (VOCAB_BLOCK, cfg.d_model), scale,
        cfg.dtype))(blocks)
    return rows.reshape(cfg.vocab_size, cfg.d_model)


def init_params(rng: Any, cfg: MlaMoeConfig) -> Params:
    """Seeded weights, made leaf by leaf and held in ``cfg.dtype``.
    ``rng`` is unused: every key derives from ``PRNGKey(0)`` and the
    leaf's place in the model, so the benchmark's reference can make
    the same numbers from the same recipe (and a share of the experts
    or of the vocabulary holds the numbers the whole model has
    there)."""
    del rng

    return {
        "embed": _vocab_leaf(cfg, "embed", 0.02),
        "layers": [_layer_leaves(cfg, i) for i in range(cfg.n_layers)],
        "norm_out": jnp.ones((cfg.d_model,), jnp.float32),
        # the head is stored [d, vocab] like the flagship's
        "unembed": _vocab_leaf(cfg, "unembed", cfg.d_model ** -0.5).T,
    }


# -- pieces of a layer ----------------------------------------------------


def _inv_freq(cfg: MlaMoeConfig) -> jax.Array:
    """RoPE frequencies of the rope slice; yarn's blend of the
    published and the stretched ones where ``rope_factor`` > 1
    (arXiv:2309.00071; HF ``DeepseekV3YarnRotaryEmbedding``)."""
    dim = cfg.qk_rope_head_dim
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    extra = cfg.rope_theta ** (-2.0 * i / dim)
    if cfg.rope_factor <= 1.0:
        return extra

    def correction(beta: float) -> float:
        return dim * math.log(
            cfg.rope_orig_max / (beta * 2 * math.pi)
        ) / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(correction(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction(cfg.rope_beta_slow)), dim - 1)
    span = (high - low) or 0.001
    mask = 1.0 - jnp.clip((i - low) / span, 0.0, 1.0)
    inter = extra / cfg.rope_factor
    return inter * (1.0 - mask) + extra * mask


def _rope(x: jax.Array, positions: jax.Array, cfg: MlaMoeConfig):
    """x: [b, m, ..., dr] rotated at ``positions`` [b, m]; the
    half-split pairing of ``transformer._rope``."""
    half = cfg.qk_rope_head_dim // 2
    angles = positions.astype(jnp.float32)[..., None] * _inv_freq(cfg)
    while angles.ndim < x.ndim:
        angles = angles[:, :, None]
    cos = jnp.cos(angles).astype(x.dtype)
    sin = jnp.sin(angles).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _latents(x, lp, cfg: MlaMoeConfig, positions):
    """Pre-norm, both down-projections with their norms, the query's
    up-projection and RoPE. Returns (q_n [b,m,H,dn], q_r [b,m,H,dr],
    c_kv [b,m,r], k_r [b,m,dr])."""
    dt = cfg.dtype
    dn, rkv = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    h = _rms_norm(x, lp["norm_attn"])
    with jax.named_scope("attn"), jax.named_scope("attn.q_lora"):
        c_q = jnp.einsum("bmd,dr->bmr", h, lp["w_dq"].astype(dt),
                         preferred_element_type=jnp.float32).astype(dt)
        c_q = _rms_norm(c_q, lp["norm_q"])
        q = jnp.einsum("bmr,rhk->bmhk", c_q, lp["w_uq"].astype(dt),
                       preferred_element_type=jnp.float32).astype(dt)
    with jax.named_scope("attn"), jax.named_scope("attn.kv_lora"):
        ckr = jnp.einsum("bmd,dr->bmr", h, lp["w_dkv"].astype(dt),
                         preferred_element_type=jnp.float32).astype(dt)
        c_kv = _rms_norm(ckr[..., :rkv], lp["norm_kv"])
    with jax.named_scope("attn"), jax.named_scope("attn.rope"):
        q_r = _rope(q[..., dn:], positions, cfg)
        k_r = _rope(ckr[..., rkv:], positions, cfg)
    return q[..., :dn], q_r, c_kv, k_r


def _attn_out(x, o, lp, cfg: MlaMoeConfig):
    with jax.named_scope("attn"), jax.named_scope("attn.out"):
        out = jnp.einsum("bmhv,hvd->bmd", o, lp["w_o"].astype(cfg.dtype),
                         preferred_element_type=jnp.float32)
        return x + out.astype(cfg.dtype)


def _expanded_attention(q_n, q_r, c_kv, k_r, lp, cfg: MlaMoeConfig):
    """Causal attention of a whole sequence from position 0, keys and
    values expanded per head from the latent. [b, s, H, dv]."""
    dt, dn = cfg.dtype, cfg.qk_nope_head_dim
    b, s = c_kv.shape[:2]
    kv = jnp.einsum("bsr,rhk->bshk", c_kv, lp["w_ukv"].astype(dt),
                    preferred_element_type=jnp.float32).astype(dt)
    k_n, v = kv[..., :dn], kv[..., dn:]
    block = Q_BLOCK if s > Q_BLOCK and s % Q_BLOCK == 0 else s
    cols = jnp.arange(s)

    def rows(start):
        qn = lax.dynamic_slice_in_dim(q_n, start, block, axis=1)
        qr = lax.dynamic_slice_in_dim(q_r, start, block, axis=1)
        scores = (
            jnp.einsum("bqhd,bkhd->bhqk", qn, k_n,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("bqhd,bkd->bhqk", qr, k_r,
                         preferred_element_type=jnp.float32)
        ) * cfg.softmax_scale
        mask = cols[None, :] <= (start + jnp.arange(block))[:, None]
        weights = jax.nn.softmax(
            jnp.where(mask[None, None], scores, NEG_INF), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", weights.astype(dt), v,
                          preferred_element_type=jnp.float32).astype(dt)

    if block == s:
        return rows(0)
    out = lax.map(rows, jnp.arange(0, s, block))  # [n, b, block, H, dv]
    return jnp.moveaxis(out, 0, 1).reshape(b, s, *out.shape[3:])


def _absorbed_attention(q_n, q_r, ckv, kpe, valid, lp, cfg: MlaMoeConfig):
    """Attention of m new positions over a cache's latents AS STORED
    (``ckv`` [b, T, r], ``kpe`` [b, T, dr]; ``valid`` [b, m, T]).
    ``W_uk`` is folded into the query and ``W_uv`` applied after the
    weighted sum, so nothing per head is ever built of the cache."""
    dt, dn = cfg.dtype, cfg.qk_nope_head_dim
    w_ukv = lp["w_ukv"].astype(dt)
    with jax.named_scope("attn"), jax.named_scope("attn.absorb"):
        q_c = jnp.einsum("bmhn,rhn->bmhr", q_n, w_ukv[..., :dn],
                         preferred_element_type=jnp.float32).astype(dt)
    with jax.named_scope("attn"), jax.named_scope("attn.scores"):
        scores = (
            jnp.einsum("bmhr,btr->bhmt", q_c, ckv,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("bmhp,btp->bhmt", q_r, kpe,
                         preferred_element_type=jnp.float32)
        ) * cfg.softmax_scale
        weights = jax.nn.softmax(
            jnp.where(valid[:, None], scores, NEG_INF), axis=-1)
        # float32 weights over the stored latents in three bf16 passes:
        # as exact as the accumulation, and the passes hide behind the
        # read of the cache (PERF.md, PR 26, on the flagship's values)
        u = jnp.einsum("bhmt,btr->bmhr", weights, ckv,
                       preferred_element_type=jnp.float32,
                       precision=lax.Precision.HIGH).astype(dt)
    with jax.named_scope("attn"), jax.named_scope("attn.absorb"):
        return jnp.einsum("bmhr,rhv->bmhv", u, w_ukv[..., dn:],
                          preferred_element_type=jnp.float32).astype(dt)


def _swiglu(h, w_gate, w_up, w_down, dt):
    gate = jnp.einsum("nd,df->nf", h, w_gate.astype(dt),
                      preferred_element_type=jnp.float32)
    up = jnp.einsum("nd,df->nf", h, w_up.astype(dt),
                    preferred_element_type=jnp.float32)
    act = (jax.nn.silu(gate) * up).astype(dt)
    return jnp.einsum("nf,fd->nd", act, w_down.astype(dt),
                      preferred_element_type=jnp.float32)


def _sparse_ffn(x, lp, cfg: MlaMoeConfig):
    """The expert layer + residual. Returns (x, assignments per held
    expert [held_n] int32)."""
    dt = cfg.dtype
    b, m, d = x.shape
    with jax.named_scope("mlp"):
        h = _rms_norm(x, lp["norm_mlp"]).reshape(b * m, d)
        idx, gate = moe.route_topk(
            h, lp["router"], cfg.experts_per_tok, cfg.routed_scale,
            cfg.norm_topk)
        routed, counts = moe.sparse_experts(
            h, idx, gate, lp["e_gate"], lp["e_up"], lp["e_down"],
            cfg.held_lo, cfg.router_experts)
        if cfg.n_shared:
            with jax.named_scope("mlp.shared"):
                routed = routed + _swiglu(
                    h, lp["s_gate"], lp["s_up"], lp["s_down"], dt)
        return x + routed.astype(dt).reshape(b, m, d), counts


# -- the cache ------------------------------------------------------------


def init_cache(cfg: MlaMoeConfig, batch: int, max_len: int) -> Cache:
    """Zeroed latent cache: per layer ``ckv[l]`` [batch, length, r]
    and ``kpe[l]`` [batch, length, dr] in the compute dtype; ``pos``
    is one number (every row at the same position) until a pool makes
    it one per row (``slot_cache``)."""
    def leaves(width):
        return [jnp.zeros((batch, max_len, width), cfg.dtype)
                for _ in range(cfg.n_layers)]

    return {
        "pos": jnp.zeros((), jnp.int32),
        "ckv": leaves(cfg.kv_lora_rank),
        "kpe": leaves(cfg.qk_rope_head_dim),
    }


def slot_cache(cfg: MlaMoeConfig, slots: int, max_len: int) -> Cache:
    """The serving pool: the cache with one row and one position per
    slot, and the expert layers' counters."""
    pool = init_cache(cfg, slots, max_len)
    pool["pos"] = jnp.zeros((slots,), jnp.int32)
    pool["stats"] = jnp.zeros((len(STATS_HEAD) + cfg.held_n,), jnp.int32)
    return pool


def insert_row(pool: Cache, row: Cache, slot: jax.Array) -> Cache:
    """Write a one-row cache (``prefill``'s) into ``slot``: the whole
    row and its position, so nothing of the slot's last occupant is
    left."""
    new = dict(pool)
    for name in ("ckv", "kpe"):
        new[name] = [
            lax.dynamic_update_slice(big, small.astype(big.dtype),
                                     (slot, 0, 0))
            for big, small in zip(pool[name], row[name])]
    new["pos"] = lax.dynamic_update_slice(
        pool["pos"], jnp.reshape(row["pos"], (1,)).astype(jnp.int32), (slot,))
    return new


def _count(stats: jax.Array, rows: int, counts, block: int) -> jax.Array:
    """Add one chunk's routing to a pool's counters; ``counts`` holds
    each sparse layer's assignments per held expert ([held_n]),
    ``block`` the rows of the row tiles the experts' kernel ran them in
    (``moe.expert_block`` of the chunk)."""
    counts = jnp.stack(counts)
    tiles = moe.expert_tiles(counts, block)
    head = jnp.stack([
        jnp.int32(rows * counts.shape[0]), jnp.sum(counts),
        jnp.sum(counts > 0), jnp.int32(counts.size), tiles, tiles * block,
    ]).astype(jnp.int32)
    return stats + jnp.concatenate([head, jnp.sum(counts, axis=0)])


# -- forward, prefill, decode --------------------------------------------


def _logits(params: Params, x: jax.Array, cfg: MlaMoeConfig) -> jax.Array:
    with jax.named_scope("head"):
        x = _rms_norm(x, params["norm_out"])
        return jnp.einsum("bsd,dv->bsv", x,
                          params["unembed"].astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


def _hidden(params: Params, tokens: jax.Array, cfg: MlaMoeConfig):
    """tokens [b, s] from position 0 -> (hidden [b, s, d], the
    latents of every layer: lists of c_kv [b, s, r] and k_r [b, s,
    dr]). Expanded-form attention."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = embed_lookup(params, tokens, cfg.dtype)

    kept_c, kept_r = [], []
    with jax.named_scope("layers"):
        for layer, lp in enumerate(params["layers"]):
            q_n, q_r, c_kv, k_r = _latents(x, lp, cfg, positions)
            with jax.named_scope("attn"), jax.named_scope("attn.scores"):
                o = _expanded_attention(q_n, q_r, c_kv, k_r, lp, cfg)
            x = _attn_out(x, o, lp, cfg)
            if layer < cfg.first_dense:
                x = _mlp(x, lp, cfg)
            else:
                x, _counts = _sparse_ffn(x, lp, cfg)
            kept_c.append(c_kv)
            kept_r.append(k_r)
    return x, (kept_c, kept_r)


def forward(params: Params, tokens: jax.Array, cfg: MlaMoeConfig):
    """tokens [b, s] -> logits [b, s, vocab] float32."""
    x, _kept = _hidden(params, tokens, cfg)
    return _logits(params, x, cfg)


def prefill(params: Params, tokens: jax.Array, cfg: MlaMoeConfig,
            max_len: int) -> Tuple[jax.Array, Cache]:
    """Process the prompt; returns (logits of the last position, the
    cache holding its latents)."""
    b, s = tokens.shape
    x, (c_kv, k_r) = _hidden(params, tokens, cfg)
    with jax.named_scope("attn"), jax.named_scope("attn.kv_write"):
        cache = init_cache(cfg, b, max_len)
        cache["ckv"] = [lax.dynamic_update_slice(big, new, (0, 0, 0))
                        for big, new in zip(cache["ckv"], c_kv)]
        cache["kpe"] = [lax.dynamic_update_slice(big, new, (0, 0, 0))
                        for big, new in zip(cache["kpe"], k_r)]
    cache["pos"] = jnp.asarray(s, jnp.int32)
    return _logits(params, x[:, -1:, :], cfg)[:, 0, :], cache


def decode_chunk(params: Params, cache: Cache, tokens: jax.Array,
                 cfg: MlaMoeConfig) -> Tuple[jax.Array, Cache]:
    """m tokens per row against the cache in one forward, absorbed
    form. ``tokens[:, i]`` sits at ``pos + i`` of its row; ``pos`` is
    one number or one per row. Each layer's leaves are written in
    place and read where they lie."""
    pos = cache["pos"]
    b, m = tokens.shape
    length = cache["ckv"][0].shape[1]
    rows = jnp.arange(b)[:, None]
    q_pos = jnp.broadcast_to(pos, (b,))[:, None] + jnp.arange(m)  # [b, m]
    valid = jnp.arange(length)[None, None, :] <= q_pos[:, :, None]
    x = embed_lookup(params, tokens, cfg.dtype)
    new_ckv, new_kpe, counts = [], [], []
    with jax.named_scope("layers"):
        for layer, lp in enumerate(params["layers"]):
            ckv, kpe = cache["ckv"][layer], cache["kpe"][layer]
            q_n, q_r, c_kv, k_r = _latents(x, lp, cfg, q_pos)
            with jax.named_scope("attn"), jax.named_scope("attn.kv_write"):
                if pos.ndim == 0:
                    ckv = lax.dynamic_update_slice(ckv, c_kv, (0, pos, 0))
                    kpe = lax.dynamic_update_slice(kpe, k_r, (0, pos, 0))
                else:
                    # a dead slot decodes on past the end: dropped there
                    ckv = ckv.at[rows, q_pos].set(c_kv, mode="drop")
                    kpe = kpe.at[rows, q_pos].set(k_r, mode="drop")
            o = _absorbed_attention(q_n, q_r, ckv, kpe, valid, lp, cfg)
            x = _attn_out(x, o, lp, cfg)
            if layer < cfg.first_dense:
                x = _mlp(x, lp, cfg)
            else:
                x, layer_counts = _sparse_ffn(x, lp, cfg)
                counts.append(layer_counts)
            new_ckv.append(ckv)
            new_kpe.append(kpe)
    new = {**cache, "ckv": new_ckv, "kpe": new_kpe, "pos": pos + m}
    if "stats" in cache:
        new["stats"] = _count(
            cache["stats"], b * m, counts, moe.expert_block(
                b * m, cfg.experts_per_tok, cfg.router_experts))
    return _logits(params, x, cfg), new


def named_stats(total, held_n: int) -> Dict[str, Any]:
    """A pool's summed ``stats`` by name: the counters of
    ``STATS_HEAD``, ``tile_fill`` (the share of the experts' row tiles'
    rows that held a token) and ``load`` (assignments per held
    expert)."""
    head = len(STATS_HEAD)
    values = [0] * (head + held_n) if total is None else [
        int(v) for v in total]
    out: Dict[str, Any] = dict(zip(STATS_HEAD, values[:head]))
    out["tile_fill"] = (
        out["assignments_here"] / out["expert_tile_rows"]
        if out["expert_tile_rows"] else None)
    out["load"] = values[head:]
    return out


def describe_stats(cfg: MlaMoeConfig, total) -> Dict[str, Any]:
    """A pool's summed ``stats`` under the names ``/v1/model``
    ``experts`` publishes (docs/90-observability.md)."""
    return {
        "published": cfg.router_experts,
        "held": [cfg.held_lo, cfg.held_lo + cfg.held_n],
        "per_token": cfg.experts_per_tok,
        **named_stats(total, cfg.held_n),
    }
