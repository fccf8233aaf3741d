"""A fourth model family: state-space (Mamba-2) mixers among attention
mixers in a DECLARED per-layer pattern, every layer over routed experts
with a shared expert (``model_type`` ``granitemoehybrid``). Serving
only.

What differs from the three families beside it, and why it is a module
of its own:

- **a layer's kind is data.** ``layer_types`` names each layer
  ``mamba`` or ``attention``; ``params["layers"]`` is a LIST of
  per-layer trees whose leaves follow the kind, unrolled (see
  models/mla_moe.py for why not scanned);
- **a mixer that is not attention** (Mamba-2, arXiv:2405.21060). With
  ``h = RMSNorm(x)``: ``[z | xBC | dt] = h W_in``; ``xBC_t <-
  silu(b_c + sum_j w_c[j] xBC_{t-3+j})``, a causal depthwise
  convolution; ``xBC`` splits into ``x_t`` [heads, head_dim], ``B_t``
  and ``C_t`` [state]; per head ``Delta_t = softplus(dt_t + dt_bias)``
  and ``S_t = exp(-Delta_t e^{A_log}) S_{t-1} + Delta_t x_t (x) B_t``;
  ``y_t = S_t C_t + D x_t``; the mixer gives ``W_out RMSNorm(y_t *
  silu(z_t))``, the norm over all heads at once, after the gate. A
  prompt runs the recurrence in chunks of ``ssm_chunk`` positions
  (``_ssm_seq``: inside a chunk a masked matrix of decays, between
  chunks the state), a decode step is the recurrence itself
  (``_ssm_step``). Sums over time (``Delta``, the decays, ``S``, the
  gated norm's statistics) are float32; the projections are matmuls in
  the compute dtype with float32 accumulation;
- **state that is not keys and values.** A mamba layer keeps, per row,
  ``S`` [heads, head_dim, state] in float32 and the last ``d_conv - 1``
  inputs of the convolution: they do not grow with the context and
  cannot be rewound to a shorter prefix (``recurrent_state``: the
  server refuses the prefix cache and the spill tier with it). An
  attention layer keeps keys and values by position as the flagship
  does. One cache holds both: ``ssm[j]`` / ``conv[j]`` for the j-th
  mamba layer, ``k[j]`` / ``v[j]`` for the j-th attention layer, one
  ``pos`` per row;
- **attention without positions.** No rotation (``nope``); scores are
  ``q . k * attention_multiplier``; grouped heads over the cache as
  stored (models/decode.py ``_grouped_attention``);
- **four multipliers.** ``x_0 = embedding_multiplier E[token]``; every
  mixer's and every expert layer's result enters the stream times
  ``residual_multiplier``; the head is the embedding (tied) and its
  logits are divided by ``logits_scaling``;
- **the experts.** ``moe.route_softmax`` (a softmax over the chosen
  ten equals the softmax over all, the chosen renormalised), a HELD
  share of them through ``moe.sparse_experts``, and a shared expert of
  its own width.

Weights follow models/mla_moe.py's recipe (made and held in bf16, a key
per leaf, expert and vocabulary block); the mixer's three vectors are
float32 and seeded in Mamba-2's own ranges.

A pool carries ``stats``: models/mla_moe.py's expert counters, then
``ssm_row_steps`` (rows x mamba layers stepped; every row of the pool
steps, a retired one too, on pads).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import mla_moe, moe
from .decode import _grouped_attention
from .mla_moe import STATS_HEAD, TOP, VOCAB_BLOCK, _count, _draw, _swiglu
from .quantized import embed_lookup
from .transformer import _rms_norm

Params = Dict[str, Any]
Cache = Dict[str, Any]

#: query rows of a prefill's attention worked on at once
Q_BLOCK = 512
KINDS = ("mamba", "attention")
F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST


@dataclass(frozen=True)
class HybridSsmConfig:
    vocab_size: int = 512
    d_model: int = 64
    layer_types: Tuple[str, ...] = ("mamba", "attention")
    n_heads: int = 4
    n_kv_heads: int = 2
    attention_multiplier: float = 0.0625
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    ssm_heads: int = 8
    ssm_head_dim: int = 16
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_chunk: int = 256
    moe_d_ff: int = 32        # one routed expert's SwiGLU width
    shared_d_ff: int = 64     # the shared expert's; 0 = none
    router_experts: int = 8   # the router's width: all published experts
    experts_per_tok: int = 2
    held_lo: int = 0          # this process holds experts
    held_n: int = 8           # [held_lo, held_lo + held_n)
    rms_eps: float = 1e-5
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    #: digest of the file the configuration was read from (part of a
    #: server's warm-up fingerprint, workload/modelcfg.py)
    source_digest: str = ""

    def __post_init__(self) -> None:
        unknown = sorted(set(self.layer_types) - set(KINDS))
        if unknown or not self.layer_types:
            raise ValueError(f"layer_types holds {unknown or 'nothing'}: "
                             f"each layer is one of {', '.join(KINDS)}")
        if self.d_model % self.n_heads or self.n_heads % self.n_kv_heads:
            raise ValueError("num_attention_heads must divide hidden_size "
                             "and num_key_value_heads the heads")
        if self.held_lo < 0 or self.held_n < 1 or (
            self.held_lo + self.held_n > self.router_experts
        ):
            raise ValueError(
                f"held experts [{self.held_lo}, "
                f"{self.held_lo + self.held_n}) lie outside the "
                f"router's {self.router_experts}")
        if not 1 <= self.experts_per_tok <= self.router_experts:
            raise ValueError("num_experts_per_tok lies outside the experts")
        if self.ssm_conv < 2 or self.ssm_chunk < 1:
            raise ValueError("mamba_d_conv must be >= 2 and "
                             "mamba_chunk_size >= 1")

    # what the serving code asks of any configuration
    window = 0
    kv_int8 = False
    #: a row's state cannot be cut back to a shorter prefix
    recurrent_state = True

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: x, B and C (one group)."""
        return self.d_inner + 2 * self.ssm_state

    @property
    def n_mamba(self) -> int:
        return self.layer_types.count("mamba")

    @property
    def n_attention(self) -> int:
        return self.layer_types.count("attention")

    @property
    def family(self):
        import sys

        return sys.modules[__name__]


def from_published(config: Dict[str, Any], max_seq_len: int,
                   source_digest: str = "") -> HybridSsmConfig:
    """The configuration from a published ``config.json``'s own keys
    (``granitemoehybrid``'s). ``num_local_experts`` counts the experts
    HELD; a ``share`` group gives the router's width and which experts
    those are (without it all are held)."""
    for key, want in (
        ("hidden_act", "silu"), ("normalization_function", "rmsnorm"),
        ("position_embedding_type", "nope"), ("attention_bias", False),
        ("mamba_proj_bias", False), ("mamba_conv_bias", True),
        ("mamba_n_groups", 1), ("tie_word_embeddings", True),
    ):
        if config.get(key, want) != want:
            raise ValueError(f"{key} {config[key]!r}: only {want!r}")
    layer_types = tuple(config["layer_types"])
    if len(layer_types) != int(config["num_hidden_layers"]):
        raise ValueError(
            f"layer_types names {len(layer_types)} layers, "
            f"num_hidden_layers {config['num_hidden_layers']}")
    heads, head_dim = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    if heads * head_dim != int(config["mamba_expand"]) * int(
            config["hidden_size"]):
        raise ValueError("mamba_n_heads x mamba_d_head must equal "
                         "mamba_expand x hidden_size")
    share = config.get("share", {})
    held = share.get("held_experts", [0, config["num_local_experts"]])
    if int(held[1]) - int(held[0]) != int(config["num_local_experts"]):
        raise ValueError("share.held_experts must span num_local_experts")
    return HybridSsmConfig(
        vocab_size=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        layer_types=layer_types,
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        attention_multiplier=float(config["attention_multiplier"]),
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        ssm_heads=heads, ssm_head_dim=head_dim,
        ssm_state=int(config["mamba_d_state"]),
        ssm_conv=int(config["mamba_d_conv"]),
        ssm_chunk=int(config["mamba_chunk_size"]),
        moe_d_ff=int(config["intermediate_size"]),
        shared_d_ff=int(config.get("shared_intermediate_size", 0)),
        router_experts=int(share.get("router_experts",
                                     config["num_local_experts"])),
        experts_per_tok=int(config["num_experts_per_tok"]),
        held_lo=int(held[0]), held_n=int(held[1]) - int(held[0]),
        rms_eps=float(config["rms_norm_eps"]),
        max_seq_len=max_seq_len, source_digest=source_digest,
    )


# -- weights ------------------------------------------------------------

#: a leaf's key is PRNGKey(0) folded with its layer (TOP for the
#: embedding) and then with its number here; an expert's with its
#: GLOBAL index after that, a vocabulary block's with its block index
LEAF = {name: i for i, name in enumerate((
    "w_in", "conv_w", "conv_b", "w_out", "a_log", "dt_bias",
    "wq", "wk", "wv", "wo",
    "router", "s_gate", "s_up", "s_down", "e_gate", "e_up", "e_down",
    "embed",
))}
#: the embedding's scale. It is also the head (tied) and ``x_0`` is
#: ``embedding_multiplier`` times it: at the recipe's usual 0.02 a
#: seeded model's largest logit is always the input token's own (20
#: standard deviations of the others at the published widths), greedy
#: decoding repeats one token and no error in a layer can move the
#: choice; at 0.001 the token's own logit lies inside the others' spread
EMBED_SCALE = 0.001
#: the ranges the mixer's vectors are seeded in (Mamba-2's own
#: initialisation): A uniform, Delta's bias the inverse softplus of a
#: uniform step
A_RANGE = (1.0, 16.0)
DT_RANGE = (0.001, 0.1)


def _leaf_key(layer: int, name: str):
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(0), layer), LEAF[name])


def _layer_leaves(cfg: HybridSsmConfig, layer: int) -> Dict[str, Any]:
    d, dt = cfg.d_model, cfg.dtype
    if cfg.layer_types[layer] == "mamba":
        di, heads = cfg.d_inner, cfg.ssm_heads
        shapes = {
            "w_in": ((d, di + cfg.conv_dim + heads), d),
            "conv_w": ((cfg.ssm_conv, cfg.conv_dim), cfg.ssm_conv),
            "conv_b": ((cfg.conv_dim,), cfg.ssm_conv),
            "w_out": ((di, d), di),
        }
    else:
        h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        shapes = {
            "wq": ((d, h, hd), d), "wk": ((d, kv, hd), d),
            "wv": ((d, kv, hd), d), "wo": ((h, hd, d), h * hd),
        }
    shapes["router"] = ((d, cfg.router_experts), d)
    if cfg.shared_d_ff:
        fs = cfg.shared_d_ff
        shapes.update({"s_gate": ((d, fs), d), "s_up": ((d, fs), d),
                       "s_down": ((fs, d), fs)})
    out = {
        name: _draw(_leaf_key(layer, name), shape, fan_in ** -0.5, dt)
        for name, (shape, fan_in) in shapes.items()
    }
    f = cfg.moe_d_ff
    experts = cfg.held_lo + jnp.arange(cfg.held_n)
    for name, shape, fan_in in (("e_gate", (d, f), d), ("e_up", (d, f), d),
                                ("e_down", (f, d), f)):
        keys = jax.vmap(
            lambda e, name=name: jax.random.fold_in(
                _leaf_key(layer, name), e))(experts)
        out[name] = jax.vmap(
            lambda k, shape=shape, fan_in=fan_in: _draw(
                k, shape, fan_in ** -0.5, dt))(keys)
    if cfg.layer_types[layer] == "mamba":
        heads = cfg.ssm_heads
        out["a_log"] = jnp.log(jax.random.uniform(
            _leaf_key(layer, "a_log"), (heads,), F32, *A_RANGE))
        step = jax.random.uniform(
            _leaf_key(layer, "dt_bias"), (heads,), F32, *DT_RANGE)
        out["dt_bias"] = jnp.log(jnp.expm1(step))
        out["d_skip"] = jnp.ones((heads,), F32)
        out["norm_ssm"] = jnp.ones((cfg.d_inner,), F32)
    out["norm_mixer"] = jnp.ones((d,), F32)
    out["norm_mlp"] = jnp.ones((d,), F32)
    return out


def init_params(rng: Any, cfg: HybridSsmConfig) -> Params:
    """Seeded weights, made leaf by leaf and held in ``cfg.dtype``
    (``rng`` is unused: see models/mla_moe.py ``init_params``). The
    embedding is also the head."""
    del rng
    if cfg.vocab_size % VOCAB_BLOCK:
        raise ValueError(f"vocab_size must be a multiple of {VOCAB_BLOCK}")
    key = _leaf_key(TOP, "embed")
    rows = jax.vmap(lambda b: _draw(
        jax.random.fold_in(key, b), (VOCAB_BLOCK, cfg.d_model), EMBED_SCALE,
        cfg.dtype))(jnp.arange(cfg.vocab_size // VOCAB_BLOCK))
    return {
        "embed": rows.reshape(cfg.vocab_size, cfg.d_model),
        "layers": [_layer_leaves(cfg, i) for i in range(cfg.n_layers)],
        "norm_out": jnp.ones((cfg.d_model,), F32),
    }


# -- pieces of a layer ----------------------------------------------------


def _residual(x: jax.Array, out: jax.Array, cfg: HybridSsmConfig):
    """``x + residual_multiplier * out`` (``out`` float32), rounded once."""
    return (x.astype(F32) + cfg.residual_multiplier * out).astype(cfg.dtype)


def _ssm_inputs(x, lp, cfg: HybridSsmConfig):
    """Pre-norm and the input projection: x [b, m, d] -> z [b, m,
    d_inner], xBC [b, m, conv_dim], dt [b, m, heads]."""
    dt = cfg.dtype
    h = _rms_norm(x, lp["norm_mixer"], cfg.rms_eps)
    with jax.named_scope("ssm"), jax.named_scope("ssm.in_proj"):
        zxd = jnp.einsum("bmd,de->bme", h, lp["w_in"].astype(dt),
                         preferred_element_type=F32).astype(dt)
    di, cd = cfg.d_inner, cfg.conv_dim
    return zxd[..., :di], zxd[..., di:di + cd], zxd[..., di + cd:]


def _conv(xbc, tail, lp, cfg: HybridSsmConfig):
    """The causal depthwise convolution and its silu over xBC [b, m,
    conv_dim], the row's last ``d_conv - 1`` inputs (``tail``) ahead
    of it. Returns (the activation [b, m, conv_dim], the new tail)."""
    m = xbc.shape[1]
    with jax.named_scope("ssm"), jax.named_scope("ssm.conv"):
        padded = jnp.concatenate([tail, xbc], axis=1)
        weights = lp["conv_w"].astype(F32)
        out = lp["conv_b"].astype(F32)
        for j in range(cfg.ssm_conv):
            out = out + weights[j] * padded[:, j:j + m].astype(F32)
        return jax.nn.silu(out).astype(cfg.dtype), padded[:, m:]


def _ssm_split(act, dt_raw, lp, cfg: HybridSsmConfig):
    """The convolution's output as x [..., heads, head_dim], B and C
    [..., state] in float32, and Delta [..., heads]."""
    di, n = cfg.d_inner, cfg.ssm_state
    x = act[..., :di].astype(F32).reshape(
        *act.shape[:-1], cfg.ssm_heads, cfg.ssm_head_dim)
    step = jax.nn.softplus(dt_raw.astype(F32) + lp["dt_bias"])
    return (x, act[..., di:di + n].astype(F32),
            act[..., di + n:].astype(F32), step)


def _ssm_step(act, dt_raw, state, lp, cfg: HybridSsmConfig):
    """One position of the recurrence for every row: act [b,
    conv_dim], dt_raw [b, heads], state [b, heads, head_dim, state]
    float32, read once and written once where it lies. Returns (y [b,
    heads, head_dim] float32, the new state)."""
    with jax.named_scope("ssm"), jax.named_scope("ssm.update"):
        x, b_in, c_out, step = _ssm_split(act, dt_raw, lp, cfg)
        decay = jnp.exp(-step * jnp.exp(lp["a_log"]))  # [b, heads]
        state = (state * decay[:, :, None, None]
                 + (x * step[..., None])[..., None]
                 * b_in[:, None, None, :])
        y = jnp.sum(state * c_out[:, None, None, :], axis=-1)
        return y + lp["d_skip"][:, None] * x, state


def _ssm_seq(act, dt_raw, state, lp, cfg: HybridSsmConfig):
    """m positions of the recurrence from ``state``, in chunks of
    ``ssm_chunk``: act [b, m, conv_dim], dt_raw [b, m, heads]. Inside
    a chunk position t reads position s <= t through ``exp(sum of the
    log-decays between them) (C_t . B_s) Delta_s x_s``; between chunks
    the state carries. A tail short of a chunk is padded with Delta 0,
    which leaves the state as it is. All of it float32. Returns (y [b,
    m, heads, head_dim] float32, the state after the last position)."""
    with jax.named_scope("ssm"), jax.named_scope("ssm.update"):
        b, m = act.shape[:2]
        x, b_in, c_out, step = _ssm_split(act, dt_raw, lp, cfg)
        size = min(cfg.ssm_chunk, m)
        pad = -m % size

        def chunks(v):
            v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            return jnp.moveaxis(
                v.reshape(b, (m + pad) // size, size, *v.shape[2:]), 1, 0)

        rate = -jnp.exp(lp["a_log"])
        earlier = jnp.tril(jnp.ones((size, size), jnp.bool_))

        def body(carry, inputs):
            xc, dc, bc, cc = inputs  # [b, Q, H, P], [b, Q, H], 2 x [b, Q, N]
            total = jnp.cumsum(dc * rate, axis=1)  # log-decay from the start
            by_head = jnp.moveaxis(total, 2, 1)    # [b, H, Q]
            between = jnp.exp(jnp.where(
                earlier, by_head[..., :, None] - by_head[..., None, :],
                -jnp.inf))                         # [b, H, Q(t), Q(s)]
            scores = jnp.einsum("bqn,bkn->bqk", cc, bc, precision=HIGHEST)
            xd = xc * dc[..., None]
            y = jnp.einsum("bhqk,bkhp->bqhp", scores[:, None] * between, xd,
                           precision=HIGHEST)
            y = y + jnp.einsum("bqn,bhpn->bqhp", cc, carry,
                               precision=HIGHEST) * jnp.exp(total)[..., None]
            last = total[:, -1]                    # [b, H]
            to_end = jnp.exp(last[:, None] - total)
            carry = carry * jnp.exp(last)[:, :, None, None] + jnp.einsum(
                "bqhp,bqn->bhpn", xd * to_end[..., None], bc,
                precision=HIGHEST)
            return carry, y

        state, ys = lax.scan(
            body, state, (chunks(x), chunks(step), chunks(b_in),
                          chunks(c_out)))
        y = jnp.moveaxis(ys, 0, 1).reshape(b, m + pad, *x.shape[2:])[:, :m]
        return y + lp["d_skip"][:, None] * x, state


def _ssm_out(x, y, z, lp, cfg: HybridSsmConfig):
    """The gate, the norm over all heads at once, the output
    projection and the residual: y [b, m, heads, head_dim] float32, z
    [b, m, d_inner]."""
    dt = cfg.dtype
    with jax.named_scope("ssm"), jax.named_scope("ssm.norm"):
        gated = y.reshape(z.shape) * jax.nn.silu(z.astype(F32))
        var = jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
        gated = (gated * lax.rsqrt(var + cfg.rms_eps)
                 * lp["norm_ssm"]).astype(dt)
    with jax.named_scope("ssm"), jax.named_scope("ssm.out_proj"):
        out = jnp.einsum("bme,ed->bmd", gated, lp["w_out"].astype(dt),
                         preferred_element_type=F32)
        return _residual(x, out, cfg)


def _mamba(x, lp, cfg: HybridSsmConfig, state, tail):
    """A mamba mixer + residual over m positions from (``state``,
    ``tail``): the recurrence itself for one position, in chunks for
    more. Returns (x, state, tail)."""
    z, xbc, dt_raw = _ssm_inputs(x, lp, cfg)
    act, tail = _conv(xbc, tail, lp, cfg)
    if x.shape[1] == 1:
        y, state = _ssm_step(act[:, 0], dt_raw[:, 0], state, lp, cfg)
        y = y[:, None]
    else:
        y, state = _ssm_seq(act, dt_raw, state, lp, cfg)
    return _ssm_out(x, y, z, lp, cfg), state, tail


def _qkv(x, lp, cfg: HybridSsmConfig):
    """Pre-norm and the three projections; nothing rotates. Returns q
    [b, m, H, hd], k and v [b, m, KV, hd]."""
    dt = cfg.dtype
    h = _rms_norm(x, lp["norm_mixer"], cfg.rms_eps)
    with jax.named_scope("attn"), jax.named_scope("attn.qkv"):
        return tuple(
            jnp.einsum("bmd,dhk->bmhk", h, lp[name].astype(dt),
                       preferred_element_type=F32).astype(dt)
            for name in ("wq", "wk", "wv"))


def _attn_out(x, o, lp, cfg: HybridSsmConfig):
    with jax.named_scope("attn"), jax.named_scope("attn.out"):
        out = jnp.einsum("bmhk,hkd->bmd", o, lp["wo"].astype(cfg.dtype),
                         preferred_element_type=F32)
        return _residual(x, out, cfg)


def _causal_attention(q, k, v, cfg: HybridSsmConfig):
    """Attention of a whole sequence from position 0, ``Q_BLOCK``
    query rows at a time where the sequence is long."""
    b, s = q.shape[:2]
    step = Q_BLOCK if s > Q_BLOCK and s % Q_BLOCK == 0 else s
    cols = jnp.arange(s)

    def rows(start):
        qs = lax.dynamic_slice_in_dim(q, start, step, axis=1)
        valid = cols[None, :] <= (start + jnp.arange(step))[:, None]
        return _grouped_attention(qs, k, v, valid, cfg.dtype,
                                  cfg.attention_multiplier)

    if step == s:
        return rows(0)
    out = lax.map(rows, jnp.arange(0, s, step))  # [n, b, step, H, hd]
    return jnp.moveaxis(out, 0, 1).reshape(b, s, *out.shape[3:])


def _sparse_ffn(x, lp, cfg: HybridSsmConfig):
    """The expert layer + residual. Returns (x, assignments per held
    expert [held_n] int32)."""
    b, m, d = x.shape
    with jax.named_scope("mlp"):
        h = _rms_norm(x, lp["norm_mlp"], cfg.rms_eps).reshape(b * m, d)
        idx, gate = moe.route_softmax(h, lp["router"], cfg.experts_per_tok)
        routed, counts = moe.sparse_experts(
            h, idx, gate, lp["e_gate"], lp["e_up"], lp["e_down"],
            cfg.held_lo, cfg.router_experts)
        if cfg.shared_d_ff:
            with jax.named_scope("mlp.shared"):
                routed = routed + _swiglu(
                    h, lp["s_gate"], lp["s_up"], lp["s_down"], cfg.dtype)
        return _residual(x, routed.reshape(b, m, d), cfg), counts


def _embed(params: Params, tokens: jax.Array, cfg: HybridSsmConfig):
    rows = embed_lookup(params, tokens, cfg.dtype)
    return (rows.astype(F32) * cfg.embedding_multiplier).astype(cfg.dtype)


def _logits(params: Params, x: jax.Array, cfg: HybridSsmConfig):
    with jax.named_scope("head"):
        x = _rms_norm(x, params["norm_out"], cfg.rms_eps)
        return jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(cfg.dtype),
                          preferred_element_type=F32) / cfg.logits_scaling


# -- the cache ------------------------------------------------------------

#: a cache's per-layer leaves, by the kind of layer that keeps them
LEAVES = {"mamba": ("ssm", "conv"), "attention": ("k", "v")}


def init_cache(cfg: HybridSsmConfig, batch: int, max_len: int) -> Cache:
    """Zeroed cache: per mamba layer ``ssm[j]`` [batch, heads,
    head_dim, state] float32 and ``conv[j]`` [batch, d_conv - 1,
    conv_dim]; per attention layer ``k[j]`` and ``v[j]`` [batch,
    length, kv_heads, head_dim]; ``pos`` one number until a pool makes
    it one per row."""
    dt = cfg.dtype
    state = (batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)
    tail = (batch, cfg.ssm_conv - 1, cfg.conv_dim)
    keys = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {
        "pos": jnp.zeros((), jnp.int32),
        "ssm": [jnp.zeros(state, F32) for _ in range(cfg.n_mamba)],
        "conv": [jnp.zeros(tail, dt) for _ in range(cfg.n_mamba)],
        "k": [jnp.zeros(keys, dt) for _ in range(cfg.n_attention)],
        "v": [jnp.zeros(keys, dt) for _ in range(cfg.n_attention)],
    }


def slot_cache(cfg: HybridSsmConfig, slots: int, max_len: int) -> Cache:
    """The serving pool: the cache with one row and one position per
    slot, and the counters (see the module's note)."""
    pool = init_cache(cfg, slots, max_len)
    pool["pos"] = jnp.zeros((slots,), jnp.int32)
    pool["stats"] = jnp.zeros(
        (len(STATS_HEAD) + cfg.held_n + 1,), jnp.int32)
    return pool


def insert_row(pool: Cache, row: Cache, slot: jax.Array) -> Cache:
    """Write a one-row cache (``prefill``'s) into ``slot``: the whole
    state, every position of the keys and values and the row's
    position, so nothing of the slot's last occupant is left."""
    new = dict(pool)
    for name in LEAVES["mamba"] + LEAVES["attention"]:
        new[name] = [
            lax.dynamic_update_slice(
                big, small.astype(big.dtype),
                (slot,) + (0,) * (big.ndim - 1))
            for big, small in zip(pool[name], row[name])]
    new["pos"] = lax.dynamic_update_slice(
        pool["pos"], jnp.reshape(row["pos"], (1,)).astype(jnp.int32), (slot,))
    return new


# -- forward, prefill, decode --------------------------------------------


def _run(params: Params, cache: Cache, tokens: jax.Array,
         cfg: HybridSsmConfig, attend) -> Tuple[jax.Array, Cache]:
    """m tokens per row through every layer, each layer's leaves of
    ``cache`` read and replaced; ``attend(j, q, k, v)`` is the j-th
    attention layer over its keys and values, and returns (the heads'
    output, the layer's new ``k`` and ``v`` leaves). Returns (hidden
    [b, m, d], the cache with its leaves replaced)."""
    b, m = tokens.shape
    x = _embed(params, tokens, cfg)
    new: Dict[str, List[jax.Array]] = {
        name: [] for names in LEAVES.values() for name in names}
    counts = []
    with jax.named_scope("layers"):
        for kind, lp in zip(cfg.layer_types, params["layers"]):
            if kind == "mamba":
                j = len(new["ssm"])
                x, state, tail = _mamba(
                    x, lp, cfg, cache["ssm"][j], cache["conv"][j])
                new["ssm"].append(state)
                new["conv"].append(tail)
            else:
                q, k, v = _qkv(x, lp, cfg)
                o, keys, values = attend(len(new["k"]), q, k, v)
                new["k"].append(keys)
                new["v"].append(values)
                x = _attn_out(x, o, lp, cfg)
            x, layer_counts = _sparse_ffn(x, lp, cfg)
            counts.append(layer_counts)
    out = {**cache, **new}
    if "stats" in cache:
        out["stats"] = jnp.concatenate([
            _count(cache["stats"][:-1], b * m, counts, moe.expert_block(
                b * m, cfg.experts_per_tok, cfg.router_experts)),
            cache["stats"][-1:] + b * m * cfg.n_mamba])
    return x, out


def _from_zero(params: Params, tokens: jax.Array, cfg: HybridSsmConfig):
    """tokens [b, s] from position 0 -> (hidden [b, s, d], a cache of
    length s: the final state of every mamba layer, the keys and values
    of every attention layer)."""
    b, s = tokens.shape

    def attend(_j, q, k, v):
        with jax.named_scope("attn"), jax.named_scope("attn.scores"):
            return _causal_attention(q, k, v, cfg), k, v

    return _run(params, init_cache(cfg, b, 0), tokens, cfg, attend)


def forward(params: Params, tokens: jax.Array, cfg: HybridSsmConfig):
    """tokens [b, s] -> logits [b, s, vocab] float32."""
    x, _cache = _from_zero(params, tokens, cfg)
    return _logits(params, x, cfg)


def prefill(params: Params, tokens: jax.Array, cfg: HybridSsmConfig,
            max_len: int) -> Tuple[jax.Array, Cache]:
    """Process the prompt; returns (logits of the last position, the
    cache: each mamba layer's final state and last inputs, each
    attention layer's keys and values in a row of ``max_len``)."""
    b, s = tokens.shape
    x, cache = _from_zero(params, tokens, cfg)
    with jax.named_scope("attn"), jax.named_scope("attn.kv_write"):
        room = init_cache(cfg, b, max_len)
        for name in LEAVES["attention"]:
            cache[name] = [
                lax.dynamic_update_slice(big, new, (0, 0, 0, 0))
                for big, new in zip(room[name], cache[name])]
    cache["pos"] = jnp.asarray(s, jnp.int32)
    return _logits(params, x[:, -1:, :], cfg)[:, 0, :], cache


def decode_chunk(params: Params, cache: Cache, tokens: jax.Array,
                 cfg: HybridSsmConfig) -> Tuple[jax.Array, Cache]:
    """m tokens per row against the cache in one forward.
    ``tokens[:, i]`` sits at ``pos + i`` of its row; ``pos`` is one
    number or one per row. One token a row is the slot engine's step:
    every mamba layer's state is read once and written once where it
    lies, every attention layer's keys and values are written at the
    row's position and read where they lie."""
    pos = cache["pos"]
    b, m = tokens.shape
    rows = jnp.arange(b)[:, None]
    q_pos = jnp.broadcast_to(pos, (b,))[:, None] + jnp.arange(m)  # [b, m]

    def attend(j, q, k, v):
        keys, values = cache["k"][j], cache["v"][j]
        with jax.named_scope("attn"), jax.named_scope("attn.kv_write"):
            if pos.ndim == 0:
                keys = lax.dynamic_update_slice(keys, k, (0, pos, 0, 0))
                values = lax.dynamic_update_slice(values, v, (0, pos, 0, 0))
            else:
                # a dead slot decodes on past the end: dropped there
                keys = keys.at[rows, q_pos].set(k, mode="drop")
                values = values.at[rows, q_pos].set(v, mode="drop")
        valid = jnp.arange(keys.shape[1])[None, None, :] <= q_pos[:, :, None]
        with jax.named_scope("attn"), jax.named_scope("attn.scores"):
            o = _grouped_attention(q, keys, values, valid, cfg.dtype,
                                   cfg.attention_multiplier)
        return o, keys, values

    x, new = _run(params, cache, tokens, cfg, attend)
    new["pos"] = pos + m
    return _logits(params, x, cfg), new


# -- what the server publishes ---------------------------------------------


def describe_stats(cfg: HybridSsmConfig, total) -> Dict[str, Any]:
    """A pool's summed ``stats`` under the names ``/v1/model``
    ``experts`` publishes (models/mla_moe.py's schema and function: the
    counters ahead of this family's last one are its)."""
    return mla_moe.describe_stats(cfg, None if total is None else total[:-1])


def describe_state(cfg: HybridSsmConfig, total) -> Dict[str, Any]:
    """``/v1/model`` ``state``: what a row keeps that is not keys and
    values, and how often the decode rounds fetched so far stepped it
    (docs/90-observability.md)."""
    item = jnp.dtype(cfg.dtype).itemsize
    state = cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * 4
    tail = (cfg.ssm_conv - 1) * cfg.conv_dim * item
    return {
        "layer_kinds": {kind: cfg.layer_types.count(kind) for kind in KINDS},
        "state_bytes_per_slot": cfg.n_mamba * (state + tail),
        "kv_bytes_per_position": (
            cfg.n_attention * 2 * cfg.n_kv_heads * cfg.head_dim * item),
        "ssm_row_steps": 0 if total is None else int(total[-1]),
    }


def refuse_request(knobs: Dict[str, Any]) -> None:
    """What this family does not take, under the server's names for a
    request's knobs: beam search reorders a cache's rows along an axis
    this family's cache does not have. Raises ValueError (the server's
    422)."""
    if knobs.get("beam_width"):
        raise ValueError(
            "beam_width is refused: a cache of recurrent state and keys "
            "and values is not reordered by beams")
