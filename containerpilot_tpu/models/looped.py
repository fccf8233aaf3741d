"""A fifth model family: a stack of layers run SEVERAL TIMES a token
(``model_type`` ``ouro``: Ouro-2.6B, a looped language model). Serving
only.

Tokens ``x -> h = E[x]``. For pass ``t = 0 .. passes - 1``, for layer
``l = 0 .. n_layers - 1``, with the SAME weights in every pass::

    a = Attn_l(N1_l(h); K[t, l], V[t, l])
    h = h + N2_l(a)
    m = W_down(silu(W_gate N3_l(h)) * W_up N3_l(h))
    h = h + N4_l(m)

and after the last layer of each pass ``h = N_out(h)``, which is the
next pass's input; ``logits = W_head h`` after the last pass. Every
``N`` is an RMSNorm with a learned scale of its own (a norm before AND
after each block: "sandwich"), no biases. Attention is plain multi-head
with the flagship's rotation (``transformer._rope``: rotate-half) and
its grouped contraction over the cache as stored
(``decode._grouped_attention``).

What differs from the families beside it, and why it is a module of its
own:

- **the pass loop.** One token costs ``passes`` reads of every layer's
  weights. ``early_exit_threshold`` 1 means no row ever leaves the loop
  early; the published exit gate (a linear map to one number, with a
  bias) is HELD as a leaf, so that the parameter tree is the published
  one, and nothing is computed from it. A threshold under 1 (rows
  leaving the loop at different passes) is refused by name.
- **a plane of keys and values per pass AND layer.** Pass ``t`` of
  layer ``l`` attends only to what pass ``t`` of layer ``l`` wrote at
  earlier positions: ``passes x n_layers`` planes, each addressable by
  position, so a row can be rewound to a shorter prefix and extended
  like the flagship's (the prefix cache and the spill tier compose).
  A cache holds, per LAYER, one leaf ``[passes, rows, length, kv_heads,
  head_dim]`` of keys and one of values. The passes are a ``fori_loop``
  whose body is the layers, unrolled: pass ``t`` writes its keys into
  plane ``t`` of each layer's leaf where it lies (the leaf is the
  loop's carried buffer) and reads plane ``t`` at the loop's index. A
  program therefore holds ``n_layers`` layer bodies, not ``passes x
  n_layers`` (tests/test_tpu_compile.py pins what the v5e's compiler
  makes of the read at a traced pass; PERF.md, PR 42).
- **the counters.** A pool carries ``stats`` ``[2]``: rows stepped, and
  passes run over them (``passes`` times the first today; the number a
  later exit per row moves).

Weights follow models/mla_moe.py's recipe (made leaf by leaf and held
in bf16, a key per leaf and vocabulary block), so no float32 copy of
the model is ever resident; norm scales are float32 ones.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .decode import _grouped_attention
from .hybrid_ssm import _causal_attention
from .mla_moe import TOP, VOCAB_BLOCK, _draw, _swiglu
from .quantized import embed_lookup
from .transformer import _rms_norm, _rope

Params = Dict[str, Any]
Cache = Dict[str, Any]

F32 = jnp.float32


@dataclass(frozen=True)
class LoopedConfig:
    vocab_size: int = 512
    d_model: int = 64
    n_layers: int = 2
    n_heads: int = 4
    n_kv_heads: int = 4
    head_dim: int = 16
    d_ff: int = 128
    passes: int = 4
    rope_theta: float = 1_000_000.0
    rms_eps: float = 1e-6
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    #: digest of the file the configuration was read from (part of a
    #: server's warm-up fingerprint, workload/modelcfg.py)
    source_digest: str = ""

    def __post_init__(self) -> None:
        if self.passes < 1 or self.n_layers < 1:
            raise ValueError("total_ut_steps and num_hidden_layers must "
                             "be >= 1")
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError("num_key_value_heads must divide the heads "
                             "and head_dim must be even")

    # what the serving code asks of any configuration
    window = 0
    kv_int8 = False

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads

    @property
    def attention_multiplier(self) -> float:
        """The scores' scale, under the name ``_causal_attention``
        (models/hybrid_ssm.py) reads it by."""
        return self.head_dim ** -0.5

    @property
    def cache_planes(self) -> int:
        return self.passes * self.n_layers

    @property
    def cache_bytes_per_position(self) -> int:
        """Keys and values one position holds, over every plane."""
        return (self.cache_planes * 2 * self.n_kv_heads * self.head_dim
                * jnp.dtype(self.dtype).itemsize)

    @property
    def family(self):
        import sys

        return sys.modules[__name__]


def from_published(config: Dict[str, Any], max_seq_len: int,
                   source_digest: str = "") -> LoopedConfig:
    """The configuration from a published ``config.json``'s own keys
    (``ouro``'s). Raises KeyError / ValueError for what is missing or
    not served."""
    if "total_ut_steps" not in config:
        raise ValueError("total_ut_steps is missing: a looped model's "
                         "file says how often its layers run")
    threshold = float(config.get("early_exit_threshold", 1.0))
    if threshold < 1.0:
        raise ValueError(
            f"early_exit_threshold {threshold}: adaptive exit per row is "
            "not served yet (every row takes every pass: only 1)")
    for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                      ("tie_word_embeddings", False),
                      ("sliding_window", None), ("rope_scaling", None)):
        if config.get(key, want) != want:
            raise ValueError(f"{key} {config[key]!r}: only {want!r}")
    kinds = set(config.get("layer_types", ())) - {"full_attention"}
    if kinds:
        raise ValueError(f"layer_types holds {sorted(kinds)}: only "
                         "full_attention")
    heads = int(config["num_attention_heads"])
    return LoopedConfig(
        vocab_size=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]),
        n_heads=heads,
        n_kv_heads=int(config.get("num_key_value_heads", heads)),
        head_dim=int(config.get("head_dim",
                                int(config["hidden_size"]) // heads)),
        d_ff=int(config["intermediate_size"]),
        passes=int(config["total_ut_steps"]),
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        max_seq_len=max_seq_len, source_digest=source_digest,
    )


# -- weights ------------------------------------------------------------

#: a leaf's key is PRNGKey(0) folded with its layer (TOP for the
#: embedding, the head and the exit gate) and then with its number
#: here; a block of 128 vocabulary rows with its block index after that
LEAF = {name: i for i, name in enumerate((
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
    "embed", "unembed", "exit_gate",
))}
#: a layer's four norms: before and after attention, before and after
#: the feed-forward block
NORMS = ("norm_attn", "norm_attn_out", "norm_mlp", "norm_mlp_out")


def _leaf_key(layer: int, name: str):
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(0), layer), LEAF[name])


def _layer_leaves(cfg: LoopedConfig, layer: int) -> Dict[str, jax.Array]:
    """One layer's leaves, each drawn in float32 inside a program of
    its own and rounded there: what stays on the device is bf16. The
    four attention matrices are drawn per head (``wq`` [d, heads,
    head_dim], ``wo`` [heads, head_dim, d]: the recipe's shapes) and
    HELD with the heads folded into one axis, the three input
    projections output-major ([heads x head_dim, d]): the form the
    v5e's compiler contracts as it lies. Held [d, heads, head_dim] or
    [d, heads x head_dim] it copied each of the 144 into a layout of
    its own at every dispatch, 1.2 GB of temporaries at the published
    widths (tests/test_tpu_compile.py)."""
    d, h, kv, hd, f = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                       cfg.head_dim, cfg.d_ff)
    shapes = {
        "wq": ((d, h, hd), d), "wk": ((d, kv, hd), d),
        "wv": ((d, kv, hd), d), "wo": ((h, hd, d), h * hd),
        "w_gate": ((d, f), d), "w_up": ((d, f), d), "w_down": ((f, d), f),
    }
    out = {
        name: _draw(_leaf_key(layer, name), shape, fan_in ** -0.5, cfg.dtype)
        for name, (shape, fan_in) in shapes.items()
    }
    for name in ("wq", "wk", "wv"):
        out[name] = out[name].reshape(d, -1).T
    out["wo"] = out["wo"].reshape(-1, d)
    for name in NORMS:
        out[name] = jnp.ones((d,), F32)
    return out


def _vocab_leaf(cfg: LoopedConfig, name: str, scale: float) -> jax.Array:
    """[vocab, d] drawn block by block of VOCAB_BLOCK rows."""
    key = _leaf_key(TOP, name)
    rows = jax.vmap(lambda b: _draw(
        jax.random.fold_in(key, b), (VOCAB_BLOCK, cfg.d_model), scale,
        cfg.dtype))(jnp.arange(cfg.vocab_size // VOCAB_BLOCK))
    return rows.reshape(cfg.vocab_size, cfg.d_model)


def init_params(rng: Any, cfg: LoopedConfig) -> Params:
    """Seeded weights, made leaf by leaf and held in ``cfg.dtype``
    (``rng`` is unused: see models/mla_moe.py ``init_params``). The
    exit gate is the published tree's and nothing reads it."""
    del rng
    if cfg.vocab_size % VOCAB_BLOCK:
        raise ValueError(f"vocab_size must be a multiple of {VOCAB_BLOCK}")
    return {
        "embed": _vocab_leaf(cfg, "embed", 0.02),
        "layers": [_layer_leaves(cfg, i) for i in range(cfg.n_layers)],
        "norm_out": jnp.ones((cfg.d_model,), F32),
        # the head is stored [d, vocab] like the flagship's
        "unembed": _vocab_leaf(cfg, "unembed", cfg.d_model ** -0.5).T,
        "exit_gate": {
            "w": _draw(_leaf_key(TOP, "exit_gate"), (cfg.d_model,),
                       cfg.d_model ** -0.5, cfg.dtype),
            "b": jnp.zeros((1,), F32),
        },
    }


# -- pieces of a layer ----------------------------------------------------


def _qkv(x, lp, cfg: LoopedConfig, offset):
    """N1 and the three projections, q and k rotated at ``offset`` (one
    number, or one per row). Returns q [b, m, H, hd], k and v [b, m,
    KV, hd]."""
    dt = cfg.dtype
    h = _rms_norm(x, lp["norm_attn"], cfg.rms_eps)
    with jax.named_scope("attn"), jax.named_scope("attn.qkv"):
        q, k, v = (
            jnp.einsum("bmd,ed->bme", h, lp[name].astype(dt),
                       preferred_element_type=F32).astype(dt).reshape(
                           *h.shape[:2], -1, cfg.head_dim)
            for name in ("wq", "wk", "wv"))
    with jax.named_scope("attn"), jax.named_scope("attn.rope"):
        return (_rope(q, cfg.rope_theta, offset),
                _rope(k, cfg.rope_theta, offset), v)


def _attn_out(x, o, lp, cfg: LoopedConfig):
    """The output projection, N2 and the residual."""
    with jax.named_scope("attn"), jax.named_scope("attn.out"):
        a = jnp.einsum("bme,ed->bmd", o.reshape(*o.shape[:2], -1),
                       lp["wo"].astype(cfg.dtype),
                       preferred_element_type=F32).astype(cfg.dtype)
    return x + _rms_norm(a, lp["norm_attn_out"], cfg.rms_eps)


def _mlp(x, lp, cfg: LoopedConfig):
    """N3, the SwiGLU block, N4 and the residual."""
    h = _rms_norm(x, lp["norm_mlp"], cfg.rms_eps)
    with jax.named_scope("mlp"):
        m = _swiglu(h.reshape(-1, h.shape[-1]), lp["w_gate"], lp["w_up"],
                    lp["w_down"], cfg.dtype).astype(cfg.dtype).reshape(x.shape)
    return x + _rms_norm(m, lp["norm_mlp_out"], cfg.rms_eps)


def _logits(params: Params, x: jax.Array, cfg: LoopedConfig):
    """The head over a stream the last pass's final norm has normed."""
    with jax.named_scope("head"):
        return jnp.einsum("bsd,dv->bsv", x,
                          params["unembed"].astype(cfg.dtype),
                          preferred_element_type=F32)


def _passes(params: Params, x: jax.Array, cfg: LoopedConfig, offset,
            planes, attend):
    """Every pass of every layer over ``x`` [b, m, d], whose first
    position stands at ``offset`` (one number, or one per row). The
    passes are ONE ``fori_loop`` body (``loop.pass``) that holds the
    layers unrolled; ``planes`` holds per layer its (keys, values)
    leaves, and ``attend(t, q, k, v, leaves)`` is a layer's attention
    in pass ``t``: it returns (the heads' output, the layer's leaves
    with plane ``t`` written). Returns (the stream after the last
    pass's final norm, the planes)."""

    def one_pass(t, state):
        x, planes = state
        new = []
        with jax.named_scope("loop.pass"), jax.named_scope("layers"):
            for lp, leaves in zip(params["layers"], planes):
                q, k, v = _qkv(x, lp, cfg, offset)
                o, leaves = attend(t, q, k, v, leaves)
                new.append(leaves)
                x = _mlp(_attn_out(x, o, lp, cfg), lp, cfg)
        with jax.named_scope("loop.norm_out"):
            x = _rms_norm(x, params["norm_out"], cfg.rms_eps)
        return x, new

    return lax.fori_loop(0, cfg.passes, one_pass, (x, list(planes)))


# -- the cache ------------------------------------------------------------


def init_cache(cfg: LoopedConfig, batch: int, max_len: int) -> Cache:
    """Zeroed cache: per layer ``k[l]`` and ``v[l]`` [passes, batch,
    length, kv_heads, head_dim]; ``pos`` one number until a pool makes
    it one per row."""
    shape = (cfg.passes, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {
        "pos": jnp.zeros((), jnp.int32),
        "k": [jnp.zeros(shape, cfg.dtype) for _ in range(cfg.n_layers)],
        "v": [jnp.zeros(shape, cfg.dtype) for _ in range(cfg.n_layers)],
    }


def slot_cache(cfg: LoopedConfig, slots: int, max_len: int) -> Cache:
    """The serving pool: the cache with one row and one position per
    slot, and the counters (see the module's note)."""
    pool = init_cache(cfg, slots, max_len)
    pool["pos"] = jnp.zeros((slots,), jnp.int32)
    pool["stats"] = jnp.zeros((2,), jnp.int32)
    return pool


def insert_row(pool: Cache, row: Cache, slot: jax.Array) -> Cache:
    """Write a one-row cache (``prefill``'s) into ``slot``: every
    position of every plane and the row's position, so nothing of the
    slot's last occupant is left."""
    new = dict(pool)
    for name in ("k", "v"):
        new[name] = [
            lax.dynamic_update_slice(
                big, small.astype(big.dtype), (0, slot, 0, 0, 0))
            for big, small in zip(pool[name], row[name])]
    new["pos"] = lax.dynamic_update_slice(
        pool["pos"], jnp.reshape(row["pos"], (1,)).astype(jnp.int32), (slot,))
    return new


# -- forward, prefill, decode --------------------------------------------


def _from_zero(params: Params, tokens: jax.Array, cfg: LoopedConfig):
    """tokens [b, s] from position 0 -> (the normed stream [b, s, d],
    per layer the (keys, values) of every pass, [passes, b, s,
    kv_heads, head_dim] each)."""
    b, s = tokens.shape
    room = jnp.zeros((cfg.passes, b, s, cfg.n_kv_heads, cfg.head_dim),
                     cfg.dtype)

    def attend(t, q, k, v, leaves):
        with jax.named_scope("attn"), jax.named_scope("attn.scores"):
            o = _causal_attention(q, k, v, cfg)
        with jax.named_scope("attn"), jax.named_scope("attn.kv_write"):
            return o, tuple(
                lax.dynamic_update_index_in_dim(leaf, new, t, 0)
                for leaf, new in zip(leaves, (k, v)))

    return _passes(params, embed_lookup(params, tokens, cfg.dtype), cfg, 0,
                   [(room, room)] * cfg.n_layers, attend)


def forward(params: Params, tokens: jax.Array, cfg: LoopedConfig):
    """tokens [b, s] -> logits [b, s, vocab] float32."""
    x, _planes = _from_zero(params, tokens, cfg)
    return _logits(params, x, cfg)


def prefill(params: Params, tokens: jax.Array, cfg: LoopedConfig,
            max_len: int) -> Tuple[jax.Array, Cache]:
    """Process the prompt; returns (logits of the last position, the
    cache: every plane's keys and values in a row of ``max_len``)."""
    b, s = tokens.shape
    x, planes = _from_zero(params, tokens, cfg)
    with jax.named_scope("attn"), jax.named_scope("attn.kv_write"):
        cache = init_cache(cfg, b, max_len)
        for i, name in enumerate(("k", "v")):
            cache[name] = [
                lax.dynamic_update_slice(room, leaves[i], (0, 0, 0, 0, 0))
                for room, leaves in zip(cache[name], planes)]
    cache["pos"] = jnp.asarray(s, jnp.int32)
    return _logits(params, x[:, -1:, :], cfg)[:, 0, :], cache


def decode_chunk(params: Params, cache: Cache, tokens: jax.Array,
                 cfg: LoopedConfig) -> Tuple[jax.Array, Cache]:
    """m tokens per row against the cache in one forward.
    ``tokens[:, i]`` sits at ``pos + i`` of its row; ``pos`` is one
    number or one per row. One token a row is the slot engine's step:
    in every pass every layer's keys and values are written into that
    pass's plane at the row's position and read where they lie."""
    pos = cache["pos"]
    b, m = tokens.shape
    rows = jnp.arange(b)[:, None]
    offset = jnp.broadcast_to(pos, (b,))
    q_pos = offset[:, None] + jnp.arange(m)  # [b, m]
    length = cache["k"][0].shape[2]
    valid = jnp.arange(length)[None, None, :] <= q_pos[:, :, None]

    def attend(t, q, k, v, leaves):
        with jax.named_scope("attn"), jax.named_scope("attn.kv_write"):
            if pos.ndim == 0:
                keys, values = (
                    lax.dynamic_update_slice(
                        leaf, new[None], (t, 0, pos, 0, 0))
                    for leaf, new in zip(leaves, (k, v)))
            else:
                # a dead slot decodes on past the end: dropped there
                keys, values = (
                    leaf.at[t, rows, q_pos].set(new, mode="drop")
                    for leaf, new in zip(leaves, (k, v)))
        with jax.named_scope("attn"), jax.named_scope("attn.scores"):
            o = _grouped_attention(
                q, lax.dynamic_index_in_dim(keys, t, 0, keepdims=False),
                lax.dynamic_index_in_dim(values, t, 0, keepdims=False),
                valid, cfg.dtype)
        return o, (keys, values)

    x, planes = _passes(params, embed_lookup(params, tokens, cfg.dtype), cfg,
                        offset, zip(cache["k"], cache["v"]), attend)
    new = {**cache, "pos": pos + m,
           "k": [keys for keys, _values in planes],
           "v": [values for _keys, values in planes]}
    if "stats" in cache:
        new["stats"] = cache["stats"] + jnp.asarray(
            [b * m, b * m * cfg.passes], jnp.int32)
    return _logits(params, x, cfg), new


# -- what the server publishes ---------------------------------------------


def describe_loop(cfg: LoopedConfig, total) -> Dict[str, Any]:
    """``/v1/model`` ``loop``: how often the layers run, the planes of
    keys and values that costs a position, and what the decode rounds
    fetched so far stepped (docs/90-observability.md)."""
    return {
        "passes": cfg.passes,
        "layers": cfg.n_layers,
        "cache_planes": cfg.cache_planes,
        "cache_bytes_per_position": cfg.cache_bytes_per_position,
        "loop_row_steps": 0 if total is None else int(total[0]),
        "loop_row_passes": 0 if total is None else int(total[1]),
    }


def refuse_request(knobs: Dict[str, Any]) -> None:
    """What this family does not take, under the server's names for a
    request's knobs: beam search reorders a cache's rows along the
    flagship cache's batch axis, which is not this cache's. Raises
    ValueError (the server's 422)."""
    if knobs.get("beam_width"):
        raise ValueError(
            "beam_width is refused: a cache of a plane per pass and "
            "layer is not reordered by beams")
