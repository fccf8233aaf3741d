"""Pallas flash attention, forward AND backward, with grid-streamed KV.

This is the TPU training/long-context kernel family. Three kernels:

- forward: online-softmax over a (batch*heads, q_blocks, kv_blocks)
  grid. KV blocks arrive through the grid's innermost axis via the
  BlockSpec index_map — each program holds ONE [block_k, head_dim] K/V
  tile in VMEM, never the full row, so a 32k-sequence forward fits
  comfortably in v5e VMEM (the round-1 kernel pinned the whole K/V row:
  ~16 MB at 32k/hd128). The online-softmax carry (running max, running
  sum, output accumulator) lives in VMEM scratch, which persists across
  the sequential innermost grid axis. The forward also emits the
  per-row logsumexp needed by the backward.
- backward dq: same grid, accumulates dQ for one q block while
  streaming KV blocks; recomputes p from (q, k, lse) — standard flash
  recomputation, nothing O(seq^2) is ever saved.
- backward dk/dv: transposed grid (batch*heads, kv_blocks, q_blocks)
  with the Q/dO/lse blocks streaming through the innermost axis,
  accumulating dK and dV for one kv block.

``flash_attention`` glues them together behind a ``jax.custom_vjp`` so
``jax.grad`` through the model trains entirely on pallas kernels. The
flash algorithm is the public technique (see PAPERS.md); the kernels
are written fresh against the pallas TPU API. Off-TPU the kernels run
in interpret mode so the CPU test mesh covers them.

The reference supervisor has no tensor code (see SURVEY.md §2); these
kernels serve the supervised TPU workload half of the framework.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _contributes(
    qi: jax.Array, ki: jax.Array, block_q: int, block_k: int,
    window: int = 0,
):
    """True iff kv block ki overlaps the causal past of q block qi —
    and, with a sliding window, is not entirely older than the window
    (the skip that makes windowed attention O(s*window) not O(s^2))."""
    causal = ki * block_k <= qi * block_q + (block_q - 1)
    if window <= 0:
        return causal
    newest_k = ki * block_k + (block_k - 1)
    oldest_needed = qi * block_q - (window - 1)
    return jnp.logical_and(causal, newest_k >= oldest_needed)


def _causal_mask(
    qi, ki, block_q: int, block_k: int, window: int = 0
) -> jax.Array:
    q_pos = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = ki * block_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    mask = q_pos >= k_pos
    if window > 0:
        mask = jnp.logical_and(mask, q_pos - k_pos < window)
    return mask


def _kv_block_base(qi, block_q: int, block_k: int, window: int,
                   total_kv: int, n_grid):
    """First kv block the windowed grid visits for q block qi (0 when
    no window). Clipped so the n_grid visited blocks are always
    in-range AND unique; non-contributing visits are masked off."""
    if window <= 0 or not total_kv:
        return 0
    first = lax.div(qi * block_q - (window - 1), block_k)
    return jnp.clip(first, 0, total_kv - n_grid)


def _windowed_kv_grid(total_kv: int, block_q: int, block_k: int,
                      window: int) -> int:
    """Number of kv blocks a q block can overlap under a window: the
    needed key span has length window + block_q - 1 and arbitrary
    alignment, so worst-case it touches
    (len + block_k - 2)//block_k + 1 blocks."""
    if window <= 0:
        return total_kv
    span = window + block_q - 1
    return min(total_kv, (span + block_k - 2) // block_k + 1)


def _q_block_base(ki, block_q: int, block_k: int, window: int,
                  total_q: int, n_grid):
    """First q block the windowed dk/dv grid visits for kv block ki
    (queries attending kv block ki span [ki*bk, ki*bk+bk-1+window-1])."""
    if window <= 0 or not total_q:
        return 0
    return jnp.clip(lax.div(ki * block_k, block_q), 0, total_q - n_grid)


def _windowed_q_grid(total_q: int, block_q: int, block_k: int,
                     window: int) -> int:
    """Number of q blocks a kv block can influence under a window
    (query span length window + block_k - 1, arbitrary alignment)."""
    if window <= 0:
        return total_q
    span = window + block_k - 1
    return min(total_q, (span + block_q - 2) // block_q + 1)


def _dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """f32 matmul on the MXU."""
    return lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot_t(a: jax.Array, b: jax.Array) -> jax.Array:
    """a @ b.T without materializing the transpose."""
    return lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot_tt(a: jax.Array, b: jax.Array) -> jax.Array:
    """a.T @ b without materializing the transpose."""
    return lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, block_q: int, block_k: int, scale: float, window: int = 0,
    total_kv: int = 0,
):
    qi = pl.program_id(1)
    j = pl.program_id(2)
    n_kv = pl.num_programs(2)
    # windowed grids only span the contributing kv blocks; the real
    # block index is the per-q-block offset (same formula as the
    # BlockSpec index_map) plus the grid position
    ki = _kv_block_base(qi, block_q, block_k, window, total_kv, n_kv) + j

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_contributes(qi, ki, block_q, block_k, window))
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        scores = _dot_t(q, k)  # [block_q, block_k]
        scores = jnp.where(
            _causal_mask(qi, ki, block_q, block_k, window), scores, NEG_INF
        )
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        # rows fully masked in THIS block still carry their old max;
        # exp(NEG_INF - finite) underflows to exactly 0 as required
        p = jnp.exp(scores - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + _dot(p, v)

    @pl.when(j == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l)


def _fwd_rows(
    qr: jax.Array, kr: jax.Array, vr: jax.Array,
    block_q: int, block_k: int, interpret: bool, window: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """[rows, s, hd] x3 -> (out [rows, s, hd], lse [rows, s, 1] f32).

    lse keeps a trailing unit axis so its blocks are (1, block_q, 1) —
    sublane-aligned for the TPU tiling rules and broadcastable against
    [block_q, block_k] score tiles in the backward without transposes.

    Grouped-query attention: kr/vr may carry fewer rows than qr (one
    per (batch, kv_head)). With group = q_rows // kv_rows, q row
    r = b*h + head reads kv row r // group = b*kv_heads + head//group —
    exact because h = kv_heads * group. The kernel then streams each
    K/V block once per query head from HBM *without* a materialized
    repeat_kv copy.
    """
    rows, s, hd = qr.shape
    kv_rows = kr.shape[0]
    if rows % kv_rows:
        raise ValueError(
            f"q rows {rows} not a multiple of kv rows {kv_rows}"
        )
    group = rows // kv_rows
    total_kv = s // block_k
    n_kv_grid = _windowed_kv_grid(total_kv, block_q, block_k, window)
    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, scale=hd ** -0.5,
        window=window, total_kv=total_kv,
    )

    def kv_map(r, i, j):
        base = _kv_block_base(i, block_q, block_k, window, total_kv,
                              n_kv_grid)
        return (r // group, base + j, 0)

    return pl.pallas_call(
        kernel,
        # stable kernel names: a profiler trace finds the three flash
        # kernels by them (docs/90-observability.md)
        name="flash_fwd",
        grid=(rows, s // block_q, n_kv_grid),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda r, i, j: (r, i, 0)),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, hd), lambda r, i, j: (r, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda r, i, j: (r, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, s, hd), qr.dtype),
            jax.ShapeDtypeStruct((rows, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),  # output accumulator
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(qr, kr, vr)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref, acc_ref,
    *, block_q: int, block_k: int, scale: float, window: int = 0,
    total_kv: int = 0,
):
    qi = pl.program_id(1)
    j = pl.program_id(2)
    n_kv = pl.num_programs(2)
    ki = _kv_block_base(qi, block_q, block_k, window, total_kv, n_kv) + j

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_contributes(qi, ki, block_q, block_k, window))
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]       # [block_q, 1]
        d_rows = d_ref[0]      # [block_q, 1]
        mask = _causal_mask(qi, ki, block_q, block_k, window)
        # p_ij = exp(s_ij - lse_i), exactly the forward's normalized
        # weights (lse folds in the running max and sum)
        s = _dot_t(q, k)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = _dot_t(do, v)
        ds = p * (dp - d_rows)
        acc_ref[...] = acc_ref[...] + _dot(ds, k)

    @pl.when(j == n_kv - 1)
    def _finalize():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkdv_kernel(
    k_ref, v_ref, q_ref, do_ref, lse_ref, d_ref, dk_ref, dv_ref,
    dk_acc, dv_acc, *, block_q: int, block_k: int, scale: float,
    window: int = 0, total_q: int = 0,
):
    ki = pl.program_id(1)
    j = pl.program_id(2)
    n_q = pl.num_programs(2)
    qi = _q_block_base(ki, block_q, block_k, window, total_q, n_q) + j

    @pl.when(j == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_contributes(qi, ki, block_q, block_k, window))
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]       # [block_q, 1]
        d_rows = d_ref[0]      # [block_q, 1]
        mask = _causal_mask(qi, ki, block_q, block_k, window)
        s = _dot_t(q, k)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv_acc[...] = dv_acc[...] + _dot_tt(p, do)
        dp = _dot_t(do, v)
        ds = p * (dp - d_rows)
        # d(s_scaled)/dk = q*scale, already folded into q above
        dk_acc[...] = dk_acc[...] + _dot_tt(ds, q)

    @pl.when(j == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_rows(
    qr, kr, vr, do_r, lse, d_rows, block_q: int, block_k: int,
    interpret: bool, window: int = 0,
):
    rows, s, hd = qr.shape
    scale = hd ** -0.5
    total_kv = s // block_k
    total_q = s // block_q
    n_kv_grid = _windowed_kv_grid(total_kv, block_q, block_k, window)
    n_q_grid = _windowed_q_grid(total_q, block_q, block_k, window)

    def kv_map(r, i, j):
        base = _kv_block_base(i, block_q, block_k, window, total_kv,
                              n_kv_grid)
        return (r, base + j, 0)

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, block_q=block_q, block_k=block_k, scale=scale,
            window=window, total_kv=total_kv,
        ),
        name="flash_bwd_dq",
        grid=(rows, s // block_q, n_kv_grid),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda r, i, j: (r, i, 0)),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_q, hd), lambda r, i, j: (r, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda r, i, j: (r, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda r, i, j: (r, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda r, i, j: (r, i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, s, hd), qr.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(qr, kr, vr, do_r, lse, d_rows)
    def q_map(r, kj, i):
        base = _q_block_base(kj, block_q, block_k, window, total_q,
                             n_q_grid)
        return (r, base + i, 0)

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkdv_kernel, block_q=block_q, block_k=block_k, scale=scale,
            window=window, total_q=total_q,
        ),
        name="flash_bwd_dkdv",
        grid=(rows, s // block_k, n_q_grid),
        in_specs=[
            pl.BlockSpec((1, block_k, hd), lambda r, j, i: (r, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda r, j, i: (r, j, 0)),
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, block_q, 1), q_map),
            pl.BlockSpec((1, block_q, 1), q_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, hd), lambda r, j, i: (r, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda r, j, i: (r, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, s, hd), kr.dtype),
            jax.ShapeDtypeStruct((rows, s, hd), vr.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, hd), jnp.float32),
            pltpu.VMEM((block_k, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(kr, vr, qr, do_r, lse, d_rows)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def _to_rows(x: jax.Array) -> jax.Array:
    b, s, h, hd = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, hd)


def _from_rows(x: jax.Array, b: int, h: int) -> jax.Array:
    bh, s, hd = x.shape
    return x.reshape(b, h, s, hd).transpose(0, 2, 1, 3)


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """None = compile the kernel for the device. Only the CPU backend
    (the test mesh) has no Mosaic target and interprets; any other
    backend compiles for real or fails loudly."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


def _check_shapes(q, block_q: int, block_k: int) -> None:
    s = q.shape[1]
    if s % block_q or s % block_k:
        raise ValueError(
            f"seq len {s} not a multiple of blocks ({block_q}, {block_k})"
        )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention_core(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    block_q: int,
    block_k: int,
    interpret: Optional[bool],
    window: int,
) -> jax.Array:
    out, _lse = _flash_fwd_impl(
        q, k, v, block_q, block_k, interpret, window
    )
    return out


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
    window: int = 0,
) -> jax.Array:
    """Causal flash attention, differentiable, all-pallas.

    [batch, seq, heads, head_dim] layout, same contract as
    ``causal_attention``; seq must be a multiple of both block sizes
    (pad upstream — static shapes keep the MXU tiling clean).

    ``window > 0`` = sliding-window attention: kv blocks entirely
    older than the window are skipped in all three kernels, so fwd
    AND bwd FLOPs are O(s*window). A plain wrapper so callers may use
    keywords; the custom_vjp core takes positions only.
    """
    return _flash_attention_core(
        q, k, v, block_q, block_k, interpret, window
    )


def _flash_fwd_impl(q, k, v, block_q, block_k, interpret, window=0):
    _check_shapes(q, block_q, block_k)
    if k.shape != q.shape or v.shape != q.shape:
        # the backward kernels index k/v by q-row; grouped (GQA) kv
        # would produce wrong-shaped, wrong-valued dk/dv here
        raise ValueError(
            f"flash_attention requires full-head k/v matching q "
            f"{q.shape}, got k {k.shape} — repeat GQA kv upstream, or "
            "use flash_attention_forward for GQA-native inference"
        )
    b, s, h, hd = q.shape
    interp = _resolve_interpret(interpret)
    out, lse = _fwd_rows(
        _to_rows(q), _to_rows(k), _to_rows(v), block_q, block_k, interp,
        window,
    )
    return _from_rows(out, b, h), lse


def _flash_fwd(q, k, v, block_q, block_k, interpret, window):
    out, lse = _flash_fwd_impl(
        q, k, v, block_q, block_k, interpret, window
    )
    return out, (q, k, v, out, lse)


def _flash_bwd(block_q, block_k, interpret, window, residuals, d_out):
    q, k, v, out, lse = residuals
    b, s, h, hd = q.shape
    interp = _resolve_interpret(interpret)
    out_r = _to_rows(out)
    do_r = _to_rows(d_out)
    # D_i = rowsum(dO * O): tiny elementwise reduction, XLA fuses it.
    # keepdims matches lse's [rows, s, 1] kernel-friendly layout.
    d_rows = jnp.sum(
        do_r.astype(jnp.float32) * out_r.astype(jnp.float32),
        axis=-1, keepdims=True,
    )
    dq, dk, dv = _bwd_rows(
        _to_rows(q), _to_rows(k), _to_rows(v), do_r, lse, d_rows,
        block_q, block_k, interp, window,
    )
    return (
        _from_rows(dq, b, h).astype(q.dtype),
        _from_rows(dk, b, h).astype(k.dtype),
        _from_rows(dv, b, h).astype(v.dtype),
    )


_flash_attention_core.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_k", "interpret", "window")
)
def flash_attention_forward(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
    window: int = 0,
) -> jax.Array:
    """Forward-only entry point (inference/serving). Same kernel as the
    differentiable path, KV grid-streamed: VMEM use is O(block) per
    program regardless of sequence length.

    Grouped-query attention is native: k/v may carry fewer heads than
    q (n_heads % kv_heads == 0) and the kernel reads the shared K/V
    rows directly — no repeat_kv materialization."""
    _check_shapes(q, block_q, block_k)
    b, s, h, hd = q.shape
    if k.shape != v.shape:
        raise ValueError(f"k {k.shape} and v {v.shape} must agree")
    kb, ks, kvh, khd = k.shape
    if kb != b or ks != s or khd != hd or kvh < 1 or h % kvh:
        raise ValueError(
            f"kv shape {k.shape} incompatible with q {q.shape}: need "
            "(batch, seq, kv_heads, head_dim) with kv_heads >= 1 "
            "dividing the query heads"
        )
    out, _lse = _fwd_rows(
        _to_rows(q), _to_rows(k), _to_rows(v), block_q, block_k,
        _resolve_interpret(interpret), window,
    )
    return _from_rows(out, b, h)
