"""Ring attention: causal attention with the sequence sharded over a
mesh axis.

Long-context sequence/context parallelism, TPU-native: each device
holds a contiguous sequence shard of Q, K, V. K/V blocks rotate around
the ring via ``lax.ppermute`` (neighbor exchange rides ICI) while every
device accumulates its queries' attention with blockwise online softmax
— O(local_seq) memory per device, full-sequence numerics identical to
single-device causal attention.

Step s gives device i the K/V block that originated on device
``(i - s) mod P``; global positions make the causal mask exact across
shards. Step 0 is the device's own (diagonal) block, so every query row
is live from the first step and the running max is never -inf when it
matters.

The public technique (blockwise ring attention; see PAPERS.md) is
implemented fresh against jax shard_map/ppermute.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .attention import NEG_INF


def shard_map(f, *, mesh, in_specs, out_specs, auto=None):
    """``jax.shard_map`` with the replication check off. ``auto``
    names mesh axes left to the automatic partitioner inside the
    manual region (pp×tp composition: pipe is manual, model stays auto
    so XLA inserts the tensor-parallel collectives inside each stage);
    jax expresses that as ``axis_names`` = the manual complement."""
    kwargs = {}
    if auto:
        kwargs["axis_names"] = frozenset(mesh.axis_names) - frozenset(auto)
    return jax.shard_map(
        f,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
        **kwargs,
    )


def _ring_shard_fn(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    axis_size: int,
) -> jax.Array:
    """Per-device body; runs under shard_map. Shapes are the local
    shards: [batch, local_seq, heads, head_dim].

    Grouped-query attention is native: k/v may carry fewer heads than
    q. The ring rotates the SMALL grouped K/V over ICI — the whole
    point of GQA — and the einsums keep K/V at kv-head width by
    carrying the query heads as a [kv_heads, group] pair of axes, so
    no repeated copy is ever materialized."""
    idx = lax.axis_index(axis_name)
    b, lq, h, hd = q.shape
    kvh = k.shape[2]
    group = h // kvh
    scale = hd ** -0.5
    # queries grouped by the kv head they attend with: [b,lq,kvh,g,hd]
    qf = q.astype(jnp.float32).reshape(b, lq, kvh, group, hd) * scale

    q_pos = idx * lq + jnp.arange(lq, dtype=jnp.int32)

    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def step(s, carry):
        k_blk, v_blk, m, l, acc = carry  # m/l: [b,kvh,g,lq]
        src = (idx - s) % axis_size
        scores = jnp.einsum(
            "bqkgd,bskd->bkgqs",
            qf,
            k_blk.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )  # [b, kvh, g, lq, lk]
        k_pos = src * lq + jnp.arange(lq, dtype=jnp.int32)
        mask = q_pos[:, None] >= k_pos[None, :]  # [lq, lk] global causal
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))  # [b,kvh,g,lq]
        # fully-masked-so-far rows keep m at NEG_INF; guard the exps
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        correction = jnp.where(
            m <= NEG_INF / 2, 0.0, jnp.exp(m - m_safe)
        )
        p = jnp.exp(scores - m_safe[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        l_new = l * correction + jnp.sum(p, axis=-1)
        # correction: [b,kvh,g,lq] -> [b,lq,kvh,g,1] to scale acc
        corr_acc = correction.transpose(0, 3, 1, 2)[..., None]
        acc_new = acc * corr_acc + jnp.einsum(
            "bkgqs,bskd->bqkgd",
            p,
            v_blk.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        # rotate K/V to the next device in the ring; the final
        # iteration's rotation would be discarded, so skip it
        k_blk, v_blk = lax.cond(
            s < axis_size - 1,
            lambda kv: (
                lax.ppermute(kv[0], axis_name, perm),
                lax.ppermute(kv[1], axis_name, perm),
            ),
            lambda kv: kv,
            (k_blk, v_blk),
        )
        return k_blk, v_blk, m_new, l_new, acc_new

    m0 = jnp.full((b, kvh, group, lq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, group, lq), jnp.float32)
    acc0 = jnp.zeros((b, lq, kvh, group, hd), jnp.float32)
    _k, _v, _m, l, acc = lax.fori_loop(
        0, axis_size, step, (k, v, m0, l0, acc0)
    )
    # l: [b,kvh,g,lq] -> [b,lq,kvh,g,1]
    denom = jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return (acc / denom).reshape(b, lq, h, hd).astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis_name: str = "seq",
) -> jax.Array:
    """Causal attention with [batch, seq, heads, head_dim] inputs whose
    sequence dimension is sharded over ``axis_name`` of ``mesh``.

    The global sequence length must divide evenly by the axis size.
    """
    if axis_name not in mesh.axis_names:
        raise ValueError(f"mesh has no {axis_name!r} axis: {mesh.axis_names}")
    axis_size = mesh.shape[axis_name]
    if q.shape[1] % axis_size:
        raise ValueError(
            f"seq len {q.shape[1]} not divisible by {axis_name}={axis_size}"
        )
    kvh = k.shape[2]
    if k.shape != v.shape or kvh < 1 or q.shape[2] % kvh:
        raise ValueError(
            f"kv shape {k.shape} incompatible with q {q.shape}: kv heads "
            "must divide the query heads and k/v must agree"
        )
    # keep batch/head sharding on their own axes inside the shard_map so
    # entering it doesn't all-gather what dp/tp already sharded
    batch_axis = "data" if "data" in mesh.axis_names else None
    head_axis = "model" if "model" in mesh.axis_names else None
    if (
        head_axis is not None
        and kvh != q.shape[2]
        and kvh % mesh.shape[head_axis]
    ):
        # grouped kv heads don't divide the tp axis: the per-device
        # group factor would be wrong, so give up the GQA ICI saving
        # and rotate full heads (correctness first)
        k = jnp.repeat(k, q.shape[2] // kvh, axis=2)
        v = jnp.repeat(v, q.shape[2] // kvh, axis=2)
    spec = P(batch_axis, axis_name, head_axis, None)
    fn = shard_map(
        functools.partial(
            _ring_shard_fn, axis_name=axis_name, axis_size=axis_size
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
