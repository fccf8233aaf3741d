"""Context parallelism: bind ring attention into the model config.

Long sequences are sharded over the mesh's ``seq`` axis; attention runs
as a ring (ops/ring_attention.py) while every other op stays local and
XLA partitions it from the shard_map boundary's in/out specs. The rest
of the stack — sharding rules, optimizer, train step — is unchanged:
context parallelism composes with tensor and data parallelism by
construction.

Serving gets the same long-context story through ``cp_generate``: the
PREFILL — the quadratic, activation-heavy part of a long-prompt
request — runs ring attention over the seq axis, then the KV cache
gathers off the ring once and the decode scan runs on the existing
(unsharded) path with the full sampling contract.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.transformer import TransformerConfig, flash_eligible
from ..ops.ring_attention import ring_attention, shard_map


def flash_parallel_config(
    cfg: TransformerConfig, mesh: Mesh
) -> TransformerConfig:
    """Bind mesh-aware attention auto-selection for pjit'd training
    and for tensor-parallel serving (``serve --tp N``'s prefill and
    scoring forward).

    pallas calls don't partition under automatic pjit sharding, so the
    flash path must run under shard_map. Causal attention is
    independent per (batch, head), and the tensor-parallel rules shard
    heads over ``model`` and batch over ``data``
    (parallel/sharding.py) — so the manual region needs no collectives
    at all: each device runs the flash kernel on its local
    [b/data, s, h/model, hd] block. Below the flash threshold the
    plain einsum path is returned and XLA partitions it as before.
    """
    spec = P("data", None, "model", None)

    def attn(q, k, v):
        if not flash_eligible(cfg, q.shape[1]):
            from ..ops.attention import causal_attention

            return causal_attention(q, k, v, window=cfg.window)
        from ..ops import tuning
        from ..ops.flash import flash_attention

        # seq is unsharded here (spec leaves axis 1 unpartitioned), so
        # the tuned 'train' blocks for the global seq apply locally too
        bq, bk = tuning.pick_blocks("train", q.shape[1])
        f = shard_map(
            lambda q, k, v: flash_attention(
                q, k, v, block_q=bq, block_k=bk, window=cfg.window
            ),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
        )
        return f(q, k, v)

    return dataclasses.replace(cfg, attention_fn=attn)


def context_parallel_config(
    cfg: TransformerConfig, mesh: Mesh, axis_name: str = "seq"
) -> TransformerConfig:
    """A config whose attention runs as a ring over ``axis_name``."""
    if axis_name not in mesh.axis_names:
        raise ValueError(
            f"mesh has no {axis_name!r} axis: {mesh.axis_names}"
        )
    if cfg.window > 0:
        raise ValueError(
            "sliding-window attention does not compose with ring "
            "attention yet: a window shorter than the shard makes "
            "most ring hops no-ops — use the flash window path on a "
            "(data, model) mesh instead"
        )

    def attn(q, k, v):
        return ring_attention(q, k, v, mesh, axis_name)

    # the ring handles grouped kv itself (rotates the SMALL K/V over
    # ICI); the layer passes unrepeated heads through
    attn.gqa_native = True
    return dataclasses.replace(cfg, attention_fn=attn)


@functools.lru_cache(maxsize=8)
def _cp_prefill_fn(cfg: TransformerConfig, mesh: Mesh, max_len: int,
                   axis_name: str):
    """One compiled context-parallel prefill per (config, mesh,
    max_len): ring attention over the seq axis while every other op
    stays seq-local under XLA's partitioner, then ONE gather point —
    the decode scan reads the whole cache every step, so the cache
    leaves the ring replicated here rather than re-gathering per
    step. Cached at this level because context_parallel_config builds
    a fresh attention closure per call (a fresh closure would defeat
    jit's own cache)."""
    cfg_cp = context_parallel_config(cfg, mesh, axis_name)
    from ..models.decode import prefill

    replicated = NamedSharding(mesh, P())

    def fn(params, prompt):
        logits, cache = prefill(params, prompt, cfg_cp, max_len)
        cache = jax.tree.map(
            lambda x: lax.with_sharding_constraint(x, replicated),
            cache,
        )
        return lax.with_sharding_constraint(logits, replicated), cache

    return jax.jit(fn)


def resolve_cp_min_len(cp_min_len: int, seq_axis: int, max_len: int,
                       flag: str = "cp") -> int:
    """The ONE copy of the cp threshold policy both servers apply
    (workload/serve.py and serve_dist.py): derive an unset threshold
    to something that amortizes a ring (self-clamped so it always CAN
    engage), clamp an explicit value below the axis up to the floor
    (the prompt's head must cover the axis), and refuse configurations
    where cp could never engage. Raises ValueError (callers map to
    their own exit types)."""
    if seq_axis >= max_len:
        # no admissible prompt can cover the axis: cp could never
        # engage no matter the threshold
        raise ValueError(
            f"--{flag} never engages: the seq axis ({seq_axis}) is "
            f"not below max_len ({max_len})"
        )
    if cp_min_len == 0:
        return min(8 * seq_axis, max_len - 1)
    if cp_min_len < seq_axis:
        return seq_axis
    if cp_min_len >= max_len:
        # the user's own threshold excludes every admissible prompt
        # (prompt_len + max_new <= max_len): fail at startup, not as
        # a feature that silently never runs
        raise ValueError(
            f"--{flag} never engages: cp_min_len {cp_min_len} >= "
            f"max_len {max_len} (lower the threshold or raise "
            "max_len)"
        )
    return cp_min_len


def cp_head_buckets(cp_min_len: int, max_len: int, axis: int):
    """The static set of ring-head lengths a multi-process server
    compiles AT STARTUP: the smallest axis-divisible length that can
    satisfy cp_min_len, then doubling below max_len.

    Why static: a ring program's ppermute needs a cross-process
    communicator whose initialization carries a hard ~30s deadline
    (observed as 'Gloo context initialization failed: GetKeyValue()
    timed out' killing a live pod when two processes compiled a
    first-use ring program with >30s skew). Replicated programs can
    compile per-shape at request time — compile skew just delays the
    slower process — but COLLECTIVE programs must all exist before
    traffic, which means their shape set must be finite. Heads
    bucket; the (local, collective-free) remainder extend stays
    per-length."""
    if axis < 2:
        return []
    floor = max(cp_min_len - cp_min_len % axis, axis)
    out = []
    b = floor
    while b < max_len:
        out.append(b)
        b *= 2
    return out


def pick_cp_head(plen: int, buckets) -> int:
    """Largest startup-compiled ring head that fits the prompt
    (0 = none fits; take the plain path)."""
    head = 0
    for b in buckets:
        if b <= plen:
            head = b
    return head


def cp_prefill_with_remainder(
    params,
    prompt_host,
    cfg: TransformerConfig,
    mesh: Mesh,
    max_len: int,
    axis_name: str = "seq",
    head: int = 0,
    prefill_chunk: int = 0,
):
    """The ONE copy of the cp prefill recipe both ``cp_generate`` and
    the pod's slot admission (workload/serve_dist.py) run: a HEAD of
    the prompt rings through prefill sharded over ``axis_name``, the
    remainder extends the gathered cache with one (local,
    collective-free) chunk. Returns (last logits, cache), both
    replicated.

    ``head`` = 0 takes the largest axis-divisible head (the
    single-process ``cp_generate`` default — maximal ring work); a
    multi-process pod passes a STARTUP-COMPILED bucket from
    ``cp_head_buckets`` instead, because a first-use ring program's
    communicator init has a hard ~30s deadline that request-time
    compile skew between processes can blow (see cp_head_buckets).

    ``prompt_host`` is a host array ([1, plen], identical on every
    process); placement uses ``make_array_from_callback`` so the same
    code serves single-process meshes and multi-host pods (where a
    plain device_put of a global sharding is not allowed).

    ``prefill_chunk`` caps the remainder's extend pieces at
    ``max(axis, prefill_chunk)`` — the pod passes its
    ``--prefill-chunk`` so the per-device activation guarantee holds
    even for the bucketed-head worst case (see the step cap below)."""
    import numpy as np

    plen = int(prompt_host.shape[1])
    axis = mesh.shape[axis_name]
    if head == 0:
        head = plen - plen % axis
    if head <= 0:
        raise ValueError(
            f"prompt len {plen} is shorter than the {axis_name} axis "
            f"({axis}): nothing to shard — use the plain path"
        )
    if head % axis or head > plen:
        raise ValueError(
            f"head {head} must be a multiple of the {axis_name} axis "
            f"({axis}) and <= prompt len {plen}"
        )
    head_host = np.ascontiguousarray(prompt_host[:, :head], np.int32)
    sharding = NamedSharding(mesh, P(None, axis_name))
    sharded = jax.make_array_from_callback(
        head_host.shape, sharding, lambda idx: head_host[idx]
    )
    logits, cache = _cp_prefill_fn(cfg, mesh, max_len, axis_name)(
        params, sharded
    )
    # Extend the remainder in power-of-two chunks down to a < axis
    # tail, NOT one remainder-length call: a bucketed head can leave a
    # remainder up to head-1 tokens, and a single extend of that would
    # (a) compile one program per distinct remainder length —
    # unbounded shape set — and (b) run one local chunk-x-cache
    # attention at up to half the full quadratic prefill, defeating
    # the memory bound cp exists to provide. The power-of-two steps
    # are CAPPED at max(axis, prefill_chunk): without the cap the
    # largest step can reach head-1 tokens, whose chunk-x-cache
    # attention peaks ~axis/2 times the ring's per-device bound —
    # exactly the worst case --sp advertises protection against
    # (ADVICE r5). The chunk shapes stay data-independent:
    # {2^k : axis <= 2^k <= cap} plus the < axis tail lengths —
    # finite, so a long-lived server stops compiling and the pod's
    # compile-skew story is unchanged. With a maximal head
    # (head == plen - plen % axis, the cp_generate default) the
    # remainder is < axis and this loop is exactly the original
    # one-tiny-chunk behavior.
    if head < plen:
        from ..models.decode import _jitted_extend

        cap = max(axis, prefill_chunk)
        pos = head
        extend = _jitted_extend(cfg)
        while pos < plen:
            left = plen - pos
            step = left
            if left >= axis:
                step = 1
                while step * 2 <= min(left, cap):
                    step *= 2
            logits, cache = extend(
                params, cache,
                jax.numpy.asarray(
                    prompt_host[:, pos:pos + step], jax.numpy.int32
                ),
            )
            pos += step
    return logits, cache


def cp_generate(
    params,
    prompt: jax.Array,
    cfg: TransformerConfig,
    mesh: Mesh,
    max_new_tokens: int,
    max_len: int,
    axis_name: str = "seq",
    **sampling,
):
    """Long-prompt generation with context-parallel prefill: the
    prompt shards over ``axis_name`` (each device holds seq/P tokens;
    the quadratic attention runs as a ring, activations stay
    seq-local), the cache gathers once, and the decode runs
    ``generate_from_cache`` with the full sampling contract
    (temperature/top_k/top_p/eos/min_new/penalties/logit_bias).

    Ring attention needs the sharded length to divide by the seq
    axis, so the largest axis-divisible HEAD of the prompt rings
    through prefill and any remainder (< axis tokens) extends the
    gathered cache with one short decode_chunk — arbitrary prompt
    lengths, exact semantics, at most axis-1 tiny extend programs.
    Numerics: ring attention's online softmax is the same math as
    single-device attention up to float reassociation — greedy output
    matches the unsharded path away from argmax ties.
    """
    plen = int(prompt.shape[1])
    if axis_name not in mesh.axis_names:
        raise ValueError(
            f"mesh has no {axis_name!r} axis: {mesh.axis_names} "
            "(build it with MeshPlan(seq=...))"
        )
    if plen + max_new_tokens > max_len:
        raise ValueError(
            f"prompt_len {plen} + max_new_tokens {max_new_tokens} "
            f"exceeds max_len {max_len}"
        )
    import numpy as np

    from ..models.decode import generate_from_cache

    logits, cache = cp_prefill_with_remainder(
        params, np.asarray(jax.device_get(prompt)), cfg, mesh,
        max_len, axis_name,
    )
    return generate_from_cache(
        params, cache, logits, cfg, max_new_tokens, pos=plen,
        **sampling,
    )
