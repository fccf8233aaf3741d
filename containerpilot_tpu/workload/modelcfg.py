"""Shared CLI plumbing for the workload triad (train/evaluate/serve):
the flag->config derivations and the checkpoint-restore/LoRA-merge
sequence must be ONE implementation, or the three entry points drift
apart and score/serve a differently-shaped model than was trained.
"""
from __future__ import annotations

import os
from typing import Any, Iterable, Optional, Sequence, Tuple

import jax


#: where the persistent XLA compile cache lives when nobody placed it
#: from outside: ONE fixed, git-ignored directory inside the checkout,
#: the same for the CLIs, the tests and chip_smoke.py. The path is
#: part of the cache's key, so it is never built from a temporary
#: name, a pid or the time.
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".compile_cache",
)


def enable_compile_cache() -> str:
    """Turn on the persistent XLA compilation cache and return its
    directory; every workload CLI (serve, serve_dist, train,
    evaluate) calls this once at start-up, and nothing else in the
    program sets a cache directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache lives THERE:
    jax reads the variable itself and this function sets no other
    directory. Where it is not set the cache goes to
    ``DEFAULT_COMPILE_CACHE``.

    The supervisor's whole failure story is crash→restart→resume; the
    dominant cost of a reincarnation is recompiling the exact
    programs the dead process already compiled. With the cache a
    restarted trainer or replica re-warms from cached executables,
    directly shrinking the restart window the supervisor's budgets
    (and a serving pod's downtime) pay for."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not path:
        path = DEFAULT_COMPILE_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    os.makedirs(path, exist_ok=True)
    # default min-compile-time gate (1s) would skip most of a tiny
    # model's programs; anything over half a second is worth a disk hit
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    # the programs' named scopes are op METADATA, which the cache key
    # leaves out by default: an executable loaded from the cache then
    # carries the scopes of whichever build compiled it first, and a
    # profiler trace is read by those scopes (PR 24: the parent's
    # replica, given the change's cache, traced with the change's
    # scopes). Keyed with its metadata, a build traces as itself.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return path


# -- warm-bucket markers (the compile cache as a fleet artifact) ------
#
# The XLA disk cache makes a RE-compile cheap; nothing tells a fresh
# replica it can skip driving the warmup compiles at all. The marker
# file records, per warmup fingerprint (model/engine shape), which
# warmup buckets a previous process on this cache dir already pushed
# through XLA — a launch that finds its buckets marked skips those
# warmup requests entirely and flips /health 200 in milliseconds,
# which is the compile_warmup collapse the cold-start work needs.
# All helpers are blocking (file I/O): executor-wrap them on serving
# loops.

WARM_MARKER = "cp_warm_buckets.json"


def warmup_fingerprint(
    cfg: Any,
    max_len: int,
    slots: int = 0,
    slot_chunk: int = 0,
    slot_window: int = 0,
    draft_layers: int = 0,
    speculate: int = 0,
    mesh: Optional[dict] = None,
    read_ladder: Sequence[int] = (),
) -> str:
    """Stable hash of everything that shapes the warmup program set:
    a marker written under one fingerprint must never skip warmup for
    a differently-shaped server sharing the cache dir."""
    import hashlib
    import json as json_mod

    key = json_mod.dumps(
        {
            # platform identity: XLA's disk cache keys include the
            # backend, and the marker must too — a cpu process's
            # marker must never skip a tpu launch's warmup (shared
            # NFS cache dirs make this a real shape)
            "backend": jax.default_backend(),
            # ...and the host's devices: XLA keys an executable on the
            # topology it was compiled for, so a one-chip host's
            # marker must not skip a four-chip host's warmup
            "devices": [jax.devices()[0].device_kind, jax.device_count()],
            "jax": jax.__version__,
            "vocab": getattr(cfg, "vocab_size", 0),
            "d_model": getattr(cfg, "d_model", 0),
            "n_heads": getattr(cfg, "n_heads", 0),
            "kv_heads": getattr(cfg, "kv_heads", 0),
            "n_layers": getattr(cfg, "n_layers", 0),
            "d_ff": getattr(cfg, "d_ff", 0),
            "window": getattr(cfg, "window", 0),
            "kv_int8": bool(getattr(cfg, "kv_int8", False)),
            # a model read from a file (--model-config): the file's
            # content and the share of the experts held. A marker
            # written for one share, or one edit of the file, must
            # not skip another's warm-up
            "model_file": getattr(cfg, "source_digest", ""),
            "held_experts": [getattr(cfg, "held_lo", 0),
                             getattr(cfg, "held_n", 0)],
            "max_len": max_len,
            "slots": slots,
            "slot_chunk": slot_chunk,
            # fused decode rounds per dispatch: the (S, chunk, K)
            # window program is part of the engine's compiled set, so
            # K is part of the marker identity — a K=1 process's
            # marker must never skip the fused program a K=4 launch
            # needs
            "slot_window": slot_window,
            # the read lengths the decode programs are compiled for
            # (models/slots.py read_ladder): a marker written before
            # the ladder, or for another one, vouches for other
            # programs
            "read_ladder": list(read_ladder),
            "draft_layers": draft_layers,
            "speculate": speculate,
            # the mesh the params are sharded over: a --tp 4 server's
            # programs are not a one-device server's
            "mesh": mesh,
        },
        sort_keys=True,
    )
    return hashlib.blake2b(key.encode(), digest_size=8).hexdigest()


def load_warm_buckets(cache_dir: str, fingerprint: str) -> set:
    """Warmup buckets already marked warm for this fingerprint in
    this cache dir; tolerant of a missing/torn marker (empty set —
    worst case the launch warms up fully, never a crash)."""
    import json as json_mod

    if not cache_dir:
        return set()
    try:
        with open(os.path.join(cache_dir, WARM_MARKER)) as fh:
            marker = json_mod.load(fh)
        buckets = marker.get(fingerprint, [])
        return {b for b in buckets if isinstance(b, str)}
    except (OSError, ValueError, AttributeError):
        return set()


def mark_warm_buckets(
    cache_dir: str, fingerprint: str, buckets: Iterable[str]
) -> None:
    """Merge ``buckets`` into the marker under ``fingerprint``
    (atomic tmp+rename write; concurrent markers last-write-win,
    which only costs a redundant warmup, never a wrong skip)."""
    import json as json_mod

    if not cache_dir:
        return
    path = os.path.join(cache_dir, WARM_MARKER)
    try:
        with open(path) as fh:
            marker = json_mod.load(fh)
        if not isinstance(marker, dict):
            marker = {}
    except (OSError, ValueError):
        marker = {}
    merged = set(marker.get(fingerprint, [])) | set(buckets)
    marker[fingerprint] = sorted(merged)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as fh:
        json_mod.dump(marker, fh, sort_keys=True)
    os.replace(tmp, path)


def compile_cache_note(cache_dir: str) -> str:
    """The heartbeat advertisement VALUE (``<digest>:<quoted dir>``,
    carried as the ``cc=`` field by ``fleet/notes.py``) a FleetMember
    appends for a replica: the compile-cache directory in force and a
    digest over its warm-bucket marker, which tells readers when the
    warm set moved. Empty when no cache dir is given."""
    import hashlib
    import json as json_mod

    from ..fleet.notes import encode_compile_cache

    if not cache_dir:
        return ""
    try:
        with open(os.path.join(cache_dir, WARM_MARKER)) as fh:
            marker_blob = json_mod.dumps(json_mod.load(fh), sort_keys=True)
    except (OSError, ValueError):
        marker_blob = ""
    digest = hashlib.blake2b(
        marker_blob.encode(), digest_size=4
    ).hexdigest()
    return encode_compile_cache(digest, cache_dir)


def parse_compile_cache_note(raw: object) -> Tuple[str, str]:
    """Tolerant reader for the ``cc=`` field's value: (digest, dir);
    both empty on garbage — never an exception on the routing path.
    Thin alias for the registry codec in ``fleet/notes.py``."""
    from ..fleet.notes import parse_compile_cache

    return parse_compile_cache(raw)


def load_model_file(path: str, max_len: int) -> Any:
    """The model configuration a ``--model-config`` file describes,
    built from the published ``config.json`` keys the file holds (the
    benchmark's configuration files are such files, with their notes
    beside the keys). The architecture is told by its keys:
    ``kv_lora_rank`` is latent attention over routed experts
    (models/mla_moe.py), a ``diffusion`` group beside ``num_experts``
    is generation by diffusion over blocks (models/block_diffusion.py),
    ``layer_types`` beside ``mamba_n_heads`` is state-space layers among
    attention layers (models/hybrid_ssm.py), ``total_ut_steps`` is a
    stack of layers run several times a token (models/looped.py),
    ``model_type`` ``phi4flash`` is a decoder-hybrid-decoder
    (models/decoder_hybrid.py). A file of another architecture is
    refused with its name; nothing is guessed."""
    import hashlib
    import json as json_mod

    try:
        with open(path, "rb") as fh:
            raw = fh.read()
        config = json_mod.loads(raw)
    except (OSError, ValueError) as exc:
        raise SystemExit(f"--model-config {path}: {exc}") from None
    if "kv_lora_rank" in config and "n_routed_experts" in config:
        from ..models.mla_moe import from_published
    elif "diffusion" in config and "num_experts" in config:
        from ..models.block_diffusion import from_published
    elif "layer_types" in config and "mamba_n_heads" in config:
        from ..models.hybrid_ssm import from_published
    elif "total_ut_steps" in config:
        from ..models.looped import from_published
    elif config.get("model_type") == "phi4flash":
        from ..models.decoder_hybrid import from_published
    else:
        raise SystemExit(
            f"--model-config {path}: model_type "
            f"{config.get('model_type')!r} has no builder here (latent "
            "attention with routed experts, block diffusion over routed "
            "experts, state-space layers among attention layers, "
            "layers run several times a token and a "
            "decoder-hybrid-decoder are the families read from a file; "
            "the flagship block still takes its flags)"
        )

    digest = hashlib.blake2b(raw, digest_size=8).hexdigest()
    try:
        return from_published(config, max_len, digest)
    except (KeyError, ValueError) as exc:
        raise SystemExit(f"--model-config {path}: {exc!r}") from None


def derive_d_ff(d_model: int) -> int:
    """The triad's shared SwiGLU width rule: ~3x d_model, floored to
    a 128 multiple (MXU tile), never 0."""
    return d_model * 3 // 128 * 128 or 128


def restore_params_only(
    cfg: Any, mesh: Any, checkpoint_dir: str, use_ema: bool = False
) -> Optional[Tuple[Any, int]]:
    """Params-only restore (optionally the EMA shadow) landing on
    ``mesh`` — optimizer moments stay PLACEHOLDERs on disk. Returns
    (params, checkpoint_step) — with ``.ema`` recording whether the
    shadow is what actually came back — or None when no checkpoint
    exists."""
    from ..parallel import abstract_train_state, restore_params
    from ..parallel.checkpoint import RestoredParams

    restored = restore_params(
        checkpoint_dir,
        abstract_train_state(jax.random.PRNGKey(0), cfg, mesh),
        prefer_ema=use_ema,
    )
    if restored is None:
        return None
    params, step = restored
    return RestoredParams(params, int(step), restored.ema)


def score_logprobs_fn(cfg: Any):
    """The ONE teacher-forced scoring function: per-token logprobs of
    toks[1:] from a forward over toks[:-1]. The single-host
    /v1/score and the pod frontend's twin both jit exactly this, so
    their numbers cannot drift."""
    import jax.numpy as jnp

    from ..models.transformer import forward

    def score(params, toks):
        logits = forward(params, toks[:, :-1], cfg)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return jnp.take_along_axis(
            logp, toks[:, 1:, None], axis=-1
        )[..., 0]

    return score


def parse_logit_bias(raw: Any, vocab_size: int):
    """The ONE HTTP-facing ``logit_bias`` parser (single-host server
    and pod frontend both call it — the bounds must not diverge):
    OpenAI's {token_id: bias} with string or int keys; ``{}`` and
    None are a no-op (OpenAI accepts an empty map). Raises ValueError
    for the 422 path; the model-side normalize_logit_bias re-checks
    the same bounds."""
    if raw is None:
        return None
    from ..models.decode import BIAS_SLOTS_MAX

    if not isinstance(raw, dict):
        raise ValueError(
            "'logit_bias' must be a {token_id: bias} object"
        )
    if not raw:
        return None  # OpenAI semantics: an empty map is a no-op
    if len(raw) > BIAS_SLOTS_MAX:
        raise ValueError(
            f"'logit_bias' is capped at {BIAS_SLOTS_MAX} tokens"
        )
    out = {}
    for k, v in raw.items():
        try:
            tok = int(k)
            bias = float(v)
        except (TypeError, ValueError):
            raise ValueError(
                "'logit_bias' keys must be token ids and values "
                "numbers"
            ) from None
        if not 0 <= tok < vocab_size:
            raise ValueError(
                f"'logit_bias' token ids must be in [0, {vocab_size})"
            )
        if not abs(bias) <= 100:
            raise ValueError(
                "'logit_bias' values must be in [-100, 100]"
            )
        out[tok] = bias
    return out


def parse_stop_ids(raw: Any, vocab_size: int):
    """The ONE token-level ``stop`` parser (single-host server and pod
    frontend — the bounds must not diverge): a list of non-empty id
    rows (text surfaces encode strings before calling). Bounded so a
    request can't smuggle in an O(stops*len) trim bill. Raises
    ValueError for the 422 path."""
    if raw is None:
        return []
    if not isinstance(raw, list) or len(raw) > 8 or not all(
        isinstance(s, list)
        and 1 <= len(s) <= 32
        and all(
            isinstance(t, int)
            and not isinstance(t, bool)
            and 0 <= t < vocab_size
            for t in s
        )
        for s in raw
    ):
        raise ValueError(
            "'stop' must be a list of at most 8 sequences, each "
            f"1..32 token ids in [0, {vocab_size})"
        )
    return raw


def parse_stop_strings(raw: Any):
    """The string-level half of the ``stop`` contract, shared by both
    text surfaces (single-host and pod /v1/completions): one string or
    a list of at most 8, each 1..32 UTF-8 bytes. Validated BEFORE
    encoding so the 422 speaks the text endpoint's language (the
    id-level bounds in parse_stop_ids would otherwise leak through).
    Returns the list of strings (None -> None)."""
    if raw is None:
        return None
    if isinstance(raw, str):
        raw = [raw]
    if (
        not isinstance(raw, list)
        or len(raw) > 8
        or not all(
            isinstance(s, str) and 1 <= len(s.encode()) <= 32
            for s in raw
        )
    ):
        raise ValueError(
            "'stop' must be a non-empty string (or a list of at "
            "most 8), each at most 32 UTF-8 bytes"
        )
    return raw


def validate_lora_flags(lora_dir: str, lora_rank: int) -> None:
    """Clean SystemExit for the flag-misuse cases every CLI shares."""
    if lora_rank > 0 and not lora_dir:
        raise SystemExit("--lora-rank without --lora-dir does nothing; "
                         "pass the adapter checkpoint dir")
    if lora_dir and lora_rank < 1:
        raise SystemExit("--lora-dir requires --lora-rank")


def merge_lora(
    params: Any, cfg: Any, mesh: Any, lora_dir: str, lora_rank: int
) -> Tuple[Any, int]:
    """Restore a trained adapter from ``lora_dir`` (on the SAME mesh
    the base lives on — a mismatched device set makes the merge add
    uncompilable) and fold it into the base weights. Merge BEFORE any
    quantization: int8 bases aren't adaptable."""
    from ..models.lora import apply_lora
    from ..parallel import lora_abstract_state, restore_params

    adapter = restore_params(
        lora_dir, lora_abstract_state(cfg, lora_rank, mesh)
    )
    if adapter is None:
        raise SystemExit(f"no adapter checkpoint in {lora_dir}")
    return apply_lora(params, adapter[0], cfg), int(adapter[1])


def restore_merged_params(
    cfg: Any,
    mesh: Any,
    checkpoint_dir: str,
    use_ema: bool = False,
    lora_dir: str = "",
    lora_rank: int = 0,
) -> Optional[Tuple[Any, int]]:
    """restore_params_only + optional merge_lora, the composition the
    evaluate CLI scores. Returns (params, checkpoint_step) — with
    ``.ema`` from the base restore — or None when no checkpoint
    exists."""
    from ..parallel.checkpoint import RestoredParams

    validate_lora_flags(lora_dir, lora_rank)
    restored = restore_params_only(cfg, mesh, checkpoint_dir, use_ema)
    if restored is None:
        return None
    params, step = restored
    if lora_dir:
        params, _ = merge_lora(params, cfg, mesh, lora_dir, lora_rank)
    return RestoredParams(params, step, restored.ema)


def average_eval_loss(params, cfg, n: int, batch_at) -> float:
    """The one eval-loss computation (jitted loss_fn averaged over n
    batches) shared by the trainer's in-loop eval and the standalone
    evaluate CLI — the comparability of their numbers is structural,
    not a convention."""
    import jax.numpy as jnp

    from ..models.transformer import loss_fn

    step = jax.jit(lambda p, t: loss_fn(p, t, cfg))
    total = 0.0
    for i in range(n):
        total += float(step(params, jnp.asarray(batch_at(i))))
    return total / n
