"""The distributed training step: pjit over the (data, model) mesh.

One jitted function does forward, backward, and the optimizer update;
XLA inserts the gradient all-reduce over ``data`` and the tensor-
parallel collectives over ``model``. Buffers are donated so the update
is in-place in HBM.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..models.transformer import TransformerConfig, init_params, loss_fn
from .sharding import batch_spec, shard_params


@dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array


def make_optimizer(
    learning_rate: float = 3e-4,
    *,
    warmup_steps: int = 0,
    decay_steps: int = 0,
    min_lr_ratio: float = 0.1,
    clip_norm: float = 1.0,
) -> optax.GradientTransformation:
    """Global-norm-clipped AdamW, optionally under a linear-warmup +
    cosine-decay schedule (the standard LLM pretraining shape).

    - ``warmup_steps > 0``: lr ramps 0 -> learning_rate linearly;
    - ``decay_steps > 0``: cosine decay from the peak down to
      ``learning_rate * min_lr_ratio`` over that many post-warmup
      steps, then holds the floor;
    - both zero (the default): constant lr, state layout unchanged.
    """
    return optax.chain(
        optax.clip_by_global_norm(clip_norm),
        optax.adamw(
            lr_schedule(learning_rate, warmup_steps, decay_steps,
                        min_lr_ratio),
            b1=0.9, b2=0.95, weight_decay=0.1,
        ),
    )


class EmaState(NamedTuple):
    """Shadow (exponential-moving-average) copy of the params."""

    ema: Any


def with_ema(
    inner: optax.GradientTransformation, decay: float
) -> optax.GradientTransformation:
    """Wrap an optimizer so its state also carries an EMA of the
    *updated* params (``ema = decay*ema + (1-decay)*params_next``).

    Living inside ``opt_state`` keeps the TrainState pytree structure
    unchanged — checkpoints, sharding resolution (the ema subtree
    mirrors the param tree, so param rules resolve), and the donated
    train step all work untouched. Extract with ``ema_params(state)``.
    """
    if not 0.0 < decay < 1.0:
        raise ValueError(f"ema decay must be in (0, 1), got {decay}")

    def init(params):
        return (
            inner.init(params),
            EmaState(jax.tree_util.tree_map(jnp.array, params)),
        )

    def update(grads, state, params=None):
        inner_state, ema_state = state
        updates, inner_state = inner.update(grads, inner_state, params)
        new_params = optax.apply_updates(params, updates)
        ema = jax.tree_util.tree_map(
            lambda e, p: decay * e + (1.0 - decay) * p,
            ema_state.ema, new_params,
        )
        return updates, (inner_state, EmaState(ema))

    return optax.GradientTransformation(init, update)


def ema_params(state: "TrainState") -> Any:
    """The EMA shadow params from a with_ema-wrapped state (None if
    the optimizer has no EMA)."""
    found = []

    def visit(node):
        if isinstance(node, EmaState):
            found.append(node.ema)
            return
        if isinstance(node, (tuple, list)):
            for child in node:
                visit(child)

    visit(state.opt_state)
    return found[0] if found else None


def lr_schedule(
    learning_rate: float,
    warmup_steps: int = 0,
    decay_steps: int = 0,
    min_lr_ratio: float = 0.1,
):
    """The lr trajectory make_optimizer uses: a float when constant,
    else an optax schedule (step -> lr)."""
    if decay_steps > 0:
        return optax.warmup_cosine_decay_schedule(
            init_value=0.0 if warmup_steps > 0 else learning_rate,
            peak_value=learning_rate,
            warmup_steps=warmup_steps,
            decay_steps=warmup_steps + decay_steps,
            end_value=learning_rate * min_lr_ratio,
        )
    if warmup_steps > 0:
        return optax.join_schedules(
            [
                optax.linear_schedule(0.0, learning_rate, warmup_steps),
                optax.constant_schedule(learning_rate),
            ],
            boundaries=[warmup_steps],
        )
    return learning_rate


def init_train_state(
    rng: jax.Array,
    cfg: TransformerConfig,
    mesh: Mesh,
    learning_rate: float = 3e-4,
    rules: Any = None,
    optimizer: optax.GradientTransformation = None,
    zero1: bool = False,
) -> TrainState:
    """Initialize params already sharded onto the mesh. ``rules``
    overrides the tensor-parallel param specs (e.g. pipeline rules);
    ``optimizer`` overrides the default make_optimizer(learning_rate)
    (pass the same one to make_train_step and abstract_train_state);
    ``zero1`` shards adam moments over the data axis (see
    train_state_shardings)."""
    params = shard_params(init_params(rng, cfg), mesh, cfg, rules=rules)
    optimizer = optimizer or make_optimizer(learning_rate)
    opt_state = optimizer.init(params)
    # commit every piece of optimizer state to its canonical sharding
    # (moments normally inherit the param placement — a no-op put —
    # but zero1 re-shards them over data; scalars commit replicated so
    # checkpoint-restored states match exactly)
    shardings = train_state_shardings(
        cfg, mesh, learning_rate, rules=rules, optimizer=optimizer,
        zero1=zero1,
    )
    opt_state = jax.tree.map(
        jax.device_put, opt_state, shardings.opt_state
    )
    return TrainState(
        params=params,
        opt_state=opt_state,
        step=jax.device_put(
            jnp.zeros((), jnp.int32), NamedSharding(mesh, P())
        ),
    )


def _abstract_init(
    rng: jax.Array, cfg: TransformerConfig, learning_rate: float,
    optimizer: optax.GradientTransformation = None,
) -> TrainState:
    def init_fn(rng):
        params = init_params(rng, cfg)
        opt_state = (optimizer or make_optimizer(learning_rate)).init(params)
        return TrainState(
            params=params,
            opt_state=opt_state,
            step=jnp.zeros((), jnp.int32),
        )

    return jax.eval_shape(init_fn, rng)


def train_state_shardings(
    cfg: TransformerConfig,
    mesh: Mesh,
    learning_rate: float = 3e-4,
    abstract: "TrainState" = None,
    rules: Any = None,
    optimizer: optax.GradientTransformation = None,
    zero1: bool = False,
) -> TrainState:
    """A TrainState-shaped pytree of NamedShardings: the canonical
    placement of every piece of training state on the mesh.

    Built by walking each leaf's tree path against
    param_sharding_rules — adam's mu/nu subtrees mirror the param tree,
    so the same rules resolve; scalar leaves replicate. Used both as
    the train step's pinned in/out shardings (so state placement can
    never drift across steps) and as the checkpoint-restore target.

    ``zero1`` additionally shards adam's mu/nu over the ``data`` axis
    (ZeRO stage 1): optimizer moments — 2x the params in f32 — stop
    being replicated across data-parallel replicas, dividing their
    memory by the data-axis size. Params stay replicated over data;
    XLA partitions the elementwise optimizer math over ``data`` and
    all-gathers the updates (reduce-scatter/all-gather in place of the
    plain grad all-reduce). Moment tensors whose dims don't divide stay
    on the param sharding.
    """
    from .sharding import param_sharding_rules

    if abstract is None:
        abstract = _abstract_init(
            jax.random.PRNGKey(0), cfg, learning_rate, optimizer
        )
    if rules is None:
        rules = param_sharding_rules(cfg, mesh)
    replicated = NamedSharding(mesh, P())
    data_size = mesh.shape.get("data", 1)

    def with_data_axis(spec: P, shape) -> P:
        """Put ``data`` on the first unsharded dim that divides."""
        entries = list(spec) + [None] * (len(shape) - len(spec))
        if "data" in entries:
            return spec  # fsdp rules already consumed the data axis
        for i, (entry, dim) in enumerate(zip(entries, shape)):
            if entry is None and dim % data_size == 0 and dim > 0:
                entries[i] = "data"
                return P(*entries)
        return spec  # nothing divides: keep the param sharding

    def resolve(path, leaf):
        if getattr(leaf, "ndim", None) == 0:
            return replicated
        cursor: Any = rules
        in_moments = False
        for key in path:
            name = getattr(key, "key", getattr(key, "name", None))
            if not isinstance(name, str):
                continue  # tuple/namedtuple positions carry no rule info
            if name in ("mu", "nu"):
                in_moments = True
            # descend first; re-anchor at the top only on a miss (mu/nu
            # subtrees mirror the param tree), so a nested param that
            # happens to share a top-level name can't mis-resolve
            if isinstance(cursor, dict) and name in cursor:
                cursor = cursor[name]
            elif name in rules:
                cursor = rules[name]
        if not isinstance(cursor, P):
            # fail as loudly as shard_params' tree_map does on a
            # rules/params mismatch — a silently replicated tensor is a
            # multi-GB placement bug at real scale
            raise ValueError(
                f"no sharding rule resolves for state leaf at path "
                f"{jax.tree_util.keystr(path)} (shape {leaf.shape})"
            )
        if zero1 and in_moments and data_size > 1:
            cursor = with_data_axis(cursor, leaf.shape)
        return NamedSharding(mesh, cursor)

    return jax.tree_util.tree_map_with_path(resolve, abstract)


def abstract_train_state(
    rng: jax.Array,
    cfg: TransformerConfig,
    mesh: Mesh,
    learning_rate: float = 3e-4,
    shardings: "TrainState" = None,
    rules: Any = None,
    optimizer: optax.GradientTransformation = None,
    zero1: bool = False,
) -> TrainState:
    """The shape/dtype/sharding skeleton of init_train_state's result,
    without materializing any arrays — the restore target for resuming
    from a checkpoint (checkpoint.restore_checkpoint accepts it), so
    resume never pays init + double residency. Pass ``shardings`` (from
    train_state_shardings) to avoid re-deriving them."""
    abstract = _abstract_init(rng, cfg, learning_rate, optimizer)
    if shardings is None:
        shardings = train_state_shardings(
            cfg, mesh, learning_rate, abstract, rules=rules, zero1=zero1
        )
    return jax.tree_util.tree_map(
        lambda leaf, s: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=s
        ),
        abstract,
        shardings,
    )


def make_train_step(
    cfg: TransformerConfig,
    mesh: Mesh,
    learning_rate: float = 3e-4,
    optimizer: optax.GradientTransformation = None,
    accum_steps: int = 1,
    zero1: bool = False,
    fsdp: bool = False,
    rules: Any = None,
) -> Callable[[TrainState, jax.Array], Tuple[TrainState, jax.Array]]:
    """Build the jitted, donated, sharded train step.

    ``zero1`` pins adam's moments sharded over the data axis (ZeRO
    stage 1) — optimizer memory per device drops by the data-parallel
    factor; XLA swaps the grad all-reduce for reduce-scatter +
    all-gather around the partitioned optimizer math.

    ``fsdp`` shards params/grads/moments themselves over ``data``
    (ZeRO-3; sharding.fsdp_sharding_rules) — per-device model state
    drops by the dp factor and XLA all-gathers weights at each use.
    ``rules`` overrides the param specs outright (rare; fsdp wins if
    both are given).

    ``accum_steps > 1`` runs gradient accumulation: the batch splits
    into that many sequential chunks inside one compiled step
    (``lax.scan``), grads average across chunks, one optimizer update —
    the effective batch stays the full batch while activation memory
    drops to one chunk's worth. Batch size must divide by it.
    """
    if cfg.attention_fn is None and mesh.size > 1 and "seq" not in mesh.axis_names:
        # multi-device without context parallelism: the flash path (if
        # the seq length triggers it) must run under shard_map — pallas
        # calls don't partition under automatic pjit sharding
        from .context import flash_parallel_config

        cfg = flash_parallel_config(cfg, mesh)
    if accum_steps < 1:
        raise ValueError("accum_steps must be >= 1")
    optimizer = optimizer or make_optimizer(learning_rate)
    data_sharding = NamedSharding(mesh, batch_spec())
    if fsdp:
        from .sharding import fsdp_sharding_rules

        rules = fsdp_sharding_rules(cfg, mesh, rules)
    # pin the state's placement on both sides of the step so shardings
    # can never drift from the rules across steps/restores
    state_shardings = train_state_shardings(
        cfg, mesh, learning_rate, optimizer=optimizer, zero1=zero1,
        rules=rules,
    )

    def grads_of(params, tokens):
        if accum_steps == 1:
            return jax.value_and_grad(loss_fn)(params, tokens, cfg)
        chunks = tokens.reshape(
            accum_steps, tokens.shape[0] // accum_steps, tokens.shape[1]
        )

        def acc(carry, chunk):
            loss_sum, grad_sum = carry
            loss, grads = jax.value_and_grad(loss_fn)(params, chunk, cfg)
            return (
                loss_sum + loss,
                jax.tree_util.tree_map(jnp.add, grad_sum, grads),
            ), None

        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        (loss_sum, grad_sum), _ = jax.lax.scan(
            acc, (jnp.zeros((), jnp.float32), zeros), chunks
        )
        # equal-sized chunks: mean-of-chunk-means == full-batch mean
        return (
            loss_sum / accum_steps,
            jax.tree_util.tree_map(lambda g: g / accum_steps, grad_sum),
        )

    def step_fn(state: TrainState, tokens: jax.Array):
        loss, grads = grads_of(state.params, tokens)
        # the layer map's name for clip + AdamW + the parameters'
        # update in the step program's op metadata
        with jax.named_scope("optimizer"):
            updates, new_opt_state = optimizer.update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)
        return (
            TrainState(
                params=new_params,
                opt_state=new_opt_state,
                step=state.step + 1,
            ),
            loss,
        )

    jitted = jax.jit(
        step_fn,
        in_shardings=(state_shardings, data_sharding),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,),
    )

    def run(state: TrainState, tokens: jax.Array):
        if tokens.shape[0] % accum_steps:
            raise ValueError(
                f"batch {tokens.shape[0]} not divisible by "
                f"accum_steps {accum_steps}"
            )
        with mesh:
            return jitted(state, tokens)

    # register TrainState as a pytree once, lazily
    return run


def lora_abstract_state(
    cfg: TransformerConfig,
    rank: int,
    mesh: Mesh,
    learning_rate: float = 1e-4,
    optimizer: optax.GradientTransformation = None,
) -> TrainState:
    """Checkpoint-restore skeleton for a LoRA TrainState: adapter
    pairs + optimizer state, every leaf replicated on ``mesh``. Used
    by the trainer (resume) and by serve (params-only adapter
    restore) — both must build it over the SAME mesh the base weights
    live on, or the merge add commits to conflicting device sets."""
    from ..models.lora import init_lora_params

    optimizer = optimizer or make_optimizer(learning_rate)

    def fresh(rng):
        lora = init_lora_params(rng, cfg, rank)
        return TrainState(
            params=lora,
            opt_state=optimizer.init(lora),
            step=jnp.zeros((), jnp.int32),
        )

    replicated = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=replicated
        ),
        jax.eval_shape(fresh, jax.random.PRNGKey(0)),
    )


def make_lora_train_step(
    cfg: TransformerConfig,
    mesh: Mesh,
    rank: int,
    learning_rate: float = 1e-4,
    optimizer: optax.GradientTransformation = None,
    alpha: float = 2.0,
):
    """LoRA fine-tuning step: returns ``(init_fn, step_fn, abstract)``.

    The TrainState's params are the (tiny, replicated) LoRA pairs;
    the sharded base params ride along as a frozen operand —
    ``step_fn(state, base_params, tokens)``. Gradients are taken only
    w.r.t. the LoRA pytree (the base is frozen by construction), so
    optimizer state is ~2*d*rank per target per layer instead of a
    full model copy. ``abstract`` is the checkpoint-restore target for
    resuming (same contract as abstract_train_state).
    """
    from ..models.lora import apply_lora, init_lora_params

    if cfg.attention_fn is None and mesh.size > 1 and "seq" not in mesh.axis_names:
        from .context import flash_parallel_config

        cfg = flash_parallel_config(cfg, mesh)
    optimizer = optimizer or make_optimizer(learning_rate)
    data_sharding = NamedSharding(mesh, batch_spec())
    abstract = lora_abstract_state(
        cfg, rank, mesh, learning_rate, optimizer
    )
    state_shardings = jax.tree_util.tree_map(
        lambda leaf: leaf.sharding, abstract
    )

    def init_fn(rng) -> TrainState:
        lora = init_lora_params(rng, cfg, rank)
        state = TrainState(
            params=lora,
            opt_state=optimizer.init(lora),
            step=jnp.zeros((), jnp.int32),
        )
        return jax.tree_util.tree_map(
            jax.device_put, state, state_shardings
        )

    def loss_of(lora, base, tokens):
        return loss_fn(apply_lora(base, lora, cfg, alpha), tokens, cfg)

    def step_fn(state: TrainState, base: Any, tokens: jax.Array):
        loss, grads = jax.value_and_grad(loss_of)(
            state.params, base, tokens
        )
        with jax.named_scope("optimizer"):
            updates, new_opt_state = optimizer.update(
                grads, state.opt_state, state.params
            )
            new_lora = optax.apply_updates(state.params, updates)
        return (
            TrainState(
                params=new_lora,
                opt_state=new_opt_state,
                step=state.step + 1,
            ),
            loss,
        )

    jitted = jax.jit(
        step_fn,
        in_shardings=(state_shardings, None, data_sharding),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,),
    )

    def run(state: TrainState, base: Any, tokens: jax.Array):
        with mesh:
            return jitted(state, base, tokens)

    return init_fn, run, abstract


def make_pipeline_train_step(
    cfg: TransformerConfig,
    mesh: Mesh,
    learning_rate: float = 3e-4,
    n_microbatches: int = 4,
    optimizer: optax.GradientTransformation = None,
) -> Callable[[TrainState, jax.Array], Tuple[TrainState, jax.Array]]:
    """The pipelined (GPipe) train step over a ("data","pipe"[,"model"])
    mesh: layers shard over pipe stages, microbatches stream with
    ppermute handoffs, tensor parallelism stays live inside each stage
    (pipeline.py). Same TrainState/optimizer contract as
    make_train_step, so checkpointing and the supervised trainer reuse
    everything."""
    from .pipeline import pipeline_loss_fn, pipeline_sharding_rules

    if "pipe" not in mesh.axis_names:
        raise ValueError(f"mesh has no 'pipe' axis: {mesh.axis_names}")
    optimizer = optimizer or make_optimizer(learning_rate)
    data_sharding = NamedSharding(
        mesh, P("data") if "data" in mesh.axis_names else P()
    )
    rules = pipeline_sharding_rules(cfg, mesh)
    state_shardings = train_state_shardings(
        cfg, mesh, learning_rate, rules=rules, optimizer=optimizer
    )

    def step_fn(state: TrainState, tokens: jax.Array):
        loss, grads = jax.value_and_grad(pipeline_loss_fn)(
            state.params, tokens, cfg, mesh, n_microbatches
        )
        with jax.named_scope("optimizer"):
            updates, new_opt_state = optimizer.update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)
        return (
            TrainState(
                params=new_params,
                opt_state=new_opt_state,
                step=state.step + 1,
            ),
            loss,
        )

    jitted = jax.jit(
        step_fn,
        in_shardings=(state_shardings, data_sharding),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,),
    )

    def run(state: TrainState, tokens: jax.Array):
        with mesh:
            return jitted(state, tokens)

    return run


def _trainstate_flatten(s: TrainState):
    return (s.params, s.opt_state, s.step), None


def _trainstate_unflatten(_aux, children):
    return TrainState(*children)


jax.tree_util.register_pytree_node(
    TrainState, _trainstate_flatten, _trainstate_unflatten
)
