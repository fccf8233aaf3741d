"""Parallelism: device meshes, sharding rules, distributed train step.

This is the TPU-native "distributed communication backend" of the
framework's workload half. Where the reference supervisor coordinates
*processes* through a catalog (reference: discovery/), the workload it
supervises coordinates *chips* through jax.sharding: pick a Mesh,
annotate shardings, and let XLA insert the collectives over ICI/DCN
(SURVEY.md §5 distributed-backend mapping).
"""
from .checkpoint import (
    wait_for_checkpoints,
    latest_step,
    restore_checkpoint,
    restore_params,
    save_checkpoint,
)
from .context import (
    context_parallel_config,
    cp_generate,
    flash_parallel_config,
)
from .distributed import initialize_from_catalog, initialize_from_env
from .watchdog import StepWatchdog
from .mesh import MeshPlan, make_mesh
from .pipeline import (
    pipeline_forward,
    pipeline_loss_fn,
    pipeline_sharding_rules,
)
from .sharding import (
    fsdp_sharding_rules,
    param_sharding_rules,
    shard_params,
)
from .train import (
    TrainState,
    abstract_train_state,
    ema_params,
    with_ema,
    init_train_state,
    lora_abstract_state,
    make_lora_train_step,
    make_optimizer,
    make_pipeline_train_step,
    make_train_step,
    train_state_shardings,
)

__all__ = [
    "MeshPlan",
    "context_parallel_config",
    "cp_generate",
    "flash_parallel_config",
    "make_pipeline_train_step",
    "make_mesh",
    "fsdp_sharding_rules",
    "param_sharding_rules",
    "shard_params",
    "TrainState",
    "abstract_train_state",
    "ema_params",
    "with_ema",
    "make_train_step",
    "init_train_state",
    "lora_abstract_state",
    "make_lora_train_step",
    "make_optimizer",
    "train_state_shardings",
    "save_checkpoint",
    "wait_for_checkpoints",
    "restore_checkpoint",
    "restore_params",
    "latest_step",
    "initialize_from_catalog",
    "initialize_from_env",
    "StepWatchdog",
    "pipeline_forward",
    "pipeline_loss_fn",
    "pipeline_sharding_rules",
]
