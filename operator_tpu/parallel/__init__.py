"""Mesh / sharding layer (SURVEY.md §2.3, §7 stage 4): DP + TP + FSDP over
ICI via jax.sharding, multi-host over DCN via jax.distributed."""

from .mesh import (
    AXES,
    MeshPlan,
    batch_spec,
    device_memory_bytes,
    initialize_distributed,
    kv_cache_spec,
    logits_spec,
    make_mesh,
    mesh_summary,
    paged_cache_specs,
    param_shardings,
    param_specs,
    plan_for,
    shard_params,
    validate_param_shardings,
)
from .lora import (
    apply_lora,
    init_lora,
    load_lora,
    lora_param_count,
    lora_shardings,
    make_lora_train_step,
    merge_lora,
    save_lora,
    stack_adapters,
    zero_lora,
)
from .train import (
    TrainState,
    load_train_state,
    make_optimizer,
    make_train_step,
    next_token_loss,
    save_train_state,
)

__all__ = [name for name in dir() if not name.startswith("_")]
