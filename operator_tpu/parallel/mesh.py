"""Device mesh + sharding rules — the ICI/DCN scaling layer.

The reference has no distributed compute at all (SURVEY.md §2.3); this module
is the tpu-native equivalent of the comm backend the rebuild must add:

- one ``jax.sharding.Mesh`` with named axes ``("dp", "fsdp", "tp")``:
  * **dp**   — data parallel over failure events (BASELINE config 5:
    Mistral-7B DP over a v5e-8's ICI);
  * **tp**   — tensor parallel within a pod (Llama-3-8B on v5e-4: heads and
    MLP columns split 4-way, XLA inserts the psum after the row-parallel
    projections);
  * **fsdp** — parameter sharding for training/fine-tune flows (LoRA-style
    adaptation of the explanation model) and for fitting larger checkpoints;
- multi-host: ``initialize_distributed()`` wraps ``jax.distributed`` so DCN
  topologies work with the same mesh axes (dp outermost over hosts, so
  cross-host traffic is gradient/batch-level, and tp stays inside a pod's
  ICI domain — the scaling-book layout).

Pipeline (pp), expert (ep) and ring/sequence (sp) axes are deliberately not
wired into the default mesh: at the 1B-8B scale this system serves, a v5e-8
fits every model with dp x tp alone (SURVEY.md §5 long-context: "ring/Ulysses
CP is not required at 8B scale").  Long-log scaling is handled by windowed
embedding scoring (operator_tpu.patterns) + prompt context selection instead
of sequence-parallel attention.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.configs import ModelConfig
from ..models.llama import Params

log = logging.getLogger(__name__)

AXES = ("dp", "fsdp", "tp")


@dataclass(frozen=True)
class MeshPlan:
    dp: int = 1
    fsdp: int = 1
    tp: int = 1

    @property
    def total(self) -> int:
        return self.dp * self.fsdp * self.tp


def device_memory_bytes(device: Any) -> int:
    """The device's memory size as its runtime reports it
    (``memory_stats()["bytes_limit"]``).  A backend that reports none (the
    CPU) raises: sizing a mesh from an assumed chip would hide which
    device the program is really on."""
    stats = device.memory_stats() or {}
    limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    if not limit:
        raise ValueError(
            f"{device.device_kind!r} reports no memory size "
            f"(memory_stats() = {stats!r}); give the mesh explicitly "
            f"('dp=N,tp=N') instead of sizing it from the device"
        )
    return int(limit)


def plan_for(
    n_devices: int,
    *,
    tp: Optional[int] = None,
    fsdp: int = 1,
    config: Optional[ModelConfig] = None,
    hbm_bytes: Optional[int] = None,
) -> MeshPlan:
    """Choose a mesh factorisation for ``n_devices``.

    Defaults: smallest tp that fits the model's KV heads evenly (tp must
    divide num_kv_heads so attention never crosses chips for one KV head),
    everything else data-parallel — the throughput-first layout for serving.
    Sizing tp from ``config`` needs ``hbm_bytes``, one device's measured
    memory (:func:`device_memory_bytes`); 12.5% of it is left to XLA.
    """
    if tp is None:
        tp = 1
        if config is not None:
            if not hbm_bytes:
                raise ValueError("plan_for(config=...) needs hbm_bytes")
            # Llama-3-8B wants tp=4 on v5e-4 (16 GB HBM/chip); smaller models
            # run tp=1 and scale with dp alone
            approx_params = (
                config.vocab_size * config.hidden_size * 2
                + config.num_layers
                * (4 * config.hidden_size * config.num_heads * config.head_dim
                   + 3 * config.hidden_size * config.intermediate_size)
            )
            bytes_needed = approx_params * 2  # bf16
            hbm_per_chip = hbm_bytes * 0.875
            while tp < n_devices and (bytes_needed / tp) > hbm_per_chip:
                tp *= 2
            while tp > 1 and config.num_kv_heads % tp != 0:
                tp //= 2
    if tp * fsdp > n_devices:
        raise ValueError(f"tp*fsdp={tp*fsdp} exceeds {n_devices} devices")
    dp = n_devices // (tp * fsdp)
    plan = MeshPlan(dp=dp, fsdp=fsdp, tp=tp)
    if plan.total != n_devices:
        log.warning("mesh uses %d of %d devices", plan.total, n_devices)
    return plan


def make_mesh(plan: Optional[MeshPlan] = None, devices: Optional[list] = None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    plan = plan or plan_for(len(devices))
    used = devices[: plan.total]
    array = np.asarray(used).reshape(plan.dp, plan.fsdp, plan.tp)
    return Mesh(array, AXES)


def initialize_distributed(**kwargs: Any) -> None:
    """Multi-host init over DCN.  Must run before anything touches the jax
    backend (so this function must not query devices/process_count itself —
    that would initialise a single-host backend and make later init fail).
    Initialises when the caller passes coordinator kwargs or the standard
    coordinator env vars are present; single-process launches no-op."""
    import os

    if kwargs or os.environ.get("JAX_COORDINATOR_ADDRESS") or os.environ.get(
        "COORDINATOR_ADDRESS"
    ):
        jax.distributed.initialize(**kwargs)


# --------------------------------------------------------------------------
# sharding rules
# --------------------------------------------------------------------------


def param_specs(
    config: ModelConfig, *, shard_fsdp: bool = True, quantized: bool = False
) -> Params:
    """PartitionSpecs mirroring the param pytree of ``llama.init_params``.

    Megatron-style TP: column-parallel in-projections (heads / MLP columns
    on ``tp``), row-parallel out-projections (XLA auto-inserts the psum on
    the residual add).  fsdp shards the *other* matrix axis so tp x fsdp
    tiles every large matrix fully.

    ``quantized`` mirrors the int8 tree (models/quant.py): each layer matrix
    becomes ``{q: <matrix spec>, s: <out-axis spec>}`` — per-output-channel
    scales shard exactly like the matrix's output axis.
    """
    f = "fsdp" if shard_fsdp else None
    layer_specs: dict[str, Any] = {
        "wq": P(None, f, "tp"),
        "wk": P(None, f, "tp"),
        "wv": P(None, f, "tp"),
        "wo": P(None, "tp", f),
        "w_gate": P(None, f, "tp"),
        "w_up": P(None, f, "tp"),
        "w_down": P(None, "tp", f),
        "ln_attn": P(None, None),
        "ln_mlp": P(None, None),
    }
    if config.attention_bias:
        # Qwen2 q/k/v biases live on the projections' OUTPUT axis, which is
        # tp-sharded — the bias add happens on the tp-local shard
        layer_specs["bq"] = P(None, "tp")
        layer_specs["bk"] = P(None, "tp")
        layer_specs["bv"] = P(None, "tp")
    if quantized:
        from ..models.quant import QUANTIZED_LAYER_MATRICES

        for name in QUANTIZED_LAYER_MATRICES:
            spec = layer_specs[name]
            layer_specs[name] = {"q": spec, "s": P(None, spec[2])}  # out axis
    specs: dict[str, Any] = {
        "embed": P(f, None),   # vocab-sharded over fsdp, hidden replicated
        "layers": layer_specs,
        "ln_final": P(None),
    }
    if not config.tie_embeddings:
        specs["lm_head"] = P(f, "tp")
    return specs


def param_shardings(mesh: Mesh, config: ModelConfig, **kw: Any) -> Params:
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), param_specs(config, **kw),
        is_leaf=lambda x: isinstance(x, P),
    )


def batch_spec() -> P:
    """Token/position batches shard over (dp, fsdp) jointly — fsdp acts as a
    second data axis at run time (ZeRO-style)."""
    return P(("dp", "fsdp"), None)


def kv_cache_spec() -> P:
    """[layers, B, S, kv_heads, head_dim]: batch over dp(+fsdp), heads over tp."""
    return P(None, ("dp", "fsdp"), None, "tp", None)


def paged_cache_specs() -> Any:
    """PartitionSpecs mirroring the ``PagedKVCache`` pytree.

    The page pool is shared by every sequence (any slot may hold any page),
    so the page axis can NOT shard over dp — pages shard over **tp on the
    KV-head axis** only, and dp parallelism comes from the batch-sharded
    queries/tokens.  The per-step token writes a dp shard contributes are
    [B/dp, 1, KH/tp, D] — kilobytes over ICI — so replicating the pool
    across dp costs bandwidth only at that scatter, not attention reads.
    Tables/lengths are tiny and replicated.
    """
    from ..ops.paged_attention import PagedKVCache

    pages = P(None, None, None, "tp", None)  # [L, pages, page_size, KH, D]
    return PagedKVCache(
        k_pages=pages, v_pages=pages, page_table=P(None, None), lengths=P(None)
    )


def logits_spec() -> P:
    return P(("dp", "fsdp"), None, "tp")


def shard_params(params: Params, mesh: Mesh, config: ModelConfig, **kw: Any) -> Params:
    """Place an existing (host or single-device) param tree onto the mesh."""
    shardings = param_shardings(mesh, config, **kw)
    return jax.tree_util.tree_map(jax.device_put, params, shardings)


def validate_param_shardings(
    mesh: Mesh, config: ModelConfig, *, quantized: bool = False
) -> int:
    """Prove every parameter leaf divides evenly over the mesh — WITHOUT
    allocating the model (``jax.eval_shape``).  Returns the leaf count.

    This is how the llama-3-8b factorisation (kv_heads=8 @ tp=4, vocab
    128256 over fsdp, quantized {q, s} trees) is checked on a virtual mesh
    before any real multi-chip run: ``NamedSharding.shard_shape`` raises on
    any axis a mesh dimension does not divide.
    """
    from ..models.llama import init_params

    def build(key):
        params = init_params(config, key)
        if quantized:
            from ..models.quant import quantize_params

            params = quantize_params(params, config)
        return params

    shapes = jax.eval_shape(build, jax.ShapeDtypeStruct((2,), np.uint32))
    shardings = param_shardings(mesh, config, quantized=quantized)
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    sharding_leaves = treedef.flatten_up_to(shardings)
    for leaf, sharding in zip(leaves, sharding_leaves):
        sharding.shard_shape(leaf.shape)  # raises on non-divisible axes
    return len(leaves)


def mesh_summary(mesh: Mesh) -> str:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return f"mesh {sizes} over {mesh.devices.size} {mesh.devices.flat[0].platform} device(s)"
