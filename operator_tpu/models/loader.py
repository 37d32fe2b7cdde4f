"""Checkpoint loading: HF-format weights -> our stacked JAX param pytree.

Sources:
- a local directory of ``*.safetensors`` files (with or without the
  ``model.safetensors.index.json`` shard index) in Hugging Face Llama
  layout, or
- any in-memory mapping of HF parameter names to arrays (used by the parity
  tests, which convert a freshly-initialised ``transformers`` model).

The HF layout stores projections as ``[out_features, in_features]``; we
transpose once at load so runtime is always ``x @ W`` (llama.py docstring),
and stack the per-layer tensors along a leading axis for ``lax.scan``.

The rebuild's "checkpoint restore" is loading weights into TPU HBM
(SURVEY.md §5 checkpoint entry): tensors stream lazily out of the shard
files, each stacked layer group is placed on device (optionally straight to
its mesh sharding) the moment its last layer arrives, and the host copies
are freed — peak host memory is the not-yet-complete groups plus one stack
temporary, not 2x the model.
"""

from __future__ import annotations

import json
import logging
import os
import re
import threading
import time
from typing import Any, Callable, Iterable, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .configs import ModelConfig
from .llama import Params

log = logging.getLogger(__name__)

_LAYER_RE = re.compile(r"model\.layers\.(\d+)\.(.+)\.weight")
_BIAS_RE = re.compile(r"model\.layers\.(\d+)\.self_attn\.([qkv])_proj\.bias")

#: HF sub-name -> (our stacked name, transpose?)
_LAYER_MAP = {
    "self_attn.q_proj": ("wq", True),
    "self_attn.k_proj": ("wk", True),
    "self_attn.v_proj": ("wv", True),
    "self_attn.o_proj": ("wo", True),
    "mlp.gate_proj": ("w_gate", True),
    "mlp.up_proj": ("w_up", True),
    "mlp.down_proj": ("w_down", True),
    "input_layernorm": ("ln_attn", False),
    "post_attention_layernorm": ("ln_mlp", False),
}


#: what a family's checkpoint holds beyond the Llama layout.  Ouro's
#: published names (transformers ``modeling_ouro.py``): a second norm
#: after the attention and after the MLP of every layer, and the exit
#: gate's linear layer beside the final norm
_OURO_LAYER_MAP = {
    **_LAYER_MAP,
    "input_layernorm_2": ("ln_attn_post", False),
    "post_attention_layernorm_2": ("ln_mlp_post", False),
}
#: HF name -> (our top-level name, the shape it is stored in here)
_OURO_TOP = {
    "model.early_exit_gate.weight": ("exit_w", (-1,)),  # [1, hidden]
    "model.early_exit_gate.bias": ("exit_b", ()),  # [1]
}
_FAMILY_LAYER_MAPS = {"llama": _LAYER_MAP, "ouro": _OURO_LAYER_MAP}
_FAMILY_TOPS = {"llama": {}, "ouro": _OURO_TOP}


def _to_numpy(value: Any) -> np.ndarray:
    """Accept numpy / jax arrays and torch tensors (incl. bfloat16)."""
    if isinstance(value, np.ndarray):
        return value
    if hasattr(value, "detach"):  # torch tensor, without importing torch here
        value = value.detach()
        if str(value.dtype) == "torch.bfloat16":
            return value.to(dtype=__import__("torch").float32).cpu().numpy()
        return value.cpu().numpy()
    return np.asarray(value)


def convert_hf_state_dict(
    state: "Mapping[str, Any] | Iterable[tuple[str, Any]]",
    config: ModelConfig,
    dtype: jnp.dtype = jnp.bfloat16,
    *,
    put: Optional[Callable[[str, np.ndarray], jax.Array]] = None,
) -> Params:
    """Map HF names to the stacked pytree the family's ``init_params``
    makes: the Llama layout, and for ``config.family == "ouro"`` the two
    further norms a layer and the exit gate.

    ``state`` may be a dict (e.g. a torch ``state_dict()``) or a lazy
    ``(name, tensor)`` iterable (``iter_safetensors``).  ``put(name, array)``
    controls device placement (default: jnp.asarray with ``dtype``); native
    checkpoint dtypes are preserved until ``put`` converts them.
    """
    if put is None:
        def put(name: str, array: np.ndarray) -> jax.Array:  # noqa: ANN001
            return jnp.asarray(array, dtype)

    n = config.num_layers
    layer_map = _FAMILY_LAYER_MAPS[config.family]
    family_top = _FAMILY_TOPS[config.family]
    per_layer: dict[str, list[Optional[np.ndarray]]] = {
        ours: [None] * n for ours, _ in layer_map.values()
    }
    if config.attention_bias:
        per_layer.update({f"b{axis}": [None] * n for axis in "qkv"})
    filled: dict[str, int] = {ours: 0 for ours in per_layer}
    layers: dict[str, jax.Array] = {}
    top: dict[str, jax.Array] = {}
    def record(ours: str, idx: int, array: np.ndarray) -> None:
        per_layer[ours][idx] = array
        filled[ours] += 1
        if filled[ours] == n:
            # group complete: stack (native dtype), place, free host refs
            layers[ours] = put(ours, np.stack(per_layer[ours]))
            per_layer[ours] = []

    items = state.items() if hasattr(state, "items") else state
    for name, raw in items:
        if name == "model.embed_tokens.weight":
            top["embed"] = put("embed", _to_numpy(raw))
        elif name == "model.norm.weight":
            top["ln_final"] = put("ln_final", _to_numpy(raw))
        elif name == "lm_head.weight":
            top["lm_head"] = put("lm_head", _to_numpy(raw).T)
        elif name in family_top:
            ours, shape = family_top[name]
            top[ours] = put(ours, _to_numpy(raw).reshape(shape))
        else:
            bias_match = _BIAS_RE.fullmatch(name)
            if bias_match:
                idx = int(bias_match.group(1))
                if not config.attention_bias:
                    log.debug("config has no attention_bias; ignoring %s", name)
                elif idx < n:
                    record(f"b{bias_match.group(2)}", idx, _to_numpy(raw))
                continue
            match = _LAYER_RE.fullmatch(name)
            if not match:
                log.debug("ignoring unknown checkpoint tensor %s", name)
                continue
            idx, sub = int(match.group(1)), match.group(2)
            mapped = layer_map.get(sub)
            if mapped is None:
                log.debug("ignoring unknown layer tensor %s", name)
                continue
            ours, transpose = mapped
            if idx >= n:
                continue  # scaled-down config loads a prefix of the layers
            array = _to_numpy(raw)
            record(ours, idx, array.T if transpose else array)

    missing = [
        f"{ours}[{i}]"
        for ours, slots in per_layer.items()
        if ours not in layers
        for i, s in enumerate(slots)
        if s is None
    ]
    if missing:
        raise ValueError(f"checkpoint is missing {len(missing)} tensors, e.g. {missing[:4]}")
    absent = [hf for hf, (ours, _) in family_top.items() if ours not in top]
    if absent:
        raise ValueError(f"checkpoint is missing {absent}")
    params: Params = {"embed": top["embed"], "layers": layers, "ln_final": top["ln_final"]}
    params.update({ours: top[ours] for ours, _ in family_top.values()})
    if config.tie_embeddings:
        if "lm_head" in top:
            log.info("config ties embeddings; ignoring checkpoint lm_head")
    else:
        if "lm_head" not in top:
            raise ValueError("checkpoint has no lm_head.weight but config does not tie embeddings")
        params["lm_head"] = top["lm_head"]
    return params


# --------------------------------------------------------------------------
# safetensors directory loading
# --------------------------------------------------------------------------


def iter_safetensors(checkpoint_dir: str):
    """Yield ``(name, tensor)`` lazily across all shard files, so the loader
    holds at most the layer tensors not yet flushed to device (completed
    groups are stacked + placed + freed as soon as their last layer
    arrives — see convert_hf_state_dict)."""
    from safetensors import safe_open

    index_path = os.path.join(checkpoint_dir, "model.safetensors.index.json")
    files: list[str]
    if os.path.exists(index_path):
        with open(index_path) as f:
            index = json.load(f)
        files = sorted({os.path.join(checkpoint_dir, v) for v in index["weight_map"].values()})
    else:
        files = sorted(
            os.path.join(checkpoint_dir, f)
            for f in os.listdir(checkpoint_dir)
            if f.endswith(".safetensors")
        )
    if not files:
        raise FileNotFoundError(f"no .safetensors files under {checkpoint_dir}")

    for path in files:
        with safe_open(path, framework="np") as f:
            for name in f.keys():
                yield name, f.get_tensor(name)


# inverse of _LAYER_MAP: ours -> (hf name, transpose) — derived so the two
# directions can never drift
_HF_LAYER_NAMES = {ours: (hf, t) for hf, (ours, t) in _LAYER_MAP.items()}


def save_params(
    params: Params,
    checkpoint_dir: str,
    config: ModelConfig,
    *,
    shard_bytes: int = 4 << 30,
) -> list[str]:
    """Write our stacked pytree back out as a sharded HF-layout safetensors
    checkpoint (with ``model.safetensors.index.json``) that ``load_params``
    — or any HF Llama loader — reads back.

    Completes the checkpoint/resume story for the fine-tune flows
    (parallel/train.py, parallel/lora.py merge_lora output): train on the
    mesh, save, reload for serving.  Quantized trees must be dequantized or
    merged first (HF layout has no {q, s} convention).

    Returns the written shard file names.
    """
    from safetensors.numpy import save_file

    from .quant import is_quantized

    if config.family != "llama":
        raise NotImplementedError(
            f"save_params writes the Llama layout only, not the "
            f"{config.family!r} family's (model {config.name!r})"
        )

    if is_quantized(params):
        raise ValueError(
            "save_params writes HF layout, which has no int8 {q, s} "
            "convention — expand with quant.dequantize_params first "
            "(merge_lora output still holds untargeted int8 groups)"
        )
    os.makedirs(checkpoint_dir, exist_ok=True)

    def tensors():
        """(name, array) lazily — one stacked group fetched at a time, so
        host peak is one group + the shard being packed (mirrors the
        loader's streaming discipline)."""
        yield "model.embed_tokens.weight", np.asarray(params["embed"])
        yield "model.norm.weight", np.asarray(params["ln_final"])
        if "lm_head" in params:
            yield "lm_head.weight", np.ascontiguousarray(
                np.asarray(params["lm_head"]).T
            )
        for ours, (hf, transpose) in _HF_LAYER_NAMES.items():
            stacked = np.asarray(params["layers"][ours])
            for i in range(config.num_layers):
                tensor = stacked[i].T if transpose else stacked[i]
                yield f"model.layers.{i}.{hf}.weight", np.ascontiguousarray(tensor)
            del stacked
        for axis in "qkv":
            if f"b{axis}" not in params["layers"]:
                continue
            stacked = np.asarray(params["layers"][f"b{axis}"])
            for i in range(config.num_layers):
                yield (
                    f"model.layers.{i}.self_attn.{axis}_proj.bias",
                    np.ascontiguousarray(stacked[i]),
                )
            del stacked

    # pack + write shard-by-shard; rename to the final -of-NNNNN names once
    # the count is known
    weight_map: dict[str, str] = {}
    tmp_files: list[str] = []
    shard: dict[str, np.ndarray] = {}
    size = total_size = 0

    def flush():
        nonlocal shard, size
        if not shard:
            return
        fname = f"model-{len(tmp_files) + 1:05d}.tmp"
        save_file(shard, os.path.join(checkpoint_dir, fname))
        tmp_files.append(fname)
        for name in shard:
            weight_map[name] = fname
        shard, size = {}, 0

    for name, array in tensors():
        if size and size + array.nbytes > shard_bytes:
            flush()
        shard[name] = array
        size += array.nbytes
        total_size += array.nbytes
    flush()

    total = len(tmp_files)
    files: list[str] = []
    renames = {}
    for i, tmp in enumerate(tmp_files, start=1):
        final = f"model-{i:05d}-of-{total:05d}.safetensors"
        os.replace(
            os.path.join(checkpoint_dir, tmp), os.path.join(checkpoint_dir, final)
        )
        renames[tmp] = final
        files.append(final)
    weight_map = {name: renames[tmp] for name, tmp in weight_map.items()}
    with open(os.path.join(checkpoint_dir, "model.safetensors.index.json"), "w") as f:
        json.dump(
            {"metadata": {"total_size": total_size}, "weight_map": weight_map}, f
        )
    return files


def load_params(
    checkpoint_dir: str,
    config: ModelConfig,
    dtype: jnp.dtype = jnp.bfloat16,
    *,
    shardings: Optional[Mapping[str, Any]] = None,
    quantize: bool = False,
) -> Params:
    """Load a HF Llama checkpoint directory onto device.

    ``shardings`` optionally maps our param names (embed/lm_head/ln_final or
    stacked layer names wq/wk/...) to ``jax.sharding.Sharding``s so each
    tensor goes straight to its mesh placement (the TP path for Llama-3-8B
    on v5e-4, BASELINE config ladder); for quantized matrices the entry may
    be a ``{"q": ..., "s": ...}`` mapping (parallel/mesh.py param_shardings
    with quantized=True), or a single sharding applied to ``q`` with ``s``
    replicated (a matrix-rank spec cannot place the rank-2 scales).

    ``quantize=True`` quantizes each layer-matrix GROUP the moment it is
    placed (models/quant.py int8 scheme), so device peak memory is the int8
    tree plus ONE bf16 group — loading then calling ``quantize_params``
    would peak at float tree + int8 tree, an OOM for 8B-class checkpoints
    on a 16 GB chip.
    """
    from .quant import quantize_matrix, quantized_layer_matrices

    if config.family not in _FAMILY_LAYER_MAPS:
        raise NotImplementedError(
            f"no checkpoint converter for the {config.family!r} family "
            f"(model {config.name!r}): convert_hf_state_dict maps the tensor "
            f"names of {sorted(_FAMILY_LAYER_MAPS)} only; serve it with "
            "ALLOW_RANDOM_WEIGHTS=true"
        )
    quantized = quantized_layer_matrices(config)
    state = iter_safetensors(checkpoint_dir)
    quantize_jit = jax.jit(quantize_matrix) if quantize else None

    def place(value: jax.Array, sharding: Any) -> jax.Array:
        return jax.device_put(value, sharding) if sharding is not None else value

    def put(name: str, array: np.ndarray) -> Any:
        value = jnp.asarray(array, dtype)
        sharding = shardings.get(name) if shardings else None
        if quantize and name in quantized:
            out = quantize_jit(value)
            # block so XLA frees the bf16 group before the next one arrives
            out = jax.block_until_ready(out)
            del value
            if isinstance(sharding, Mapping):
                return {k: place(v, sharding.get(k)) for k, v in out.items()}
            # single sharding: it has the matrix's rank, so it can only
            # place q; scales stay replicated (they're [n_layers, out])
            return {"q": place(out["q"], sharding), "s": out["s"]}
        return place(value, sharding)

    return convert_hf_state_dict(state, config, dtype, put=put)


class _AsyncLoad:
    """Handle for an in-flight streamed weight load (``load_params_async``).

    The load streams safetensors groups onto device from a daemon thread:
    HBM transfers overlap host-side work — in the serving provider that is
    the AOT-cache preload + any live compiles, which need only SHAPES, not
    weight values (serving/provider.py bring-up overlap).  ``result()``
    joins and re-raises any load failure on the caller."""

    def __init__(self, target, args, kwargs) -> None:
        self._result: Any = None
        self._error: Optional[BaseException] = None
        self._started = time.perf_counter()
        self.seconds: Optional[float] = None

        def _run() -> None:
            try:
                self._result = target(*args, **kwargs)
            except BaseException as exc:  # noqa: BLE001 - re-raised in result()
                self._error = exc
            finally:
                self.seconds = time.perf_counter() - self._started

        self._thread = threading.Thread(
            target=_run, name="weight-stream", daemon=True
        )
        self._thread.start()

    def done(self) -> bool:
        return not self._thread.is_alive()

    def result(self, timeout: Optional[float] = None) -> Params:
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise TimeoutError("weight stream still loading")
        if self._error is not None:
            raise self._error
        return self._result


def load_params_async(
    checkpoint_dir: str,
    config: ModelConfig,
    dtype: jnp.dtype = jnp.bfloat16,
    *,
    shardings: Optional[Mapping[str, Any]] = None,
    quantize: bool = False,
) -> _AsyncLoad:
    """Start ``load_params`` on a background thread and return a handle.

    Safe to overlap with tracing/lowering/AOT-cache deserialization: jax
    device_put and the quantize jit are thread-safe, and the consumer only
    touches params after ``result()``.  The GIL releases during the actual
    HBM transfers and safetensors reads, so the overlap is real, not
    cooperative."""
    return _AsyncLoad(
        load_params, (checkpoint_dir, config, dtype),
        {"shardings": shardings, "quantize": quantize},
    )
