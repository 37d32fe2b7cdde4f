"""Int8 weight-only quantization for serving.

Decode at batch sizes this system runs (8-32 slots) is HBM-bandwidth bound:
every step streams the full weight set through the MXU once, so halving
weight bytes is close to halving step time — and it is what fits
Mistral-7B-per-chip DP on a 16 GB v5e (BASELINE config 5) with KV headroom.

Scheme: symmetric per-output-channel absmax.  For a stored ``[in, out]``
matrix ``W``::

    s   = absmax(W, axis=in) / 127          # [out]
    q   = round(W / s)  as int8             # [in, out]
    x @ W  ≈  (x @ q) * s                   # scale folds in AFTER the matmul

Per-output-channel scales commute with the contraction, so the dequant is
one fused multiply on the [B, T, out] activation — XLA fuses it into the
matmul epilogue; the int8->bf16 cast happens in-register.  The seven layer
matrices (wq/wk/wv/wo/w_gate/w_up/w_down — the overwhelming parameter mass)
are quantized; embeddings, lm_head and norms stay in the float dtype
(embedding quality is vocab-critical and the tied-embedding transpose would
need per-row scales on the head side).

TP sharding composes cleanly: scales are per-output-channel, so they shard
exactly like the matrix's output axis (parallel/mesh.py mirrors the
{q, s} tree).

The continuous step holds the three projections it splits into heads
(``HEAD_PROJECTIONS``) transposed, ``{qt: int8 [..., out, in], s}``
(:func:`hold_head_projections`): its consumer wants q, k and v
heads-major, and XLA computes such a product batched over heads from a
weight whose ``in`` axis is minor.  From ``[in, out]`` that was a
transposing copy of every layer's matrix in every step (or of the whole
stack, at the start of a step that loops its stack); from ``[out, in]``
the layer's slice of the stack is read as it lies.  Every other reader
(the wave engine, the mesh specs, LoRA, ``save_params``, the loader)
keeps the canonical ``{q, s}``.

The reference has no quantization (or any ML) — this is pure tpu-native
performance work against the north-star throughput target (BASELINE.md).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from .configs import ModelConfig

Params = dict[str, Any]

#: the Llama family's layer matrices (stored [n_layers, in, out]); what a
#: given model quantises is its family's list: ``quantized_layer_matrices``
QUANTIZED_LAYER_MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")

#: the projections whose product ``sched/mixed.py attend`` splits into
#: heads, in every family: what :func:`hold_head_projections` transposes
HEAD_PROJECTIONS = ("wq", "wk", "wv")


def quantized_layer_matrices(config: ModelConfig) -> tuple:
    """The layer matrices ``config``'s family holds int8 a column."""
    from . import family_of

    return tuple(family_of(config).LAYER_MATRICES)


def is_quantized(params: Params) -> bool:
    """True if ANY layer matrix is an int8 {q, s} group — partially-merged
    trees (e.g. LoRA merged into a quantized base, which dequantizes only
    its targets) count as quantized."""
    return any(
        isinstance(leaf, dict) and ("q" in leaf or "qt" in leaf)
        for leaf in params.get("layers", {}).values()
    )


def dequantize_params(params: Params, dtype: jnp.dtype = jnp.bfloat16) -> Params:
    """Expand every int8 {q, s} group back to a float matrix (e.g. before
    save_params, whose HF layout has no quantized convention)."""
    layers = {
        name: (
            (leaf["q"].astype(jnp.float32) * leaf["s"][..., None, :]).astype(dtype)
            if isinstance(leaf, dict) and "q" in leaf
            else leaf
        )
        for name, leaf in params["layers"].items()
    }
    return {**params, "layers": layers}


def quantize_matrix(w: jax.Array) -> dict[str, jax.Array]:
    """[..., in, out] float -> {q: int8 [..., in, out], s: [..., out]}."""
    w32 = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w32), axis=-2)  # [..., out]
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w32 / scale[..., None, :]), -127, 127).astype(jnp.int8)
    return {"q": q, "s": scale}


def quantize_params(params: Params, config: ModelConfig) -> Params:
    """Quantize the layer matrices of a loaded/initialised param tree."""
    layers = dict(params["layers"])
    for name in quantized_layer_matrices(config):
        layers[name] = quantize_matrix(layers[name])
    return {**params, "layers": layers}


@jax.jit
def _transposed(q: jax.Array) -> jax.Array:
    return jnp.swapaxes(q, -1, -2)


def hold_head_projections(params: Params) -> Params:
    """``params`` as the continuous step reads them: each int8
    ``HEAD_PROJECTIONS`` leaf ``{q: [L, in, out], s: [L, out]}`` becomes
    ``{qt: [L, out, in], s}``, the same values transposed, and every other
    leaf is the one given.  A tree already held, or a float tree, comes
    back as it is.  The given tree is not changed: a caller that rebinds
    its name to the result frees each original matrix."""
    layers = dict(params["layers"])
    for name in HEAD_PROJECTIONS:
        leaf = layers.get(name)
        if isinstance(leaf, dict) and "q" in leaf:
            layers[name] = {"qt": _transposed(leaf["q"]), "s": leaf["s"]}
    return {**params, "layers": layers}


def mm(
    x: jax.Array, w: "jax.Array | dict[str, jax.Array]", out_dtype: Any = None,
) -> jax.Array:
    """``x @ W`` for plain or quantized weights.

    The int8 matrix is cast to the activation dtype going INTO the matmul
    (the MXU has no int8xbf16 path; the cast is free relative to the HBM
    read we saved) and the per-channel scale folds into the epilogue.
    With ``out_dtype`` the product leaves the accumulator in that dtype
    and the scale is applied there: no rounding to the activations' dtype
    between the sum and whoever reads it (models/ouro.py norms every
    branch in float32).  A held ``{qt, s}`` (:func:`hold_head_projections`)
    is contracted on its minor axis.
    """
    if isinstance(w, dict) and "qt" in w:
        y = jax.lax.dot_general(
            x, w["qt"].astype(x.dtype), (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=out_dtype,
        )
        return y * w["s"].astype(out_dtype or x.dtype)
    if out_dtype is not None:
        weight = w["q"] if isinstance(w, dict) else w
        y = jnp.matmul(x, weight.astype(x.dtype), preferred_element_type=out_dtype)
        return y * w["s"].astype(out_dtype) if isinstance(w, dict) else y
    if isinstance(w, dict):
        return (x @ w["q"].astype(x.dtype)) * w["s"].astype(x.dtype)
    return x @ w


def quantized_bytes(params: Params) -> int:
    return sum(int(p.size * p.dtype.itemsize) for p in jax.tree_util.tree_leaves(params))


def init_params_quantized(
    config: ModelConfig, key: jax.Array, dtype: jnp.dtype = jnp.bfloat16
) -> Params:
    """Random-init an ALREADY-quantized tree without ever materialising the
    full float tree.

    ``init_params`` + ``quantize_params`` peaks at float-tree + int8-tree
    simultaneously — for llama-3-8b that is ~16 GB of bf16 alone, i.e. an
    OOM before quantization can start on a 16 GB chip.  Here each stacked
    layer matrix is initialised and quantized in its own jitted call (the
    float tensor is a transient XLA frees immediately), so peak memory is
    the final int8 tree plus ONE bf16 matrix stack (~1 GB at 8B scale).

    Matches ``quantize_params(init_params(config, key, dtype), config)`` to
    within one quantization level / one bf16 ulp (same per-matrix PRNG keys
    and distribution; XLA rounds fused init slightly differently across jit
    boundaries, so bit-exactness is not promised) — tests/test_quant.py
    pins the tolerance.
    """
    from . import family_of
    from .llama import dense_init

    h = config.hidden_size
    # dense-init and quantize are SEPARATE jits on purpose: fused, XLA elides
    # the f32->bf16->f32 round trip and quantizes unrounded values — bit
    # drift vs the two-step reference path this function promises to match
    init_dense = jax.jit(
        lambda key, shape: dense_init(key, shape, h, dtype),
        static_argnames=("shape",),
    )
    quantize = jax.jit(quantize_matrix)

    def init_quantized_matrix(key: jax.Array, shape: tuple[int, ...]) -> Any:
        # block per matrix so the bf16 transient frees before the next one
        return jax.block_until_ready(quantize(init_dense(key, shape=shape)))

    return family_of(config).init_params(
        config, key, dtype, layer_matrix_init=init_quantized_matrix
    )


def parity_report(
    params_float: Params,
    params_quant: Params,
    config: ModelConfig,
    prompts: "list[list[int]]",
    *,
    max_new_tokens: int = 16,
) -> dict:
    """The int8-by-default parity gate (docs/SERVING.md "Bring-up").

    Greedy-decodes each token-id prompt under the float params and the
    quantized params on a fresh single-sequence KV cache each, and reports

    - ``greedy_match``: every prompt produced token-identical output,
    - ``max_logit_diff``: max abs difference between the two logit streams
      along the float path's greedy trajectory (teacher-forced with the
      float tokens, so the comparison never diverges and the number stays
      meaningful even when an argmax near-tie flips a token).

    Tiny models must pass ``greedy_match``; 1B-class configs gate on
    ``max_logit_diff`` instead (absolute threshold), because a near-tie
    argmax flip on a long generation is expected at that scale while the
    logit error stays bounded by the quantization step.
    """
    from .llama import forward

    def last_logits(params: Params, ids: list[int]) -> jax.Array:
        arr = jnp.asarray([ids], jnp.int32)
        pos = jnp.arange(len(ids), dtype=jnp.int32)[None]
        logits, _ = forward(params, config, arr, pos)
        return logits[0, -1]

    def greedy(params: Params, prompt: list[int]) -> tuple[list[int], list[jax.Array]]:
        # cache-free full-sequence forward per step: O(T^2) but the gate
        # runs tiny configs only, and it exercises the same numerics
        ids = list(prompt)
        toks: list[int] = []
        steps: list[jax.Array] = []
        for _ in range(max_new_tokens):
            logits = last_logits(params, ids)
            steps.append(logits)
            tok = int(jnp.argmax(logits))
            toks.append(tok)
            ids.append(tok)
        return toks, steps

    def forced(params: Params, prompt: list[int], driven: list[int]) -> list[jax.Array]:
        # teacher-forced along the FLOAT path's tokens: logit comparison
        # stays step-aligned even if the quantized argmax flips somewhere
        ids = list(prompt)
        steps: list[jax.Array] = []
        for tok in driven:
            steps.append(last_logits(params, ids))
            ids.append(tok)
        return steps

    matches = []
    max_diff = 0.0
    for prompt in prompts:
        float_toks, float_steps = greedy(params_float, prompt)
        quant_toks, _ = greedy(params_quant, prompt)
        matches.append(quant_toks == float_toks)
        quant_steps = forced(params_quant, prompt, float_toks)
        for a, b in zip(float_steps, quant_steps):
            diff = float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - b.astype(jnp.float32)
            )))
            max_diff = max(max_diff, diff)
    return {
        "greedy_match": all(matches),
        "prompts": len(prompts),
        "mismatched_prompts": sum(1 for m in matches if not m),
        "max_logit_diff": max_diff,
    }
