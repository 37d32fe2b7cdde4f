"""SDAR (``model_type: sdar_moe``): a sparse-expert decoder under a
block-causal mask, generating by denoising blocks of positions.

JetLM/SDAR-30B-A3B-Chat (``config.json`` keys in ``configs.SdarConfig``;
the layer is the Qwen3-MoE decoder layer, the sampler the family's public
``generate.py``).  With ``x`` the residual stream, ``N`` an RMSNorm and
``B = block_length``, for layer ``l``::

    a = N(x; ln_attn[l]);  q, k, v = a Wq[l], a Wk[l], a Wv[l]    (no bias)
    q = N(q; q_norm[l]),  k = N(k; k_norm[l])     per head, over head_dim
    RoPE (half rotation) on q, k; softmax at head_dim^-0.5 over the keys j
    with  j // B <= i // B : every earlier block and the whole own block
    x = x + attn Wo[l]
    m = N(x; ln_mlp[l]);  p = softmax_float32(m Wr[l])   over all E experts
    the K largest p_e, divided by their sum (norm_topk_prob)
    x = x + sum_e p_e (silu(m Wg[l, e]) * (m Wu[l, e])) Wd[l, e]
    after the last layer:  logits = N(x; ln_final) W_head

Logits at position ``i`` predict position ``i`` itself (no shift): a
position that holds ``mask_token_id`` is denoised into a token.  Every
layer is sparse and there is no shared expert, so ``intermediate_size``
multiplies nothing.

Published names: ``self_attn.{q,k,v,o}_proj`` (``wq wk wv wo``),
``self_attn.q_norm`` / ``k_norm``, ``input_layernorm`` (``ln_attn``),
``post_attention_layernorm`` (``ln_mlp``), ``mlp.gate`` (``w_router``),
``mlp.experts.{e}.{gate,up,down}_proj`` (``w_gate w_up w_down [l, e]``),
``norm`` (``ln_final``).  No loader maps them yet (models/loader.py refuses
the family): the serving path runs the seeded recipe below.

Three entry points share :func:`layer_body`: :func:`init_params`,
:func:`forward` (a whole sequence at once, no cache: the tests' path) and
:func:`mixed_layer` (the continuous scheduler's layer body).  How a block is
denoised — which positions a step keeps — is the mixed step's tail
(``serving/sched/mixed.py``) and the scheduler's (``serving/sched/``).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ..ops.moe_experts import expert_counts, moe_experts
from .configs import SdarConfig
from .llama import _attention, apply_rope, dense_init, rms_norm, rope_frequencies
from .quant import QUANTIZED_LAYER_MATRICES, mm

Params = dict[str, Any]

#: what int8 holds a column (and, for the expert stacks, an expert): the
#: four attention matrices and the three expert stacks.  The router stays
#: in the float dtype: its 128 logits order the experts
LAYER_MATRICES = QUANTIZED_LAYER_MATRICES
#: the expert stacks ``[L, E, in, out]``: the layer loop does not scan
#: them (``sched/mixed.py``); they go whole into ``ops/moe_experts.py``,
#: which fetches an expert from ``(layer, expert)``
WHOLE_STACKS = ("w_gate", "w_up", "w_down")
#: the norm vectors of a layer, in the order the init draws them
LAYER_NORMS = ("ln_attn", "q_norm", "k_norm", "ln_mlp")

__all__ = [
    "LAYER_MATRICES", "LAYER_NORMS", "WHOLE_STACKS", "block_causal_mask",
    "forward", "init_params", "layer_body", "layer_matrix_shapes",
    "matmul_params_per_token", "mixed_layer", "route",
]


def layer_matrix_shapes(config: SdarConfig) -> dict[str, tuple[int, ...]]:
    """Stacked shapes of the int8 matrices, in the order the init's key
    split follows: attention ``[L, in, out]``, expert stacks ``[L, E, in,
    out]``."""
    h, f, e = config.hidden_size, config.moe_intermediate_size, config.num_experts
    kvh, qh, d = config.num_kv_heads, config.num_heads, config.head_dim
    n = config.num_layers
    return {
        "wq": (n, h, qh * d),
        "wk": (n, h, kvh * d),
        "wv": (n, h, kvh * d),
        "wo": (n, qh * d, h),
        "w_gate": (n, e, h, f),
        "w_up": (n, e, h, f),
        "w_down": (n, e, f, h),
    }


def matmul_params_per_token(config: SdarConfig) -> int:
    """Layer weights that multiply ONE token (``serving/perf.py``): the
    attention matrices, the router, and ``num_experts_per_tok`` of the
    ``num_experts`` gated MLPs."""
    h, f = config.hidden_size, config.moe_intermediate_size
    attention = 2 * h * (config.num_heads + config.num_kv_heads) * config.head_dim
    experts = config.num_experts_per_tok * 3 * h * f
    return config.num_layers * (attention + h * config.num_experts + experts)


def init_params(
    config: SdarConfig,
    key: jax.Array,
    dtype: jnp.dtype = jnp.bfloat16,
    *,
    layer_matrix_init: Optional[Callable] = None,
) -> Params:
    """Seeded random init, layers stacked on axis 0.  A recipe two sides
    can follow: ``key`` split in four (embedding, matrices, head, vectors).
    The matrices' key is split in eight, in the order ``wq wk wv wo
    w_router w_gate w_up w_down``: every matrix normal x fan-in^-0.5
    (``llama.dense_init``), drawn, scaled and cast in one compiled program
    -- an attention matrix and the router as one stacked leaf, an expert
    stack A LAYER AT A TIME from its key split in ``num_layers`` (a stack
    of 128 experts is 2.4 G elements: its float32 draw would not fit the
    chip beside the rest), each layer's ``[E, in, out]`` through
    ``layer_matrix_init`` (which is where int8 is made) and stacked.  The
    vectors' key is split in five: the four norms of a layer and the final
    norm, ``1 + 0.1 x normal`` (ones would hide a swapped or a dropped
    norm)."""
    k_embed, k_layers, k_head, k_vectors = jax.random.split(key, 4)
    h, n = config.hidden_size, config.num_layers
    draw = jax.jit(
        lambda k, shape: dense_init(k, shape, h, dtype), static_argnames=("shape",)
    )
    if layer_matrix_init is None:
        def layer_matrix_init(k, shape):
            return draw(k, shape=shape)

    params: Params = {
        "embed": jax.block_until_ready(draw(k_embed, shape=(config.vocab_size, h))),
        "lm_head": jax.block_until_ready(draw(k_head, shape=(h, config.vocab_size))),
    }
    shapes = layer_matrix_shapes(config)
    names = ("wq", "wk", "wv", "wo", "w_router", "w_gate", "w_up", "w_down")
    layers: dict[str, Any] = {}
    for name, k in zip(names, jax.random.split(k_layers, len(names))):
        if name == "w_router":
            layers[name] = draw(k, shape=(n, h, config.num_experts))
        elif name in WHOLE_STACKS:
            each = [
                layer_matrix_init(k_layer, shapes[name][1:])
                for k_layer in jax.random.split(k, n)
            ]
            layers[name] = jax.block_until_ready(
                jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *each)
            )
            del each
        else:
            layers[name] = layer_matrix_init(k, shapes[name])

    def norm_scale(k, shape):
        return (1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    *k_norms, k_final = jax.random.split(k_vectors, len(LAYER_NORMS) + 1)
    for name, k in zip(LAYER_NORMS, k_norms):
        width = config.head_dim if name in ("q_norm", "k_norm") else h
        layers[name] = norm_scale(k, (n, width))
    params["layers"] = layers
    params["ln_final"] = norm_scale(k_final, (h,))
    return params


def route(config: SdarConfig, m: jax.Array, w_router: jax.Array,
          valid: Optional[jax.Array] = None) -> tuple[jax.Array, jax.Array]:
    """``m [T, H]`` through the router: ``(expert_ids [T, K], gates [T, K]
    float32)``.  The logits are float32 sums, the softmax runs over all
    experts, and the ``K`` largest probabilities are divided by their sum.
    A token with ``valid`` false is routed nowhere: its ids are
    ``num_experts``."""
    logits = jnp.matmul(m, w_router.astype(m.dtype), preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, expert_ids = jax.lax.top_k(probs, config.num_experts_per_tok)
    if config.norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    expert_ids = expert_ids.astype(jnp.int32)
    if valid is not None:
        expert_ids = jnp.where(valid[:, None], expert_ids, config.num_experts)
    return expert_ids, gates


def layer_body(
    config: SdarConfig, weights: Params, stacks: Params, layer: jax.Array,
    x: jax.Array, attend: Callable, valid: Optional[jax.Array] = None,
):
    """One layer on ``x [1, T, H]``.  ``weights`` are the layer's scanned
    leaves, ``stacks`` the whole expert stacks and ``layer`` the index into
    them.  ``attend(q, k, v)`` is given the three projections, q and k
    normed per head, and returns ``(attn [1, T, QH * D], aux)``: RoPE, the
    cache and the attention itself are the caller's.  Returns ``(x, aux,
    counts [E])``, the tokens each expert was given."""
    eps, d = config.rms_norm_eps, config.head_dim
    a = rms_norm(x, weights["ln_attn"], eps)

    def head_norm(y, scale):
        shape = y.shape
        return rms_norm(y.reshape(shape[:-1] + (-1, d)), scale, eps).reshape(shape)

    attn, aux = attend(
        head_norm(mm(a, weights["wq"]), weights["q_norm"]),
        head_norm(mm(a, weights["wk"]), weights["k_norm"]),
        mm(a, weights["wv"]),
    )
    x = x + mm(attn, weights["wo"])
    with jax.named_scope("moe_route"):
        m = rms_norm(x, weights["ln_mlp"], eps)[0]  # [T, H]
        expert_ids, gates = route(config, m, weights["w_router"], valid)
        counts = expert_counts(expert_ids, config.num_experts)
    with jax.named_scope("moe_experts"):
        y = moe_experts(
            m, expert_ids, gates, stacks["w_gate"], stacks["w_up"],
            stacks["w_down"], layer,
        )
    return x + y.astype(x.dtype)[None], aux, counts


# --------------------------------------------------------------------------
# the whole sequence at once (no cache)
# --------------------------------------------------------------------------


def block_causal_mask(positions: jax.Array, block: int) -> jax.Array:
    """``[B, T, T]``: query ``i`` sees key ``j`` iff ``j // block <= i //
    block``."""
    return (positions[:, None, :] // block) <= (positions[:, :, None] // block)


def forward(
    params: Params, config: SdarConfig, token_ids: jax.Array, positions: jax.Array,
) -> tuple[jax.Array, None]:
    """Logits ``[1, T, vocab]`` (float32) of ONE sequence under the
    block-causal mask, and None, as ``llama.forward`` returns without a
    cache: the program's own numerics (operands in the parameters' dtype,
    int8 matrices through ``mm`` and the expert op) without cache, kernel
    page or scheduler.  Position ``i``'s logits predict position ``i``."""
    assert token_ids.shape[0] == 1, "one sequence: the expert op's token axis is flat"
    inv_freq = rope_frequencies(config)
    x = jnp.take(params["embed"], token_ids, axis=0)
    t = x.shape[1]
    mask = block_causal_mask(positions, config.block_length)
    layers = params["layers"]
    stacks = {name: layers[name] for name in WHOLE_STACKS}

    def attend(q, k, v):
        q = q.reshape(1, t, config.num_heads, config.head_dim)
        k = k.reshape(1, t, config.num_kv_heads, config.head_dim)
        v = v.reshape(1, t, config.num_kv_heads, config.head_dim)
        return _attention(
            apply_rope(q, positions, inv_freq), apply_rope(k, positions, inv_freq),
            v, mask, config,
        ), None

    def layer_step(x, scanned):
        x, _, _ = layer_body(
            config, scanned["w"], stacks, scanned["layer"], x, attend
        )
        return x, None

    x, _ = jax.lax.scan(layer_step, x, {
        "w": {k: v for k, v in layers.items() if k not in WHOLE_STACKS},
        "layer": jnp.arange(config.num_layers, dtype=jnp.int32),
    })
    x = rms_norm(x, params["ln_final"], config.rms_norm_eps)
    logits = jnp.einsum(
        "bth,hv->btv", x, params["lm_head"], preferred_element_type=jnp.float32
    )
    return logits, None


# --------------------------------------------------------------------------
# the continuous scheduler's layer body (serving/sched/mixed.py)
# --------------------------------------------------------------------------


def mixed_layer(config: SdarConfig, step: Any) -> Callable:
    """The layer body of the mixed step for this family.  The carry is
    ``(x, pools, None)``; ``step.stacks`` holds the whole expert stacks
    (``WHOLE_STACKS``: not scanned) and ``scanned["layer"]`` indexes them
    and the KV pools.  Padding tokens (``step.valid`` false) are routed
    nowhere.  Each layer also yields the tokens its experts were given,
    ``[E]``: what the step's two expert counters are reduced from."""

    def layer_step(carry, scanned):
        x, pools, recurrent = carry
        x, pools, counts = layer_body(
            config, scanned["w"], step.stacks, scanned["layer"], x,
            lambda q, k, v: step.attend(q, k, v, pools, scanned["layer"]),
            step.valid,
        )
        return (x, pools, recurrent), counts

    return layer_step
