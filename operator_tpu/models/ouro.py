"""Ouro (LoopLM): one stack of decoder layers run several times a token.

ByteDance/Ouro (``config.json`` keys in ``configs.OuroConfig``; the
published forward is transformers' ``modeling_ouro.py``).  With ``x`` the
residual stream, ``N`` an RMSNorm, the same weights in every pass, for
pass ``t = 0 .. total_ut_steps - 1`` and layer ``l = 0 .. L - 1``::

    a = N(x; ln_attn[l]);  q, k, v = a Wq[l], a Wk[l], a Wv[l]    (no bias)
    RoPE (half rotation) on q, k; causal softmax at head_dim^-0.5 over the
    keys and values of THIS pass and layer only (cache plane t*L + l)
    x = x + N(attn Wo[l]; ln_attn_post[l])
    m = N(x; ln_mlp[l])
    x = x + N((silu(m Wg[l]) * (m Wu[l])) Wd[l]; ln_mlp_post[l])
    after layer L-1 of EVERY pass:  x = N(x; ln_final)
    logits = x W_head   (the last pass's x)

    exit gate:  lam_t = sigmoid(x_t . exit_w + exit_b) on the normed x of
    pass t;  p_t = lam_t * prod_{s<t} (1 - lam_s),  the last pass takes the
    remainder.  A row would leave the loop where the running sum of p
    reaches ``early_exit_threshold``; at the published 1.0 none does.

Published names: ``input_layernorm`` (``ln_attn``), ``input_layernorm_2``
(``ln_attn_post``), ``post_attention_layernorm`` (``ln_mlp``),
``post_attention_layernorm_2`` (``ln_mlp_post``), ``norm`` (``ln_final``),
``early_exit_gate`` (``exit_w``, ``exit_b``).

Three entry points share :func:`layer_body`: :func:`init_params` (the
seeded recipe), :func:`forward` with :func:`exit_distribution` (the whole
sequence at once, no cache: the tests' path) and :func:`mixed_layer` (the
continuous scheduler's layer body).  The loop over passes on the serving
path is ``serving/sched/mixed.py``'s, which runs every pass for every
token and does not evaluate the gate: at the published threshold nothing
reads it.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from .configs import OuroConfig
from .llama import (
    _attention,
    apply_rope,
    dense_init,
    layer_matrix_shapes,
    make_causal_mask,
    rms_norm,
    rope_frequencies,
)
from .quant import QUANTIZED_LAYER_MATRICES, mm

Params = dict[str, Any]

#: the Llama family's seven matrices a layer, in the init key-split's order
LAYER_MATRICES = QUANTIZED_LAYER_MATRICES

#: the residual stream's dtype, whatever the parameters' (the mixed step
#: reads it: ``sched/mixed.py``).  A token's stream takes passes x layers x
#: 2 = 384 additions of a normed branch: carried in bfloat16, every one
#: rounds the sum by 2^-8 of its size, and the looped stack spreads the
#: error (served tokens lay up to 1.25 under the float32 reference's best
#: logit, my chip runs, PR 34, call 1, at a post-norm gain of 1).  The
#: matrix products stay in the parameters' dtype; their sums leave the
#: accumulator in this one (``quant.mm``'s ``out_dtype``)
STREAM_DTYPE = jnp.float32

#: the seeded init's mean gain of the norm AFTER a branch (init_params)
POST_NORM_GAIN = 0.5

#: the four norms of a layer, in the order the init draws them
LAYER_NORMS = ("ln_attn", "ln_attn_post", "ln_mlp", "ln_mlp_post")

__all__ = [
    "LAYER_MATRICES", "LAYER_NORMS", "POST_NORM_GAIN", "STREAM_DTYPE",
    "exit_distribution", "forward", "init_params", "layer_body",
    "layer_matrix_shapes", "mixed_layer", "pass_states",
]


def init_params(
    config: OuroConfig,
    key: jax.Array,
    dtype: jnp.dtype = jnp.bfloat16,
    *,
    layer_matrix_init: Optional[Callable] = None,
) -> Params:
    """Seeded random init, layers stacked on axis 0.  A recipe two sides
    can follow: ``key`` split in four (embedding, matrices, head, vectors);
    matrices, embedding and head normal x fan-in^-0.5 (``llama.dense_init``),
    each leaf drawn, scaled and cast in one compiled program.  The vectors'
    key is split in seven, operation by operation: the norm before each
    branch and the final norm ``1 + 0.1 x normal``, the norm after each
    branch ``0.5 + 0.05 x normal`` (no norm is ones, or a swapped or a
    dropped one would hide; and a branch joins at half the stream's size:
    at gain 1 every branch is as large as the stream at the start of each
    pass, and 192 layer applications of random weights spread rounding
    until the comparison with a float32 reference can tell no fault from
    it: ``STREAM_DTYPE``), the exit gate's weight normal x hidden^-0.5 and
    its bias one normal draw."""
    k_embed, k_layers, k_head, k_vectors = jax.random.split(key, 4)
    h, n = config.hidden_size, config.num_layers
    if layer_matrix_init is None:
        def layer_matrix_init(k, shape):
            return dense_init(k, shape, h, dtype)

    draw = jax.jit(
        lambda k, shape: dense_init(k, shape, h, dtype), static_argnames=("shape",)
    )
    params: Params = {
        "embed": jax.block_until_ready(draw(k_embed, shape=(config.vocab_size, h))),
        "lm_head": jax.block_until_ready(draw(k_head, shape=(h, config.vocab_size))),
    }
    shapes = layer_matrix_shapes(config)
    layers: dict[str, Any] = {
        name: layer_matrix_init(k, shape)
        for k, (name, shape) in zip(
            jax.random.split(k_layers, len(shapes)), shapes.items()
        )
    }
    *k_norms, k_final, k_gate_w, k_gate_b = jax.random.split(
        k_vectors, len(LAYER_NORMS) + 3
    )

    def norm_scale(k, shape, gain=1.0):
        drawn = gain + 0.1 * gain * jax.random.normal(k, shape, jnp.float32)
        return drawn.astype(dtype)

    for name, k in zip(LAYER_NORMS, k_norms):
        layers[name] = norm_scale(k, (n, h), POST_NORM_GAIN if name.endswith("_post") else 1.0)
    params["layers"] = layers
    params["ln_final"] = norm_scale(k_final, (h,))
    params["exit_w"] = (
        jax.random.normal(k_gate_w, (h,), jnp.float32) * h ** -0.5
    ).astype(dtype)
    params["exit_b"] = jax.random.normal(k_gate_b, (), jnp.float32).astype(dtype)
    return params


def layer_body(config: OuroConfig, weights: Params, x: jax.Array, attend: Callable):
    """One sandwich-normed layer on ``x [..., T, H]``.  ``attend(q, k, v)``
    is given the three projections and returns ``(attn [..., T, QH * D],
    aux)``: RoPE, the cache and the attention itself are the caller's."""
    eps = config.rms_norm_eps
    # the stream is float32 (STREAM_DTYPE); the matrix products run in the
    # parameters' dtype, and a branch is normed in float32 before it joins
    dtype = weights["ln_attn"].dtype
    a = rms_norm(x, weights["ln_attn"], eps).astype(dtype)
    attn, aux = attend(
        mm(a, weights["wq"]), mm(a, weights["wk"]), mm(a, weights["wv"])
    )
    out = mm(attn, weights["wo"], x.dtype)
    x = x + rms_norm(out, weights["ln_attn_post"], eps)
    with jax.named_scope("mlp"):
        m = rms_norm(x, weights["ln_mlp"], eps).astype(dtype)
        gated = jax.nn.silu(mm(m, weights["w_gate"], x.dtype)) * mm(m, weights["w_up"], x.dtype)
        out = mm(gated.astype(dtype), weights["w_down"], x.dtype)
        x = x + rms_norm(out, weights["ln_mlp_post"], eps)
    return x, aux


# --------------------------------------------------------------------------
# the whole sequence at once (no cache)
# --------------------------------------------------------------------------


def pass_states(
    params: Params, config: OuroConfig, token_ids: jax.Array, positions: jax.Array,
) -> jax.Array:
    """The normed residual stream after each pass, ``[passes, B, T, H]``:
    plain causal attention over the pass's own keys, the program's own
    numerics (the stream in ``STREAM_DTYPE``, the products' operands in
    the parameters' dtype, int8 matrices through ``mm``) without cache,
    kernel or scheduler."""
    inv_freq = rope_frequencies(config)
    x = jnp.take(params["embed"], token_ids, axis=0).astype(STREAM_DTYPE)
    b, t, _ = x.shape
    mask = make_causal_mask(positions, positions, jnp.ones((b, t), bool))

    def attend(q, k, v):
        q = q.reshape(b, t, config.num_heads, config.head_dim)
        k = k.reshape(b, t, config.num_kv_heads, config.head_dim)
        v = v.reshape(b, t, config.num_kv_heads, config.head_dim)
        return _attention(
            apply_rope(q, positions, inv_freq), apply_rope(k, positions, inv_freq),
            v, mask, config,
        ), None

    def one_pass(x, _):
        x, _ = jax.lax.scan(
            lambda x, weights: layer_body(config, weights, x, attend),
            x, params["layers"],
        )
        x = rms_norm(x, params["ln_final"], config.rms_norm_eps)
        return x, x

    _, states = jax.lax.scan(one_pass, x, None, length=config.total_ut_steps)
    return states


def forward(
    params: Params, config: OuroConfig, token_ids: jax.Array, positions: jax.Array,
) -> tuple[jax.Array, None]:
    """Logits ``[B, T, vocab]`` (float32) of the LAST pass, and None, as
    ``llama.forward`` returns without a cache: at the published exit
    threshold every token takes every pass."""
    x = pass_states(params, config, token_ids, positions)[-1]
    x = x.astype(params["lm_head"].dtype)
    logits = jnp.einsum(
        "bth,hv->btv", x, params["lm_head"], preferred_element_type=jnp.float32
    )
    return logits, None


def exit_distribution(params: Params, states: jax.Array) -> jax.Array:
    """``p [passes, B, T]`` from :func:`pass_states`' output: the
    probability, by the gate, that a token's computation ends after each
    pass; the last pass takes what is left, so it sums to one."""
    gate = jax.nn.sigmoid(
        jnp.einsum("pbth,h->pbt", states.astype(jnp.float32),
                   params["exit_w"].astype(jnp.float32))
        + params["exit_b"].astype(jnp.float32)
    )
    stay = jnp.cumprod(1.0 - gate, axis=0)  # prod_{s<=t} (1 - lam_s)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([(gate * before)[:-1], before[-1:]], axis=0)


# --------------------------------------------------------------------------
# the continuous scheduler's layer body (serving/sched/mixed.py)
# --------------------------------------------------------------------------


def mixed_layer(config: OuroConfig, step: Any) -> Callable:
    """The layer body of the mixed step for this family.  The carry is
    ``(x, pools, None)``; ``scanned["layer"]`` is the cache PLANE this
    pass and layer writes and reads (``pass * num_layers + layer``: the
    step's pass loop hands each pass its own planes), and
    ``step.attend`` does both at it."""

    def layer_step(carry, scanned):
        x, pools, recurrent = carry
        x, pools = layer_body(
            config, scanned["w"], x,
            lambda q, k, v: step.attend(q, k, v, pools, scanned["layer"]),
        )
        return (x, pools, recurrent), None

    return layer_step
