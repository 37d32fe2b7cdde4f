"""Olmo Hybrid: gated delta-rule layers and full-attention layers in one
stack, in the period ``layer_types`` repeats (allenai/Olmo-Hybrid-7B:
three linear layers, then one full layer, eight times; ``config.json``
keys in ``configs.OlmoHybridConfig``).

The Olmo 2 order: no norm before a branch; each branch's output is
RMSNormed and then added.  With ``x`` the residual stream and ``N`` an
RMSNorm (``rms_norm_eps``), for every layer::

    x = x + N(Mixer(x); ln_attn_post)
    x = x + N(down(silu(gate(x)) * up(x)); ln_mlp_post)
    logits = N(x; ln_final) W_head

**A linear layer** (Gated DeltaNet: Yang, Kautz and Hatamizadeh,
arXiv:2412.06464, as the ``fla`` library's ``GatedDeltaNet``), per head
``h`` of ``linear_num_value_heads``, with a state ``S`` of
``[linear_key_head_dim, linear_value_head_dim]`` in float32, zero before a
sequence's first token::

    q, k, v = silu(causal depthwise conv_K(x Wq | x Wk | x Wv))   (no bias)
    q, k    = L2-normed per head (eps 1e-6), q scaled by key_head_dim^-0.5
    beta    = 2 sigmoid(x Wb)                 (negative eigenvalues allowed)
    g       = -exp(A_log) softplus(x Wa + dt_bias)          (one a head)
    S_t     = exp(g_t) S_{t-1};  S_t += k_t (x) beta_t (v_t - S_t^T k_t)
    o_t     = S_t^T q_t
    Mixer   = (N_dv(o) * silu(x Wg)) Wo    (N_dv: an RMSNorm over a head's
                                            value_head_dim, one weight)

**A full layer**: multi-head attention, ``num_attention_heads`` of
``head_dim`` (hidden / heads), q and k each RMSNormed over the WHOLE
projection before the heads are split (the Olmo 2 QK norm), no positional
rotation (``rope_theta`` null: the recurrence carries the order), causal
softmax at head_dim^-0.5, ``Wo``.

**The leaves** are stacked by period: a linear layer's ``lin_<name>``
``[periods, linear a period, ...]`` and a full layer's ``<name>``
``[periods, full a period, ...]``.  The mixed step's loop makes one step a
period and scans no leaf: a period's body reads layer ``j`` of a kind at
``(period, j)`` of the whole stack, one dynamic index where its product
reads the matrix, so no layer's weights and no period's are copied out
(a scanned period's slice, cut up by three layers, was: AOT compile).
Layer ``j`` of the linear layers of period ``p`` holds state ``p x
linear_per_period + j``; full layer ``i`` writes and reads KV plane ``p x
full_per_period + i``.

Three entry points share :func:`period_body`: :func:`init_params` (the
seeded recipe), :func:`forward` (the whole sequence at once, no cache: the
tests' path) and :func:`mixed_layer` (the continuous scheduler's body, one
period, the recurrence by ``ops/gated_delta.py``).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from . import recurrent
from .configs import LINEAR_ATTENTION, OlmoHybridConfig
from .llama import _attention, dense_init, make_causal_mask, rms_norm
from .quant import mm

Params = dict[str, Any]

#: a linear layer's leaves carry this prefix; a full layer's carry none
LINEAR = "lin_"
#: the RMSNorm weights of both kinds, in the order the init draws them
LAYER_NORMS = (
    "lin_o_norm", "lin_ln_attn_post", "lin_ln_mlp_post",
    "q_norm", "k_norm", "ln_attn_post", "ln_mlp_post",
)
#: the L2 norm's epsilon on q and k (the ``fla`` library's ``l2norm``)
L2_EPS = 1e-6

__all__ = [
    "LAYER_MATRICES", "LAYER_NORMS", "WHOLE_STACKS", "forward", "init_params",
    "layer_matrix_shapes", "matmul_params_per_token", "mixed_layer", "period_body",
    "scan_steps",
]


def layer_matrix_shapes(config: OlmoHybridConfig) -> dict[str, tuple[int, ...]]:
    """Stacked ``[periods, layers of the kind a period, in, out]`` shapes of
    the int8 matrices, in the order the init's key split follows."""
    h, f = config.hidden_size, config.intermediate_size
    heads, kd, vd = (
        config.linear_num_value_heads, config.linear_key_dim, config.linear_value_dim
    )
    qd = config.num_heads * config.head_dim
    kvd = config.num_kv_heads * config.head_dim
    lin = (config.periods, config.linear_per_period)
    full = (config.periods, config.full_per_period)
    return {
        "lin_wq": (*lin, h, kd), "lin_wk": (*lin, h, kd), "lin_wv": (*lin, h, vd),
        "lin_wa": (*lin, h, heads), "lin_wb": (*lin, h, heads), "lin_wg": (*lin, h, vd),
        "lin_wo": (*lin, vd, h), "lin_w_gate": (*lin, h, f), "lin_w_up": (*lin, h, f),
        "lin_w_down": (*lin, f, h),
        "wq": (*full, h, qd), "wk": (*full, h, kvd), "wv": (*full, h, kvd),
        "wo": (*full, qd, h), "w_gate": (*full, h, f), "w_up": (*full, h, f),
        "w_down": (*full, f, h),
    }


#: every matrix of both kinds: all are held int8 a column
LAYER_MATRICES = (
    "lin_wq", "lin_wk", "lin_wv", "lin_wa", "lin_wb", "lin_wg", "lin_wo",
    "lin_w_gate", "lin_w_up", "lin_w_down",
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
)


#: every layer leaf: the mixed step's loop scans none of them, and a
#: period's body reads each layer at ``(period, position)`` from the whole
#: stack (``sched/mixed.py``)
WHOLE_STACKS = LAYER_MATRICES + LAYER_NORMS + ("lin_a_log", "lin_dt_bias", "lin_conv_w")


def matmul_params_per_token(config: OlmoHybridConfig) -> int:
    """Layer weights that multiply one token (``serving/perf.py``): every
    matrix of every layer, whatever its kind."""
    total = 0
    for shape in layer_matrix_shapes(config).values():
        count = 1
        for size in shape:
            count *= size
        total += count
    return total


def scan_steps(config: OlmoHybridConfig) -> int:
    """Steps of the mixed step's layer loop: one a period
    (``serving/sched/mixed.py``)."""
    return config.periods


def init_params(
    config: OlmoHybridConfig,
    key: jax.Array,
    dtype: jnp.dtype = jnp.bfloat16,
    *,
    layer_matrix_init: Optional[Callable] = None,
) -> Params:
    """Seeded random init.  A recipe two sides can follow: ``key`` split in
    four (embedding, matrices, head, vectors); the matrices' key split in
    ``len(LAYER_MATRICES)`` in that order, each matrix normal x fan-in^-0.5
    (``llama.dense_init``), a stacked leaf drawn, scaled and cast in one
    compiled program, the embedding and the head too, each waited for.  The
    vectors' key is split in ``len(LAYER_NORMS) + 4``: the norms, then the
    final norm, each ``1 + 0.1 x normal`` (a norm of ones would hide a
    dropped one); ``A_log = log(uniform[1, 16])`` and ``dt_bias =
    softplus^-1(loguniform[1e-3, 1e-1])`` ``[periods, linear, heads]``
    float32 (Falcon-H1's recipe); the convolution weights ``normal x
    K^-0.5`` ``[periods, linear, K, q|k|v]``.  Everything but ``A_log``
    and ``dt_bias`` in ``dtype``."""
    k_embed, k_layers, k_head, k_vectors = jax.random.split(key, 4)
    h = config.hidden_size
    draw = jax.jit(
        lambda k, shape: dense_init(k, shape, h, dtype), static_argnames=("shape",)
    )
    if layer_matrix_init is None:
        def layer_matrix_init(k, shape):
            return jax.block_until_ready(draw(k, shape=shape))

    params: Params = {
        "embed": jax.block_until_ready(draw(k_embed, shape=(config.vocab_size, h))),
        "lm_head": jax.block_until_ready(draw(k_head, shape=(h, config.vocab_size))),
    }
    shapes = layer_matrix_shapes(config)
    layers: dict[str, Any] = {
        name: layer_matrix_init(k, shape)
        for k, (name, shape) in zip(jax.random.split(k_layers, len(shapes)), shapes.items())
    }
    *k_norms, k_final, k_a, k_dt, k_conv = jax.random.split(k_vectors, len(LAYER_NORMS) + 4)
    lin = (config.periods, config.linear_per_period)
    full = (config.periods, config.full_per_period)
    widths = {
        "lin_o_norm": (*lin, config.linear_value_head_dim),
        "lin_ln_attn_post": (*lin, h), "lin_ln_mlp_post": (*lin, h),
        "q_norm": (*full, config.num_heads * config.head_dim),
        "k_norm": (*full, config.num_kv_heads * config.head_dim),
        "ln_attn_post": (*full, h), "ln_mlp_post": (*full, h),
    }

    def norm_scale(k, shape):
        return (1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    for name, k in zip(LAYER_NORMS, k_norms):
        layers[name] = norm_scale(k, widths[name])
    heads, width = config.linear_num_value_heads, config.linear_conv_kernel_dim
    layers["lin_a_log"] = jnp.log(
        jax.random.uniform(k_a, (*lin, heads), jnp.float32, 1.0, 16.0)
    )
    layers["lin_dt_bias"] = recurrent.softplus_inverse(jnp.exp(
        jax.random.uniform(k_dt, (*lin, heads), jnp.float32, jnp.log(1e-3), jnp.log(1e-1))
    ))
    layers["lin_conv_w"] = (
        jax.random.normal(k_conv, (*lin, width, config.linear_conv_dim), jnp.float32)
        * width ** -0.5
    ).astype(dtype)
    params["layers"] = layers
    params["ln_final"] = norm_scale(k_final, (h,))
    return params


# --------------------------------------------------------------------------
# the pieces every path shares (``[..., T, ...]``)
# --------------------------------------------------------------------------


def add_normed(config: OlmoHybridConfig, x: jax.Array, branch: jax.Array,
               scale: jax.Array) -> jax.Array:
    """``x + N(branch; scale)``: the norm and the sum in float32, rounded
    to the stream's dtype once."""
    b32 = branch.astype(jnp.float32)
    variance = jnp.mean(jnp.square(b32), axis=-1, keepdims=True)
    normed = b32 * jax.lax.rsqrt(variance + config.rms_norm_eps) * scale.astype(jnp.float32)
    return (x.astype(jnp.float32) + normed).astype(x.dtype)


def mlp(weights: Params, x: jax.Array) -> jax.Array:
    gate = jax.nn.silu(mm(x, weights["w_gate"]))
    return mm(gate * mm(x, weights["w_up"]), weights["w_down"])


def delta_projections(config: OlmoHybridConfig, weights: Params, x: jax.Array):
    """``(q|k|v before the convolution, g, beta)``: the first ``[..., T,
    conv channels]`` in the activations' dtype, ``g`` (the log decay) and
    ``beta`` ``[..., T, heads]`` float32."""
    qkv = jnp.concatenate(
        [mm(x, weights["wq"]), mm(x, weights["wk"]), mm(x, weights["wv"])], axis=-1
    )
    beta = 2.0 * jax.nn.sigmoid(mm(x, weights["wb"]).astype(jnp.float32))
    g = -jnp.exp(weights["a_log"]) * jax.nn.softplus(
        mm(x, weights["wa"]).astype(jnp.float32) + weights["dt_bias"]
    )
    return qkv, g, beta


def delta_heads(config: OlmoHybridConfig, mixed: jax.Array):
    """The convolution's activated output split into heads: ``q`` and
    ``k`` ``[..., T, heads, key_head_dim]`` L2-normed (q scaled) in
    float32, ``v`` ``[..., T, heads, value_head_dim]``."""
    lead = mixed.shape[:-1]
    heads, dk, kd = (
        config.linear_num_value_heads, config.linear_key_head_dim, config.linear_key_dim
    )

    def l2(a):
        a = a.astype(jnp.float32)
        return a * jax.lax.rsqrt(jnp.sum(jnp.square(a), axis=-1, keepdims=True) + L2_EPS)

    q = l2(mixed[..., :kd].reshape(*lead, heads, dk)) * dk ** -0.5
    k = l2(mixed[..., kd:2 * kd].reshape(*lead, heads, dk))
    v = mixed[..., 2 * kd:].reshape(*lead, heads, config.linear_value_head_dim)
    return q, k, v


def delta_out(config: OlmoHybridConfig, weights: Params, o: jax.Array,
              x: jax.Array) -> jax.Array:
    """The read-out ``o [..., T, heads, value_head_dim]`` (float32) normed
    per head, gated by ``silu(x Wg)`` and projected out."""
    variance = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
    o = o * jax.lax.rsqrt(variance + config.rms_norm_eps)
    o = o * weights["o_norm"].astype(jnp.float32)
    gate = mm(x, weights["wg"]).astype(jnp.float32).reshape(o.shape)
    o = (o * jax.nn.silu(gate)).reshape(*x.shape[:-1], config.linear_value_dim)
    return mm(o.astype(x.dtype), weights["wo"])


def layer_of(weights: Params, linear: bool, index: Any) -> Params:
    """One layer's leaves, its kind's prefix dropped: ``index`` into each
    leaf of its kind (a position into a period's slice, or ``(period,
    position)`` into a whole stack)."""
    return {
        name[len(LINEAR):] if linear else name: jax.tree_util.tree_map(
            lambda a: a[index], leaf
        )
        for name, leaf in weights.items()
        if name.startswith(LINEAR) == linear
    }


def period_body(config: OlmoHybridConfig, layer_weights: Callable, x: jax.Array,
                mix: Callable, attend: Callable) -> jax.Array:
    """One period of layers on ``x [..., T, hidden]``.  ``layer_weights(
    linear, j)`` gives the ``j``-th layer of that kind in the period;
    ``mix(j, weights, qkv, g, beta)`` is the ``j``-th linear layer's
    convolution and recurrence, returning its read-out ``[..., T, heads,
    value_head_dim]`` float32; ``attend(i, q, k, v)`` the ``i``-th full
    layer's attention, returning ``[..., T, heads x head_dim]``.  Caches
    are the callers'."""
    linear = full = 0
    for kind in config.period:
        if kind == LINEAR_ATTENTION:
            w = layer_weights(True, linear)
            qkv, g, beta = delta_projections(config, w, x)
            o = mix(linear, w, qkv, g, beta)
            x = add_normed(config, x, delta_out(config, w, o, x), w["ln_attn_post"])
            linear += 1
        else:
            w = layer_weights(False, full)
            eps = config.rms_norm_eps
            q = rms_norm(mm(x, w["wq"]), w["q_norm"], eps)
            k = rms_norm(mm(x, w["wk"]), w["k_norm"], eps)
            attn = attend(full, q, k, mm(x, w["wv"]))
            x = add_normed(config, x, mm(attn, w["wo"]), w["ln_attn_post"])
            full += 1
        with jax.named_scope("mlp"):
            x = add_normed(config, x, mlp(w, x), w["ln_mlp_post"])
    return x


def head_logits(params: Params, x: jax.Array, eps: float) -> jax.Array:
    x = rms_norm(x, params["ln_final"], eps)
    return jnp.einsum("...h,hv->...v", x, params["lm_head"], preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# the whole sequence at once (no cache)
# --------------------------------------------------------------------------


def forward(
    params: Params, config: OlmoHybridConfig, token_ids: jax.Array,
    positions: jax.Array,
) -> tuple[jax.Array, None]:
    """One pass over ``[B, T]`` tokens with plain causal attention and the
    recurrence token by token from a zero state: the program's own numerics
    (bfloat16 activations, int8 matrices through ``mm``) without cache,
    kernel or scheduler.  Returns ``(logits [B, T, vocab], None)``."""
    from ..ops.gated_delta import gated_delta_reference

    x = jnp.take(params["embed"], token_ids, axis=0)
    b, t, _ = x.shape
    mask = make_causal_mask(positions, positions, jnp.ones((b, t), bool))
    width = config.linear_conv_kernel_dim
    heads = config.linear_num_value_heads

    def mix(_, w, qkv, g, beta):
        padded = jnp.pad(qkv, ((0, 0), (width - 1, 0), (0, 0)))
        mixed = recurrent.conv_taps(w["conv_w"], None, [padded[:, k:k + t] for k in range(width)])
        q, k, v = delta_heads(config, mixed)
        # every sequence is one row of t tokens from a zero state: the
        # token-by-token recurrence of ops/gated_delta.py, never the kernel
        flat = lambda a: a.reshape(b * t, *a.shape[2:])  # noqa: E731
        o, _ = gated_delta_reference(
            flat(q), flat(k), flat(v), flat(g), flat(beta),
            jnp.zeros((1, b, heads // 2, config.linear_key_head_dim,
                       2 * config.linear_value_head_dim), jnp.float32),
            0, jnp.arange(b, dtype=jnp.int32) * t, jnp.full((b,), t, jnp.int32),
            jnp.ones((b,), bool), chunk=t,
        )
        return o.reshape(b, t, heads, -1)

    def attend(_, q, k, v):
        split = lambda a, n: a.reshape(b, t, n, config.head_dim)  # noqa: E731
        return _attention(
            split(q, config.num_heads), split(k, config.num_kv_heads),
            split(v, config.num_kv_heads), mask, config,
        )

    def one_period(x, w):
        pick = lambda linear, j: layer_of(w, linear, j)  # noqa: E731
        return period_body(config, pick, x, mix, attend), None

    x, _ = jax.lax.scan(one_period, x, params["layers"])
    return head_logits(params, x, config.rms_norm_eps), None


# --------------------------------------------------------------------------
# the continuous scheduler's body (serving/sched/mixed.py)
# --------------------------------------------------------------------------


def mixed_layer(config: OlmoHybridConfig, step: Any) -> Callable:
    """The scan body of the mixed step for this family: ONE PERIOD, the
    loop making :func:`scan_steps` of them.  ``scanned["layer"]`` is the
    period ``p``; the carry is ``(x, pools, recurrent)`` with ``pools`` the
    KV pools (planes of the full layers alone) and ``recurrent = {"ssm":
    [delta layers, S, heads / 2, Dk, 2 Dv] f32, "conv": [delta layers, S,
    K - 1, q|k|v]}``, all WHOLE: the kernel updates a layer's state in
    place and the convolution's tail is one small row update."""
    from ..ops.gated_delta import gated_delta

    fresh = recurrent.slot_fresh(step)  # [S]
    nl, nf = config.linear_per_period, config.full_per_period

    def layer_step(carry, scanned):
        x, pools, state = carry
        period = scanned["layer"]
        held = {"pools": pools, "ssm": state["ssm"], "conv": state["conv"]}

        def mix(j, w, qkv, g, beta):
            index = period * nl + j
            with jax.named_scope("delta_conv"):
                mixed, tails = recurrent.flat_conv(
                    step, fresh, qkv, held["conv"][index], w["conv_w"],
                )
                held["conv"] = jax.lax.dynamic_update_index_in_dim(
                    held["conv"], tails.astype(held["conv"].dtype), index, 0
                )
            with jax.named_scope("delta"):
                q, k, v = delta_heads(config, mixed[0])
                o, held["ssm"] = gated_delta(
                    q, k, v, g[0], beta[0], held["ssm"], index,
                    step.q_start, step.q_count, fresh, chunk=step.chunk,
                )
                # the kernel leaves rows of no live slot unwritten
                return jnp.where(step.valid[:, None, None], o, 0.0)[None]

        def attend(i, q, k, v):
            attn, held["pools"] = step.attend(q, k, v, held["pools"], period * nf + i)
            return attn

        # each layer's leaves straight from the whole stacks (the module's
        # text says why)
        def pick(linear, j):
            return layer_of(step.stacks, linear, (period, j))

        x = period_body(config, pick, x, mix, attend)
        return (x, held["pools"], {"ssm": held["ssm"], "conv": held["conv"]}), None

    return layer_step
