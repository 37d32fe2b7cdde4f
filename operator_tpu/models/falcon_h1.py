"""Falcon-H1: a Mamba-2 mixer in parallel with grouped-query attention in
every layer, then a gated MLP, with muP multipliers on nearly every branch
(tiiuae/Falcon-H1, ``config.json`` keys in ``configs.FalconH1Config``).

With ``x`` the residual stream and ``N*`` RMSNorms::

    x0     = embed[ids] * embedding_multiplier
    u      = N1(x)
    x      = x + ssm_out_multiplier * Mixer(u)
               + attention_out_multiplier * Attn(attention_in_multiplier * u)
    x      = x + MLP(N2(x))
    logits = (N_f(x) @ lm_head) * lm_head_multiplier

    Attn:  q = u Wq, k = (u Wk) * key_multiplier, v = u Wv; RoPE on q, k;
           causal softmax at head_dim^-0.5; Wo.  No bias.
    MLP:   down(up(h) * silu(gate(h) * mlp_multipliers[0])) * mlp_multipliers[1]
    Mixer: p = ((ssm_in_multiplier * u) W_in) * m, m holding
           ssm_multipliers[0..4] over the segments z | x | B | C | dt;
           [x|B|C] = silu(causal depthwise conv([x|B|C]) + b_conv);
           dt = softplus(dt + dt_bias); A = -exp(A_log); per head h of
           group g: H_t = exp(dt_t A_h) H_{t-1} + dt_t x_t B_{g,t}^T,
           y_t = H_t C_{g,t} + D_h x_t; gate then norm
           (mamba_norm_before_gate false): y = GroupRMSNorm(y * silu(z)) * w;
           W_out.

Three entry points share the pieces below: :func:`init_params` (the
seeded recipe, one quantiser for the nine layer matrices),
:func:`forward` (the whole sequence at once, no cache: the tests' and the
parity gate's path) and :func:`mixed_layer` (the continuous scheduler's
layer body: flat tokens, the paged KV pool, the recurrent state pool
carried and updated in place by ``ops/ssm_scan.py``).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from .configs import FalconH1Config
from .quant import mm

Params = dict[str, Any]

#: the layer matrices, in the order the init key-split follows; all nine
#: are held int8 per output column under ``serving_dtype=int8``
LAYER_MATRICES = (
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "w_in", "w_out",
)


def layer_matrix_shapes(config: FalconH1Config) -> dict[str, tuple[int, int, int]]:
    """Stacked ``[layer, in, out]`` shapes of the nine layer matrices."""
    h, f = config.hidden_size, config.intermediate_size
    kvh, qh, d = config.num_kv_heads, config.num_heads, config.head_dim
    n = config.num_layers
    return {
        "wq": (n, h, qh * d),
        "wk": (n, h, kvh * d),
        "wv": (n, h, kvh * d),
        "wo": (n, qh * d, h),
        "w_gate": (n, h, f),
        "w_up": (n, h, f),
        "w_down": (n, f, h),
        "w_in": (n, h, config.mamba_in_dim),
        "w_out": (n, config.mamba_d_ssm, h),
    }


def scale(x: jax.Array, multiplier: Any) -> jax.Array:
    """``x`` times a muP multiplier (a number or a float32 vector), the
    product taken in float32: a multiplier rounded to bfloat16 would be a
    systematic 2^-9 error on its branch."""
    return (x.astype(jnp.float32) * multiplier).astype(x.dtype)


def _softplus_inverse(y: jax.Array) -> jax.Array:
    return y + jnp.log(-jnp.expm1(-y))


def init_params(
    config: FalconH1Config,
    key: jax.Array,
    dtype: jnp.dtype = jnp.bfloat16,
    *,
    layer_matrix_init: Optional[Callable] = None,
) -> Params:
    """Seeded random init, layers stacked on axis 0.  A recipe two sides
    can follow: ``key`` split in four (embedding, matrices, head, mixer
    vectors); matrices, embedding and head normal x fan-in^-0.5
    (``llama.dense_init``), each leaf drawn, scaled and cast in one
    compiled program;
    ``A_log = log(uniform[1, 16])``, ``dt_bias =
    softplus^-1(loguniform[1e-3, 1e-1])``, ``D = 1``, convolution weights
    normal x d_conv^-0.5 and a NON-ZERO convolution bias (normal x 0.02: a
    zero bias would hide a dropped one); norms ones.  ``A_log``, ``D``
    and ``dt_bias`` stay float32, everything else ``dtype``."""
    from .llama import dense_init

    k_embed, k_layers, k_head, k_mixer = jax.random.split(key, 4)
    h, n = config.hidden_size, config.num_layers
    if layer_matrix_init is None:
        def layer_matrix_init(k, shape):
            return dense_init(k, shape, h, dtype)

    # embedding and head FIRST, each in ONE compiled program and waited
    # for: drawn operation by operation, a 261,120 x 5,120 leaf is 5.3 GB
    # in float32 twice over before its cast, and with the host running
    # ahead of the device the head's draw found the embedding's transients
    # still there (16.04 of 16.9 GB, my chip run, PR 29)
    draw = jax.jit(
        lambda k, shape: dense_init(k, shape, h, dtype), static_argnames=("shape",)
    )
    params: Params = {
        "embed": jax.block_until_ready(draw(k_embed, shape=(config.vocab_size, h)))
    }
    if not config.tie_embeddings:
        params["lm_head"] = jax.block_until_ready(
            draw(k_head, shape=(h, config.vocab_size))
        )
    shapes = layer_matrix_shapes(config)
    keys = jax.random.split(k_layers, len(shapes))
    layers: dict[str, Any] = {
        name: layer_matrix_init(k, shape)
        for k, (name, shape) in zip(keys, shapes.items())
    }
    k_a, k_dt, k_conv, k_bias = jax.random.split(k_mixer, 4)
    heads, width, conv = config.mamba_n_heads, config.mamba_d_conv, config.mamba_conv_dim
    layers["a_log"] = jnp.log(
        jax.random.uniform(k_a, (n, heads), jnp.float32, 1.0, 16.0)
    )
    layers["dt_bias"] = _softplus_inverse(jnp.exp(
        jax.random.uniform(k_dt, (n, heads), jnp.float32, jnp.log(1e-3), jnp.log(1e-1))
    ))
    layers["d_skip"] = jnp.ones((n, heads), jnp.float32)
    layers["conv_w"] = (
        jax.random.normal(k_conv, (n, width, conv), jnp.float32) * width ** -0.5
    ).astype(dtype)
    layers["conv_b"] = (
        jax.random.normal(k_bias, (n, conv), jnp.float32) * 0.02
    ).astype(dtype)
    layers["ln_attn"] = jnp.ones((n, h), dtype)
    layers["ln_mlp"] = jnp.ones((n, h), dtype)
    layers["ln_ssm"] = jnp.ones((n, config.mamba_d_ssm), dtype)
    params["layers"] = layers
    params["ln_final"] = jnp.ones((h,), dtype)
    return params


# --------------------------------------------------------------------------
# the pieces both passes share (flat ``[1, T, ...]`` or ``[B, T, ...]``)
# --------------------------------------------------------------------------


def segment_multipliers(config: FalconH1Config) -> jax.Array:
    """``m``: ``ssm_multipliers[0..4]`` over z | x | B | C | dt, float32."""
    d, gn = config.mamba_d_ssm, config.mamba_n_groups * config.mamba_d_state
    widths = (d, d, gn, gn, config.mamba_n_heads)
    return jnp.concatenate([
        jnp.full((w,), m, jnp.float32) for w, m in zip(widths, config.ssm_multipliers)
    ])


def mixer_in(config: FalconH1Config, weights: Params, u: jax.Array):
    """The in-projection and its split: ``z``, the convolution's input
    ``x|B|C`` and the raw ``dt``, all ``[..., T, width]``."""
    p = mm(scale(u, config.ssm_in_multiplier), weights["w_in"])
    p = scale(p, segment_multipliers(config))
    d = config.mamba_d_ssm
    return p[..., :d], p[..., d : d + config.mamba_conv_dim], p[..., d + config.mamba_conv_dim :]


def conv_taps(config: FalconH1Config, weights: Params, taps: list) -> jax.Array:
    """``silu(sum_k w[k] * taps[k] + b)`` in float32, cast back: ``taps[k]``
    is the convolution's input ``d_conv - 1 - k`` tokens back (zeros
    before the sequence's start), oldest first."""
    w = weights["conv_w"].astype(jnp.float32)
    acc = weights["conv_b"].astype(jnp.float32)
    for k, tap in enumerate(taps):
        acc = acc + w[k] * tap.astype(jnp.float32)
    return jax.nn.silu(acc).astype(taps[-1].dtype)


def scan_inputs(config: FalconH1Config, weights: Params, xbc: jax.Array, dt_raw: jax.Array):
    """What the recurrence reads: ``x [..., H, P]``, ``B`` and ``C``
    ``[..., G, N]``, ``dt [..., H]`` (float32, after softplus) and
    ``A [H]`` (float32, negative)."""
    d, g, n = config.mamba_d_ssm, config.mamba_n_groups, config.mamba_d_state
    lead = xbc.shape[:-1]
    x = xbc[..., :d].reshape(*lead, config.mamba_n_heads, config.mamba_d_head)
    b = xbc[..., d : d + g * n].reshape(*lead, g, n)
    c = xbc[..., d + g * n :].reshape(*lead, g, n)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + weights["dt_bias"])
    return x, b, c, dt, -jnp.exp(weights["a_log"])


def mixer_out(config: FalconH1Config, weights: Params, y: jax.Array,
              x: jax.Array, z: jax.Array) -> jax.Array:
    """The ``D`` skip, the gate, the grouped norm and the out-projection:
    ``y`` is the recurrence's read-out ``[..., H, P]`` in float32."""
    lead = z.shape[:-1]
    y = y + weights["d_skip"][:, None] * x.astype(jnp.float32)
    y = y.reshape(*lead, config.mamba_d_ssm) * jax.nn.silu(z.astype(jnp.float32))
    grouped = y.reshape(*lead, config.mamba_n_groups, -1)
    variance = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
    normed = (grouped * jax.lax.rsqrt(variance + config.rms_norm_eps)).reshape(y.shape)
    normed = (normed * weights["ln_ssm"].astype(jnp.float32)).astype(z.dtype)
    return mm(normed, weights["w_out"])


def residual(config: FalconH1Config, x: jax.Array, mixed: jax.Array, attn: jax.Array) -> jax.Array:
    """``x + ssm_out_multiplier * Mixer + attention_out_multiplier * Attn``,
    summed in float32."""
    return (
        x.astype(jnp.float32)
        + mixed.astype(jnp.float32) * config.ssm_out_multiplier
        + attn.astype(jnp.float32) * config.attention_out_multiplier
    ).astype(x.dtype)


def mlp(config: FalconH1Config, weights: Params, h_in: jax.Array) -> jax.Array:
    gate_m, down_m = config.mlp_multipliers
    gate = jax.nn.silu(scale(mm(h_in, weights["w_gate"]), gate_m))
    return scale(mm(mm(h_in, weights["w_up"]) * gate, weights["w_down"]), down_m)


def head_logits(config: FalconH1Config, params: Params, x: jax.Array) -> jax.Array:
    """``[..., H]`` after the final norm -> float32 logits."""
    head = params["embed"].T if config.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("...h,hv->...v", x, head, preferred_element_type=jnp.float32)
    return logits * config.lm_head_multiplier


# --------------------------------------------------------------------------
# the whole sequence at once (no cache)
# --------------------------------------------------------------------------


def forward(
    params: Params, config: FalconH1Config, token_ids: jax.Array,
    positions: jax.Array,
) -> tuple[jax.Array, None]:
    """One pass over ``[B, T]`` tokens with plain causal attention and the
    recurrence token by token from a zero state: the program's own
    numerics (bfloat16 activations, int8 matrices through ``mm``) without
    cache, kernel or scheduler.  Returns ``(logits [B, T, vocab], None)``
    as ``llama.forward`` does without a cache."""
    from ..ops.ssm_scan import ssm_scan_reference
    from .llama import _attention, apply_rope, make_causal_mask, rms_norm, rope_frequencies

    inv_freq = rope_frequencies(config)
    x = scale(jnp.take(params["embed"], token_ids, axis=0), config.embedding_multiplier)
    b, t, _ = x.shape
    mask = make_causal_mask(positions, positions, jnp.ones((b, t), bool))
    width = config.mamba_d_conv

    def layer_step(x, weights):
        u = rms_norm(x, weights["ln_attn"], config.rms_norm_eps)
        # -- mixer
        z, xbc, dt_raw = mixer_in(config, weights, u)
        padded = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
        xbc = conv_taps(config, weights, [padded[:, k : k + t] for k in range(width)])
        xs, bs, cs, dt, a = scan_inputs(config, weights, xbc, dt_raw)
        # every sequence is one row of t tokens from a zero state: the
        # token-by-token recurrence of ops/ssm_scan.py, never the kernel
        flat = lambda v: v.reshape(b * t, *v.shape[2:])  # noqa: E731
        y, _ = ssm_scan_reference(
            flat(xs), flat(dt), a, flat(bs), flat(cs),
            jnp.zeros((1, b, config.mamba_n_heads, config.mamba_d_state,
                       config.mamba_d_head), jnp.float32),
            0, jnp.arange(b, dtype=jnp.int32) * t, jnp.full((b,), t, jnp.int32),
            jnp.ones((b,), bool), chunk=t,
        )
        y = y.reshape(b, t, *y.shape[1:])
        mixed = mixer_out(config, weights, y, xs, z)
        # -- attention
        a_in = scale(u, config.attention_in_multiplier)
        q = mm(a_in, weights["wq"]).reshape(b, t, config.num_heads, config.head_dim)
        k = scale(mm(a_in, weights["wk"]), config.key_multiplier)
        k = k.reshape(b, t, config.num_kv_heads, config.head_dim)
        v = mm(a_in, weights["wv"]).reshape(b, t, config.num_kv_heads, config.head_dim)
        attn = _attention(
            apply_rope(q, positions, inv_freq), apply_rope(k, positions, inv_freq),
            v, mask, config,
        )
        x = residual(config, x, mixed, mm(attn, weights["wo"]))
        x = x + mlp(config, weights, rms_norm(x, weights["ln_mlp"], config.rms_norm_eps))
        return x, None

    x, _ = jax.lax.scan(layer_step, x, params["layers"])
    x = rms_norm(x, params["ln_final"], config.rms_norm_eps)
    return head_logits(config, params, x), None


# --------------------------------------------------------------------------
# the continuous scheduler's layer body (serving/sched/mixed.py)
# --------------------------------------------------------------------------


def mixed_layer(config: FalconH1Config, step: Any) -> Callable:
    """The layer body of the mixed step for this family.  ``step`` is
    ``sched/mixed.py``'s view of one dispatch: the flat tokens' packing
    (``rows``, ``in_row``, ``pos``, ``valid``, ``q_start``, ``q_count``),
    ``chunk`` and ``attend``, the shared attention (KV write, ragged
    kernel, gather back).  The carry is ``(x, pools, recurrent)`` with
    ``pools`` the KV pools (``step.attend`` writes and reads them at the
    layer) and ``recurrent = {"ssm": [L, S, H, N, P] f32, "conv": [L, S,
    d_conv - 1, conv_dim]}``, all WHOLE: the scan kernel updates the
    layer's state in place and the conv tail is one small row update, so
    nothing slices a layer out of a pool or writes one back."""
    from ..ops.ssm_scan import ssm_scan
    from .llama import rms_norm

    width = config.mamba_d_conv
    tail = width - 1
    t_budget = step.t_budget
    # a row whose first token sits at position 0 starts from nothing: a
    # slot's next tenant never reads what the last one left
    slot_fresh = (step.pos[jnp.clip(step.q_start, 0, t_budget - 1)] == 0) & (
        step.q_count > 0
    )  # [S]

    def conv(weights, xbc, tails):
        """Causal depthwise convolution on the flat axis: a token's tap
        ``s`` tokens back is the flat token before it while that is still
        in its row, else the slot's carried tail.  Returns the activated
        ``x|B|C`` and the slots' new tails."""
        flat = xbc[0]  # [T, conv]
        tails = jnp.where(slot_fresh[:, None, None], 0, tails).astype(flat.dtype)
        taps = []
        for back in range(tail, 0, -1):  # oldest first
            inside = step.in_row >= back
            from_flat = flat[jnp.clip(jnp.arange(t_budget) - back, 0, t_budget - 1)]
            from_tail = tails[
                step.rows, jnp.clip(tail + step.in_row - back, 0, tail - 1)
            ]
            taps.append(jnp.where(inside[:, None], from_flat, from_tail))
        taps.append(flat)
        out = conv_taps(config, weights, taps)
        # the last `tail` inputs of [old tail | this step's tokens]: an
        # idle slot's tail comes back as it was
        at = step.q_count[:, None] + jnp.arange(tail, dtype=jnp.int32)[None]  # [S, tail]
        from_flat = flat[jnp.clip(step.q_start[:, None] + at - tail, 0, t_budget - 1)]
        from_tail = jnp.take_along_axis(
            tails, jnp.clip(at, 0, tail - 1)[..., None], axis=1
        )
        return out[None], jnp.where((at >= tail)[..., None], from_flat, from_tail)

    def layer_step(carry, scanned):
        x, pools, recurrent = carry
        weights, layer = scanned["w"], scanned["layer"]
        u = rms_norm(x, weights["ln_attn"], config.rms_norm_eps)
        with jax.named_scope("ssm_conv"):
            z, xbc, dt_raw = mixer_in(config, weights, u)
            xbc, new_tails = conv(weights, xbc, recurrent["conv"][layer])
            conv_pool = jax.lax.dynamic_update_index_in_dim(
                recurrent["conv"], new_tails.astype(recurrent["conv"].dtype), layer, 0
            )
        with jax.named_scope("ssm"):
            xs, bs, cs, dt, a = scan_inputs(config, weights, xbc, dt_raw)
            y, ssm_pool = ssm_scan(
                xs[0], dt[0], a, bs[0], cs[0], recurrent["ssm"], layer,
                step.q_start, step.q_count, slot_fresh, chunk=step.chunk,
            )
            # the kernel leaves rows of no live slot unwritten
            y = jnp.where(step.valid[:, None, None], y, 0.0)[None]
            mixed = mixer_out(config, weights, y, xs, z)
        a_in = scale(u, config.attention_in_multiplier)
        q = mm(a_in, weights["wq"])
        k = scale(mm(a_in, weights["wk"]), config.key_multiplier)
        v = mm(a_in, weights["wv"])
        attn, pools = step.attend(q, k, v, pools, layer)
        x = residual(config, x, mixed, mm(attn, weights["wo"]))
        with jax.named_scope("mlp"):
            x = x + mlp(config, weights, rms_norm(x, weights["ln_mlp"], config.rms_norm_eps))
        return (x, pools, {"ssm": ssm_pool, "conv": conv_pool}), None

    return layer_step
