"""The plain reference for Falcon-H1: in every layer a Mamba-2 mixer in
parallel with grouped-query attention, then a gated MLP, with the muP
multipliers of the published ``config.json`` -- in float32.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision(
"highest")``: full causal attention with no cache, and the mixer as **the
token-by-token recurrence** (``lax.scan`` over positions, all sampled
sequences together, fixed shapes), which owes nothing to the program's
kernel, its chunking or its per-slot state pool.  One layer at a time, so
that one layer's float32 weights exist at once, on weights of its own
(``falcon_h1_f32_weights.make``); nothing of the program is imported.  The
head, blocked over the vocabulary, is ``decoder_f32``'s (a file of the
benchmark beside this one).  The only departure from the published
description is that every sequence is right-padded to one power of two,
which a causal model cannot see from the positions that are scored.

With ``x`` the residual stream, ``N*`` RMSNorms (``rms_norm_eps``) and the
multipliers as named in the configuration's ``architecture`` group::

    x0     = embed[ids] * embedding_multiplier
    u      = N1(x)
    x      = x + ssm_out_multiplier * Mixer(u)
               + attention_out_multiplier * Attn(attention_in_multiplier * u)
    x      = x + MLP(N2(x))
    logits = (N_f(x) @ lm_head) * lm_head_multiplier

    Attn:  q = u Wq, k = (u Wk) * key_multiplier, v = u Wv; RoPE
           (half-rotation, rope_theta) on q and k; causal softmax at
           head_dim^-0.5, num_attention_heads / num_key_value_heads
           queries a KV head; Wo.  No bias.
    MLP:   down(up(h) * silu(gate(h) * mlp_multipliers[0])) * mlp_multipliers[1]
    Mixer: p = ((ssm_in_multiplier * u) W_in) * m, with m holding
           ssm_multipliers[0..4] over the segments z | x | B | C | dt of
           widths d_ssm | d_ssm | groups x d_state | groups x d_state | heads;
           [x|B|C] = silu(causal depthwise conv_{d_conv}([x|B|C]) + b_conv);
           dt = softplus(dt + dt_bias); A = -exp(A_log); per head h of
           group g:  H_t = exp(dt_t A_h) H_{t-1} + dt_t x_t B_{g,t}^T
           (H is d_head x d_state, zero before the first token),
           y_t = H_t C_{g,t} + D_h x_t;  gate then norm
           (mamba_norm_before_gate false):
           y = GroupRMSNorm_groups(y * silu(z)) * w;  W_out.

The order of the five segments and the place of ``key_multiplier`` are the
published implementation's (``transformers``, ``modeling_falcon_h1.py``),
not keys of the config: the configuration's file lists them under
``assumed``.  ``mamba_chunk_size`` is a property of a scan algorithm, not
of this function, and is not read.

**The interface** is ``decoder_f32``'s (``benchmark/README.md``, "A
reference"): ``WEIGHTS``, ``greedy_gaps(config_doc, weights, sequences)``
and ``control_gaps`` (the nine layer matrices at int4 in place of int8).
This model's logits are small (``lm_head_multiplier`` 2^-7), so its gaps
and its limits are too: they are the configuration's, measured, in its
``probe`` group.
"""

from __future__ import annotations

import functools
import importlib
from typing import Any

from . import decoder_f32

WEIGHTS = "falcon_h1_f32_weights"

#: sizes of ``architecture`` the layer's compiled function is keyed by
_SIZES = (
    "num_attention_heads", "num_key_value_heads", "head_dim", "mamba_n_heads",
    "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
)
_NUMBERS = (
    "rms_norm_eps", "rope_theta", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier",
)


@functools.lru_cache(maxsize=None)
def _layer_fn(sizes: tuple, numbers: tuple, mlp_m: tuple, ssm_m: tuple, padded: int) -> Any:
    """The jitted one-layer function for one geometry and padded length:
    ``x [sequences, padded, hidden]``."""
    import jax
    import jax.numpy as jnp

    heads, kv_heads, head_dim, m_heads, m_head, d_state, groups, d_conv = sizes
    eps, theta, attn_in_m, attn_out_m, key_m, ssm_in_m, ssm_out_m = numbers
    d_ssm, gn = m_heads * m_head, groups * d_state
    segments = jnp.concatenate([
        jnp.full((w,), m, jnp.float32)
        for w, m in zip((d_ssm, d_ssm, gn, gn, m_heads), ssm_m)
    ])

    def rms_norm(x, scale):
        variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(variance + eps) * scale

    def rope(x, cos, sin):  # [T, heads, D]
        half = head_dim // 2
        x1, x2 = x[..., :half], x[..., half:]
        c, s = cos[:, None, :], sin[:, None, :]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)

    def attention(u, w):  # [T, hidden], one sequence
        exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
        inv_freq = 1.0 / (theta ** exponents)
        angles = jnp.arange(padded, dtype=jnp.float32)[:, None] * inv_freq[None, :]
        cos, sin = jnp.cos(angles), jnp.sin(angles)
        h = u * attn_in_m
        q = rope((h @ w["wq"]).reshape(padded, heads, head_dim), cos, sin)
        k = rope(((h @ w["wk"]) * key_m).reshape(padded, kv_heads, head_dim), cos, sin)
        v = (h @ w["wv"]).reshape(padded, kv_heads, head_dim)
        group = heads // kv_heads
        k = jnp.repeat(k, group, axis=1)  # query head i reads kv head i // group
        v = jnp.repeat(v, group, axis=1)
        scores = jnp.einsum("thd,shd->hts", q, k) * head_dim ** -0.5
        causal = jnp.tril(jnp.ones((padded, padded), bool))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        attn = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
        return attn.reshape(padded, heads * head_dim) @ w["wo"]

    def mixer(u, w):  # [S, T, hidden], all sequences together
        n = u.shape[0]
        p = ((u * ssm_in_m) @ w["w_in"]) * segments
        z, xbc, dt = p[..., :d_ssm], p[..., d_ssm:2 * d_ssm + 2 * gn], p[..., 2 * d_ssm + 2 * gn:]
        # causal depthwise convolution: tap k reads d_conv - 1 - k tokens back
        back = jnp.pad(xbc, ((0, 0), (d_conv - 1, 0), (0, 0)))
        conv = w["conv_b"] + sum(
            w["conv_w"][k] * back[:, k:k + padded] for k in range(d_conv)
        )
        xbc = jax.nn.silu(conv)
        x = xbc[..., :d_ssm].reshape(n, padded, m_heads, m_head)
        per_group = m_heads // groups
        b = jnp.repeat(xbc[..., d_ssm:d_ssm + gn].reshape(n, padded, groups, d_state), per_group, axis=2)
        c = jnp.repeat(xbc[..., d_ssm + gn:].reshape(n, padded, groups, d_state), per_group, axis=2)
        dt = jax.nn.softplus(dt + w["dt_bias"])  # [S, T, heads]
        a = -jnp.exp(w["a_log"])

        def token(state, inputs):  # state [S, heads, d_head, d_state]
            x_t, b_t, c_t, dt_t = inputs
            state = (
                jnp.exp(dt_t * a)[..., None, None] * state
                + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
            )
            return state, jnp.einsum("shpn,shn->shp", state, c_t) + w["d_skip"][:, None] * x_t

        first = lambda v: jnp.moveaxis(v, 1, 0)  # noqa: E731 - positions lead
        zero = jnp.zeros((n, m_heads, m_head, d_state), jnp.float32)
        _, y = jax.lax.scan(token, zero, (first(x), first(b), first(c), first(dt)))
        y = first(y).reshape(n, padded, d_ssm) * jax.nn.silu(z)
        grouped = y.reshape(n, padded, groups, d_ssm // groups)
        variance = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
        normed = (grouped * jax.lax.rsqrt(variance + eps)).reshape(n, padded, d_ssm)
        return (normed * w["ln_ssm"]) @ w["w_out"]

    @jax.jit
    def layer(x, w):
        with jax.default_matmul_precision("highest"):
            u = rms_norm(x, w["ln_attn"])
            attn = jax.lax.map(lambda one: attention(one, w), u)
            x = x + ssm_out_m * mixer(u, w) + attn_out_m * attn
            h = rms_norm(x, w["ln_mlp"])
            gate = jax.nn.silu((h @ w["w_gate"]) * mlp_m[0])
            return x + (((h @ w["w_up"]) * gate) @ w["w_down"]) * mlp_m[1]

    return layer, rms_norm


def hidden_states(weights: Any, arch: dict, sequences: list) -> Any:
    """Final-norm hidden states ``[len(sequences), padded, hidden]`` in
    float32 of token-id lists right-padded to one length."""
    import jax.numpy as jnp

    padded = decoder_f32._pad_length(max(len(ids) for ids in sequences))
    layer, rms_norm = _layer_fn(
        tuple(int(arch[k]) for k in _SIZES), tuple(float(arch[k]) for k in _NUMBERS),
        tuple(float(m) for m in arch["mlp_multipliers"]),
        tuple(float(m) for m in arch["ssm_multipliers"]), padded,
    )
    tokens = jnp.asarray(
        [list(ids) + [0] * (padded - len(ids)) for ids in sequences], jnp.int32
    )
    x = jnp.take(weights.embed, tokens, axis=0).astype(jnp.float32)
    x = x * float(arch["embedding_multiplier"])
    for index in range(int(arch["num_hidden_layers"])):
        x = layer(x, weights.layer(index))
    return rms_norm(x, weights.ln_final.astype(jnp.float32))


def _rows(weights: Any, arch: dict, sequences: list) -> Any:
    """Every position of every ``prompt ids + served ids`` as
    ``[sequences x padded, hidden]``, scaled so that the head's product
    is the logits (``lm_head_multiplier``)."""
    hidden = hidden_states(weights, arch, [list(p) + list(c) for p, c in sequences])
    hidden = hidden * float(arch["lm_head_multiplier"])
    return hidden.reshape(-1, hidden.shape[-1]), hidden.shape[1]


def logits(config_doc: dict, weights: Any, ids: list) -> Any:
    """``[len(ids), vocab]`` float32 logits of one sequence (the tests'
    whole-vocabulary view; a run reads the blocked head below)."""
    import jax
    import jax.numpy as jnp

    arch = config_doc["architecture"]
    rows, _ = _rows(weights, arch, [(list(ids), [])])
    head = weights.embed.T if weights.head is None else weights.head
    with jax.default_matmul_precision("highest"):
        return (rows @ head.astype(jnp.float32))[: len(ids)]


def greedy_gaps(config_doc: dict, weights: Any, sequences: list) -> list:
    """Teacher-forced on each ``prompt ids + served ids``: per sequence, for
    each served token, the position's largest reference logit minus the
    served token's."""
    import numpy as np

    arch = config_doc["architecture"]
    rows, padded = _rows(weights, arch, sequences)
    scored = decoder_f32._scored(sequences, padded)
    index = np.zeros(rows.shape[0], np.int32)
    for (_, served), where in zip(sequences, scored):
        index[where] = served
    largest, _, picked = decoder_f32.head_reduce(weights, arch, rows, index)
    return [[float(largest[r] - picked[r]) for r in where] for where in scored]


def control_gaps(config_doc: dict, weights: Any, sequences: list) -> list:
    """The control: this reference with its nine layer matrices at the
    nearest precision below the configuration's (int4 a column for int8),
    put in the program's place: at each scored position, the float32 gap
    of the token the lower precision puts first."""
    import numpy as np

    own = importlib.import_module("." + WEIGHTS, __package__)
    arch = config_doc["architecture"]
    rows, padded = _rows(weights, arch, sequences)
    weights.release_layers()
    bits = decoder_f32.LOWER_BITS[int(config_doc["weights"].get("bits") or 0)]
    low = own.make(config_doc, bits, like=weights)
    low_rows, _ = _rows(low, arch, sequences)
    low.release_layers()
    nothing = np.zeros(rows.shape[0], np.int32)
    _, first, _ = decoder_f32.head_reduce(weights, arch, low_rows, nothing)
    largest, _, picked = decoder_f32.head_reduce(weights, arch, rows, first)
    return [
        [float(largest[r] - picked[r]) for r in where]
        for where in decoder_f32._scored(sequences, padded)
    ]
