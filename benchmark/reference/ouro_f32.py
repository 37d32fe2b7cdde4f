"""The plain reference for Ouro (LoopLM): one stack of sandwich-normed
decoder layers run ``total_ut_steps`` times a token with the same weights,
the final norm after every pass, and an exit gate read after each -- in
float32.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision(
"highest")``: full causal attention with **no cache**, so a pass can only
attend to the keys and values it made itself, which is the published
model's rule; no kernel, no plane, no page.  One layer's float32 weights
exist at a time (each pass makes them again), on weights of its own
(``ouro_f32_weights.make``); nothing of the program is imported.  The head,
blocked over the vocabulary, is ``decoder_f32``'s (a file of the benchmark
beside this one).  The only departure from the published description is
that every sequence is right-padded to one power of two, which a causal
model cannot see from the positions that are scored.

With ``x`` the residual stream and ``N`` an RMSNorm (``rms_norm_eps``),
for pass ``t = 0 .. total_ut_steps - 1`` and layer ``l = 0 ..
num_hidden_layers - 1``, sizes from the configuration's ``architecture``
group::

    a = N(x; ln_attn[l]);  q, k, v = a Wq[l], a Wk[l], a Wv[l]   (no bias)
    RoPE (half rotation, rope_theta) on q and k; causal softmax at
    head_dim^-0.5, num_attention_heads / num_key_value_heads queries a KV
    head, over this pass's own k and v
    x = x + N(attn Wo[l]; ln_attn_post[l])
    m = N(x; ln_mlp[l])
    x = x + N((silu(m Wg[l]) * (m Wu[l])) Wd[l]; ln_mlp_post[l])
    after the last layer of EVERY pass:  x = N(x; ln_final)
    logits = x W_head  (the last pass's x)

    exit gate:  lam_t = sigmoid(x_t . exit_w + exit_b) on pass t's normed
    x;  p_t = lam_t * prod_{s<t} (1 - lam_s), the last pass the remainder.
    Where the running sum of p reaches ``early_exit_threshold`` a row would
    take that pass's x to the head; at 1.0 (the published value, the only
    one this reference accepts for ``greedy_gaps``) no row does.

The place of the four norms, the final norm after every pass and the
absence of bias are the published implementation's (``transformers``,
``modeling_ouro.py``), not keys of the config: the configuration's file
lists them under ``assumed``.

**The interface** is ``decoder_f32``'s (``benchmark/README.md``, "A
reference"): ``WEIGHTS``, ``greedy_gaps(config_doc, weights, sequences)``
and ``control_gaps`` (the seven layer matrices at int4 in place of int8);
``logits`` and ``exit_distribution`` serve the tests.
"""

from __future__ import annotations

import functools
import importlib
from typing import Any

from . import decoder_f32

#: the weights module beside this file
WEIGHTS = "ouro_f32_weights"


@functools.lru_cache(maxsize=None)
def _layer_fn(heads: int, kv_heads: int, head_dim: int, eps: float, theta: float,
              padded: int) -> Any:
    """The jitted one-layer function for one geometry and padded length:
    ``x [sequences, padded, hidden]``, one sequence at a time inside."""
    import jax
    import jax.numpy as jnp

    def rms_norm(x, scale):
        variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(variance + eps) * scale

    def rope(x, cos, sin):  # [T, heads, D]
        half = head_dim // 2
        x1, x2 = x[..., :half], x[..., half:]
        c, s = cos[:, None, :], sin[:, None, :]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)

    def one_sequence(x, w):  # [T, hidden]
        exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
        angles = (
            jnp.arange(padded, dtype=jnp.float32)[:, None]
            * (1.0 / (theta ** exponents))[None, :]
        )
        cos, sin = jnp.cos(angles), jnp.sin(angles)
        a = rms_norm(x, w["ln_attn"])
        q = rope((a @ w["wq"]).reshape(padded, heads, head_dim), cos, sin)
        k = rope((a @ w["wk"]).reshape(padded, kv_heads, head_dim), cos, sin)
        v = (a @ w["wv"]).reshape(padded, kv_heads, head_dim)
        k = jnp.repeat(k, heads // kv_heads, axis=1)
        v = jnp.repeat(v, heads // kv_heads, axis=1)
        scores = jnp.einsum("thd,shd->hts", q, k) * head_dim ** -0.5
        causal = jnp.tril(jnp.ones((padded, padded), bool))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        attn = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
        x = x + rms_norm(attn.reshape(padded, heads * head_dim) @ w["wo"], w["ln_attn_post"])
        m = rms_norm(x, w["ln_mlp"])
        mlp = (jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"]
        return x + rms_norm(mlp, w["ln_mlp_post"])

    @jax.jit
    def layer(x, w):
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(lambda one: one_sequence(one, w), x)

    return layer, rms_norm


def pass_states(weights: Any, arch: dict, sequences: list) -> list:
    """The normed stream after each pass, ``total_ut_steps`` arrays
    ``[len(sequences), padded, hidden]`` in float32, of token-id lists
    right-padded to one length."""
    import jax.numpy as jnp

    heads = int(arch["num_attention_heads"])
    padded = decoder_f32._pad_length(max(len(ids) for ids in sequences))
    layer, rms_norm = _layer_fn(
        heads, int(arch["num_key_value_heads"]), int(arch["head_dim"]),
        float(arch["rms_norm_eps"]), float(arch["rope_theta"]), padded,
    )
    tokens = jnp.asarray(
        [list(ids) + [0] * (padded - len(ids)) for ids in sequences], jnp.int32
    )
    x = jnp.take(weights.embed, tokens, axis=0).astype(jnp.float32)
    final = weights.ln_final.astype(jnp.float32)
    states = []
    for _ in range(int(arch["total_ut_steps"])):
        for index in range(int(arch["num_hidden_layers"])):
            x = layer(x, weights.layer(index))
        x = rms_norm(x, final)
        states.append(x)
    return states


def _rows(weights: Any, arch: dict, sequences: list) -> Any:
    """Every position of every ``prompt ids + served ids`` after the last
    pass, as ``[sequences x padded, hidden]``."""
    if float(arch["early_exit_threshold"]) < 1.0:
        raise ValueError(
            "ouro_f32 scores the last pass's logits: rows that leave the "
            "loop early (early_exit_threshold < 1) are not implemented"
        )
    hidden = pass_states(weights, arch, [list(p) + list(c) for p, c in sequences])[-1]
    return hidden.reshape(-1, hidden.shape[-1]), hidden.shape[1]


def logits(config_doc: dict, weights: Any, ids: list) -> Any:
    """``[len(ids), vocab]`` float32 logits of one sequence (the tests'
    whole-vocabulary view; a run reads the blocked head below)."""
    import jax
    import jax.numpy as jnp

    rows, _ = _rows(weights, config_doc["architecture"], [(list(ids), [])])
    with jax.default_matmul_precision("highest"):
        return (rows @ weights.head.astype(jnp.float32))[: len(ids)]


def exit_distribution(config_doc: dict, weights: Any, ids: list) -> Any:
    """``[total_ut_steps, len(ids)]``: by the gate, the probability that a
    token's computation ends after each pass (the last takes the rest)."""
    import jax
    import jax.numpy as jnp

    states = pass_states(weights, config_doc["architecture"], [list(ids)])
    w = weights.leaves["exit_w"].astype(jnp.float32)
    b = weights.leaves["exit_b"].astype(jnp.float32)
    out, left = [], 1.0
    with jax.default_matmul_precision("highest"):
        for state in states[:-1]:
            gate = jax.nn.sigmoid(state[0, : len(ids)] @ w + b)
            out.append(gate * left)
            left = left * (1.0 - gate)
    out.append(left * jnp.ones((len(ids),), jnp.float32))
    return jnp.stack(out)


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
    """The control: this reference with its seven layer matrices at the
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
