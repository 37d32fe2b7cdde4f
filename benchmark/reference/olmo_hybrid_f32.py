"""The plain reference for Olmo Hybrid: gated delta-rule layers and
full-attention layers in the order ``layer_types`` gives, every branch
normed after it and before its residual add (the Olmo 2 order) -- in
float32.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision(
"highest")``: full causal softmax attention with no cache and no positional
rotation, and the linear layer's recurrence **token by token from a zero
state** (``lax.scan`` over positions, all sampled sequences together, fixed
shapes), which owes nothing to the program's kernel, its head pairs or its
per-slot state pool.  One layer at a time, each widened to float32 as it is
reached, so that one layer's float32 weights exist at once, on weights of
its own (``olmo_hybrid_f32_weights.make``); nothing of the program is
imported.  The head, blocked over the vocabulary, is ``decoder_f32``'s.
The only departure from the published description is that every sequence
is right-padded to one power of two, which a causal model cannot see from
the positions that are scored.

With ``x`` the residual stream and ``N`` an RMSNorm (``rms_norm_eps``)::

    x = x + N(Mixer(x); ln_attn_post);  x = x + N(MLP(x); ln_mlp_post)
    logits = N(x; ln_final) @ lm_head
    MLP(h) = down(silu(gate(h)) * up(h))

    linear_attention (Gated DeltaNet, arXiv:2412.06464, per head):
      q, k, v = silu(causal depthwise conv_K([x Wq | x Wk | x Wv]))  (no bias)
      q, k = q / sqrt(|q|^2 + 1e-6), k / sqrt(|k|^2 + 1e-6); q *= Dk^-0.5
      beta = 2 sigmoid(x Wb);  g = -exp(A_log) softplus(x Wa + dt_bias)
      S_t = exp(g_t) S_{t-1};  S_t += k_t (x) beta_t (v_t - S_t^T k_t)
      o_t = S_t^T q_t   (S is Dk x Dv, zero before the first token)
      Mixer = ((o / rms(o)) * o_norm * silu(x Wg)) Wo     (rms over Dv)
    full_attention:
      q = N(x Wq; q_norm), k = N(x Wk; k_norm) over the whole projection,
      v = x Wv; causal softmax at head_dim^-0.5, no RoPE; Wo

The L2 norm's epsilon, the gate after the per-head norm and ``beta``'s
factor 2 are the ``fla`` library's ``GatedDeltaNet`` (with
``allow_neg_eigval``), not keys of the config: the configuration's file
lists them under ``assumed``.

**The interface** is ``decoder_f32``'s (``benchmark/README.md``, "A
reference"): ``WEIGHTS``, ``greedy_gaps(config_doc, weights, sequences)``
and ``control_gaps`` (the seventeen layer matrices at int4 in place of
int8).
"""

from __future__ import annotations

import functools
import importlib
from typing import Any

from . import decoder_f32

WEIGHTS = "olmo_hybrid_f32_weights"

#: sizes of ``architecture`` the layers' compiled functions are keyed by
_SIZES = (
    "num_attention_heads", "head_dim", "linear_num_value_heads",
    "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim",
)


@functools.lru_cache(maxsize=None)
def _layer_fns(sizes: tuple, eps: float, padded: int) -> Any:
    """The jitted layer functions of one geometry and padded length, each
    on ``x [sequences, padded, hidden]``: ``(linear, full, rms_norm)``."""
    import jax
    import jax.numpy as jnp

    heads, head_dim, l_heads, dk, dv, width = sizes
    kd = l_heads * dk

    def rms_norm(x, scale):
        variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(variance + eps) * scale

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)

    def mlp(x, w):
        return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]

    def mixer(x, w):  # [S, T, hidden], all sequences together
        n = x.shape[0]
        qkv = jnp.concatenate([x @ w["wq"], x @ w["wk"], x @ w["wv"]], axis=-1)
        # causal depthwise convolution: tap k reads width - 1 - k tokens back
        back = jnp.pad(qkv, ((0, 0), (width - 1, 0), (0, 0)))
        qkv = jax.nn.silu(sum(w["conv_w"][k] * back[:, k:k + padded] for k in range(width)))
        q = l2(qkv[..., :kd].reshape(n, padded, l_heads, dk)) * dk ** -0.5
        k = l2(qkv[..., kd:2 * kd].reshape(n, padded, l_heads, dk))
        v = qkv[..., 2 * kd:].reshape(n, padded, l_heads, dv)
        beta = 2.0 * jax.nn.sigmoid(x @ w["wb"])  # [S, T, heads]
        g = -jnp.exp(w["a_log"]) * jax.nn.softplus(x @ w["wa"] + w["dt_bias"])

        def token(state, inputs):  # state [S, heads, Dk, Dv]
            q_t, k_t, v_t, g_t, beta_t = inputs
            state = jnp.exp(g_t)[..., None, None] * state
            remembered = jnp.einsum("shkv,shk->shv", state, k_t)
            state = state + k_t[..., :, None] * (beta_t[..., None] * (v_t - remembered))[..., None, :]
            return state, jnp.einsum("shkv,shk->shv", state, q_t)

        first = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731 - positions lead
        zero = jnp.zeros((n, l_heads, dk, dv), jnp.float32)
        _, o = jax.lax.scan(token, zero, tuple(map(first, (q, k, v, g, beta))))
        o = first(o)  # [S, T, heads, Dv]
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + eps)
        gate = jax.nn.silu((x @ w["wg"]).reshape(o.shape))
        return ((o * w["o_norm"] * gate).reshape(n, padded, l_heads * dv)) @ w["wo"]

    def attention(x, w):  # [T, hidden], one sequence
        q = rms_norm(x @ w["wq"], w["q_norm"]).reshape(padded, heads, head_dim)
        k = rms_norm(x @ w["wk"], w["k_norm"]).reshape(padded, heads, head_dim)
        v = (x @ w["wv"]).reshape(padded, heads, head_dim)
        scores = jnp.einsum("thd,shd->hts", q, k) * head_dim ** -0.5
        causal = jnp.tril(jnp.ones((padded, padded), bool))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        attn = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
        return attn.reshape(padded, heads * head_dim) @ w["wo"]

    @jax.jit
    def linear(x, w):
        with jax.default_matmul_precision("highest"):
            x = x + rms_norm(mixer(x, w), w["ln_attn_post"])
            return x + rms_norm(mlp(x, w), w["ln_mlp_post"])

    @jax.jit
    def full(x, w):
        with jax.default_matmul_precision("highest"):
            attn = jax.lax.map(lambda one: attention(one, w), x)
            x = x + rms_norm(attn, w["ln_attn_post"])
            return x + rms_norm(mlp(x, w), w["ln_mlp_post"])

    return linear, full, rms_norm


def hidden_states(weights: Any, arch: dict, sequences: list) -> Any:
    """Final-norm hidden states ``[len(sequences), padded, hidden]`` in
    float32 of token-id lists right-padded to one length."""
    import jax.numpy as jnp

    own = importlib.import_module("." + WEIGHTS, __package__)
    padded = decoder_f32._pad_length(max(len(ids) for ids in sequences))
    linear, full, rms_norm = _layer_fns(
        tuple(int(arch[k]) for k in _SIZES), float(arch["rms_norm_eps"]), padded,
    )
    tokens = jnp.asarray(
        [list(ids) + [0] * (padded - len(ids)) for ids in sequences], jnp.int32
    )
    x = jnp.take(weights.embed, tokens, axis=0).astype(jnp.float32)
    for is_linear, p, j in own.placement(arch):
        x = (linear if is_linear else full)(x, weights.layer_at(is_linear, p, j))
    return rms_norm(x, weights.ln_final.astype(jnp.float32))


def _rows(weights: Any, arch: dict, sequences: list) -> Any:
    """Every position of every ``prompt ids + served ids`` as ``[sequences
    x padded, hidden]``."""
    hidden = hidden_states(weights, arch, [list(p) + list(c) for p, c in sequences])
    return hidden.reshape(-1, hidden.shape[-1]), hidden.shape[1]


def logits(config_doc: dict, weights: Any, ids: list) -> Any:
    """``[len(ids), vocab]`` float32 logits of one sequence (the tests'
    whole-vocabulary view; a run reads the blocked head below)."""
    import jax
    import jax.numpy as jnp

    rows, _ = _rows(weights, config_doc["architecture"], [(list(ids), [])])
    with jax.default_matmul_precision("highest"):
        return (rows @ weights.head.astype(jnp.float32))[: len(ids)]


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
    """The control: this reference with its seventeen layer matrices at the
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
