"""The plain reference: a Qwen2/Llama-style decoder in float32.

RMSNorm, rotary embeddings (half-rotation, as the published Hugging Face
implementation), grouped-query attention with optional q/k/v bias, a
SiLU-gated MLP, a tied or untied head.  Straightforward ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no cache, no kernel, no
batching, one layer at a time so that a 7B model's float32 weights never
exist all at once.  It follows the published description; the only
departure is that the sequence is right-padded to a power of two, which a
causal model cannot see from the positions that are scored.

Sizes come from the ``architecture`` group of the benchmark's own
configuration file (the source's key names), not from the program.

``weights`` is any object with ``embed`` ``[vocab, hidden]``, ``ln_final``,
``head`` (``[hidden, vocab]`` or None when tied) and ``layer(i)`` returning
float32 ``wq wk wv wo w_gate w_up w_down ln_attn ln_mlp`` and, with bias,
``bq bk bv`` — matrices stored ``[in, out]``.

**Tolerance** (``LOGIT_TOLERANCE``).  The engine multiplies in bfloat16
with float32 accumulation (int8 weights are cast to bfloat16, the scale
folded in after); the reference multiplies the same dequantised weights in
float32.  With these seeded weights a position's logits are about unit
normal and its largest is 4 to 5; bfloat16 activations through 28 layers
move a logit by a few hundredths.  So a greedily chosen token may differ
from the reference's own choice only where the two leading logits are that
close: its reference logit must lie within ``LOGIT_TOLERANCE`` of the
position's maximum.  A wrong rotary convention, a missing layer, a head or
group mismatch, or a stale cache page moves logits by order one and fails;
so does anything that costs more precision than bfloat16 activations do
(the largest gap measured on the chip is in ``PERF.md``).  Biases are zero
in the program's seeded init, so a dropped bias shows only in the CPU test,
which sets random ones.
"""

from __future__ import annotations

import functools
from typing import Any

#: see the module text; measured gaps are in PERF.md section 6
LOGIT_TOLERANCE = 0.05


def _pad_length(n: int) -> int:
    """A power of two, 256 at least: few shapes to compile, and the short
    prompts of one mix all take the same one whatever the seed."""
    size = 256
    while size < n:
        size *= 2
    return size


@functools.lru_cache(maxsize=None)
def _layer_fn(
    heads: int, kv_heads: int, head_dim: int, eps: float, theta: float, padded: int
) -> Any:
    """The jitted one-layer function for one geometry and padded length."""
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

    @jax.jit
    def layer(x, w):
        exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
        inv_freq = 1.0 / (theta ** exponents)
        angles = jnp.arange(padded, dtype=jnp.float32)[:, None] * inv_freq[None, :]
        cos, sin = jnp.cos(angles), jnp.sin(angles)  # [T, D/2]
        with jax.default_matmul_precision("highest"):
            h = rms_norm(x, w["ln_attn"])
            q = h @ w["wq"] + w.get("bq", 0.0)
            k = h @ w["wk"] + w.get("bk", 0.0)
            v = h @ w["wv"] + w.get("bv", 0.0)
            q = rope(q.reshape(padded, heads, head_dim), cos, sin)
            k = rope(k.reshape(padded, kv_heads, head_dim), cos, sin)
            v = v.reshape(padded, kv_heads, head_dim)
            group = heads // kv_heads
            k = jnp.repeat(k, group, axis=1)  # query head i reads kv head i // group
            v = jnp.repeat(v, group, axis=1)
            scores = jnp.einsum("thd,shd->hts", q, k) * head_dim ** -0.5
            causal = jnp.tril(jnp.ones((padded, padded), bool))
            scores = jnp.where(causal[None], scores, -jnp.inf)
            attn = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
            x = x + attn.reshape(padded, heads * head_dim) @ w["wo"]
            h = rms_norm(x, w["ln_mlp"])
            return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]

    return layer, rms_norm


def hidden_states(weights: Any, arch: dict, ids: list[int]) -> Any:
    """Final-norm hidden states ``[len(ids), hidden]`` in float32."""
    import jax.numpy as jnp

    heads = int(arch["num_attention_heads"])
    n = len(ids)
    padded = _pad_length(n)
    layer, rms_norm = _layer_fn(
        heads, int(arch["num_key_value_heads"]),
        int(arch.get("head_dim") or arch["hidden_size"] // heads),
        float(arch["rms_norm_eps"]), float(arch["rope_theta"]), padded,
    )
    tokens = jnp.asarray(list(ids) + [0] * (padded - n), jnp.int32)
    x = jnp.take(weights.embed, tokens, axis=0).astype(jnp.float32)
    for index in range(int(arch["num_hidden_layers"])):
        x = layer(x, weights.layer(index))
    return rms_norm(x, weights.ln_final.astype(jnp.float32))[:n]


def logits(weights: Any, arch: dict, hidden: Any, blocks: int = 8) -> Any:
    """``hidden [n, hidden]`` through the head, ``[n, vocab]`` float32,
    the head converted to float32 a block of the vocabulary at a time."""
    import jax
    import jax.numpy as jnp

    tied = weights.head is None
    if bool(arch["tie_word_embeddings"]) != tied:
        raise ValueError("the parameters' head does not match tie_word_embeddings")
    vocab = int(arch["vocab_size"])

    @jax.jit
    def project(h, block):
        with jax.default_matmul_precision("highest"):
            block = block.astype(jnp.float32)
            return h @ (block.T if tied else block)

    edges = [vocab * i // blocks for i in range(blocks + 1)]
    parts = []
    for lo, hi in zip(edges, edges[1:]):
        block = weights.embed[lo:hi] if tied else weights.head[:, lo:hi]
        parts.append(project(hidden, block))
    return jnp.concatenate(parts, axis=-1)


def greedy_gaps(
    weights: Any, arch: dict, prompt_ids: list[int], chosen: list[int]
) -> list[float]:
    """Teacher-forced on ``prompt_ids + chosen``: for each chosen token,
    the position's largest reference logit minus the chosen token's."""
    import numpy as np

    ids = list(prompt_ids) + list(chosen)
    hidden = hidden_states(weights, arch, ids)
    first = len(prompt_ids) - 1  # the position that predicts chosen[0]
    scored = np.asarray(logits(weights, arch, hidden[first:first + len(chosen)]))
    return [
        float(scored[i].max() - scored[i, token]) for i, token in enumerate(chosen)
    ]
