"""The plain reference: a Qwen2/Llama-style decoder in float32.

RMSNorm, rotary embeddings (half-rotation, as the published Hugging Face
implementation), grouped-query attention with optional q/k/v bias, a
SiLU-gated MLP, a tied or untied head.  Straightforward ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no cache, no kernel, one layer
at a time so that a 7B model's float32 weights never exist all at once, on
weights of its own (``decoder_f32_weights.make``).  It
follows the published description; the only departure is that every
sequence is right-padded to one power of two, which a causal model cannot
see from the positions that are scored.

**The interface a reference module offers** (``benchmark/README.md``, "A
reference"; the harness finds this file by the ``reference`` key of a
configuration's file and imports nothing of it by name):

- ``WEIGHTS``: the name of the weights module, a file beside this one, whose
  ``make(config_doc)`` makes this reference's **own** weights from the recipe
  the configuration states.  Neither file imports anything of the program or
  reads anything the program made.
- ``greedy_gaps(config_doc, weights, sequences)``: ``config_doc`` is the
  configuration's **whole** file (sizes are read from its ``architecture``
  group, under the source's key names, never from the program); ``weights``
  is what ``make`` returned; ``sequences`` is every sampled request at once,
  ``[(prompt ids, served ids), ...]``.  Returns, per sequence, for each
  served token the position's largest reference logit less the served
  token's.  All sequences go through a layer together, so a layer's float32
  weights are made once a run; every shape follows the number of sequences
  and their padded length, never the seed's counts of tokens.
- ``control_gaps(config_doc, weights, sequences)`` (optional): the same
  sequences through this reference at the nearest precision below the one
  the configuration states (here: the recipe's init rounded to int4 a
  channel in place of int8), put in the program's place: at each scored
  position, the float32 gap of the token the lower precision puts first.  A
  limit has to lie under what this reads (``tools/served_seeds.py
  --control``, which holds it to the harness's own ``judge``).

``weights`` here is any object with ``embed`` ``[vocab, hidden]``,
``ln_final``, ``head`` (``[hidden, vocab]`` or None when tied) and
``layer(i)`` returning float32 ``wq wk wv wo w_gate w_up w_down ln_attn
ln_mlp`` and, with bias, ``bq bk bv`` -- matrices stored ``[in, out]``.

**Why a gap, and what a limit has to separate.**  The engine multiplies in
bfloat16 with float32 accumulation (int8 weights are cast to bfloat16, the
scale folded in after); the reference multiplies the same model's
dequantised weights in float32.  With these seeded weights a position's
logits are about unit normal and its largest is 4 to 5; bfloat16
activations through 28 layers move a logit by a few hundredths.  So a
greedily served token may differ from the reference's own choice only where
the two leading logits are that close: its reference logit must lie within
the limit of the position's maximum.  The limit is the configuration's (its
file's ``probe`` group, with the readings it was set from: depth, width and
dtype decide how far rounding moves a logit, not this file).  Over the
~1,500 served tokens of four of a window's own greedy requests, the largest
gap read on the chip is 0.0080 at 1.5B (24 seeds, two mixes) and 0.076 at
7B (14 seeds), and the 99th percentile of the gaps 0.0036 and 0.023
(``PERF.md`` section 6, PR 27); the control reads 0.275 and 3.49 at the
least.  A wrong rotary convention, a missing layer, a head or group mismatch, a
stale cache page, a wrong scale or another init moves logits by order one
-- a token served with such a fault lies 2 to 5 under the maximum -- and
fails any of the limits; so does int4 in place of int8 (``control_gaps``).
Biases are zero in the recipe, so a dropped bias shows only in the CPU
test, which sets random ones.
"""

from __future__ import annotations

import functools
from typing import Any

#: the adapter beside this file: ``decoder_f32_weights.adapt(params, config_doc)``
WEIGHTS = "decoder_f32_weights"


def _pad_length(n: int) -> int:
    """A power of two, 256 at least: few shapes to compile, and the
    prompts of one mix all take the same one whatever the seed."""
    size = 256
    while size < n:
        size *= 2
    return size


@functools.lru_cache(maxsize=None)
def _layer_fn(
    heads: int, kv_heads: int, head_dim: int, eps: float, theta: float, padded: int
) -> Any:
    """The jitted one-layer function for one geometry and padded length:
    ``x [sequences, padded, hidden]``, one sequence at a time inside (so
    that one sequence's scores exist at once, not the probe's)."""
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
        inv_freq = 1.0 / (theta ** exponents)
        angles = jnp.arange(padded, dtype=jnp.float32)[:, None] * inv_freq[None, :]
        cos, sin = jnp.cos(angles), jnp.sin(angles)  # [T, D/2]
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

    @jax.jit
    def layer(x, w):
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(lambda one: one_sequence(one, w), x)

    return layer, rms_norm


def hidden_states(weights: Any, arch: dict, sequences: list) -> Any:
    """Final-norm hidden states ``[len(sequences), padded, hidden]`` in
    float32 of token-id lists right-padded to one length."""
    import jax.numpy as jnp

    heads = int(arch["num_attention_heads"])
    padded = _pad_length(max(len(ids) for ids in sequences))
    layer, rms_norm = _layer_fn(
        heads, int(arch["num_key_value_heads"]),
        int(arch.get("head_dim") or arch["hidden_size"] // heads),
        float(arch["rms_norm_eps"]), float(arch["rope_theta"]), padded,
    )
    tokens = jnp.asarray(
        [list(ids) + [0] * (padded - len(ids)) for ids in sequences], jnp.int32
    )
    x = jnp.take(weights.embed, tokens, axis=0).astype(jnp.float32)
    for index in range(int(arch["num_hidden_layers"])):
        x = layer(x, weights.layer(index))
    return rms_norm(x, weights.ln_final.astype(jnp.float32))


@functools.lru_cache(maxsize=None)
def _reduce_fn(tied: bool) -> Any:
    """One block of the head, reduced on the device: each row's largest
    logit in the block, where it lies, and the row's logit at ``index``
    (relative to the block; minus infinity where that lies outside it)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def reduce(h, block, index):
        with jax.default_matmul_precision("highest"):
            block = block.astype(jnp.float32)
            part = h @ (block.T if tied else block)
        width = part.shape[-1]
        at = jnp.take_along_axis(part, jnp.clip(index, 0, width - 1)[:, None], axis=1)[:, 0]
        return (
            part.max(axis=-1), part.argmax(axis=-1).astype(jnp.int32),
            jnp.where((index >= 0) & (index < width), at, -jnp.inf),
        )

    return reduce


def head_reduce(weights: Any, arch: dict, rows: Any, index: Any, blocks: int = 8) -> tuple:
    """``rows [n, hidden]`` through the head, a block of the vocabulary at
    a time and reduced there, so that no ``[n, vocab]`` array exists: each
    row's largest logit, the token that has it, and the row's logit of the
    token ``index[row]``.  The one head path: the served tokens' gaps and
    the control's both read it."""
    import jax.numpy as jnp
    import numpy as np

    tied = weights.head is None
    if bool(arch["tie_word_embeddings"]) != tied:
        raise ValueError("the weights' head does not match tie_word_embeddings")
    vocab = int(arch["vocab_size"])
    edges = [vocab * i // blocks for i in range(blocks + 1)]
    reduce = _reduce_fn(tied)
    index = np.asarray(index, np.int32)
    largest = first = picked = None
    for lo, hi in zip(edges, edges[1:]):
        # [ids, hidden] rows of the embedding where tied, else [hidden, ids] columns
        block = weights.embed[lo:hi] if tied else weights.head[:, lo:hi]
        block_largest, block_first, block_picked = reduce(rows, block, jnp.asarray(index - lo))
        if largest is None:
            largest, first, picked = block_largest, block_first + lo, block_picked
            continue
        first = jnp.where(block_largest > largest, block_first + lo, first)
        largest = jnp.maximum(largest, block_largest)
        picked = jnp.maximum(picked, block_picked)
    return np.asarray(largest), np.asarray(first), np.asarray(picked)


def _rows(weights: Any, arch: dict, sequences: list) -> Any:
    """Every position of every sequence ``prompt ids + served ids``, as
    ``[sequences x padded, hidden]``: shapes follow the number of sequences
    and the padded length, never the seed's counts of tokens."""
    hidden = hidden_states(weights, arch, [list(p) + list(c) for p, c in sequences])
    return hidden.reshape(-1, hidden.shape[-1]), hidden.shape[1]


def _scored(sequences: list, padded: int) -> list:
    """Per sequence, the rows of ``_rows`` that predict its served tokens."""
    return [
        [i * padded + len(p) - 1 + j for j in range(len(c))]
        for i, (p, c) in enumerate(sequences)
    ]


def greedy_gaps(config_doc: dict, weights: Any, sequences: list) -> list:
    """Teacher-forced on each ``prompt ids + served ids``: per sequence, for
    each served token, the position's largest reference logit minus the
    served token's."""
    import numpy as np

    arch = config_doc["architecture"]
    rows, padded = _rows(weights, arch, sequences)
    scored = _scored(sequences, padded)
    index = np.zeros(rows.shape[0], np.int32)
    for (_, served), where in zip(sequences, scored):
        index[where] = served
    largest, _, picked = head_reduce(weights, arch, rows, index)
    return [[float(largest[r] - picked[r]) for r in where] for where in scored]


#: the nearest precision below the one a configuration states (its
#: ``weights.bits``; 0 is the float dtype alone)
LOWER_BITS = {0: 8, 8: 4}


def control_gaps(config_doc: dict, weights: Any, sequences: list) -> list:
    """The control: this reference with its layer matrices at the nearest
    precision below the configuration's (int4 a channel for int8), put in
    the program's place.  It need not decode: at each scored position of
    the same sequences, the float32 gap of the token the lower precision
    puts first.  ``weights`` loses its layer leaves on the way (two sets
    do not fit beside each other at 7B); they are made again on next use."""
    import numpy as np

    import importlib

    own = importlib.import_module("." + WEIGHTS, __package__)
    arch = config_doc["architecture"]
    rows, padded = _rows(weights, arch, sequences)
    weights.release_layers()
    bits = LOWER_BITS[int(config_doc["weights"].get("bits") or 0)]
    low = own.make(config_doc, bits, like=weights)
    low_rows, _ = _rows(low, arch, sequences)
    low.release_layers()
    nothing = np.zeros(rows.shape[0], np.int32)
    _, first, _ = head_reduce(weights, arch, low_rows, nothing)
    largest, _, picked = head_reduce(weights, arch, rows, first)
    return [[float(largest[r] - picked[r]) for r in where] for where in _scored(sequences, padded)]
