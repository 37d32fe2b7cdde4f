"""The plain reference for DeepSeek-V2 (``model_type: deepseek_v2``):
multi-head latent attention in its EXPANDED form and a feed-forward layer
that is dense in the leading layers and sparse, with shared experts, after
them -- in float32.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision(
"highest")``: no kernel, no cache, no page, no batching of requests into a
step, nothing of the program imported.  It holds its weights as the
recipe's int8 groups (``deepseek_v2_f32_weights.make``) and widens each
layer's matrices as the layer is reached, and ONE expert's three matrices
at a time: an expert is computed for the tokens routed to it, gathered by
plain index (``sdar_f32``'s expert program), and no product runs over all
experts.

The layer, sizes from the configuration's ``architecture`` group under the
published keys, ``x`` the stream, ``N`` an RMSNorm (``rms_norm_eps``)::

    a = N(x; ln_attn);  q = a Wq  [heads x (nope | rope)]   (q_lora_rank null)
    [c | k_r] = a Wkva;  c = N(c; kv_norm)                  (kv_lora_rank | rope)
    per head h:  k_nope_h = c W_uk[h],  v_h = c W_uv[h]      (the EXPANDED form:
                 every head's keys and values decompressed from c)
    q_rope_h, k_r: YaRN RoPE (rope_scaling; rope_theta) over the rope lanes,
        de-interleaved (even lanes, then odd) and rotated by halves, as the
        published modeling_deepseek.py does
    score = [q_nope_h | q_rope_h] . [k_nope_h | k_r] x qk_head_dim^-0.5 x
            mscale(factor, mscale_all_dim)^2,  causal softmax,  o_h = p v_h
    x = x + concat_h(o_h) Wo
    first_k_dense_replace layers:  x = x + down(silu(gate(m)) * up(m)),
        m = N(x; ln_mlp), intermediate_size wide
    the others:  m = N(x; ln_mlp);  p = softmax(m Wr) over n_routed_experts;
        the num_experts_per_tok largest (divided by their sum only where
        norm_topk_prob), times routed_scaling_factor;
        x = x + sum_e p_e E_e(m) + S(m)   (S: the shared experts as one
        gated MLP of n_shared_experts x moe_intermediate_size)
    logits = N(x; ln_final) W_head

The program serves the ABSORBED form (the queries taken into the latent
space, the cache holding ``[c | k_r]``); this file decompresses per head:
the same function written the other way, so a fault of the absorption
(a dropped scale, a head's matrix transposed, the wrong lanes of the
cache) shows here.

Departures from the published description, each of them the configuration's
``assumed``: the shared experts are one MLP of twice the width (the public
code's own construction); every sequence is right-padded to one power of two
(``decoder_f32._pad_length``), which no scored position can see.

**The interface** is ``decoder_f32``'s (``benchmark/README.md``, "A
reference"): ``WEIGHTS``, ``greedy_gaps(config_doc, weights, sequences)``
and ``control_gaps`` (every matrix but the router at int4 in place of
int8); ``logits`` serves the tests.
"""

from __future__ import annotations

import functools
import importlib
import math
from typing import Any

from . import decoder_f32, sdar_f32
from .deepseek_v2_f32_weights import ATTENTION, DENSE, EXPERTS, depths, widen

#: the weights module beside this file
WEIGHTS = "deepseek_v2_f32_weights"


def rope_frequencies(arch: dict):
    """``[rope / 2]`` inverse frequencies with YaRN's blend
    (``rope_scaling``: the bands turning more than ``beta_fast`` times over
    the original context kept, fewer than ``beta_slow`` divided by
    ``factor``, a linear ramp between), and the factor cos and sin are
    multiplied by."""
    import numpy as np

    d, base = int(arch["qk_rope_head_dim"]), float(arch["rope_theta"])
    inv = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    scaling = arch.get("rope_scaling")
    if not scaling:
        return inv.astype(np.float32), 1.0
    if scaling["type"] != "yarn":
        raise ValueError(f"deepseek_v2_f32 knows no rope_scaling {scaling['type']!r}")
    factor, original = float(scaling["factor"]), float(scaling["original_max_position_embeddings"])

    def dim(turns):
        return d * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(dim(scaling["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    kept = 1.0 - np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    inv = (inv / factor) * (1.0 - kept) + inv * kept
    magnitude = mscale(factor, scaling["mscale"]) / mscale(factor, scaling["mscale_all_dim"])
    return inv.astype(np.float32), magnitude


def mscale(factor: float, scale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * scale * math.log(factor) + 1.0


def softmax_scale(arch: dict) -> float:
    scale = (int(arch["qk_nope_head_dim"]) + int(arch["qk_rope_head_dim"])) ** -0.5
    scaling = arch.get("rope_scaling")
    if scaling and scaling.get("mscale_all_dim"):
        scale *= mscale(float(scaling["factor"]), float(scaling["mscale_all_dim"])) ** 2
    return scale


@functools.lru_cache(maxsize=None)
def _attention_fn(heads: int, rank: int, nope: int, rope: int, eps: float, scale: float,
                  magnitude: float, padded: int) -> Any:
    """The jitted attention half of a layer for one geometry: ``x [rows,
    padded, hidden]`` and the layer's widened matrices and norms; one
    sequence's scores at a time."""
    import jax
    import jax.numpy as jnp

    def rms_norm(x, w):
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w

    order = jnp.concatenate([jnp.arange(0, rope, 2), jnp.arange(1, rope, 2)])

    def rotate(x, cos, sin):  # [T, ..., rope], de-interleaved, then by halves
        x = x[..., order]
        half = rope // 2
        x1, x2 = x[..., :half], x[..., half:]
        extra = (None,) * (x.ndim - 2)
        c, s = cos[(slice(None), *extra)], sin[(slice(None), *extra)]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1) * magnitude

    @jax.jit
    def attention(x, w, inv_freq):
        with jax.default_matmul_precision("highest"):
            angles = jnp.arange(padded, dtype=jnp.float32)[:, None] * inv_freq[None, :]
            cos, sin = jnp.cos(angles), jnp.sin(angles)
            a = rms_norm(x, w["ln_attn"])
            rows = x.shape[0]
            q = (a @ w["wq"]).reshape(rows, padded, heads, nope + rope)
            kv = a @ w["w_kva"]
            c = rms_norm(kv[..., :rank], w["kv_norm"])
            k_r = kv[..., rank:]
            causal = jnp.arange(padded)[None, :] <= jnp.arange(padded)[:, None]

            def one_row(args):
                q_r, c_r, kr_r = args
                k_nope = jnp.einsum("sr,hrd->shd", c_r, w["w_uk"])
                v = jnp.einsum("sr,hrd->shd", c_r, w["w_uv"])
                q_full = jnp.concatenate([q_r[..., :nope], rotate(q_r[..., nope:], cos, sin)], -1)
                k_rot = rotate(kr_r, cos, sin)  # [S, rope], one for every head
                k_full = jnp.concatenate(
                    [k_nope, jnp.broadcast_to(k_rot[:, None], (padded, heads, rope))], -1
                )
                scores = jnp.einsum("thd,shd->hts", q_full, k_full) * scale
                probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
                return jnp.einsum("hts,shd->thd", probs, v).reshape(padded, -1)

            attn = jax.lax.map(one_row, (q, c, k_r))
            x = x + attn @ w["wo"]
            return x, rms_norm(x, w["ln_mlp"])

    return attention, rms_norm


@functools.lru_cache(maxsize=None)
def _mlp_fn() -> Any:
    import jax

    @jax.jit
    def mlp(m, w_gate, w_up, w_down):
        with jax.default_matmul_precision("highest"):
            return (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down

    return mlp


@functools.lru_cache(maxsize=None)
def _router_fn(top: int, norm: bool, factor: float) -> Any:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def router(m, w_router, layer):
        with jax.default_matmul_precision("highest"):
            probs = jax.nn.softmax(m @ widen(w_router, layer), axis=-1)
        gates, chosen = jax.lax.top_k(probs, top)
        if norm:
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        return gates * factor, chosen

    return router


def _experts(weights: Any, arch: dict, layer: int, m: Any) -> Any:
    """The routed half of an expert layer on ``m [tokens, hidden]`` (normed
    already): each expert computed for the tokens routed to it."""
    import jax.numpy as jnp
    import numpy as np

    layers = weights.layers
    at = jnp.asarray(layer, jnp.int32)
    gates, chosen = _router_fn(
        int(arch["num_experts_per_tok"]), bool(arch["norm_topk_prob"]),
        float(arch["routed_scaling_factor"]),
    )(m, layers["w_router"], at)
    chosen, gates_host = np.asarray(chosen), np.asarray(gates)
    out = jnp.zeros_like(m)
    expert = sdar_f32._expert_fn()
    for e in range(int(arch["n_routed_experts"])):
        tokens, part = np.nonzero(chosen == e)
        if not len(tokens):
            continue
        # few shapes to compile: the rows padded to a power of two, 64 at
        # least, with token 0 at gate 0
        size = 64
        while size < len(tokens):
            size *= 2
        index = np.zeros(size, np.int32)
        index[: len(tokens)] = tokens
        gate = np.zeros(size, np.float32)
        gate[: len(tokens)] = gates_host[tokens, part]
        out = expert(
            out, m, jnp.asarray(index), jnp.asarray(gate),
            *(layers[name] for name in EXPERTS), at, jnp.asarray(e, jnp.int32),
        )
    return out


def hidden_states(weights: Any, arch: dict, sequences: list) -> Any:
    """Final-normed hidden states ``[len(sequences), padded, hidden]`` in
    float32 of token-id lists right-padded to one length."""
    import jax.numpy as jnp

    padded = decoder_f32._pad_length(max(len(ids) for ids in sequences))
    inv_freq, magnitude = rope_frequencies(arch)
    attention, rms_norm = _attention_fn(
        int(arch["num_attention_heads"]), int(arch["kv_lora_rank"]),
        int(arch["qk_nope_head_dim"]), int(arch["qk_rope_head_dim"]),
        float(arch["rms_norm_eps"]), softmax_scale(arch), magnitude, padded,
    )
    tokens = jnp.asarray(
        [list(ids) + [0] * (padded - len(ids)) for ids in sequences], jnp.int32
    )
    x = jnp.take(weights.embed, tokens, axis=0).astype(jnp.float32)
    rows = x.shape[0]
    layers = weights.layers
    mlp = _mlp_fn()
    dense, moe = depths(arch)
    for layer in range(dense + moe):
        prefix, at = (DENSE, layer) if layer < dense else ("", layer - dense)
        index = jnp.asarray(at, jnp.int32)
        w = {
            name: widen(layers[prefix + name], index)
            for name in ATTENTION + ("ln_attn", "kv_norm", "ln_mlp")
        }
        x, m = attention(x, w, jnp.asarray(inv_freq))
        m = m.reshape(rows * padded, -1)
        if prefix:
            y = mlp(m, *(widen(layers[prefix + name], index) for name in EXPERTS))
        else:
            y = _experts(weights, arch, at, m) + mlp(
                m, *(widen(layers[name], index) for name in ("sh_gate", "sh_up", "sh_down"))
            )
        x = x + y.reshape(x.shape)
    return rms_norm(x, weights.ln_final.astype(jnp.float32))


def _rows(weights: Any, arch: dict, sequences: list) -> tuple:
    """Every position of every ``prompt ids + served ids``, ``[sequences x
    padded, hidden]``, and the padded length."""
    hidden = hidden_states(weights, arch, [list(p) + list(c) for p, c in sequences])
    return hidden.reshape(-1, hidden.shape[-1]), hidden.shape[1]


def logits(config_doc: dict, weights: Any, ids: list) -> Any:
    """``[len(ids), vocab]`` float32 logits of one sequence (the tests'
    whole-vocabulary view)."""
    import jax
    import jax.numpy as jnp

    hidden = hidden_states(weights, config_doc["architecture"], [list(ids)])
    with jax.default_matmul_precision("highest"):
        return (hidden[0] @ weights.head.astype(jnp.float32))[: len(ids)]


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
    """The control: this reference with its int8 matrices at the nearest
    precision below the configuration's (int4 a column for int8) put in the
    program's place: at each scored position of the same sequences, the
    float32 gap of the token the lower precision puts first."""
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
