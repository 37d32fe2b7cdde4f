"""Llama-family decoder in pure functional JAX.

TPU-first design decisions (not a port of any torch implementation):

- **scan over layers**: per-layer parameters are stacked along a leading
  ``num_layers`` axis and the layer loop is ``jax.lax.scan`` — compile time
  is O(1) in depth and XLA sees one fused layer body;
- **bfloat16 compute, float32 accumulation** where it matters (RMSNorm mean,
  softmax, logits) — the MXU natively multiplies bf16 with f32 accumulate;
- **grouped-query attention without materialising repeated KV**: the query
  tensor is shaped [B, T, kv_heads, q_per_kv, head_dim] and contracted
  against [B, S, kv_heads, head_dim] in one einsum, so GQA costs no extra
  HBM bandwidth;
- **explicit KV cache** as a pytree of [layers, batch, max_seq, kv_heads,
  head_dim] arrays updated with ``dynamic_update_slice`` inside the same
  scan — prefill and decode are the same jitted function at different
  sequence lengths (the serving engine in ``operator_tpu.serving`` drives
  it; the paged variant lives in ``operator_tpu.ops.paged_attention``).

Weight layout convention: all projections are stored as ``[in_features,
out_features]`` so the forward pass is always ``x @ W`` (no transposes at
run time; the HF checkpoint loader transposes once at load).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..ops.flash_prefill import (
    flash_prefill_attention,
    flash_prefill_enabled,
    flash_prefill_supported,
)
from .configs import ModelConfig
from .quant import QUANTIZED_LAYER_MATRICES, mm

Params = dict[str, Any]

#: projections that carry a bias vector when config.attention_bias (Qwen2)
_PROJ_BIAS = {"wq": "bq", "wk": "bk", "wv": "bv"}


# --------------------------------------------------------------------------
# parameter init
# --------------------------------------------------------------------------


#: the layer matrices, in the order the init key-split follows
#: (``models.family_of(config).LAYER_MATRICES``: what int8 quantises)
LAYER_MATRICES = QUANTIZED_LAYER_MATRICES


def layer_matrix_shapes(config: ModelConfig) -> dict[str, tuple[int, int, int]]:
    """Stacked shapes of the seven per-layer matrices, in the canonical
    order the init key-split follows (shared with quant.init_params_quantized
    so the two init paths can never drift structurally)."""
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
    }


def dense_init(
    key: jax.Array, shape: tuple[int, ...], fallback_fan_in: int, dtype: jnp.dtype
) -> jax.Array:
    """Normal init scaled by fan-in (the second-to-last axis)."""
    scale = (shape[-2] if len(shape) >= 2 else fallback_fan_in) ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_params(
    config: ModelConfig,
    key: jax.Array,
    dtype: jnp.dtype = jnp.bfloat16,
    *,
    layer_matrix_init: Optional[Any] = None,
) -> Params:
    """Random init with per-layer params stacked on axis 0 for lax.scan.

    ``layer_matrix_init(key, shape) -> leaf`` overrides how the seven layer
    matrices are built (default: ``dense_init``).  quant.py passes a
    per-matrix jitted init+quantize so the int8 tree never coexists with a
    full float tree — ONE assembly of the non-matrix leaves serves both.
    """
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    h = config.hidden_size
    n = config.num_layers
    if layer_matrix_init is None:
        def layer_matrix_init(k, shape):
            return dense_init(k, shape, h, dtype)

    shapes = layer_matrix_shapes(config)
    keys = jax.random.split(k_layers, len(shapes))
    layers: dict[str, Any] = {
        name: layer_matrix_init(k, shape)
        for k, (name, shape) in zip(keys, shapes.items())
    }
    layers["ln_attn"] = jnp.ones((n, h), dtype)
    layers["ln_mlp"] = jnp.ones((n, h), dtype)
    if config.attention_bias:
        # Qwen2-style q/k/v projection biases (HF Qwen2Config attention_bias);
        # zero-init so random-weight parity tests see the unbiased model
        d, kvh, qh = config.head_dim, config.num_kv_heads, config.num_heads
        layers["bq"] = jnp.zeros((n, qh * d), dtype)
        layers["bk"] = jnp.zeros((n, kvh * d), dtype)
        layers["bv"] = jnp.zeros((n, kvh * d), dtype)
    params: Params = {
        "embed": dense_init(k_embed, (config.vocab_size, h), h, dtype),
        "layers": layers,
        "ln_final": jnp.ones((h,), dtype),
    }
    if not config.tie_embeddings:
        params["lm_head"] = dense_init(k_head, (h, config.vocab_size), h, dtype)
    return params


def param_count(params: Params) -> int:
    return sum(int(p.size) for p in jax.tree_util.tree_leaves(params))


# --------------------------------------------------------------------------
# building blocks
# --------------------------------------------------------------------------


def _lora_path(
    h_in: jax.Array,  # [B, T, in]
    factors: dict[str, jax.Array],
    alpha: float,
    lora_indices: Optional[jax.Array],  # [B] adapter ids, or None
) -> jax.Array:
    """The low-rank delta ``(x @ A @ B) * alpha/r``, never expanded to a
    full matrix.  With ``lora_indices``, the factors carry a per-layer
    ADAPTER axis (``[n_adapters, in, r]`` — parallel/lora.py
    ``stack_adapters``) and each batch row applies its own adapter: the
    multi-LoRA serving path, one compiled program for the whole set."""
    a = factors["a"].astype(h_in.dtype)
    b = factors["b"].astype(h_in.dtype)
    scale = alpha / a.shape[-1]
    if lora_indices is None:
        return ((h_in @ a) @ b) * scale
    a_sel = a[lora_indices]  # [B, in, r] — rank-r gather, kilobytes per row
    b_sel = b[lora_indices]  # [B, r, out]
    low = jnp.einsum("bti,bir->btr", h_in, a_sel)
    return jnp.einsum("btr,bro->bto", low, b_sel) * scale


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """Float32 accumulation regardless of activation dtype."""
    x32 = x.astype(jnp.float32)
    variance = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    normed = x32 * jax.lax.rsqrt(variance + eps)
    return (normed * scale.astype(jnp.float32)).astype(x.dtype)


def rope_frequencies(config: ModelConfig) -> jax.Array:
    """Inverse frequencies [head_dim // 2] (HF half-rotation convention),
    with Llama-3.1-style NTK-by-parts scaling when configured: wavelengths
    beyond the original training context are slowed by ``factor``, short
    wavelengths kept, the band between linearly interpolated (matches HF
    ``rope_type: llama3``)."""
    d = config.head_dim
    exponents = jnp.arange(0, d, 2, dtype=jnp.float32) / d
    inv_freq = 1.0 / (config.rope_theta**exponents)
    scaling = config.rope_scaling
    if scaling is None:
        return inv_freq
    wavelen = 2.0 * jnp.pi / inv_freq
    low_freq_wavelen = scaling.original_max_positions / scaling.low_freq_factor
    high_freq_wavelen = scaling.original_max_positions / scaling.high_freq_factor
    scaled = inv_freq / scaling.factor
    smooth = (scaling.original_max_positions / wavelen - scaling.low_freq_factor) / (
        scaling.high_freq_factor - scaling.low_freq_factor
    )
    smoothed = (1.0 - smooth) * scaled + smooth * inv_freq
    out = jnp.where(wavelen > low_freq_wavelen, scaled, inv_freq)
    mid = (wavelen <= low_freq_wavelen) & (wavelen >= high_freq_wavelen)
    return jnp.where(mid, smoothed, out)


def apply_rope(x: jax.Array, positions: jax.Array, inv_freq: jax.Array) -> jax.Array:
    """x: [B, T, ..., head_dim]; positions: [B, T] — HF ``rotate_half``."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, T, d/2]
    cos = jnp.cos(angles)
    sin = jnp.sin(angles)
    # broadcast over any head axes between T and head_dim
    extra_axes = x.ndim - 3
    for _ in range(extra_axes):
        cos = cos[:, :, None]
        sin = sin[:, :, None]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return rotated.astype(x.dtype)


def make_causal_mask(
    q_positions: jax.Array,
    kv_positions: jax.Array,
    kv_valid: jax.Array,
    *,
    sliding_window: Optional[int] = None,
) -> jax.Array:
    """[B, Tq, S] boolean mask: causal + validity + optional sliding window.

    ``q_positions``: [B, Tq] absolute positions of the query tokens;
    ``kv_positions``: [B, S] absolute positions of cache slots;
    ``kv_valid``: [B, S] whether the slot holds a real token.
    """
    causal = kv_positions[:, None, :] <= q_positions[:, :, None]
    mask = causal & kv_valid[:, None, :]
    if sliding_window is not None:
        recent = kv_positions[:, None, :] > (q_positions[:, :, None] - sliding_window)
        mask = mask & recent
    return mask


# --------------------------------------------------------------------------
# KV cache
# --------------------------------------------------------------------------


@dataclass
class KVCache:
    """Contiguous per-layer cache (the paged variant lives in ops/)."""

    k: jax.Array  # [layers, B, max_seq, kv_heads, head_dim]
    v: jax.Array  # [layers, B, max_seq, kv_heads, head_dim]

    @classmethod
    def create(
        cls,
        config: ModelConfig,
        batch_size: int,
        max_seq_len: Optional[int] = None,
        dtype: jnp.dtype = jnp.bfloat16,
    ) -> "KVCache":
        shape = (
            config.num_layers,
            batch_size,
            max_seq_len or config.max_seq_len,
            config.num_kv_heads,
            config.head_dim,
        )
        return cls(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


jax.tree_util.register_pytree_node(
    KVCache,
    lambda cache: ((cache.k, cache.v), None),
    lambda _, children: KVCache(k=children[0], v=children[1]),
)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _attention(
    q: jax.Array,  # [B, T, QH, D]
    k: jax.Array,  # [B, S, KH, D]
    v: jax.Array,  # [B, S, KH, D]
    mask: jax.Array,  # [B, T, S] bool
    config: ModelConfig,
) -> jax.Array:
    b, t, qh, d = q.shape
    kh = config.num_kv_heads
    g = config.q_per_kv
    q_grouped = q.reshape(b, t, kh, g, d)
    # [B, KH, G, T, S] with f32 accumulation on the MXU
    scores = jnp.einsum(
        "btkgd,bskd->bkgts", q_grouped, k, preferred_element_type=jnp.float32
    )
    scores = scores * (d**-0.5)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v)
    return out.reshape(b, t, qh * d)


#: f32 score-tensor budget for one prefill attention: above this the query
#: axis is chunked (lax.scan) so the [B, KH, G, T, S] tensor never
#: materialises.  256 MB keeps an 8B prefill bucket (n=8, t=4096) well
#: inside a 16 GB v5e while staying coarse enough that XLA sees big matmuls.
_SCORE_BUDGET_BYTES = int(
    float(os.environ.get("OPERATOR_TPU_SCORE_BUDGET_MB", "256")) * 2**20
)

#: unroll factor for the layer lax.scan (1 = rolled).  Unrolling lets XLA
#: schedule/alias per-layer cache updates without the scan's stacked-ys
#: round trip — a decode-bandwidth experiment knob (ROADMAP D2);
#: compile time grows with the factor.
_LAYER_UNROLL = int(os.environ.get("OPERATOR_TPU_LAYER_UNROLL", "1"))


def _pick_q_chunk(b: int, t: int, s: int, qh: int, shards: int = 1) -> Optional[int]:
    """Largest divisor-of-t query chunk whose f32 scores fit the budget;
    None means no chunking (the dense tensor already fits).  ``shards``
    divides the effective batch: under a dp-sharded prefill each device
    holds b/shards of the score tensor, so the global shape overstates
    per-device memory by that factor."""
    rows = max(1, b // max(1, shards))
    row_bytes = rows * qh * s * 4  # score bytes per query position
    if row_bytes * t <= _SCORE_BUDGET_BYTES:
        return None
    target = max(1, _SCORE_BUDGET_BYTES // row_bytes)
    for chunk in range(min(t - 1, target), 0, -1):
        if t % chunk == 0:
            return chunk
    return 1


def _attention_chunked(
    q: jax.Array,  # [B, T, QH, D]
    k: jax.Array,  # [B, S, KH, D]
    v: jax.Array,
    q_positions: jax.Array,  # [B, T]
    kv_positions: jax.Array,  # [B, S]
    kv_valid: jax.Array,  # [B, S] bool
    config: ModelConfig,
    q_chunk: int,
) -> jax.Array:
    """Long-context prefill attention: scan over query chunks, building each
    chunk's causal/window mask on the fly — peak memory is ONE chunk's f32
    scores instead of the whole [T, S] plane (SURVEY.md §7 hard part b; the
    reference ships entire pod logs as one string, application.properties:10,
    so the rebuild's prefill must not be quadratic in HBM)."""
    b, t, qh, d = q.shape
    assert t % q_chunk == 0, (t, q_chunk)
    n_chunks = t // q_chunk
    qs = jnp.moveaxis(q.reshape(b, n_chunks, q_chunk, qh, d), 1, 0)
    qps = jnp.moveaxis(q_positions.reshape(b, n_chunks, q_chunk), 1, 0)

    def body(_, xs):
        q_c, qp_c = xs
        mask = make_causal_mask(
            qp_c, kv_positions, kv_valid, sliding_window=config.sliding_window
        )
        return None, _attention(q_c, k, v, mask, config)

    _, outs = jax.lax.scan(body, None, (qs, qps))  # [n_chunks, B, q_chunk, QH*D]
    return jnp.moveaxis(outs, 0, 1).reshape(b, t, qh * d)


def forward(
    params: Params,
    config: ModelConfig,
    token_ids: jax.Array,  # [B, T] int32
    positions: jax.Array,  # [B, T] int32 absolute positions
    cache: Optional[KVCache] = None,
    cache_offset: int | jax.Array = 0,
    attn_mask: Optional[jax.Array] = None,  # [B, T, S]; forces the dense path
    kv_valid: Optional[jax.Array] = None,  # [B, S] validity override
    q_chunk: Optional[int] = None,  # explicit prefill chunk (tests)
    score_shards: int = 1,  # devices the batch axis is sharded over
    prefill_lengths: Optional[jax.Array] = None,  # [B]; enables flash prefill
    lora: Optional[dict[str, dict[str, jax.Array]]] = None,  # parallel/lora.py
    lora_alpha: float = 16.0,
    lora_indices: Optional[jax.Array] = None,  # [B]; lora holds STACKED adapters
) -> tuple[jax.Array, Optional[KVCache]]:
    """One decoder pass.

    Without a cache: plain causal self-attention over the T tokens (training
    / parity testing).  With a cache: the T tokens are written at
    ``cache_offset`` and attend over the whole cache (prefill writes many,
    decode writes one — same code path).  ``cache_offset`` may be a scalar
    or a per-sequence ``[B]`` vector — the continuous-batching engine
    decodes slots at ragged positions (serving/engine.py).

    Long prefills chunk the query axis automatically (`_pick_q_chunk`) so
    the f32 score tensor never exceeds a fixed budget — an 8B-config
    t=4096 prefill fits a 16 GB chip.  ``kv_valid`` masks cache slots that
    hold no real token (right-padded batched prefill); passing a full
    ``attn_mask`` instead forces the dense path (legacy/test hook).

    Returns (logits [B, T, vocab] float32, updated cache or None).
    """
    inv_freq = rope_frequencies(config)
    x = jnp.take(params["embed"], token_ids, axis=0)  # [B, T, H]
    b, t, h = x.shape

    use_cache = cache is not None
    offsets = jnp.broadcast_to(jnp.asarray(cache_offset, jnp.int32), (b,))
    if use_cache:
        max_seq = cache.k.shape[2]
        kv_positions = jnp.broadcast_to(
            jnp.arange(max_seq, dtype=jnp.int32)[None], (b, max_seq)
        )
        if kv_valid is None:
            kv_valid = kv_positions < offsets[:, None] + t
    else:
        max_seq = t
        kv_positions = positions
        if kv_valid is None:
            kv_valid = jnp.ones((b, t), bool)

    # flash prefill (Pallas, gated): self-attention buckets where the kv
    # range is exactly the q range and per-row validity is `pos < length`
    # (kv_valid must be the caller's pos<lengths mask — required non-None so
    # the no-cache all-ones default can never silently diverge from the
    # kernel's length masking).  score_shards>1 means the bucket is sharded
    # over a mesh: pallas_call has no SPMD rule here, so flash stays off.
    use_flash = (
        prefill_lengths is not None
        and kv_valid is not None
        and attn_mask is None
        and score_shards == 1
        and flash_prefill_enabled()
        and flash_prefill_supported(t, max_seq, cache_offset)
    )
    if use_flash:
        q_chunk = None
    elif attn_mask is None:
        q_chunk = q_chunk or _pick_q_chunk(
            b, t, max_seq, config.num_heads, shards=score_shards
        )
        if q_chunk is None:
            attn_mask = make_causal_mask(
                positions, kv_positions, kv_valid,
                sliding_window=config.sliding_window,
            )
    else:
        q_chunk = None  # explicit mask: dense semantics the mask encodes

    layers = params["layers"]

    def layer_step(carry: jax.Array, scanned: dict[str, jax.Array]):
        x = carry
        weights, layer_cache = scanned["w"], scanned.get("cache")
        layer_lora = scanned.get("lora")

        def proj(h_in: jax.Array, name: str) -> jax.Array:
            """x @ W plus the low-rank LoRA path x @ A @ B — the factors
            are never expanded to a full delta matrix, so training memory
            stays rank-r (parallel/lora.py)."""
            y = mm(h_in, weights[name])
            bias = _PROJ_BIAS.get(name)
            if bias is not None and bias in weights:
                y = y + weights[bias].astype(y.dtype)
            if layer_lora is not None and name in layer_lora:
                y = y + _lora_path(
                    h_in, layer_lora[name], lora_alpha, lora_indices
                )
            return y

        # -- attention ---------------------------------------------------
        attn_in = rms_norm(x, weights["ln_attn"], config.rms_norm_eps)
        q = proj(attn_in, "wq").reshape(b, t, config.num_heads, config.head_dim)
        k = proj(attn_in, "wk").reshape(b, t, config.num_kv_heads, config.head_dim)
        v = proj(attn_in, "wv").reshape(b, t, config.num_kv_heads, config.head_dim)
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)
        if layer_cache is not None:
            # per-sequence write offsets (ragged continuous batching)
            write = jax.vmap(
                lambda buf, new, off: jax.lax.dynamic_update_slice(
                    buf, new.astype(buf.dtype), (off, 0, 0)
                )
            )
            k_all = write(layer_cache["k"], k, offsets)
            v_all = write(layer_cache["v"], v, offsets)
            new_cache = {"k": k_all, "v": v_all}
        else:
            k_all, v_all = k, v
            new_cache = None
        k_att = k_all.astype(q.dtype)
        v_att = v_all.astype(q.dtype)
        if use_flash:
            attn = flash_prefill_attention(
                q, k_att, v_att, prefill_lengths,
                sliding_window=config.sliding_window,
            )
        elif q_chunk is not None:
            attn = _attention_chunked(
                q, k_att, v_att, positions, kv_positions, kv_valid, config, q_chunk
            )
        else:
            attn = _attention(q, k_att, v_att, attn_mask, config)
        x = x + proj(attn, "wo")
        # -- mlp ----------------------------------------------------------
        mlp_in = rms_norm(x, weights["ln_mlp"], config.rms_norm_eps)
        gate = jax.nn.silu(proj(mlp_in, "w_gate"))
        up = proj(mlp_in, "w_up")
        x = x + proj(gate * up, "w_down")
        return x, new_cache

    if use_cache:
        scanned_in = {"w": layers, "cache": {"k": cache.k, "v": cache.v}}
        if lora is not None:
            scanned_in["lora"] = lora
        x, cache_out = jax.lax.scan(
            lambda carry, s: layer_step(carry, s), x, scanned_in,
            unroll=_LAYER_UNROLL,
        )
        new_cache = KVCache(k=cache_out["k"], v=cache_out["v"])
    else:
        scanned_in = {"w": layers}
        if lora is not None:
            scanned_in["lora"] = lora
        x, _ = jax.lax.scan(
            lambda carry, s: (layer_step(carry, s)[0], None), x, scanned_in,
            unroll=_LAYER_UNROLL,
        )
        new_cache = None

    x = rms_norm(x, params["ln_final"], config.rms_norm_eps)
    head = params["embed"].T if config.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bth,hv->btv", x, head, preferred_element_type=jnp.float32)
    return logits, new_cache


def mixed_layer(config: ModelConfig, step: Any):
    """The continuous scheduler's layer body for this family
    (``serving/sched/mixed.py``): projections with the optional q/k/v
    bias, the step's shared attention (``step.attend``), the gated MLP.
    The carry is ``(x, pools, None)``: the residual stream and the whole
    KV pools, which ``step.attend`` writes and reads at ``scanned["layer"]``;
    no recurrent state."""

    def layer_step(carry, scanned):
        x, pools, recurrent = carry
        weights = scanned["w"]
        attn_in = rms_norm(x, weights["ln_attn"], config.rms_norm_eps)

        def proj(h_in, name):
            y = mm(h_in, weights[name])
            bias = _PROJ_BIAS.get(name)
            if bias is not None and bias in weights:
                y = y + weights[bias].astype(y.dtype)
            return y

        attn, pools = step.attend(
            proj(attn_in, "wq"), proj(attn_in, "wk"), proj(attn_in, "wv"),
            pools, scanned["layer"],
        )
        x = x + proj(attn, "wo")
        with jax.named_scope("mlp"):
            mlp_in = rms_norm(x, weights["ln_mlp"], config.rms_norm_eps)
            gate = jax.nn.silu(proj(mlp_in, "w_gate"))
            up = proj(mlp_in, "w_up")
            x = x + proj(gate * up, "w_down")
        return (x, pools, recurrent), None

    return layer_step


def decode_step(
    params: Params,
    config: ModelConfig,
    token_ids: jax.Array,  # [B, 1]
    positions: jax.Array,  # [B, 1]
    cache: KVCache,
    cache_offset: jax.Array,
    lora: Optional[dict[str, dict[str, jax.Array]]] = None,
    lora_alpha: float = 16.0,
    lora_indices: Optional[jax.Array] = None,
) -> tuple[jax.Array, KVCache]:
    """Single-token decode (jit once, call per step)."""
    logits, new_cache = forward(
        params, config, token_ids, positions, cache=cache, cache_offset=cache_offset,
        lora=lora, lora_alpha=lora_alpha, lora_indices=lora_indices,
    )
    return logits[:, -1, :], new_cache


def decode_step_paged(
    params: Params,
    config: ModelConfig,
    token_ids: jax.Array,  # [B, 1]
    paged: "PagedKVCache",
    lora: Optional[dict[str, dict[str, jax.Array]]] = None,  # stacked adapters
    lora_alpha: float = 16.0,
    lora_indices: Optional[jax.Array] = None,  # [B] adapter id per slot
    mesh: Optional[jax.sharding.Mesh] = None,  # the caller's serving mesh
) -> tuple[jax.Array, "PagedKVCache"]:
    """Single-token decode over a paged KV cache (ops/paged_attention.py).

    Each sequence appends at its own ``lengths[b]`` position (the page
    table maps it to a page/slot) and attends over exactly its own pages —
    the ragged-batch decode of SURVEY.md §7 hard part (c).  Sliding-window
    configs (Mistral) mask to the last ``sliding_window`` tokens, matching
    the contiguous path's make_causal_mask semantics.

    Returns (last-token logits [B, vocab] float32, cache with lengths+1).
    """
    from ..ops.paged_attention import PagedKVCache, paged_attention, write_tokens

    inv_freq = rope_frequencies(config)
    b = token_ids.shape[0]
    positions = paged.lengths[:, None]  # [B, 1] append position
    x = jnp.take(params["embed"], token_ids, axis=0)  # [B, 1, H]
    new_lengths = paged.lengths + 1

    def layer_step(carry: jax.Array, scanned: dict[str, jax.Array]):
        x = carry
        weights = scanned["w"]
        layer_lora = scanned.get("lora")
        attn_in = rms_norm(x, weights["ln_attn"], config.rms_norm_eps)

        def proj(h_in: jax.Array, name: str) -> jax.Array:
            y = mm(h_in, weights[name])
            bias = _PROJ_BIAS.get(name)
            if bias is not None and bias in weights:
                y = y + weights[bias].astype(y.dtype)
            if layer_lora is not None and name in layer_lora:
                y = y + _lora_path(
                    h_in, layer_lora[name], lora_alpha, lora_indices
                )
            return y

        q = proj(attn_in, "wq").reshape(b, 1, config.num_heads, config.head_dim)
        k = proj(attn_in, "wk").reshape(b, 1, config.num_kv_heads, config.head_dim)
        v = proj(attn_in, "wv").reshape(b, 1, config.num_kv_heads, config.head_dim)
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)
        k_pages = write_tokens(scanned["k"], paged.page_table, k, paged.lengths)
        v_pages = write_tokens(scanned["v"], paged.page_table, v, paged.lengths)
        attn = paged_attention(
            q[:, 0].astype(k_pages.dtype), k_pages, v_pages,
            paged.page_table, new_lengths,
            sliding_window=config.sliding_window, mesh=mesh,
        )  # [B, QH, D]
        x = x + proj(attn.astype(x.dtype).reshape(b, 1, -1), "wo")
        mlp_in = rms_norm(x, weights["ln_mlp"], config.rms_norm_eps)
        gate = jax.nn.silu(proj(mlp_in, "w_gate"))
        up = proj(mlp_in, "w_up")
        x = x + proj(gate * up, "w_down")
        return x, {"k": k_pages, "v": v_pages}

    scanned_in = {"w": params["layers"], "k": paged.k_pages, "v": paged.v_pages}
    if lora is not None:
        scanned_in["lora"] = lora
    x, pages_out = jax.lax.scan(layer_step, x, scanned_in, unroll=_LAYER_UNROLL)

    x = rms_norm(x, params["ln_final"], config.rms_norm_eps)
    head = params["embed"].T if config.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bth,hv->btv", x, head, preferred_element_type=jnp.float32)
    new_cache = PagedKVCache(
        k_pages=pages_out["k"], v_pages=pages_out["v"],
        page_table=paged.page_table, lengths=new_lengths,
    )
    return logits[:, -1, :], new_cache
