"""Flash prefill attention: Pallas kernel for the batched-prefill forward.

The prefill bucket self-attends over its own right-padded tokens (the
serving engine's mini-cache, serving/engine.py): q = kv, positions
``0..T``, per-row validity ``pos < lengths[b]``.  The chunked-XLA path
(models/llama.py ``_attention_chunked``) already bounds score memory; this
kernel additionally:

- never materialises scores in HBM at all (VMEM running max/sum/acc);
- skips kv blocks the causal mask zeroes (the j > q-block blocks) AND
  blocks past the row's valid length — the BlockSpec-free in-kernel walk
  DMAs only what contributes (same design as ops/paged_attention.py v2);
- with a sliding window, starts each q block's walk at the first
  in-window kv block.

Gated off by default (OPERATOR_TPU_FLASH_PREFILL=1 enables) until
validated on hardware; the dense/chunked XLA paths remain the oracle.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

from ._flash_common import finalize, init_state, update_state

_LANE = 128
_NEG_INF = -1e30


def flash_prefill_enabled() -> bool:
    return os.environ.get("OPERATOR_TPU_FLASH_PREFILL", "0").strip() == "1"


def flash_prefill_supported(t: int, s: int, cache_offset) -> bool:
    """Trace-time gate: self-attention prefill shapes only — kv range is
    exactly the q range (mini-cache, offset 0) and T divides into blocks."""
    if t != s or t < 2:
        return False
    # graftlint: disable=GL002 reason=the isinstance guard short-circuits before any tracer comparison; a traced cache_offset yields False without concretising
    if not isinstance(cache_offset, int) or cache_offset != 0:
        return False
    q_block = min(128, t)
    return t % q_block == 0


def flash_prefill_reference(
    q: jax.Array,  # [B, T, QH, D]
    k: jax.Array,  # [B, T, KH, D]
    v: jax.Array,
    lengths: jax.Array,  # [B]
    sliding_window: Optional[int] = None,
) -> jax.Array:
    """Dense oracle (same math as models/llama._attention + its mask)."""
    b, t, qh, d = q.shape
    kh = k.shape[2]
    g = qh // kh
    positions = jnp.arange(t, dtype=jnp.int32)
    causal = positions[None, :] <= positions[:, None]  # [T, S]
    valid = positions[None, None, :] < lengths[:, None, None]  # [B, 1, S]
    mask = causal[None] & valid
    if sliding_window is not None:
        mask = mask & (positions[None, :] > positions[:, None] - sliding_window)[None]
    q_grouped = q.reshape(b, t, kh, g, d)
    scores = jnp.einsum(
        "btkgd,bskd->bkgts", q_grouped, k, preferred_element_type=jnp.float32
    ) * (d**-0.5)
    scores = jnp.where(mask[:, None, None, :, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v)
    return out.reshape(b, t, qh * d).astype(q.dtype)


def _flash_prefill_kernel(
    # scalar prefetch
    len_ref,  # [B] int32 (SMEM)
    # blocks
    q_ref,  # [1, q_block, 1, G, D] (VMEM)
    k_hbm,  # [B, KH, S, D] (HBM) — head-major: the per-head DMA below
    # slices a FULL head plane, so the tiled trailing dims (S, D) keep
    # their extents and bf16's (8,128)x2 tiling stays aligned (a [B, S,
    # KH, D] layout put KH in the tiled pair and its size-1 slice failed
    # Mosaic lowering for bf16 — caught by scripts/aot_tpu_check.py)
    v_hbm,
    out_ref,  # [1, q_block, 1, G, D] f32
    # scratch
    k_buf,  # [2, kv_block, D] VMEM double buffer
    v_buf,
    sem,  # DMA semaphores [2, 2]
    m_scratch,  # [rows, LANE] f32
    l_scratch,
    acc_scratch,  # [rows, D] f32
    *,
    q_block: int,
    kv_block: int,
    g: int,
    scale: float,
    window: Optional[int] = None,
):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    h = pl.program_id(1)
    i = pl.program_id(2)
    length = len_ref[b]
    rows = q_block * g

    # kv range this q block can touch: causal upper bound AND validity
    high = jnp.minimum(length, (i + 1) * q_block)
    nblocks = pl.cdiv(high, kv_block)  # 0 when the whole block is padding
    if window is not None:
        # earliest kv any row here can see: q_lo - window + 1
        first = jnp.maximum(i * q_block - window + 1, 0) // kv_block
    else:
        first = 0

    init_state(m_scratch, l_scratch, acc_scratch)

    def dma(slot, j):
        return (
            pltpu.make_async_copy(
                k_hbm.at[b, h, pl.ds(j * kv_block, kv_block)],
                k_buf.at[slot], sem.at[slot, 0],
            ),
            pltpu.make_async_copy(
                v_hbm.at[b, h, pl.ds(j * kv_block, kv_block)],
                v_buf.at[slot], sem.at[slot, 1],
            ),
        )

    @pl.when(nblocks > first)
    def _prologue():
        for copy in dma(first % 2, first):
            copy.start()

    q = q_ref[0, :, 0].astype(jnp.float32).reshape(rows, -1)  # [rows, D]
    # row r serves q position i*q_block + r // g
    q_pos = i * q_block + jax.lax.broadcasted_iota(
        jnp.int32, (rows, kv_block), 0
    ) // g

    def body(j, _):
        slot = j % 2

        @pl.when(j + 1 < nblocks)
        def _prefetch_next():
            for copy in dma((j + 1) % 2, j + 1):
                copy.start()

        for copy in dma(slot, j):
            copy.wait()

        k = k_buf[slot].astype(jnp.float32)  # [kv_block, D]
        v = v_buf[slot].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [rows, kv_block]

        kv_pos = j * kv_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = (kv_pos <= q_pos) & (kv_pos < length)
        if window is not None:
            mask = mask & (kv_pos > q_pos - window)
        s = jnp.where(mask, s, _NEG_INF)

        update_state(
            m_scratch, l_scratch, acc_scratch, s,
            lambda p: jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ),
        )
        return 0

    jax.lax.fori_loop(first, nblocks, body, 0)
    out = finalize(l_scratch, acc_scratch)  # [rows, D]
    out_ref[0, :, 0] = out.reshape(q_block, g, -1).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("sliding_window", "q_block", "kv_block", "interpret")
)
def _flash_prefill_pallas(
    q: jax.Array,  # [B, T, QH, D]
    k: jax.Array,  # [B, T, KH, D]
    v: jax.Array,
    lengths: jax.Array,  # [B]
    *,
    sliding_window: Optional[int] = None,
    q_block: int = 128,
    kv_block: int = 128,
    interpret: bool = False,
) -> jax.Array:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, qh, d = q.shape
    kh = k.shape[2]
    g = qh // kh
    q_block = min(q_block, t)
    kv_block = min(kv_block, t)
    assert t % q_block == 0 and t % kv_block == 0, (t, q_block, kv_block)
    rows = q_block * g
    scale = d**-0.5

    kernel = functools.partial(
        _flash_prefill_kernel,
        q_block=q_block, kv_block=kv_block, g=g, scale=scale,
        window=sliding_window,
    )
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kh, t // q_block),
        in_specs=[
            pl.BlockSpec(
                (1, q_block, 1, g, d), lambda b, h, i, ln: (b, i, h, 0, 0)
            ),
            any_space,
            any_space,
        ],
        out_specs=pl.BlockSpec(
            (1, q_block, 1, g, d), lambda b, h, i, ln: (b, i, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((2, kv_block, d), k.dtype),
            pltpu.VMEM((2, kv_block, d), v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((rows, _LANE), jnp.float32),
            pltpu.VMEM((rows, _LANE), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
        ],
    )
    q5 = q.reshape(b, t, kh, g, d)
    # head-major K/V: the kernel DMAs one head's [kv_block, D] plane per
    # grid step, and with [B, KH, S, D] that slice keeps the tiled (S, D)
    # pair at full alignment for bf16 (see _flash_prefill_kernel)
    k_hm = jnp.swapaxes(k, 1, 2)
    v_hm = jnp.swapaxes(v, 1, 2)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, t, kh, g, d), jnp.float32),
        interpret=interpret,
    )(lengths, q5, k_hm, v_hm)
    return out.reshape(b, t, qh * d).astype(q.dtype)


def flash_prefill_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lengths: jax.Array,
    sliding_window: Optional[int] = None,
) -> jax.Array:
    """Dispatch: Pallas kernel on TPU, dense oracle elsewhere."""
    from ._dispatch import on_tpu

    if on_tpu():
        return _flash_prefill_pallas(
            q, k, v, lengths, sliding_window=sliding_window
        )
    return flash_prefill_reference(q, k, v, lengths, sliding_window=sliding_window)
