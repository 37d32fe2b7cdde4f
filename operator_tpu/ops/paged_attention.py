"""Paged KV-cache attention for batched variable-length decode.

The serving engine batches up to 32 concurrent failure-event explanations
(BASELINE config 4).  Their sequence lengths are ragged — a contiguous
``[B, max_seq]`` cache would reserve worst-case HBM for every slot, which
is exactly what kills batch size at 8B scale on v5e (SURVEY.md §7 hard
part c).  Instead KV lives in fixed-size pages:

    k_pages, v_pages  [num_pages, page_size, kv_heads, head_dim]
    page_table        [batch, pages_per_seq] int32  (page ids per sequence)
    lengths           [batch] int32                 (tokens currently held)

The Pallas kernel walks each sequence's page list with the page table as
*scalar prefetch* (the table is read on the scalar core before the grid
step, steering the DMA of exactly the pages the sequence owns — no gather
materialisation), keeping a flash-attention style running
(max, sum, acc) in VMEM.  Grouped-query heads are expanded in-kernel, so
repeated KV never hits HBM (same trick as models/llama.py's einsum).

The dense reference gathers pages into a contiguous cache and runs masked
softmax attention — the oracle for parity tests and the CPU path.
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

_LANE = 128
_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# paged cache container + host-free update ops
# ---------------------------------------------------------------------------


@dataclass
class PagedKVCache:
    """Per-layer paged KV storage, the planes stacked on axis 0: one a
    layer, or one a pass and layer (``pass * layers + layer``) for a model
    whose stack runs several times a token (``config.kv_planes``).  A page
    id names one page in every plane."""

    k_pages: jax.Array  # [planes, num_pages, page_size, kv_heads, head_dim]
    #: None for a latent cache, which holds no values
    v_pages: Optional[jax.Array]
    page_table: jax.Array  # [batch, pages_per_seq] int32
    lengths: jax.Array  # [batch] int32
    #: recurrent state beside the pages, for a model that has it
    #: (``config.recurrent_state``; ops/ssm_scan.py, ops/gated_delta.py):
    #: per recurrent layer and SLOT, not per page -- allocated, donated,
    #: reset and freed with the pool, because it is part of the one cache
    #: object.  None for the others: an absent child of the pytree, so
    #: their programs see no change.  Shapes: the config's
    #: ``recurrent_state_shapes``
    ssm_state: Optional[jax.Array] = None  # [layers, batch, ...] f32
    conv_state: Optional[jax.Array] = None  # [layers, batch, kernel - 1, channels]
    #: a LATENT cache (multi-head latent attention: ``config.latent_dims``,
    #: ops/latent_attention.py owns the layout): ``k_pages`` holds one
    #: latent row a token ``[planes, pages, page_size, rank]``, this the
    #: rotary keys ``g`` tokens to a 128-lane row, and ``v_pages`` is None.
    #: None for every other model, an absent child of the pytree
    rope_pages: Optional[jax.Array] = None  # [planes, pages, page_size / g, 128]

    @property
    def page_size(self) -> int:
        return self.k_pages.shape[2]

    @staticmethod
    def recurrent_shapes(config, batch_size: int) -> Optional[tuple]:
        """``(ssm_state shape, conv_state shape)`` for ``config``, or None
        for a model without recurrent state: the config says what its
        layers carry (``recurrent_state_shapes``)."""
        if not getattr(config, "recurrent_state", False):
            return None
        return config.recurrent_state_shapes(batch_size)

    @classmethod
    def create(
        cls,
        num_layers: int,
        num_pages: int,
        page_size: int,
        kv_heads: int,
        head_dim: int,
        batch_size: int,
        pages_per_seq: int,
        dtype: jnp.dtype = jnp.bfloat16,
        recurrent: Optional[tuple] = None,
        latent: Optional[tuple] = None,
    ) -> "PagedKVCache":
        """``num_layers`` is the pool's planes, the model's ``kv_planes``
        (a looped model's passes x layers).  ``kv_heads`` is the model's; a
        page holds ``ragged_attention.pool_heads`` of them (30 held as the
        chip's tile holds them, 32).  ``recurrent`` is
        :meth:`recurrent_shapes`' pair: the state is
        float32 (a sum over every token the row has seen), the conv tail
        the activations' ``dtype``.  ``latent`` is a latent model's
        ``config.latent_dims``, (latent width, rotary key width): the pool
        is then a latent one (``kv_heads`` and ``head_dim``
        unused)."""
        from .ragged_attention import pool_heads

        rope = None
        if latent is not None:
            from .latent_attention import pool_shapes

            shape, rope_shape = pool_shapes(num_layers, num_pages, page_size, *latent)
            rope = jnp.zeros(rope_shape, dtype)
        else:
            shape = (num_layers, num_pages, page_size, pool_heads(kv_heads), head_dim)
        return cls(
            k_pages=jnp.zeros(shape, dtype),
            v_pages=None if latent is not None else jnp.zeros(shape, dtype),
            page_table=jnp.zeros((batch_size, pages_per_seq), jnp.int32),
            lengths=jnp.zeros((batch_size,), jnp.int32),
            ssm_state=None if recurrent is None else jnp.zeros(recurrent[0], jnp.float32),
            conv_state=None if recurrent is None else jnp.zeros(recurrent[1], dtype),
            rope_pages=rope,
        )


jax.tree_util.register_pytree_node(
    PagedKVCache,
    lambda c: (
        (c.k_pages, c.v_pages, c.page_table, c.lengths, c.ssm_state, c.conv_state,
         c.rope_pages),
        None,
    ),
    lambda _, ch: PagedKVCache(*ch),
)


def write_tokens(
    pages: jax.Array,  # [num_pages, page_size, KH, D] (single layer)
    page_table: jax.Array,  # [B, pages_per_seq]
    new: jax.Array,  # [B, T, KH, D] tokens to store
    start: jax.Array,  # [B] int32 position of new[:, 0]
    valid_len: Optional[jax.Array] = None,  # [B] tokens of new[] that are real
) -> jax.Array:
    """Scatter T new tokens per sequence into their pages (prefill or
    decode append — decode is T=1, start=lengths).

    Rows past ``valid_len`` (prefill padding) are redirected to page 0,
    which the allocator reserves as a trash page (serving/engine.py) — a
    padded row must never land in another sequence's pages.
    """
    b, t = new.shape[0], new.shape[1]
    page_size = pages.shape[1]
    positions = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]  # [B, T]
    page_ids = jnp.take_along_axis(
        page_table, positions // page_size, axis=1
    )  # [B, T]
    slots = positions % page_size
    if valid_len is not None:
        valid = jnp.arange(t, dtype=jnp.int32)[None, :] < valid_len[:, None]
        page_ids = jnp.where(valid, page_ids, 0)
        slots = jnp.where(valid, slots, 0)
    return pages.at[page_ids, slots].set(new.astype(pages.dtype))


# ---------------------------------------------------------------------------
# dense reference
# ---------------------------------------------------------------------------


def paged_attention_reference(
    q: jax.Array,  # [B, QH, D] current-token queries (RoPE applied)
    k_pages: jax.Array,  # [num_pages, page_size, KH, D] (single layer)
    v_pages: jax.Array,
    page_table: jax.Array,  # [B, pages_per_seq]
    lengths: jax.Array,  # [B] number of valid tokens (incl. current)
    sliding_window: Optional[int] = None,
) -> jax.Array:
    """Gather-then-attend oracle.  Returns [B, QH, D] in q.dtype."""
    b, qh, d = q.shape
    kh = k_pages.shape[2]
    g = qh // kh
    page_size = k_pages.shape[1]
    max_seq = page_table.shape[1] * page_size

    # [B, S, KH, D] contiguous gather of each sequence's pages
    k = k_pages[page_table].reshape(b, max_seq, kh, d)
    v = v_pages[page_table].reshape(b, max_seq, kh, d)

    q_grouped = q.reshape(b, kh, g, d)
    scores = jnp.einsum(
        "bkgd,bskd->bkgs", q_grouped, k, preferred_element_type=jnp.float32
    ) * (d**-0.5)
    positions = jnp.arange(max_seq, dtype=jnp.int32)[None, :]
    valid = positions < lengths[:, None]
    if sliding_window is not None:
        # the decoding token (position lengths-1) attends to the last
        # `window` tokens: positions >= lengths - window (same semantics
        # as make_causal_mask's `recent` term in models/llama.py)
        valid = valid & (positions >= lengths[:, None] - sliding_window)
    scores = jnp.where(valid[:, None, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, v)
    return out.reshape(b, qh, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

from ._flash_common import finalize, init_state, update_state  # noqa: E402


def _gqa_scores(q, k, kv_heads: int, q_per_kv: int) -> jax.Array:
    """[QH, D] q x [page, KH, D] k -> [QH, page] scores; GQA expanded via
    per-kv-head dots so repeated KV never materialises."""
    parts = []
    for h in range(kv_heads):
        q_h = q[h * q_per_kv : (h + 1) * q_per_kv]  # [G, D]
        k_h = k[:, h, :].astype(jnp.float32)  # [page, D]
        parts.append(
            jax.lax.dot_general(
                q_h, k_h, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        )
    return jnp.concatenate(parts, axis=0)


def _gqa_values(p, v, kv_heads: int, q_per_kv: int) -> jax.Array:
    """[QH, page] probabilities x [page, KH, D] v -> [QH, D]."""
    parts = []
    for h in range(kv_heads):
        p_h = p[h * q_per_kv : (h + 1) * q_per_kv]  # [G, page]
        v_h = v[:, h, :].astype(jnp.float32)  # [page, D]
        parts.append(
            jax.lax.dot_general(
                p_h, v_h, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        )
    return jnp.concatenate(parts, axis=0)


def _paged_attn_kernel(
    # scalar prefetch
    pt_ref,  # [B, pages_per_seq] int32 (SMEM)
    len_ref,  # [B] int32 (SMEM)
    # blocks
    q_ref,  # [1, QH, D]
    k_ref,  # [1, page_size, KH, D] — the page pt[b, j]
    v_ref,
    out_ref,  # [1, QH, D] f32
    # scratch
    m_scratch,  # [QH, LANE] f32 running max (lanes duplicated)
    l_scratch,  # [QH, LANE] f32 running denominator
    acc_scratch,  # [QH, D] f32
    *,
    kv_heads: int,
    q_per_kv: int,
    page_size: int,
    scale: float,
    window: Optional[int] = None,
):
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    j = pl.program_id(1)
    num_pages = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        init_state(m_scratch, l_scratch, acc_scratch)

    seq_len = len_ref[b]

    # only touch pages that hold live tokens — and, with a sliding window,
    # only pages overlapping [seq_len - window, seq_len)
    live = j * page_size < seq_len
    if window is not None:
        window_lo = jnp.maximum(seq_len - window, 0)
        live = jnp.logical_and(live, (j + 1) * page_size > window_lo)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [QH, D]
        k = k_ref[0]  # [page, KH, D]
        v = v_ref[0]

        s = _gqa_scores(q, k, kv_heads, q_per_kv) * scale  # [QH, page]
        pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < seq_len, s, _NEG_INF)
        if window is not None:
            s = jnp.where(pos >= window_lo, s, _NEG_INF)

        update_state(
            m_scratch, l_scratch, acc_scratch, s,
            lambda p: _gqa_values(p, v, kv_heads, q_per_kv),
        )

    @pl.when(j == num_pages - 1)
    def _finish():
        out_ref[0] = finalize(l_scratch, acc_scratch).astype(out_ref.dtype)


def _paged_attn_kernel_v2(
    # scalar prefetch
    pt_ref,  # [B, pages_per_seq] int32 (SMEM)
    len_ref,  # [B] int32 (SMEM)
    # blocks
    q_ref,  # [1, QH, D] (VMEM)
    k_hbm,  # [num_pages, page_size, KH, D] (stays in HBM)
    v_hbm,
    out_ref,  # [1, QH, D] f32
    # scratch
    k_buf,  # [2, page_size, KH, D] VMEM double buffer
    v_buf,
    sem,  # DMA semaphores [2, 2]
    m_scratch,  # [QH, LANE] f32
    l_scratch,
    acc_scratch,  # [QH, D] f32
    *,
    kv_heads: int,
    q_per_kv: int,
    page_size: int,
    scale: float,
    window: Optional[int] = None,
):
    """Decode paged attention, one grid step per sequence.

    The v1 kernel's grid was (B, pages_per_seq): every page slot cost a
    grid step and a BlockSpec DMA whether or not it held live tokens
    (the index map always fetches).  Here the page walk happens INSIDE the
    kernel with manual double-buffered DMAs steered by the scalar-prefetched
    page table, so exactly ceil(len/page) pages move from HBM — a sequence
    at length 100 with a 4096-token table reads 2 pages, not 64 — and page
    i+1's DMA overlaps page i's flash-attention update.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    seq_len = len_ref[b]
    num_live = pl.cdiv(seq_len, page_size)
    first = 0
    if window is not None:
        window_lo = jnp.maximum(seq_len - window, 0)
        first = window_lo // page_size

    init_state(m_scratch, l_scratch, acc_scratch)

    def dma(slot, j):
        return (
            pltpu.make_async_copy(k_hbm.at[pt_ref[b, j]], k_buf.at[slot], sem.at[slot, 0]),
            pltpu.make_async_copy(v_hbm.at[pt_ref[b, j]], v_buf.at[slot], sem.at[slot, 1]),
        )

    @pl.when(num_live > first)
    def _prologue():
        for copy in dma(first % 2, first):
            copy.start()

    def body(j, _):
        slot = j % 2

        @pl.when(j + 1 < num_live)
        def _prefetch_next():
            for copy in dma((j + 1) % 2, j + 1):
                copy.start()

        for copy in dma(slot, j):
            copy.wait()

        q = q_ref[0].astype(jnp.float32)  # [QH, D]
        k = k_buf[slot]  # [page, KH, D]
        v = v_buf[slot]

        s = _gqa_scores(q, k, kv_heads, q_per_kv) * scale  # [QH, page]
        pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < seq_len, s, _NEG_INF)
        if window is not None:
            s = jnp.where(pos >= window_lo, s, _NEG_INF)

        update_state(
            m_scratch, l_scratch, acc_scratch, s,
            lambda p: _gqa_values(p, v, kv_heads, q_per_kv),
        )
        return 0

    jax.lax.fori_loop(first, num_live, body, 0)
    out_ref[0] = finalize(l_scratch, acc_scratch).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "sliding_window"))
def _paged_attention_pallas_v2(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    *,
    interpret: bool = False,
    sliding_window: Optional[int] = None,
) -> jax.Array:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, qh, d = q.shape
    _, page_size, kh, _ = k_pages.shape
    scale = d**-0.5

    kernel = functools.partial(
        _paged_attn_kernel_v2,
        kv_heads=kh,
        q_per_kv=qh // kh,
        page_size=page_size,
        scale=scale,
        window=sliding_window,
    )
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, qh, d), lambda b, pt, ln: (b, 0, 0)),
            any_space,
            any_space,
        ],
        out_specs=pl.BlockSpec((1, qh, d), lambda b, pt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, page_size, kh, d), k_pages.dtype),
            pltpu.VMEM((2, page_size, kh, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((qh, _LANE), jnp.float32),
            pltpu.VMEM((qh, _LANE), jnp.float32),
            pltpu.VMEM((qh, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, qh, d), jnp.float32),
        interpret=interpret,
    )(page_table, lengths, q, k_pages, v_pages)
    return out.astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "sliding_window"))
def _paged_attention_pallas(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    *,
    interpret: bool = False,
    sliding_window: Optional[int] = None,
) -> jax.Array:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, qh, d = q.shape
    _, page_size, kh, _ = k_pages.shape
    pages_per_seq = page_table.shape[1]
    scale = d**-0.5

    kernel = functools.partial(
        _paged_attn_kernel,
        kv_heads=kh,
        q_per_kv=qh // kh,
        page_size=page_size,
        scale=scale,
        window=sliding_window,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pages_per_seq),
        in_specs=[
            pl.BlockSpec((1, qh, d), lambda b, j, pt, ln: (b, 0, 0)),
            pl.BlockSpec(
                (1, page_size, kh, d), lambda b, j, pt, ln: (pt[b, j], 0, 0, 0)
            ),
            pl.BlockSpec(
                (1, page_size, kh, d), lambda b, j, pt, ln: (pt[b, j], 0, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, qh, d), lambda b, j, pt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((qh, _LANE), jnp.float32),
            pltpu.VMEM((qh, _LANE), jnp.float32),
            pltpu.VMEM((qh, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, qh, d), jnp.float32),
        interpret=interpret,
    )(page_table, lengths, q, k_pages, v_pages)
    return out.astype(q.dtype)


def _kernel_version() -> str:
    """Which Pallas kernel serves decode on TPU: "v1" (BlockSpec page grid,
    every page slot DMA'd) or "v2" (in-kernel double-buffered DMA of live
    pages only).  v1 stays default until v2 is validated on hardware.  Read
    when a program is TRACED — already-compiled buckets keep whatever kernel
    they were built with, so set the env before the process starts rather
    than flipping it mid-flight.  Unknown values raise rather than silently
    benching the wrong kernel."""
    version = os.environ.get("OPERATOR_TPU_PAGED_KERNEL", "v1").strip().lower()
    if version not in ("v1", "v2"):
        raise ValueError(
            f"OPERATOR_TPU_PAGED_KERNEL={version!r}: expected 'v1' or 'v2'"
        )
    return version


def paged_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    sliding_window: Optional[int] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
) -> jax.Array:
    """Dispatch: Pallas kernel on TPU, dense reference elsewhere.

    ``mesh`` is the serving mesh when the caller's program is sharded
    (parallel/mesh.py axes).  GSPMD cannot partition a Mosaic kernel, so
    on a TPU the kernel then runs inside a ``shard_map``: it is
    independent per kv head and per row, so heads go on ``tp`` (q-head
    blocks and their kv heads are contiguous, hence co-located) and rows
    on ``dp x fsdp``; the page pool is replicated over the data axes
    (``paged_cache_specs``)."""
    from ._dispatch import on_tpu

    if not on_tpu():
        return paged_attention_reference(
            q, k_pages, v_pages, page_table, lengths, sliding_window=sliding_window
        )
    impl = functools.partial(
        _paged_attention_pallas_v2
        if _kernel_version() == "v2"
        else _paged_attention_pallas,
        sliding_window=sliding_window,
    )
    if mesh is None:
        return impl(q, k_pages, v_pages, page_table, lengths)
    from jax.sharding import PartitionSpec as P

    rows, pages = ("dp", "fsdp"), P(None, None, "tp", None)
    return jax.shard_map(
        impl,
        mesh=mesh,
        in_specs=(P(rows, "tp", None), pages, pages, P(rows, None), P(rows)),
        out_specs=P(rows, "tp", None),
        # pallas_call has no replication rule; nothing here is replicated
        # over tp on the way out, so there is nothing for the check to prove
        check_vma=False,
    )(q, k_pages, v_pages, page_table, lengths)
