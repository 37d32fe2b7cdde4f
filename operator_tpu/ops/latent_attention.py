"""Absorbed multi-head latent attention over a paged latent cache: one
kernel for decode rows and prompt chunks alike (DeepSeek-V2's MLA,
``models/deepseek_v2.py``).

A token's keys and values are decompressed per head from ONE latent ``c``
(``kv_lora_rank`` wide) and one rotary key ``k_r`` shared by every head.
Absorbed, head ``h``'s score against token ``s`` is ``(q_lat[h] . c_s +
q_rope[h] . k_r[s]) x scale`` with ``q_lat[h] = q_nope[h] W_uk[h]^T``, and
its output, before ``W_uv[h]``, is ``sum_s p_s c_s``: every head reads the
same cached rows, so the cache holds ``[c | k_r]`` a token and layer and
nothing per head, and a page is read once for scores and values alike.

**The pool** (:func:`pool_shapes`, the one owner of the page's layout;
``ops/paged_attention.PagedKVCache`` holds it as ``k_pages`` and
``rope_pages`` and no V):

    latent [planes, pages, page_size, rank]            token s at row s
    rope   [planes, pages, page_size / g, 128]         g = 128 / rope_dim

A rotary key is ``rope_dim`` (64) wide, half a 128-lane row; a row of its
own would be half padding.  So ``g`` tokens share a rope row: token ``s``
of a page sits at row ``s mod (page_size / g)``, lanes ``(s div
(page_size / g)) x rope_dim`` onward.  At rank 512 and rope 64 a token and
layer is 1,024 + 128 bytes in bfloat16: 1,152, nothing padded.

**Contract** (the step writes the rows first, :func:`write_latent`, so the
kernel is a pure read of the pool; the tokens are the mixed step's FLAT
axis, ``serving/sched/mixed.py``):

    q_lat      [T, H, rank]         absorbed queries
    q_rope     [g, T, H, 128]       rotated rope queries, part j at lanes
                                    j x rope_dim (:func:`rope_queries`)
    latent, rope   the WHOLE pools above
    page_table [B, pages_per_seq]   kv_len [B] (this step's rows included)
    q_start    [B] the slot's first flat token   q_count [B] its tokens
    layer      [] the plane read
    -> out     [T, H, rank]  o_lat for every token of a live slot; a token
                             of no live slot is never written

Query ``i`` of slot ``b`` sits at ``kv_len - q_count + i`` and sees every
position up to its own.

**The kernel** (one grid step a slot; a slot with ``q_count == 0`` runs
nothing): the slot's query tile — one token for a decode row, the
compiled ``chunk`` for any other (:func:`query_tiles`) — is copied in from
the flat axis (from ``min(q_start, T - tile)``, so a tile never reads past
the axis), ``tile x H`` flash rows token-major; its live pages are walked
in blocks of ``N`` pages (:func:`block_pages`) with double-buffered DMAs
steered by the scalar-prefetched page table, one latent and one rope copy
a live page, no copy for a page past the row's; its outputs go back to the
flat axis a token at a time, the live tokens only.  A block is folded in
three products, all 16 heads of a token at once:

- the latent scores ``q_lat [tile x H, rank] . V^T``, with the block's
  latent rows taken as ``V`` in the order ``(j, page, r)``: the ``g``
  row-bands of ``page_size / g`` rows of every page, one after another;
- the rope scores: the ``g`` parts of ``q_rope`` stacked ``[g x tile x H,
  128]`` against the block's rope rows ``[N x page_size / g, 128]``: part
  ``j``'s product is the score of the tokens in lane block ``j``, which is
  band ``j`` of ``V``'s order, so the ``g`` bands side by side are the
  rope scores in the latent scores' order;
- the values ``p . V`` (``p`` in the pool's dtype, the sum float32).

Softmax does not care in which order the keys come, so nothing is put back
in position order: each key's position is computed in the same order for
the mask.  What a buffer holds past ``kv_len`` (rows of pages no copy
fetched, a last page's tail) is selected out of the last block's values
and rope rows: the mask makes its probability 0, a rope row's other
token meets it through a zero query lane, and 0 x NaN is NaN.

The dense reference (:func:`latent_attention_reference`) is the oracle of
the tests and the path off the TPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ._flash_common import finalize, init_state, update_state

_LANE = 128
_NEG_INF = -1e30
#: the kernel's stable name in lowered programs and profiler traces: it
#: ends in ``attention_kernel``, so ``benchmark/layer_metrics/
#: attn_kernel_share.py`` counts it, and ``mla_kernel_roofline_share``
#: finds it by this name
KERNEL_NAME = "latent_attention_kernel"
#: the KV blocks the kernel may walk, in pages: at rope 64 a block's rope
#: scores are placed ``N x 32`` lanes apart, so ``N`` is a multiple of 4
BLOCK_PAGES = (4, 8)
#: what one score-shaped float32 block may take of VMEM (scores, mask and
#: probabilities of one fold are held at once, with the flash state)
VMEM_BLOCK_BUDGET = 2 * 2**20
#: VMEM the kernel may take: a chunk's queries, outputs and flash state
#: (1,024 flash rows x 512 in float32) beside two blocks of pages
VMEM_LIMIT_BYTES = 64 * 2**20


def rope_groups(rope_dim: int) -> int:
    """Tokens a 128-lane rope row holds."""
    assert _LANE % rope_dim == 0, rope_dim
    return _LANE // rope_dim


def pool_shapes(planes: int, num_pages: int, page_size: int, rank: int,
                rope_dim: int) -> tuple:
    """``(latent shape, rope shape)`` of the pool: the module's text has
    the layout."""
    g = rope_groups(rope_dim)
    assert page_size % g == 0, (page_size, g)
    return (
        (planes, num_pages, page_size, rank),
        (planes, num_pages, page_size // g, _LANE),
    )


def write_latent(latent, rope, layer, page_ids, page_slots, c, k_rope, rows, pos, valid):
    """The pools with this step's rows written in place at ``(layer,
    page_ids[t], page_slots[t])``: ``c [T, rank]`` a latent row each, and
    ``k_rope [T, rope_dim]`` into its rope row's lanes.  A rope row is
    written WHOLE, all 128 lanes, so that XLA scatters rows as it does the
    latent's (a window of ``rope_dim`` lanes it scatters a value at a time,
    or in a loop of one update a token): each token's row is its own key
    beside the row's other tokens' -- from this step where the flat axis
    holds them (``rows`` the owning slot, ``pos`` the position, ``valid``
    the live mask of each flat token: a row's tokens of a step are
    contiguous), else as the pool holds them (an earlier position written
    before; a later one rewritten whole when it comes).  Two tokens of one
    rope row in one step write the same 128 lanes."""
    nrows = rope.shape[2]
    dr = k_rope.shape[-1]
    g = _LANE // dr
    t = k_rope.shape[0]
    latent = latent.at[layer, page_ids, page_slots].set(c.astype(latent.dtype))
    row = page_slots % nrows
    mine = page_slots // nrows  # the token's lane block
    held = rope[layer, page_ids, row].reshape(t, g, dr)
    flat = jnp.arange(t, dtype=jnp.int32)
    k_rope = k_rope.astype(rope.dtype)
    blocks = []
    for j in range(g):
        shift = (j - mine) * nrows  # the position of block j, less the token's
        other = jnp.clip(flat + shift, 0, t - 1)
        here = (
            (flat + shift >= 0) & (flat + shift < t) & valid[other]
            & (rows[other] == rows) & (pos[other] == pos + shift)
        )
        blocks.append(jnp.where(here[:, None], k_rope[other], held[:, j]))
    rope = rope.at[layer, page_ids, row].set(jnp.concatenate(blocks, axis=-1))
    return latent, rope


def rope_queries(q_rope: jax.Array) -> jax.Array:
    """``q_rope [T, H, rope_dim]`` as the kernel takes it: ``[g, T, H,
    128]``, part ``j`` holding the queries at lanes ``j x rope_dim`` and
    zeros elsewhere."""
    dr = q_rope.shape[-1]
    g = rope_groups(dr)
    return jnp.stack([
        jnp.pad(q_rope, ((0, 0), (0, 0), (j * dr, _LANE - (j + 1) * dr)))
        for j in range(g)
    ])


def query_tiles(chunk: int) -> tuple:
    """The kernel's rungs: one query (a decode row), or the chunk."""
    return tuple(sorted({1, chunk}))


def query_tile_rows(q_count: np.ndarray, chunk: int) -> np.ndarray:
    """The query tile the kernel works for each slot (0 for a slot it
    skips), on the host: the scheduler counts its ``q_tile_rows`` so."""
    return np.where(q_count > 1, chunk, np.where(q_count == 1, 1, 0))


def block_pages(tile: int, *, heads: int, page_size: int) -> int:
    """Pages one fold takes at the rung of ``tile`` queries: the largest of
    ``BLOCK_PAGES`` whose ``[tile x heads, keys]`` float32 score block fits
    ``VMEM_BLOCK_BUDGET``.  Static shapes in, so the kernel and the
    scheduler's ``kv_blocks_walked`` agree by construction."""
    rows = tile * heads
    return max(
        [n for n in BLOCK_PAGES if rows * n * page_size * 4 <= VMEM_BLOCK_BUDGET],
        default=BLOCK_PAGES[0],
    )


# ---------------------------------------------------------------------------
# dense reference (oracle + the path off the TPU)
# ---------------------------------------------------------------------------


def unpack_pages(latent, rope, layer, pages):
    """The cached rows of ``pages [..., n]`` of plane ``layer`` in position
    order: ``(c [..., n x page_size, rank], k_rope [..., n x page_size,
    rope_dim])``."""
    page_size, rank = latent.shape[2], latent.shape[3]
    rows = rope.shape[2]
    g = page_size // rows
    c = latent[layer, pages]  # [..., n, page, rank]
    r = rope[layer, pages]  # [..., n, rows, 128]
    lead = r.shape[:-2]
    # row r, lane block j holds token j x rows + r
    r = r.reshape(*lead, rows, g, _LANE // g)
    r = jnp.swapaxes(r, -3, -2).reshape(*lead, page_size, _LANE // g)
    n = pages.shape[-1]
    return (
        c.reshape(*c.shape[:-3], n * page_size, rank),
        r.reshape(*r.shape[:-3], n * page_size, _LANE // g),
    )


def latent_attention_reference(
    q_lat, q_rope, latent, rope, page_table, kv_len, q_start, q_count, layer,
    *, scale: float,
):
    """Gather-then-attend oracle: ``q_lat [T, H, rank]``, ``q_rope [T, H,
    rope_dim]`` (the rotated queries, not :func:`rope_queries`' parts) ->
    ``[T, H, rank]`` in ``q_lat``'s dtype.  A token of no live slot gets
    zeros.  Scores from the pool's dtype into float32, probabilities and
    the value sum float32."""
    t = q_lat.shape[0]
    flat = jnp.arange(t, dtype=jnp.int32)
    inside = (flat[:, None] >= q_start[None]) & (flat[:, None] < (q_start + q_count)[None])
    owner = jnp.argmax(inside, axis=1)  # [T]
    live = jnp.any(inside, axis=1)
    q_pos = (kv_len - q_count)[owner] + flat - q_start[owner]  # [T]
    c, k_r = unpack_pages(latent, rope, layer, page_table[owner])  # [T, S, *]
    dtype = latent.dtype
    scores = jnp.einsum(
        "thr,tsr->ths", q_lat.astype(dtype), c, preferred_element_type=jnp.float32
    ) + jnp.einsum(
        "thd,tsd->ths", q_rope.astype(dtype), k_r, preferred_element_type=jnp.float32
    )
    kv_pos = jnp.arange(c.shape[1], dtype=jnp.int32)[None, None, :]
    mask = (kv_pos <= q_pos[:, None, None]) & (kv_pos < kv_len[owner][:, None, None])
    probs = jax.nn.softmax(jnp.where(mask, scores * scale, _NEG_INF), axis=-1)
    out = jnp.einsum("ths,tsr->thr", probs, c.astype(jnp.float32))
    return jnp.where(live[:, None, None], out, 0.0).astype(q_lat.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------


def _latent_attn_kernel(
    # scalar prefetch
    pt_ref,  # [B, pages_per_seq] int32
    len_ref,  # [B] kv_len
    cnt_ref,  # [B] q_count
    start_ref,  # [B] q_start
    layer_ref,  # [1] the plane read
    # operands, all left in HBM
    q_hbm,  # [T, H, rank]
    qr_hbm,  # [g, T, H, 128]
    c_hbm,  # [L, pages, page_size, rank]
    kr_hbm,  # [L, pages, page_size / g, 128]
    out_hbm,  # [T, H, rank]
    # scratch
    q_buf,  # [tile_max, H, rank]
    qr_buf,  # [g, tile_max, H, 128]
    c_buf,  # [2, N_max, page_size, rank]
    kr_buf,  # [2, N_max, page_size / g, 128]
    o_buf,  # [tile_max, H, rank] in the output's dtype
    page_sem,  # DMA [2, 2]: slot x (latent, rope)
    io_sem,  # DMA [3]: q, rope queries, out
    *,
    tiles: tuple,
    blocks: tuple,
    heads: int,
    page_size: int,
    scale: float,
    tokens: int,
):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    seq_len = len_ref[b]
    count = cnt_ref[b]
    start = start_ref[b]
    layer = layer_ref[0]
    q_base = seq_len - count  # position of the row's first query
    num_live = pl.cdiv(seq_len, page_size)
    g = qr_buf.shape[0]
    rows = page_size // g  # a page's rope rows: a band of V's order
    rank = q_buf.shape[-1]

    def rung(tile: int, pages: int):
        flash = tile * heads
        width = pages * page_size
        band = pages * rows
        n_blocks = pl.cdiv(num_live, pages)
        # the tile's first flat token: the row's own, unless the tile
        # would run past the axis
        base = jnp.minimum(start, tokens - tile)
        off = start - base

        def each_live_page(t, act):
            slot = t % 2
            for i in range(pages):
                j = t * pages + i

                @pl.when(j < num_live)
                def _():
                    page = pt_ref[b, j]
                    act(pltpu.make_async_copy(
                        c_hbm.at[layer, page], c_buf.at[slot, i], page_sem.at[slot, 0],
                    ))
                    act(pltpu.make_async_copy(
                        kr_hbm.at[layer, page], kr_buf.at[slot, i], page_sem.at[slot, 1],
                    ))

        query_copies = [pltpu.make_async_copy(
            q_hbm.at[pl.ds(base, tile)], q_buf.at[pl.ds(0, tile)], io_sem.at[0],
        )] + [
            pltpu.make_async_copy(
                qr_hbm.at[j, pl.ds(base, tile)], qr_buf.at[j, pl.ds(0, tile)], io_sem.at[1],
            )
            for j in range(g)
        ]

        def walk(m_scratch, l_scratch, acc_scratch):
            for copy in query_copies:
                copy.start()
            each_live_page(0, lambda copy: copy.start())
            init_state(m_scratch, l_scratch, acc_scratch)
            for copy in query_copies:
                copy.wait()
            q = q_buf[:tile].reshape(flash, rank)
            qr = jnp.concatenate(
                [qr_buf[j, :tile].reshape(flash, _LANE) for j in range(g)], axis=0
            )  # [g x flash, 128]
            # flash row i is query token i // heads of the tile; a key's
            # place in V's (band, page, row) order gives its position
            row_iota = jax.lax.broadcasted_iota(jnp.int32, (flash, width), 0)
            q_pos = q_base + row_iota // heads - off
            key = jax.lax.broadcasted_iota(jnp.int32, (flash, width), 1)
            key_off = (key % band) // rows * page_size + key // band * rows + key % rows
            val = jax.lax.broadcasted_iota(jnp.int32, (width, rank), 0)
            val_off = (val % band) // rows * page_size + val // band * rows + val % rows
            # a rope row's lane l holds the token of band l // rope_dim
            lane = jax.lax.broadcasted_iota(jnp.int32, (band, _LANE), 1)
            lane_row = jax.lax.broadcasted_iota(jnp.int32, (band, _LANE), 0)
            rope_off = lane_row // rows * page_size + lane // (_LANE // g) * rows + lane_row % rows

            def fold(t, tail: bool):
                slot = t % 2
                each_live_page(t, lambda copy: copy.wait())
                v = jnp.concatenate(
                    [
                        c_buf[slot, :pages, j * rows:(j + 1) * rows].reshape(band, rank)
                        for j in range(g)
                    ],
                    axis=0,
                )  # [width, rank] in (band, page, row) order
                kr = kr_buf[slot, :pages].reshape(band, _LANE)
                block_pos = t * width
                if tail:
                    # ... and out of the rope rows: a rope row is shared by
                    # g tokens, and its zero query lanes times a NaN past
                    # kv_len would reach a live token's score
                    kr = jnp.where(block_pos + rope_off < seq_len, kr, jnp.zeros_like(kr))
                s = jax.lax.dot_general(
                    q, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
                )
                z = jax.lax.dot_general(
                    qr, kr, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
                )  # [g x flash, band]: part j scores band j
                s = (s + jnp.concatenate(
                    [z[j * flash:(j + 1) * flash] for j in range(g)], axis=1
                )) * scale
                kv_pos = block_pos + key_off
                s = jnp.where((kv_pos <= q_pos) & (kv_pos < seq_len), s, _NEG_INF)
                if tail:
                    # the last block: rows past kv_len are selected out of
                    # the values
                    v = jnp.where(block_pos + val_off < seq_len, v, jnp.zeros_like(v))

                def values(p):
                    return jax.lax.dot_general(
                        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )

                update_state(m_scratch, l_scratch, acc_scratch, s, values)

            def body(t, _):
                @pl.when(t + 1 < n_blocks)
                def _prefetch_next():
                    each_live_page(t + 1, lambda copy: copy.start())

                fold(t, tail=False)
                return 0

            jax.lax.fori_loop(0, n_blocks - 1, body, 0)
            fold(n_blocks - 1, tail=True)
            o_buf[:tile] = finalize(l_scratch, acc_scratch).reshape(
                tile, heads, rank
            ).astype(o_buf.dtype)

        pl.run_scoped(
            walk,
            pltpu.VMEM((flash, _LANE), jnp.float32),  # running max
            pltpu.VMEM((flash, _LANE), jnp.float32),  # running denominator
            pltpu.VMEM((flash, rank), jnp.float32),
        )

        # the live tokens back to the flat axis, one copy a token
        def out_copy(i):
            return pltpu.make_async_copy(
                o_buf.at[pl.ds(off + i, 1)], out_hbm.at[pl.ds(start + i, 1)], io_sem.at[2],
            )

        def send(i, _):
            out_copy(i).start()
            return 0

        def land(i, _):
            out_copy(i).wait()
            return 0

        jax.lax.fori_loop(0, count, send, 0)
        jax.lax.fori_loop(0, count, land, 0)

    below = 0
    # graftlint: disable=GL002 reason=tiles and blocks are static tuples of ints, bound with functools.partial before the pallas_call
    for tile, pages in zip(tiles, blocks):
        pl.when((count > below) & (count <= tile))(functools.partial(rung, tile, pages))
        below = tile


@functools.partial(
    jax.jit, static_argnames=("scale", "chunk", "interpret", "block_of"),
)
def _latent_attention_pallas(
    q_lat, q_rope, latent, rope, page_table, kv_len, q_start, q_count, layer,
    *, scale: float, chunk: int, interpret: bool = False,
    block_of: Optional[tuple] = None,
):
    """``q_rope`` is :func:`rope_queries`' ``[g, T, H, 128]``; ``block_of``
    names each rung's block in pages, smallest tile first (a test's and a
    probe's argument: the serving path leaves it to :func:`block_pages`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, heads, rank = q_lat.shape
    g = q_rope.shape[0]
    page_size = latent.shape[2]
    tiles = query_tiles(chunk)
    blocks = block_of or tuple(
        block_pages(tile, heads=heads, page_size=page_size) for tile in tiles
    )
    assert len(blocks) == len(tiles) and t >= max(tiles), (blocks, tiles, t)
    kernel = functools.partial(
        _latent_attn_kernel, tiles=tiles, blocks=blocks, heads=heads,
        page_size=page_size, scale=scale, tokens=t,
    )
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    tile_max, n_max = max(tiles), max(blocks)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(kv_len.shape[0],),
        in_specs=[any_space] * 4,
        out_specs=any_space,
        scratch_shapes=[
            pltpu.VMEM((tile_max, heads, rank), q_lat.dtype),
            pltpu.VMEM((g, tile_max, heads, _LANE), q_rope.dtype),
            pltpu.VMEM((2, n_max, page_size, rank), latent.dtype),
            pltpu.VMEM((2, n_max, rope.shape[2], _LANE), rope.dtype),
            pltpu.VMEM((tile_max, heads, rank), q_lat.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((3,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_lat.shape, q_lat.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=KERNEL_NAME,
    )(
        page_table, kv_len, q_count, q_start,
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        q_lat, q_rope, latent, rope,
    )


def latent_attention(
    q_lat, q_rope, latent, rope, page_table, kv_len, q_start, q_count, layer,
    *, scale: float, chunk: int,
):
    """Dispatch: the Pallas kernel on a TPU, the dense reference elsewhere.
    ``q_lat [T, H, rank]`` and ``q_rope [T, H, rope_dim]`` (rotated) in,
    ``[T, H, rank]`` out; ``chunk`` is the most query tokens a slot packs."""
    from ._dispatch import on_tpu

    if on_tpu():
        return _latent_attention_pallas(
            q_lat, rope_queries(q_rope.astype(rope.dtype)), latent, rope, page_table,
            kv_len, q_start, q_count, layer, scale=scale, chunk=chunk,
        )
    return latent_attention_reference(
        q_lat, q_rope, latent, rope, page_table, kv_len, q_start, q_count, layer,
        scale=scale,
    )
