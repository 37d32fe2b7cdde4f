"""Ragged mixed-phase paged attention: one kernel for prefill AND decode.

The serving engine historically ran two program families — batched
prefill over a right-padded ``[B, T]`` bucket and a fixed
``decode_block`` program over ``[B, 1]`` tokens — as separate phases, so
a long prefill stalled every in-flight decode and short decodes padded
out the block while the MXU idled.  This
module is the kernel half of the fix (PAPERS.md: *Ragged Paged
Attention*, arxiv 2604.15464): ONE program where every batch row sits at
an arbitrary position — a decode row contributes one query token, a
prefill row contributes its next chunk — against the shared paged KV
cache (``ops/paged_attention.py`` layout).

Contract (KV is written to the pages BEFORE attention runs, so the
kernel is a pure read over the page pool):

    q         [B, C, QH, D]  this step's query tokens, row-padded past
                             ``q_count[b]`` (padding rows are ignored)
    k_pages   [L, num_pages, page_size, KH, D]  the WHOLE stacked pool
    v_pages   likewise
    page_table [B, pages_per_seq] int32
    kv_len    [B] int32  valid tokens in the row's pages INCLUDING this
                         step's writes
    q_count   [B] int32  live query rows this step (0 = inactive row)
    layer     [] int32   which layer's pages this call reads

The pool goes in whole and the layer is one more scalar-prefetched value:
a page is fetched from ``(layer, page_table[b, j])``, so the layer loop
(``sched/mixed.py``) can carry the pool and nothing slices a layer out of
it.  One layer's pages are all a call ever touches.

Query token ``i`` of row ``b`` sits at absolute position
``kv_len[b] - q_count[b] + i`` and attends causally over positions
``<= `` its own (``attend_block`` 1), or, under the block-causal mask of a
model that denoises blocks of ``attend_block`` positions (models/sdar.py),
over positions ``<= q_pos | (attend_block - 1)``: every earlier block and
the whole of its own, written by this step like any other key.  A decode row is the ``q_count == 1`` special case; a
whole-prompt prefill is ``q_count == kv_len``; a mid-prompt chunk is
anything in between — one program covers all three, which is what lets
the scheduler (serving/sched/) dispatch a mixed wave every step.  A
speculation VERIFY row (sched/draft.py prompt-lookup drafts) is the same
geometry again: ``q_count = 1 + k`` query tokens — the committed last
token plus ``k`` drafts — where draft ``j`` at position
``kv_len - q_count + 1 + j`` causally attends over the committed context
AND every earlier draft, which is exactly the attention pattern
speculative verification needs; no kernel change, the scheduler just
samples all ``k + 1`` positions and accepts the longest confirmed
prefix (sched/mixed.py).

The Pallas kernel walks each row's live pages with in-kernel
double-buffered DMAs steered by the scalar-prefetched page table (the
``_paged_attn_kernel_v2`` design: exactly ``ceil(kv_len/page)`` pages
move from HBM), a block of several pages to a loop turn, and keeps a
flash-attention running (max, sum, acc) per (query row, head) in VMEM.
The dense reference is the oracle for parity tests and the CPU path.

**The kernel pays by the work.**  One grid step serves one row, and what
it computes follows that row's ``q_count``, not the compiled chunk ``C``:

- *the query tile*: a row with at most ``SMALL_TILE`` queries — a decode
  row, a verify row of ``1 + spec_lookup_k`` — works its first
  ``min(SMALL_TILE, C)`` query tokens, any other row all ``C``.  Flash
  state, score block, mask, ``exp`` and both products have the tile's
  rows (:func:`query_tile_rows` is the rule, and what the scheduler's
  ``q_tile_rows`` counts).  The rungs are branches of the one kernel;
- *the rung of one query*, at every geometry (:func:`query_tiles`): a
  row with ``q_count == 1`` does not take a product a KV head, which
  streams 8 x ``q_per_kv`` query rows to serve ``q_per_kv`` and pays a
  product's fixed cost once a KV head and page (1.25 us a page of 16
  heads at MHA, twice its copies, and 0.47 us a page of 4 heads at 28 /
  4, three times its copies, on a v5e).  It reads the block's ``[keys,
  KH', D]`` buffer as ``keys x KH'`` rows in its own layout and makes
  ONE score product of the row's ``[QH, D]`` query against all of them
  and ONE value product, query head ``i``'s flash row keeping the keys
  of its own KV head ``i // q_per_kv`` alone (the rest masked to
  probability 0, and a value row of a head past the model's selected
  out as past ``kv_len`` is).  Operands as on the other rungs: bf16
  scores into float32, probabilities and the value product float32;
- *the KV block*: a live row's pages ``first .. num_live`` are walked
  ``N`` to a turn of the loop, and a turn scores, masks and folds its
  ``N x page_size`` keys into the flash state in ONE update (one wait
  for the copies, one score product a KV head of ``[slab, D] x [D, N x
  page_size]``, one mask, one lane reduction, one read and write of max,
  sum and accumulator, one value product) where a page a turn paid that
  chain once for every 64 keys.  A page is still one ``[page_size, KH,
  D]`` copy from ``(layer, page_table[b, j])`` into its rows of the
  block's buffer slot, the next block's copies run while this one
  computes, and ONLY LIVE PAGES MOVE: no copy starts for a page at or
  past ``num_live``, and a window's walk starts at page ``first``, not
  at a block's edge below it.  On the rungs of one query and of the
  small tile a partial last block is folded at the narrowest of the
  widths 1, 2, 4 .. ``N`` pages that holds its live pages, so a decode
  row of one page pays for one (the whole chunk's rung folds every
  block at ``N``: its fold is the kernel's largest code, and copies of
  it at other widths outgrew the core's instruction memory at 28 / 4
  heads).  What a buffer holds past
  ``kv_len`` (rows no copy fetched, the tail of a last page) is selected
  out of the value product: the mask makes its probability 0, and 0 x
  NaN is NaN.  ``N`` follows from static shapes, a rung at a time
  (:func:`kv_block_pages`; :func:`kv_blocks_walked` is what the
  scheduler's ``kv_blocks_walked`` counts): the largest of 1, 2, 4, 8 at
  which neither the block's page buffers nor one of its score-shaped
  float32 blocks passes ``VMEM_BLOCK_BUDGET``, 2 MiB.  Both are what
  grows with ``N`` in the 16 MiB of VMEM a kernel gets: the buffers hold
  K and V in two slots, ``4 x N x page_size x KH x D x itemsize``, and a
  flash update holds four or so ``[tile x QH, N x page_size]`` float32
  blocks at once (scores, the mask's positions, probabilities), so 2 MiB
  of one is ~8 MiB of them beside the flash state and the q / out
  blocks (the rung of one query's is ``[QH, N x page_size x KH']``).
  The 7B whole-chunk rung (64 x 28 = 1,792 flash rows): a score block
  is 1,792 x 256 x 4 B = 1.84 MB at four pages and 3.67 MB at eight, so
  it takes 4; its small tile (8 x 28 = 224 rows) 8, and its rung of one
  query (28 rows x 8 x 64 x 4 columns, 229 KB) 8.  An Ouro page (16 KV
  heads) is 64 x 16 x 128 x 2 B = 256 KiB of K alone, so K and V in two
  slots are 2 MiB at two pages: all its rungs take 2.  The 1.5B (12 / 2
  heads) takes 8 on every rung.  A longer block also delays a row's
  first update behind more copies (nothing of the row before overlaps
  them), which is the other reason the buffers are capped;
- *the idle rule*: a row with ``q_count == 0`` (an empty slot, or a live
  row the step's token budget left out) runs nothing — no state, no page,
  no finalise — and moves no block: its grid step's q / out index is the
  last live row's, and the pipeline copies a block only when the index
  changes.  Its output is whatever the buffer held;
- *the output's dtype* is the query's: float32 inside, one cast at the
  finalise.  Scores multiply the pool's dtype (bf16 values are exact
  products in the float32 accumulator); probabilities, running max, sum
  and accumulator stay float32.

So output rows past a row's tile, and every row of an idle slot, are
never written: a caller gathers the ``q_count`` live rows and nothing
else (``sched/mixed.py`` masks its padding tokens).

The page DMA moves one ``[page_size, KH, D]`` page per copy, and Mosaic
requires the minor dimension of a DMA slice to fill the 128-lane tile, so
the kernel serves ``head_dim`` in multiples of 128 only.  The slice's
second-minor dimension, the page's heads, must fill the chip's tile of 8
rows too where there are more than 8: a page holds :func:`pool_heads` of
the model's KV heads (30 held as 32, which is how the chip stores 30
anyway), the pool is written through :func:`write_rows`, and the kernel
and the reference read the model's ``kv_heads`` of each page and never
the rest.  There is no
second attention for the continuous scheduler: a model the kernel cannot
serve is refused at engine build (:func:`require_ragged_kernel_support`),
never routed to the reference.  Serving head_dim 64 needs a lane-dense
page layout — a cache redesign (ROADMAP.md).
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
#: the kernel's stable name in lowered programs and profiler traces: what
#: trace reductions look for (``benchmark/layer_metrics/
#: attn_kernel_share.py`` matches ``ragged_attention``), so a refactor of
#: the wrapper around the ``pallas_call`` cannot rename it by accident
KERNEL_NAME = "ragged_attention_kernel"
#: the query tile of a row with few queries: a decode row (1) and a
#: speculation-verify row (1 + spec_lookup_k = 5) fit it; a row with more
#: takes the whole chunk
SMALL_TILE = 8


#: the KV blocks a rung may walk its pages in, in pages
KV_BLOCK_PAGES = (1, 2, 4, 8)
#: what a KV block may take of VMEM, twice over: its page buffers (K and
#: V, two slots each) and ONE of the score-shaped float32 blocks a flash
#: update holds (scores, the mask's positions, probabilities: four or so
#: at once).  The module doc has the arithmetic
VMEM_BLOCK_BUDGET = 2 * 2**20


def query_tiles(chunk: int) -> tuple[int, ...]:
    """The kernel's rungs at a compiled chunk: the query tiles, ascending.
    The tile of one query leads, whose rung scores a row against every KV
    head of a block at once (the module doc)."""
    return tuple(sorted({1, min(SMALL_TILE, chunk), chunk}))


def query_tile_rows(q_count: np.ndarray, chunk: int) -> np.ndarray:
    """The query tile the kernel works for each slot (0 for a slot it
    skips): the smallest of :func:`query_tiles` that holds the slot's
    queries, the rule ``_ragged_attn_kernel`` branches on.  On the host:
    the scheduler counts its ``q_tile_rows`` with it."""
    tiles = np.asarray(query_tiles(chunk))
    fit = tiles[np.minimum(np.searchsorted(tiles, q_count), len(tiles) - 1)]
    return np.where(q_count > 0, fit, 0)


def pool_heads(kv_heads: int) -> int:
    """KV heads a page of the pool holds for a model of ``kv_heads``: its
    own, rounded up to a multiple of 8 where there are more than 8 and not
    a multiple (30 -> 32).  A page is copied whole and the chip tiles its
    head axis by 8, so the extra heads cost no memory; they are written
    with zeros and read by nothing."""
    if kv_heads <= 8 or kv_heads % 8 == 0:
        return kv_heads
    return -(-kv_heads // 8) * 8


def write_rows(pages, layer, page_ids, page_slots, rows):
    """``pages [L, num_pages, page_size, KH', D]`` with ``rows [T, KH, D]``
    written in place at ``(layer, page_ids[t], page_slots[t])``, each row
    widened with zero heads to the pool's ``KH'`` (:func:`pool_heads`)."""
    extra = pages.shape[3] - rows.shape[1]
    if extra:
        rows = jnp.pad(rows, ((0, 0), (0, extra), (0, 0)))
    return pages.at[layer, page_ids, page_slots].set(rows.astype(pages.dtype))


def kv_block_pages(
    tile: int, *, q_per_kv: int, kv_heads: int, head_dim: int,
    page_size: int, itemsize: int,
) -> int:
    """Pages one flash update folds in at the rung of query tile ``tile``:
    the largest of ``KV_BLOCK_PAGES`` at which neither the page buffers
    (K and V, two slots, of the pool's :func:`pool_heads`) nor one ``[flash
    rows, keys]`` float32 block passes ``VMEM_BLOCK_BUDGET`` (the rung of
    one query: ``[query heads, keys x pool heads]``).  Static shapes in,
    so the kernel's trace and the scheduler's ``kv_blocks_walked`` agree
    by construction."""
    held = pool_heads(kv_heads)
    buffers = 4 * page_size * held * head_dim * itemsize
    columns = page_size * (held if tile == 1 else 1)
    scores = tile * q_per_kv * kv_heads * columns * 4
    return max(
        [n for n in KV_BLOCK_PAGES if n * max(buffers, scores) <= VMEM_BLOCK_BUDGET],
        default=KV_BLOCK_PAGES[0],
    )


def kv_blocks_walked(
    pages: np.ndarray, tile_rows: np.ndarray, block_of: dict[int, int]
) -> int:
    """Flash updates ONE layer's call makes: the sum over slots of
    ``ceil(pages / N)``, with ``pages`` the slot's ``num_live - first``,
    ``tile_rows`` its :func:`query_tile_rows` and ``N = block_of[tile]``
    the :func:`kv_block_pages` of that rung.  On the host, for the
    scheduler."""
    block = np.ones_like(pages)
    for tile, n in block_of.items():
        block[tile_rows == tile] = n
    return int((-(-pages // block))[tile_rows > 0].sum())


class UnsupportedHeadDim(ValueError):
    """The model's head_dim cannot go through the ragged Pallas kernel."""


def require_ragged_kernel_support(config) -> None:
    """Raise :class:`UnsupportedHeadDim` unless ``_ragged_attention_pallas``
    can lower for ``config`` (see the module doc for the constraint)."""
    if config.head_dim % _LANE:
        raise UnsupportedHeadDim(
            f"model {config.name!r} has head_dim={config.head_dim}: the "
            f"ragged paged-attention kernel (_ragged_attention_pallas, the "
            f"continuous scheduler's only attention on a TPU) DMAs KV pages "
            f"whose minor dimension must be a multiple of {_LANE} lanes"
        )


# ---------------------------------------------------------------------------
# dense reference (oracle + CPU path)
# ---------------------------------------------------------------------------


def _block_end(q_pos, attend_block: int):
    """The last position a query at ``q_pos`` sees: its own under the
    causal mask (``attend_block`` 1, and then nothing is traced), the end
    of its block of ``attend_block`` positions (a power of two) under the
    block-causal one."""
    if attend_block == 1:
        return q_pos
    return q_pos | (attend_block - 1)


def ragged_attention_reference(
    q: jax.Array,  # [B, C, QH, D]
    k_pages: jax.Array,  # [L, num_pages, page_size, KH, D]
    v_pages: jax.Array,
    page_table: jax.Array,  # [B, pages_per_seq]
    kv_len: jax.Array,  # [B]
    q_count: jax.Array,  # [B]
    layer: jax.Array,  # [] int32
    sliding_window: Optional[int] = None,
    attend_block: int = 1,
    kv_heads: Optional[int] = None,
) -> jax.Array:
    """Gather-then-attend oracle.  Returns [B, C, QH, D] in q.dtype.
    ``kv_heads``: the model's, of the pool's ``KH`` (None: all of them).

    Rows past ``q_count`` (and rows of inactive slots) produce garbage —
    callers gather only the valid rows, exactly as the kernel's
    flash-state finalize leaves NaN in fully-masked rows."""
    b, c, qh, d = q.shape
    kh = kv_heads or k_pages.shape[3]
    if kh < k_pages.shape[3]:
        k_pages, v_pages = k_pages[..., :kh, :], v_pages[..., :kh, :]
    g = qh // kh
    page_size = k_pages.shape[2]
    max_seq = page_table.shape[1] * page_size

    k = k_pages[layer, page_table].reshape(b, max_seq, kh, d)
    v = v_pages[layer, page_table].reshape(b, max_seq, kh, d)

    q_grouped = q.reshape(b, c, kh, g, d)
    scores = jnp.einsum(
        "bckgd,bskd->bkgcs", q_grouped, k, preferred_element_type=jnp.float32
    ) * (d**-0.5)
    kv_pos = jnp.arange(max_seq, dtype=jnp.int32)[None, None, :]  # [1, 1, S]
    q_pos = (
        (kv_len - q_count)[:, None]
        + jnp.arange(c, dtype=jnp.int32)[None, :]
    )[:, :, None]  # [B, C, 1]
    mask = (kv_pos <= _block_end(q_pos, attend_block)) & (
        kv_pos < kv_len[:, None, None]
    )
    if sliding_window is not None:
        mask = mask & (kv_pos > q_pos - sliding_window)
    scores = jnp.where(mask[:, None, None, :, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgcs,bskd->bckgd", probs, v)
    return out.reshape(b, c, qh, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------


def _ragged_attn_kernel(
    # scalar prefetch
    pt_ref,  # [B, pages_per_seq] int32 (SMEM)
    len_ref,  # [B] int32 kv_len (SMEM)
    cnt_ref,  # [B] int32 q_count (SMEM)
    blk_ref,  # [B] int32: the q / out block each grid step holds
    layer_ref,  # [1] int32: the layer whose pages are read
    # blocks
    q_ref,  # [1, C, QH, D] (VMEM)
    k_hbm,  # [L, num_pages, page_size, KH', D] (stays in HBM; KH' >= KH)
    v_hbm,
    out_ref,  # [1, C, QH, D] in q's dtype
    # scratch
    k_buf,  # [2, max(blocks) * page_size, KH', D] VMEM double buffer
    v_buf,
    sem,  # DMA semaphores [2, 2]: block slot x (k, v)
    *,
    tiles: tuple[int, ...],
    blocks: tuple[int, ...],
    kv_heads: int,
    q_per_kv: int,
    page_size: int,
    scale: float,
    window: Optional[int] = None,
    attend_block: int = 1,
):
    """One grid step per batch row; a row with ``q_count == 0`` runs
    nothing.  A live row's query tile — the smallest of ``tiles`` (query
    tokens, ascending) that holds its ``q_count`` — sets the rows of
    everything computed:
    flash state, scores, mask, ``exp`` and both products.  Its live KV
    pages are walked in blocks of ``blocks[rung]`` pages from page
    ``first``: a turn of the loop starts the next block's page copies
    into the other buffer slot (one copy a live page, none for a page at
    or past ``num_live``), waits for exactly the copies the turn before
    started for it, and folds the block's ``pages x page_size`` keys into
    the flash state in one update.  Flash-state rows are laid
    out head-major — row ``h*T*G + i*G + j`` is query token ``i`` of q
    head ``h*G + j`` at tile ``T`` — so the per-kv-head GQA dots write
    contiguous slabs; the finalize transposes back to [T, QH, D] and
    casts to the output's dtype once.  The rung of one query
    (``all_heads``) keeps one flash row a query head."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del blk_ref  # the index maps' business
    b = pl.program_id(0)
    seq_len = len_ref[b]
    count = cnt_ref[b]
    q_base = seq_len - count  # absolute position of q row 0
    num_live = pl.cdiv(seq_len, page_size)
    first = 0
    if window is not None:
        # earliest kv ANY live q row can see: q_base - window + 1
        first = jnp.maximum(q_base - window + 1, 0) // page_size
    head_dim = q_ref.shape[-1]
    layer = layer_ref[0]

    def rung(tile: int, pages: int):
        """The whole of a live row's work at a static query tile, its
        pages walked ``pages`` to a block."""
        # the rung of one query takes every head of a block at once
        # (``all_heads``); every other rung a product a KV head
        one_query = tile == 1
        slab = tile * q_per_kv  # flash rows per kv head (token-major within)
        total = kv_heads * slab
        walked = num_live - first  # >= 1 for a live row
        n_blocks = pl.cdiv(walked, pages)
        rest = walked % pages  # live pages of a partial last block
        # the widths a partial last block may be folded at, beside the
        # whole block's: the small tile's rung alone has them (its fold is
        # little code; a whole chunk's is the kernel's largest, and a
        # kernel that outgrows the core's instruction memory reloads its
        # code row by row: 13 us a chunk row at 28 / 4 heads)
        narrow = ()
        # graftlint: disable=GL002 reason=tile is a static int, bound with functools.partial where the rungs are laid out
        if tile <= SMALL_TILE:
            narrow = tuple(fit for fit in KV_BLOCK_PAGES if fit < pages)
        # turns of the loop, each a whole block's width: every full block,
        # and a last one too full for the narrow widths
        n_turns = n_blocks
        if narrow:
            n_turns -= ((rest > 0) & (rest <= narrow[-1])).astype(jnp.int32)

        def each_live_page(t, act, upto=pages):
            """``act`` on both copies of every live page of block ``t``:
            page ``i`` of the block lands at rows ``i * page_size`` of
            slot ``t % 2``.  The pages at or past ``num_live`` of a last
            block are not touched: no copy starts, none is waited for."""
            slot = t % 2
            for i in range(upto):
                j = first + t * pages + i
                rows = pl.ds(i * page_size, page_size)

                @pl.when(j < num_live)
                def _():
                    page = pt_ref[b, j]
                    act(pltpu.make_async_copy(
                        k_hbm.at[layer, page], k_buf.at[slot, rows],
                        sem.at[slot, 0],
                    ))
                    act(pltpu.make_async_copy(
                        v_hbm.at[layer, page], v_buf.at[slot, rows],
                        sem.at[slot, 1],
                    ))

        def head_products(m_scratch, l_scratch, acc_scratch):
            """A KV head's query rows against its keys on the MXU, one
            score and one value product a head: what the rungs of several
            query rows a head fold with.  Returns the fold's positions,
            the fold and the finish."""
            q = q_ref[0, :tile].astype(jnp.float32)  # [tile, QH, D]
            # the score product's operands keep the pool's dtype (bf16
            # values multiply exactly into the f32 accumulator); the
            # [tile, G, D] -> [slab, D] regroup runs on f32 tiles
            q_slabs = [
                q[:, h * q_per_kv : (h + 1) * q_per_kv, :]
                .reshape(slab, head_dim).astype(k_buf.dtype)
                for h in range(kv_heads)
            ]

            def positions(width):
                """What a fold of ``width`` keys masks with and nothing of
                it follows the block: each flash row's query position, a
                key's and a value row's place in the block."""
                # flash rows: kv-head slabs stacked, token-major inside
                # each — row h*slab + i*G + j is query token i of q head
                # h*G + j.  Its q position depends only on the token
                # index within the slab.
                row_iota = jax.lax.broadcasted_iota(jnp.int32, (total, width), 0)
                return (
                    q_base + (row_iota % slab) // q_per_kv,
                    jax.lax.broadcasted_iota(jnp.int32, (total, width), 1),
                    jax.lax.broadcasted_iota(jnp.int32, (width, head_dim), 0),
                )

            def fold(t, width, q_pos, key_iota, value_iota):
                """Block ``t``'s first ``width`` keys into the flash state
                in one update, once its copies have landed."""
                slot = t % 2
                each_live_page(
                    t, lambda copy: copy.wait(), upto=width // page_size
                )
                k = k_buf[slot, :width]  # [width, KH, D]
                v = v_buf[slot, :width]
                block_pos = (first + t * pages) * page_size
                kv_pos = block_pos + key_iota

                # scores for every slab against this block, [total, width]
                s = jnp.concatenate(
                    [
                        jax.lax.dot_general(
                            q_slabs[h], k[:, h, :], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                        )
                        for h in range(kv_heads)
                    ],
                    axis=0,
                ) * scale
                mask = (kv_pos <= _block_end(q_pos, attend_block)) & (
                    kv_pos < seq_len
                )
                if window is not None:
                    mask = mask & (kv_pos > q_pos - window)
                s = jnp.where(mask, s, _NEG_INF)
                # the mask zeroes the probability of a key at or past
                # ``kv_len``, and 0 x NaN is NaN: what a buffer holds
                # there (the rows of a page no copy fetched, the tail of
                # a last page) must not reach the value product
                value_live = value_iota < seq_len - block_pos

                def values(p):
                    return jnp.concatenate(
                        [
                            jax.lax.dot_general(
                                p[h * slab : (h + 1) * slab],
                                jnp.where(
                                    value_live,
                                    v[:, h, :].astype(jnp.float32), 0.0,
                                ),
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32,
                            )
                            for h in range(kv_heads)
                        ],
                        axis=0,
                    )

                update_state(m_scratch, l_scratch, acc_scratch, s, values)

            def finish():
                out = finalize(l_scratch, acc_scratch)  # [KH*tile*G, D]
                # slab h holds [tile, G, D]: the head band of [tile, QH, D]
                for h in range(kv_heads):
                    out_ref[0, :tile, h * q_per_kv : (h + 1) * q_per_kv, :] = (
                        out[h * slab : (h + 1) * slab]
                        .reshape(tile, q_per_kv, head_dim).astype(out_ref.dtype)
                    )

            return positions, fold, finish

        def all_heads(m_scratch, l_scratch, acc_scratch):
            """One query against every head of a block at once, in the
            page's own layout (the rung of one query): the block's
            ``[width, KH', D]`` keys read as ``width x KH'`` rows, ONE
            score product of the row's query heads against all of them and
            ONE value product, where each query head keeps its own KV
            head's keys alone (the others masked out: probability 0).  No
            head is sliced out of the buffer and no product streams rows
            that serve nothing; flash state is one row a query head.
            Returns what ``head_products`` does."""
            held = k_buf.shape[2]  # the page's heads (pool_heads)
            q_heads = q_ref[0, 0].astype(k_buf.dtype)  # [QH, D]
            q_end = _block_end(q_base, attend_block)

            def positions(width):
                """Score column and value row ``j`` are key ``j // held``
                of KV head ``j % held``: each column's key, whether it is
                the query head's own KV head's, and each value row's key
                and whether its head is the model's."""
                col = jax.lax.broadcasted_iota(jnp.int32, (total, width * held), 1)
                row = jax.lax.broadcasted_iota(jnp.int32, (total, width * held), 0)
                val = jax.lax.broadcasted_iota(jnp.int32, (width * held, head_dim), 0)
                # graftlint: disable=GL002 reason=q_per_kv is a static int, bound with functools.partial before the pallas_call
                if q_per_kv > 1:
                    row = row // q_per_kv  # query head i reads KV head i // G
                return col // held, col % held == row, val // held, val % held < kv_heads

            def fold(t, width, key_of, own, value_key, value_head):
                slot = t % 2
                each_live_page(
                    t, lambda copy: copy.wait(), upto=width // page_size
                )
                k = k_buf[slot, :width].reshape(width * held, head_dim)
                v = v_buf[slot, :width].reshape(width * held, head_dim)
                block_pos = (first + t * pages) * page_size
                s = jax.lax.dot_general(
                    q_heads, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale  # [QH, width x KH']
                kv_pos = block_pos + key_of
                mask = own & (kv_pos <= q_end) & (kv_pos < seq_len)
                if window is not None:
                    mask = mask & (kv_pos > q_base - window)
                s = jnp.where(mask, s, _NEG_INF)
                # as in head_products: a key at or past ``kv_len``, and a
                # head past the model's, are selected out of the values,
                # not multiplied by their zero probability
                value_live = value_key < seq_len - block_pos
                if held > kv_heads:
                    value_live = value_live & value_head

                def values(p):
                    return jax.lax.dot_general(
                        p, jnp.where(value_live, v.astype(jnp.float32), 0.0),
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )

                update_state(m_scratch, l_scratch, acc_scratch, s, values)

            def finish():
                out_ref[0, 0] = finalize(l_scratch, acc_scratch).astype(out_ref.dtype)

            return positions, fold, finish

        def walk(m_scratch, l_scratch, acc_scratch):
            each_live_page(0, lambda copy: copy.start())
            init_state(m_scratch, l_scratch, acc_scratch)
            positions, fold, finish = (all_heads if one_query else head_products)(
                m_scratch, l_scratch, acc_scratch
            )

            @pl.when(n_turns > 0)
            def _whole_blocks():
                at_width = positions(pages * page_size)

                def body(t, _):
                    @pl.when(t + 1 < n_blocks)
                    def _prefetch_next():
                        each_live_page(t + 1, lambda copy: copy.start())

                    fold(t, pages * page_size, *at_width)
                    return 0

                jax.lax.fori_loop(0, n_turns, body, 0)

            # a last block of few pages is folded at the narrowest width
            # that holds them: a row of one page pays for one
            below = 0
            for fit in narrow:
                @pl.when((rest > below) & (rest <= fit))
                def _last_block(fit=fit):
                    fold(n_turns, fit * page_size, *positions(fit * page_size))

                below = fit

            finish()

        pl.run_scoped(
            walk,
            pltpu.VMEM((total, _LANE), jnp.float32),  # running max
            pltpu.VMEM((total, _LANE), jnp.float32),  # running denominator
            pltpu.VMEM((total, head_dim), jnp.float32),
        )

    # the smallest tile that holds the row's queries; none for count == 0
    below = 0
    # graftlint: disable=GL002 reason=tiles and blocks are static tuples of ints, bound with functools.partial before the pallas_call
    for tile, pages in zip(tiles, blocks):
        pl.when((count > below) & (count <= tile))(
            functools.partial(rung, tile, pages)
        )
        below = tile


@functools.partial(
    jax.jit,
    static_argnames=(
        "interpret", "sliding_window", "block_pages", "attend_block", "kv_heads",
    ),
)
def _ragged_attention_pallas(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    kv_len: jax.Array,
    q_count: jax.Array,
    layer: jax.Array,
    *,
    interpret: bool = False,
    sliding_window: Optional[int] = None,
    block_pages: Optional[tuple[int, ...]] = None,
    attend_block: int = 1,
    kv_heads: Optional[int] = None,
) -> jax.Array:
    """``block_pages`` names the KV block of every rung, smallest tile
    first (a test's and a probe's argument: the serving path leaves it
    to :func:`kv_block_pages`).  ``kv_heads``: the model's, of the pool's
    ``KH`` (None: all of them); the heads a page holds past them are
    copied with the page and never computed with."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, c, qh, d = q.shape
    _, _, page_size, page_heads, _ = k_pages.shape
    kh = kv_heads or page_heads
    assert qh % kh == 0 and kh <= page_heads, (qh, kh, page_heads)

    tiles = query_tiles(c)
    blocks = block_pages or tuple(
        kv_block_pages(
            tile, q_per_kv=qh // kh, kv_heads=kh, head_dim=d,
            page_size=page_size, itemsize=k_pages.dtype.itemsize,
        )
        for tile in tiles
    )
    assert len(blocks) == len(tiles), (blocks, tiles)
    kernel = functools.partial(
        _ragged_attn_kernel,
        tiles=tiles,
        blocks=blocks,
        kv_heads=kh,
        q_per_kv=qh // kh,
        page_size=page_size,
        scale=d**-0.5,
        window=sliding_window,
        attend_block=attend_block,
    )
    # an idle slot's grid step holds the block of the last live slot
    # before it (of the first live slot, ahead of it): the pipeline moves
    # a block only when its index changes, so an idle slot fetches no
    # queries and writes no output back
    live = q_count > 0
    slots = jnp.arange(b, dtype=jnp.int32)
    held = jax.lax.cummax(jnp.where(live, slots, -1))
    block = jnp.where(held < 0, jnp.argmax(live).astype(jnp.int32), held)

    def row_block(i, pt, ln, cn, blk, layer):
        return (blk[i], 0, 0, 0)

    any_space = pl.BlockSpec(memory_space=pl.ANY)
    buffer_rows = max(blocks) * page_size
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, c, qh, d), row_block), any_space, any_space],
        out_specs=pl.BlockSpec((1, c, qh, d), row_block),
        scratch_shapes=[
            pltpu.VMEM((2, buffer_rows, page_heads, d), k_pages.dtype),
            pltpu.VMEM((2, buffer_rows, page_heads, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name=KERNEL_NAME,
    )(
        page_table, kv_len, q_count, block,
        jnp.reshape(layer, (1,)).astype(jnp.int32), q, k_pages, v_pages,
    )


def ragged_paged_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    kv_len: jax.Array,
    q_count: jax.Array,
    layer: jax.Array,
    sliding_window: Optional[int] = None,
    attend_block: int = 1,
    kv_heads: Optional[int] = None,
) -> jax.Array:
    """Dispatch: Pallas kernel on TPU, dense reference elsewhere.
    ``kv_heads``: the model's KV heads, where a page holds more
    (:func:`pool_heads`)."""
    from ._dispatch import on_tpu

    if on_tpu():
        return _ragged_attention_pallas(
            q, k_pages, v_pages, page_table, kv_len, q_count, layer,
            sliding_window=sliding_window, attend_block=attend_block,
            kv_heads=kv_heads,
        )
    return ragged_attention_reference(
        q, k_pages, v_pages, page_table, kv_len, q_count, layer,
        sliding_window=sliding_window, attend_block=attend_block,
        kv_heads=kv_heads,
    )
