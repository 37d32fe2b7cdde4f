"""What the latent attention kernel needs for a step, from shapes: bytes
moved and operations done by the algorithm, not by the implementation.  A
file of the benchmark, so that a PR that changes the kernel cannot change
what its time is held against.

One call of the kernel serves one layer (``ops/latent_attention.py``:
absorbed multi-head latent attention over a latent cache).  It has to read
every latent page its rows walk once -- ``page_size`` positions of ``rank
+ rope_dim`` values, for scores and values alike -- read the live query
tokens' absorbed queries (``heads x (rank + rope_dim)``) and write their
latent outputs (``heads x rank``); it has to score every live query head
against every position on those pages (``rank + rope_dim`` multiply-adds)
and weigh the latents by the scores (``rank``): ``2 x heads x (2 rank +
rope_dim)`` operations a query and position.  A page is counted whole
(the kernel's copies are whole pages; a row's last page is on average half
full), and the rope queries' zero lanes and the folds of whole blocks past
a row's last page are the implementation's and are not counted.  At the
cell's sizes (16 heads, 512 + 64, pages of 64) a page is 73,728 bytes
against 2.2 M operations a query: a decode row is bound by bandwidth, a
prompt chunk of 64 queries by compute.
"""

from __future__ import annotations


def page_bytes(page_size: int, rank: int, rope_dim: int, itemsize: int) -> int:
    """Bytes of one latent page (latent and rope rows) in one layer."""
    return page_size * (rank + rope_dim) * itemsize


def latent_attention_cost(
    *, latent_pages: int, qk_pairs: int, tokens: int, layers: int, page_size: int,
    heads: int, rank: int, rope_dim: int, itemsize: int = 2,
) -> tuple[int, int]:
    """``(bytes, operations)`` of one step's ``layers`` kernel calls.

    ``latent_pages`` is what one call walks (the dispatch span's
    ``kv_pages``, the step record's ``latent_pages_walked``), ``qk_pairs``
    the sum over its rows of queries x positions scored, ``tokens`` the
    live query tokens."""
    moved = latent_pages * page_bytes(page_size, rank, rope_dim, itemsize)
    moved += tokens * heads * (2 * rank + rope_dim) * itemsize
    return layers * moved, layers * 2 * heads * (2 * rank + rope_dim) * qk_pairs
