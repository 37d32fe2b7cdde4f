"""What the ragged paged-attention kernel needs for a step, from shapes:
bytes moved and operations done by the algorithm, not by the
implementation.  A file of the benchmark, so that a PR that changes the
kernel cannot change what its time is held against.

One call of the kernel serves one layer.  It has to read the K and V of
every KV page its rows walk, read the live query tokens and write their
outputs; it has to score every live query against every position on those
pages (``q @ k``) and weigh the values by the scores (``p @ v``): 2 x 2
operations per query, position, head and head dimension.  The 64-query
tile the kernel computes for every slot, whatever the slot holds, is the
implementation's and is not counted.

Two conventions lean towards the implementation and make the bytes, and
so a bandwidth-bound share, read high: a page is counted whole although a
row's last page is on average half full (some 10% too many bytes at five
pages a row), because the kernel's copies are whole pages; and the output
is counted at the kernel's own ``f32`` (``out_itemsize=4``) although the
model's dtype would do.  Neither matters at a share of 0.02; a kernel that
nears its roofline should be held to positions walked and to the model's
dtype (a change for a ``benchmark`` issue, with the move to per cent).
"""

from __future__ import annotations


def page_bytes(page_size: int, kv_heads: int, head_dim: int, itemsize: int) -> int:
    """Bytes of one page's K and V in one layer."""
    return 2 * page_size * kv_heads * head_dim * itemsize


def ragged_attention_cost(
    *, kv_pages: int, qk_pairs: int, tokens: int, layers: int, page_size: int,
    kv_heads: int, q_heads: int, head_dim: int, kv_itemsize: int,
    q_itemsize: int, out_itemsize: int = 4,
) -> tuple[int, int]:
    """``(bytes, operations)`` of one step's ``layers`` kernel calls.

    ``kv_pages`` is what one call walks (the step record's
    ``kv_pages_walked``), ``qk_pairs`` the sum over its rows of queries x
    positions scored, ``tokens`` the live query tokens."""
    moved = kv_pages * page_bytes(page_size, kv_heads, head_dim, kv_itemsize)
    moved += tokens * q_heads * head_dim * (q_itemsize + out_itemsize)
    return layers * moved, layers * 4 * head_dim * q_heads * qk_pairs


def least_seconds(moved: int, operations: int, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound it is: bytes
    over the memory bandwidth or operations over the matmul peak
    (``peaks.json``: ``hbm_gbps``, ``bf16_tflops``)."""
    by_bytes = moved / (peaks["hbm_gbps"] * 1e9)
    by_operations = operations / (peaks["bf16_tflops"] * 1e12)
    if by_bytes >= by_operations:
        return by_bytes, "bandwidth"
    return by_operations, "compute"
