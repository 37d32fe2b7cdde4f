"""What a sparse-expert layer's grouped product needs for a step, from
shapes: bytes moved and operations done by the algorithm, not by the
implementation.  A file of the benchmark, so that a PR that changes the
kernel cannot change what its time is held against.

One call of the kernel serves one layer.  It has to read, once, the three
matrices of every expert that was given a token (``hidden x inner`` twice
and ``inner x hidden``, at the stored item size, with a float32 scale an
output column where they are held int8), read every routed token's row
once for each of the experts it goes to and write that many rows back
(``hidden`` wide, at the activations' item size); it has to multiply every
assignment by its expert's three matrices: ``2 x 3 x hidden x inner``
operations an assignment.  Rows of a tile that hold no token, and the
widening of int8 in VMEM, are the implementation's and are not counted.
``experts_hit`` is summed over the layers already (the step record's
``moe_experts_hit``), ``tokens`` is a layer's (``moe_tokens``: every layer
routes every valid token).  At the cell's sizes the bound is bandwidth:
4.72 MB an expert against 48 assignments x 9.4 M operations.
"""

from __future__ import annotations


def expert_bytes(hidden: int, inner: int, itemsize: int, scaled: bool) -> int:
    """Bytes of one expert's three matrices (and their column scales)."""
    moved = 3 * hidden * inner * itemsize
    if scaled:
        moved += (2 * inner + hidden) * 4
    return moved


def moe_experts_cost(
    *, experts_hit: int, tokens: int, layers: int, experts_per_token: int,
    hidden: int, inner: int, weight_itemsize: int = 1, scaled: bool = True,
    token_itemsize: int = 2,
) -> tuple[int, int]:
    """``(bytes, operations)`` of one step's ``layers`` kernel calls."""
    assignments = layers * tokens * experts_per_token
    moved = experts_hit * expert_bytes(hidden, inner, weight_itemsize, scaled)
    moved += assignments * 2 * hidden * token_itemsize
    return moved, 2 * 3 * hidden * inner * assignments
