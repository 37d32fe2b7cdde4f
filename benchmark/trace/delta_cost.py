"""What the gated delta rule needs for a step, from shapes: bytes moved and
operations done by the algorithm, not by the implementation.  A file of
the benchmark, so that a PR that changes the kernel cannot change what its
time is held against.

One call of the kernel serves one linear layer.  For every slot that has a
token this step (``state_rows``) it has to read the slot's state and write
it back: ``2 x heads x key_dim x value_dim`` elements at the state's item
size (float32), whatever the slot's length -- the step's largest stream
after the weights.  For every live token it reads ``q`` and ``k``
(``heads x key_dim`` each), ``v`` (``heads x value_dim``), ``g`` and
``beta`` (``heads`` each, float32) and writes ``o`` (``heads x
value_dim``), at the model's item size.  Those token bytes are the
algorithm's bfloat16 minimum, not the kernel's operands (it takes ``q``,
``k`` and ``v`` in float32, ``q`` and ``k`` padded to 128 lanes, and
writes ``o`` in float32): a kernel that narrows its operands moves its
time, not this floor.  Per token, head and state
element: a multiplication for the decay, a multiplication and an addition
for ``S^T k``, a multiplication and an addition for the rank-1 correction,
a multiplication and an addition for the read-out: ``7 x heads x key_dim x
value_dim`` operations.  At these sizes the bound is bandwidth: 2 x 2.21
MB a slot and layer against 3.9 M operations a token.
"""

from __future__ import annotations


def state_bytes(heads: int, key_dim: int, value_dim: int, itemsize: int = 4) -> int:
    """Bytes of one slot's state in one layer."""
    return heads * key_dim * value_dim * itemsize


def gated_delta_cost(
    *, state_rows: int, tokens: int, layers: int, heads: int, key_dim: int,
    value_dim: int, state_itemsize: int = 4, token_itemsize: int = 2,
) -> tuple[int, int]:
    """``(bytes, operations)`` of one step's ``layers`` kernel calls.

    ``state_rows`` is the slots with a token this step (the dispatch span's
    ``state_rows``), ``tokens`` the live tokens, ``layers`` the linear
    layers (the span's ``delta_layers``)."""
    moved = state_rows * 2 * state_bytes(heads, key_dim, value_dim, state_itemsize)
    per_token = heads * (2 * key_dim + 2 * value_dim) * token_itemsize + 2 * heads * 4
    moved += tokens * per_token
    return layers * moved, layers * 7 * heads * key_dim * value_dim * tokens
