"""What the selective state-space scan needs for a step, from shapes:
bytes moved and operations done by the algorithm, not by the
implementation.  A file of the benchmark, so that a PR that changes the
kernel cannot change what its time is held against.

One call of the kernel serves one layer.  For every slot that has a token
this step (``state_rows``) it has to read the slot's recurrent state and
write it back: ``2 x heads x head_dim x d_state`` elements at the state's
item size (float32), whatever the slot's length -- that is what makes the
state update the largest stream of a decode step.  For every live token it
reads ``x`` and the gate ``z`` (``heads x head_dim`` each), ``B`` and ``C``
(``groups x d_state`` each) and ``dt`` (``heads``), and writes ``y``
(``heads x head_dim``), at the model's item size.  Per token, head and
state element: one multiplication for the decay, a multiplication and an
addition for the rank-1 update, a multiplication and an addition for the
read-out: ``5 x heads x head_dim x d_state`` operations.  A chunked (SSD)
implementation spends more operations to put them on the matrix unit; that
is the implementation's and is not counted.  At these sizes the bound is
bandwidth: 2 x 4.19 MB a slot against 0.17 M operations a token.
"""

from __future__ import annotations


def state_bytes(heads: int, head_dim: int, d_state: int, itemsize: int = 4) -> int:
    """Bytes of one slot's state in one layer."""
    return heads * head_dim * d_state * itemsize


def ssm_scan_cost(
    *, state_rows: int, tokens: int, layers: int, heads: int, head_dim: int,
    d_state: int, groups: int, state_itemsize: int = 4, token_itemsize: int = 2,
) -> tuple[int, int]:
    """``(bytes, operations)`` of one step's ``layers`` scan calls.

    ``state_rows`` is the slots with a token this step (the step
    record's ``state_rows``), ``tokens`` the live tokens."""
    moved = state_rows * 2 * state_bytes(heads, head_dim, d_state, state_itemsize)
    per_token = (3 * heads * head_dim + 2 * groups * d_state) * token_itemsize + heads * 4
    moved += tokens * per_token
    return layers * moved, layers * 5 * heads * head_dim * d_state * tokens
