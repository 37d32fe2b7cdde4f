"""What the recurrent families' layer bodies share on the continuous path
(``models/falcon_h1.py``, ``models/olmo_hybrid.py``): which slots start
from nothing this step, and the causal depthwise convolution over the
mixed step's flat token axis, whose taps before a row's first token of the
step come from the slot's carried tail.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp


def conv_taps(weight: jax.Array, bias: Optional[jax.Array], taps: list) -> jax.Array:
    """``silu(sum_k weight[k] * taps[k] (+ bias))`` in float32, cast back:
    ``taps[k]`` is the convolution's input ``len(taps) - 1 - k`` tokens
    back (zeros before the sequence's start), oldest first."""
    w = weight.astype(jnp.float32)
    acc = None if bias is None else bias.astype(jnp.float32)
    for k, tap in enumerate(taps):
        term = w[k] * tap.astype(jnp.float32)
        acc = term if acc is None else acc + term
    return jax.nn.silu(acc).astype(taps[-1].dtype)


def softplus_inverse(y: jax.Array) -> jax.Array:
    """``x`` with ``softplus(x) == y``: how both families' seeded inits
    place ``dt_bias`` so that ``softplus(dt_bias)`` is the step drawn."""
    return y + jnp.log(-jnp.expm1(-y))


def slot_fresh(step: Any) -> jax.Array:
    """``[S]``: the slot's row starts at position 0 this step, so its
    recurrence starts from nothing -- a slot's next tenant never reads
    what the last one left, and no clearing from the host is needed."""
    first = step.pos[jnp.clip(step.q_start, 0, step.t_budget - 1)]
    return (first == 0) & (step.q_count > 0)


def flat_conv(
    step: Any, fresh: jax.Array, inputs: jax.Array, tails: jax.Array,
    weight: jax.Array, bias: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """Causal depthwise convolution on the flat axis: a token's tap ``s``
    tokens back is the flat token before it while that is still in its
    row, else the slot's carried tail.  ``inputs [1, T, C]``, ``tails [S,
    kernel - 1, C]`` (one layer's), ``weight [kernel, C]``.  Returns the
    activated ``[1, T, C]`` and the slots' new tails: the last ``kernel -
    1`` inputs of [old tail | this step's tokens], an idle slot's as it
    was."""
    t_budget = step.t_budget
    tail = tails.shape[1]
    flat = inputs[0]  # [T, C]
    tails = jnp.where(fresh[:, None, None], 0, tails).astype(flat.dtype)
    taps = []
    for back in range(tail, 0, -1):  # oldest first
        inside = step.in_row >= back
        from_flat = flat[jnp.clip(jnp.arange(t_budget) - back, 0, t_budget - 1)]
        from_tail = tails[step.rows, jnp.clip(tail + step.in_row - back, 0, tail - 1)]
        taps.append(jnp.where(inside[:, None], from_flat, from_tail))
    taps.append(flat)
    out = conv_taps(weight, bias, taps)
    at = step.q_count[:, None] + jnp.arange(tail, dtype=jnp.int32)[None]  # [S, tail]
    from_flat = flat[jnp.clip(step.q_start[:, None] + at - tail, 0, t_budget - 1)]
    from_tail = jnp.take_along_axis(tails, jnp.clip(at, 0, tail - 1)[..., None], axis=1)
    return out[None], jnp.where((at >= tail)[..., None], from_flat, from_tail)
