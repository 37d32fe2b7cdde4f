"""The gated delta rule (Gated DeltaNet) per slot, from and into a per-slot
recurrent state: the mixed step's kernel for a linear-attention layer.

A model with such layers (``models/olmo_hybrid.py``) keeps, beside the
paged KV pool of its full-attention layers, one state per slot, linear
layer and head, ``S`` of ``[key_dim, value_dim]`` in float32, that every
token of the slot's row reads and rewrites::

    S_t  = exp(g_t) S_{t-1}                                  (decay)
    S_t += k_t (x) beta_t (v_t - S_t^T k_t)                   (delta rule)
    o_t  = S_t^T q_t                                          (read-out)

with ``q_t``, ``k_t`` ``[key_dim]`` (L2-normed, q scaled by key_dim^-0.5:
the caller's), ``v_t`` ``[value_dim]``, and ``g_t`` (a log decay, negative)
and ``beta_t`` (in (0, 2)) scalars per head and token.  The update reads
``S^T k`` before it writes the rank-1 correction, so a token takes two
passes over a state tile where the Mamba-2 scan (``ops/ssm_scan.py``)
takes one.

**The pool's layout stores no padding.**  A state of 96 x 192 float32 would
be padded to 96 x 256 on the chip (lanes come in 128s): a third more bytes
on the step's largest stream.  So heads are held in PAIRS side by side,
``[heads / 2, key_dim, 2 x value_dim]``: head ``2p`` in lanes ``[0,
value_dim)`` of pair ``p``, head ``2p + 1`` in ``[value_dim, 2 x
value_dim)``, 384 lanes for two heads of 192 (``configs.OlmoHybridConfig
.recurrent_state_shapes``).

Contract (``ssm_scan``'s: one call serves one layer; tokens are the mixed
step's FLAT token axis, a slot's tokens contiguous from ``q_start``)::

    q, k    [T, H, Dk]    this step's tokens (normed, q scaled)
    v       [T, H, Dv]
    g, beta [T, H]  f32   log decay (<= 0) and the delta rule's step
    state   [L, S, H/2, Dk, 2 Dv] f32   the WHOLE stacked state pool
    layer   [] int32      which layer's state this call updates
    q_start [S] int32     flat offset of the slot's first token
    q_count [S] int32     the slot's tokens this step (0 = idle)
    fresh   [S] bool      the slot's first token is at position 0

    -> o [T, H, Dv] f32,  state (the same buffer, updated in place)

A slot with ``q_count == 0`` is neither read nor written (its grid step
holds the block of the last live slot before it); a ``fresh`` slot starts
from zero whatever the pool holds; rows of ``o`` of no live slot are never
written, and the caller masks its padding tokens.  The pool goes in whole
and comes out aliased, the layer a scalar-prefetched block index.

**The first form is sequential**: a loop over the slot's tokens, each a
decay, two reductions and a rank-1 update of a ``[Dk, 2 Dv]`` tile on the
vector unit, the state staying in VMEM for the whole row.  The chunked
(WY / UT) form, which would put a prefill chunk's work on the MXU, is a
later optimisation.  :func:`gated_delta_reference`, token by token, is
the oracle for the tests and the path of every backend but the TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: the kernel's stable name in lowered programs and profiler traces: what
#: ``benchmark/layer_metrics/delta_kernel_share.py`` looks for (and what
#: ``attn_kernel_share``, which counts ``attention_kernel``, does not)
KERNEL_NAME = "gated_delta_kernel"
#: head pairs whose state one grid step moves, at most: 5 x [96, 384]
#: float32 is 0.74 MB a block
PAIRS_PER_BLOCK = 5
_LANE = 128


def unpack_pairs(pool_slice: jax.Array, value_dim: int) -> jax.Array:
    """``[..., H/2, Dk, 2 Dv]`` -> ``[..., H, Dk, Dv]``: head ``2p`` is the
    pair's left half."""
    *lead, pairs, dk, _ = pool_slice.shape
    split = pool_slice.reshape(*lead, pairs, dk, 2, value_dim)
    return jnp.moveaxis(split, -2, -3).reshape(*lead, 2 * pairs, dk, value_dim)


def pack_pairs(states: jax.Array) -> jax.Array:
    """The inverse of :func:`unpack_pairs`."""
    *lead, heads, dk, dv = states.shape
    split = states.reshape(*lead, heads // 2, 2, dk, dv)
    return jnp.moveaxis(split, -3, -2).reshape(*lead, heads // 2, dk, 2 * dv)


def gated_delta_reference(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
    state: jax.Array, layer: jax.Array, q_start: jax.Array,
    q_count: jax.Array, fresh: jax.Array, *, chunk: int,
) -> tuple[jax.Array, jax.Array]:
    """The recurrence token by token, plain ``jax.numpy``: every slot's (at
    most ``chunk``) tokens gathered to ``[S, chunk]`` and scanned in order,
    a token past ``q_count`` leaving the state as it was."""
    t = q.shape[0]
    dv = v.shape[-1]
    idx = jnp.clip(
        q_start[:, None] + jnp.arange(chunk, dtype=jnp.int32)[None], 0, t - 1
    )  # [S, C]
    live = jnp.arange(chunk, dtype=jnp.int32)[None] < q_count[:, None]  # [S, C]
    gather = lambda a: a.astype(jnp.float32)[idx]  # noqa: E731 - [S, C, ...]
    q_p, k_p, v_p, g_p, beta_p = map(gather, (q, k, v, g, beta))
    held = unpack_pairs(state[layer], dv)  # [S, H, Dk, Dv]
    s0 = jnp.where(fresh[:, None, None, None], 0.0, held)

    def token(s, inputs):
        q_t, k_t, v_t, g_t, beta_t, live_t = inputs  # [S, H, ...]
        s_new = jnp.exp(g_t)[..., None, None] * s
        remembered = jnp.einsum("shkv,shk->shv", s_new, k_t)
        delta = beta_t[..., None] * (v_t - remembered)
        s_new = s_new + k_t[..., :, None] * delta[..., None, :]
        s_new = jnp.where(live_t[:, None, None, None], s_new, s)
        return s_new, jnp.einsum("shkv,shk->shv", s_new, q_t)

    swap = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731 - [S, C, ...] -> [C, S, ...]
    s_end, o_p = jax.lax.scan(
        token, s0, tuple(map(swap, (q_p, k_p, v_p, g_p, beta_p, live)))
    )
    o_p = swap(o_p)  # [S, C, H, Dv]
    o = jnp.zeros(v.shape, jnp.float32).at[
        jnp.where(live, idx, t)  # out of range -> dropped
    ].set(o_p, mode="drop")
    # an idle slot keeps its state, fresh or not
    s_end = jnp.where((q_count > 0)[:, None, None, None], s_end, held)
    return o, state.at[layer].set(pack_pairs(s_end))


def _gated_delta_kernel(
    # scalar prefetch
    layer_ref, start_ref, count_ref, fresh_ref, block_ref, live_ref,
    decay_ref, beta_ref,
    # blocks
    kq_ref, v_ref, state_ref,
    # outputs
    o_ref, state_out_ref,
    *, heads: int, pairs_per_block: int, key_dim: int, value_dim: int,
):
    from jax.experimental import pallas as pl

    del layer_ref, block_ref  # read by the index maps
    pb, slot = pl.program_id(0), pl.program_id(1)
    count, start = count_ref[slot], start_ref[slot]
    wide = 2 * value_dim
    left = jax.lax.broadcasted_iota(jnp.int32, (1, wide), 1) < value_dim

    @pl.when((live_ref[0] == 0) & (slot == 0))
    def _():
        # no slot has a token (the scheduler's empty warm-up step): every
        # grid step holds slot 0's block, which is written back at the
        # sweep's end -- hand it back as it came
        state_out_ref[...] = state_ref[...]

    def column(rows, r):
        """Row ``r`` of a ``[4, Dk padded]`` tile as a ``[Dk, 2 Dv]``
        column, the value of element ``n`` on every lane."""
        return jnp.broadcast_to(rows[r:r + 1].T[:key_dim], (key_dim, wide))

    def per_pair(at, scalar_ref):
        """``[1, 2 Dv]``: the pair's two heads' scalars, each on its half."""
        return jnp.where(left, scalar_ref[at], scalar_ref[at + 1])

    def token(index, read_state):
        # the token axis leads its blocks, untiled: one token's pairs are
        # one tile, read and written whole
        tok = start + index
        kq = kq_ref[0, tok]  # [pairs_per_block * 4, Dk padded]: k_a k_b q_a q_b
        v = v_ref[0, tok]  # [pairs_per_block, 2 Dv]
        o_rows = []
        for h in range(pairs_per_block):  # static: pairs of this block
            rows = kq[4 * h:4 * h + 4]
            k_col = jnp.where(left, column(rows, 0), column(rows, 1))
            q_col = jnp.where(left, column(rows, 2), column(rows, 3))
            at = tok * heads + 2 * (pb * pairs_per_block + h)
            s = per_pair(at, decay_ref) * read_state(h)
            remembered = jnp.sum(s * k_col, axis=0, keepdims=True)  # [1, 2 Dv]
            delta = per_pair(at, beta_ref) * (v[h:h + 1] - remembered)
            s = s + k_col * delta
            state_out_ref[0, 0, h] = s
            o_rows.append(jnp.sum(s * q_col, axis=0, keepdims=True))
        o_ref[0, tok] = jnp.concatenate(o_rows, axis=0)

    @pl.when(count > 0)
    def _():
        keep = (fresh_ref[slot] == 0).astype(jnp.float32)
        # the row's first token reads the pool's block (zero where the row
        # is fresh), the others what the token before them wrote
        token(0, lambda h: state_ref[0, 0, h] * keep)

        def later(index, carry):
            token(index, lambda h: state_out_ref[0, 0, h])
            return carry

        jax.lax.fori_loop(1, count, later, 0)


def pairs_per_block(pairs: int) -> int:
    """The largest divisor of ``pairs`` up to :data:`PAIRS_PER_BLOCK`."""
    return max(d for d in range(1, min(pairs, PAIRS_PER_BLOCK) + 1) if pairs % d == 0)


@functools.partial(jax.jit, static_argnames=("interpret", "block_pairs"))
def _gated_delta_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
    state: jax.Array, layer: jax.Array, q_start: jax.Array,
    q_count: jax.Array, fresh: jax.Array, *, interpret: bool = False,
    block_pairs: int = 0,
) -> tuple[jax.Array, jax.Array]:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, heads, key_dim = q.shape
    value_dim = v.shape[-1]
    slots, pairs = state.shape[1], state.shape[2]
    ppb = block_pairs or pairs_per_block(pairs)
    assert heads == 2 * pairs and pairs % ppb == 0, (heads, pairs, ppb)
    blocks = pairs // ppb
    padded = -(-key_dim // _LANE) * _LANE

    # [blocks, T, 4 x pairs of a block, Dk padded]: a pair's k_a k_b q_a q_b
    # as rows (a row becomes a column in the kernel, which wants whole lanes)
    kq = jnp.concatenate([
        k.astype(jnp.float32).reshape(t, pairs, 2, key_dim),
        q.astype(jnp.float32).reshape(t, pairs, 2, key_dim),
    ], axis=2)
    kq = jnp.pad(kq, ((0, 0), (0, 0), (0, 0), (0, padded - key_dim)))
    kq = jnp.transpose(kq.reshape(t, blocks, ppb * 4, padded), (1, 0, 2, 3))
    # [blocks, T, pairs of a block, 2 Dv]: the pool's own pairing
    v_blocks = jnp.transpose(
        v.astype(jnp.float32).reshape(t, blocks, ppb, 2 * value_dim), (1, 0, 2, 3)
    )
    # an idle slot's grid step holds the block of the last live slot before
    # it (of the first live slot, ahead of it): no block moves
    live = q_count > 0
    ids = jnp.arange(slots, dtype=jnp.int32)
    held = jax.lax.cummax(jnp.where(live, ids, -1))
    block = jnp.where(held < 0, jnp.argmax(live).astype(jnp.int32), held)

    def tokens_of(pb, slot, *prefetch):
        return pb, 0, 0, 0

    def state_of(pb, slot, layer_ref, start_ref, count_ref, fresh_ref, block_ref, *rest):
        return layer_ref[0], block_ref[slot], pb, 0, 0

    state_spec = pl.BlockSpec((1, 1, ppb, key_dim, 2 * value_dim), state_of)
    value_spec = pl.BlockSpec((1, t, ppb, 2 * value_dim), tokens_of)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(blocks, slots),
        in_specs=[pl.BlockSpec((1, t, ppb * 4, padded), tokens_of), value_spec, state_spec],
        out_specs=[value_spec, state_spec],
    )
    kernel = functools.partial(
        _gated_delta_kernel, heads=heads, pairs_per_block=ppb,
        key_dim=key_dim, value_dim=value_dim,
    )
    o_blocks, new_state = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(v_blocks.shape, jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # the pool is operand 10 (after the eight prefetched scalars, k|q
        # and v) and comes back as output 1: updated in place
        input_output_aliases={10: 1},
        interpret=interpret,
        name=KERNEL_NAME,
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        q_start.astype(jnp.int32), q_count.astype(jnp.int32),
        fresh.astype(jnp.int32), block,
        jnp.any(live).astype(jnp.int32).reshape(1),
        jnp.exp(g.astype(jnp.float32)).reshape(t * heads),
        beta.astype(jnp.float32).reshape(t * heads),
        kq, v_blocks, state,
    )
    o = jnp.transpose(o_blocks, (1, 0, 2, 3)).reshape(t, heads, value_dim)
    return o, new_state


def gated_delta(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
    state: jax.Array, layer: jax.Array, q_start: jax.Array,
    q_count: jax.Array, fresh: jax.Array, *, chunk: int,
) -> tuple[jax.Array, jax.Array]:
    """Dispatch: Pallas kernel on TPU, the token-by-token reference
    elsewhere (``chunk`` bounds a slot's tokens a step; the kernel reads
    them from ``q_count``)."""
    from ._dispatch import on_tpu

    if on_tpu():
        return _gated_delta_pallas(q, k, v, g, beta, state, layer, q_start, q_count, fresh)
    return gated_delta_reference(
        q, k, v, g, beta, state, layer, q_start, q_count, fresh, chunk=chunk
    )
