"""Selective state-space scan (Mamba-2) per slot, from and into a per-slot
recurrent state: the mixed step's second kernel.

A model with state-space layers keeps, beside the paged KV pool, one
recurrent state per slot, layer and head — ``H`` of ``[d_state, head_dim]``
in float32 — that every token of the slot's row reads and rewrites::

    H_t = exp(dt_t * A_h) * H_{t-1} + dt_t * B_t (x) x_t     (rank-1 update)
    y_t = C_t . H_t                                          (read-out)

with ``x_t`` ``[head_dim]`` of head ``h``, ``B_t`` and ``C_t``
``[d_state]`` of the head's group, ``dt_t`` a positive scalar per head and
token (after softplus) and ``A_h`` a negative scalar per head.  The ``D``
skip (``y_t += D_h x_t``) is the caller's: it touches no state.

Contract (one call serves one layer; tokens are the mixed step's FLAT
token axis, a slot's tokens contiguous from ``q_start``)::

    x       [T, H, P]     this step's tokens, heads x head_dim
    dt      [T, H]  f32   step sizes, after softplus
    a       [H]     f32   A_h (negative)
    b, c    [T, G, N]     input / output projections per group
    state   [L, S, H, N, P] f32   the WHOLE stacked state pool
    layer   [] int32      which layer's state this call updates
    q_start [S] int32     flat offset of the slot's first token
    q_count [S] int32     the slot's tokens this step (0 = idle)
    fresh   [S] bool      the slot's first token is at position 0

    -> y [T, H, P] f32,  state (the same buffer, updated in place)

**By ``q_count``.**  ``q_count == 1`` is a decode row, up to the step's
chunk a prefill chunk: the slot's tokens run in order against its state.
A slot with ``q_count == 0`` is skipped and its state is NOT touched —
neither read nor written: its grid step holds the state block of the last
live slot before it (the ragged attention kernel's idle rule: the pipeline
moves a block only when its index changes).  A ``fresh`` slot starts from
a zero state whatever the pool holds, so recycling a slot needs no
clearing from the host.  Rows of ``y`` that belong to no live slot are
never written; the caller masks its padding tokens.

**In place.**  The pool goes in whole and comes out aliased
(``input_output_aliases``); the layer is a scalar-prefetched block index,
so the layer loop can carry the pool and nothing slices a layer out of it
or writes one back.  Per grid step one ``[heads_per_block, N, P]`` block
of one slot moves in and out, double-buffered by the pipeline: 2 x state
bytes a live slot and layer, the bandwidth floor of the algorithm
(``benchmark/trace/ssm_cost.py``).

**The first form is sequential**: a loop over the slot's tokens, each a
decay, a rank-1 update and a read-out of a ``[N, P]`` tile on the vector
unit, the state staying in VMEM for the whole row.  The chunked matmul
(SSD) form, which would put a prefill chunk's work on the MXU, is a later
optimisation; a decode row (one token) has no such form to gain from.

The token-by-token reference (:func:`ssm_scan_reference`) is the oracle
for the tests and the path of every backend but the TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: the kernel's stable name in lowered programs and profiler traces: what
#: ``benchmark/layer_metrics/ssm_kernel_share.py`` looks for (and what
#: ``attn_kernel_share``, which counts ``attention_kernel``, does not)
KERNEL_NAME = "ssm_scan_kernel"
#: heads whose state one grid step moves (a divisor of the heads a group
#: holds): 8 x [256, 128] float32 is 1 MB a block, four of them in flight
HEADS_PER_BLOCK = 8


def ssm_scan_reference(
    x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
    state: jax.Array, layer: jax.Array, q_start: jax.Array,
    q_count: jax.Array, fresh: jax.Array, *, chunk: int,
) -> tuple[jax.Array, jax.Array]:
    """The recurrence token by token, plain ``jax.numpy``: every slot's
    (at most ``chunk``) tokens gathered to ``[S, chunk]`` and scanned in
    order, a token past ``q_count`` leaving the state as it was."""
    t, heads, _ = x.shape
    groups = b.shape[1]
    per_group = heads // groups
    idx = jnp.clip(
        q_start[:, None] + jnp.arange(chunk, dtype=jnp.int32)[None], 0, t - 1
    )  # [S, C]
    live = jnp.arange(chunk, dtype=jnp.int32)[None] < q_count[:, None]  # [S, C]
    x_p = x.astype(jnp.float32)[idx]  # [S, C, H, P]
    dt_p = dt.astype(jnp.float32)[idx]  # [S, C, H]
    b_p = jnp.repeat(b.astype(jnp.float32)[idx], per_group, axis=2)  # [S, C, H, N]
    c_p = jnp.repeat(c.astype(jnp.float32)[idx], per_group, axis=2)
    h0 = jnp.where(fresh[:, None, None, None], 0.0, state[layer])  # [S, H, N, P]

    def token(h, inputs):
        x_t, dt_t, b_t, c_t, live_t = inputs  # [S, ...]
        decay = jnp.exp(dt_t * a[None])[..., None, None]  # [S, H, 1, 1]
        h_new = decay * h + (dt_t[..., None] * b_t)[..., None] * x_t[:, :, None, :]
        h_new = jnp.where(live_t[:, None, None, None], h_new, h)
        return h_new, jnp.einsum("shn,shnp->shp", c_t, h_new)

    swap = lambda v: jnp.moveaxis(v, 1, 0)  # noqa: E731 - [S, C, ...] -> [C, S, ...]
    h_end, y_p = jax.lax.scan(
        token, h0, (swap(x_p), swap(dt_p), swap(b_p), swap(c_p), swap(live))
    )
    y_p = swap(y_p)  # [S, C, H, P]
    # back to the flat axis: a live token's own row and place
    y = jnp.zeros(x.shape, jnp.float32).at[
        jnp.where(live, idx, t)  # out of range -> dropped
    ].set(y_p, mode="drop")
    # an idle slot keeps its state, fresh or not
    h_end = jnp.where((q_count > 0)[:, None, None, None], h_end, state[layer])
    return y, state.at[layer].set(h_end)


def _ssm_scan_kernel(
    # scalar prefetch
    layer_ref, start_ref, count_ref, fresh_ref, block_ref, live_ref,
    dt_ref, decay_ref,
    # blocks
    x_ref, bc_ref, state_ref,
    # outputs
    y_ref, state_out_ref,
    *, heads: int, heads_per_block: int, head_dim: int,
):
    from jax.experimental import pallas as pl

    del layer_ref, block_ref  # read by the index maps
    hb, slot = pl.program_id(0), pl.program_id(1)
    count, start = count_ref[slot], start_ref[slot]
    n_state = state_ref.shape[3]

    @pl.when((live_ref[0] == 0) & (slot == 0))
    def _():
        # no slot has a token (the scheduler's empty warm-up step): every
        # grid step holds slot 0's block, which is written back at the
        # sweep's end -- hand it back as it came
        state_out_ref[...] = state_ref[...]

    def column(row):
        """``[1, N]`` across lanes -> ``[N, P]``, the value of row ``n``
        on every lane."""
        return jnp.broadcast_to(row.T, (n_state, head_dim))

    def token(index, read_state):
        # the token axis leads its blocks, untiled: one token's heads are
        # one [heads_per_block, P] tile, read and written whole (Mosaic
        # loads no row at an unaligned dynamic offset inside a tile)
        tok = start + index
        bc = bc_ref[0, tok]  # [2, N]: B over C
        b_col, c_col = column(bc[0:1]), column(bc[1:2])
        x_tile = x_ref[0, tok]  # [heads_per_block, P]
        y_rows = []
        for h in range(heads_per_block):  # static: heads of this block
            at = tok * heads + hb * heads_per_block + h
            x_row = x_tile[h:h + 1] * dt_ref[at]  # [1, P]
            state = decay_ref[at] * read_state(h) + b_col * x_row
            state_out_ref[0, 0, h] = state
            y_rows.append(jnp.sum(state * c_col, axis=0, keepdims=True))
        y_ref[0, tok] = jnp.concatenate(y_rows, axis=0)

    @pl.when(count > 0)
    def _():
        keep = (fresh_ref[slot] == 0).astype(jnp.float32)
        # the row's first token reads the pool's block (zero where the
        # row is fresh), the others what the token before them wrote
        token(0, lambda h: state_ref[0, 0, h] * keep)

        def later(index, carry):
            token(index, lambda h: state_out_ref[0, 0, h])
            return carry

        jax.lax.fori_loop(1, count, later, 0)


@functools.partial(jax.jit, static_argnames=("interpret", "heads_per_block"))
def _ssm_scan_pallas(
    x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
    state: jax.Array, layer: jax.Array, q_start: jax.Array,
    q_count: jax.Array, fresh: jax.Array, *, interpret: bool = False,
    heads_per_block: int = 0,
) -> tuple[jax.Array, jax.Array]:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, heads, head_dim = x.shape
    groups, n_state = b.shape[1], b.shape[2]
    slots = state.shape[1]
    per_group = heads // groups
    hpb = heads_per_block or min(HEADS_PER_BLOCK, per_group)
    assert per_group % hpb == 0 and heads == groups * per_group, (heads, groups, hpb)
    blocks = heads // hpb

    dt = dt.astype(jnp.float32)
    # [blocks, T, heads of a block, P] and [G, T, (B, C), N]: the block
    # leads, then the token, then one tile a token
    x_blocks = jnp.transpose(
        x.astype(jnp.float32).reshape(t, blocks, hpb, head_dim), (1, 0, 2, 3)
    )
    bc_groups = jnp.transpose(
        jnp.stack([b, c], axis=2).astype(jnp.float32), (1, 0, 2, 3)
    )
    # an idle slot's grid step holds the block of the last live slot
    # before it (of the first live slot, ahead of it): no block moves
    live = q_count > 0
    ids = jnp.arange(slots, dtype=jnp.int32)
    held = jax.lax.cummax(jnp.where(live, ids, -1))
    block = jnp.where(held < 0, jnp.argmax(live).astype(jnp.int32), held)

    def tokens_of(hb, slot, *prefetch):
        return hb, 0, 0, 0

    def group_of(hb, slot, *prefetch):
        return hb * hpb // per_group, 0, 0, 0

    def state_of(hb, slot, layer_ref, start_ref, count_ref, fresh_ref, block_ref, *rest):
        return layer_ref[0], block_ref[slot], hb, 0, 0

    state_spec = pl.BlockSpec((1, 1, hpb, n_state, head_dim), state_of)
    token_spec = pl.BlockSpec((1, t, hpb, head_dim), tokens_of)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(blocks, slots),
        in_specs=[
            token_spec,
            pl.BlockSpec((1, t, 2, n_state), group_of),
            state_spec,
        ],
        out_specs=[token_spec, state_spec],
    )
    kernel = functools.partial(
        _ssm_scan_kernel, heads=heads, heads_per_block=hpb, head_dim=head_dim,
    )
    y_blocks, new_state = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(x_blocks.shape, jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # the pool is operand 10 (after the eight prefetched scalars, x and
        # B over C) and comes back as output 1: updated in place
        input_output_aliases={10: 1},
        interpret=interpret,
        name=KERNEL_NAME,
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        q_start.astype(jnp.int32), q_count.astype(jnp.int32),
        fresh.astype(jnp.int32), block,
        jnp.any(live).astype(jnp.int32).reshape(1),
        dt.reshape(t * heads), jnp.exp(dt * a[None].astype(jnp.float32)).reshape(t * heads),
        x_blocks, bc_groups, state,
    )
    y = jnp.transpose(y_blocks, (1, 0, 2, 3)).reshape(t, heads, head_dim)
    return y, new_state


def ssm_scan(
    x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
    state: jax.Array, layer: jax.Array, q_start: jax.Array,
    q_count: jax.Array, fresh: jax.Array, *, chunk: int,
) -> tuple[jax.Array, jax.Array]:
    """Dispatch: Pallas kernel on TPU, the token-by-token reference
    elsewhere (``chunk`` bounds a slot's tokens a step; the kernel reads
    them from ``q_count``)."""
    from ._dispatch import on_tpu

    if on_tpu():
        return _ssm_scan_pallas(x, dt, a, b, c, state, layer, q_start, q_count, fresh)
    return ssm_scan_reference(
        x, dt, a, b, c, state, layer, q_start, q_count, fresh, chunk=chunk
    )
