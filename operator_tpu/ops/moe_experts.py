"""The sparse-expert layer over the flat token axis: a grouped product.

A token of the mixed step (``serving/sched/mixed.py``) is routed to ``K``
of a layer's ``E`` gated MLPs (``models/sdar.py`` has the router).  What
this module computes, for ``x [T, H]``, the routed experts ``expert_ids
[T, K]`` (``E`` where a part is routed nowhere: a padding token's) and
their weights ``gates [T, K]``::

    y[t] = sum_k gates[t, k] * (silu(x[t] Wg[e]) * (x[t] Wu[e])) Wd[e],   e = expert_ids[t, k]

with ``Wg``, ``Wu [E, H, F]`` and ``Wd [E, F, H]`` one layer of the stacks
``[L, E, in, out]``, held int8 with a scale an expert and output column
(``models/quant.py``) or in a float dtype.

**The stacks go in whole and the layer is one more scalar-prefetched
value**, as the KV pools go into the ragged attention kernel: an expert's
three matrices are fetched from ``(layer, expert)`` by the kernel's block
pipeline, so the layer loop slices no layer out of a stack (a sliced
operand of a custom call is a copy: 604 MB a layer at 128 experts of
2048 x 768) and no bfloat16 copy of an expert ever exists in HBM: the int8
tile is widened in VMEM, multiplied, and its column scales applied to the
float32 sums.

**The layout** (:func:`group_rows`).  The step's ``T x K`` assignments are
grouped by expert, each group padded to a whole number of row tiles of
``tile`` rows: at most ``ceil(T K / tile) + E`` tiles, a static count.
One grid step works one tile against its expert's matrices; tiles of one
expert follow each other, so the pipeline moves an expert's 4.7 MB once
(a block whose index does not change is not fetched again), and an expert
no token chose is never fetched.  The tiles past the step's last are
skipped: they hold the last tile's block indices, so nothing moves for
them.  Rows are gathered into that layout and the ``K`` parts of a token
gathered back and summed with their gates by XLA, outside the kernel.

The reference (CPU path and the tests' oracle) computes every expert for
every token and selects: plain, quadratic in nothing that matters at a
test's size.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

#: the kernel's stable name in lowered programs and profiler traces: what
#: trace reductions look for (``benchmark/layer_metrics/moe_kernel_share.py``)
KERNEL_NAME = "moe_experts_kernel"
#: rows of a tile: one grid step's share of an expert's tokens.  An expert
#: of the benchmark's cell sees ~48 tokens a step (768 x 8 / 128), and the
#: MXU pays for an expert's matrices once a tile whatever the rows, so a
#: tile holds the usual expert whole
TILE_ROWS = 64
#: VMEM the kernel may take: an expert's three int8 matrices in two
#: pipeline slots (9.4 MB at 2048 x 768) and their widened copies
VMEM_LIMIT_BYTES = 64 * 2**20


def _leaves(w: Any) -> tuple:
    """``(matrix stack, scale stack or None)`` of a plain or int8 leaf."""
    if isinstance(w, dict):
        return w["q"], w["s"]
    return w, None


def expert_counts(expert_ids: jax.Array, num_experts: int) -> jax.Array:
    """``[E]`` tokens routed to each expert (parts routed nowhere, id
    ``E``, count for none)."""
    flat = expert_ids.reshape(-1)
    return jnp.sum(
        flat[:, None] == jnp.arange(num_experts, dtype=flat.dtype)[None], axis=0,
        dtype=jnp.int32,
    )


def group_rows(expert_ids: jax.Array, num_experts: int, tile: int) -> dict:
    """The grouped layout of ``expert_ids [T, K]``: ``dest [T * K]`` the
    row each assignment takes (``rows``, one past the end, where it is
    routed nowhere), ``row_token [rows]`` the token each row holds (0 for
    padding), ``tile_expert [tiles]`` the expert of each tile and
    ``n_tiles`` how many the step fills.  Shapes are static: ``tiles =
    ceil(T K / tile) + E``."""
    t, k = expert_ids.shape
    flat = expert_ids.reshape(-1)
    tiles = -(-t * k // tile) + num_experts
    rows = tiles * tile
    onehot = flat[:, None] == jnp.arange(num_experts, dtype=flat.dtype)[None]
    running = jnp.cumsum(onehot.astype(jnp.int32), axis=0)  # [T K, E]
    counts = running[-1]
    routed = flat < num_experts
    own = jnp.clip(flat, 0, num_experts - 1)
    rank = jnp.take_along_axis(running, own[:, None], axis=1)[:, 0] - 1
    tiles_of = -(-counts // tile)
    tile_end = jnp.cumsum(tiles_of)
    tile_start = tile_end - tiles_of
    dest = jnp.where(routed, tile_start[own] * tile + rank, rows)
    row_token = jnp.zeros((rows,), jnp.int32).at[dest].set(
        jnp.arange(t * k, dtype=jnp.int32) // k, mode="drop"
    )
    n_tiles = tile_end[-1]
    index = jnp.arange(tiles, dtype=jnp.int32)
    expert_of = jnp.searchsorted(tile_end, index, side="right").astype(jnp.int32)
    # a tile past the last holds the last one's expert: no matrix moves for it
    last = jnp.clip(expert_of[jnp.maximum(n_tiles - 1, 0)], 0, num_experts - 1)
    tile_expert = jnp.where(index < n_tiles, expert_of, last)
    return {
        "dest": dest, "row_token": row_token, "tile_expert": tile_expert,
        "n_tiles": n_tiles.astype(jnp.int32), "rows": rows, "tiles": tiles,
    }


def combine(y_rows: jax.Array, dest: jax.Array, gates: jax.Array) -> jax.Array:
    """``[T, H]`` float32: each token's ``K`` rows of ``y_rows`` summed
    with its gates; a part routed nowhere adds nothing (selected out, not
    multiplied by zero: its row was never written)."""
    t, k = gates.shape
    rows = y_rows.shape[0]
    parts = y_rows[jnp.clip(dest, 0, rows - 1)].reshape(t, k, -1)
    live = (dest < rows).reshape(t, k, 1)
    weighted = parts.astype(jnp.float32) * gates.astype(jnp.float32)[..., None]
    return jnp.sum(jnp.where(live, weighted, 0.0), axis=1)


# ---------------------------------------------------------------------------
# dense reference (oracle + CPU path)
# ---------------------------------------------------------------------------


def moe_experts_reference(
    x: jax.Array, expert_ids: jax.Array, gates: jax.Array,
    w_gate: Any, w_up: Any, w_down: Any, layer: jax.Array,
) -> jax.Array:
    """Every expert for every token, then the routed ones selected and
    summed with their gates: ``[T, H]`` float32.  The operands of a
    product keep ``x``'s dtype, its sums are float32 and an int8 leaf's
    scales are applied there, as in the kernel."""
    num_experts = _leaves(w_gate)[0].shape[1]

    def product(a, w):
        q, s = _leaves(w)
        y = jnp.einsum(
            "etk,ekn->etn", a, q[layer].astype(a.dtype),
            preferred_element_type=jnp.float32,
        )
        return y if s is None else y * s[layer].astype(jnp.float32)[:, None, :]

    every = jnp.broadcast_to(x[None], (num_experts,) + x.shape)
    hidden = jax.nn.silu(product(every, w_gate)) * product(every, w_up)
    out = product(hidden.astype(x.dtype), w_down).astype(x.dtype)  # [E, T, H]
    # gate of expert e for token t: the sum over its parts routed there
    chosen = expert_ids[:, :, None] == jnp.arange(num_experts)[None, None, :]
    share = jnp.sum(
        jnp.where(chosen, gates.astype(jnp.float32)[..., None], 0.0), axis=1
    )  # [T, E]
    return jnp.einsum("te,eth->th", share, out.astype(jnp.float32))


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------


def _moe_experts_kernel(
    # scalar prefetch
    expert_ref,  # [tiles] int32: the index maps' business
    meta_ref,  # [2] int32: tiles the step fills, the layer
    # blocks
    x_ref,  # [tile, H]
    *refs,  # w_gate, (s_gate), w_up, (s_up), w_down, (s_down), out
    quantized: bool,
):
    """One grid step a row tile: the tile's rows through its expert's
    gated MLP.  The matrices arrive as stored (int8 or float) and are
    widened here; products sum in float32, where the column scales of an
    int8 matrix are applied."""
    from jax.experimental import pallas as pl

    del expert_ref
    out_ref = refs[-1]
    # graftlint: disable=GL002 reason=quantized is a static bool, bound with functools.partial before the pallas_call
    if quantized:
        wg_ref, sg_ref, wu_ref, su_ref, wd_ref, sd_ref = refs[:-1]
    else:
        wg_ref, wu_ref, wd_ref = refs[:-1]
        sg_ref = su_ref = sd_ref = None

    @pl.when(pl.program_id(0) < meta_ref[0])
    def _tile():
        x = x_ref[...]

        def product(a, w_ref, s_ref):
            y = jnp.dot(
                a, w_ref[...].astype(a.dtype), preferred_element_type=jnp.float32
            )
            return y if s_ref is None else y * s_ref[...].astype(jnp.float32)

        hidden = jax.nn.silu(product(x, wg_ref, sg_ref)) * product(x, wu_ref, su_ref)
        out_ref[...] = product(hidden.astype(x.dtype), wd_ref, sd_ref).astype(
            out_ref.dtype
        )


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _moe_experts_pallas(
    x: jax.Array, expert_ids: jax.Array, gates: jax.Array,
    w_gate: Any, w_up: Any, w_down: Any, layer: jax.Array,
    *, tile: int = TILE_ROWS, interpret: bool = False,
) -> jax.Array:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (wg, sg), (wu, su), (wd, sd) = _leaves(w_gate), _leaves(w_up), _leaves(w_down)
    _, num_experts, hidden, inner = wg.shape
    quantized = sg is not None
    with jax.named_scope("moe_route"):
        layout = group_rows(expert_ids, num_experts, tile)
        x_rows = x[layout["row_token"]]  # [rows, H]
    meta = jnp.stack([layout["n_tiles"], jnp.asarray(layer, jnp.int32).reshape(())])

    def row_block(i, expert, meta):
        # a tile past the step's last holds the last one's rows: none move
        return jnp.maximum(jnp.minimum(i, meta[0] - 1), 0), 0

    def matrix_block(i, expert, meta):
        return meta[1], expert[i], 0, 0

    def matrix_spec(rows, cols):
        return pl.BlockSpec((None, None, rows, cols), matrix_block)

    operands, specs = [], []
    # graftlint: disable=GL002 reason=a static tuple of the three stacks' leaves and shapes, not a traced value
    for w, s, (rows, cols) in (
        (wg, sg, (hidden, inner)), (wu, su, (hidden, inner)), (wd, sd, (inner, hidden)),
    ):
        operands.append(w)
        specs.append(matrix_spec(rows, cols))
        if quantized:
            # [L, E, out] -> [L, E, 1, out]: a block's last two dims are whole
            operands.append(s[:, :, None, :])
            specs.append(matrix_spec(1, cols))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(layout["tiles"],),
        in_specs=[pl.BlockSpec((tile, hidden), row_block), *specs],
        out_specs=pl.BlockSpec((tile, hidden), row_block),
    )
    y_rows = pl.pallas_call(
        functools.partial(_moe_experts_kernel, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((layout["rows"], hidden), x.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=KERNEL_NAME,
    )(layout["tile_expert"], meta, x_rows, *operands)
    with jax.named_scope("moe_combine"):
        return combine(y_rows, layout["dest"], gates)


def moe_experts(
    x: jax.Array, expert_ids: jax.Array, gates: jax.Array,
    w_gate: Any, w_up: Any, w_down: Any, layer: jax.Array,
) -> jax.Array:
    """Dispatch: Pallas kernel on TPU, the dense reference elsewhere.
    ``x [T, H]``, ``expert_ids``, ``gates [T, K]``, the three whole stacks
    ``[L, E, in, out]`` (plain or ``{"q", "s"}``) and the layer; ``[T, H]``
    float32."""
    from ._dispatch import on_tpu

    if on_tpu():
        return _moe_experts_pallas(x, expert_ids, gates, w_gate, w_up, w_down, layer)
    return moe_experts_reference(x, expert_ids, gates, w_gate, w_up, w_down, layer)
