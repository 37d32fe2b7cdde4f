"""Embedding-similarity scoring: log windows × pattern library.

The semantic pattern path (SURVEY.md §7 stage 3) embeds every log window
and every pattern description, then scores ``windows @ patterns.T``.  Both
sides are L2-normalised so the dot product *is* cosine similarity.

The fused Pallas kernel streams window blocks from HBM and keeps only the
per-pattern running max (score + argmax window) in VMEM — the full
``[num_windows, num_patterns]`` score matrix never touches HBM.  For a
10k-window log against a 1k-pattern library that skips a 40 MB round trip;
the op becomes pure compute on the MXU plus an O(P) output.

Shapes (D = embedding dim, a multiple of 128 by construction — MiniLM 384):

    windows  [W, D]  float32/bfloat16, L2-normalised rows
    patterns [P, D]  same dtype
    -> scores [P] float32, best_window [P] int32
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_LANE = 128
_BLOCK_W = 256  # window rows streamed per grid step


def _pad_to(x: jax.Array, size: int, axis: int) -> jax.Array:
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


# ---------------------------------------------------------------------------
# reference implementations (also the CPU execution path)
# ---------------------------------------------------------------------------


def similarity_matrix(windows: jax.Array, patterns: jax.Array) -> jax.Array:
    """Dense ``[W, P]`` cosine-score matrix (inputs assumed normalised)."""
    return jnp.einsum(
        "wd,pd->wp", windows, patterns, preferred_element_type=jnp.float32
    )


def best_window_scores_reference(
    windows: jax.Array, patterns: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Per-pattern best window: (scores [P] f32, indices [P] i32)."""
    scores = similarity_matrix(windows, patterns)  # [W, P]
    return jnp.max(scores, axis=0), jnp.argmax(scores, axis=0).astype(jnp.int32)


def top_k_windows(
    windows: jax.Array, patterns: jax.Array, k: int
) -> tuple[jax.Array, jax.Array]:
    """Top-k windows by best-pattern score (for prompt context selection).

    Returns (scores [k] f32, window indices [k] i32), descending.  This is
    how long logs fit the LLM context budget: the serving prompt takes the
    k highest-evidence windows instead of the raw log (SURVEY.md §5
    long-context entry).
    """
    per_window = jnp.max(similarity_matrix(windows, patterns), axis=1)  # [W]
    k = min(k, per_window.shape[0])
    scores, idx = jax.lax.top_k(per_window, k)
    return scores, idx.astype(jnp.int32)


# ---------------------------------------------------------------------------
# fused Pallas kernel
# ---------------------------------------------------------------------------


def _best_window_kernel(
    w_ref,  # [BLOCK_W, D] window block (VMEM)
    p_ref,  # [P_pad, D] full pattern matrix (VMEM)
    scores_out,  # [P_pad] f32
    idx_out,  # [P_pad] i32
    max_scratch,  # [1, P_pad] f32 running max
    idx_scratch,  # [1, P_pad] i32 running argmax
    *,
    num_windows: int,
    block_w: int,
):
    from jax.experimental import pallas as pl

    step = pl.program_id(0)
    num_steps = pl.num_programs(0)

    @pl.when(step == 0)
    def _init():
        max_scratch[...] = jnp.full_like(max_scratch, -jnp.inf)
        idx_scratch[...] = jnp.zeros_like(idx_scratch)

    # [BLOCK_W, P_pad] on the MXU, f32 accumulation
    scores = jax.lax.dot_general(
        w_ref[...],
        p_ref[...],
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # mask padded window rows (static shapes: W known at trace time)
    row = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0) + step * block_w
    valid = row < num_windows
    scores = jnp.where(valid, scores, -jnp.inf)

    block_max = jnp.max(scores, axis=0, keepdims=True)  # [1, P_pad]
    # manual argmax: Mosaic lowers neither argmax nor integer reductions
    # (this jax version fails AOT on both) — take the SMALLEST row index
    # achieving the max (jnp.argmax's first-match tie-breaking), with the
    # min computed in f32.  Exact while window indices stay below 2^24
    # (~16.7M windows; a 1 GiB log at 256-byte stride is ~4M).
    is_max = scores == block_max  # [BLOCK_W, P_pad] vs broadcast [1, P_pad]
    block_arg = jnp.min(
        jnp.where(is_max, row.astype(jnp.float32), jnp.inf),
        axis=0,
        keepdims=True,
    ).astype(jnp.int32)

    better = block_max > max_scratch[...]
    idx_scratch[...] = jnp.where(better, block_arg, idx_scratch[...])
    max_scratch[...] = jnp.where(better, block_max, max_scratch[...])

    @pl.when(step == num_steps - 1)
    def _finish():
        scores_out[...] = max_scratch[0, :]
        idx_out[...] = idx_scratch[0, :]


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def _best_window_pallas(
    windows: jax.Array,
    patterns: jax.Array,
    *,
    block_w: int = _BLOCK_W,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    num_windows, dim = windows.shape
    num_patterns = patterns.shape[0]
    assert patterns.shape[1] == dim, "embedding dims must match"

    p_pad = _round_up(num_patterns, _LANE)
    w_pad = _round_up(num_windows, block_w)
    windows = _pad_to(windows, w_pad, 0)
    patterns = _pad_to(patterns, p_pad, 0)

    kernel = functools.partial(
        _best_window_kernel, num_windows=num_windows, block_w=block_w
    )
    scores, idx = pl.pallas_call(
        kernel,
        grid=(w_pad // block_w,),
        in_specs=[
            pl.BlockSpec((block_w, dim), lambda i: (i, 0)),
            pl.BlockSpec((p_pad, dim), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((p_pad,), lambda i: (0,)),
            pl.BlockSpec((p_pad,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p_pad,), jnp.float32),
            jax.ShapeDtypeStruct((p_pad,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, p_pad), jnp.float32),
            pltpu.VMEM((1, p_pad), jnp.int32),
        ],
        interpret=interpret,
    )(windows, patterns)
    return scores[:num_patterns], idx[:num_patterns]


def best_window_scores(
    windows: jax.Array, patterns: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Dispatch: fused Pallas kernel on TPU, XLA reference elsewhere."""
    from ._dispatch import on_tpu

    if on_tpu():
        return _best_window_pallas(windows, patterns)
    return best_window_scores_reference(windows, patterns)
