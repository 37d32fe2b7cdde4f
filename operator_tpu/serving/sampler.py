"""The sampler: one function, traced into every program that samples.

The continuous scheduler's mixed step (``sched/mixed.py``) and the wave
engine's decode / prefill / finish programs (``programs.py``) call the
same :func:`sample` through their runtime's ``sample`` attribute, which
binds ``top_k`` (``serving/runtime.py``).
"""

from __future__ import annotations

#: nucleus-sampling candidate-set size (constructor: ``sample_top_k``).
#: The contract: a row is sampled from its ``SAMPLE_TOP_K`` largest
#: temperature-scaled logits by value, the lowest id first among equals,
#: and the top-p cutoff is computed inside them, in float32 — i.e. the
#: served distribution is top-k AND top-p composed, the standard serving
#: trade.  The selection is exact (:func:`_top_k`): it ranks only the
#: vocabulary blocks that can hold a candidate, so a step pays one read
#: of the row and a sort of ``top_k`` blocks, not a sort of the row.
#: At this system's temperatures (0.3 default, aiprovider-crd.yaml:56-58)
#: the top-64 hold ~all the nucleus mass; at temperatures ~1+ the
#: truncation measurably narrows diversity vs true nucleus sampling —
#: raise sample_top_k (e.g. 256) if that matters more than decode
#: latency (the sort grows with it: ``top_k`` blocks of ``_LANE``).
SAMPLE_TOP_K = 64

#: columns of a vocabulary block: the TPU's lane width, which divides
#: every served vocabulary (151,936 = 1187 x 128; 256 does not)
_LANE = 128
#: a row of fewer than this many times ``k`` blocks is sorted whole: the
#: pruned form would rank most of it anyway
_MIN_BLOCKS_PER_K = 4


def _top_k(scaled, k: int):
    """``jax.lax.top_k(scaled, k)`` over the last axis of ``[B, V]``,
    bit for bit (values, ids, the lowest id first among equal values),
    without sorting the row where its static shape allows.

    The row is viewed as contiguous blocks of ``_LANE`` columns.  The
    ``k`` blocks with the largest maxima hold every candidate: their
    ``k``-th maximum is a lower bound of the row's ``k``-th value (``k``
    elements stand at or above it), every element above it lies in one
    of them, and among blocks whose maximum equals it ``lax.top_k``
    keeps the lowest, as it would among the elements.  Those blocks,
    gathered in ascending order so that equal values keep their order
    by id, are what is sorted.  No fallback at run time: which form a
    program holds follows from ``V`` and ``k`` alone.
    """
    import jax
    import jax.numpy as jnp

    rows, vocab = scaled.shape
    blocks = vocab // _LANE
    if vocab % _LANE or blocks < _MIN_BLOCKS_PER_K * k:
        return jax.lax.top_k(scaled, k)
    # [blocks, B, lane]: what the chip's gather reads its rows from, and
    # its maxima, [blocks, B], lie as the chip's sort wants them (rows on
    # the lanes): one re-laid-out copy of the row, not two (PERF.md §6)
    blocked = scaled.reshape(rows, blocks, _LANE).transpose(1, 0, 2)
    _, block_ids = jax.lax.top_k(jnp.max(blocked, axis=-1).T, k)
    block_ids = jnp.sort(block_ids, axis=-1)  # [B, k], ascending
    candidates = jnp.take_along_axis(blocked, block_ids.T[:, :, None], axis=0)
    values, place = jax.lax.top_k(
        candidates.transpose(1, 0, 2).reshape(rows, k * _LANE), k
    )
    block_of = jnp.take_along_axis(block_ids, place // _LANE, axis=-1)
    return values, block_of * _LANE + place % _LANE


def _nucleus(logits, temp, top_p, top_k: int):
    """The candidates a row is sampled from: ``(top_idx [B, k], filtered
    [B, k])``, the ``k`` largest logits' ids and their temperature-scaled
    logits with minus infinity outside the nucleus."""
    import jax
    import jax.numpy as jnp

    safe_temp = jnp.maximum(temp, 1e-4)[:, None]
    scaled = logits.astype(jnp.float32) / safe_temp
    k = min(top_k, logits.shape[-1])
    top_logits, top_idx = _top_k(scaled, k)
    probs = jax.nn.softmax(top_logits, axis=-1)
    cumulative = jnp.cumsum(probs, axis=-1) - probs  # exclusive prefix
    keep = cumulative < top_p[:, None]  # first token always kept
    return top_idx, jnp.where(keep, top_logits, -jnp.inf)


def sample(logits, rng, temp, top_p, *, top_k: int):
    """Temperature + truncated-nucleus sampling; temp<=0 means greedy.

    [B, V] logits -> ([B] token ids, the advanced rng).  top-p filtering
    runs inside the top-``top_k`` candidates (renormalised by
    categorical), not the full vocab — see SAMPLE_TOP_K above for the
    semantics trade.
    """
    import jax
    import jax.numpy as jnp

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    top_idx, filtered = _nucleus(logits, temp, top_p, top_k)
    rng, sub = jax.random.split(rng)
    choice = jax.random.categorical(sub, filtered, axis=-1)
    sampled = jnp.take_along_axis(top_idx, choice[:, None], axis=-1)[:, 0]
    picked = jnp.where(temp <= 0.0, greedy, sampled.astype(jnp.int32))
    return picked, rng


def sample_with_confidence(logits, rng, temp, top_p, *, top_k: int):
    """:func:`sample`, and beside each token its confidence: the
    probability the token had among the candidates it was drawn from (the
    nucleus inside the top ``top_k``, at the row's temperature).  What a
    denoising step ranks its positions by (``sched/mixed.py``).  A greedy
    row's token is the first candidate, and its confidence that one's.

    [B, V] logits -> ([B] token ids, [B] float32 confidences, the rng).
    """
    import jax
    import jax.numpy as jnp

    top_idx, filtered = _nucleus(logits, temp, top_p, top_k)
    rng, sub = jax.random.split(rng)
    choice = jax.random.categorical(sub, filtered, axis=-1)
    choice = jnp.where(temp <= 0.0, 0, choice)  # the largest logit leads
    picked = jnp.take_along_axis(top_idx, choice[:, None], axis=-1)[:, 0]
    among = jax.nn.softmax(filtered, axis=-1)
    confidence = jnp.take_along_axis(among, choice[:, None], axis=-1)[:, 0]
    return picked.astype(jnp.int32), confidence, rng
