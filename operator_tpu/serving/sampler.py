"""The sampler: one function, traced into every program that samples.

The continuous scheduler's mixed step (``sched/mixed.py``) and the wave
engine's decode / prefill / finish programs (``programs.py``) call the
same :func:`sample` through their runtime's ``sample`` attribute, which
binds ``top_k`` (``serving/runtime.py``).
"""

from __future__ import annotations

#: nucleus-sampling candidate-set size (constructor: ``sample_top_k``).
#: A full-vocab ``top_k`` is a 32k-128k element sort on the TPU vector
#: units EVERY decode step, so sampling is truncated to the top-k
#: candidates FIRST and the top-p cutoff computed within them — i.e.
#: the served distribution is top-k AND top-p composed, the standard
#: serving trade.  At this system's temperatures (0.3 default,
#: aiprovider-crd.yaml:56-58) the top-64 hold ~all the nucleus mass; at
#: temperatures ~1+ the truncation measurably narrows diversity vs true
#: nucleus sampling — raise sample_top_k (e.g. 256) if that matters
#: more than decode latency.
SAMPLE_TOP_K = 64


def _nucleus(logits, temp, top_p, top_k: int):
    """The candidates a row is sampled from: ``(top_idx [B, k], filtered
    [B, k])``, the ``k`` largest logits' ids and their temperature-scaled
    logits with minus infinity outside the nucleus."""
    import jax
    import jax.numpy as jnp

    safe_temp = jnp.maximum(temp, 1e-4)[:, None]
    scaled = logits.astype(jnp.float32) / safe_temp
    k = min(top_k, logits.shape[-1])
    top_logits, top_idx = jax.lax.top_k(scaled, k)
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
