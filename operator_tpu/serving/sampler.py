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

    safe_temp = jnp.maximum(temp, 1e-4)[:, None]
    scaled = logits.astype(jnp.float32) / safe_temp
    k = min(top_k, logits.shape[-1])
    top_logits, top_idx = jax.lax.top_k(scaled, k)
    probs = jax.nn.softmax(top_logits, axis=-1)
    cumulative = jnp.cumsum(probs, axis=-1) - probs  # exclusive prefix
    keep = cumulative < top_p[:, None]  # first token always kept
    filtered = jnp.where(keep, top_logits, -jnp.inf)
    rng, sub = jax.random.split(rng)
    choice = jax.random.categorical(sub, filtered, axis=-1)
    sampled = jnp.take_along_axis(top_idx, choice[:, None], axis=-1)[:, 0]
    picked = jnp.where(temp <= 0.0, greedy, sampled.astype(jnp.int32))
    return picked, rng
