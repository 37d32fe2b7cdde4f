#!/usr/bin/env python
"""Decode attribution at engine-block granularity (the real dispatch unit).

Times BatchedGenerator.step() — one lax.scan block of decode_block steps,
one host token fetch — under one-variable-at-a-time toggles:

  paged vs contiguous | sampler: topp/topk/greedy | donate cache or not

Env: PD_BLOCK (8), PD_SLOTS (16), PD_SEQ (1024), PD_STEPS (12 blocks).
"""

from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")

from operator_tpu.models import get_config, init_params
from operator_tpu.models.tokenizer import load_tokenizer
from operator_tpu.serving.engine import BatchedGenerator, SamplingParams

BLOCK = int(os.environ.get("PD_BLOCK", "8"))
SLOTS = int(os.environ.get("PD_SLOTS", "16"))
SEQ = int(os.environ.get("PD_SEQ", "1024"))
STEPS = int(os.environ.get("PD_STEPS", "12"))


def measure(params, config, *, paged, sampler, donate, block=BLOCK):
    gen = BatchedGenerator(
        params, config, load_tokenizer(None), max_slots=SLOTS, max_seq=SEQ,
        paged=paged, page_size=64, decode_block=block,
    )
    if sampler == "greedy":
        def greedy(logits, rng, temp, top_p):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), rng
        gen._sample = greedy
    elif sampler == "fullsort":
        # the pre-r3 sampler: full-vocab sort every step (what the engine
        # shipped before truncated top-k; kept here so the trade stays
        # measurable against sampler == "default")
        def fullsort(logits, rng, temp, top_p):
            vocab = logits.shape[-1]
            greedy_t = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            safe_temp = jnp.maximum(temp, 1e-4)[:, None]
            scaled = logits.astype(jnp.float32) / safe_temp
            sorted_logits, sorted_idx = jax.lax.top_k(scaled, vocab)
            probs = jax.nn.softmax(sorted_logits, axis=-1)
            cumulative = jnp.cumsum(probs, axis=-1) - probs
            keep = cumulative < top_p[:, None]
            filtered = jnp.where(keep, sorted_logits, -jnp.inf)
            rng, sub = jax.random.split(rng)
            choice = jax.random.categorical(sub, filtered, axis=-1)
            sampled = jnp.take_along_axis(sorted_idx, choice[:, None], axis=-1)[:, 0]
            return jnp.where(temp <= 0.0, greedy_t, sampled.astype(jnp.int32)), rng
        gen._sample = fullsort
    else:
        assert sampler == "default"  # engine's truncated top-k nucleus
    if donate:
        # re-jit the decode fn with cache donation (arg 1 in both layouts)
        fn = gen._decode_block_paged if paged else gen._decode_block
        gen._decode_fn = jax.jit(fn, donate_argnums=(1,))

    prompts = ["pod failed with exit code 137 " * 8] * SLOTS
    sampling = SamplingParams(max_tokens=BLOCK * (STEPS + 6), temperature=0.3,
                              stop_on_eos=False)
    gen.admit(prompts, [sampling] * SLOTS)
    # warm the decode program
    for _ in range(3):
        gen.step()
    t0 = time.perf_counter()
    for _ in range(STEPS):
        gen.step()
    dt = time.perf_counter() - t0
    ms_per_step = dt / (STEPS * block) * 1e3
    toks = SLOTS * STEPS * block / dt
    return ms_per_step, toks


def main():
    print(f"device: {jax.devices()[0]}  block={BLOCK} slots={SLOTS} seq={SEQ}",
          flush=True)
    config = get_config(os.environ.get("PD_MODEL", "tinyllama-1.1b"))
    params = jax.block_until_ready(
        jax.jit(lambda k: init_params(config, k, dtype=jnp.bfloat16))(
            jax.random.PRNGKey(0)
        )
    )

    cases = [
        dict(paged=True, sampler="default", donate=False),   # shipped config
        dict(paged=True, sampler="fullsort", donate=False),  # pre-r3 sampler
        dict(paged=True, sampler="greedy", donate=False),
        dict(paged=True, sampler="greedy", donate=True),
        dict(paged=False, sampler="default", donate=False),
        dict(paged=False, sampler="greedy", donate=False),
        dict(paged=False, sampler="greedy", donate=True),
        dict(paged=False, sampler="default", donate=True),
        dict(paged=True, sampler="default", donate=True),
    ]
    for case in cases:
        ms, toks = measure(params, config, **case)
        print(f"paged={case['paged']!s:5} sampler={case['sampler']:6} "
              f"donate={case['donate']!s:5} -> {ms:6.2f} ms/step  {toks:7.0f} tok/s",
              flush=True)


if __name__ == "__main__":
    main()
