#!/usr/bin/env python3
"""The sampler alone on the chip: what a call costs at each cell's logit
rows and vocabulary, with the whole-row ``lax.top_k`` beside it.

    chiprun -- python3 scripts/sampler_probe.py [--shapes 128x151936,...]

For every ``rows x vocab`` of ``--shapes`` (default: the benchmark's
cells, BENCHMARK.json) three programs are compiled once and timed over
``--calls`` calls inside one jitted loop (the dispatch is paid once; the
logits are shifted by the loop's counter so that nothing of a call can be
hoisted out of it), least of five:

- ``plain``: the candidates by ``jax.lax.top_k`` over the whole row,
  ``_nucleus`` as it stood until PR 37 (kept whole here: the chip sorts a
  row ten times slower when the division before it is laid out otherwise);
- ``nucleus``: ``serving/sampler.py _nucleus`` as it stands;
- ``sample``: the whole of ``sampler.sample`` (the argmax, the
  candidates, the draw), or ``sample_with_confidence`` for a shape given
  as ``rowsxvocab:c`` (rows that denoise a block).

``--stages`` times the parts of the pruned selection too.  Before any
timing: ``nucleus`` against ``plain``, bit for bit, on rows with planted
ties, on this device.

A probe, not a cell: it is read by no metric.  Its numbers are device
numbers only when it ran on the chip (the first line it prints).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())  # run from the root of a checkout

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from operator_tpu.serving import sampler  # noqa: E402

#: logit rows a step and vocabulary of the benchmark's cells: both 1.5B
#: cells, SDAR (4 rows a slot, with confidences), both 7B, Falcon, Ouro
CELLS = "128x151936,512x151936:c,32x152064,128x261120,10x49152"
TOP_K = sampler.SAMPLE_TOP_K
LANE = sampler._LANE


def plain_nucleus(logits, temp, top_p, top_k):
    safe_temp = jnp.maximum(temp, 1e-4)[:, None]
    scaled = logits.astype(jnp.float32) / safe_temp
    top_logits, top_idx = jax.lax.top_k(scaled, top_k)
    probs = jax.nn.softmax(top_logits, axis=-1)
    cumulative = jnp.cumsum(probs, axis=-1) - probs
    keep = cumulative < top_p[:, None]
    return top_idx, jnp.where(keep, top_logits, -jnp.inf)


def planted(rows, vocab, seed=0):
    """Two-decimal logits (ties in every row) with the patterns of
    tests/test_sampler.py in the first rows."""
    rng = np.random.default_rng(seed)
    x = np.round(rng.normal(size=(rows, vocab)).astype(np.float32) * 3, 2)
    k = TOP_K
    x[0] = 0.25
    x[1 % rows, 5 * LANE - k: 5 * LANE + k] = 19.0
    x[2 % rows, min(151_669, vocab - 3)] = -np.inf
    x[2 % rows, 2 * LANE: 4 * LANE] = -np.inf
    x[3 % rows, rng.choice(vocab // 2, size=k - 1, replace=False)] = 22.0
    x[3 % rows, vocab - 3 * LANE - 2: vocab - 3 * LANE + 2] = 21.0
    x[4 % rows] = -1.0
    x[4 % rows, LANE // 2:: LANE] = 3.0
    x[4 % rows, 7:: 2 * LANE] = 3.0
    return x


def parity(rows, vocab):
    logits = jnp.asarray(planted(rows, vocab, seed=vocab))
    temp = jnp.asarray(np.resize(np.array([0.3, 1.0, 1e-4, 0.7], np.float32), rows))
    top_p = jnp.asarray(np.resize(np.array([0.95, 1.0, 1e-6], np.float32), rows))
    got = jax.jit(sampler._nucleus, static_argnames="top_k")(
        logits, temp, top_p, top_k=TOP_K)
    want = jax.jit(plain_nucleus, static_argnames="top_k")(
        logits, temp, top_p, top_k=TOP_K)
    return {
        "ids_equal": bool(np.array_equal(np.asarray(got[0]), np.asarray(want[0]))),
        "filtered_bits_equal": bool(np.array_equal(
            np.asarray(got[1]).view(np.uint32), np.asarray(want[1]).view(np.uint32)
        )),
    }


def ms_a_call(fn, logits, calls, reps=5):
    """``fn(logits) -> a pytree of arrays``, timed inside one loop."""

    @jax.jit
    def many(logits):
        def body(i, acc):
            out = fn(logits + i.astype(jnp.float32) * 1e-3, i)
            return acc + sum(
                jnp.sum(leaf.astype(jnp.float32)[..., :1])
                for leaf in jax.tree_util.tree_leaves(out)
            )
        return jax.lax.fori_loop(0, calls, body, jnp.float32(0))

    many(logits).block_until_ready()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        many(logits).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / calls * 1e3


def programs(rows, vocab, confident, stages):
    temp = jnp.full((rows,), 0.3, jnp.float32)
    top_p = jnp.full((rows,), 0.95, jnp.float32)
    key = jax.random.PRNGKey(0)
    draw = sampler.sample_with_confidence if confident else sampler.sample
    out = {
        "plain": lambda x, i: plain_nucleus(x, temp, top_p, TOP_K),
        "nucleus": lambda x, i: sampler._nucleus(x, temp, top_p, TOP_K),
        "sample": lambda x, i: draw(
            x, jax.random.fold_in(key, i), temp, top_p, top_k=TOP_K),
    }
    if stages and vocab % LANE == 0:
        blocks = vocab // LANE
        ids = jnp.sort(jax.random.randint(key, (rows, TOP_K), 0, blocks), axis=-1)
        out.update({
            "stage_block_max": lambda x, i: jnp.max(
                (x / 0.3).reshape(rows, blocks, LANE), axis=-1),
            "stage_block_top_k": lambda x, i: jax.lax.top_k(
                x[:, :blocks] / 0.3, TOP_K),
            "stage_gather": lambda x, i: jnp.take_along_axis(
                (x / 0.3).reshape(rows, blocks, LANE),
                ((ids + i) % blocks)[:, :, None], axis=1),
            "stage_candidates_top_k": lambda x, i: jax.lax.top_k(
                x[:, : TOP_K * LANE] / 0.3, TOP_K),
        })
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=CELLS)
    ap.add_argument("--calls", type=int, default=40)
    ap.add_argument("--stages", action="store_true")
    ap.add_argument("--out", default="chiprun_out/kernel_probe")
    args = ap.parse_args()

    device = jax.devices()[0]
    print("device", device.platform, device.device_kind, flush=True)
    result = {"device": device.device_kind, "top_k": TOP_K, "lane": LANE,
              "calls": args.calls, "shapes": {}}
    for shape in args.shapes.split(","):
        size, _, flag = shape.partition(":")
        rows, vocab = (int(n) for n in size.split("x"))
        entry = {"parity": parity(min(rows, 16), vocab)}
        logits = jax.random.normal(
            jax.random.PRNGKey(rows), (rows, vocab), jnp.float32) * 3
        for name, fn in programs(rows, vocab, flag == "c", args.stages).items():
            ms = ms_a_call(fn, logits, args.calls)
            entry[name] = {"ms_a_call": ms, "ns_an_element": ms * 1e6 / (rows * vocab)}
        result["shapes"][shape] = entry
        print(json.dumps({shape: entry}), flush=True)
        jax.clear_caches()

    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "sampler.json"), "w") as fh:
        json.dump(result, fh, indent=1)


if __name__ == "__main__":
    main()
