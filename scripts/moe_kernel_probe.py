#!/usr/bin/env python3
"""The grouped expert product alone on the chip, at the benchmark cell's
sizes: ``--tokens`` of 1,024 flat tokens valid (the rest routed nowhere),
each to 8 of a layer's 128 int8 experts of 2048 x 768.

    chiprun -- python3 scripts/moe_kernel_probe.py [--tiles 32,64,128]

Parity first (the kernel against ``moe_experts_reference``, a plain product
over all experts, on the same routing), then for every row tile the whole
op (the row layout, the two gathers and the kernel) timed over ``--calls``
calls inside one jitted loop, alternating the stack's two layers; beside
it the plain product's time, and what reading the hit experts once would
take (604 MB a layer over 819 GB/s = 0.74 ms).  A probe, not a cell: it is
read by no metric.  Its numbers are device numbers only when it ran on the
chip (the first line it prints); ``--interpret --small`` rehearses the
script on the CPU.
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

from operator_tpu.models.quant import quantize_matrix  # noqa: E402
from operator_tpu.ops import moe_experts as moe  # noqa: E402

LAYERS = 2


def make_case(budget, tokens, experts, top, hidden, inner, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    draw = jax.jit(
        lambda key, shape: quantize_matrix(
            (jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5).astype(jnp.bfloat16)
        ), static_argnums=1,
    )
    stacks = [
        draw(keys[0], (LAYERS, experts, hidden, inner)),
        draw(keys[1], (LAYERS, experts, hidden, inner)),
        draw(keys[2], (LAYERS, experts, inner, hidden)),
    ]
    x = jax.random.normal(keys[3], (budget, hidden), jnp.bfloat16)
    gates, ids = jax.lax.top_k(jax.nn.softmax(jax.random.normal(keys[4], (budget, experts))), top)
    gates = gates / gates.sum(-1, keepdims=True)
    ids = jnp.where((jnp.arange(budget) < tokens)[:, None], ids.astype(jnp.int32), experts)
    return x, ids, gates, stacks


def us_a_call(fn, x, ids, gates, stacks, calls, reps=5):
    @jax.jit
    def many(x, ids, gates, stacks):
        def body(i, acc):
            return acc + fn(x, ids, gates, *stacks, i % LAYERS)[:, 0]
        return jax.lax.fori_loop(0, calls, body, jnp.zeros((x.shape[0],), jnp.float32))

    many(x, ids, gates, stacks).block_until_ready()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        many(x, ids, gates, stacks).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / calls * 1e6


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=int, default=1024)
    ap.add_argument("--tokens", default="768,1024,128")
    ap.add_argument("--tiles", default="32,64,128")
    ap.add_argument("--calls", type=int, default=24)
    ap.add_argument("--small", action="store_true", help="8 experts of 128 x 64")
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--out", default="chiprun_out/kernel_probe")
    args = ap.parse_args()
    device = jax.devices()[0]
    print("device", device.platform, device.device_kind, flush=True)
    experts, top, hidden, inner = (8, 2, 128, 64) if args.small else (128, 8, 2048, 768)
    extra = {"interpret": True} if args.interpret else {}
    result = {"device": device.device_kind, "experts": experts, "cases": {}}
    for tokens in (int(n) for n in args.tokens.split(",")):
        x, ids, gates, stacks = make_case(args.budget, tokens, experts, top, hidden, inner)
        want = np.asarray(moe.moe_experts_reference(x, ids, gates, *stacks, jnp.int32(1)))
        hit = int((np.asarray(moe.expert_counts(ids, experts)) > 0).sum())
        entry = {
            "experts_hit": hit,
            "read_once_us": hit * 3 * hidden * inner / 819e9 * 1e6,
            "plain_us_a_call": us_a_call(
                moe.moe_experts_reference, x, ids, gates, stacks, max(2, args.calls // 8)
            ),
            "tiles": {},
        }
        for tile in (int(n) for n in args.tiles.split(",")):
            def kernel(*a, tile=tile):
                return moe._moe_experts_pallas(*a, tile=tile, **extra)

            got = np.asarray(kernel(x, ids, gates, *stacks, jnp.int32(1)))
            entry["tiles"][str(tile)] = {
                "max_abs_err": float(np.abs(got - want).max()),
                "max_abs": float(np.abs(want).max()),
                "tiles_filled": int(moe.group_rows(ids, experts, tile)["n_tiles"]),
                "us_a_call": us_a_call(kernel, x, ids, gates, stacks, args.calls),
            }
        result["cases"][str(tokens)] = entry
        print(json.dumps({tokens: entry}), flush=True)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "moe_experts.json"), "w") as fh:
        json.dump(result, fh, indent=1)


if __name__ == "__main__":
    main()
