#!/usr/bin/env python3
"""The latent attention kernel alone on the chip: parity with its dense
reference at the DeepSeek-V2-Lite cell's geometry, then what a call costs
against the latent stream's floor, for rows of 1 to 2,048 positions.

    python3 scripts/latent_kernel_probe.py [--blocks auto,4x8] [--out DIR]     (on a TPU)

Parity: one call whose slots mix what the mixed step hands the kernel:
decode rows of one query at contexts from 1 to 2,000 positions, prompt
chunks of 64 and of 5 queries, idle slots; the pool is poisoned with NaN
wherever no row was written (a last page's tail, pages no row owns), so a
value row past ``kv_len`` that reached the value product would show.  The
outputs of every live token are held against ``ops/latent_attention.
latent_attention_reference`` on the same pool with zeros for the poison,
under ``default_matmul_precision("highest")``.

Timing: every slot of 128 decodes one token at a context of ``L``
positions, ``--calls`` calls inside one jitted loop (the dispatch paid
once), best of five, beside the floor: the latent and rope pages the walk
copies (``1,152 B`` a position, whole pages) and the queries in and outputs
out, at 819 GB/s; then the cell's own mix, 114 decode rows at ~1,500
positions and 14 prompt chunks of 64 at ~700.  ``AxB`` in ``--blocks``
gives the decode rung A pages a fold and the chunk rung B.

``--small --interpret`` rehearses the script on the CPU; its times mean
nothing.  A probe, not a cell: read by no metric.  Result in
``latent_attention.json`` under ``--out`` (``benchmark/out/kernel_probe``).
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

from operator_tpu.ops.latent_attention import (  # noqa: E402
    _latent_attention_pallas,
    latent_attention_reference,
    pool_shapes,
    rope_queries,
    write_latent,
)

#: the cell's geometry (BENCHMARK.json deepseek-v2-lite-int8.decode-long)
CELL = dict(slots=128, tokens=1024, heads=16, rank=512, rope=64, page=64, chunk=64,
            max_seq=2048, scale=0.11472138679292611)
SMALL = dict(slots=6, tokens=48, heads=4, rank=32, rope=16, page=16, chunk=8,
             max_seq=256, scale=0.2)
PLANES = 2
HBM_BYTES_PER_S = 819e9  # benchmark/peaks.json, TPU v5 lite


def pool(sizes: dict, lens: np.ndarray, seed: int):
    """Page tables of disjoint pages and a pool holding each slot's first
    ``lens`` positions in both planes; ``(latent, rope, poisoned latent,
    poisoned rope, page_table)``, the poisoned pair NaN everywhere else."""
    slots, page = sizes["slots"], sizes["page"]
    per_seq = sizes["max_seq"] // page
    pages = slots * per_seq + 1
    rng = np.random.default_rng(seed)
    table = (1 + rng.permutation(pages - 1)).reshape(slots, per_seq).astype(np.int32)
    latent_shape, rope_shape = pool_shapes(PLANES, pages, page, sizes["rank"], sizes["rope"])
    page_ids = np.concatenate([table[b, np.arange(n) // page] for b, n in enumerate(lens)])
    slots_of = np.concatenate([np.arange(n) % page for n in lens])
    # the flat axis of every slot's positions, a slot's contiguous
    rows = jnp.asarray(np.repeat(np.arange(slots), lens).astype(np.int32))
    positions = jnp.asarray(np.concatenate([np.arange(n) for n in lens]).astype(np.int32))
    total = int(lens.sum())
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    c = jax.random.normal(keys[0], (total, sizes["rank"]), jnp.float32).astype(jnp.bfloat16)
    kr = jax.random.normal(keys[1], (total, sizes["rope"]), jnp.float32).astype(jnp.bfloat16)
    out = []
    for fill in (0.0, np.nan):
        latent = jnp.full(latent_shape, fill, jnp.bfloat16)
        rope = jnp.full(rope_shape, fill, jnp.bfloat16)
        for layer in range(PLANES):
            latent, rope = write_latent(
                latent, rope, layer, jnp.asarray(page_ids), jnp.asarray(slots_of), c, kr,
                rows, positions, jnp.ones(positions.shape, bool),
            )
        out += [latent, rope]
    return (*out, jnp.asarray(table))


def queries(sizes: dict, tokens: int, seed: int):
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), 2)
    h = sizes["heads"]
    q_lat = jax.random.normal(keys[0], (tokens, h, sizes["rank"]), jnp.float32) * 0.05
    q_rope = jax.random.normal(keys[1], (tokens, h, sizes["rope"]), jnp.float32) * 0.3
    return q_lat.astype(jnp.bfloat16), q_rope.astype(jnp.bfloat16)


def parity(sizes: dict, extra: dict) -> dict:
    slots, chunk, top = sizes["slots"], sizes["chunk"], sizes["max_seq"] - 48
    count = np.ones(slots, np.int32)
    count[[2, slots - 1]] = 0  # idle
    count[0], count[1] = chunk, 5  # a whole chunk, a prompt's last few
    lens = np.linspace(1, top, slots).astype(np.int32)
    lens = np.maximum(lens, count)
    lens[3] = 1  # a decode row of one position: its own
    lens[2] = 0
    q_start = np.concatenate([[0], np.cumsum(count)[:-1]]).astype(np.int32)
    tokens = max(int(count.sum()), chunk)
    latent, rope, bad_latent, bad_rope, table = pool(sizes, lens, seed=5)
    q_lat, q_rope = queries(sizes, tokens, seed=5)
    args = (table, jnp.asarray(lens), jnp.asarray(q_start), jnp.asarray(count), jnp.int32(1))
    with jax.default_matmul_precision("highest"):
        want = latent_attention_reference(
            q_lat, q_rope, latent, rope, *args, scale=sizes["scale"],
        )
    got = _latent_attention_pallas(
        q_lat, rope_queries(q_rope), bad_latent, bad_rope, *args,
        scale=sizes["scale"], chunk=chunk, **extra,
    )
    live = np.zeros(tokens, bool)
    for start, n in zip(q_start, count):
        live[start:start + n] = True
    diff = np.abs(np.asarray(got, np.float32)[live] - np.asarray(want, np.float32)[live])
    return {
        "max_abs_diff": float(np.nanmax(diff)) if diff.size else 0.0,
        "nan": bool(np.isnan(np.asarray(got, np.float32)[live]).any()),
        "scale": float(np.abs(np.asarray(want, np.float32)[live]).max()),
        "contexts": [int(lens.min()), int(lens.max())],
    }


def timed(sizes: dict, count: np.ndarray, lens: np.ndarray, calls: int, extra: dict) -> dict:
    q_start = np.concatenate([[0], np.cumsum(count)[:-1]]).astype(np.int32)
    tokens = max(int(count.sum()), sizes["chunk"])
    latent, rope, _, _, table = pool(sizes, lens, seed=7)
    q_lat, q_rope = queries(sizes, tokens, seed=7)
    parts = rope_queries(q_rope)
    args = (table, jnp.asarray(lens), jnp.asarray(q_start), jnp.asarray(count))

    @jax.jit
    def many(q_lat):
        def body(i, q):
            out = _latent_attention_pallas(
                q, parts, latent, rope, *args, i % PLANES,
                scale=sizes["scale"], chunk=sizes["chunk"], **extra,
            )
            return q + 0 * out[:, :, :1]
        return jax.lax.fori_loop(0, calls, body, q_lat)

    many(q_lat).block_until_ready()
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        many(q_lat).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    page = sizes["page"]
    pages = int((-(-lens // page))[count > 0].sum())
    token_bytes = (sizes["rank"] + sizes["rope"]) * 2
    moved = pages * page * token_bytes + int(count.sum()) * sizes["heads"] * (
        sizes["rank"] + sizes["rope"] + sizes["rank"]
    ) * 2
    call_us = best / calls * 1e6
    return {
        "call_us": call_us, "pages": pages, "us_per_page": call_us / max(pages, 1),
        "floor_us": moved / HBM_BYTES_PER_S * 1e6,
        "floor_share": moved / HBM_BYTES_PER_S * 1e6 / call_us,
        "calls": calls,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=28)
    ap.add_argument("--blocks", default="auto", help="auto, or AxB (decode, chunk rung)")
    ap.add_argument("--small", action="store_true", help="the CPU rehearsal's sizes")
    ap.add_argument("--interpret", action="store_true",
                    help="a CPU rehearsal of the script: its times mean nothing")
    ap.add_argument("--out", default="benchmark/out/kernel_probe")
    args = ap.parse_args()

    device = jax.devices()[0]
    print("device", device.platform, device.device_kind, flush=True)
    sizes = SMALL if args.small else CELL
    base = {"interpret": True} if args.interpret else {}
    result = {"device": device.device_kind, "sizes": sizes, "runs": {}}
    slots = sizes["slots"]
    for blocks in args.blocks.split(","):
        extra = dict(base)
        if blocks != "auto":
            extra["block_of"] = tuple(int(n) for n in blocks.split("x"))
        run = {"parity": parity(sizes, extra), "decode": {}}
        print(blocks, "parity", run["parity"], flush=True)
        for length in (64, 512, 1024, 1536, 2048):
            length = min(length, sizes["max_seq"])
            run["decode"][length] = timed(
                sizes, np.ones(slots, np.int32), np.full(slots, length, np.int32),
                args.calls, extra,
            )
            print(blocks, "decode", length, run["decode"][length], flush=True)
        # the cell's mix: 114 decode rows at ~1,500 positions, 14 chunks of 64
        count = np.ones(slots, np.int32)
        chunks = min(14, slots // 4)
        count[:chunks] = sizes["chunk"]
        rng = np.random.default_rng(11)
        top = sizes["max_seq"] - 48
        lens = rng.integers(int(0.56 * top), top, slots).astype(np.int32)
        lens[:chunks] = rng.integers(sizes["chunk"], top // 2, chunks)
        run["cell_mix"] = timed(sizes, count, lens, args.calls, extra)
        print(blocks, "cell mix", run["cell_mix"], flush=True)
        result["runs"][blocks] = run
    print(json.dumps(result), flush=True)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "latent_attention.json"), "w") as fh:
        json.dump(result, fh, indent=1)


if __name__ == "__main__":
    main()
