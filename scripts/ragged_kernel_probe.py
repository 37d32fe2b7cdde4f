#!/usr/bin/env python3
"""The ragged attention kernel alone on the chip: what a row and a page
cost at a cell's geometry, for each KV block the walk could take.

    chiprun -- python3 scripts/ragged_kernel_probe.py --shape 1.5b \
        --blocks auto,1x1,2x1,4x1,8x1 [--parent _archive/parent]

For every ``--blocks`` entry (``auto``: what ``kv_block_pages`` chooses;
``AxB``: A pages a block at the small query tile, B at the whole chunk)
the kernel is compiled once and timed over ``--calls`` calls inside one
jitted loop (the dispatch is paid once), with every slot a decode row of
P pages, then every slot a whole chunk over P pages, for each P of
``--pages``.  A least-squares line through the P's gives the two terms
PERF.md quotes: us a row and us a page.  ``--parent`` times the kernel
of another checkout's ``ops/ragged_attention.py`` the same way.  Before
any timing: parity against the dense reference with every page no table
names, and the keys past ``kv_len`` of every last page, poisoned with NaN.

A probe, not a cell: it is read by no metric.  Its numbers are device
numbers only when it ran on the chip (the first line it prints).
"""

import argparse
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())  # run from the root of a checkout

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from operator_tpu.ops import ragged_attention as ours  # noqa: E402

#: heads, KV heads, slots of the benchmark's cells (BENCHMARK.json)
SHAPES = {
    "1.5b": (12, 2, 128), "7b": (28, 4, 32),
    "ouro": (16, 16, 10), "falcon": (20, 4, 128),
    # with --attend-block 4 --row-queries 1,4,8: rows that denoise a block
    "sdar": (32, 4, 128),
}
PAGE, HEAD_DIM, CHUNK, LAYERS = 64, 128, 64, 2


def load_parent(root: str):
    """Another checkout's kernel module, beside this one's package (its
    ``._flash_common`` import resolves here: that file is shared)."""
    path = os.path.join(root, "operator_tpu", "ops", "ragged_attention.py")
    spec = importlib.util.spec_from_file_location(
        "operator_tpu.ops.ragged_attention_parent", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def make_case(heads, kv_heads, slots, pages_a_row, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    num_pages = slots * pages_a_row + 1
    pool = (LAYERS, num_pages, PAGE, kv_heads, HEAD_DIM)
    q = jax.random.normal(keys[0], (slots, CHUNK, heads, HEAD_DIM), jnp.bfloat16)
    k = jax.random.normal(keys[1], pool, jnp.bfloat16)
    v = jax.random.normal(keys[2], pool, jnp.bfloat16)
    # the allocator hands pages out in no order worth keeping
    order = np.random.default_rng(seed).permutation(num_pages - 1) + 1
    table = jnp.asarray(order.reshape(slots, pages_a_row), jnp.int32)
    return q, k, v, table


def parity(kernel, heads, kv_heads, blocks):
    """Both rungs beside idle slots, NaN wherever no row may look."""
    slots, pages_a_row = 8, 12
    q, k, v, table = make_case(heads, kv_heads, slots, pages_a_row, seed=3)
    kv_len = np.array([1, 200, 0, 64, 448, 333, 577, 768], np.int32)
    q_count = np.array([1, 1, 0, 64, 64, 5, 8, 1], np.int32)
    table_np = np.asarray(table)
    named = np.zeros(k.shape[1], bool)
    dead = np.zeros(k.shape[1:3], bool)  # [page, key]
    for row in range(slots):
        live = -(-int(kv_len[row]) // PAGE)
        named[table_np[row, :live]] = True
        if kv_len[row] % PAGE:
            dead[table_np[row, live - 1], kv_len[row] % PAGE:] = True
    dead[~named] = True
    bad = jnp.asarray(dead)[None, :, :, None, None]
    k_bad, v_bad = (jnp.where(bad, jnp.nan, x) for x in (k, v))
    got = kernel(q, k_bad, v_bad, table, jnp.asarray(kv_len),
                 jnp.asarray(q_count), jnp.int32(1), **blocks)
    with jax.default_matmul_precision("highest"):
        want = ours.ragged_attention_reference(
            *(x.astype(jnp.float32) for x in (q, k, v)), table,
            jnp.asarray(kv_len), jnp.asarray(q_count), jnp.int32(1),
            **{k_: v_ for k_, v_ in blocks.items() if k_ == "attend_block"},
        )
    live = np.arange(CHUNK)[None, :] < q_count[:, None]
    g, w = np.asarray(got, np.float32)[live], np.asarray(want)[live]
    return {
        "finite": bool(np.isfinite(g).all()),
        "max_abs_err": float(np.nanmax(np.abs(g - w))),
        "ok": bool((np.abs(g - w) <= 2e-2 + 2e-2 * np.abs(w)).all()),
    }


def timer(kernel, case, calls, blocks):
    q, k, v, table = case

    @jax.jit
    def many(q, k, v, table, kv_len, q_count):
        def body(i, acc):
            out = kernel(q, k, v, table, kv_len, q_count, i % LAYERS, **blocks)
            return acc + out[:, 0, 0, 0].astype(jnp.float32)
        return jax.lax.fori_loop(
            0, calls, body, jnp.zeros((q.shape[0],), jnp.float32)
        )

    def us_a_call(kv_len, q_count, reps=5):
        args = (q, k, v, table, jnp.asarray(kv_len, jnp.int32),
                jnp.asarray(q_count, jnp.int32))
        many(*args).block_until_ready()
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            many(*args).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best / calls * 1e6

    return us_a_call


def line_fit(pages, us, slots):
    slope, intercept = np.polyfit(np.asarray(pages, float), np.asarray(us), 1)
    return {"us_a_row": intercept / slots, "us_a_page": slope / slots}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", required=True, choices=sorted(SHAPES))
    ap.add_argument("--blocks", default="auto")
    ap.add_argument("--pages", default="1,2,3,4,5,6,8,9,12,16,18,24")
    ap.add_argument("--chunk-pages", default="1,2,4,5,8,9,16,17")
    ap.add_argument("--calls", type=int, default=56)
    ap.add_argument("--parent", default=None)
    ap.add_argument("--attend-block", type=int, default=1,
                    help="the block-causal mask's block (models/sdar.py); 1 = causal")
    ap.add_argument("--row-queries", default="1",
                    help="queries of a row on the small tile, one fit for each")
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--interpret", action="store_true",
                    help="a CPU rehearsal of the script: its times mean nothing")
    ap.add_argument("--out", default="chiprun_out/kernel_probe")
    args = ap.parse_args()

    device = jax.devices()[0]
    print("device", device.platform, device.device_kind, flush=True)
    heads, kv_heads, slots = SHAPES[args.shape]
    slots = args.slots or slots
    pages = [int(p) for p in args.pages.split(",")]
    chunk_pages = [int(p) for p in args.chunk_pages.split(",")]
    case = make_case(heads, kv_heads, slots, max(pages + chunk_pages))
    geometry = dict(
        q_per_kv=heads // kv_heads, kv_heads=kv_heads, head_dim=HEAD_DIM,
        page_size=PAGE, itemsize=2,
    )
    chosen = tuple(
        ours.kv_block_pages(tile, **geometry) for tile in ours.query_tiles(CHUNK)
    )
    variants = []
    extra = {"interpret": True} if args.interpret else {}
    if args.attend_block != 1:
        extra["attend_block"] = args.attend_block
    for name in args.blocks.split(","):
        blocks = chosen if name == "auto" else tuple(
            int(n) for n in name.split("x")
        )
        variants.append((name, ours._ragged_attention_pallas,
                         {"block_pages": blocks, **extra}))
    if args.parent:
        variants.append(
            ("parent", load_parent(args.parent)._ragged_attention_pallas, extra)
        )

    result = {"shape": args.shape, "device": device.device_kind, "slots": slots,
              "auto": list(chosen), "variants": {}}
    for name, kernel, blocks in variants:
        entry = {"blocks": list(blocks.get("block_pages", ())),
                 "parity": parity(kernel, heads, kv_heads, blocks)}
        us_a_call = timer(kernel, case, args.calls, blocks)
        # the last page holds 47 of its 64 keys: a partial page, as a row's is
        decode = [us_a_call([p * PAGE - 17] * slots, [1] * slots) for p in pages]
        for queries in (int(n) for n in args.row_queries.split(",") if int(n) != 1):
            # rows of a few queries (a block, or a block led by the one before)
            times = [us_a_call([p * PAGE - 16] * slots, [queries] * slots) for p in pages]
            entry[f"rows_of_{queries}_us_a_call"] = dict(zip(map(str, pages), times))
            entry[f"rows_of_{queries}_fit"] = line_fit(pages, times, slots)
        chunk = [us_a_call([p * PAGE - 17] * slots, [CHUNK] * slots)
                 for p in chunk_pages]
        idle = us_a_call([6 * PAGE - 17] * slots, [1] + [0] * (slots - 1))
        entry["decode_us_a_call"] = dict(zip(map(str, pages), decode))
        entry["chunk_us_a_call"] = dict(zip(map(str, chunk_pages), chunk))
        entry["decode_fit"] = line_fit(pages, decode, slots)
        entry["chunk_fit"] = line_fit(chunk_pages, chunk, slots)
        entry["one_row_live_us_a_call"] = idle
        result["variants"][name] = entry
        print(json.dumps({name: entry}), flush=True)
        jax.clear_caches()

    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"{args.shape}.json"), "w") as fh:
        json.dump(result, fh, indent=1)


if __name__ == "__main__":
    main()
