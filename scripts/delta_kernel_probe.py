#!/usr/bin/env python3
"""The gated delta-rule kernel alone on the chip: parity with its
token-by-token reference at the hybrid cell's sizes, then what a decode
call costs against the state stream's floor.

    chiprun -- python3 scripts/delta_kernel_probe.py

One call's rows mix every case the mixed step hands the kernel: slots that
decode one token from a held state, slots that start a prompt (``fresh``:
the pool's contents must not be read), a slot whose chunk continues a
prompt, and idle slots, whose state must come back bit for bit.  Both
outputs, ``o`` on every live token and the state of every slot, are held
against ``ops/gated_delta.gated_delta_reference`` run under
``default_matmul_precision("highest")``.  Then every slot decodes one token
``--calls`` times inside one jitted loop (the dispatch paid once), and the
best of five is set beside the floor: the state read and written once a
call, ``2 x slots x heads x Dk x Dv x 4 B`` at 819 GB/s.

``--small --interpret`` rehearses the script on the CPU; its times mean
nothing.  A probe, not a cell: read by no metric.  Result under
``chiprun_out/kernel_probe/gated_delta.json``.
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

from operator_tpu.ops.gated_delta import (  # noqa: E402
    _gated_delta_pallas,
    gated_delta_reference,
    pack_pairs,
)

#: the cell's sizes (BENCHMARK.json olmo-hybrid-7b-int8.decode): slots,
#: flat tokens a step, linear heads, key and value widths
CELL = dict(slots=48, tokens=96, heads=30, key_dim=96, value_dim=192)
SMALL = dict(slots=6, tokens=16, heads=4, key_dim=16, value_dim=32)
LAYERS = 2
HBM_BYTES_PER_S = 819e9  # benchmark/peaks.json, TPU v5 lite


def rows(slots: int, tokens: int):
    """``q_count``, ``fresh``: two prompt rows (one starting, one carried
    on), two idle slots, every other slot one decode token, and the flat
    tokens the step has left over going to the starting prompt."""
    count = np.ones(slots, np.int32)
    fresh = np.zeros(slots, bool)
    count[[2, slots - 1]] = 0  # idle
    count[1] = 3 if tokens > 8 else 2  # a prompt's chunk carried on
    count[0] = tokens - int(count.sum()) + count[0]  # a prompt's first chunk
    fresh[0] = True
    fresh[3] = True  # a decode row whose slot was just given: one token from zero
    assert count[0] >= 1 and count.sum() == tokens, count
    return count, fresh


def inputs(sizes: dict, count, fresh, seed: int = 3):
    t, h, dk, dv = sizes["tokens"], sizes["heads"], sizes["key_dim"], sizes["value_dim"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)

    def unit(key):
        x = jax.random.normal(key, (t, h, dk), jnp.float32)
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(keys[0]) * dk**-0.5
    k = unit(keys[1])
    v = jax.random.normal(keys[2], (t, h, dv), jnp.float32).astype(jnp.bfloat16)
    g = -jax.nn.softplus(jax.random.normal(keys[3], (t, h))) * 0.5
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], (t, h)))
    held = jax.random.normal(keys[5], (LAYERS, sizes["slots"], h, dk, dv)) * 0.1
    state = pack_pairs(held)
    q_start = np.concatenate([[0], np.cumsum(count)[:-1]]).astype(np.int32)
    return q, k, v, g, beta, state, jnp.asarray(q_start), jnp.asarray(count), jnp.asarray(fresh)


def parity(sizes: dict, extra: dict) -> dict:
    count, fresh = rows(sizes["slots"], sizes["tokens"])
    q, k, v, g, beta, state, q_start, q_count, fresh_ = inputs(sizes, count, fresh)
    layer = jnp.int32(1)
    with jax.default_matmul_precision("highest"):
        want_o, want_state = gated_delta_reference(
            q, k, v, g, beta, state, layer, q_start, q_count, fresh_,
            chunk=int(count.max()),
        )
    before = np.asarray(state)
    got_o, got_state = _gated_delta_pallas(
        q, k, v, g, beta, state, layer, q_start, q_count, fresh_, **extra
    )
    live = np.repeat(count > 0, count)  # every token belongs to a live slot
    o_diff = np.abs(np.asarray(got_o)[live] - np.asarray(want_o)[live])
    s_diff = np.abs(np.asarray(got_state) - np.asarray(want_state))
    idle = np.flatnonzero(count == 0)
    return {
        "o_max_abs_diff": float(o_diff.max()),
        "o_scale": float(np.abs(np.asarray(want_o)[live]).max()),
        "state_max_abs_diff": float(s_diff.max()),
        "state_scale": float(np.abs(np.asarray(want_state)).max()),
        "idle_state_unchanged": bool(
            np.array_equal(np.asarray(got_state)[:, idle], before[:, idle])
        ),
        "other_layer_unchanged": bool(
            np.array_equal(np.asarray(got_state)[0], before[0])
        ),
    }


def decode_timing(sizes: dict, calls: int, extra: dict) -> dict:
    slots = sizes["slots"]
    count = np.ones(slots, np.int32)
    fresh = np.zeros(slots, bool)
    sized = dict(sizes, tokens=slots)
    q, k, v, g, beta, state, q_start, q_count, fresh_ = inputs(sized, count, fresh)

    @jax.jit
    def many(state):
        def body(i, s):
            _, s = _gated_delta_pallas(
                q, k, v, g, beta, s, i % LAYERS, q_start, q_count, fresh_, **extra
            )
            return s
        return jax.lax.fori_loop(0, calls, body, state)

    state = many(state).block_until_ready()
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        state = many(state).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    state_bytes = slots * sizes["heads"] * sizes["key_dim"] * sizes["value_dim"] * 4
    return {
        "decode_call_us": best / calls * 1e6,
        "floor_us": 2 * state_bytes / HBM_BYTES_PER_S * 1e6,
        "calls": calls,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=48)
    ap.add_argument("--small", action="store_true", help="the CPU rehearsal's sizes")
    ap.add_argument("--interpret", action="store_true",
                    help="a CPU rehearsal of the script: its times mean nothing")
    ap.add_argument("--out", default="chiprun_out/kernel_probe")
    args = ap.parse_args()

    device = jax.devices()[0]
    print("device", device.platform, device.device_kind, flush=True)
    sizes = SMALL if args.small else CELL
    extra = {"interpret": True} if args.interpret else {}
    result = {"device": device.device_kind, "sizes": sizes,
              "parity": parity(sizes, extra),
              "timing": decode_timing(sizes, args.calls, extra)}
    print(json.dumps(result), flush=True)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "gated_delta.json"), "w") as fh:
        json.dump(result, fh, indent=1)


if __name__ == "__main__":
    main()
