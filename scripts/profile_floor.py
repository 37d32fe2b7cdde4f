#!/usr/bin/env python
"""Hardware floors for the decode roofline on this chip.

1. HBM bandwidth: elementwise update over a 1 GB array.
2. MXU: 8192^3 bf16 matmul.
3. Weights-streaming floor: lax.scan over 22 stacked TinyLlama layers,
   batch-16 activations through the 7 layer matmuls + lm_head — the decode
   step minus attention/cache/sampling. Run as a scan-of-K outer block like
   the engine's decode block.

FLOOR_SMOKE=1 shrinks every leg to trivial CPU shapes (MiB transfer,
256^3 matmul, 2 layers) and pins the cpu backend: it proves the probes
compile+run without the chip — round 3 lost its floor measurement to a
leg first executed ON the chip that didn't compile.
"""

from __future__ import annotations

import os
import sys
import time

SMOKE = os.environ.get("FLOOR_SMOKE", "0") == "1"
if SMOKE:
    os.environ["JAX_PLATFORMS"] = "cpu"  # the smoke never opens a chip

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")


def fetch_time(probe_fn, iters, warmup=2):
    for _ in range(warmup):
        p = probe_fn()
    np.asarray(p)
    t0 = time.perf_counter()
    for _ in range(iters):
        p = probe_fn()
    np.asarray(p)
    return (time.perf_counter() - t0) / iters


def main():
    print(f"device: {jax.devices()[0]}", flush=True)
    key = jax.random.PRNGKey(0)

    # 1. HBM bandwidth ------------------------------------------------------
    nbytes = 1 << (20 if SMOKE else 30)
    x = jnp.zeros((nbytes // 2,), jnp.bfloat16)

    @jax.jit
    def bump(x):
        return x * 1.0001 + 1.0

    state = {"x": x}
    def step():
        state["x"] = bump(state["x"])
        return state["x"][:1]
    dt = fetch_time(step, iters=10)
    # read + write = 2x nbytes per iteration (2 GB full, 2 MiB smoke)
    print(f"HBM elementwise: {dt*1e3:.2f} ms for {nbytes/2**30:.3g} GiB r+w -> "
          f"{2*nbytes/dt/1e9:.0f} GB/s", flush=True)

    # 2. MXU ---------------------------------------------------------------
    n = 256 if SMOKE else 8192
    a = jax.random.normal(key, (n, n), jnp.bfloat16)

    @jax.jit
    def mat(a):
        return a @ a

    state = {"a": a}
    def step2():
        state["a"] = mat(state["a"])
        return state["a"][:1, :1]
    dt = fetch_time(step2, iters=10)
    print(f"MXU {n}^3 bf16: {dt*1e3:.2f} ms -> {2*n**3/dt/1e12:.0f} TFLOP/s", flush=True)

    # 3. weights-streaming floor -------------------------------------------
    if SMOKE:
        B, H, F, L = 4, 128, 256, 2
        QH, KH, D, V = 4, 2, 32, 1024
    else:
        B, H, F, L = 16, 2048, 5632, 22
        QH, KH, D, V = 32, 4, 64, 32000
    keys = jax.random.split(key, 8)
    layers = {
        "wq": jax.random.normal(keys[0], (L, H, QH * D), jnp.bfloat16),
        "wk": jax.random.normal(keys[1], (L, H, KH * D), jnp.bfloat16),
        "wv": jax.random.normal(keys[2], (L, H, KH * D), jnp.bfloat16),
        "wo": jax.random.normal(keys[3], (L, QH * D, H), jnp.bfloat16),
        "w_gate": jax.random.normal(keys[4], (L, H, F), jnp.bfloat16),
        "w_up": jax.random.normal(keys[5], (L, H, F), jnp.bfloat16),
        "w_down": jax.random.normal(keys[6], (L, F, H), jnp.bfloat16),
    }
    head = jax.random.normal(keys[7], (H, V), jnp.bfloat16)
    wbytes = sum(w.nbytes for w in jax.tree_util.tree_leaves(layers)) + head.nbytes
    print(f"streamed weights: {wbytes/1e9:.2f} GB", flush=True)

    def layer_step(x, w):
        q = x @ w["wq"]
        k = x @ w["wk"]
        v = x @ w["wv"]
        x = x + (q * 0.01) @ w["wo"] + (k @ w["wk"].T + v @ w["wv"].T) * 1e-6
        gate = jax.nn.silu(x @ w["w_gate"])
        up = x @ w["w_up"]
        x = x + (gate * up) @ w["w_down"]
        return x * 0.999, None

    def one_token(x, layers, head):
        x, _ = jax.lax.scan(layer_step, x, layers)
        logits = (x @ head).astype(jnp.float32)
        return x * 0.9 + logits[:, :H].astype(jnp.bfloat16) * 1e-6

    for K in (1, 8):
        # weights are runtime ARGUMENTS, not closed-over constants: capturing
        # 2 GB as constants makes lowering/compile pathologically slow and
        # lets XLA constant-fold the thing being measured
        @jax.jit
        def block(x, layers, head, K=K):
            def body(x, _):
                return one_token(x, layers, head), None
            x, _ = jax.lax.scan(body, x, None, length=K)
            return x

        x0 = jax.random.normal(key, (B, H), jnp.bfloat16)
        state3 = {"x": x0}
        def step3():
            state3["x"] = block(state3["x"], layers, head)
            return state3["x"][:1, :1]
        dt = fetch_time(step3, iters=8)
        per = dt / K
        print(f"stream floor (block {K}): {per*1e3:.2f} ms/token-step -> "
              f"{wbytes/per/1e9:.0f} GB/s effective", flush=True)


if __name__ == "__main__":
    main()
