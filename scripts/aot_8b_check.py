#!/usr/bin/env python
"""AOT-compile the Llama-3-8B int8 serving programs for v5e — no chip.

The north-star config (BASELINE.md: Llama-3-8B on a 16 GB v5e chip) has
never produced an on-chip number (VERDICT r4).  The CPU end-to-end run
(`RUN_8B_CPU=1`) proves the graph composes; THIS check makes the memory
claim chip-credible: the 8B int8 prefill and decode programs are lowered
and compiled against an abstract v5e topology, and the XLA compiler's own
memory analysis (argument/output/temp bytes) is reported against the
16 GB HBM budget.  `jax.eval_shape` supplies the quantized parameter and
KV-cache trees as shapes only — nothing is materialised.

Prints one JSON line; exit 1 on compile failure or budget overflow, 42
when this jax install has no TPU compiler (skip sentinel, matching
scripts/aot_tpu_check.py).
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

HBM_BYTES = 16e9  # v5e chip
SLOTS, MAX_SEQ = 8, 2048  # the 8B bench shape


def _size(tree) -> int:
    return sum(
        math.prod(x.shape) * x.dtype.itemsize for x in jax.tree.leaves(tree)
    )


def main() -> int:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2x1"
        )
    except Exception as exc:
        if os.environ.get("AOT_TPU_TOPOLOGY"):
            raise
        print(f"SKIP: no TPU topology support here ({exc})", file=sys.stderr)
        return 42
    sharding = SingleDeviceSharding(topo.devices[0])

    from operator_tpu.models.configs import LLAMA_3_8B
    from operator_tpu.models.llama import KVCache, forward, init_params
    from operator_tpu.models.quant import quantize_params

    config = dataclasses.replace(LLAMA_3_8B, max_seq_len=MAX_SEQ)

    def place(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
            tree,
        )

    params = place(jax.eval_shape(
        lambda key: quantize_params(
            init_params(config, key, dtype=jnp.bfloat16), config
        ),
        jax.random.PRNGKey(0),
    ))
    cache = place(jax.eval_shape(
        lambda: KVCache.create(config, SLOTS, MAX_SEQ, dtype=jnp.bfloat16)
    ))

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def prefill(params, cache, ids, lengths):
        positions = jnp.broadcast_to(
            jnp.arange(MAX_SEQ, dtype=jnp.int32)[None], (SLOTS, MAX_SEQ)
        )
        kv_valid = positions < lengths[:, None]
        logits, cache = forward(
            params, config, ids, positions, cache=cache, cache_offset=0,
            kv_valid=kv_valid, prefill_lengths=lengths,
        )
        return logits[:, -1, :], cache

    def decode(params, cache, tokens, offsets):
        logits, cache = forward(
            params, config, tokens, offsets[:, None], cache=cache,
            cache_offset=offsets,
        )
        return jnp.argmax(logits[:, -1, :], axis=-1), cache

    record = {
        "metric": "aot_8b_v5e",
        "model": config.name,
        "slots": SLOTS,
        "max_seq": MAX_SEQ,
        "weights_int8_gb": round(_size(params) / 1e9, 2),
        "kv_cache_gb": round(_size(cache) / 1e9, 2),
        "hbm_budget_gb": HBM_BYTES / 1e9,
        "programs": {},
    }
    failed = 0
    cases = [
        # decode first: the latency-critical program, and the cheaper
        # compile — a timeboxed run records it even if prefill's larger
        # graph exhausts the window
        ("decode_8", decode, (
            params, cache,
            shaped((SLOTS, 1), jnp.int32), shaped((SLOTS,), jnp.int32),
        )),
        ("prefill_8x2048", prefill, (
            params, cache,
            shaped((SLOTS, MAX_SEQ), jnp.int32), shaped((SLOTS,), jnp.int32),
        )),
    ]
    for name, fn, args in cases:
        try:
            compiled = jax.jit(fn).lower(*args).compile()
            entry = {"ok": True}
            try:
                mem = compiled.memory_analysis()
                arg_b = int(getattr(mem, "argument_size_in_bytes", 0))
                out_b = int(getattr(mem, "output_size_in_bytes", 0))
                tmp_b = int(getattr(mem, "temp_size_in_bytes", 0))
                alias_b = int(getattr(mem, "alias_size_in_bytes", 0))
                # peak live bytes: arguments + outputs + temporaries minus
                # buffers XLA aliases between args and outputs (the cache)
                peak = arg_b + out_b + tmp_b - alias_b
                entry.update({
                    "argument_gb": round(arg_b / 1e9, 2),
                    "output_gb": round(out_b / 1e9, 2),
                    "temp_gb": round(tmp_b / 1e9, 2),
                    "aliased_gb": round(alias_b / 1e9, 2),
                    "peak_gb": round(peak / 1e9, 2),
                    "fits_16gb": bool(peak < HBM_BYTES),
                })
                if peak >= HBM_BYTES:
                    failed += 1
            except Exception as exc:  # noqa: BLE001 - stats best-effort
                entry["memory_analysis_error"] = str(exc)[:120]
            record["programs"][name] = entry
            print(f"OK   {name}: {entry}", file=sys.stderr)
        except Exception as exc:  # noqa: BLE001 - record and continue
            failed += 1
            record["programs"][name] = {
                "ok": False, "error": f"{type(exc).__name__}: {exc}"[:300],
            }
            print(f"FAIL {name}: {exc}", file=sys.stderr)
    record["failed"] = failed
    print(json.dumps(record))
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
