"""The least time the chip could take for the ragged paged-attention
kernel's calls in the traced slice, over the device time they took.

Least time, step by step: the larger of bytes over the memory bandwidth
and operations over the matmul peak (``peaks.json``), with bytes and
operations from ``benchmark/trace/kernel_cost.py`` — the KV pages the
step's rows walk, times the layers, times one page's K and V in one
layer, plus the live queries in and their outputs back; 4 x head
dimension x query heads x (queries x positions scored).  The pages come
from the program's own ``podmortem.sched.dispatch`` span of each step,
joined by order to the device's runs of the step program
(``benchmark/trace/steps.py``: a join off by one step, which decode-ahead
pipelining can cause at the slice's edge, moves the share by under 1%).
The kernel's time is that of the device events ``attn_kernel_share``
reads, inside those runs.  Says on stderr which bound it took.  None off
the chip, and for a program that writes no such span.
"""

import sys

from benchmark.layer_metrics import attn_kernel_share
from benchmark.trace import kernel_cost, reduce as trace_reduce, steps

NAME = "attn_kernel_roofline_share"
UNIT = "share"
LAYER = "kernels"
MOVES = "token_gap_mean_ms"
SOURCE = "device_trace"


def kv_shapes(handle):
    """Layers, page size, KV heads, head dimension and item sizes from the
    engine's own paged pool and configuration; None where it has none."""
    generator = getattr(getattr(handle, "engine", None), "generator", None)
    pool = getattr(getattr(generator, "paged_cache", None), "k_pages", None)
    if pool is None or len(pool.shape) != 5:
        return None
    layers, _, page_size, kv_heads, head_dim = pool.shape
    return {
        "layers": int(layers), "page_size": int(page_size),
        "kv_heads": int(kv_heads), "head_dim": int(head_dim),
        "q_heads": int(generator.config.num_heads),
        "kv_itemsize": int(pool.dtype.itemsize),
        # the step casts the queries to the pool's type for the kernel
        "q_itemsize": int(pool.dtype.itemsize),
    }


def share(joined: list, shapes: dict, peaks: dict):
    """``(share, seconds by bound)`` over the joined steps."""
    least = {"bandwidth": 0.0, "compute": 0.0}
    for step in joined:
        moved, operations = kernel_cost.ragged_attention_cost(
            kv_pages=step["kv_pages"], qk_pairs=step["qk_pairs"],
            tokens=step["tokens"], **shapes,
        )
        seconds, bound = kernel_cost.least_seconds(moved, operations, peaks)
        least[bound] += seconds
    kernel_s = sum(step["kernel_s"] for step in joined)
    if kernel_s <= 0:
        return None, least
    return sum(least.values()) / kernel_s, least


def read(run):
    if run.trace is None or run.peaks is None or not run.window.trace_dir:
        return None
    path = trace_reduce.newest_xplane(run.window.trace_dir)
    shapes = kv_shapes(run.handle)
    if path is None or shapes is None:
        return None
    joined = steps.kernel_steps(steps.load(path), attn_kernel_share.PATTERN)
    if not joined:
        return None
    value, least = share(joined, shapes, run.peaks)
    if value is None:
        return None
    bound = max(least, key=least.get)
    print(
        f"[benchmark] {NAME}: {len(joined)} steps, "
        f"{sum(s['kv_pages'] for s in joined)} KV pages a layer, kernel "
        f"{sum(s['kernel_s'] for s in joined):.4f} s, least "
        f"{sum(least.values()):.6f} s ({bound}-bound: bandwidth "
        f"{least['bandwidth']:.6f} s, compute {least['compute']:.6f} s)",
        file=sys.stderr, flush=True,
    )
    return value
