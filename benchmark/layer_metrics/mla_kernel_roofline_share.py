"""The least time the chip could take for the latent attention kernel's
calls in the traced slice (``benchmark/trace/mla_cost.py``: the latent
pages the step's rows walk read once, the absorbed queries in and the
latent outputs out; larger of bytes over the memory bandwidth and
operations over the matmul peak, ``trace/kernel_cost.py least_seconds``)
over the device time those calls took.  The pages, pairs, tokens and the
layers the kernel runs in (``latent_layers``) come from the program's own
``podmortem.sched.dispatch`` span of each step, joined by order to the
device's runs of the step program (``trace/steps.py``'s rule); the
kernel's time is that of the device events named
``latent_attention_kernel`` (``ops/latent_attention.py KERNEL_NAME``)
inside those runs.  Says on stderr which bound it took.  None off the
chip, and for a program whose spans carry no ``latent_layers`` or whose
pool is not a latent one."""

import re
import sys

from benchmark.trace import kernel_cost, mla_cost, reduce as trace_reduce, steps

NAME = "mla_kernel_roofline_share"
UNIT = "share"
LAYER = "kernels"
MOVES = "token_gap_mean_ms"
SOURCE = "device_trace"

PATTERN = re.compile(r"latent_attention_kernel")


def latent_shapes(handle):
    """Page size, heads, latent and rope widths and item size from the
    engine's own latent pool and configuration; None for a model whose
    pool is not a latent one."""
    generator = getattr(getattr(handle, "engine", None), "generator", None)
    cache = getattr(generator, "paged_cache", None)
    latent = getattr(cache, "k_pages", None)
    if latent is None or getattr(cache, "rope_pages", None) is None:
        return None
    _, _, page_size, rank = latent.shape
    return {
        "page_size": int(page_size), "heads": int(generator.config.num_heads),
        "rank": int(rank), "rope_dim": int(generator.config.qk_rope_head_dim),
        "itemsize": int(latent.dtype.itemsize),
    }


def latent_steps(events: dict) -> list:
    """One dict per joined step of the slice: the dispatch span's
    ``kv_pages``, ``qk_pairs``, ``tokens`` and ``latent_layers``, and
    ``kernel_s``, the seconds of the kernel's device events inside the run
    it was joined to."""
    window = steps.window_of(events)
    if window is None:
        return []
    spans = [
        stats for _, name, start, _, stats in events.get("spans", [])
        if name == steps.DISPATCH_SPAN and "latent_layers" in stats
        and window[0] <= start <= window[1]
    ]
    joined = []
    for stats, (plane, start, dur) in zip(spans, steps.step_runs(events, window)):
        kernel_ns = sum(
            d for name, s, d in events["device"].get(plane, [])
            if start <= s <= start + dur and PATTERN.search(name)
        )
        joined.append({
            "pages": int(stats["kv_pages"]), "pairs": int(stats.get("qk_pairs", 0)),
            "tokens": int(stats.get("tokens", 0)), "layers": int(stats["latent_layers"]),
            "kernel_s": kernel_ns / 1e9,
        })
    return joined


def share(joined: list, shapes: dict, peaks: dict):
    """``(share, seconds by bound)`` over the joined steps."""
    least = {"bandwidth": 0.0, "compute": 0.0}
    for step in joined:
        moved, operations = mla_cost.latent_attention_cost(
            latent_pages=step["pages"], qk_pairs=step["pairs"], tokens=step["tokens"],
            layers=step["layers"], **shapes,
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
    shapes = latent_shapes(run.handle)
    if path is None or shapes is None:
        return None
    joined = latent_steps(steps.load(path))
    if not joined:
        return None
    value, least = share(joined, shapes, run.peaks)
    if value is None:
        return None
    bound = max(least, key=least.get)
    print(
        f"[benchmark] {NAME}: {len(joined)} steps, "
        f"{sum(s['pages'] for s in joined)} latent pages a layer, "
        f"{sum(s['tokens'] for s in joined)} query tokens, {joined[0]['layers']} layers, "
        f"kernel {sum(s['kernel_s'] for s in joined):.4f} s, least "
        f"{sum(least.values()):.6f} s ({bound}-bound: bandwidth "
        f"{least['bandwidth']:.6f} s, compute {least['compute']:.6f} s)",
        file=sys.stderr, flush=True,
    )
    return value
