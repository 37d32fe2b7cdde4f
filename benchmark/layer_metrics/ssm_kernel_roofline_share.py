"""The least time the chip could take for the state-space scan kernel's
calls in the traced slice (``benchmark/trace/ssm_cost.py``: the slots with
a token read and rewrite their recurrent state, the live tokens go in and
out; larger of bytes over the memory bandwidth and operations over the
matmul peak, ``trace/kernel_cost.py least_seconds``) over the device time
those calls took.  ``state_rows`` and ``tokens`` come from the program's
own ``podmortem.sched.dispatch`` span of each step, joined by order to the
device's runs of the step program (``trace/steps.py``'s rule: off by one
step at the slice's edge under decode-ahead pipelining, under 1%).  Says
on stderr which bound it took.  None off the chip, and for a program whose
spans carry no ``state_rows`` or whose cache holds no recurrent state."""

import sys

from benchmark.layer_metrics import ssm_kernel_share
from benchmark.trace import kernel_cost, reduce as trace_reduce, ssm_cost, steps

NAME = "ssm_kernel_roofline_share"
UNIT = "share"
LAYER = "kernels"
MOVES = "token_gap_mean_ms"
SOURCE = "device_trace"


def state_shapes(handle):
    """Layers, heads, head dimension, state size, groups and item sizes
    from the engine's own cache and configuration; None where the cache
    holds no recurrent state."""
    generator = getattr(getattr(handle, "engine", None), "generator", None)
    cache = getattr(generator, "paged_cache", None)
    state = getattr(cache, "ssm_state", None)
    if state is None or len(state.shape) != 5:
        return None
    layers, _, heads, d_state, head_dim = state.shape
    return {
        "layers": int(layers), "heads": int(heads), "head_dim": int(head_dim),
        "d_state": int(d_state), "groups": int(generator.config.mamba_n_groups),
        "state_itemsize": int(state.dtype.itemsize),
        "token_itemsize": int(cache.conv_state.dtype.itemsize),
    }


def scan_steps(events: dict) -> list:
    """One dict per joined step of the slice: the dispatch span's
    ``state_rows`` and ``tokens`` and ``kernel_s``, the seconds of the scan
    kernel's device events inside the run it was joined to."""
    window = steps.window_of(events)
    if window is None:
        return []
    spans = [
        stats for _, name, start, _, stats in events.get("spans", [])
        if name == steps.DISPATCH_SPAN and "state_rows" in stats
        and window[0] <= start <= window[1]
    ]
    joined = []
    for stats, (plane, start, dur) in zip(spans, steps.step_runs(events, window)):
        kernel_ns = sum(
            d for name, s, d in events["device"].get(plane, [])
            if start <= s <= start + dur and ssm_kernel_share.PATTERN.search(name)
        )
        joined.append({
            "state_rows": int(stats["state_rows"]),
            "tokens": int(stats.get("tokens", 0)),
            "kernel_s": kernel_ns / 1e9,
        })
    return joined


def share(joined: list, shapes: dict, peaks: dict):
    """``(share, seconds by bound)`` over the joined steps."""
    least = {"bandwidth": 0.0, "compute": 0.0}
    for step in joined:
        moved, operations = ssm_cost.ssm_scan_cost(
            state_rows=step["state_rows"], tokens=step["tokens"], **shapes,
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
    shapes = state_shapes(run.handle)
    if path is None or shapes is None:
        return None
    joined = scan_steps(steps.load(path))
    if not joined:
        return None
    value, least = share(joined, shapes, run.peaks)
    if value is None:
        return None
    bound = max(least, key=least.get)
    print(
        f"[benchmark] {NAME}: {len(joined)} steps, "
        f"{sum(s['state_rows'] for s in joined)} state rows a layer, kernel "
        f"{sum(s['kernel_s'] for s in joined):.4f} s, least "
        f"{sum(least.values()):.6f} s ({bound}-bound: bandwidth "
        f"{least['bandwidth']:.6f} s, compute {least['compute']:.6f} s)",
        file=sys.stderr, flush=True,
    )
    return value
