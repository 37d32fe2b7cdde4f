"""The least time the chip could take for the gated delta-rule kernel's
calls in the traced slice (``benchmark/trace/delta_cost.py``: the slots
with a token read and rewrite their state in every linear layer, the live
tokens go in and out; larger of bytes over the memory bandwidth and
operations over the matmul peak, ``trace/kernel_cost.py least_seconds``)
over the device time those calls took.  ``state_rows``, ``tokens`` and
``delta_layers`` come from the program's own ``podmortem.sched.dispatch``
span of each step, joined by order to the device's runs of the step
program (``trace/steps.py``'s rule, as ``ssm_kernel_roofline_share``).
Heads, key and value sizes come from the engine's own state pool, which
holds heads in pairs, ``[layers, slots, heads / 2, key_dim, 2 x
value_dim]``.  Says on stderr which bound it took.  None off the chip, and
for a program whose spans carry no ``delta_layers``."""

import sys

from benchmark.layer_metrics import delta_kernel_share
from benchmark.trace import delta_cost, kernel_cost, reduce as trace_reduce, steps

NAME = "delta_kernel_roofline_share"
UNIT = "share"
LAYER = "kernels"
MOVES = "token_gap_mean_ms"
SOURCE = "device_trace"


def state_shapes(handle):
    """Heads, key and value sizes and the state's item size from the
    engine's own pool; None where it holds no such state."""
    generator = getattr(getattr(handle, "engine", None), "generator", None)
    state = getattr(getattr(generator, "paged_cache", None), "ssm_state", None)
    if state is None or len(state.shape) != 5:
        return None
    _, _, pairs, key_dim, wide = state.shape
    return {
        "heads": 2 * int(pairs), "key_dim": int(key_dim), "value_dim": int(wide) // 2,
        "state_itemsize": int(state.dtype.itemsize),
    }


def delta_steps(events: dict) -> list:
    """One dict per joined step of the slice: the dispatch span's
    ``state_rows``, ``tokens`` and ``delta_layers``, and ``kernel_s``, the
    seconds of the delta kernel's device events inside the run it was
    joined to."""
    window = steps.window_of(events)
    if window is None:
        return []
    spans = [
        stats for _, name, start, _, stats in events.get("spans", [])
        if name == steps.DISPATCH_SPAN and "delta_layers" in stats
        and window[0] <= start <= window[1]
    ]
    joined = []
    for stats, (plane, start, dur) in zip(spans, steps.step_runs(events, window)):
        kernel_ns = sum(
            d for name, s, d in events["device"].get(plane, [])
            if start <= s <= start + dur and delta_kernel_share.PATTERN.search(name)
        )
        joined.append({
            "state_rows": int(stats["state_rows"]),
            "tokens": int(stats.get("tokens", 0)),
            "layers": int(stats["delta_layers"]),
            "kernel_s": kernel_ns / 1e9,
        })
    return joined


def share(joined: list, shapes: dict, peaks: dict):
    """``(share, seconds by bound)`` over the joined steps."""
    least = {"bandwidth": 0.0, "compute": 0.0}
    for step in joined:
        moved, operations = delta_cost.gated_delta_cost(
            state_rows=step["state_rows"], tokens=step["tokens"],
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
    shapes = state_shapes(run.handle)
    if path is None or shapes is None:
        return None
    joined = delta_steps(steps.load(path))
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
