"""The least time the chip could take for the grouped expert product's
calls in the traced slice (``benchmark/trace/moe_cost.py``: the matrices
of every expert given a token read once, every assignment's row in and
out; larger of bytes over the memory bandwidth and operations over the
matmul peak, ``trace/kernel_cost.py least_seconds``) over the device time
those calls took.  ``moe_tokens`` comes from the program's own
``podmortem.sched.dispatch`` span of each step, joined by order to the
device's runs of the step program (``trace/steps.py``'s rule), and the
experts that were given a token from the step record the span's ``step``
names (``StepRecord.moe_experts_hit``: the device's own count).  Says on
stderr which bound it took.  None off the chip, and for a program whose
spans carry no ``moe_tokens`` or whose model has no experts."""

import sys

from benchmark.layer_metrics import moe_kernel_share
from benchmark.trace import kernel_cost, moe_cost, reduce as trace_reduce, steps

NAME = "moe_kernel_roofline_share"
UNIT = "share"
LAYER = "kernels"
MOVES = "token_gap_mean_ms"
SOURCE = "device_trace"


def expert_shapes(handle):
    """Layers, experts a token, widths and item sizes from the engine's
    own configuration and parameters; None for a model without experts."""
    generator = getattr(getattr(handle, "engine", None), "generator", None)
    config = getattr(generator, "config", None)
    layers = (getattr(generator, "params", None) or {}).get("layers", {})
    stack = layers.get("w_gate")
    if config is None or stack is None or not getattr(config, "num_experts", 0):
        return None
    scaled = isinstance(stack, dict)
    return {
        "layers": int(config.num_layers),
        "experts_per_token": int(config.num_experts_per_tok),
        "hidden": int(config.hidden_size),
        "inner": int(config.moe_intermediate_size),
        "weight_itemsize": int((stack["q"] if scaled else stack).dtype.itemsize),
        "scaled": scaled,
        "token_itemsize": int(layers["w_router"].dtype.itemsize),
    }


def expert_steps(events: dict, records: list) -> list:
    """One dict per joined step of the slice: the dispatch span's
    ``moe_tokens``, the ``moe_experts_hit`` of the record its ``step``
    names, and ``kernel_s``, the seconds of the kernel's device events
    inside the run it was joined to."""
    window = steps.window_of(events)
    if window is None:
        return []
    hit = {r.seq: getattr(r, "moe_experts_hit", None) for r in records}
    spans = [
        stats for _, name, start, _, stats in events.get("spans", [])
        if name == steps.DISPATCH_SPAN and "moe_tokens" in stats
        and window[0] <= start <= window[1]
    ]
    joined = []
    for stats, (plane, start, dur) in zip(spans, steps.step_runs(events, window)):
        experts_hit = hit.get(int(stats.get("step", -1)))
        if experts_hit is None:
            continue
        kernel_ns = sum(
            d for name, s, d in events["device"].get(plane, [])
            if start <= s <= start + dur and moe_kernel_share.PATTERN.search(name)
        )
        joined.append({
            "tokens": int(stats["moe_tokens"]), "experts_hit": int(experts_hit),
            "kernel_s": kernel_ns / 1e9,
        })
    return joined


def share(joined: list, shapes: dict, peaks: dict):
    """``(share, seconds by bound)`` over the joined steps."""
    least = {"bandwidth": 0.0, "compute": 0.0}
    for step in joined:
        moved, operations = moe_cost.moe_experts_cost(
            experts_hit=step["experts_hit"], tokens=step["tokens"], **shapes,
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
    shapes = expert_shapes(run.handle)
    if path is None or shapes is None:
        return None
    joined = expert_steps(steps.load(path), run.steps)
    if not joined:
        return None
    value, least = share(joined, shapes, run.peaks)
    if value is None:
        return None
    bound = max(least, key=least.get)
    print(
        f"[benchmark] {NAME}: {len(joined)} steps, "
        f"{sum(s['experts_hit'] for s in joined)} experts read, "
        f"{sum(s['tokens'] for s in joined)} tokens a layer, kernel "
        f"{sum(s['kernel_s'] for s in joined):.4f} s, least "
        f"{sum(least.values()):.6f} s ({bound}-bound: bandwidth "
        f"{least['bandwidth']:.6f} s, compute {least['compute']:.6f} s)",
        file=sys.stderr, flush=True,
    )
    return value
