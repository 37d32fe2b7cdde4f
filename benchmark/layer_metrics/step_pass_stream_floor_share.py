"""The least time a step can take to stream its parameters as a looped
model's step must: the leaves under ``layers`` of the parameter tree once
for EVERY pass (``passes`` of the program's ``podmortem.sched.dispatch``
span: the stack runs that often a token, and 2.5 GB of layers do not stay
on the chip between passes), every other leaf once; bytes over the chip's
memory bandwidth (``peaks.json``), as a share of the step's device time in
the trace (``step_device_ms``).  ``step_weight_floor_share``, "the
parameters once", understates this floor ``passes``-fold for such a model
and keeps its own definition.  None off the chip, and for a program whose
dispatch span says no ``passes``."""

from benchmark.layer_metrics import step_device_ms
from benchmark.trace import reduce as trace_reduce, steps

NAME = "step_pass_stream_floor_share"
UNIT = "share"
LAYER = "mixed step"
MOVES = "token_gap_mean_ms"
SOURCE = "device_trace"


def tree_bytes(tree) -> int:
    import jax

    return sum(
        int(leaf.size * leaf.dtype.itemsize) for leaf in jax.tree_util.tree_leaves(tree)
    )


def passes_of(spans: list):
    """The mean ``passes`` over the dispatch spans that say one; None
    where none does."""
    said = [
        int(stats["passes"]) for _, name, _, _, stats in spans
        if name == steps.DISPATCH_SPAN and "passes" in stats
    ]
    return sum(said) / len(said) if said else None


def floor_ms(passes: float, layer_bytes: int, other_bytes: int, peaks: dict) -> float:
    return (passes * layer_bytes + other_bytes) / (peaks["hbm_gbps"] * 1e9) * 1e3


def read(run):
    step_ms = step_device_ms.read(run)
    if run.peaks is None or not step_ms or not run.window.trace_dir:
        return None
    path = trace_reduce.newest_xplane(run.window.trace_dir)
    if path is None:
        return None
    passes = passes_of(steps.load(path).get("spans", []))
    params = run.handle.parameters()
    if passes is None or not isinstance(params, dict) or "layers" not in params:
        return None
    layer_bytes = tree_bytes(params["layers"])
    other_bytes = tree_bytes({k: v for k, v in params.items() if k != "layers"})
    return floor_ms(passes, layer_bytes, other_bytes, run.peaks) / step_ms
