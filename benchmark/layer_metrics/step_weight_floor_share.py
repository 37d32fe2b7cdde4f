"""The least time a step can take to stream the parameters once (bytes of
the parameter tree over the chip's memory bandwidth, ``peaks.json``) as a
share of the step's device time in the trace (``step_device_ms``).  Near 1
means only fewer bytes can help."""

from benchmark.layer_metrics import step_device_ms

NAME = "step_weight_floor_share"
UNIT = "share"
LAYER = "mixed step"
MOVES = "token_gap_mean_ms"
SOURCE = "device_trace"


def read(run):
    step_ms = step_device_ms.read(run)
    if run.peaks is None or not step_ms:
        return None
    floor_ms = run.handle.param_bytes() / (run.peaks["hbm_gbps"] * 1e9) * 1e3
    return floor_ms / step_ms
