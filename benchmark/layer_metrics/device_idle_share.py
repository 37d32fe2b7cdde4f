"""One minus the union of the device's operation intervals over the
traced slice of the window."""

NAME = "device_idle_share"
UNIT = "share"
LAYER = "device"
MOVES = "token_gap_mean_ms"
SOURCE = "device_trace"


def read(run):
    if run.trace is None:
        return None
    return run.trace["idle_share"]
