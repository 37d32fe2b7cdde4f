"""Device time of one serving step, from the trace: the seconds of the
program that took most of the traced slice (the mixed step) over its runs
there.  Unlike the step clock's ``device_ms`` it sums nothing that
overlaps: each run is one event of the device's ``XLA Modules`` line."""

NAME = "step_device_ms"
UNIT = "ms"
LAYER = "mixed step"
MOVES = "token_gap_mean_ms"
SOURCE = "device_trace"


def read(run):
    if run.trace is None or not run.trace["programs"]:
        return None
    _, runs, seconds = run.trace["programs"][0]
    return seconds * 1e3 / runs
