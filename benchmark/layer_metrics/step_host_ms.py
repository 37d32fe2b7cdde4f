"""Mean of what the host needed a step (``StepRecord.host_ms``: the step's
wall on the worker thread less its wait on the device and the token
fetch; planning, packing and enqueueing, committing, and the event loop's
turn between two steps), over the window's steps.  Beside
``step_device_ms`` it says how much shorter the step can get before the
host paces it.  None for a program whose records carry no such split."""

NAME = "step_host_ms"
UNIT = "ms"
LAYER = "admission and scheduler"
MOVES = "token_gap_mean_ms"
SOURCE = "program_span"


def read(run):
    host = [getattr(s, "host_ms", None) for s in run.steps]
    host = [h for h in host if h is not None]
    if not host:
        return None
    return sum(host) / len(host)
