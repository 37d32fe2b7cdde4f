"""The share of the window's step walls that the worker thread spent
blocked on the device (sum of ``StepRecord.wait_ms`` over sum of
``wall_ms``): the host's slack.  Near 0 means the host paces the step,
whatever ``device_idle_share`` says.  None for a program whose records
carry no such split."""

NAME = "step_host_wait_share"
UNIT = "share"
LAYER = "admission and scheduler"
MOVES = "token_gap_mean_ms"
SOURCE = "program_span"


def read(run):
    split = [s for s in run.steps if getattr(s, "wait_ms", None) is not None]
    wall = sum(s.wall_ms for s in split)
    if not wall:
        return None
    return sum(s.wait_ms for s in split) / wall
