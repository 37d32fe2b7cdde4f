"""Window seconds over the step records committed in it: a count over
wall time, sound under pipelining.  Exact where the engine never waits
for work (closed loop); in an open loop it includes that waiting, so read
it beside ``device_idle_share``."""

NAME = "step_ms_mean"
UNIT = "ms"
LAYER = "mixed step"
MOVES = "token_gap_mean_ms"
SOURCE = "program_counter"


def read(run):
    if not run.steps:
        return None
    return run.window.seconds * 1e3 / len(run.steps)
