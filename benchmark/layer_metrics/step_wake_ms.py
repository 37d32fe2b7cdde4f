"""Mean over the window's step records of ``StepRecord.wake_ms``: the time
inside the commit's hand-overs of a row's tokens to the event loop
(``Scheduler.partial_hook`` -> ``call_soon_threadsafe``, one a streaming
row a step).  Part of ``step_commit_ms``, not beside it.  None for a
program whose clock does not time them (``host_clock``)."""

from . import host_clock

NAME = "step_wake_ms"
UNIT = "ms"
LAYER = "admission and scheduler"
MOVES = "token_gap_mean_ms"
SOURCE = "program_span"


def read(run):
    return host_clock.part_mean(run, "wake")
