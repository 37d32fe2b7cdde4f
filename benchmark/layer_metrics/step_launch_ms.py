"""Mean over the window's step records of ``StepRecord.launch_ms``, one of the
six parts that tile ``host_ms`` on the worker thread: the call of the
compiled step until it returns its handles, and the bookkeeping after it up
to the wait (``podmortem.sched.launch``, inside ``.dispatch``).  None for a
program whose clock does not name every part (``host_clock``)."""

from . import host_clock

NAME = "step_launch_ms"
UNIT = "ms"
LAYER = "admission and scheduler"
MOVES = "token_gap_mean_ms"
SOURCE = "program_span"


def read(run):
    return host_clock.part_mean(run, "launch")
