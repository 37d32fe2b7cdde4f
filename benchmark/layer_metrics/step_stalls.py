"""Step records of the window that the program's clock marked as stalls
(``StepRecord.stall``: a wall over both 50 ms and three times the median
of the recent walls, ``obs/steptrace.stall_over_ms``).  None for a
program whose clock keeps no stalls (``host_clock``)."""

from . import host_clock

NAME = "step_stalls"
UNIT = "count"
LAYER = "admission and scheduler"
MOVES = "token_gap_mean_ms"
SOURCE = "program_counter"


def read(run):
    steps = host_clock.records(run)
    if not steps:
        return None
    return sum(1 for s in steps if s.stall)
