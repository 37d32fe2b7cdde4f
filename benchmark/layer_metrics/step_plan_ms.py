"""Mean over the window's step records of ``StepRecord.plan_ms``, one of the
six parts that tile ``host_ms`` on the worker thread: planning: scheduling
and admission (``podmortem.sched.plan``), from the ``step()``'s start.  None
for a program whose clock does not name every part (``host_clock``)."""

from . import host_clock

NAME = "step_plan_ms"
UNIT = "ms"
LAYER = "admission and scheduler"
MOVES = "token_gap_mean_ms"
SOURCE = "program_span"


def read(run):
    return host_clock.part_mean(run, "plan")
