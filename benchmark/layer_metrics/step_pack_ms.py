"""Mean over the window's step records of ``StepRecord.pack_ms``, one of the
six parts that tile ``host_ms`` on the worker thread: ``Scheduler._pack``
alone: packing the plan onto the flat token axis on the host
(``podmortem.sched.pack``); the puts and the launch are ``step_put_ms`` and
``step_launch_ms``.  None for a program whose clock does not name every part
(``host_clock``)."""

from . import host_clock

NAME = "step_pack_ms"
UNIT = "ms"
LAYER = "admission and scheduler"
MOVES = "token_gap_mean_ms"
SOURCE = "program_span"


def read(run):
    return host_clock.part_mean(run, "pack")
