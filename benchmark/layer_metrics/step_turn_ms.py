"""Mean over the window's step records of ``StepRecord.turn_ms``, one of the
six parts that tile ``host_ms`` on the worker thread: the event loop's turn
between two ``step()`` calls while work was pending
(``podmortem.serve.turn``).  None for a program whose clock does not name
every part (``host_clock``)."""

from . import host_clock

NAME = "step_turn_ms"
UNIT = "ms"
LAYER = "admission and scheduler"
MOVES = "token_gap_mean_ms"
SOURCE = "program_span"


def read(run):
    return host_clock.part_mean(run, "turn")
