"""Mean over the window's step records of ``StepRecord.commit_ms``, one of the
six parts that tile ``host_ms`` on the worker thread: the commit after the
token fetch: row commits, hand-overs to the event loop, offload drains,
outcomes, up to the next stamp (``podmortem.sched.commit`` and the glue
after it).  None for a program whose clock does not name every part
(``host_clock``)."""

from . import host_clock

NAME = "step_commit_ms"
UNIT = "ms"
LAYER = "admission and scheduler"
MOVES = "token_gap_mean_ms"
SOURCE = "program_span"


def read(run):
    return host_clock.part_mean(run, "commit")
