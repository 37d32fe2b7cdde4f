"""The share of the window's step walls spent inside Python's collector:
sum of ``StepRecord.gc_ms`` (the pauses its ``gc.callbacks`` hook timed,
on whichever thread) over sum of ``wall_ms``.  None for a program whose
records carry no such count (``host_clock``)."""

from . import host_clock

NAME = "step_gc_share"
UNIT = "share"
LAYER = "admission and scheduler"
MOVES = "token_gap_mean_ms"
SOURCE = "program_counter"


def read(run):
    return host_clock.share(run, "gc_ms", "wall_ms")
