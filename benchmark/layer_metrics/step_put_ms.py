"""Mean over the window's step records of ``StepRecord.put_ms``, one of the six
parts that tile ``host_ms`` on the worker thread: the host-to-device puts of
a step: the staged page tables' update and every ``jnp.asarray`` of the
packed arrays, up to the call of the compiled step (``podmortem.sched.put``,
inside ``.dispatch``).  None for a program whose clock does not name every
part (``host_clock``)."""

from . import host_clock

NAME = "step_put_ms"
UNIT = "ms"
LAYER = "admission and scheduler"
MOVES = "token_gap_mean_ms"
SOURCE = "program_span"


def read(run):
    return host_clock.part_mean(run, "put")
