"""Mean age of a streamed snapshot when it reached the event loop's
``ServingEngine._deliver_partial``, since the commit that holds its
tokens began on the worker thread: sum of ``StepRecord.deliver_lag_ms``
over sum of ``delivered``, over the window.  What a commit that wakes the
loop less often has to leave where it is.  None for a program whose
records carry no such count, or in a window that streamed nothing."""

from . import host_clock

NAME = "token_delivery_lag_mean_ms"
UNIT = "ms"
LAYER = "service"
MOVES = "token_gap_mean_ms"
SOURCE = "program_span"


def read(run):
    return host_clock.share(run, "deliver_lag_ms", "delivered")
