"""The share of the worker thread's own time that it was on the CPU: sum of
``StepRecord.cpu_ms`` (the thread's CPU clock over the interval, less
what it used inside the device wait) over sum of ``host_ms + xfer_ms``.
What is missing from 1 is time the worker had work and was not running:
the GIL held by the event loop's deliveries, or the machine.  None for a
program whose records carry no such clock (``host_clock``)."""

from . import host_clock

NAME = "step_host_cpu_share"
UNIT = "share"
LAYER = "admission and scheduler"
MOVES = "token_gap_mean_ms"
SOURCE = "program_counter"


def read(run):
    return host_clock.share(run, "cpu_ms", "host_ms", "xfer_ms")
