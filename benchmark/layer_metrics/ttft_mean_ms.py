"""Mean, over every request due in the window, of the time from the moment
it was due to its first streamed token; a failed request is infinite, so
one failure shows.  Recorded, not judged: the driver's check read a spread
of 6.5% and 14.3% for it in two sets of six storm runs, more than any bound
of at most 10% carries (PERF.md, PR 22).  ``moves`` names the gap because
the manifest wants an end-to-end metric of the cell there."""

import math

NAME = "ttft_mean_ms"
UNIT = "ms"
LAYER = "service"
MOVES = "token_gap_mean_ms"
SOURCE = "host_clock"


def read(run):
    ttft = [math.inf if r.failed else r.ttft_ms for r in run.window.attempted]
    return sum(ttft) / len(ttft) if ttft else None
