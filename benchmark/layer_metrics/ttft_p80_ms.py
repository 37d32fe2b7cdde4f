"""The 80th percentile of the time from the moment a request was due to
its first streamed token; a failed request ranks last.  A tail is reported
only with ten samples beyond it, so this one is absent under 47 requests.
Recorded, not judged: it moved 0.6% in one set of three runs and 7.6% in
the next (PERF.md, PR 22)."""

import math

from benchmark.harness import stats

NAME = "ttft_p80_ms"
UNIT = "ms"
LAYER = "service"
MOVES = "token_gap_mean_ms"
SOURCE = "host_clock"


def read(run):
    ttft = [math.inf if r.failed else r.ttft_ms for r in run.window.attempted]
    if stats.samples_beyond(len(ttft), 80) < stats.MIN_BEYOND:
        return None
    return stats.percentile(ttft, 80)
