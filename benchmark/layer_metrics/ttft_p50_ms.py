"""Median time from the moment a request was due to its first streamed
token; a failed request ranks last.  Recorded beside ``ttft_mean_ms``,
which is recorded too: no TTFT of the storm is judged yet (PERF.md, PR 22)."""

import math

from benchmark.harness import stats

NAME = "ttft_p50_ms"
UNIT = "ms"
LAYER = "service"
MOVES = "token_gap_mean_ms"
SOURCE = "host_clock"


def read(run):
    return stats.percentile(
        [math.inf if r.failed else r.ttft_ms for r in run.window.attempted], 50
    )
