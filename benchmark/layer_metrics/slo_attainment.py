"""Share of attempted requests that met the mix's limits on TTFT and on
the token gap; a failed request misses.  Recorded, never judged: near the
knee it swings with the smallest change."""

import math

from benchmark.harness import stats

NAME = "slo_attainment"
UNIT = "share"
LAYER = "service"
MOVES = "token_gap_mean_ms"
SOURCE = "host_clock"


def read(run):
    limits = run.spec.traffic.get("limits")
    if not limits:
        return None
    attempted = run.window.attempted
    return stats.attainment(
        [math.inf if r.failed else r.ttft_ms for r in attempted],
        [None if r.failed else r.gap_ms for r in attempted],
        float(limits["ttft_ms"]), float(limits["token_gap_ms"]),
    )
