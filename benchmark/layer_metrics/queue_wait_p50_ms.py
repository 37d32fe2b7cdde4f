"""Median of the program's own submit-to-admission wait
(``GenerationResult.queue_wait_ms``) over the requests that finished."""

from benchmark.harness import stats

NAME = "queue_wait_p50_ms"
UNIT = "ms"
LAYER = "admission and scheduler"
MOVES = "token_gap_mean_ms"
SOURCE = "program_span"


def read(run):
    waits = [
        r.queue_wait_ms for r in run.window.attempted if r.queue_wait_ms is not None
    ]
    return stats.percentile(waits, 50)
