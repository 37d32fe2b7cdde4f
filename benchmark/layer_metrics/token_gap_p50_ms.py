"""Median over requests of (last token time - first token time) /
(tokens - 1), on requests with at least 8 tokens by the cut-off: what one
typical caller sees, where ``token_gap_mean_ms`` weighs every token alike."""

from benchmark.harness import stats

NAME = "token_gap_p50_ms"
UNIT = "ms"
LAYER = "service"
MOVES = "token_gap_mean_ms"
SOURCE = "host_clock"


def read(run):
    gaps = [
        r.gap_ms for r in run.window.attempted if not r.failed and r.gap_ms is not None
    ]
    return stats.percentile(gaps, 50)
