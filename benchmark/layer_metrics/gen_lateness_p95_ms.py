"""How late the load generator sent: send time less due time, 95th
percentile over the window's requests.  Over about 20 ms the TTFTs are
suspect: a starved generator reads as a fast server."""

from benchmark.harness import stats

NAME = "gen_lateness_p95_ms"
UNIT = "ms"
LAYER = "load generator"
MOVES = "token_gap_mean_ms"
SOURCE = "host_clock"


def read(run):
    late = [
        (r.sent_t - r.due_t) * 1e3 for r in run.window.attempted if r.sent_t is not None
    ]
    return stats.percentile(late, 95)
