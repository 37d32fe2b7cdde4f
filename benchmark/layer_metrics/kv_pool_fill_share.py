"""The most of the paged KV pool that was in use at once: pages held by
live rows and by the prefix cache over the pool's pages, sampled four
times a second through the window."""

NAME = "kv_pool_fill_share"
UNIT = "share"
LAYER = "admission and scheduler"
MOVES = "token_gap_mean_ms"
SOURCE = "program_counter"


def read(run):
    if not run.window.pool:
        return None
    return max(used / total for _, used, total in run.window.pool)
