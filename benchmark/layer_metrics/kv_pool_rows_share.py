"""The most of the paged KV pool that live rows held at once: pages
granted to rows (``Scheduler.page_accounting``) over the pool's pages,
sampled four times a second through the window.  It says how much of the
pool the traffic needs; ``kv_pool_fill_share`` counts the prefix cache's
pages too."""

NAME = "kv_pool_rows_share"
UNIT = "share"
LAYER = "admission and scheduler"
MOVES = "token_gap_mean_ms"
SOURCE = "program_counter"


def read(run):
    if not run.window.pool:
        return None
    return max(rows / total for rows, _, total in run.window.pool)
