"""Mean rows in a denoising step (``StepRecord.block_rows``: the rows
whose step ran a block of positions, counted where the scheduler packs
the step's arrays), over the window's steps that had one.  None for a
program whose records carry no such count (a model that does not
denoise)."""

NAME = "step_block_rows_mean"
UNIT = "count"
LAYER = "mixed step"
MOVES = "token_gap_mean_ms"
SOURCE = "program_counter"


def read(run):
    rows = [s.block_rows for s in run.steps if getattr(s, "block_rows", None)]
    if not rows:
        return None
    return sum(rows) / len(rows)
