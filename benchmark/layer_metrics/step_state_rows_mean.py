"""Mean slots whose recurrent state one layer's scan call reads and
rewrites a step (``StepRecord.state_rows``: the slots with a token this
step, counted where the scheduler packs the step's arrays), over the
window's steps: the scan kernel's work as a count.  None for a program
whose records carry no such count (a model without recurrent state)."""

NAME = "step_state_rows_mean"
UNIT = "count"
LAYER = "kernels"
MOVES = "token_gap_mean_ms"
SOURCE = "program_counter"


def read(run):
    rows = [getattr(s, "state_rows", None) for s in run.steps]
    rows = [r for r in rows if r is not None]
    if not rows:
        return None
    return sum(rows) / len(rows)
