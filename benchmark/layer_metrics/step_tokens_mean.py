"""Mean tokens a step processed (``StepRecord.tokens``: one per decoding
row, up to a chunk per prompt row) over the window's steps: how full the
step's token budget is."""

NAME = "step_tokens_mean"
UNIT = "count"
LAYER = "mixed step"
MOVES = "token_gap_mean_ms"
SOURCE = "program_counter"


def read(run):
    if not run.steps:
        return None
    return sum(s.tokens for s in run.steps) / len(run.steps)
