"""Mean times a token took the layer stack in a step
(``StepRecord.passes``: 1, or a looped model's ``total_ut_steps``, which
the mixed step runs for every row), over the window's steps: the guard
that a cell of a looped model runs the passes it says.  None for a
program whose records carry no such count."""

NAME = "step_passes_mean"
UNIT = "count"
LAYER = "mixed step"
MOVES = "token_gap_mean_ms"
SOURCE = "program_counter"


def read(run):
    passes = [getattr(s, "passes", None) for s in run.steps]
    passes = [p for p in passes if p is not None]
    if not passes:
        return None
    return sum(passes) / len(passes)
