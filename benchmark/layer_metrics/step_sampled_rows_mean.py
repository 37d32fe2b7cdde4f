"""Mean logit rows the step's head and sampler were asked for
(``StepRecord.sampled_rows``: one a slot, or the verify width a slot in a
step that carries a draft), over the window's steps: the tail's work as a
count.  The scheduler counts it where it packs the step's arrays, from
the ``spec_len`` the compiled step branches on: it says what was asked
for, not which branch ran -- the trace's operation shapes say that.  None
for a program whose records carry no such count.

``BENCHMARK.json`` has no entry for it yet (PERF.md, Open questions): a
``benchmark`` PR appends one, with the four serving cells as
``workloads``."""

NAME = "step_sampled_rows_mean"
UNIT = "count"
LAYER = "mixed step"
MOVES = "token_gap_mean_ms"
SOURCE = "program_counter"


def read(run):
    rows = [getattr(s, "sampled_rows", None) for s in run.steps]
    rows = [r for r in rows if r is not None]
    if not rows:
        return None
    return sum(rows) / len(rows)
