"""A reader no manifest names: the per-layer metric that
``test_falcon_h1_benchmark.py`` appends to a copy of ``BENCHMARK.json`` to
show that a later PR's entry, with a reader of its own under one of the
manifest's ``paths``, is found and breaks nothing.  Requests of the window
that finished."""

NAME = "appended_requests_finished"
UNIT = "count"
LAYER = "service"
MOVES = "token_gap_mean_ms"
SOURCE = "host_clock"


def read(run):
    finished = sum(1 for r in run.window.attempted if r.finished)
    return float(finished) if finished else None
