"""Compilations the program's compile log (``engine.compile_watch``) saw
inside the window.  Each is a stall for every row; the warm-up is there so
that this reads 0."""

NAME = "midrun_compiles"
UNIT = "count"
LAYER = "XLA and compile cache"
MOVES = "token_gap_mean_ms"
SOURCE = "program_counter"


def read(run):
    return float(len(run.window.compiles))
