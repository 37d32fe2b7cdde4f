"""Mean KV pages one layer's ragged-attention call walks a step
(``StepRecord.kv_pages_walked``, counted where the scheduler packs the
step's arrays), over the window's steps: the kernel's work as a count.
None for a program whose records carry no such count."""

NAME = "step_kv_pages_mean"
UNIT = "count"
LAYER = "kernels"
MOVES = "token_gap_mean_ms"
SOURCE = "program_counter"


def read(run):
    pages = [getattr(s, "kv_pages_walked", None) for s in run.steps]
    pages = [p for p in pages if p is not None]
    if not pages:
        return None
    return sum(pages) / len(pages)
