"""Mean answer tokens a step's denoising rows kept
(``StepRecord.unmasked_tokens``: the bits the device set, counted at the
commit), over the window's steps that had such a row.  Over
``step_block_rows_mean`` it is the tokens a row keeps a step: the block's
length over the requests' ``denoise_steps`` (2.0 in a cell of blocks of 4
at two steps a block, less what a request's first and last block fall
short), the guard that the cell runs the schedule it says.  None for a
program whose records carry no such count (a model that does not
denoise)."""

NAME = "step_unmasked_tokens_mean"
UNIT = "count"
LAYER = "mixed step"
MOVES = "token_gap_mean_ms"
SOURCE = "program_counter"


def read(run):
    kept = [
        s.unmasked_tokens for s in run.steps
        if getattr(s, "unmasked_tokens", None) is not None and getattr(s, "block_rows", 0)
    ]
    if not kept:
        return None
    return sum(kept) / len(kept)
