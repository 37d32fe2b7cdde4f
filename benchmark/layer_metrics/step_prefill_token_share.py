"""Prompt tokens over all tokens the window's steps processed
(``StepRecord.prefill_tokens`` over ``tokens``): the mixed step's split
into prompt processing and generation, in the unit a mixed step can be
split in.  None for a program whose records do not tell the two apart."""

NAME = "step_prefill_token_share"
UNIT = "share"
LAYER = "mixed step"
MOVES = "token_gap_mean_ms"
SOURCE = "program_counter"


def read(run):
    counted = [
        s for s in run.steps if getattr(s, "prefill_tokens", None) is not None
    ]
    tokens = sum(s.tokens for s in counted)
    if not tokens:
        return None
    return sum(s.prefill_tokens for s in counted) / tokens
