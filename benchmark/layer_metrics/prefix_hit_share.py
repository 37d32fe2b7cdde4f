"""Prompt tokens served from the prefix cache over prompt tokens sent: the
step records' ``cached_tokens`` summed over the window, against the prompt
lengths (under the engine's own tokenizer and truncation) of the window's
requests that were sent and not refused."""

NAME = "prefix_hit_share"
UNIT = "share"
LAYER = "admission and scheduler"
MOVES = "token_gap_mean_ms"
SOURCE = "program_counter"


def read(run):
    cached = [s.cached_tokens for s in run.steps if s.cached_tokens is not None]
    prompt = sum(
        len(run.handle.prompt_ids(r.prompt, r.max_tokens))
        for r in run.window.attempted if r.sent_t is not None and r.error is None
    )
    if not cached or not prompt:
        return None
    return sum(cached) / prompt
