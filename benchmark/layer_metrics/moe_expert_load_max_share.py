"""How far the fullest expert of a step stands over an even share: the
tokens of the fullest expert, the largest over the layers
(``StepRecord.moe_assign_max``: the device's own count), over the mean
tokens an expert (``moe_tokens x num_experts_per_tok / num_experts``),
averaged over the window's steps.  1 is a router that spreads its tokens
evenly; the grouped product pays a row tile for every started 64 rows of
an expert, so what stands over 1 is padding and a second tile.  None for
a program whose records carry no such counts (a model without experts)."""

NAME = "moe_expert_load_max_share"
UNIT = "ratio"
LAYER = "kernels"
MOVES = "token_gap_mean_ms"
SOURCE = "program_counter"


def read(run):
    generator = getattr(getattr(run.handle, "engine", None), "generator", None)
    config = getattr(generator, "config", None)
    experts = int(getattr(config, "num_experts", 0) or 0)
    if not experts:
        return None
    ratios = []
    for step in run.steps:
        tokens = getattr(step, "moe_tokens", None)
        fullest = getattr(step, "moe_assign_max", None)
        if tokens and fullest is not None:
            ratios.append(fullest * experts / (tokens * config.num_experts_per_tok))
    if not ratios:
        return None
    return sum(ratios) / len(ratios)
