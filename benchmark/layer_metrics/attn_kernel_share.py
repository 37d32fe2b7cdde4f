"""Device time of the ragged paged-attention kernel over device busy time,
from the trace.  ``PATTERN`` matches the kernel's events under the names
the trace prints today (the ``pallas_call`` has no stable ``name`` yet;
giving it one is on the list for the tracing issue)."""

import re

NAME = "attn_kernel_share"
UNIT = "share"
LAYER = "kernels"
MOVES = "token_gap_mean_ms"
SOURCE = "device_trace"

PATTERN = re.compile(r"ragged_attention|pallas", re.IGNORECASE)


def read(run):
    if run.trace is None:
        return None
    kernel_s = sum(
        seconds for name, seconds in run.trace["op_self_s"].items()
        if PATTERN.search(name)
    )
    if kernel_s <= 0:
        return None
    return kernel_s / sum(run.trace["op_self_s"].values())
