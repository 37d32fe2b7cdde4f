"""Device time of the attention kernels over device busy time, from the
trace.  The convention (``benchmark/README.md``): an attention kernel's
``pallas_call`` is given a ``name`` that ends in ``attention_kernel`` (the
program's is ``ragged_attention_kernel``, ``ops/ragged_attention.py
KERNEL_NAME``), so a later attention kernel is counted by naming it so and
any other Pallas kernel (a grouped expert product, say) is not."""

import re

NAME = "attn_kernel_share"
UNIT = "share"
LAYER = "kernels"
MOVES = "token_gap_mean_ms"
SOURCE = "device_trace"

PATTERN = re.compile(r"attention_kernel", re.IGNORECASE)


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
