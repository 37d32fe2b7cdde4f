"""Device self time of the grouped expert product (events named
``moe_experts_kernel``: ``ops/moe_experts.py KERNEL_NAME``) over device
busy time, from the trace.  The gathers into and out of the kernel's row
layout are XLA's and are not in it.  None for a program that runs no such
kernel."""

import re

NAME = "moe_kernel_share"
UNIT = "share"
LAYER = "kernels"
MOVES = "token_gap_mean_ms"
SOURCE = "device_trace"

PATTERN = re.compile(r"moe_experts_kernel", re.IGNORECASE)


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
