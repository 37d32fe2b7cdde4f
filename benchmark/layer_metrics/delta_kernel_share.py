"""Device self time of the gated delta-rule kernel (events named
``gated_delta_kernel``: ``ops/gated_delta.py KERNEL_NAME``) over device
busy time, from the trace.  ``attn_kernel_share`` counts
``attention_kernel`` and ``ssm_kernel_share`` ``ssm_scan_kernel``, so
neither this one.  None for a program that runs no such kernel."""

import re

NAME = "delta_kernel_share"
UNIT = "share"
LAYER = "kernels"
MOVES = "token_gap_mean_ms"
SOURCE = "device_trace"

PATTERN = re.compile(r"gated_delta_kernel", re.IGNORECASE)


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
