"""Kernel-vs-reference dispatch: the one rule every op in this package
follows.

A TPU default backend runs the Pallas kernel; any other backend runs the
dense reference.  Nothing in between: on a TPU a kernel that fails to
lower or run raises to the caller — no dispatcher, and no caller of one,
catches the failure and substitutes the reference, interpret mode or
numpy, because a substituted path is exactly what a chip bring-up must
not hide.
"""

from __future__ import annotations

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"
