"""A reference that is deliberately wrong, for the rehearsal: the decoder
of ``numpy_f64.py`` with the rotary pairs taken as ``(x[2i], x[2i + 1])``,
the other published convention and not this model's.  A configuration that
names it must read ``correct: false``: the probe is decided by the
reference the file names, and an order-one fault fails its limit."""

from __future__ import annotations

import numpy as np

from . import numpy_f64

WEIGHTS = numpy_f64.WEIGHTS


def interleaved_rotation(x: np.ndarray, angles: np.ndarray) -> np.ndarray:
    cos, sin = np.cos(angles), np.sin(angles)
    out = np.empty_like(x)
    out[:, 0::2] = x[:, 0::2] * cos - x[:, 1::2] * sin
    out[:, 1::2] = x[:, 1::2] * cos + x[:, 0::2] * sin
    return out


def greedy_gaps(config_doc: dict, weights: dict, sequences: list) -> list:
    return numpy_f64.greedy_gaps(config_doc, weights, sequences, interleaved_rotation)
