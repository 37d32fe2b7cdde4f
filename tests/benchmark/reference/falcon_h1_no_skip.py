"""A reference that is deliberately wrong, for the rehearsal: Falcon-H1's
(``benchmark/reference/falcon_h1_f32.py``) without the mixer's ``D`` skip
(``y_t = H_t C_t`` in place of ``H_t C_t + D_h x_t``).  A configuration
that names it must read ``correct: false``: the served tokens are then
held to another model's logits."""

from __future__ import annotations

from benchmark.reference import falcon_h1_f32

WEIGHTS = falcon_h1_f32.WEIGHTS


class _NoSkip:
    """The weights with ``D`` at zero in every layer."""

    def __init__(self, weights):
        self._weights = weights

    def __getattr__(self, name):
        return getattr(self._weights, name)

    def layer(self, index):
        layer = dict(self._weights.layer(index))
        layer["d_skip"] = layer["d_skip"] * 0.0
        return layer


def greedy_gaps(config_doc: dict, weights, sequences: list) -> list:
    return falcon_h1_f32.greedy_gaps(config_doc, _NoSkip(weights), sequences)
