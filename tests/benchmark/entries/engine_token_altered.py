"""Entry ``engine`` with the timed path broken underneath, for the
rehearsal: every result comes back with one token id altered where it is
produced.  A configuration that names this entry must read ``correct:
false``: the probe teacher-forces the reference on the ids the engine
returned, and an altered id lies far under the position's maximum."""

from __future__ import annotations

import dataclasses

from benchmark.entries import engine

ALTERED_POSITION = 3


class Handle(engine.Handle):
    async def generate(self, prompt, max_tokens, sampling, on_partial=None):
        result = await super().generate(prompt, max_tokens, sampling, on_partial)
        ids = list(result.token_ids)
        if len(ids) > ALTERED_POSITION:
            ids[ALTERED_POSITION] = (ids[ALTERED_POSITION] + 1) % self.vocab_size
        return dataclasses.replace(result, token_ids=ids)


def build(config_doc: dict) -> Handle:
    built = engine.build(config_doc)
    return Handle(built.engine, built.model_id)
