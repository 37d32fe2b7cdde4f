"""Entry ``engine``: the cell drives ``ServingEngine.generate`` in-process.

Builds an ``OperatorConfig`` from the configuration file's ``engine`` map
(existing field names only), calls ``serving/provider.py
build_serving_engine`` exactly as the ``tpu-native`` provider does, and
hands the harness a small handle.  Everything the benchmark reads from the
program passes through this file, so a change to the program's internals
has one place to be followed here.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional


class Handle:
    """What a cell needs from the system under test."""

    def __init__(self, engine: Any, model_id: str) -> None:
        self.engine = engine
        self.model_id = model_id
        self._generator = engine.generator

    # -- sizes --------------------------------------------------------
    @property
    def slots(self) -> int:
        return int(self._generator.max_slots)

    @property
    def vocab_size(self) -> int:
        return int(self._generator.config.vocab_size)

    @property
    def eos_id(self) -> Optional[int]:
        return self._generator.tokenizer.eos_id

    # -- requests -----------------------------------------------------
    async def generate(
        self,
        prompt: str,
        max_tokens: int,
        sampling: dict,
        on_partial: Optional[Callable[[list], None]] = None,
    ) -> Any:
        """One request; returns the program's ``GenerationResult``
        (``token_ids``, ``prompt_tokens``, ``completion_tokens``,
        ``finish_reason``, ``queue_wait_ms``)."""
        from operator_tpu.serving.types import SamplingParams

        params = SamplingParams(max_tokens=int(max_tokens), **sampling)
        return await self.engine.generate(prompt, params, on_partial=on_partial)

    def prompt_ids(self, prompt: str, max_tokens: int) -> list[int]:
        """The token ids the scheduler prefills for ``prompt``: its own
        tokenizer, BOS and truncation rule (``Scheduler.enqueue``)."""
        from operator_tpu.serving.types import prompt_budget

        g = self._generator
        return list(g._truncate_prompt(
            g.tokenizer.encode(prompt), prompt_budget(g.max_seq, max_tokens)
        ))

    # -- counters and spans --------------------------------------------
    def steps_recorded(self) -> int:
        """Step records appended so far (the next record's ``seq``)."""
        ring = self._generator.step_clock.ring
        with ring._lock:
            return ring._seq

    def step_records(self, first_seq: int, end_seq: int) -> list:
        """The step clock's records with ``first_seq <= seq < end_seq``.
        Raises if the ring evicted some of them (capacity too small)."""
        records = [
            r for r in self._generator.step_clock.ring.records()
            if first_seq <= r.seq < end_seq
        ]
        if len(records) != end_seq - first_seq:
            raise RuntimeError(
                f"step ring kept {len(records)} of {end_seq - first_seq} "
                "records of the window: raise step_ring_capacity"
            )
        return records

    def mark_compiles(self) -> None:
        self.engine.compile_watch.mark()

    def compiles_since_mark(self) -> list[tuple]:
        """``(seconds after the mark, program, compile seconds, cache hit)``."""
        return list(self.engine.compile_watch.events_since_mark())

    def pool_pages(self) -> Optional[tuple]:
        """``(pages granted to live rows, pages in use, pages in all)`` of
        the paged KV pool right now (``Scheduler.page_accounting``); in use
        counts the prefix cache's pages too.  Read from the load
        generator's thread while the scheduler's thread admits and
        retires rows: a read that catches the row table changing is
        dropped (None)."""
        sched = getattr(self.engine, "_sched", None)
        if sched is None:
            return None
        try:
            pages = sched.page_accounting()
        except RuntimeError:  # dictionary changed size during iteration
            return None
        return pages["row_pages"], pages["total"] - pages["available"], pages["total"]

    def engine_resets(self) -> int:
        return int(self._generator.metrics.counter("supervisor_restart"))

    # -- parameters (for the weight floor and the reference's adapter) ---
    def param_bytes(self) -> int:
        import jax

        return sum(
            int(leaf.size * leaf.dtype.itemsize)
            for leaf in jax.tree_util.tree_leaves(self._generator.params)
        )

    def parameters(self) -> Any:
        """The program's parameter tree as the engine holds it, for
        ``tools/weights_check.py`` alone: what decides ``correct`` never
        reads it (the reference makes weights of its own)."""
        return self._generator.params

    async def close(self) -> None:
        """Close the engine and free its device state, the weights and the
        KV pool: the reference, which runs next, needs the room."""
        import jax

        await self.engine.close()
        g = self._generator
        state = (g.params, getattr(g, "paged_cache", None), getattr(g, "cache", None))
        g.params = g.paged_cache = g.cache = None
        for leaf in jax.tree_util.tree_leaves(state):
            if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                leaf.delete()


def operator_config(engine_map: dict) -> Any:
    """An ``OperatorConfig`` with the program's defaults and the
    configuration's ``engine`` values; an unknown name is an error."""
    from operator_tpu.utils.config import OperatorConfig

    known = {f.name for f in dataclasses.fields(OperatorConfig)}
    unknown = sorted(set(engine_map) - known)
    if unknown:
        raise ValueError(f"not OperatorConfig fields: {unknown}")
    return OperatorConfig(**engine_map)


def build(config_doc: dict) -> Handle:
    """Build the serving engine for one configuration file.  Fails where
    ``utils/platform.resolve_device`` fails: no chip, no numbers."""
    from operator_tpu.serving.provider import build_serving_engine

    config = operator_config(
        {"model_id": config_doc["model_id"], **config_doc.get("engine", {})}
    )
    engine, model_id = build_serving_engine(config)
    return Handle(engine, model_id)
