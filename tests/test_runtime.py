"""The seam between the engines: ``serving/runtime.py``.

The continuous scheduler stands on a bare :class:`Runtime` and reads
nothing a ``Runtime`` does not define; a continuous deployment builds no
wave engine; the benchmark's handle finds its names on the runtime; and
the two engines trace one sampler.
"""

import asyncio

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from operator_tpu.models import family_of, get_config  # noqa: E402
from operator_tpu.models.tokenizer import ByteTokenizer  # noqa: E402
from operator_tpu.serving import sampler  # noqa: E402
from operator_tpu.serving.engine import BatchedGenerator, ServingEngine  # noqa: E402
from operator_tpu.serving.runtime import Runtime  # noqa: E402
from operator_tpu.serving.sched import Scheduler  # noqa: E402
from operator_tpu.serving.types import SamplingParams  # noqa: E402
from operator_tpu.utils.config import OperatorConfig  # noqa: E402
from operator_tpu.utils.timing import MetricsRegistry  # noqa: E402

#: what only the wave engine has: none of it on a continuous deployment
WAVE_ONLY = (
    "_decode_fn", "_prefill_fns", "_guided_cache", "lora", "_prefixes",
    "_inflight_blocks",
)

PROMPTS = [
    "pod api-7 OOMKilled after 3 restarts in namespace payments",
    "x",
    "CrashLoopBackOff: back-off restarting failed container worker " * 2,
    "ImagePullBackOff registry timeout",
    "liveness probe failed: connection refused on :8080/healthz",
]


def build(cls, model_id, **kw):
    config = get_config(model_id)
    params = family_of(config).init_params(
        config, jax.random.PRNGKey(0), dtype=jnp.float32
    )
    return cls(
        params, config, ByteTokenizer(), max_slots=3, max_seq=128,
        page_size=16, cache_dtype=jnp.float32, metrics=MetricsRegistry(), **kw,
    )


def drain(sched, prompts, sampling):
    """Token ids per prompt, in prompt order, through ``sched`` alone."""
    ids = [sched.enqueue(p, sampling) for p in prompts]
    done = {}
    while sched.total_work:
        for outcome in sched.step():
            assert outcome.error is None, outcome.error
            done[outcome.req_id] = outcome.result.token_ids
    return [done[i] for i in ids]


@pytest.mark.parametrize("model_id", ["tiny-test", "tiny-falcon-h1"])
def test_a_bare_runtime_carries_the_scheduler_as_a_wave_generator_does(model_id):
    """Five requests over three slots, chunked prefill, pipelined, a
    reset in between: whatever ``sched/`` reads, ``Runtime`` defines, and
    the tokens are those the same scheduler gives over a
    ``BatchedGenerator``."""
    sampling = SamplingParams(max_tokens=6, temperature=0.0, stop_on_eos=False)

    def served(cls, **kw):
        runtime = build(cls, model_id, **kw)
        sched = Scheduler(runtime, chunk=8, token_budget=12, pipeline_depth=2)
        first = drain(sched, PROMPTS, sampling)
        runtime.reset()
        sched.reset()
        assert runtime.free_slots() == [0, 1, 2]
        assert runtime.allocator.available == runtime.allocator.num_pages - 1
        return first, drain(sched, PROMPTS[:2], sampling)

    bare = served(Runtime)
    assert type(build(Runtime, model_id)) is Runtime
    assert bare == served(BatchedGenerator, paged=True)
    assert bare[1] == bare[0][:2]  # after the reset, the same answers


def test_every_name_the_scheduler_reads_is_one_the_runtime_defines():
    """Read from the text of ``serving/sched/``: each attribute taken off
    ``g`` / ``self.generator`` / ``runtime`` is assigned in ``Runtime``'s
    own source or defined on the class, and nothing there imports the
    wave engine's modules."""
    import inspect
    import pathlib
    import re

    import operator_tpu.serving.sched as sched_pkg

    defined = set(vars(Runtime)) | set(
        re.findall(r"self\.(\w+)\s*(?::[^=\n]+)?=", inspect.getsource(Runtime))
    )
    read = set()
    for path in pathlib.Path(sched_pkg.__file__).parent.glob("*.py"):
        text = path.read_text()
        assert not re.search(
            r"^\s*(from|import)\s+\S*(engine|programs|admission)\b", text, re.M
        ), path.name
        # not the path "serving/runtime.py" in a docstring
        read |= set(re.findall(r"(?<![/\w])(?:g|generator|runtime)\.(\w+)", text))
    assert len(read) > 20  # the pattern still finds them
    assert read <= defined, sorted(read - defined)


def operator_config(**kw):
    kw.setdefault("model_id", "tiny-test")
    return OperatorConfig(
        allow_random_weights=True, serving_dtype="bf16", max_batch_size=3,
        kv_page_size=16, sched_chunk=8, sched_token_budget=12, **kw,
    )


def built(config):
    from operator_tpu.serving.provider import build_serving_engine

    engine, _ = build_serving_engine(config)
    return engine


def close(engine):
    asyncio.run(engine.close())


def test_the_default_deployment_builds_a_runtime_and_no_wave_engine():
    engine = built(operator_config())
    try:
        assert type(engine.generator) is Runtime
        assert engine._sched is not None
        assert engine._sched.generator is engine.generator
        for name in WAVE_ONLY:
            assert not hasattr(engine.generator, name), name
    finally:
        close(engine)
    wave = built(operator_config(sched_mode="wave"))
    try:
        assert isinstance(wave.generator, BatchedGenerator)
        assert wave._sched is None
        for name in WAVE_ONLY:
            assert hasattr(wave.generator, name), name
    finally:
        close(wave)


def test_the_benchmarks_handle_finds_its_names_on_the_runtime():
    """``benchmark/entries/engine.py Handle``, name for name."""
    engine = built(operator_config())
    g = engine.generator
    reads = {
        "max_slots": int, "max_seq": int, "config": object,
        "tokenizer": object, "metrics": MetricsRegistry, "params": dict,
        "paged_cache": object, "_truncate_prompt": object,
        "step_clock": object, "_clock": object, "_jax": object,
    }
    for name, kind in reads.items():
        assert isinstance(getattr(g, name), kind), name
    assert g.step_clock.ring.records() == []
    assert g.config.vocab_size and g.tokenizer.eos_id is not None
    assert g._truncate_prompt(list(range(10)), 4) == [6, 7, 8, 9]
    pages = engine._sched.page_accounting()
    assert {"row_pages", "total", "available", "prefix_pages"} <= set(pages)
    assert pages["prefix_pages"] == 0
    assert engine.compile_watch is not None
    close(engine)
    # Handle.close's three assignments
    g.params = g.paged_cache = g.cache = None
    assert g.params is None and g.paged_cache is None and g.cache is None


def test_wave_knobs_no_longer_decide_a_continuous_deployment():
    """``pipeline_depth * decode_block * 2 > max_seq`` is the wave decode
    block's stop margin: it refuses a wave engine and nothing else."""
    knobs = dict(decode_block=64, pipeline_depth=8)  # 1,024 > 256
    engine = built(operator_config(**knobs))
    assert type(engine.generator) is Runtime
    close(engine)
    with pytest.raises(ValueError, match="stop margin"):
        built(operator_config(sched_mode="wave", **knobs))


def test_both_engines_trace_the_one_sampler(monkeypatch):
    """The mixed step and the wave programs call ``runtime.sample``, and
    that is ``sampler.sample`` with the runtime's ``top_k`` bound."""
    calls = []
    real = sampler.sample

    def counting(logits, rng, temp, top_p, *, top_k):
        calls.append(top_k)
        return real(logits, rng, temp, top_p, top_k=top_k)

    monkeypatch.setattr("operator_tpu.serving.runtime.sample", counting)
    sampling = SamplingParams(max_tokens=2, temperature=0.0, stop_on_eos=False)
    bare = build(Runtime, "tiny-test", sample_top_k=7)
    assert bare.sample.func is counting and bare.sample.keywords == {"top_k": 7}
    drain(Scheduler(bare, chunk=8, token_budget=12), ["a"], sampling)
    assert calls == [7]  # the mixed step, traced once
    wave = build(BatchedGenerator, "tiny-test", paged=True, sample_top_k=9)
    assert wave.sample.func is counting
    wave.generate("a", sampling)
    assert calls[1:] == [9, 9]  # the prefill program and the decode block


def scheduled_engine():
    runtime = build(Runtime, "tiny-test")
    return ServingEngine(
        runtime, scheduler=Scheduler(runtime, chunk=8, token_budget=12)
    )


def test_add_prefix_is_no_device_work_under_the_scheduler():
    engine = scheduled_engine()
    before = engine._sched.page_accounting()
    pool = engine.generator.paged_cache

    async def run():
        cached = await engine.add_prefix("You are an SRE. " * 40)
        await engine.close()
        return cached

    assert asyncio.run(run()) == 0
    assert engine._sched.page_accounting() == before
    assert engine.generator.paged_cache is pool  # no program ran over it


def test_ensure_guided_under_the_scheduler_is_generates_value_error():
    engine = scheduled_engine()

    async def run():
        with pytest.raises(ValueError, match="continuous scheduler mode") as told:
            await engine.ensure_guided(("choice", ("a", "b")))
        with pytest.raises(ValueError) as same:
            await engine.generate(
                "p", SamplingParams(max_tokens=2, guided_choice=["a", "b"])
            )
        await engine.close()
        return str(told.value), str(same.value)

    told, same = asyncio.run(run())
    assert told == same


def test_the_sampler_keeps_its_candidates_inside_top_k():
    """A nucleus that would reach past ``top_k`` candidates is cut at
    ``top_k``: with top_k=1 every temperature is the argmax."""
    logits = jnp.asarray(np.random.default_rng(2).normal(size=(4, 32)), jnp.float32)
    picked, _ = sampler.sample(
        logits, jax.random.PRNGKey(2), jnp.full((4,), 2.0), jnp.ones(4), top_k=1
    )
    np.testing.assert_array_equal(
        np.asarray(picked), np.asarray(jnp.argmax(logits, axis=-1))
    )
