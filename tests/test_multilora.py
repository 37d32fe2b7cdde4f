"""Multi-LoRA serving: per-request adapters in one shared batch.

The gold standard is merge parity: a request served with adapter X
through the stacked multi-adapter engine must produce the same greedy
tokens as a plain engine whose weights had X merged in at load
(parallel/lora.py merge_lora) — for several adapters concurrently in ONE
batch, plus base-model requests riding along at index 0.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from operator_tpu.models import TINY_TEST, init_params
from operator_tpu.models.tokenizer import ByteTokenizer
from operator_tpu.parallel import (
    init_lora,
    load_lora,
    merge_lora,
    save_lora,
    stack_adapters,
    zero_lora,
)
from operator_tpu.serving.engine import BatchedGenerator, SamplingParams

CONFIG = TINY_TEST
RANK = 4


def _adapter(seed: int):
    """A rank-4 adapter with NONZERO b (init_lora zeros b, which would make
    every adapter a no-op and the parity tests vacuous)."""
    base = init_lora(CONFIG, jax.random.PRNGKey(seed), rank=RANK, dtype=jnp.float32)
    return {
        name: {
            "a": factors["a"],
            "b": jax.random.normal(
                jax.random.PRNGKey(seed + 100), factors["b"].shape, jnp.float32
            ) * 0.2,
        }
        for name, factors in base.items()
    }


@pytest.fixture(scope="module")
def params():
    return init_params(CONFIG, jax.random.PRNGKey(0), dtype=jnp.float32)


@pytest.fixture(scope="module")
def adapters():
    return {"incident": _adapter(1), "verbose": _adapter(2)}


def _generator(params, lora_adapters=None, **kw):
    return BatchedGenerator(
        params, CONFIG, ByteTokenizer(), max_slots=4, max_seq=128,
        cache_dtype=jnp.float32, paged=kw.pop("paged", True),
        page_size=16, decode_block=2, lora_adapters=lora_adapters, **kw,
    )


PROMPTS = ["oom killed", "crash loop", "disk is full"]
GREEDY = SamplingParams(max_tokens=8, temperature=0.0, stop_on_eos=False)


@pytest.mark.parametrize("paged", [True, False])
def test_mixed_adapters_match_merged_engines(params, adapters, paged):
    """One batch carrying base + two different adapters reproduces, token
    for token, three separate single-model engines (base, merge(incident),
    merge(verbose))."""
    multi = _generator(params, lora_adapters=adapters, paged=paged)
    sampling = [
        GREEDY,  # base model
        SamplingParams(max_tokens=8, temperature=0.0, stop_on_eos=False,
                       adapter="incident"),
        SamplingParams(max_tokens=8, temperature=0.0, stop_on_eos=False,
                       adapter="verbose"),
    ]
    slot_ids = multi.admit(PROMPTS, sampling)
    results = {}
    while multi.num_active:
        for slot_id, result in multi.step():
            results[slot_id] = result
    mixed = [results[s].token_ids for s in slot_ids]

    expected = []
    for adapter_name in (None, "incident", "verbose"):
        weights = (
            params if adapter_name is None
            else merge_lora(params, adapters[adapter_name])
        )
        single = _generator(weights, paged=paged)
        row = PROMPTS[[None, "incident", "verbose"].index(adapter_name)]
        expected.append(single.generate(row, GREEDY).token_ids)

    assert mixed == expected


def test_unknown_adapter_rejected(params, adapters):
    generator = _generator(params, lora_adapters=adapters)
    with pytest.raises(ValueError, match="unknown LoRA adapter"):
        generator.admit(
            ["x"], [SamplingParams(max_tokens=2, adapter="nope")]
        )
    assert generator.adapter_names == ["incident", "verbose"]
    # an engine without adapters rejects ANY adapter name
    plain = _generator(params)
    with pytest.raises(ValueError, match="unknown LoRA adapter"):
        plain.admit(["x"], [SamplingParams(max_tokens=2, adapter="incident")])


def test_zero_adapter_is_identity(params, adapters):
    """Requests with no adapter through a multi-LoRA engine match a plain
    engine exactly (stacked index 0 is the all-zeros adapter)."""
    multi = _generator(params, lora_adapters=adapters)
    plain = _generator(params)
    a = multi.generate("pod failed", GREEDY)
    b = plain.generate("pod failed", GREEDY)
    assert a.token_ids == b.token_ids


def test_save_load_roundtrip(tmp_path, adapters):
    path = str(tmp_path / "incident.safetensors")
    save_lora(adapters["incident"], path)
    loaded = load_lora(path, dtype=jnp.float32)
    for name, factors in adapters["incident"].items():
        for factor in ("a", "b"):
            assert loaded[name][factor].shape == factors[factor].shape
            assert jnp.allclose(loaded[name][factor], factors[factor])


def test_stack_shape_contract(adapters):
    zero = zero_lora(CONFIG, rank=RANK, targets=tuple(adapters["incident"]),
                     dtype=jnp.float32)
    stacked = stack_adapters([zero, adapters["incident"], adapters["verbose"]])
    wq = stacked["wq"]["a"]
    # [n_layers, n_adapters, in, r]: the layer axis stays leading for scan
    assert wq.shape == (CONFIG.num_layers, 3, CONFIG.hidden_size, RANK)


def test_completion_api_routes_adapters(params, adapters):
    """model=<adapter> on the OpenAI API selects the adapter; the base id
    and unknown names behave per the OpenAI contract."""
    import asyncio
    import json

    from operator_tpu.serving.engine import ServingEngine
    from operator_tpu.serving.httpserver import CompletionServer

    async def scenario():
        engine = ServingEngine(
            _generator(params, lora_adapters=adapters), admission_wait_s=0.005
        )
        server = CompletionServer(engine, model_id="tiny-test",
                                  host="127.0.0.1", port=0)
        await server.start()
        port = server.bound_port

        async def post(path, body):
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            payload = json.dumps(body).encode()
            writer.write(
                f"POST {path} HTTP/1.1\r\nHost: t\r\n"
                f"Content-Length: {len(payload)}\r\n\r\n".encode() + payload
            )
            await writer.drain()
            raw = await asyncio.wait_for(reader.read(), timeout=120)
            writer.close()
            head, _, body_bytes = raw.partition(b"\r\n\r\n")
            return int(head.split()[1]), json.loads(body_bytes)

        async def get(path):
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            writer.write(f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
            await writer.drain()
            raw = await asyncio.wait_for(reader.read(), timeout=60)
            writer.close()
            head, _, body_bytes = raw.partition(b"\r\n\r\n")
            return int(head.split()[1]), json.loads(body_bytes)

        try:
            status, body = await get("/v1/models")
            assert status == 200
            ids = [m["id"] for m in body["data"]]
            assert ids[:3] == ["tiny-test", "incident", "verbose"]
            assert body["data"][1]["parent"] == "tiny-test"

            request = {"prompt": "oom killed", "max_tokens": 6,
                       "temperature": 0.0}
            status, base = await post("/v1/completions", request)
            assert status == 200
            status, adapted = await post(
                "/v1/completions", {**request, "model": "incident"})
            assert status == 200
            assert adapted["model"] == "incident"
            # adapter selection reached the engine: the greedy tokens match
            # what the engine produces for that adapter directly (the full
            # merge-parity proof is test_mixed_adapters_match_merged_engines)
            direct = _generator(params, lora_adapters=adapters).generate(
                "oom killed",
                SamplingParams(max_tokens=6, temperature=0.0, adapter="incident"),
            )
            assert adapted["choices"][0]["text"] == direct.text

            status, err = await post(
                "/v1/completions", {**request, "model": "gpt-4"})
            assert status == 404
            assert "not found" in err["error"]["message"]
        finally:
            await server.stop()
            await engine.close()

    asyncio.run(scenario())


def test_unknown_adapter_fails_only_that_request(params, adapters):
    """A bad adapter name from any caller is rejected at SUBMIT time with a
    ValueError; co-batched valid requests are unaffected and the serving
    loop stays alive."""
    import asyncio

    from operator_tpu.serving.engine import ServingEngine

    async def scenario():
        engine = ServingEngine(
            _generator(params, lora_adapters=adapters), admission_wait_s=0.005
        )
        await engine.start()
        try:
            with pytest.raises(ValueError, match="unknown LoRA adapter"):
                await engine.generate(
                    "x", SamplingParams(max_tokens=2, adapter="typo"))
            # the loop survived: a valid request still completes
            ok = await engine.generate(
                "y", SamplingParams(max_tokens=2, temperature=0.0,
                                    adapter="incident"))
            assert ok.completion_tokens >= 1
        finally:
            await engine.close()

    asyncio.run(scenario())


def test_lora_dir_loader_isolates_bad_adapters(tmp_path, params, adapters, monkeypatch):
    """build_serving_engine survives a LORA_DIR containing: a valid adapter,
    an empty file, a rank-mismatched adapter, a corrupt file, and one whose
    name collides with the base model id — only the valid one registers."""
    from safetensors.numpy import save_file

    from operator_tpu.serving.provider import build_serving_engine
    from operator_tpu.utils.config import OperatorConfig

    lora_dir = tmp_path / "loras"
    lora_dir.mkdir()
    save_lora(adapters["incident"], str(lora_dir / "good.safetensors"))
    save_file({}, str(lora_dir / "empty.safetensors"))
    other_rank = init_lora(CONFIG, jax.random.PRNGKey(9), rank=RANK * 2,
                           dtype=jnp.float32)
    save_lora(other_rank, str(lora_dir / "rank8.safetensors"))
    (lora_dir / "corrupt.safetensors").write_bytes(b"not a safetensors file")
    save_lora(adapters["verbose"], str(lora_dir / "tiny-test.safetensors"))

    config = OperatorConfig(
        model_id="tiny-test", allow_random_weights=True,
        max_batch_size=2, decode_block=2, lora_dir=str(lora_dir),
        sched_mode="wave",  # adapters are the wave engine's to serve
    )
    engine, model_id = build_serving_engine(config)
    try:
        assert model_id == "tiny-test"
        assert engine.generator.adapter_names == ["good"]
    finally:
        engine._executor.shutdown(wait=False)


def test_lora_dir_missing_warns_not_crashes(tmp_path, caplog):
    from operator_tpu.serving.provider import build_serving_engine
    from operator_tpu.utils.config import OperatorConfig

    config = OperatorConfig(
        model_id="tiny-test", allow_random_weights=True,
        max_batch_size=2, decode_block=2,
        lora_dir=str(tmp_path / "does-not-exist"),
    )
    import logging

    with caplog.at_level(logging.WARNING):
        engine, _ = build_serving_engine(config)
    try:
        # a wave name: the HTTP server reads it the same way
        assert getattr(engine.generator, "adapter_names", []) == []
        assert any("lora_dir" in r.message for r in caplog.records)
    finally:
        engine._executor.shutdown(wait=False)
