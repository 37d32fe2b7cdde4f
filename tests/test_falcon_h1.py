"""Falcon-H1 (models/falcon_h1.py) at a small size on the CPU: the family's
forward pass against the benchmark's plain reference, the continuous
scheduler's mixed step with its recurrent state pool against the
reference's full forward at every position, the scan kernel in interpret
mode against the token-by-token recurrence, what the program switches off
or refuses for a model with recurrent state, and the matmul parameter
count of both families against their initialised trees.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import falcon_h1_f32 as reference  # noqa: E402
from benchmark.reference import falcon_h1_f32_weights as own  # noqa: E402
from operator_tpu.models import family_of, get_config  # noqa: E402
from operator_tpu.models.quant import init_params_quantized  # noqa: E402
from operator_tpu.models.tokenizer import ByteTokenizer  # noqa: E402
from operator_tpu.ops.ssm_scan import (  # noqa: E402
    _ssm_scan_pallas,
    ssm_scan_reference,
)
from operator_tpu.serving.engine import BatchedGenerator, SamplingParams  # noqa: E402
from operator_tpu.serving.sched import Scheduler  # noqa: E402
from operator_tpu.utils.timing import MetricsRegistry  # noqa: E402

CONFIG = get_config("tiny-falcon-h1")
FAMILY = family_of(CONFIG)


def config_doc(dtype="float32", bits=0):
    """A configuration file's two groups the reference reads, for the
    tiny model: every ``architecture`` key from the program's config."""
    return {
        "architecture": {
            key: getattr(CONFIG, attribute)
            for key, attribute in own.PROGRAM_CONFIG.items()
        },
        "weights": {"seed": 0, "init": "falcon_h1_fan_in", "dtype": dtype, "bits": bits},
    }


@pytest.fixture(scope="module")
def params():
    return FAMILY.init_params(CONFIG, jax.random.PRNGKey(0), dtype=jnp.float32)


@pytest.fixture(scope="module")
def reference_weights(params):
    # the program's float32 tree under the reference's interface (float32
    # draws differ in the last bit between a jitted and a stepwise draw;
    # bfloat16 and int8 leaves, which a cell runs, are equal bit for bit)
    return own.adapt(params, config_doc())


def test_every_multiplier_of_the_tiny_model_differs_from_one():
    scalars = [
        getattr(CONFIG, name) for name in dir(CONFIG)
        if name.endswith("_multiplier") and not name.startswith("_")
    ]
    assert len(scalars) == 7
    assert all(m != 1.0 for m in scalars + list(CONFIG.mlp_multipliers) + list(CONFIG.ssm_multipliers))


# -- (a) the program's full forward against the reference ---------------------


@pytest.mark.parametrize("dtype, bits, tolerance", [
    ("float32", 0, 2e-5),
    # bfloat16 activations against float32: what a cell's probe limit is for
    ("bfloat16", 8, 0.06),
])
def test_forward_equals_the_reference(dtype, bits, tolerance):
    doc = config_doc(dtype, bits)
    if bits:
        tree = init_params_quantized(CONFIG, jax.random.PRNGKey(0))
    else:
        tree = FAMILY.init_params(CONFIG, jax.random.PRNGKey(0), dtype=jnp.float32)
    ids = [int(t) for t in np.random.default_rng(1).integers(1, 500, 45)]
    got, _ = FAMILY.forward(
        tree, CONFIG, jnp.asarray([ids], jnp.int32), jnp.arange(len(ids))[None]
    )
    want = np.asarray(reference.logits(doc, own.adapt(tree, doc), ids))
    assert np.abs(want).max() > 0.3  # the logits are not all alike
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=tolerance, rtol=0)


def test_the_references_own_int8_weights_are_the_programs_bit_for_bit():
    doc = config_doc("bfloat16", 8)
    mine = own.make(doc)
    theirs = own.adapt(init_params_quantized(CONFIG, jax.random.PRNGKey(0)), doc)
    assert set(mine.layers) == set(theirs.layers) == set(own.MATRICES + own.VECTORS)
    for name, leaf in mine.layers.items():
        other = theirs.layers[name]
        pairs = (
            [(leaf["q"], other["q"]), (leaf["s"], other["s"])]
            if isinstance(leaf, dict) else [(leaf, other)]
        )
        for a, b in pairs:
            assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b)), name
    for name in ("embed", "lm_head", "ln_final"):
        assert np.array_equal(np.asarray(mine.leaves[name]), np.asarray(theirs.leaves[name]))
    # a non-zero convolution bias: a dropped one cannot hide
    assert float(jnp.abs(mine.layers["conv_b"].astype(jnp.float32)).max()) > 0.01


# -- (b) through the scheduler -------------------------------------------------


def make_generator(params, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_seq", 128)
    kw.setdefault("page_size", 16)
    return BatchedGenerator(
        params, CONFIG, ByteTokenizer(), paged=True,
        cache_dtype=jnp.float32, metrics=MetricsRegistry(), **kw,
    )


def capture_logits(generator):
    """Every step's ``[slots, vocab]`` logits, as the sampler is given
    them, in step order."""
    seen = []
    sample = generator.sample

    def recording(logits, rng, temp, top_p):
        jax.debug.callback(lambda value: seen.append(np.asarray(value)), logits)
        return sample(logits, rng, temp, top_p)

    generator.sample = recording
    return seen


def test_every_position_through_the_scheduler_equals_the_full_forward(
    params, reference_weights
):
    """Prompts prefilled in chunks of 8 (the convolution reaches 3 tokens
    back across every chunk boundary), then decoded; five requests over
    three slots, so rows join and leave, slots get a second tenant without
    any clearing from the host, and slots sit idle between."""
    generator = make_generator(params)
    seen = capture_logits(generator)
    sched = Scheduler(generator, chunk=8, token_budget=12)
    sched.plan_log = []
    rng = np.random.default_rng(5)
    lengths = [19, 5, 11, 16, 3]
    prompts = ["".join(chr(int(c)) for c in rng.integers(97, 123, n)) for n in lengths]
    answers = [6, 9, 4, 5, 7]
    ids, done, state_rows = {}, {}, []
    for i, (prompt, n) in enumerate(zip(prompts, answers)):
        ids[sched.enqueue(
            prompt, SamplingParams(max_tokens=n, temperature=0.0, stop_on_eos=False)
        )] = i
        for outcome in sched.step():  # arrivals spread over the steps
            done[outcome.req_id] = outcome
    for _ in range(200):
        if len(done) == len(prompts):
            break
        for outcome in sched.step():
            done[outcome.req_id] = outcome
    assert len(done) == len(prompts) and all(o.error is None for o in done.values())
    assert generator.metrics.counter("sched_recycled_slot") == len(prompts)
    assert len(seen) == len(sched.plan_log)
    # the reference's full forward over each request's prompt + answer
    tokenizer = generator.tokenizer
    full = {}
    for req_id, i in ids.items():
        sequence = list(tokenizer.encode(prompts[i])) + list(done[req_id].result.token_ids)
        full[req_id] = np.asarray(reference.logits(config_doc(), reference_weights, sequence))
    compared, second_tenants, slots_of = 0, 0, {}
    for step_logits, plan in zip(seen, sched.plan_log):
        state_rows.append(len(plan))
        for slot, req_id, _, count, kind, pos0, *_ in plan:
            if slots_of.setdefault(slot, req_id) != req_id:
                second_tenants += 1
                slots_of[slot] = req_id
            position = pos0 + count - 1  # the work's last token is sampled
            np.testing.assert_allclose(
                step_logits[slot], full[req_id][position], atol=3e-5, rtol=0,
                err_msg=f"request {req_id} {kind} at position {position}",
            )
            compared += 1
    assert second_tenants >= 2 and compared >= 35
    assert min(state_rows) < generator.max_slots  # steps with idle slots between
    # the step records carry the count of slots whose state a step touches
    records = generator.step_clock.ring.records()
    assert [r.state_rows for r in records] == state_rows
    # every state lives in the one cache object
    cache = generator.paged_cache
    assert cache.ssm_state.shape == (3, 3, 4, 16, 16) and cache.ssm_state.dtype == jnp.float32
    assert cache.conv_state.shape == (3, 3, 3, CONFIG.mamba_conv_dim)


def test_reset_zeroes_the_state_with_the_pool(params):
    generator = make_generator(params)
    sched = Scheduler(generator, chunk=8, token_budget=12)
    sched.enqueue("a crashed pod", SamplingParams(max_tokens=3, temperature=0.0))
    for _ in range(3):
        sched.step()
    assert float(jnp.abs(generator.paged_cache.ssm_state).max()) > 0
    generator.reset()
    sched.reset()
    assert float(jnp.abs(generator.paged_cache.ssm_state).max()) == 0
    assert float(jnp.abs(generator.paged_cache.conv_state).max()) == 0


# -- (c) the scan kernel, interpreted, against the recurrence -------------------


def recurrence(x, dt, a, b, c, state, layer, q_start, q_count, fresh):
    """The recurrence as the module's text states it, one slot, head and
    token at a time in numpy."""
    state = np.array(state)
    tokens, heads, _ = x.shape
    y = np.zeros(x.shape, np.float32)
    per_group = heads // b.shape[1]
    for slot, count in enumerate(q_count):
        for head in range(heads):
            group = head // per_group
            h = np.zeros_like(state[layer, slot, head]) if fresh[slot] else state[layer, slot, head].copy()
            for j in range(count):
                t = q_start[slot] + j
                h = np.exp(dt[t, head] * a[head]) * h + dt[t, head] * np.outer(b[t, group], x[t, head])
                y[t, head] = c[t, group] @ h
            if count:
                state[layer, slot, head] = h
    return y, state


@pytest.mark.parametrize("q_count", [
    [0, 1, 8, 0, 3, 1],  # idle, decode, a full chunk, idle, a part chunk, decode
    [0, 0, 0, 0, 0, 0],  # the scheduler's empty warm-up step
    [1, 1, 1, 1, 1, 1],  # all decoding
    [0, 0, 0, 0, 0, 8],  # live slots only after idle ones
])
@pytest.mark.parametrize("path", ["kernel", "reference"])
def test_scan_kernel_against_the_token_by_token_recurrence(q_count, path):
    rng = np.random.default_rng(0)
    tokens, heads, dim, groups, n, slots, layers, chunk = 32, 4, 16, 2, 16, 6, 3, 8
    x = rng.normal(size=(tokens, heads, dim)).astype(np.float32)
    dt = (np.abs(rng.normal(size=(tokens, heads))) * 0.5).astype(np.float32)
    a = -np.abs(rng.normal(size=(heads,))).astype(np.float32)
    b = rng.normal(size=(tokens, groups, n)).astype(np.float32)
    c = rng.normal(size=(tokens, groups, n)).astype(np.float32)
    state = rng.normal(size=(layers, slots, heads, n, dim)).astype(np.float32)
    q_count = np.asarray(q_count, np.int32)
    q_start = (np.concatenate([[0], np.cumsum(q_count)[:-1]]) + 2).astype(np.int32)
    fresh = np.asarray([0, 1, 0, 1, 1, 0], bool)
    want_y, want_state = recurrence(x, dt, a, b, c, state, 1, q_start, q_count, fresh)
    args = [jnp.asarray(v) for v in (x, dt, a, b, c, state)] + [
        jnp.int32(1), jnp.asarray(q_start), jnp.asarray(q_count), jnp.asarray(fresh),
    ]
    if path == "kernel":
        got_y, got_state = _ssm_scan_pallas(*args, interpret=True, heads_per_block=2)
    else:
        got_y, got_state = ssm_scan_reference(*args, chunk=chunk)
    live = np.zeros(tokens, bool)
    for start, count in zip(q_start, q_count):
        live[start:start + count] = True
    np.testing.assert_allclose(np.asarray(got_y)[live], want_y[live], atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(got_state), want_state, atol=2e-5, rtol=0)
    # a slot without tokens is not touched, fresh or not; nor another layer
    idle = q_count == 0
    assert np.array_equal(np.asarray(got_state)[:, idle], state[:, idle])
    assert np.array_equal(np.asarray(got_state)[[0, 2]], state[[0, 2]])


# -- (d) what is switched off, and what is refused -----------------------------


def operator_config(**kw):
    from operator_tpu.utils.config import OperatorConfig

    return OperatorConfig(
        model_id="tiny-falcon-h1", allow_random_weights=True, serving_dtype="bf16",
        max_batch_size=3, kv_page_size=16, sched_chunk=8, sched_token_budget=12, **kw,
    )


def serve(config, prompts, sampling):
    import asyncio

    from operator_tpu.serving.provider import build_serving_engine

    engine, _ = build_serving_engine(config)

    async def run():
        out = [await engine.generate(p, sampling) for p in prompts]
        features = engine.serving_features()
        await engine.close()
        return out, features

    return asyncio.run(run())


def test_speculation_and_the_prefix_store_are_off_whatever_the_configuration_says():
    """A re-asked prompt (with the store on it would be served from pages
    with a zero state) and a ``temperature=0`` row (with speculation on it
    would draft, and a rejected draft cannot be rolled back) return what
    they return with both off, and ``/healthz`` says both are off."""
    prompt = "status: container app terminated exit code 137 reason=OOMKilled " * 2
    sampling = SamplingParams(max_tokens=10, temperature=0.0, stop_on_eos=False)
    plain, off = serve(
        operator_config(spec_decode=False, kv_prefix_cache=False), [prompt, prompt], sampling
    )
    asked, on = serve(
        operator_config(spec_decode=True, kv_prefix_cache=True), [prompt, prompt], sampling
    )
    assert [r.token_ids for r in asked] == [r.token_ids for r in plain]
    assert plain[0].token_ids == plain[1].token_ids  # the re-asked prompt
    for features in (off, on):
        assert features["recurrentState"] and features["modelFamily"] == "falcon_h1"
        assert features["schedMode"] == "continuous"
        assert features["specDecode"] is False and features["kvPrefixCache"] is False
    assert off["switchedOff"] == {}
    assert set(on["switchedOff"]) == {"spec_decode", "kv_prefix_cache"}


def test_healthz_reports_what_really_runs():
    import asyncio
    import json

    from operator_tpu.serving.httpserver import CompletionServer
    from operator_tpu.serving.provider import build_serving_engine

    engine, model_id = build_serving_engine(
        operator_config(spec_decode=True, kv_prefix_cache=True)
    )
    app = CompletionServer(engine, model_id=model_id)

    async def run():
        status, body = await app._route("GET", "/healthz", b"", None)
        await engine.close()
        return status, body

    status, body = asyncio.run(run())
    assert status == 200
    features = json.loads(json.dumps(body))["features"]
    assert features["specDecode"] is False and features["kvPrefixCache"] is False
    assert "recurrent state" in features["switchedOff"]["kv_prefix_cache"]


@pytest.mark.parametrize("change, names", [
    ({"sched_mode": "wave"}, "sched_mode='wave'"),
    ({"serving_mesh": "dp=1,tp=2"}, "serving_mesh='dp=1,tp=2'"),
])
def test_wave_mode_and_a_mesh_refuse_at_start_up(change, names):
    from operator_tpu.serving.provider import build_serving_engine

    with pytest.raises(ValueError) as refused:
        build_serving_engine(operator_config(**change))
    assert "falcon_h1 family" in str(refused.value) and names in str(refused.value)


def test_guided_decoding_and_lora_are_submit_errors_that_name_the_family(params):
    import asyncio

    from operator_tpu.serving.engine import ServingEngine

    generator = make_generator(params)
    engine = ServingEngine(generator, scheduler=Scheduler(generator, chunk=8, token_budget=12))

    async def run():
        for bad in ({"guided_choice": ["a", "b"]}, {"adapter": "x"}):
            with pytest.raises(ValueError, match="falcon_h1 family|unknown LoRA adapter"):
                await engine.generate("p", SamplingParams(max_tokens=2, **bad))
        await engine.close()

    asyncio.run(run())


# -- (e) the analytic parameter count, both families ---------------------------


@pytest.mark.parametrize("model_id", ["tiny-test", "tiny-falcon-h1"])
def test_matmul_param_count_equals_a_count_over_the_tree(model_id):
    from operator_tpu.serving.perf import matmul_param_count

    config = get_config(model_id)
    family = family_of(config)
    tree = family.init_params(config, jax.random.PRNGKey(0), dtype=jnp.float32)
    counted = sum(int(tree["layers"][name].size) for name in family.LAYER_MATRICES)
    counted += int((tree["embed"] if config.tie_embeddings else tree["lm_head"]).size)
    assert matmul_param_count(config) == counted
    # every other leaf of a layer is a vector, a norm or a convolution tap
    others = set(tree["layers"]) - set(family.LAYER_MATRICES)
    assert all(tree["layers"][name].ndim <= 3 for name in others)
    quantised = init_params_quantized(config, jax.random.PRNGKey(0))
    assert {
        name for name, leaf in quantised["layers"].items() if isinstance(leaf, dict)
    } == set(family.LAYER_MATRICES)


def test_the_published_sizes_and_the_cut(params):
    whole, cut = get_config("falcon-h1-34b"), get_config("falcon-h1-34b-6l")
    assert dataclasses.replace(whole, name=cut.name, num_layers=6) == cut
    assert (whole.num_layers, whole.mamba_in_dim, whole.mamba_conv_dim) == (72, 9248, 5120)
    shapes = family_of(whole).layer_matrix_shapes(cut)
    per_layer = sum(rows * cols for _, rows, cols in shapes.values())
    assert round(per_layer / 1e6, 1) == 430.1  # ISSUE 29's reckoning
