"""Ouro (models/ouro.py) at a small size on the CPU: the served path
(chunked prefill, then decode through a paged pool with a plane for every
pass and layer) against the benchmark's plain float32 reference's full
forward; three faulty programs that the same comparison catches; a
prefix-cache hit, a host-pool restore and a verify row with rejected
drafts against a cold plain row; the exit distribution; the published
checkpoint's key names; what the program refuses for this family; and the
matmul parameter count with its passes.
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

from benchmark.reference import ouro_f32 as reference  # noqa: E402
from benchmark.reference import ouro_f32_weights as own  # noqa: E402
from operator_tpu.models import family_of, get_config, llama, ouro  # noqa: E402
from operator_tpu.models.configs import OuroConfig  # noqa: E402
from operator_tpu.models.quant import init_params_quantized  # noqa: E402
from operator_tpu.models.tokenizer import ByteTokenizer  # noqa: E402
from operator_tpu.serving.engine import BatchedGenerator, SamplingParams  # noqa: E402
from operator_tpu.serving.sched import Scheduler  # noqa: E402
from operator_tpu.utils.timing import MetricsRegistry  # noqa: E402

TINY = get_config("tiny-ouro")  # two layers, three passes, 4 MHA heads x 128
CONFIGS = {3: TINY, 2: dataclasses.replace(TINY, name="tiny-ouro-2", total_ut_steps=2)}
#: float32 on both sides: what is left is the order of the sums
ATOL = 5e-5


def config_doc(config, dtype="float32", bits=0):
    """A configuration file's two groups the reference reads, for a tiny
    model: every ``architecture`` key from the program's config."""
    return {
        "architecture": {
            key: getattr(config, attribute)
            for key, attribute in own.PROGRAM_CONFIG.items()
        },
        "weights": {"seed": 0, "init": "ouro_fan_in", "dtype": dtype, "bits": bits},
    }


def tiny_params(config, seed=0):
    """The seeded init with every norm scale and the gate drawn well away
    from 1 and 0, so that a swapped or a dropped norm moves the logits."""
    tree = ouro.init_params(config, jax.random.PRNGKey(seed), dtype=jnp.float32)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 8))
    for name in ouro.LAYER_NORMS:
        tree["layers"][name] = jax.random.uniform(
            next(keys), tree["layers"][name].shape, jnp.float32, 0.4, 1.8
        )
    tree["ln_final"] = jax.random.uniform(next(keys), tree["ln_final"].shape, jnp.float32, 0.4, 1.8)
    tree["exit_w"] = jax.random.normal(next(keys), tree["exit_w"].shape, jnp.float32) * 0.3
    tree["exit_b"] = jnp.float32(-0.7)
    return tree


@pytest.fixture(scope="module")
def params():
    return {passes: tiny_params(config) for passes, config in CONFIGS.items()}


def make_generator(tree, config, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_seq", 128)
    kw.setdefault("page_size", 16)
    return BatchedGenerator(
        tree, config, ByteTokenizer(), paged=True,
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


def drain(sched, want, done=None, limit=300):
    """Step until ``want`` requests are done (``done`` holds those so far)."""
    done = {} if done is None else done
    for _ in range(limit):
        if len(done) >= want:
            break
        for outcome in sched.step():
            done[outcome.req_id] = outcome
    assert len(done) >= want and all(o.error is None for o in done.values())
    return done


def served_against_the_reference(tree, config, reference_tree=None, reference_config=None):
    """Five requests over three slots, prompts prefilled in chunks of 8 and
    then decoded, so that every pass reads keys an earlier STEP wrote: the
    largest difference between the logits the sampler was given and the
    reference's full forward at the same position, the positions compared,
    and the generator."""
    generator = make_generator(tree, config)
    seen = capture_logits(generator)
    sched = Scheduler(generator, chunk=8, token_budget=12)
    sched.plan_log = []
    rng = np.random.default_rng(5)
    prompts = [
        "".join(chr(int(c)) for c in rng.integers(97, 123, n)) for n in [19, 5, 11, 16, 3]
    ]
    ids, done = {}, {}
    for i, (prompt, n) in enumerate(zip(prompts, [6, 9, 4, 5, 7])):
        ids[sched.enqueue(
            prompt, SamplingParams(max_tokens=n, temperature=0.0, stop_on_eos=False)
        )] = i
        for outcome in sched.step():  # arrivals spread over the steps
            done[outcome.req_id] = outcome
    drain(sched, len(prompts), done)
    assert len(done) == len(prompts) and len(seen) == len(sched.plan_log)
    doc = config_doc(reference_config or config)
    weights = own.adapt(reference_tree or tree, doc)
    full = {}
    for req_id, i in ids.items():
        sequence = list(generator.tokenizer.encode(prompts[i])) + list(done[req_id].result.token_ids)
        full[req_id] = np.asarray(reference.logits(doc, weights, sequence))
    worst, compared = 0.0, 0
    for step_logits, plan in zip(seen, sched.plan_log):
        for slot, req_id, _, count, _, pos0, *_ in plan:
            position = pos0 + count - 1  # the work's last token is sampled
            worst = max(worst, float(np.abs(step_logits[slot] - full[req_id][position]).max()))
            compared += 1
    return worst, compared, generator


# -- (a) the served path against the reference's full forward ------------------


@pytest.mark.parametrize("passes", [3, 2])
def test_every_position_through_the_scheduler_equals_the_full_forward(params, passes):
    config = CONFIGS[passes]
    worst, compared, generator = served_against_the_reference(params[passes], config)
    assert compared >= 35 and worst < ATOL, worst
    # a plane for every pass and layer, pass-major, in the one pool
    pool = generator.paged_cache.k_pages
    assert pool.shape[0] == config.kv_planes == passes * config.num_layers
    filled = np.asarray(jnp.abs(pool).sum(axis=(1, 2, 3, 4)) > 0)
    assert filled.all()  # every plane was written
    # and the step records say how often a token took the stack
    records = generator.step_clock.ring.records()
    assert records and {r.passes for r in records} == {passes}


def test_a_model_of_one_pass_records_one_pass_and_has_a_plane_a_layer():
    config = get_config("tiny-test")
    assert config.kv_planes == config.num_layers
    tree = family_of(config).init_params(config, jax.random.PRNGKey(0), dtype=jnp.float32)
    generator = make_generator(tree, config)
    sched = Scheduler(generator, chunk=8, token_budget=12)
    sched.enqueue("a crashed pod", SamplingParams(max_tokens=3, temperature=0.0))
    drain(sched, 1)
    assert {r.passes for r in generator.step_clock.ring.records()} == {1}
    assert generator.paged_cache.k_pages.shape[0] == config.num_layers


MIXED_LAYER = ouro.mixed_layer


def shared_planes(config, step):
    """A faulty layer body: every pass writes and reads the planes of the
    first, as a pool with a plane a layer would make it."""
    body = MIXED_LAYER(config, step)
    return lambda carry, scanned: body(
        carry, {**scanned, "layer": scanned["layer"] % config.num_layers}
    )


def unrolled_without_the_pass_norm(tree, config):
    """The same layers as ONE pass over a stack ``passes`` times as deep:
    what a pass loop without the end-of-pass norm computes (plane ``t * L
    + l`` is layer ``t * L + l``'s), with the final norm at the end."""
    deep = dataclasses.replace(
        config, name="tiny-ouro-unrolled", total_ut_steps=1,
        num_layers=config.num_layers * config.total_ut_steps,
    )
    layers = {
        name: jnp.concatenate([leaf] * config.total_ut_steps, axis=0)
        for name, leaf in tree["layers"].items()
    }
    return {**tree, "layers": layers}, deep


@pytest.mark.parametrize("fault", ["shared planes", "no end-of-pass norm", "pre-norm"])
def test_a_faulty_program_fails_the_same_comparison(params, monkeypatch, fault):
    """The comparison above is not passed by a pool whose passes share
    planes, by a pass loop without the end-of-pass norm, or by the Llama
    family's pre-norm layer in place of the sandwich."""
    config, tree = CONFIGS[3], params[3]
    served = (tree, config)
    if fault == "shared planes":
        monkeypatch.setattr(ouro, "mixed_layer", shared_planes)
    elif fault == "pre-norm":
        monkeypatch.setattr(ouro, "mixed_layer", llama.mixed_layer)
    else:
        served = unrolled_without_the_pass_norm(tree, config)
    worst, compared, _ = served_against_the_reference(
        *served, reference_tree=tree, reference_config=config
    )
    assert compared >= 35 and worst > 100 * ATOL, (fault, worst)


# -- (b) prefix hits, host-pool restores and rejected drafts --------------------


TEMPLATED = "the pod was OOMKilled after its memory limit was exceeded " * 2


def serve_twice(tree, config, *, spec_decode=False, kvstore=None, max_tokens=12):
    """The same prompt twice, one after the other (the second finds the
    first's pages in the store, where there is one), greedy."""
    generator = make_generator(tree, config, max_slots=2)
    sched = Scheduler(
        generator, chunk=16, token_budget=32, spec_decode=spec_decode, kvstore=kvstore,
    )
    sampling = SamplingParams(max_tokens=max_tokens, temperature=0.0, stop_on_eos=False)
    tokens = []
    for _ in range(2):
        req = sched.enqueue(TEMPLATED, sampling)
        tokens.append(drain(sched, 1)[req].result.token_ids)
    return tokens, sched, generator


def test_a_prefix_hit_and_a_verify_row_serve_what_a_cold_plain_row_serves(params):
    from operator_tpu.serving.kvstore import PrefixKVStore

    config, tree = CONFIGS[3], params[3]
    (cold, again), _, _ = serve_twice(tree, config)
    assert cold == again
    store = PrefixKVStore(16, metrics=MetricsRegistry())
    (first, hit), sched, generator = serve_twice(tree, config, kvstore=store)
    assert first == hit == cold
    # the second request was served from the first's pages: a page id
    # names that page in every plane, so the hit covers all the passes
    assert generator.metrics.counter("kv_prefill_tokens_saved") >= 5 * 16
    (drafted, _), sched, _ = serve_twice(tree, config, spec_decode=True, max_tokens=24)
    (plain, _), _, _ = serve_twice(tree, config, max_tokens=24)
    assert drafted == plain
    ledger = sched.stats()["spec_decode"]
    # drafts were verified, and some were rejected and rolled back by length
    assert ledger["verify_rounds"] >= 1
    assert ledger["drafts_accepted"] < ledger["drafts_proposed"]


def test_a_page_goes_to_the_host_pool_and_comes_back_in_every_plane(params):
    from operator_tpu.ops import kv_transfer

    config = CONFIGS[3]
    generator = make_generator(params[3], config)
    sched = Scheduler(generator, chunk=8, token_budget=12)
    sched.enqueue("a crashed pod, twice over", SamplingParams(max_tokens=3, temperature=0.0))
    drain(sched, 1)
    paged = generator.paged_cache
    page = 1  # the first page the allocator grants
    k, v = kv_transfer.fetch_page(*kv_transfer.gather_page(paged, page))
    assert k.shape[0] == config.kv_planes and np.abs(k).sum(axis=(1, 2, 3)).all()
    target = 5
    restored = kv_transfer.restore_page(paged, target, k, v)
    assert np.array_equal(np.asarray(restored.k_pages[:, target]), k)
    assert np.array_equal(np.asarray(restored.v_pages[:, target]), v)


# -- (c) the program's full forward and the exit distribution -------------------


@pytest.mark.parametrize("passes", [3, 2])
def test_forward_and_the_exit_distribution_equal_the_references(params, passes):
    config, tree = CONFIGS[passes], params[passes]
    doc = config_doc(config)
    ids = [int(t) for t in np.random.default_rng(1).integers(1, 500, 37)]
    tokens, positions = jnp.asarray([ids], jnp.int32), jnp.arange(len(ids))[None]
    got, _ = ouro.forward(tree, config, tokens, positions)
    weights = own.adapt(tree, doc)
    want = np.asarray(reference.logits(doc, weights, ids))
    assert np.abs(want).max() > 0.3
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=ATOL, rtol=0)
    states = ouro.pass_states(tree, config, tokens, positions)
    assert states.shape[0] == passes
    exits = np.asarray(ouro.exit_distribution(tree, states))[:, 0]
    want_exits = np.asarray(reference.exit_distribution(doc, weights, ids))
    np.testing.assert_allclose(exits, want_exits, atol=1e-5, rtol=0)
    np.testing.assert_allclose(exits.sum(axis=0), 1.0, atol=1e-6)
    # the gate is not a constant: it moves with the position and the pass
    assert exits[:-1].std() > 1e-3 and (exits > 0).all()


# -- (d) the published checkpoint's names ---------------------------------------


def test_a_checkpoint_under_the_published_names_loads_every_key():
    from operator_tpu.models.loader import convert_hf_state_dict

    config = CONFIGS[3]
    rng = np.random.default_rng(0)
    h, f, d = config.hidden_size, config.intermediate_size, config.head_dim
    qh = kvh = config.num_heads
    shapes = {
        "self_attn.q_proj": (qh * d, h), "self_attn.k_proj": (kvh * d, h),
        "self_attn.v_proj": (kvh * d, h), "self_attn.o_proj": (h, qh * d),
        "mlp.gate_proj": (f, h), "mlp.up_proj": (f, h), "mlp.down_proj": (h, f),
        "input_layernorm": (h,), "input_layernorm_2": (h,),
        "post_attention_layernorm": (h,), "post_attention_layernorm_2": (h,),
    }
    state = {
        f"model.layers.{i}.{sub}.weight": rng.normal(size=shape).astype(np.float32)
        for i in range(config.num_layers) for sub, shape in shapes.items()
    }
    state["model.embed_tokens.weight"] = rng.normal(size=(config.vocab_size, h)).astype(np.float32)
    state["model.norm.weight"] = rng.normal(size=(h,)).astype(np.float32)
    state["lm_head.weight"] = rng.normal(size=(config.vocab_size, h)).astype(np.float32)
    state["model.early_exit_gate.weight"] = rng.normal(size=(1, h)).astype(np.float32)
    state["model.early_exit_gate.bias"] = rng.normal(size=(1,)).astype(np.float32)
    tree = convert_hf_state_dict(state, config, dtype=jnp.float32)
    # the same tree the init makes, leaf for leaf and shape for shape
    made = ouro.init_params(config, jax.random.PRNGKey(0), dtype=jnp.float32)
    assert jax.tree_util.tree_map(jnp.shape, tree) == jax.tree_util.tree_map(jnp.shape, made)
    # every tensor of the checkpoint is in it: nothing was dropped on load
    held = sum(float(jnp.abs(leaf).sum()) for leaf in jax.tree_util.tree_leaves(tree))
    given = sum(float(np.abs(value).sum()) for value in state.values())
    assert held == pytest.approx(given, rel=1e-5)
    layer = tree["layers"]
    assert np.array_equal(layer["ln_attn_post"][1], state["model.layers.1.input_layernorm_2.weight"])
    assert np.array_equal(
        layer["ln_mlp_post"][0], state["model.layers.0.post_attention_layernorm_2.weight"]
    )
    assert np.array_equal(layer["wq"][1], state["model.layers.1.self_attn.q_proj.weight"].T)
    assert np.array_equal(tree["exit_w"], state["model.early_exit_gate.weight"][0])
    assert float(tree["exit_b"]) == float(state["model.early_exit_gate.bias"][0])
    # and it is the model: the converted tree runs
    logits, _ = ouro.forward(tree, config, jnp.asarray([[1, 2, 3]]), jnp.arange(3)[None])
    assert logits.shape == (1, 3, config.vocab_size)
    del state["model.early_exit_gate.bias"]
    with pytest.raises(ValueError, match="early_exit_gate.bias"):
        convert_hf_state_dict(state, config, dtype=jnp.float32)


# -- (e) what is refused ---------------------------------------------------------


def operator_config(**kw):
    from operator_tpu.utils.config import OperatorConfig

    return OperatorConfig(
        model_id="tiny-ouro", allow_random_weights=True, serving_dtype="bf16",
        max_batch_size=3, kv_page_size=16, sched_chunk=8, sched_token_budget=12, **kw,
    )


@pytest.mark.parametrize("change, names", [
    ({"sched_mode": "wave"}, "sched_mode='wave'"),
    ({"serving_mesh": "dp=1,tp=2"}, "serving_mesh='dp=1,tp=2'"),
    ({"lora_dir": "adapters"}, "lora_dir adapters"),
])
def test_wave_mode_a_mesh_and_lora_refuse_at_start_up(change, names, tmp_path):
    from operator_tpu.parallel.lora import save_lora
    from operator_tpu.serving import provider

    if "lora_dir" in change:
        change = {"lora_dir": str(tmp_path)}
        n, h = TINY.num_layers, TINY.hidden_size
        save_lora(
            {"wq": {"a": jnp.ones((n, h, 2)), "b": jnp.ones((n, 2, TINY.num_heads * TINY.head_dim))}},
            str(tmp_path / "incident.safetensors"),
        )
    with pytest.raises(ValueError) as refused:
        provider.build_serving_engine(operator_config(**change))
    assert "ouro family" in str(refused.value) and names in str(refused.value)
    assert "several times a token" in str(refused.value)


def test_guided_decoding_and_lora_are_submit_errors_that_name_the_family(params):
    import asyncio

    from operator_tpu.serving.engine import ServingEngine

    generator = make_generator(params[3], CONFIGS[3])
    engine = ServingEngine(generator, scheduler=Scheduler(generator, chunk=8, token_budget=12))

    async def run():
        for bad in ({"guided_choice": ["a", "b"]}, {"adapter": "x"}):
            with pytest.raises(ValueError, match="ouro family|unknown LoRA adapter"):
                await engine.generate("p", SamplingParams(max_tokens=2, **bad))
        await engine.close()

    asyncio.run(run())


def test_the_served_model_keeps_speculation_and_the_prefix_store():
    """Nothing is switched off for this family: its cache is pages only."""
    import asyncio

    from operator_tpu.serving.provider import build_serving_engine

    engine, model_id = build_serving_engine(operator_config())
    features = engine.serving_features()
    asyncio.run(engine.close())
    assert model_id == "tiny-ouro" and features["modelFamily"] == "ouro"
    assert features["schedMode"] == "continuous" and features["switchedOff"] == {}
    assert features["specDecode"] is True and features["kvPrefixCache"] is True


# -- (f) sizes -------------------------------------------------------------------


def test_matmul_param_count_is_the_layers_once_a_pass_and_the_head():
    from operator_tpu.serving.perf import flops_per_token, matmul_param_count

    for config in CONFIGS.values():
        tree = ouro.init_params(config, jax.random.PRNGKey(0), dtype=jnp.float32)
        layers = sum(int(tree["layers"][name].size) for name in ouro.LAYER_MATRICES)
        want = config.total_ut_steps * layers + int(tree["lm_head"].size)
        assert matmul_param_count(config) == want
        assert flops_per_token(config) == 2.0 * want
    quantised = init_params_quantized(TINY, jax.random.PRNGKey(0))
    assert {
        name for name, leaf in quantised["layers"].items() if isinstance(leaf, dict)
    } == set(ouro.LAYER_MATRICES)


def test_the_published_sizes():
    config = get_config("ouro-2.6b")
    assert isinstance(config, OuroConfig) and config.family == "ouro"
    assert (config.num_layers, config.total_ut_steps, config.kv_planes) == (48, 4, 192)
    assert (config.num_heads, config.num_kv_heads, config.head_dim, config.q_per_kv) == (16, 16, 128, 1)
    shapes = ouro.layer_matrix_shapes(config)
    per_layer = sum(rows * cols for _, rows, cols in shapes.values())
    assert round(per_layer / 1e6, 1) == 51.4  # ISSUE 34's reckoning
    # KV a token over every plane, bfloat16: 1.5 MiB
    assert config.kv_planes * 2 * config.num_kv_heads * config.head_dim * 2 == 1.5 * 2 ** 20
    with pytest.raises(AssertionError, match="does not implement"):
        dataclasses.replace(config, attention_bias=True)
