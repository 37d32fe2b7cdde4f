"""Olmo Hybrid (models/olmo_hybrid.py) at a small size on the CPU: the gated
delta-rule kernel in interpret mode against the recurrence written out
token by token, the family's forward pass against the benchmark's plain
float32 reference (and that comparison failing where beta's factor 2 or
the L2 norm is dropped), the continuous scheduler's mixed step against the
full forward at every position, and the pools sized by layer kind.
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

from benchmark.reference import olmo_hybrid_f32 as reference  # noqa: E402
from benchmark.reference import olmo_hybrid_f32_weights as own  # noqa: E402
from operator_tpu.models import family_of, get_config, olmo_hybrid  # noqa: E402
from operator_tpu.models.configs import OlmoHybridConfig  # noqa: E402
from operator_tpu.models.quant import init_params_quantized  # noqa: E402
from operator_tpu.models.tokenizer import ByteTokenizer  # noqa: E402
from operator_tpu.ops.gated_delta import (  # noqa: E402
    _gated_delta_pallas,
    gated_delta_reference,
)
from operator_tpu.ops.paged_attention import PagedKVCache  # noqa: E402
from operator_tpu.ops.ragged_attention import pool_heads  # noqa: E402
from operator_tpu.serving.engine import BatchedGenerator, SamplingParams  # noqa: E402
from operator_tpu.serving.sched import Scheduler  # noqa: E402
from operator_tpu.utils.timing import MetricsRegistry  # noqa: E402

CONFIG = get_config("tiny-olmo-hybrid")
FAMILY = family_of(CONFIG)
#: the tiny model with ten heads a full layer: a page of its pool holds
#: sixteen (``ragged_attention.pool_heads``), written with zeros past the
#: ten and read at the ten
WIDE = dataclasses.replace(CONFIG, name="tiny-olmo-hybrid-ten-heads", num_heads=10,
                           num_kv_heads=10, head_dim=16, hidden_size=160)
#: float32 program against float32 reference: the same equations summed in
#: another order through eight layers, each branch renormed (7.8e-4 read at
#: most here); a dropped factor or norm moves the logits by 0.3 and more
F32_TOLERANCE = 2e-3
#: bfloat16 activations and int8 matrices against float32: what a cell's
#: probe limit is for (0.67 read here at logits of 4.7)
BF16_TOLERANCE = 1.0


def config_doc(config=CONFIG, dtype="float32", bits=0):
    """A configuration file's two groups the reference reads, for a tiny
    model: every ``architecture`` key from the program's config."""
    return {
        "architecture": {
            key: getattr(config, attribute) for key, attribute in own.PROGRAM_CONFIG.items()
        },
        "weights": {"seed": 0, "init": "olmo_hybrid_fan_in", "dtype": dtype, "bits": bits},
    }


@pytest.fixture(scope="module")
def params():
    return FAMILY.init_params(CONFIG, jax.random.PRNGKey(0), dtype=jnp.float32)


IDS = [int(t) for t in np.random.default_rng(1).integers(1, 500, 45)]


def forward_gap(tree, doc, config=CONFIG):
    got, _ = family_of(config).forward(
        tree, config, jnp.asarray([IDS], jnp.int32), jnp.arange(len(IDS))[None]
    )
    want = np.asarray(reference.logits(doc, own.adapt(tree, doc), IDS))
    assert np.abs(want).max() > 1.0  # the logits are not all alike
    return float(np.abs(np.asarray(got[0]) - want).max())


# -- (a) the kernel, interpreted, against the recurrence ------------------------


def recurrence(q, k, v, g, beta, state, layer, q_start, q_count, fresh):
    """The gated delta rule as the module's text states it, one slot, head
    and token at a time in numpy, on the pool's paired layout."""
    state = np.array(state)
    heads, dv = v.shape[1], v.shape[2]
    o = np.zeros(v.shape, np.float32)
    for slot, count in enumerate(q_count):
        for head in range(heads):
            pair, half = divmod(head, 2)
            lanes = slice(half * dv, (half + 1) * dv)
            s = np.zeros_like(state[layer, slot, pair][:, lanes]) if fresh[slot] else (
                state[layer, slot, pair][:, lanes].copy()
            )
            for j in range(count):
                t = q_start[slot] + j
                s = np.exp(g[t, head]) * s
                s = s + np.outer(k[t, head], beta[t, head] * (v[t, head] - s.T @ k[t, head]))
                o[t, head] = s.T @ q[t, head]
            if count:
                state[layer, slot, pair][:, lanes] = s
    return o, state


def delta_inputs(seed=0, tokens=32, heads=4, dk=16, dv=32, slots=6, layers=3):
    rng = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = (unit(rng.normal(size=(tokens, heads, dk))) * dk ** -0.5).astype(np.float32)
    k = unit(rng.normal(size=(tokens, heads, dk))).astype(np.float32)
    v = rng.normal(size=(tokens, heads, dv)).astype(np.float32)
    g = -np.abs(rng.normal(size=(tokens, heads))).astype(np.float32)
    # 2 sigmoid: over 1 for half the tokens (negative eigenvalues)
    beta = (2.0 / (1.0 + np.exp(-2.0 * rng.normal(size=(tokens, heads))))).astype(np.float32)
    state = rng.normal(size=(layers, slots, heads // 2, dk, 2 * dv)).astype(np.float32)
    return q, k, v, g, beta, state


@pytest.mark.parametrize("q_count", [
    [0, 1, 8, 0, 3, 1],  # idle, decode, a full chunk, idle, a part chunk, decode
    [0, 0, 0, 0, 0, 0],  # the scheduler's empty warm-up step
    [1, 1, 1, 1, 1, 1],  # all decoding
    [0, 0, 0, 0, 0, 8],  # live slots only after idle ones
])
@pytest.mark.parametrize("path", ["kernel", "reference"])
def test_delta_kernel_against_the_token_by_token_recurrence(q_count, path):
    q, k, v, g, beta, state = delta_inputs()
    assert (beta > 1).mean() > 0.3
    q_count = np.asarray(q_count, np.int32)
    q_start = (np.concatenate([[0], np.cumsum(q_count)[:-1]]) + 2).astype(np.int32)
    fresh = np.asarray([0, 1, 0, 1, 1, 0], bool)
    want_o, want_state = recurrence(q, k, v, g, beta, state, 1, q_start, q_count, fresh)
    args = [jnp.asarray(a) for a in (q, k, v, g, beta, state)] + [
        jnp.int32(1), jnp.asarray(q_start), jnp.asarray(q_count), jnp.asarray(fresh),
    ]
    if path == "kernel":
        # one head pair a grid step: two blocks, so a block's index matters
        got_o, got_state = _gated_delta_pallas(*args, interpret=True, block_pairs=1)
    else:
        got_o, got_state = gated_delta_reference(*args, chunk=8)
    live = np.zeros(q.shape[0], bool)
    for start, count in zip(q_start, q_count):
        live[start:start + count] = True
    # float32 sums of 16 to 32 terms in another order: 1e-5
    np.testing.assert_allclose(np.asarray(got_o)[live], want_o[live], atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(got_state), want_state, atol=1e-5, rtol=0)
    # a slot without tokens is not touched, fresh or not; nor another layer
    idle = q_count == 0
    assert np.array_equal(np.asarray(got_state)[:, idle], state[:, idle])
    assert np.array_equal(np.asarray(got_state)[[0, 2]], state[[0, 2]])


def test_beta_over_one_is_what_the_kernel_applies():
    """A recurrence with beta halved (a dropped factor 2) reads far from
    what the kernel computes on the same inputs, where beta > 1."""
    q, k, v, g, beta, state = delta_inputs(seed=3)
    q_count = np.asarray([8, 8, 8, 0, 0, 0], np.int32)
    q_start = np.asarray([0, 8, 16, 24, 24, 24], np.int32)
    fresh = np.asarray([1, 1, 1, 0, 0, 0], bool)
    got, _ = _gated_delta_pallas(
        *[jnp.asarray(a) for a in (q, k, v, g, beta, state)], jnp.int32(0),
        jnp.asarray(q_start), jnp.asarray(q_count), jnp.asarray(fresh), interpret=True,
    )
    halved, _ = recurrence(q, k, v, g, beta / 2, state, 0, q_start, q_count, fresh)
    assert np.abs(np.asarray(got)[:24] - halved[:24]).max() > 0.1


# -- (b) the program's forward against the plain reference ----------------------


@pytest.mark.parametrize("dtype, bits, tolerance", [
    ("float32", 0, F32_TOLERANCE),
    ("bfloat16", 8, BF16_TOLERANCE),
])
def test_forward_equals_the_reference(dtype, bits, tolerance):
    if bits:
        tree = init_params_quantized(CONFIG, jax.random.PRNGKey(0))
    else:
        tree = FAMILY.init_params(CONFIG, jax.random.PRNGKey(0), dtype=jnp.float32)
    assert forward_gap(tree, config_doc(CONFIG, dtype, bits)) < tolerance


@pytest.mark.parametrize("fault", ["beta without its factor 2", "k without its L2 norm"])
def test_a_dropped_part_of_the_layer_fails_the_comparison(params, monkeypatch, fault):
    """The float32 comparison is tight enough to see each: the program is
    broken in one place and read against the unbroken reference."""
    if fault.startswith("beta"):
        projections = olmo_hybrid.delta_projections

        def broken(config, weights, x):
            qkv, g, beta = projections(config, weights, x)
            return qkv, g, beta / 2

        # the factor matters here: the first layer's beta passes 1
        first = olmo_hybrid.layer_of(
            jax.tree_util.tree_map(lambda a: a[0], params["layers"]), True, 0
        )
        x = jnp.take(params["embed"], jnp.asarray([IDS]), axis=0)
        assert float(projections(CONFIG, first, x)[2].max()) > 1.0

        monkeypatch.setattr(olmo_hybrid, "delta_projections", broken)
    else:
        heads = olmo_hybrid.delta_heads

        def broken(config, mixed):
            q, _, v = heads(config, mixed)
            dk, kd = config.linear_key_head_dim, config.linear_key_dim
            k = mixed[..., kd:2 * kd].reshape(*mixed.shape[:-1], -1, dk).astype(jnp.float32)
            return q, k, v

        monkeypatch.setattr(olmo_hybrid, "delta_heads", broken)
    # unnormed keys make the recurrence diverge: a NaN fails it too
    assert not forward_gap(params, config_doc()) <= 50 * F32_TOLERANCE


def test_the_references_own_int8_weights_are_the_programs_bit_for_bit():
    doc = config_doc(CONFIG, "bfloat16", 8)
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
    # no norm is ones: a dropped one cannot hide
    for name in own.NORMS:
        assert float(jnp.abs(mine.layers[name].astype(jnp.float32) - 1).max()) > 0.05, name


# -- (c) through the scheduler -------------------------------------------------


def serve_and_compare(config, tree):
    """Prompts prefilled in chunks of 8 (the convolution reaches 3 tokens
    back across every chunk boundary), then decoded; five requests over
    three slots, so rows join and leave, slots get a second tenant without
    any clearing from the host, and slots sit idle between.  Every step's
    logits against the full forward of the request's prompt and answer."""
    generator = BatchedGenerator(
        tree, config, ByteTokenizer(), paged=True, cache_dtype=jnp.float32,
        metrics=MetricsRegistry(), max_slots=3, max_seq=128, page_size=16,
    )
    seen = []
    sample = generator.sample

    def recording(logits, rng, temp, top_p):
        jax.debug.callback(lambda value: seen.append(np.asarray(value)), logits)
        return sample(logits, rng, temp, top_p)

    generator.sample = recording
    sched = Scheduler(generator, chunk=8, token_budget=12)
    sched.plan_log = []
    rng = np.random.default_rng(5)
    prompts = ["".join(chr(int(c)) for c in rng.integers(97, 123, n)) for n in (19, 5, 11, 16, 3)]
    answers = [6, 9, 4, 5, 7]
    ids, done = {}, {}
    for i, (prompt, n) in enumerate(zip(prompts, answers)):
        ids[sched.enqueue(
            prompt, SamplingParams(max_tokens=n, temperature=0.0, stop_on_eos=False)
        )] = i
        for outcome in sched.step():
            done[outcome.req_id] = outcome
    for _ in range(200):
        if len(done) == len(prompts):
            break
        for outcome in sched.step():
            done[outcome.req_id] = outcome
    assert len(done) == len(prompts) and all(o.error is None for o in done.values())
    assert generator.metrics.counter("sched_recycled_slot") == len(prompts)
    full = {}
    for req_id, i in ids.items():
        sequence = list(generator.tokenizer.encode(prompts[i])) + list(done[req_id].result.token_ids)
        logits, _ = family_of(config).forward(
            tree, config, jnp.asarray([sequence], jnp.int32), jnp.arange(len(sequence))[None]
        )
        full[req_id] = np.asarray(logits[0])
    compared, second_tenants, slots_of, state_rows = 0, 0, {}, []
    for step_logits, plan in zip(seen, sched.plan_log):
        state_rows.append(len(plan))
        for slot, req_id, _, count, kind, pos0, *_ in plan:
            if slots_of.setdefault(slot, req_id) != req_id:
                second_tenants += 1
                slots_of[slot] = req_id
            # the same float32 program, flat and paged against whole: sums
            # in another order, 5e-6 read
            np.testing.assert_allclose(
                step_logits[slot], full[req_id][pos0 + count - 1], atol=5e-5, rtol=0,
                err_msg=f"request {req_id} {kind} at position {pos0 + count - 1}",
            )
            compared += 1
    assert second_tenants >= 2 and compared >= 35
    assert min(state_rows) < generator.max_slots  # steps with idle slots between
    assert [r.state_rows for r in generator.step_clock.ring.records()] == state_rows
    return generator


@pytest.mark.parametrize("config", [CONFIG, WIDE], ids=["two-heads", "ten-heads-held-sixteen"])
def test_every_position_through_the_scheduler_equals_the_full_forward(config):
    tree = family_of(config).init_params(config, jax.random.PRNGKey(0), dtype=jnp.float32)
    generator = serve_and_compare(config, tree)
    cache = generator.paged_cache
    # the KV pool holds planes for the full layers alone, the state pool
    # the linear layers alone, heads in pairs side by side
    assert cache.k_pages.shape[0] == config.kv_planes == 2
    assert cache.k_pages.shape[3] == pool_heads(config.num_kv_heads)
    assert cache.ssm_state.shape == (6, 3, 1, 16, 64) and cache.ssm_state.dtype == jnp.float32
    assert cache.conv_state.shape == (6, 3, 3, config.linear_conv_dim)


def test_pools_are_sized_by_layer_kind_at_the_published_size():
    config = get_config("olmo-hybrid-7b")
    assert config.period == ("linear_attention",) * 3 + ("full_attention",)
    assert (config.periods, config.delta_layers, config.kv_planes) == (8, 24, 8)
    state, tails = PagedKVCache.recurrent_shapes(config, 48)
    # 30 heads of 96 x 192 float32 as 15 pairs of 96 x 384: no lane padded
    assert state == (24, 48, 15, 96, 384) and state[-1] % 128 == 0
    assert 4 * int(np.prod(state[2:])) == 2_211_840  # 2.21 MB a slot and layer
    assert tails == (24, 48, 3, 2 * 2880 + 5760)
    assert pool_heads(config.num_kv_heads) == 32  # 30 heads, a page's tile holds 32
    # every other registered model's pool holds its own heads
    from operator_tpu.models.configs import _REGISTRY

    for other in _REGISTRY.values():
        if other.family != "olmo_hybrid":
            assert pool_heads(other.num_kv_heads) == other.num_kv_heads, other.name


@pytest.mark.parametrize("layer_types, problem", [
    (("linear_attention",) * 7 + ("full_attention",), "repeat no period"),
    (("linear_attention", "full_attention") * 3 + ("linear_attention",) * 2, "repeat no period"),
    (("linear_attention",) * 8, "both kinds"),
])
def test_a_pattern_that_is_not_periodic_is_refused(layer_types, problem):
    with pytest.raises(AssertionError, match=problem):
        dataclasses.replace(CONFIG, layer_types=layer_types)


def test_the_step_scans_a_period_and_the_leaves_are_stacked_by_period(params):
    assert olmo_hybrid.scan_steps(CONFIG) == CONFIG.periods == 2
    for name, leaf in params["layers"].items():
        per = CONFIG.linear_per_period if name.startswith("lin_") else CONFIG.full_per_period
        assert leaf.shape[:2] == (2, per), name


def test_matmul_param_count_equals_a_count_over_the_tree(params):
    from operator_tpu.serving.perf import matmul_param_count

    matrices = sum(int(params["layers"][name].size) for name in olmo_hybrid.LAYER_MATRICES)
    assert matmul_param_count(CONFIG) == matrices + CONFIG.hidden_size * CONFIG.vocab_size
    # the published size: 24 x 215.5 M + 8 x 185.8 M in the layers
    big = get_config("olmo-hybrid-7b")
    assert abs(olmo_hybrid.matmul_params_per_token(big) / 1e9 - 6.66) < 0.01


def test_wave_mode_is_refused_at_start_up():
    from operator_tpu.serving.provider import build_serving_engine
    from operator_tpu.utils.config import OperatorConfig

    with pytest.raises(ValueError, match="olmo_hybrid family"):
        build_serving_engine(OperatorConfig(
            model_id="tiny-olmo-hybrid", allow_random_weights=True, sched_mode="wave",
        ))


def test_olmo_hybrid_config_is_a_sibling_with_the_published_keys():
    config = get_config("olmo-hybrid-7b")
    assert isinstance(config, OlmoHybridConfig) and config.rope_theta is None
    assert config.recurrent_state and config.continuous_only
    assert (config.linear_key_dim, config.linear_value_dim) == (2880, 5760)
