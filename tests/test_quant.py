"""Int8 weight-only quantization: numeric fidelity, end-to-end generation,
memory halving, and TP/DP sharding of the {q, s} tree."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from operator_tpu.models import TINY_TEST, init_params
from operator_tpu.models.llama import forward, param_count
from operator_tpu.models.quant import (
    is_quantized,
    mm,
    quantize_matrix,
    quantize_params,
    quantized_bytes,
)
from operator_tpu.models.tokenizer import ByteTokenizer
from operator_tpu.parallel import MeshPlan, make_mesh
from operator_tpu.serving.engine import BatchedGenerator, SamplingParams


@pytest.fixture(scope="module")
def params():
    return init_params(TINY_TEST, jax.random.PRNGKey(0), dtype=jnp.float32)


@pytest.fixture(scope="module")
def qparams(params):
    return quantize_params(params, TINY_TEST)


class TestQuantMath:
    def test_roundtrip_error_bounded(self):
        w = jax.random.normal(jax.random.PRNGKey(1), (64, 128), jnp.float32)
        packed = quantize_matrix(w)
        assert packed["q"].dtype == jnp.int8
        dequant = packed["q"].astype(jnp.float32) * packed["s"][None, :]
        # symmetric absmax: worst-case error is half a quantization step
        step = np.asarray(packed["s"])[None, :]
        assert float(jnp.max(jnp.abs(dequant - w))) <= float(step.max()) * 0.5 + 1e-6

    def test_mm_dispatch(self):
        x = jax.random.normal(jax.random.PRNGKey(2), (4, 64), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(3), (64, 32), jnp.float32)
        plain = mm(x, w)
        np.testing.assert_allclose(np.asarray(plain), np.asarray(x @ w), rtol=1e-6)
        approx = mm(x, quantize_matrix(w))
        # int8 per-channel keeps matmul outputs within ~1% relative error
        rel = np.abs(np.asarray(approx - plain)) / (np.abs(np.asarray(plain)) + 1e-3)
        assert float(np.median(rel)) < 0.02

    def test_stacked_layers_quantize_along_right_axis(self, qparams):
        wq = qparams["layers"]["wq"]
        n, h, out = TINY_TEST.num_layers, TINY_TEST.hidden_size, (
            TINY_TEST.num_heads * TINY_TEST.head_dim
        )
        assert wq["q"].shape == (n, h, out) and wq["s"].shape == (n, out)


class TestQuantForward:
    def test_logits_close_to_float(self, params, qparams):
        assert is_quantized(qparams) and not is_quantized(params)
        tokens = jax.random.randint(
            jax.random.PRNGKey(4), (2, 16), 0, TINY_TEST.vocab_size, dtype=jnp.int32
        )
        positions = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32)[None], (2, 16))
        ref, _ = forward(params, TINY_TEST, tokens, positions)
        got, _ = forward(qparams, TINY_TEST, tokens, positions)
        a = np.asarray(ref).reshape(-1)
        b = np.asarray(got).reshape(-1)
        cos = float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b)))
        assert cos > 0.999, f"quantized logits diverged: cos={cos}"

    def test_memory_halved(self, params, qparams):
        # layer matrices dominate TINY_TEST less than a real model, but the
        # quantized tree must still be well under the float32 total
        assert quantized_bytes(qparams) < quantized_bytes(params) * 0.5
        assert param_count(params) > 0

    def test_generation_runs_quantized(self, qparams):
        generator = BatchedGenerator(
            qparams, TINY_TEST, ByteTokenizer(), max_slots=2, max_seq=128,
            cache_dtype=jnp.float32, paged=True, page_size=16, decode_block=4,
        )
        result = generator.generate(
            "pod failed exit 137",
            SamplingParams(max_tokens=8, temperature=0.0, stop_on_eos=False),
        )
        assert result.completion_tokens == 8


class TestQuantSharded:
    def test_sharded_quantized_matches_single_device(self, qparams):
        devices = jax.devices("cpu")
        if len(devices) < 4:
            pytest.skip("need 4 cpu devices")
        greedy = SamplingParams(max_tokens=10, temperature=0.0, stop_on_eos=False)

        def run(mesh):
            generator = BatchedGenerator(
                qparams, TINY_TEST, ByteTokenizer(), max_slots=4, max_seq=128,
                cache_dtype=jnp.float32, paged=True, page_size=16, mesh=mesh,
                decode_block=2,
            )
            if mesh is not None:
                packed = generator.params["layers"]["wq"]
                assert not packed["q"].sharding.is_fully_replicated
            ids = generator.admit(["crash a", "oom b", "exit c", "fail d"], [greedy] * 4)
            out = {}
            while generator.num_active:
                for slot_id, result in generator.step():
                    out[slot_id] = result.token_ids
            return [out[i] for i in ids]

        ref = run(None)
        got = run(make_mesh(MeshPlan(dp=2, fsdp=1, tp=2), devices[:4]))
        assert got == ref


def test_init_params_quantized_matches_two_step():
    """The memory-safe quantized init must match init_params + quantize to
    within one quantization level (int8 q) / one bf16 ulp (float leaves) —
    XLA rounds fused init differently across jit boundaries, so exact bit
    equality is not the contract."""
    import numpy as np

    from operator_tpu.models import TINY_TEST, init_params
    from operator_tpu.models.quant import init_params_quantized, quantize_params

    key = jax.random.PRNGKey(42)
    want = quantize_params(init_params(TINY_TEST, key, dtype=jnp.bfloat16), TINY_TEST)
    got = init_params_quantized(TINY_TEST, key, dtype=jnp.bfloat16)
    flat_w, tree_w = jax.tree_util.tree_flatten(want)
    flat_g, tree_g = jax.tree_util.tree_flatten(got)
    assert tree_w == tree_g
    for a, b in zip(flat_w, flat_g):
        assert a.dtype == b.dtype and a.shape == b.shape
        af = np.asarray(a, np.float32)
        bf = np.asarray(b, np.float32)
        if a.dtype == jnp.int8:
            assert np.abs(af - bf).max() <= 1  # one quantization level
            assert (af != bf).mean() < 0.05  # and only on rounding boundaries
        else:
            np.testing.assert_allclose(af, bf, rtol=1e-2, atol=1e-3)


#: one tiny config of each family the continuous step runs, Qwen2's q/k/v
#: biases counted as a family of their own
HELD_FAMILIES = ["tiny-test", "tiny-qwen2-bias", "tiny-falcon-h1", "tiny-ouro", "tiny-sdar"]


def family_config(name):
    import dataclasses

    from operator_tpu.models import get_config

    if name == "tiny-qwen2-bias":
        return dataclasses.replace(TINY_TEST, name=name, attention_bias=True)
    return get_config(name)


@pytest.fixture(scope="module")
def int8_trees():
    """``{family: (config, canonical int8 tree, held tree)}``, built once."""
    from operator_tpu.models.quant import hold_head_projections, init_params_quantized

    out = {}
    for name in HELD_FAMILIES:
        config = family_config(name)
        tree = init_params_quantized(config, jax.random.PRNGKey(0), dtype=jnp.float32)
        out[name] = (config, tree, hold_head_projections(tree))
    return out


@pytest.mark.parametrize("out_dtype", [None, jnp.float32])
@pytest.mark.parametrize("family", HELD_FAMILIES)
def test_mm_on_a_held_leaf_is_mm_on_the_canonical_leaf(int8_trees, family, out_dtype):
    """A held ``{qt: [out, in], s}`` gives the product of ``{q: [in, out],
    s}`` in the same dtype, to float32's accumulation order."""
    from operator_tpu.models.quant import HEAD_PROJECTIONS

    config, tree, held = int8_trees[family]
    for dtype in (jnp.float32, jnp.bfloat16):
        x = jax.random.normal(jax.random.PRNGKey(7), (1, 5, config.hidden_size), dtype)
        for name in HEAD_PROJECTIONS:
            layer = config.num_layers - 1
            want = mm(x, jax.tree_util.tree_map(lambda a: a[layer], tree["layers"][name]), out_dtype)
            got = mm(x, jax.tree_util.tree_map(lambda a: a[layer], held["layers"][name]), out_dtype)
            assert got.dtype == want.dtype and got.shape == want.shape
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(want, np.float32),
                rtol=1e-5, atol=1e-5,
            )


@pytest.mark.parametrize("family", HELD_FAMILIES)
def test_holding_transposes_the_head_projections_alone(int8_trees, family):
    """Exactly ``wq``, ``wk`` and ``wv`` change, each to its own values
    transposed beside the same scale; every other leaf is the one given,
    the bytes are the same, the given tree is untouched, and holding a
    held tree changes nothing."""
    from operator_tpu.models.quant import HEAD_PROJECTIONS, hold_head_projections

    _, tree, held = int8_trees[family]
    assert set(held) == set(tree) and set(held["layers"]) == set(tree["layers"])
    for name, leaf in tree["layers"].items():
        if name in HEAD_PROJECTIONS:
            assert set(held["layers"][name]) == {"qt", "s"} and "q" in leaf
            assert held["layers"][name]["s"] is leaf["s"]
            assert np.array_equal(
                np.asarray(held["layers"][name]["qt"]), np.swapaxes(np.asarray(leaf["q"]), -1, -2)
            )
        else:
            assert held["layers"][name] is leaf
    assert all(held[k] is tree[k] for k in tree if k != "layers")
    assert quantized_bytes(held) == quantized_bytes(tree)
    again = hold_head_projections(held)
    assert jax.tree_util.tree_structure(again) == jax.tree_util.tree_structure(held)
    assert all(a is b for a, b in zip(
        jax.tree_util.tree_leaves(again), jax.tree_util.tree_leaves(held)
    ))
    assert is_quantized(held)


@pytest.mark.parametrize("family", HELD_FAMILIES)
def test_the_continuous_path_gives_the_same_greedy_tokens_held(int8_trees, family):
    """Three requests, prompts in chunks, greedy on the continuous path
    (a bare ``Runtime`` and the ``Scheduler``), over the canonical tree and
    the held one: the same tokens."""
    from operator_tpu.serving.runtime import Runtime
    from operator_tpu.serving.sched import Scheduler
    from operator_tpu.utils.timing import MetricsRegistry

    config, tree, held = int8_trees[family]
    greedy = SamplingParams(max_tokens=5, temperature=0.0, stop_on_eos=False)

    def served(params):
        runtime = Runtime(
            params, config, ByteTokenizer(), max_slots=3, max_seq=128,
            page_size=16, cache_dtype=jnp.float32, metrics=MetricsRegistry(),
        )
        sched = Scheduler(runtime, chunk=8)
        ids = [sched.enqueue(p, greedy) for p in ("pod crashed", "exit 137 oom", "x")]
        done = {}
        while sched.total_work:
            for outcome in sched.step():
                assert outcome.error is None, outcome.error
                done[outcome.req_id] = outcome.result.token_ids
        return [done[i] for i in ids]

    want = served(tree)
    assert all(len(t) == 5 for t in want)
    assert served(held) == want
