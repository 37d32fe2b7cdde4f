"""DeepSeek-V2 (models/deepseek_v2.py) at a small size on the CPU: the latent
attention kernel in interpret mode against its dense reference, the
absorbed attention against the expanded one, the family's forward pass
against the benchmark's plain float32 reference (and that comparison
failing for each part of the layer dropped or changed), the continuous
scheduler's mixed step against the full forward at every position, the
latent pool's layout, YaRN's frequencies and the refusals at build.
"""

import dataclasses
import math
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import deepseek_v2_f32 as reference  # noqa: E402
from benchmark.reference import deepseek_v2_f32_weights as own  # noqa: E402
from operator_tpu.models import deepseek_v2, family_of, get_config  # noqa: E402
from operator_tpu.models.configs import DeepseekV2Config  # noqa: E402
from operator_tpu.models.llama import rope_frequencies  # noqa: E402
from operator_tpu.models.quant import hold_head_projections, init_params_quantized  # noqa: E402
from operator_tpu.models.tokenizer import ByteTokenizer  # noqa: E402
from operator_tpu.ops import latent_attention as la  # noqa: E402
from operator_tpu.ops.paged_attention import PagedKVCache  # noqa: E402
from operator_tpu.serving.engine import BatchedGenerator, SamplingParams  # noqa: E402
from operator_tpu.serving.sched import Scheduler  # noqa: E402
from operator_tpu.utils.timing import MetricsRegistry  # noqa: E402

CONFIG = get_config("tiny-deepseek-v2")
#: float32 program against float32 reference: the same function, the
#: attention absorbed in one and expanded in the other, summed in another
#: order through three layers (6.8e-6 read here at logits of 4.4); every
#: fault below moves the logits by 0.02 and more
F32_TOLERANCE = 1e-4
#: bfloat16 activations and int8 matrices against float32 on the same
#: int8 weights: what a cell's probe limit is for (0.25 read here)
BF16_TOLERANCE = 1.0
#: the flat program against the whole forward, both float32: sums in
#: another order (7.8e-6 read)
SERVED_TOLERANCE = 5e-5
IDS = [int(t) for t in np.random.default_rng(1).integers(1, 500, 45)]


def config_doc(config=CONFIG, dtype="float32", bits=0):
    """A configuration file's two groups the reference reads, for a tiny
    model: every ``architecture`` key from the program's config."""
    return {
        "architecture": {
            key: getattr(config, attribute) for key, attribute in own.PROGRAM_CONFIG.items()
        },
        "weights": {"seed": 0, "init": "deepseek_v2_fan_in", "dtype": dtype, "bits": bits},
    }


@pytest.fixture(scope="module")
def params():
    """The seeded init in float32 (its norms are drawn away from 1)."""
    return deepseek_v2.init_params(CONFIG, jax.random.PRNGKey(0), dtype=jnp.float32)


def forward_gap(tree, doc, config=CONFIG):
    got, _ = family_of(config).forward(
        tree, config, jnp.asarray([IDS], jnp.int32), jnp.arange(len(IDS))[None]
    )
    want = np.asarray(reference.logits(doc, own.adapt(tree, doc), IDS))
    assert np.abs(want).max() > 1.0  # the logits are not all alike
    return float(np.abs(np.asarray(got[0]) - want).max())


# -- (a) the kernel, interpreted, against the reference --------------------------


def kernel_inputs(q_count, kv_len, *, heads=4, rank=32, rope=16, page=16, chunk=8,
                  slots=6, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    per_seq = 12
    pages = slots * per_seq + 1  # every slot's pages its own, page 0 the trash
    latent_shape, rope_shape = la.pool_shapes(2, pages, page, rank, rope)
    latent = jnp.asarray(rng.normal(size=latent_shape), dtype)
    rope_rows = jnp.asarray(rng.normal(size=rope_shape), dtype)
    table = (1 + rng.permutation(pages - 1)).reshape(slots, per_seq).astype(np.int32)
    q_count = np.asarray(q_count, np.int32)
    q_start = (np.concatenate([[0], np.cumsum(q_count)[:-1]]) + 1).astype(np.int32)
    tokens = max(int(q_start[-1] + q_count[-1]) + 3, chunk)
    q_lat = jnp.asarray(rng.normal(size=(tokens, heads, rank)), dtype)
    q_rope = jnp.asarray(rng.normal(size=(tokens, heads, rope)), dtype)
    live = np.zeros(tokens, bool)
    for start, count in zip(q_start, q_count):
        live[start:start + count] = True
    args = (
        jnp.asarray(table), jnp.asarray(kv_len, jnp.int32), jnp.asarray(q_start),
        jnp.asarray(q_count), jnp.int32(1),
    )
    return q_lat, q_rope, latent, rope_rows, args, live


@pytest.mark.parametrize("q_count, kv_len", [
    # idle, decode, a whole chunk, idle, a part chunk, decode: one step
    ([0, 1, 8, 0, 3, 1], [0, 40, 57, 9, 3, 100]),
    # a fresh prompt (its first chunk is all it holds) and one-position rows
    ([8, 1, 1, 0, 0, 5], [8, 1, 17, 0, 30, 5]),
    ([0, 0, 0, 0, 0, 0], [0, 0, 0, 0, 0, 0]),  # the scheduler's empty step
])
@pytest.mark.parametrize("block_of", [(2, 1), (4, 4)])
def test_latent_kernel_against_the_reference(q_count, kv_len, block_of):
    q_lat, q_rope, latent, rope_rows, args, live = kernel_inputs(q_count, kv_len)
    want = la.latent_attention_reference(q_lat, q_rope, latent, rope_rows, *args, scale=0.3)
    got = la._latent_attention_pallas(
        q_lat, la.rope_queries(q_rope), latent, rope_rows, *args,
        scale=0.3, chunk=8, interpret=True, block_of=block_of,
    )
    # float32 sums of up to 192 positions in another order (4e-7 read)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], atol=1e-5, rtol=0)


def one_row(length, start=0):
    """``(rows, pos, valid)`` of a flat axis that holds one row's positions
    ``start .. start + length``."""
    return (
        jnp.zeros(length, jnp.int32), jnp.arange(start, start + length, dtype=jnp.int32),
        jnp.ones(length, bool),
    )


def test_what_lies_past_a_rows_length_never_reaches_its_output():
    """The pool poisoned with NaN wherever no row wrote (a last page's
    tail shares its rope rows with live positions): the kernel's outputs
    are the reference's on the clean pool."""
    q_count, kv_len = [1, 8, 3, 1, 0, 1], [37, 21, 50, 1, 0, 16]
    q_lat, q_rope, latent, rope_rows, args, live = kernel_inputs(q_count, kv_len, seed=4)
    table = np.asarray(args[0])
    c, kr = la.unpack_pages(latent, rope_rows, 1, jnp.asarray(table))
    clean_latent = jnp.zeros_like(latent)
    clean_rope = jnp.zeros_like(rope_rows)
    page = latent.shape[2]
    for slot, length in enumerate(kv_len):
        positions = np.arange(length)
        page_ids = jnp.asarray(table[slot, positions // page])
        at = jnp.asarray(positions % page)
        clean_latent, clean_rope = la.write_latent(
            clean_latent, clean_rope, 1, page_ids, at, c[slot, :length], kr[slot, :length],
            *one_row(length),
        )
    poisoned_latent = jnp.where(clean_latent == 0, jnp.nan, clean_latent)
    poisoned_rope = jnp.where(clean_rope == 0, jnp.nan, clean_rope)
    want = la.latent_attention_reference(
        q_lat, q_rope, clean_latent, clean_rope, *args, scale=0.3,
    )
    got = la._latent_attention_pallas(
        q_lat, la.rope_queries(q_rope), poisoned_latent, poisoned_rope, *args,
        scale=0.3, chunk=8, interpret=True, block_of=(2, 2),
    )
    assert not np.isnan(np.asarray(got)[live]).any()
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], atol=1e-5, rtol=0)


def test_the_rope_rows_pack_tokens_into_whole_lanes():
    """At the published widths a page is 64 latent rows of 512 and 32 rope
    rows of 128, two tokens' rotary keys side by side: 1,152 bytes a token
    and layer in bfloat16, no lane padded."""
    config = get_config("deepseek-v2-lite-14l")
    cache = jax.eval_shape(lambda: PagedKVCache.create(
        config.kv_planes, 4096, 64, config.num_kv_heads, config.head_dim, 128, 32,
        latent=config.latent_dims,
    ))
    assert cache.v_pages is None  # no values: they are the latent rows
    assert cache.k_pages.shape == (14, 4096, 64, 512)
    assert cache.rope_pages.shape == (14, 4096, 32, 128)
    assert cache.k_pages.shape[-1] % 128 == 0 and cache.rope_pages.shape[-1] == 128
    per_token = (math.prod(cache.k_pages.shape) + math.prod(cache.rope_pages.shape)) * 2 // (
        14 * 4096 * 64
    )
    assert per_token == 1152
    # the worst-case pool: 128 rows of 2,048 positions
    assert 14 * 4096 * 64 * 1152 == 4_227_858_432
    # token s of a page: latent row s, rope row s mod 32, lanes 64 x (s div 32)
    latent, rope_rows = la.pool_shapes(1, 2, 64, 8, 64)
    c = jnp.arange(64 * 8, dtype=jnp.float32).reshape(64, 8)
    kr = jnp.arange(64 * 64, dtype=jnp.float32).reshape(64, 64)
    got_c, got_r = la.write_latent(
        jnp.zeros(latent), jnp.zeros(rope_rows), 0, jnp.ones(64, jnp.int32),
        jnp.arange(64, dtype=jnp.int32), c, kr, *one_row(64),
    )
    assert np.array_equal(np.asarray(got_r[0, 1, 5, 64:]), np.asarray(kr[37]))
    back_c, back_r = la.unpack_pages(got_c, got_r, 0, jnp.asarray([1]))
    assert np.array_equal(np.asarray(back_c), np.asarray(c))
    assert np.array_equal(np.asarray(back_r), np.asarray(kr))
    # a rope row is written whole: written a chunk at a time over a page
    # whose rows hold NaN (a page reused), each token keeps its partners'
    # lanes from the step (same chunk) or the pool (earlier chunks)
    latent_nan = jnp.full(latent, jnp.nan)
    rope_nan = jnp.full(rope_rows, jnp.nan)
    start = 0
    for size in (5, 27, 1, 31):
        at = jnp.arange(start, start + size, dtype=jnp.int32)
        latent_nan, rope_nan = la.write_latent(
            latent_nan, rope_nan, 0, jnp.ones(size, jnp.int32), at, c[at], kr[at],
            *one_row(size, start),
        )
        start += size
    assert np.array_equal(np.asarray(rope_nan[0, 1]), np.asarray(got_r[0, 1]))
    assert np.array_equal(np.asarray(latent_nan[0, 1]), np.asarray(got_c[0, 1]))


# -- (b) absorbed against expanded, and the program against the reference -------


def test_absorbed_attention_equals_the_expanded_form_at_float32(params):
    """The same scores and outputs written the two ways, on one layer's
    int8-shaped weights: ``q_nope . (c W_uk)`` against ``(q_nope W_uk^T) .
    c``, ``(p c) W_uv`` against ``p (c W_uv)``."""
    tree = init_params_quantized(CONFIG, jax.random.PRNGKey(0), dtype=jnp.float32)
    w = deepseek_v2.layer_of(tree["layers"], "", 1)
    rng = np.random.default_rng(2)
    heads, rank = CONFIG.num_heads, CONFIG.kv_lora_rank
    c = jnp.asarray(rng.normal(size=(9, rank)), jnp.float32)
    q_nope = jnp.asarray(rng.normal(size=(5, heads, CONFIG.qk_nope_head_dim)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        expanded = jnp.einsum("thd,shd->ths", q_nope, deepseek_v2.per_head_up(c, w["w_uk"]))
        absorbed = jnp.einsum("thr,sr->ths", deepseek_v2.absorb(q_nope, w["w_uk"]), c)
        p = jax.nn.softmax(expanded, axis=-1)
        values = jnp.einsum("ths,shd->thd", p, deepseek_v2.per_head_up(c, w["w_uv"]))
        latent = deepseek_v2.per_head_up(
            jnp.einsum("ths,sr->thr", p, c), w["w_uv"], heads_in=True,
        )
    assert float(jnp.abs(expanded).max()) > 1.0
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded), atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(latent), np.asarray(values), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("dtype, bits, tolerance", [
    ("float32", 0, F32_TOLERANCE),
    ("bfloat16", 8, BF16_TOLERANCE),
])
def test_forward_equals_the_reference(dtype, bits, tolerance):
    if bits:
        tree = init_params_quantized(CONFIG, jax.random.PRNGKey(0))
    else:
        tree = deepseek_v2.init_params(CONFIG, jax.random.PRNGKey(0), dtype=jnp.float32)
    assert forward_gap(tree, config_doc(CONFIG, dtype, bits)) < tolerance


def _unnormed_attention(config, w, x, attend):
    """``deepseek_v2.attention`` with the latent's norm left out."""
    eps, heads = config.rms_norm_eps, config.num_heads
    dn, rank = config.qk_nope_head_dim, config.kv_lora_rank
    a = deepseek_v2.rms_norm(x, w["ln_attn"], eps)
    q = deepseek_v2.mm(a, w["wq"]).reshape(*x.shape[:-1], heads, -1)
    kv = deepseek_v2.mm(a, w["w_kva"])
    o = attend(w, q[..., :dn], q[..., dn:], kv[..., :rank], kv[..., rank:])
    return x + deepseek_v2.mm(o.reshape(*x.shape[:-1], -1), w["wo"])


FAULTS = [
    "the softmax scale without mscale squared", "q_rope unrotated",
    "the latent without kv_a_norm", "the top-k renormalised",
    "the shared expert dropped", "layer 0 run as an expert layer",
]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_changed_part_of_the_layer_fails_the_comparison(params, monkeypatch, fault):
    """The float32 comparison is tight enough to see each: the program is
    broken in one place and read against the unbroken reference."""
    config = CONFIG
    if fault.startswith("the softmax scale"):
        monkeypatch.setattr(
            DeepseekV2Config, "softmax_scale", property(lambda self: self.head_dim ** -0.5)
        )
    elif fault == "q_rope unrotated":
        rotate = deepseek_v2.rotate
        # the queries carry a head axis, the shared rotary key none
        monkeypatch.setattr(deepseek_v2, "rotate", lambda config, x, positions, inv_freq: (
            x if x.ndim == 4 else rotate(config, x, positions, inv_freq)
        ))
    elif fault == "the latent without kv_a_norm":
        monkeypatch.setattr(deepseek_v2, "attention", _unnormed_attention)
    elif fault == "the top-k renormalised":
        config = dataclasses.replace(CONFIG, norm_topk_prob=True)
    elif fault == "the shared expert dropped":
        mlp = deepseek_v2.mlp
        monkeypatch.setattr(deepseek_v2, "mlp", lambda w, m, prefix="": (
            jnp.zeros_like(m) if prefix == "sh_" else mlp(w, m, prefix)
        ))
    else:
        layers = params["layers"]
        first = deepseek_v2.layer_of(
            {k: v for k, v in layers.items() if k not in deepseek_v2.EXPERT_STACKS}, "", 0,
        )
        stacks = {name: layers[name] for name in deepseek_v2.EXPERT_STACKS}
        moe_ffn = deepseek_v2.moe_ffn
        # the dense layer's feed-forward replaced by an expert layer's
        monkeypatch.setattr(deepseek_v2, "dense_ffn", lambda config, w, x: moe_ffn(
            config, {**first, "ln_mlp": w["ln_mlp"]}, stacks, jnp.int32(0), x,
        )[0])
    gap = forward_gap(params, config_doc(), config)
    assert not gap <= 50 * F32_TOLERANCE, (fault, gap)


def test_the_references_own_int8_weights_are_the_programs_bit_for_bit():
    doc = config_doc(CONFIG, "bfloat16", 8)
    mine = own.make(doc)
    # as the serving path holds them: wq and dense_wq transposed
    held = hold_head_projections(init_params_quantized(CONFIG, jax.random.PRNGKey(0)))
    theirs = own.adapt(held, doc)
    assert set(mine.layers) == set(theirs.layers) == set(own.MATRICES + own.NORMS)
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
    for name in own.NORMS:  # no norm is ones: a dropped one cannot hide
        assert float(jnp.abs(mine.layers[name].astype(jnp.float32) - 1).max()) > 0.05, name


def test_routing_keeps_the_raw_top_k_probabilities(params):
    m = jax.random.normal(jax.random.PRNGKey(3), (7, CONFIG.hidden_size), jnp.float32)
    w_router = params["layers"]["w_router"][0]
    ids, gates = deepseek_v2.route(CONFIG, m, w_router)
    probs = np.asarray(jax.nn.softmax(m @ w_router, axis=-1))
    want = np.sort(probs, axis=-1)[:, ::-1][:, : CONFIG.num_experts_per_tok]
    np.testing.assert_allclose(np.asarray(gates), want, rtol=1e-5)
    assert float(np.asarray(gates).sum(axis=-1).max()) < 0.99  # not renormalised
    _, renormed = deepseek_v2.route(dataclasses.replace(CONFIG, norm_topk_prob=True), m, w_router)
    np.testing.assert_allclose(np.asarray(renormed).sum(axis=-1), 1.0, rtol=1e-5)


# -- (c) through the scheduler -------------------------------------------------


def test_every_position_through_the_scheduler_equals_the_full_forward(params):
    """Prompts prefilled in chunks of 8, then decoded; five requests over
    three slots, so rows join and leave and slots get a second tenant.
    Every step's logits (the absorbed attention over the latent pool)
    against the full expanded forward of the request's prompt and answer,
    both float32."""
    generator = BatchedGenerator(
        params, CONFIG, ByteTokenizer(), paged=True, cache_dtype=jnp.float32,
        metrics=MetricsRegistry(), max_slots=3, max_seq=128, page_size=16,
    )
    seen = []
    sample = generator.sample

    def recording(logits, rng, temp, top_p):
        jax.debug.callback(lambda value: seen.append(np.asarray(value)), logits)
        return sample(logits, rng, temp, top_p)

    generator.sample = recording
    spans = []
    annotation = generator._annotation

    def spying(name, params_list=None, **args):
        spans.append((name, args))
        return annotation(name, params_list, **args)

    generator._annotation = spying
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
    jax.effects_barrier()
    full = {}
    for req_id, i in ids.items():
        answer = list(done[req_id].result.token_ids)
        sequence = list(generator.tokenizer.encode(prompts[i])) + answer
        logits, _ = deepseek_v2.forward(
            params, CONFIG, jnp.asarray([sequence], jnp.int32), jnp.arange(len(sequence))[None]
        )
        full[req_id] = np.asarray(logits[0])
    compared, second_tenants, slots_of = 0, 0, {}
    for step_logits, plan in zip(seen, sched.plan_log):
        for slot, req_id, _, count, kind, pos0, *_ in plan:
            if slots_of.setdefault(slot, req_id) != req_id:
                second_tenants += 1
                slots_of[slot] = req_id
            np.testing.assert_allclose(
                step_logits[slot], full[req_id][pos0 + count - 1], atol=SERVED_TOLERANCE,
                rtol=0, err_msg=f"request {req_id} {kind} at position {pos0 + count - 1}",
            )
            compared += 1
    assert second_tenants >= 2 and compared >= 35
    cache = generator.paged_cache
    # the latent pool: a plane for every layer, latent rows and rope rows
    assert cache.v_pages is None and cache.k_pages.shape[0] == CONFIG.kv_planes == 3
    assert cache.k_pages.shape[-1] == CONFIG.kv_lora_rank
    assert cache.rope_pages.shape[2:] == (16 * CONFIG.qk_rope_head_dim // 128, 128)
    records = generator.step_clock.ring.records()
    assert all(r.latent_pages_walked == r.kv_pages_walked for r in records)
    # the dispatch span names the depths the two kernels' costs are counted
    # over: the latent kernel's planes, the layers with experts
    dispatched = [args for name, args in spans if name == "podmortem.sched.dispatch"]
    assert dispatched and all(
        (args["latent_layers"], args["moe_layers"]) == (3, 2) for args in dispatched
    )
    assert all(r.moe_tokens == r.tokens and r.moe_experts_hit is not None for r in records)


# -- the sizes, the frequencies and the refusals -------------------------------


def test_the_dense_layer_runs_ahead_of_a_loop_over_the_expert_layers(params):
    assert deepseek_v2.scan_steps(CONFIG) == CONFIG.moe_layers == 2
    for name, leaf in params["layers"].items():
        depth = 1 if name.startswith("dense_") else 2
        assert leaf.shape[0] == depth, name
    assert set(deepseek_v2.WHOLE_STACKS) == {
        name for name in params["layers"] if name.startswith("dense_")
    } | {"w_gate", "w_up", "w_down"}


def test_matmul_param_count_is_six_of_the_experts_and_the_shared_ones():
    big = get_config("deepseek-v2-lite-14l")
    h, heads = 2048, 16
    attention = h * heads * 192 + h * 576 + 512 * heads * 256 + heads * 128 * h
    assert attention == 13_762_560
    expert_layer = attention + h * 64 + 6 * 3 * h * 1408 + 3 * h * 2816
    dense_layer = attention + 3 * h * 10944
    assert deepseek_v2.matmul_params_per_token(big) == dense_layer + 13 * expert_layer
    # the weights held: 13 x 584.8 M + 81.0 M in the layers
    shapes = deepseek_v2.layer_matrix_shapes(big)
    held = sum(math.prod(shape) for shape in shapes.values())
    assert abs(held / 1e9 - (13 * 0.5848 + 0.0810)) < 0.002


def yarn_published(dim, base, factor, original, beta_fast, beta_slow):
    """The published DeepseekV2YarnRotaryEmbedding's inverse frequencies, in
    plain Python."""
    def correction(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    out = []
    for i in range(dim // 2):
        extra = 1.0 / base ** (2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(extra / factor * ramp + extra * (1 - ramp))
    return np.asarray(out)


def test_yarn_frequencies_are_the_published_ones():
    big = get_config("deepseek-v2-lite-14l")
    got = np.asarray(rope_frequencies(big, big.qk_rope_head_dim))
    want = yarn_published(64, 10000.0, 40.0, 4096, 32.0, 1.0)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got.shape == (32,) and got[0] == 1.0 and got[-1] == pytest.approx(want[-1])
    # mscale(40, 0.707)^2 over sqrt(192): 0.11472
    assert big.softmax_scale == pytest.approx(192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2)
    assert round(big.softmax_scale, 5) == 0.11472


@pytest.mark.parametrize("change, problem", [
    ({"q_lora_rank": 1536}, "q_lora_rank"),
    ({"topk_method": "group_limited_greedy"}, "topk_method"),
    ({"scoring_func": "sigmoid"}, "scoring_func"),
    ({"n_group": 8}, "n_group"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
])
def test_a_variant_the_layer_does_not_implement_is_refused_at_build(change, problem):
    with pytest.raises(AssertionError, match=problem):
        dataclasses.replace(get_config("deepseek-v2-lite"), **change)


def test_wave_mode_is_refused_at_start_up():
    from operator_tpu.serving.provider import build_serving_engine
    from operator_tpu.utils.config import OperatorConfig

    with pytest.raises(ValueError, match="deepseek_v2 family"):
        build_serving_engine(OperatorConfig(
            model_id="tiny-deepseek-v2", allow_random_weights=True, sched_mode="wave",
        ))


def test_the_published_sizes():
    config = get_config("deepseek-v2-lite")
    assert isinstance(config, DeepseekV2Config) and config.continuous_only
    assert (config.num_layers, config.first_k_dense_replace, config.moe_layers) == (27, 1, 26)
    assert config.latent_dims == (512, 64) and config.shared_intermediate_size == 2816
    assert config.rope_scaling_published == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096, "type": "yarn",
    }
    cut = get_config("deepseek-v2-lite-14l")
    assert cut == dataclasses.replace(config, name=cut.name, num_layers=14)
