"""SDAR (models/sdar.py) at a small size on the CPU: the program's forward
and the served path (chunked prefill of the prompt's whole blocks, then
blocks denoised through the paged pool, the scheduler and the one mixed
step) against the benchmark's plain float32 reference, logits not tokens;
faulty programs that the same comparisons catch; the expert layer's kernel
against a dense product over all experts; which positions a
``low_confidence`` step keeps; what a served answer looks like; what the
program refuses for this family; and its sizes.
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

from benchmark.reference import sdar_f32 as reference  # noqa: E402
from benchmark.reference import sdar_f32_weights as own  # noqa: E402
from operator_tpu.models import get_config, sdar  # noqa: E402
from operator_tpu.models.configs import SdarConfig  # noqa: E402
from operator_tpu.models.quant import init_params_quantized  # noqa: E402
from operator_tpu.models.tokenizer import ByteTokenizer  # noqa: E402
from operator_tpu.ops import moe_experts as moe  # noqa: E402
from operator_tpu.serving.engine import BatchedGenerator, SamplingParams  # noqa: E402
from operator_tpu.serving.sched import Scheduler  # noqa: E402
from operator_tpu.utils.timing import MetricsRegistry  # noqa: E402

TINY = get_config("tiny-sdar")  # 4 layers, 16 experts top-8, blocks of 4, hidden 64
BLOCK, MASK = TINY.block_length, TINY.mask_token_id
#: float32 on both sides, the same experts chosen on both: what is left is
#: the order of the sums.  One wrong choice of an expert, a causal mask in
#: the block-causal one's place or int4 for int8 move a logit by 0.05 to 2
ATOL = 5e-5


def config_doc(config, dtype="float32", bits=0, steps=2):
    """The groups of a configuration file the reference reads, for a tiny
    model: every ``architecture`` key from the program's config."""
    return {
        "architecture": {
            key: getattr(config, attribute)
            for key, attribute in own.PROGRAM_CONFIG.items()
        },
        "generation": {"denoise_steps": steps, "remask": "sequential"},
        "weights": {"seed": 0, "init": "sdar_fan_in", "dtype": dtype, "bits": bits},
    }


@pytest.fixture(scope="module")
def params():
    """The seeded init in float32 (its norms are drawn away from 1)."""
    return sdar.init_params(TINY, jax.random.PRNGKey(0), dtype=jnp.float32)


def make_generator(tree, config=TINY, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_seq", 128)
    kw.setdefault("page_size", 16)
    return BatchedGenerator(
        tree, config, ByteTokenizer(), paged=True,
        cache_dtype=jnp.float32, metrics=MetricsRegistry(), **kw,
    )


def capture_steps(generator, sched):
    """Every step's ``[slots x block, vocab]`` logits and drawn tokens as
    the sampler sees them, and the bits of the positions each step kept,
    in step order."""
    seen = {"logits": [], "drawn": [], "kept": []}
    sample = generator.sample_confident

    def recording(logits, rng, temp, top_p):
        toks, conf, rng = sample(logits, rng, temp, top_p)
        jax.debug.callback(
            lambda a, b: (seen["logits"].append(np.asarray(a)), seen["drawn"].append(np.asarray(b))),
            logits, toks,
        )
        return toks, conf, rng

    generator.sample_confident = recording
    real = sched._get_fn()

    def spy(*args):
        out = real(*args)
        seen["kept"].append(np.asarray(out[2]))
        return out

    sched._fn = spy
    return seen


def drain(sched, want, done=None, limit=400):
    done = {} if done is None else done
    for _ in range(limit):
        if len(done) >= want:
            break
        for outcome in sched.step():
            done[outcome.req_id] = outcome
    assert len(done) >= want and all(o.error is None for o in done.values())
    return done


#: prompts whose length leaves 0, 1 and 3 tokens past a block's end, one
#: longer than a chunk of 8, one shorter than a block; answers that end
#: inside a block
REQUESTS = [(12, 7), (9, 10), (19, 6), (3, 5), (16, 9)]


def serve(tree, steps, *, config=TINY, remask="sequential", temperature=0.0, depth=2):
    """The requests above over three slots, prompts prefilled in chunks of
    8: ``(generator, scheduler, captured steps, {req id: (prompt ids,
    result)}, streamed partials)``."""
    generator = make_generator(tree, config)
    sched = Scheduler(generator, chunk=8, token_budget=32, pipeline_depth=depth)
    sched.plan_log = []
    seen = capture_steps(generator, sched)
    partials = {}
    def heard(handed):
        for req_id, ids in handed:
            partials.setdefault(req_id, []).append(ids)

    sched.partial_hook = heard
    rng = np.random.default_rng(5)
    sent, done = {}, {}
    for length, max_tokens in REQUESTS:
        prompt = "".join(chr(int(c)) for c in rng.integers(97, 123, length - 1))  # + BOS
        req_id = sched.enqueue(prompt, SamplingParams(
            max_tokens=max_tokens, temperature=temperature, top_p=0.9, stop_on_eos=False,
            denoise_steps=steps, remask=remask,
        ))
        sent[req_id] = list(generator.tokenizer.encode(prompt))
        assert len(sent[req_id]) == length
        for outcome in sched.step():  # arrivals spread over the steps
            done[outcome.req_id] = outcome
    drain(sched, len(REQUESTS), done)
    jax.effects_barrier()  # the last steps' callbacks
    # a round that found every row's next step in flight dispatched nothing
    sched.plan_log = [plan for plan in sched.plan_log if plan]
    assert len(seen["logits"]) == len(sched.plan_log) == len(seen["kept"])
    served = {r: (sent[r], done[r].result) for r in sent}
    return generator, sched, seen, served, partials


def block_steps(sched):
    """``{(req id, block start, step of the block): (dispatch, slot)}`` of
    every denoising step the scheduler planned."""
    out, count = {}, {}
    for number, plan in enumerate(sched.plan_log):
        for slot, req_id, _, tokens, kind, pos0, *_ in plan:
            if kind != "block":
                continue
            start = pos0 + tokens - BLOCK
            step = count.get((req_id, start), 0)
            count[(req_id, start)] = step + 1
            out[(req_id, start, step)] = (number, slot)
    return out


def served_against_the_reference(tree, steps, reference_tree=None, **kw):
    """The largest difference, over every served token, between the logits
    the sampler was given at the token's position in the step that kept it
    (the mask id's column apart: the program takes it out) and the
    reference's, and how many were compared."""
    generator, sched, seen, served, _ = serve(tree, steps, **kw)
    doc = config_doc(TINY, steps=steps)
    weights = own.adapt(reference_tree or tree, doc)
    where = block_steps(sched)
    columns = np.arange(TINY.vocab_size) != MASK
    worst, compared = 0.0, 0
    for req_id, (prompt, result) in served.items():
        assert len(result.token_ids) == dict(REQUESTS)[len(prompt)]
        want = np.asarray(reference.step_logits(doc, weights, prompt, result.token_ids))
        plan = reference.schedule(len(prompt), len(result.token_ids), BLOCK, steps)
        for row, (step, position) in enumerate(plan["kept"]):
            start = position - position % BLOCK
            dispatch, slot = where[(req_id, start, step)]
            got = seen["logits"][dispatch][slot * BLOCK + position - start]
            assert got[MASK] == -np.inf
            worst = max(worst, float(np.abs(got[columns] - want[row][columns]).max()))
            # the token served is the program's own first choice there
            assert int(np.argmax(got)) == result.token_ids[row]
            compared += 1
    return worst, compared, generator


# -- (a) the forward and the served path against the reference -------------------


def test_forward_equals_the_reference_on_the_seeded_weights(params):
    """Whole blocks of a sequence, float32 on both sides."""
    ids = [int(t) for t in np.random.default_rng(0).integers(0, 500, 24)]
    doc = config_doc(TINY)
    want = np.asarray(reference.logits(doc, own.adapt(params, doc), ids))
    got, _ = sdar.forward(params, TINY, jnp.asarray([ids]), jnp.arange(24)[None])
    assert np.abs(np.asarray(got[0]) - want).max() < ATOL
    # the mask is block-causal: a position's logits move with a LATER
    # position of its own block and with none of a later block
    changed = list(ids)
    changed[14] = (changed[14] + 1) % 500
    other, _ = sdar.forward(params, TINY, jnp.asarray([changed]), jnp.arange(24)[None])
    moved = np.abs(np.asarray(other[0]) - np.asarray(got[0])).max(axis=-1)
    assert moved[12] > 100 * ATOL and moved[:12].max() == 0.0


def test_the_recipe_makes_the_programs_weights_bit_for_bit():
    doc = config_doc(TINY, dtype="bfloat16", bits=8)
    mine = own.make(doc)
    theirs = own.adapt(init_params_quantized(TINY, jax.random.PRNGKey(0)), doc)
    for name in own.MATRICES + own.VECTORS:
        for a, b in zip(
            jax.tree_util.tree_leaves(mine.layers[name]),
            jax.tree_util.tree_leaves(theirs.layers[name]),
        ):
            assert a.dtype == b.dtype and bool((np.asarray(a) == np.asarray(b)).all()), name
    for name in ("embed", "lm_head", "ln_final"):
        assert bool((np.asarray(mine.leaves[name]) == np.asarray(theirs.leaves[name])).all())
    quantised = {n for n, leaf in theirs.layers.items() if isinstance(leaf, dict)}
    assert quantised == set(sdar.LAYER_MATRICES) and "w_router" not in quantised


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_every_served_token_through_the_scheduler_equals_the_reference(params, steps):
    worst, compared, generator = served_against_the_reference(params, steps)
    assert compared == sum(m for _, m in REQUESTS) and worst < ATOL, worst
    records = generator.step_clock.ring.records()
    block_rows = sum(r.block_rows for r in records)
    unmasked = sum(r.unmasked_tokens for r in records)
    assert unmasked == compared and generator.metrics.counter("unmasked_tokens") == compared
    # a step keeps `BLOCK / steps` positions, or what a first or a last
    # block has left
    assert 1 <= unmasked / block_rows <= BLOCK / steps
    assert {r.sampled_rows for r in records} == {generator.max_slots * BLOCK}
    assert all(r.moe_tokens == r.tokens for r in records)
    assert all(1 <= r.moe_assign_max <= r.tokens for r in records if r.tokens)
    assert all(
        r.moe_experts_hit <= TINY.num_layers * TINY.num_experts for r in records
    )
    commits = sum(r.commit_tokens for r in records)
    assert commits and commits % BLOCK == 0  # a block leads the next one's first step


def causal_kernel(monkeypatch):
    from operator_tpu.ops import ragged_attention

    real = ragged_attention.ragged_paged_attention
    monkeypatch.setattr(
        ragged_attention, "ragged_paged_attention",
        lambda *a, attend_block=1, **kw: real(*a, **kw),
    )


@pytest.mark.parametrize("fault", ["a causal mask", "int4 for int8", "a prompt's tail prefilled"])
def test_a_faulty_program_fails_the_same_comparison(params, monkeypatch, fault):
    """The comparison above is not passed by a step whose attention is
    causal, by int4 where the reference holds int8, or by a prompt whose
    tail is written as context and not denoised beside the first block."""
    if fault == "a causal mask":
        causal_kernel(monkeypatch)
        worst, _, _ = served_against_the_reference(params, 2)
    elif fault == "int4 for int8":
        served = own.make(config_doc(TINY, bits=4)).leaves
        sound = own.make(config_doc(TINY, bits=8)).leaves
        worst, _, _ = served_against_the_reference(served, 2, reference_tree=sound)
    else:
        from operator_tpu.serving.sched import types

        monkeypatch.setattr(
            types.BlockSchedule, "prefill_len", property(lambda self: self.prompt_len),
        )
        with pytest.raises((AssertionError, KeyError)):
            served_against_the_reference(params, 2)
        return
    assert worst > 100 * ATOL, (fault, worst)


def test_depth_one_and_two_serve_the_same_and_stream_in_order(params):
    """Pipelined two steps deep the host packs every count ahead; what is
    served is what the synchronous loop serves, every answer is exactly
    ``max_tokens`` ids, and each partial is the one before it and more."""
    _, _, _, ahead, partials = serve(params, 2, depth=2)
    _, _, _, plain, _ = serve(params, 2, depth=1)
    for req_id, (prompt, result) in ahead.items():
        assert result.token_ids == plain[req_id][1].token_ids
        assert result.finish_reason == "length" and MASK not in result.token_ids
        streamed = partials.get(req_id, [])
        for before, after in zip(streamed, streamed[1:]):
            assert len(after) > len(before) and after[: len(before)] == before
        if streamed:
            assert streamed[-1] == result.token_ids[: len(streamed[-1])]


def test_low_confidence_keeps_the_references_own_ranking(params):
    """At a temperature, under ``remask: "low_confidence"``: the positions
    every step kept are those the reference's plain ranking of the
    sampler's candidate probabilities keeps, on steps whose confidences
    lie clearly apart."""
    generator, sched, seen, served, _ = serve(
        params, 2, remask="low_confidence", temperature=0.8,
    )
    checked = out_of_order = 0
    for (req_id, start, step), (dispatch, slot) in block_steps(sched).items():
        prompt, result = served[req_id]
        rows = slice(slot * BLOCK, (slot + 1) * BLOCK)
        logits, drawn = seen["logits"][dispatch][rows], seen["drawn"][dispatch][rows]
        kept = [j for j in range(BLOCK) if int(seen["kept"][dispatch][slot]) >> j & 1]
        # what was open: below the answer's end, not the prompt's tail, not
        # kept by an earlier step of the block
        earlier = set()
        for before in range(step):
            d, s = block_steps(sched)[(req_id, start, before)]
            earlier |= {j for j in range(BLOCK) if int(seen["kept"][d][s]) >> j & 1}
        end = len(prompt) + len(result.token_ids)
        open_ = [
            j for j in range(BLOCK)
            if len(prompt) <= start + j < end and j not in earlier
        ]
        want = reference.kept_positions(
            logits, list(drawn), open_, min(BLOCK // 2, len(open_)),
            0.8, 0.9, generator.sample_top_k,
        )
        assert kept == want, (req_id, start, step)
        checked += 1
        out_of_order += kept != open_[: len(kept)]
        for j in kept:  # a kept position holds what was drawn there, for good
            assert result.token_ids[start + j - len(prompt)] == int(drawn[j])
    assert checked >= 15 and out_of_order >= 1  # the rule is not the sequential one


# -- (b) the expert layer ---------------------------------------------------------


def dense_experts(x, expert_ids, gates, stacks, layer):
    """Every expert for every token in numpy float64, then the routed ones
    summed with their gates."""
    def widen(leaf):
        if isinstance(leaf, dict):
            return np.asarray(leaf["q"][layer], np.float64) * np.asarray(leaf["s"][layer], np.float64)[:, None, :]
        return np.asarray(leaf[layer], np.float64)

    wg, wu, wd = (widen(stacks[n]) for n in ("w_gate", "w_up", "w_down"))
    x = np.asarray(x, np.float64)
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        for e, g in zip(np.asarray(expert_ids[t]), np.asarray(gates[t], np.float64)):
            if e >= wg.shape[0]:
                continue  # routed nowhere
            h = x[t] @ wg[e]
            out[t] += g * (((h / (1 + np.exp(-h))) * (x[t] @ wu[e])) @ wd[e])
    return out


@pytest.mark.parametrize("routing", ["the router's", "one expert takes all", "padding and an idle expert"])
@pytest.mark.parametrize("quantised", [False, True])
def test_the_expert_kernel_equals_a_dense_product_over_all_experts(routing, quantised):
    experts, top, hidden, inner, tokens, layers = 8, 2, 64, 32, 24, 2
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    x = jax.random.normal(keys[0], (tokens, hidden), jnp.float32)
    stacks = {
        "w_gate": jax.random.normal(keys[1], (layers, experts, hidden, inner)) * hidden ** -0.5,
        "w_up": jax.random.normal(keys[2], (layers, experts, hidden, inner)) * hidden ** -0.5,
        "w_down": jax.random.normal(keys[3], (layers, experts, inner, hidden)) * inner ** -0.5,
    }
    if quantised:
        from operator_tpu.models.quant import quantize_matrix

        stacks = {name: quantize_matrix(w) for name, w in stacks.items()}
    gates, expert_ids = jax.lax.top_k(jax.nn.softmax(jax.random.normal(keys[4], (tokens, experts))), top)
    expert_ids = expert_ids.astype(jnp.int32)
    if routing == "one expert takes all":
        expert_ids = expert_ids.at[:, 0].set(5)
        expert_ids = expert_ids.at[:, 1].set(jnp.where(expert_ids[:, 1] == 5, 6, expert_ids[:, 1]))
    elif routing == "padding and an idle expert":
        expert_ids = jnp.where(expert_ids == 2, 3, expert_ids)  # expert 2 gets none
        expert_ids = expert_ids.at[::3].set(experts)  # every third token is padding
    layer = jnp.asarray(1, jnp.int32)
    want = dense_experts(x, expert_ids, gates, stacks, 1)
    args = (x, expert_ids, gates, stacks["w_gate"], stacks["w_up"], stacks["w_down"], layer)
    for tile in (8, 16):
        got = moe._moe_experts_pallas(*args, tile=tile, interpret=True)
        assert np.abs(np.asarray(got, np.float64) - want).max() < 2e-5, tile
    assert np.abs(np.asarray(moe.moe_experts_reference(*args), np.float64) - want).max() < 2e-5
    if routing == "padding and an idle expert":
        assert np.abs(want[::3]).max() == 0.0 and np.abs(np.asarray(got)[::3]).max() == 0.0
    counts = np.asarray(moe.expert_counts(expert_ids, experts))
    assert counts.sum() == int((np.asarray(expert_ids) < experts).sum())
    layout = moe.group_rows(expert_ids, experts, 8)
    assert int(layout["n_tiles"]) == int(np.ceil(counts / 8).sum())
    # tiles of one expert follow each other, so its matrices move once
    used = np.asarray(layout["tile_expert"])[: int(layout["n_tiles"])]
    assert list(used) == sorted(used) and set(used) == set(np.nonzero(counts)[0])


def test_the_router_takes_the_largest_and_divides_by_their_sum(params):
    m = jax.random.normal(jax.random.PRNGKey(1), (10, TINY.hidden_size), jnp.float32)
    router = params["layers"]["w_router"][0]
    valid = jnp.arange(10) < 7
    expert_ids, gates = sdar.route(TINY, m, router, valid)
    probs = np.asarray(jax.nn.softmax(m @ router, axis=-1))
    for t in range(7):
        best = np.argsort(-probs[t])[: TINY.num_experts_per_tok]
        assert list(np.asarray(expert_ids[t])) == list(best)
        assert np.allclose(np.asarray(gates[t]), probs[t][best] / probs[t][best].sum(), atol=1e-6)
    assert (np.asarray(expert_ids[7:]) == TINY.num_experts).all()  # routed nowhere


# -- (c) what is refused, and what the program says of itself -----------------------


def operator_config(**kw):
    from operator_tpu.utils.config import OperatorConfig

    return OperatorConfig(
        model_id="tiny-sdar", allow_random_weights=True, serving_dtype="int8",
        max_batch_size=3, kv_page_size=16, sched_chunk=8, sched_token_budget=32, **kw,
    )


@pytest.mark.parametrize("change, names", [
    ({"sched_mode": "wave"}, "sched_mode='wave'"),
    ({"serving_mesh": "dp=1,tp=2"}, "serving_mesh='dp=1,tp=2'"),
])
def test_wave_mode_and_a_mesh_refuse_at_start_up(change, names):
    from operator_tpu.serving import provider

    with pytest.raises(ValueError) as refused:
        provider.build_serving_engine(operator_config(**change))
    assert "sdar family" in str(refused.value) and names in str(refused.value)
    assert "denoises a block" in str(refused.value)


def test_the_served_model_switches_speculation_and_the_prefix_store_off():
    import asyncio

    from operator_tpu.serving.provider import build_serving_engine

    engine, model_id = build_serving_engine(operator_config(spec_decode=True, kv_prefix_cache=True))
    features = engine.serving_features()

    async def run():
        result = await engine.generate("the pod was OOMKilled", SamplingParams(
            max_tokens=6, temperature=0.0, stop_on_eos=False, denoise_steps=2,
        ))
        for bad, match in (
            ({"guided_choice": ["a", "b"]}, "sdar family|guided"),
            ({"denoise_steps": 3}, "does not divide"),
            ({"remask": "random"}, "remask"),
        ):
            with pytest.raises(ValueError, match=match):
                await engine.generate("p", SamplingParams(max_tokens=2, **bad))
        await engine.close()
        return result

    result = asyncio.run(run())
    assert model_id == "tiny-sdar" and features["modelFamily"] == "sdar"
    assert set(features["switchedOff"]) == {"spec_decode", "kv_prefix_cache"}
    assert features["specDecode"] is False and features["kvPrefixCache"] is False
    assert len(result.token_ids) == 6 and result.finish_reason == "length"


def test_a_model_that_does_not_denoise_refuses_the_denoising_parameters():
    from operator_tpu.models import family_of

    config = get_config("tiny-test")
    tree = family_of(config).init_params(config, jax.random.PRNGKey(0), dtype=jnp.float32)
    sched = Scheduler(make_generator(tree, config), chunk=8, token_budget=12)
    for bad in ({"denoise_steps": 2}, {"remask": "sequential"}):
        with pytest.raises(ValueError, match="denoises blocks"):
            sched.enqueue("p", SamplingParams(max_tokens=2, **bad))
    sched.enqueue("p", SamplingParams(max_tokens=2))
    records = []
    drain(sched, 1)
    records = sched.generator.step_clock.ring.records()
    assert records and all(
        getattr(r, name) is None for r in records
        for name in ("block_rows", "unmasked_tokens", "commit_tokens",
                     "moe_tokens", "moe_experts_hit", "moe_assign_max")
    )


def test_the_token_budget_holds_two_blocks_a_slot(params):
    generator = make_generator(params)
    with pytest.raises(ValueError, match="full decode batch"):
        Scheduler(generator, chunk=8, token_budget=16)
    with pytest.raises(ValueError, match="multiple of the model's block"):
        Scheduler(generator, chunk=6, token_budget=32)
    assert Scheduler(generator, chunk=8).t_budget == 3 * 2 * BLOCK


# -- (d) sizes ----------------------------------------------------------------------


def test_matmul_param_count_is_eight_of_the_experts_and_the_head():
    from operator_tpu.serving.perf import flops_per_token, matmul_param_count

    tree = sdar.init_params(TINY, jax.random.PRNGKey(0), dtype=jnp.float32)
    layers = tree["layers"]
    attention = sum(int(layers[name].size) for name in ("wq", "wk", "wv", "wo", "w_router"))
    experts = sum(int(layers[name].size) for name in sdar.WHOLE_STACKS)
    want = (
        attention + experts * TINY.num_experts_per_tok // TINY.num_experts
        + int(tree["lm_head"].size)
    )
    assert matmul_param_count(TINY) == want and flops_per_token(TINY) == 2.0 * want


def test_the_published_sizes():
    config = get_config("sdar-30b-a3b")
    assert isinstance(config, SdarConfig) and config.family == "sdar"
    assert (config.num_layers, config.num_experts, config.num_experts_per_tok) == (48, 128, 8)
    assert (config.num_heads, config.num_kv_heads, config.head_dim, config.q_per_kv) == (32, 4, 128, 8)
    assert (config.block_length, config.mask_token_id) == (4, 151669)
    cut = get_config("sdar-30b-a3b-12l")
    assert dataclasses.replace(cut, name=config.name, num_layers=48) == config
    shapes = sdar.layer_matrix_shapes(cut)
    per_layer = sum(int(np.prod(shape[1:])) for shape in shapes.values())
    per_layer += cut.hidden_size * cut.num_experts  # the router
    assert round(per_layer / 1e6, 1) == 623.1  # ISSUE 36's reckoning
    # KV a token over the cut's 12 layers, bfloat16: 24 KB
    assert cut.kv_planes * 2 * cut.num_kv_heads * cut.head_dim * 2 == 24 * 1024
    with pytest.raises(AssertionError, match="does not implement"):
        dataclasses.replace(config, attention_bias=True)
    with pytest.raises(AssertionError, match="power of two"):
        dataclasses.replace(config, block_length=3)
