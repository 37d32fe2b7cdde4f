"""Decode-ahead pipelining + prompt-lookup speculation (ISSUE 13).

Covers the pipelined scheduler's acceptance surface: byte-identical
greedy output pipelined-vs-sync and spec-on-vs-off (the replan +
longest-accepted-prefix invariants), seeded acceptance-rate
determinism, cancel-mid-flight returning the in-flight step's pages
exactly once, the chaos stall mid-pipelined-step (supervised restart
requeues survivors with their residual deadlines, zero slot/page
leaks), and per-request streaming token order under pipelined commits.
"""

import asyncio

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from operator_tpu.models import TINY_TEST, init_params  # noqa: E402
from operator_tpu.models.tokenizer import ByteTokenizer  # noqa: E402
from operator_tpu.serving.engine import (  # noqa: E402
    BatchedGenerator,
    SamplingParams,
    ServingEngine,
    SupervisorPolicy,
)
from operator_tpu.serving.sched import Scheduler  # noqa: E402
from operator_tpu.serving.sched.draft import PromptLookupDraft  # noqa: E402
from operator_tpu.utils.timing import MetricsRegistry  # noqa: E402

# templated traffic: the repetitive text prompt-lookup drafting exists
# for (an n-gram seen earlier in the request's own context recurs)
TEMPLATED = "the pod was OOMKilled after its memory limit was exceeded " * 3
PROMPTS = [
    "pod crashed with exit code 137",
    TEMPLATED,
    "a much longer prompt " * 8,
]


@pytest.fixture(scope="module")
def params():
    return init_params(TINY_TEST, jax.random.PRNGKey(0), dtype=jnp.float32)


def make_generator(params, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq", 128)
    kw.setdefault("page_size", 16)
    return BatchedGenerator(
        params, TINY_TEST, ByteTokenizer(), paged=True,
        cache_dtype=jnp.float32, metrics=MetricsRegistry(), **kw,
    )


def make_sched(generator, **kw):
    kw.setdefault("chunk", 16)
    kw.setdefault("token_budget", 32)
    return Scheduler(generator, **kw)


def drain(sched, want, limit=400):
    done = {}
    for _ in range(limit):
        for outcome in sched.step():
            done[outcome.req_id] = outcome
        if len(done) >= want:
            return done
    raise AssertionError(f"only {len(done)}/{want} finished in {limit} steps")


def assert_no_leaks(generator):
    assert len(generator.free_slots()) == generator.max_slots
    assert generator.allocator.available == generator.allocator.num_pages - 1


def run_trace(params, prompts, *, max_tokens=12, **sched_kw):
    """Run ``prompts`` greedily to completion; returns (token_ids per
    prompt, scheduler stats)."""
    generator = make_generator(params)
    sched = make_sched(generator, **sched_kw)
    sampling = SamplingParams(max_tokens=max_tokens, temperature=0.0,
                              stop_on_eos=False)
    ids = {sched.enqueue(p, sampling): p for p in prompts}
    done = drain(sched, len(prompts))
    assert all(done[r].error is None for r in ids)
    assert_no_leaks(generator)
    tokens = {ids[r]: done[r].result.token_ids for r in ids}
    return tokens, sched.stats()


# ---------------------------------------------------------------------------
# prompt-lookup draft (host-side, pure)
# ---------------------------------------------------------------------------


class TestPromptLookupDraft:
    def test_proposes_continuation_of_repeated_ngram(self):
        draft = PromptLookupDraft()
        context = [1, 2, 3, 4, 5, 9, 9, 1, 2, 3]
        # trigram (1,2,3) seen earlier -> continuation [4, 5, 9]
        assert list(draft.propose(context, 3)) == [4, 5, 9]

    def test_no_match_returns_empty(self):
        draft = PromptLookupDraft()
        assert list(draft.propose([1, 2, 3, 4], 4)) == []
        assert list(draft.propose([], 4)) == []

    def test_deterministic(self):
        draft = PromptLookupDraft()
        context = list(range(20)) * 2
        assert draft.propose(context, 5) == draft.propose(context, 5)


# ---------------------------------------------------------------------------
# byte-identical greedy parity
# ---------------------------------------------------------------------------


class TestPipelinedParity:
    def test_greedy_parity_pipelined_vs_sync(self, params):
        """depth=1 (synchronous commit-every-step) and depth>=2
        (dispatch-ahead from predicted row state) must produce
        byte-identical greedy tokens — the conservative-replan
        contract."""
        sync_tokens, sync_stats = run_trace(params, PROMPTS, pipeline_depth=1)
        for depth in (2, 3):
            toks, stats = run_trace(params, PROMPTS, pipeline_depth=depth)
            assert toks == sync_tokens, f"depth={depth} diverged"
            assert stats["dispatch_ahead"] > 0  # actually pipelined
        assert sync_stats["dispatch_ahead"] == 0

    def test_greedy_parity_spec_on_vs_off(self, params):
        """Speculation accepts the longest prefix of drafts matching
        what the model would have sampled anyway, so greedy output is
        byte-identical by construction — and on templated traffic the
        verify path must actually fire."""
        plain, _ = run_trace(params, PROMPTS, max_tokens=20,
                             pipeline_depth=2, spec_decode=False)
        spec, stats = run_trace(params, PROMPTS, max_tokens=20,
                                pipeline_depth=2, spec_decode=True)
        assert spec == plain
        assert stats["spec_decode"]["verify_rounds"] >= 1
        assert stats["spec_decode"]["drafts_proposed"] >= 1

    def test_spec_multi_accept_beats_one_token_per_sync(self, params):
        """A self-continuing prompt (pure repetition) must commit more
        than one decode token per host sync — the headline metric the
        whole PR exists for."""
        tokens, stats = run_trace(
            params, ["abcabcabcabcabcabcabcabc"], max_tokens=24,
            pipeline_depth=2, spec_decode=True,
        )
        assert stats["decode_tokens_per_host_sync"] is not None
        assert stats["decode_tokens_per_host_sync"] > 1.0


# ---------------------------------------------------------------------------
# the head and the sampler run at the width the step's drafts need
# ---------------------------------------------------------------------------


def run_counted(params, *, depth, spec):
    """Three greedy rows (one of them templated: it drafts) beside a row
    at temperature 0.8, which never does.  Returns the greedy rows'
    tokens, whether each dispatch carried a draft (in order), the step
    records and the scheduler."""
    import numpy as np

    generator = make_generator(params)
    sched = make_sched(generator, pipeline_depth=depth, spec_decode=spec)
    real = sched._get_fn()
    drafted = []

    def spy(*args):
        drafted.append(bool(np.asarray(args[13]).any()))  # spec_len
        return real(*args)

    sched._fn = spy
    greedy = SamplingParams(max_tokens=20, temperature=0.0, stop_on_eos=False)
    warm = SamplingParams(max_tokens=20, temperature=0.8, stop_on_eos=False)
    ids = {sched.enqueue(p, greedy): p for p in PROMPTS}
    sched.enqueue("the kubelet evicted the pod", warm)
    done = drain(sched, len(PROMPTS) + 1)
    assert all(outcome.error is None for outcome in done.values())
    assert_no_leaks(generator)
    tokens = {ids[r]: done[r].result.token_ids for r in ids}
    return tokens, drafted, generator.step_clock.ring.records(), sched


class TestSampledWidth:
    @pytest.mark.parametrize("depth", [1, 2])
    def test_narrow_and_wide_steps_commit_the_one_token_tokens(self, params, depth):
        """With the verify width compiled, a step without a verify row
        samples one row a slot and a step with one samples the width:
        both commit, for greedy rows, byte for byte what the scheduler
        without speculation commits; and each step's record counts the
        logit rows its tail worked."""
        plain, plain_drafted, plain_records, _ = run_counted(
            params, depth=depth, spec=False
        )
        spec, drafted, records, sched = run_counted(params, depth=depth, spec=True)
        assert spec == plain
        slots, width = sched.generator.max_slots, sched.width
        assert width == 5 and len(records) == len(drafted)
        assert True in drafted and False in drafted  # both kinds of step ran
        assert [r.sampled_rows for r in records] == [
            slots * width if wide else slots for wide in drafted
        ]
        wide_steps = sum(drafted)
        assert sched.metrics.counter("sample_wide_steps") == wide_steps
        rounds = sched.stats()["spec_decode"]["verify_rounds"]
        assert 1 <= rounds and wide_steps <= rounds
        # compiled at width 1 every step is narrow, and none is counted
        assert not any(plain_drafted)
        assert {r.sampled_rows for r in plain_records} == {slots}

    @pytest.mark.parametrize("name, width, conditionals", [
        ("tiny-test", 5, 1), ("tiny-test", 1, 0), ("tiny-falcon-h1", 1, 0),
    ])
    def test_the_lowered_step_holds_one_conditional_when_it_can_verify(
        self, name, width, conditionals
    ):
        """At a verify width the step's tail is one conditional on
        ``spec_len``: its first (narrow) branch makes ``[slots, V]``
        logits and no ``[slots * width, .]`` value, its second today's
        ``[slots, width, V]``; the pools are operands of neither.  At
        width 1 (no speculation: every recurrent model) nothing branches."""
        import re

        from operator_tpu.models import family_of, get_config
        from operator_tpu.serving.sched.mixed import make_mixed_fn

        config = get_config(name)
        weights = family_of(config).init_params(
            config, jax.random.PRNGKey(0), dtype=jnp.float32
        )
        generator = BatchedGenerator(
            weights, config, ByteTokenizer(), paged=True, max_slots=4,
            max_seq=128, page_size=16, cache_dtype=jnp.float32,
            metrics=MetricsRegistry(),
        )
        slots, t_budget, vocab = 4, 16, config.vocab_size
        flat_i, flat_b = jnp.zeros((t_budget,), jnp.int32), jnp.zeros((t_budget,), bool)
        slot_i, slot_f = jnp.zeros((slots,), jnp.int32), jnp.zeros((slots,), jnp.float32)
        text = make_mixed_fn(generator, t_budget, 8, spec_width=width).lower(
            weights, generator.paged_cache, flat_i, flat_i, flat_i, flat_b, flat_i,
            slot_i, slot_i, slot_i, slot_i, flat_b, slot_i, slot_i,
            jax.random.PRNGKey(0), slot_f, slot_f,
        ).as_text()
        assert text.count('"stablehlo.case"') == conditionals
        assert "stablehlo.if" not in text
        if not conditionals:
            return
        opened = text.index('"stablehlo.case"')
        narrow, wide = text[opened:].split("\n    }, {\n", 1)
        wide = wide[: wide.index("\n    }) : (tensor<i32>)")]
        wide_rows = re.compile(rf"tensor<{slots * width}x|tensor<{slots}x{width}x")
        assert f"tensor<{slots}x{vocab}xf32>" in narrow
        assert not wide_rows.search(narrow.replace(f"tensor<{slots}x{width}xi32>", ""))
        assert f"tensor<{slots}x{width}x{vocab}xf32>" in wide
        assert f"tensor<{slots * width}x{vocab}xf32>" in wide
        pool = "x".join(str(d) for d in generator.paged_cache.k_pages.shape)
        assert f"tensor<{pool}x" in text[:opened]  # the pools are in the step ...
        assert f"tensor<{pool}x" not in narrow + wide  # ... and in no branch


# ---------------------------------------------------------------------------
# seeded determinism
# ---------------------------------------------------------------------------


class TestAcceptanceDeterminism:
    def test_seeded_storm_accepts_identically_twice(self, params):
        """Same arrival trace, two fresh schedulers: every token AND the
        full speculation ledger (proposed/accepted/rounds/rests) must
        replay identically — acceptance is a pure function of the seeded
        model + deterministic draft."""

        def run_once():
            tokens, stats = run_trace(
                params, PROMPTS + [TEMPLATED + " exit code 137"],
                max_tokens=16, pipeline_depth=2, spec_decode=True,
            )
            ledger = dict(stats["spec_decode"])
            ledger.pop("draft_overhead_ms")  # wall-clock, not semantic
            return tokens, ledger

        tokens_a, ledger_a = run_once()
        tokens_b, ledger_b = run_once()
        assert tokens_a == tokens_b
        assert ledger_a == ledger_b
        assert ledger_a["drafts_proposed"] >= 1


# ---------------------------------------------------------------------------
# cancellation with work in flight
# ---------------------------------------------------------------------------


class TestCancelMidFlight:
    def test_cancel_returns_inflight_pages_exactly_once(self, params):
        """Cancel a row while a dispatched-ahead step is still in
        flight: its slot/pages come back NOW, the stale in-flight work
        is voided at commit (not double-freed), and the pool audit
        balances exactly."""
        generator = make_generator(params)
        sched = make_sched(generator, pipeline_depth=3)
        victim = sched.enqueue(
            "cancelled with two steps in flight " * 2,
            SamplingParams(max_tokens=50, temperature=0.0, stop_on_eos=False),
        )
        survivor = sched.enqueue(
            "keeps decoding",
            SamplingParams(max_tokens=8, temperature=0.0, stop_on_eos=False),
        )
        for _ in range(6):
            sched.step()
        assert sched.num_active == 2
        assert len(sched._inflight) >= 1  # work genuinely in flight
        assert sched.cancel(victim) is True
        assert sched.num_active == 1
        done = drain(sched, 1)
        assert done[survivor].error is None
        assert done[survivor].result.completion_tokens == 8
        assert generator.metrics.counter("sched_pipeline_voided") >= 1
        assert_no_leaks(generator)

    def test_finish_with_inflight_successor_voids_cleanly(self, params):
        """A row that hits max_tokens while its speculatively planned
        successor step is in flight must finish once, void the
        successor, and leak nothing."""
        generator = make_generator(params)
        sched = make_sched(generator, pipeline_depth=2)
        req = sched.enqueue(
            "short budget",
            SamplingParams(max_tokens=3, temperature=0.0, stop_on_eos=False),
        )
        done = drain(sched, 1)
        assert done[req].result.completion_tokens == 3
        assert_no_leaks(generator)


# ---------------------------------------------------------------------------
# chaos: stall mid-pipelined-step
# ---------------------------------------------------------------------------


class TestChaosStallPipelined:
    def test_stall_midpipeline_requeues_with_residual_deadline(self, params):
        """Wedge a step while the pipeline holds dispatched-ahead work:
        the supervisor must restart, requeue the survivor with its
        ORIGINAL deadline still attached (residual budget, not a reset),
        and the pool must audit clean afterwards."""
        from operator_tpu.utils.faultinject import OK, FaultPlan, sleep_

        generator = make_generator(params)
        sched = make_sched(generator, pipeline_depth=2, spec_decode=True)
        policy = SupervisorPolicy(stall_timeout_s=120.0, join_grace_s=2.0)
        engine = ServingEngine(generator, scheduler=sched, supervisor=policy)

        async def scenario():
            await engine.start()
            await engine.generate(
                "warm", SamplingParams(max_tokens=2, temperature=0.0,
                                       stop_on_eos=False),
            )
            policy.stall_timeout_s = 0.4
            plan = FaultPlan(seed=13)
            plan.rule("engine.step", [OK, OK, sleep_(1.5)])
            generator.fault_plan = plan
            deadline = generator._clock() + 60.0  # generous residual
            result = await asyncio.wait_for(
                engine.generate(
                    "stalled while dispatched ahead then requeued",
                    SamplingParams(max_tokens=12, temperature=0.0,
                                   stop_on_eos=False, deadline=deadline),
                ),
                30,
            )
            generator.fault_plan = None
            assert plan.pending() == {}, plan.pending()
            await engine.close()
            return result

        result = asyncio.run(scenario())
        assert result.completion_tokens == 12
        counters = generator.metrics.snapshot()["counters"]
        assert counters.get("supervisor_restart") == 1
        assert counters.get("supervisor_requeue") == 1
        assert not counters.get("supervisor_gaveup")
        assert not counters.get("supervisor_leak")
        assert_no_leaks(generator)


# ---------------------------------------------------------------------------
# streaming under pipelined commits
# ---------------------------------------------------------------------------


class TestStreamingOrder:
    def test_partials_strictly_extend_per_request(self, params):
        """Each request's partial snapshots must strictly extend the
        previous one (no rewinds, no duplicates) even though commits now
        land from a pipeline — and the final snapshot must be a prefix
        of the result."""
        generator = make_generator(params)
        sched = make_sched(generator, pipeline_depth=2, spec_decode=True)
        engine = ServingEngine(generator, scheduler=sched)

        async def scenario():
            await engine.start()
            streams: dict[str, list[list[int]]] = {p: [] for p in PROMPTS}
            sampling = SamplingParams(max_tokens=10, temperature=0.0,
                                      stop_on_eos=False)

            def collect(prompt):
                return lambda ids: streams[prompt].append(list(ids))

            results = await asyncio.gather(*[
                engine.generate(p, sampling, on_partial=collect(p))
                for p in PROMPTS
            ])
            await asyncio.sleep(0.05)
            await engine.close()
            return streams, results

        streams, results = asyncio.run(scenario())
        for prompt, result in zip(PROMPTS, results):
            snapshots = streams[prompt]
            assert snapshots, f"no partials for {prompt!r}"
            for earlier, later in zip(snapshots, snapshots[1:]):
                assert len(later) > len(earlier), "stream rewound"
                assert later[: len(earlier)] == earlier, "stream reordered"
            final = snapshots[-1]
            assert result.token_ids[: len(final)] == final
        assert_no_leaks(generator)

    @pytest.mark.parametrize("depth", [1, 2])
    def test_every_partial_the_stream_saw_is_a_delivery_on_the_step_clock(
        self, params, depth
    ):
        """The records count the snapshots that reached the event loop
        (``delivered``) and their age since their commit began; over a run
        with no cancellation that is every partial the streams saw, and
        every snapshot the commits handed over (``wake_rows``), in one
        hand-over a commit at most (``wakeups``)."""
        generator = make_generator(params)
        sched = make_sched(generator, pipeline_depth=depth)
        engine = ServingEngine(generator, scheduler=sched)

        async def scenario():
            await engine.start()
            seen = []
            sampling = SamplingParams(max_tokens=10, temperature=0.0,
                                      stop_on_eos=False)
            await asyncio.gather(*[
                engine.generate(p, sampling, on_partial=seen.append)
                for p in PROMPTS
            ])
            await asyncio.sleep(0.05)
            await engine.close()
            return len(seen)

        seen = asyncio.run(scenario())
        clock = generator.step_clock
        records = clock.ring.records()
        # what the loop took after the last commit is still with the clock
        delivered = sum(r.delivered for r in records) + len(clock.lags)
        assert delivered == seen == sum(r.wake_rows for r in records) > 0
        for r in records:
            assert r.wakeups <= 1
            assert 0.0 <= r.deliver_lag_max_ms <= r.deliver_lag_ms
            assert r.wake_ms <= r.commit_ms
            if r.delivered:
                assert r.deliver_lag_ms / r.delivered <= r.deliver_lag_max_ms
        assert any(r.deliver_lag_ms > 0.0 for r in records)

    def test_one_hand_over_delivers_the_live_snapshots_in_commit_order(
        self, params
    ):
        """One hand-over holding a request whose future is done, a stale
        snapshot and a key restarted while the delivery was queued is ONE
        call onto the loop; it delivers the others in commit order, and
        every stream stays monotonic."""
        generator = make_generator(params)
        engine = ServingEngine(generator, scheduler=make_sched(generator))

        async def scenario():
            loop = asyncio.get_running_loop()
            engine._loop = loop
            scheduled = []
            real_call = loop.call_soon_threadsafe

            def counted(callback, *args):
                scheduled.append(callback)
                return real_call(callback, *args)

            loop.call_soon_threadsafe = counted
            heard = []

            def stream(name):
                return lambda ids: heard.append((name, list(ids)))

            for key in (1, 2, 3, 4, 5):
                engine._partial_cbs[key] = (stream(key), loop.create_future())
            engine._partial_cbs[2][1].cancel()  # the client went away
            engine._partial_sent[3] = 4  # that stream already holds 4 tokens
            engine._on_partial_from_worker(
                [(5, [7]), (1, [1, 2]), (2, [3]), (3, [4, 5]), (4, [6])]
            )
            # key 4 restarts: its fresh registration lands before the turn
            engine._partial_cbs[4] = (stream("4'"), loop.create_future())
            await asyncio.sleep(0)
            first = list(heard)
            engine._on_partial_from_worker(
                [(1, [1]), (5, [7, 8]), (3, [4, 5, 6, 7, 8]), (4, [9])]
            )
            await asyncio.sleep(0)
            return scheduled, first, heard

        scheduled, first, heard = asyncio.run(scenario())
        assert len(scheduled) == 2  # one call onto the loop a hand-over
        assert first == [(5, [7]), (1, [1, 2])]
        assert heard[2:] == [(5, [7, 8]), (3, [4, 5, 6, 7, 8]), ("4'", [9])]
        # the snapshots that crossed, stale or orphaned ones too, are
        # deliveries on the step clock; the cancelled one never crossed
        assert len(generator.step_clock.lags) == 4 + 4
        for name in {name for name, _ in heard}:
            stream = [ids for n, ids in heard if n == name]
            for earlier, later in zip(stream, stream[1:]):
                assert len(later) > len(earlier)
                assert later[: len(earlier)] == earlier

    def test_the_wave_loop_hands_over_once_a_block(self, params):
        """The wave engine's decode loop gathers its slots' snapshots and
        makes one hook call a processed block."""
        generator = make_generator(params)
        calls, blocks = [], []
        generator.partial_hook = lambda handed: calls.append(
            [(slot, list(ids)) for slot, ids in handed]
        )
        real_block = generator._process_block

        def counted(toks, snapshot):
            blocks.append(len(snapshot))
            return real_block(toks, snapshot)

        generator._process_block = counted
        slots = generator.admit(
            ["pod crashed with exit code 137", "three", "node not ready"],
            [SamplingParams(max_tokens=n, temperature=0.0, stop_on_eos=False)
             for n in (6, 9, 12)],
        )
        results = {}
        for _ in range(200):
            for slot, result in generator.step():
                results[slot] = result
            if len(results) == len(slots):
                break
        assert sorted(results) == sorted(slots)
        assert 0 < len(calls) <= len(blocks)
        assert all(calls)  # never an empty call
        assert max(len(handed) for handed in calls) == len(slots)
        for slot in slots:
            stream = [ids for handed in calls for s, ids in handed if s == slot]
            assert stream
            for earlier, later in zip(stream, stream[1:]):
                assert len(later) > len(earlier)
                assert later[: len(earlier)] == earlier
            assert results[slot].token_ids[: len(stream[-1])] == stream[-1]
