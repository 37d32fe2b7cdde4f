"""Step clock + fleet perf view (ISSUE 11 acceptance surface).

Covers the per-step attribution ring (bounds, eviction, monotonic
cumulative totals), the analytic flops/token model against hand-computed
TINY_TEST values, Prometheus-correct histogram exposition in both text
flavours plus /metrics.json, span/step-record agreement on the live
engine (the span's queue/prefill/decode numbers are COPIED from the step
clock, so they can never disagree), structural replay-identity of the
step sequence under a seeded fault plan, the fleet roll-up fed by faked
/healthz bodies behind the operator's token-gated ``GET /fleet``, and the
on-demand ``POST /profile`` capture.
"""

from __future__ import annotations

import asyncio
import json
import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from operator_tpu.models import TINY_TEST, init_params  # noqa: E402
from operator_tpu.models.tokenizer import ByteTokenizer  # noqa: E402
from operator_tpu.obs import Tracer  # noqa: E402
from operator_tpu.obs.steptrace import (  # noqa: E402
    HOST_PARTS,
    STEP_KINDS,
    StepRecord,
    StepRing,
    attribution,
    render_steps,
)
from operator_tpu.router import Replica  # noqa: E402
from operator_tpu.router.health import (  # noqa: E402
    HealthBoard,
    ReplicaLoad,
    fleet_rollup,
)
from operator_tpu.serving.engine import (  # noqa: E402
    BatchedGenerator,
    SamplingParams,
    ServingEngine,
)
from operator_tpu.serving.perf import (  # noqa: E402
    StepClock,
    flops_per_token,
    matmul_param_count,
    peak_tflops,
)
from operator_tpu.serving.sched import Scheduler  # noqa: E402
from operator_tpu.utils.timing import MetricsRegistry  # noqa: E402


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


def run(coro):
    return asyncio.run(coro)


def _decode_record(seq_tokens=4, gap=1.0, dev=2.0, xfer=1.0, kind="decode"):
    """A record whose interval is ``gap`` of host, ``dev`` of waiting on
    the device and ``xfer`` of token fetch."""
    return StepRecord(
        seq=0, kind=kind, tokens=seq_tokens, slots=2, occupancy=0.5,
        wall_ms=gap + dev + xfer, host_ms=gap, wait_ms=dev, xfer_ms=xfer,
    )


# ---------------------------------------------------------------------------
# the bounded ring
# ---------------------------------------------------------------------------


class TestStepRing:
    def test_bounded_eviction_keeps_newest(self):
        ring = StepRing(capacity=4)
        for i in range(10):
            ring.append(kind="decode", tokens=i, slots=1, occupancy=0.25,
                        wall_ms=3.0, wait_ms=1.0, xfer_ms=1.0)
        assert len(ring) == 4
        assert ring.evicted == 6
        records = ring.records()
        # the window holds the NEWEST records; seq keeps counting across
        # evictions so the timeline stays addressable
        assert [r.seq for r in records] == [6, 7, 8, 9]
        assert [r.tokens for r in records] == [6, 7, 8, 9]
        assert ring.records(last=2) == records[-2:]
        assert ring.records(last=0) == []

    def test_cumulative_totals_survive_eviction(self):
        ring = StepRing(capacity=2)
        for _ in range(5):
            ring.append(kind="decode", tokens=2, slots=1, occupancy=0.25,
                        wall_ms=4.0, wait_ms=2.0, xfer_ms=1.0)
        ring.append(kind="mixed", tokens=3, slots=2, occupancy=0.5,
                    wall_ms=4.0, wait_ms=4.0, xfer_ms=0.0)
        ring.append(kind="prefill", tokens=8, slots=1, occupancy=0.25,
                    wall_ms=8.0, wait_ms=8.0, xfer_ms=0.0)
        # 5 decode steps x 4ms + 1 mixed x 4ms, prefill excluded
        assert ring.decode_cum_ms == pytest.approx(24.0)
        assert ring.cum_tokens["decode"] == 10
        assert ring.cum_tokens["mixed"] == 3
        assert ring.cum_tokens["prefill"] == 8
        assert len(ring) == 2  # the window itself stayed bounded

    def test_reset_zeroes_everything(self):
        ring = StepRing(capacity=3)
        for _ in range(5):
            ring.append(kind="decode", tokens=1, slots=1, occupancy=0.25,
                        wall_ms=3.0, wait_ms=1.0, xfer_ms=1.0)
        ring.reset()
        assert len(ring) == 0
        assert ring.evicted == 0
        assert ring.decode_cum_ms == 0.0
        record = ring.append(kind="decode", tokens=1, slots=1, occupancy=0.25,
                             wall_ms=1.0, wait_ms=1.0, xfer_ms=0.0)
        assert record.seq == 0  # seq restarts with the new timeline

    def test_capacity_from_env(self, monkeypatch):
        monkeypatch.setenv("STEP_RING_CAPACITY", "7")
        assert StepRing(None).capacity == 7
        monkeypatch.setenv("STEP_RING_CAPACITY", "garbage")
        assert StepRing(None).capacity == 512  # default, never raises
        monkeypatch.delenv("STEP_RING_CAPACITY")
        assert StepRing(None).capacity == 512
        assert StepRing(capacity=9).capacity == 9  # explicit beats env

    def test_rejects_unknown_kind(self):
        with pytest.raises(ValueError, match="unknown step kind"):
            StepRing(capacity=2).append(
                kind="warmup", tokens=1, slots=1, occupancy=0.25,
                wall_ms=1.0, wait_ms=1.0, xfer_ms=0.0,
            )

    def test_record_dict_roundtrip(self):
        record = StepRecord(
            seq=3, kind="mixed", tokens=5, slots=2, occupancy=0.5,
            wall_ms=4.0, host_ms=1.25, wait_ms=2.5, xfer_ms=0.25, mfu=0.125,
        )
        parsed = StepRecord.from_dict(record.to_dict())
        assert parsed == record
        assert StepRecord.from_dict({}).kind == "decode"  # tolerant default

    def test_record_dict_roundtrip_carries_every_new_field(self):
        record = StepRecord(
            seq=9, kind="mixed", tokens=70, slots=3, occupancy=0.75,
            wall_ms=12.5, host_ms=2.0, wait_ms=10.0, xfer_ms=0.5,
            plan_ms=0.5, pack_ms=0.75, commit_ms=0.25, turn_ms=0.125,
            prefill_tokens=64, kv_pages_walked=11, kv_blocks_walked=4,
            q_tile_rows=80, accepted=3, cached_tokens=16, sampled_rows=20,
        )
        raw = record.to_dict()
        assert json.loads(json.dumps(raw)) == raw  # plain JSON
        assert StepRecord.from_dict(raw) == record
        assert record.total_ms == record.wall_ms == 12.5
        # a record of an engine that does not count leaves the keys out
        bare = _decode_record().to_dict()
        assert "kv_pages_walked" not in bare and "prefill_tokens" not in bare
        assert StepRecord.from_dict(bare).kv_pages_walked is None
        assert "q_tile_rows" not in bare
        assert StepRecord.from_dict(bare).q_tile_rows is None
        assert raw["kv_blocks_walked"] == 4 and "kv_blocks_walked" not in bare
        assert StepRecord.from_dict(bare).kv_blocks_walked is None
        assert raw["sampled_rows"] == 20 and "sampled_rows" not in bare
        assert StepRecord.from_dict(bare).sampled_rows is None


# ---------------------------------------------------------------------------
# attribution + the analytic flops model
# ---------------------------------------------------------------------------


class TestAttribution:
    def test_fractions_sum_to_one(self):
        records = [
            _decode_record(gap=1.0, dev=5.0, xfer=0.5),
            _decode_record(gap=2.5, dev=1.0, xfer=0.25, kind="mixed"),
            _decode_record(gap=0.0, dev=8.0, xfer=0.0, kind="prefill"),
        ]
        out = attribution(records)
        fractions = out["fractions"]
        assert sum(fractions.values()) == pytest.approx(1.0, abs=0.02)
        assert out["steps"] == 3
        assert out["prefill_steps"] == 1
        assert out["decode_steps"] == 1
        assert out["mixed_steps"] == 1

    def test_empty_window_degrades_to_none(self):
        out = attribution([])
        assert out["steps"] == 0
        assert out["fractions"]["host"] is None
        assert out["decode_mfu"] is None
        assert out["occupancy_avg"] is None

    def test_decode_mfu_hand_value(self):
        """4 tokens x 1000 flops over 4ms = 1e6 flop/s = 1e-6 TFLOP/s;
        against a 1.0-TFLOP/s peak that is an MFU of 1e-6.  The prefill
        record must not enter the decode window."""
        records = [
            _decode_record(seq_tokens=4, gap=1.0, dev=2.0, xfer=1.0),
            _decode_record(seq_tokens=64, gap=0.0, dev=50.0, xfer=0.0,
                           kind="prefill"),
        ]
        out = attribution(records, flops_per_token=1000.0, peak_tflops=1.0)
        assert out["achieved_tflops"] == pytest.approx(1e-6)
        assert out["decode_mfu"] == pytest.approx(1e-6)


class TestFlopsModel:
    def test_tiny_model_hand_value(self):
        """The analytic matmul-weight count, written out by hand from the
        TINY_TEST config so a model-shape change breaks loudly."""
        c = TINY_TEST
        q = c.num_heads * c.head_dim
        kv = c.num_kv_heads * c.head_dim
        attn = c.hidden_size * q + 2 * c.hidden_size * kv + q * c.hidden_size
        mlp = 3 * c.hidden_size * c.intermediate_size
        expected = c.num_layers * (attn + mlp) + c.hidden_size * c.vocab_size
        assert matmul_param_count(c) == expected == 593920
        assert flops_per_token(c) == 2.0 * expected == 1187840.0

    def test_peak_table_is_keyed_by_device_kind(self, monkeypatch):
        v5e = "TPU v5 lite"
        assert peak_tflops(v5e, "bf16") == peak_tflops(v5e, "bfloat16") == 197.0
        # weight-only int8 multiplies in bf16 (models/quant.py mm): judged
        # against the bf16 row, not the chip's int8 peak
        assert peak_tflops(v5e, "int8") == 197.0
        # no assumed numbers: an unknown device or dtype has no peak
        assert peak_tflops("cpu", "bf16") is None
        assert peak_tflops("TPU v9 imaginary", "bf16") is None
        assert peak_tflops(v5e, "float32") is None
        # ... and no env override can invent one
        monkeypatch.setenv("PEAK_TFLOPS", "123.5")
        monkeypatch.setenv("BENCH_PEAK_TFLOPS", "123.5")
        assert peak_tflops(v5e, "bf16") == 197.0
        assert peak_tflops("cpu", "bf16") is None


class _FakeClock:
    """Seconds that move only when told to (``StepClock.now``)."""

    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, ms):
        self.t += ms / 1e3


def _fake_clock(**kw):
    clock = StepClock(capacity=8, max_slots=kw.pop("max_slots", 1), **kw)
    clock.now = _FakeClock()
    return clock


def _one_step(clock, *, host=1.0, wait=2.0, xfer=1.0, kind="decode",
              tokens=1, slots=1, busy=True, **counts):
    """One ``step()`` as the loops drive the clock: ``host`` ms of
    planning, then ``wait`` and ``xfer``, commit, leave."""
    clock.enter()
    clock.now.advance(host)
    clock.begin("wait")
    clock.now.advance(wait)
    clock.begin("xfer")
    clock.now.advance(xfer)
    clock.begin("commit")
    record = clock.observe(kind=kind, tokens=tokens, slots=slots, **counts)
    clock.leave(busy=busy)
    return record


class TestStepClock:
    def test_mfu_on_decode_records_only(self):
        clock = _fake_clock(flops_per_token=1000.0, peak_tflops=1.0,
                            max_slots=4)
        prefill = _one_step(clock, host=0.0, wait=10.0, xfer=0.0,
                            kind="prefill", tokens=16)
        assert prefill.mfu is None
        decode = _one_step(clock, host=1.0, wait=2.0, xfer=1.0, tokens=4,
                           slots=2)
        assert decode.wall_ms == pytest.approx(4.0)
        assert decode.mfu == pytest.approx(1e-6)
        assert decode.occupancy == pytest.approx(0.5)
        summary = clock.summary()
        assert summary["decode_mfu"] == pytest.approx(1e-6)

    def test_interval_runs_from_the_previous_commit(self):
        clock = _fake_clock()
        first = _one_step(clock, host=1.0, wait=2.0, xfer=0.5)
        # the first interval starts at its own step()'s start
        assert first.wall_ms == pytest.approx(3.5)
        assert (first.host_ms, first.plan_ms, first.turn_ms) == (
            pytest.approx(1.0), pytest.approx(1.0), 0.0,
        )
        clock.now.advance(5.0)  # the event loop's turn, work pending
        second = _one_step(clock, host=1.0, wait=2.0, xfer=0.5)
        assert second.turn_ms == pytest.approx(5.0)
        assert second.host_ms == pytest.approx(6.0)  # turn + plan
        assert second.wall_ms == pytest.approx(8.5)
        clock.reset()
        third = _one_step(clock, host=1.0, wait=1.0, xfer=0.0)
        assert third.wall_ms == pytest.approx(2.0)  # reset forgets the commit
        assert third.seq == 0

    def test_an_idle_engine_adds_nothing_to_host_ms(self):
        clock = _fake_clock()
        _one_step(clock, busy=False)  # the last request finished here
        clock.now.advance(60_000.0)  # a minute with nothing to do
        record = _one_step(clock, host=1.0, wait=2.0, xfer=1.0)
        assert record.wall_ms == pytest.approx(4.0)
        assert record.host_ms == pytest.approx(1.0)
        assert record.turn_ms == 0.0
        assert clock.ring.cum_ms["decode"] == pytest.approx(8.0)

    @pytest.mark.parametrize("wait,xfer", [(2.0, 1.0), (0.0, 0.0), (50.0, 9.0)])
    def test_host_wait_and_xfer_sum_to_the_wall(self, wait, xfer):
        clock = _fake_clock()
        for _ in range(3):
            clock.now.advance(0.75)
            record = _one_step(clock, host=1.25, wait=wait, xfer=xfer)
            assert record.host_ms + record.wait_ms + record.xfer_ms == (
                pytest.approx(record.wall_ms)
            )
            assert record.host_ms >= 0 and record.wait_ms >= 0
        # ... and the ring never lets the two waits exceed the wall
        odd = clock.ring.append(kind="decode", tokens=1, slots=1,
                                occupancy=1.0, wall_ms=1.0, wait_ms=5.0,
                                xfer_ms=5.0)
        assert (odd.host_ms, odd.wait_ms, odd.xfer_ms) == (0.0, 1.0, 0.0)

    def test_a_step_observed_on_an_idle_clock_stands_alone(self):
        """The wave engine's admission prefill runs between ``step()``
        calls: on an idle clock its record's wall is its own wait."""
        clock = _fake_clock()
        _one_step(clock, busy=False)
        clock.now.advance(1000.0)
        alone = clock.observe(kind="prefill", tokens=8, slots=1, wait_ms=7.0)
        assert alone.wall_ms == alone.wait_ms == pytest.approx(7.0)
        clock.now.advance(2.0)
        record = _one_step(clock, host=1.0, wait=1.0, xfer=0.0)
        assert record.turn_ms == pytest.approx(2.0)
        assert record.wall_ms == pytest.approx(4.0)

    def test_feeds_step_histograms(self):
        metrics = MetricsRegistry()
        clock = _fake_clock(metrics=metrics)
        for _ in range(3):
            _one_step(clock, host=2.0, wait=3.0, xfer=1.0)
        duration = metrics.histogram("step_duration_milliseconds")
        gap = metrics.histogram("step_host_gap_milliseconds")
        assert duration is not None and duration.count == 3
        assert duration.sum == pytest.approx(18.0)  # the walls
        assert gap is not None and gap.count == 3
        assert gap.sum == pytest.approx(6.0)  # the host's part of them


# ---------------------------------------------------------------------------
# histogram exposition: classic text, OpenMetrics, /metrics.json
# ---------------------------------------------------------------------------


class TestHistogramExposition:
    def _registry(self):
        metrics = MetricsRegistry()
        for value in (0.4, 3.0, 30.0, 30.0, 9000.0):
            metrics.observe("step_duration_milliseconds", value)
        return metrics

    def test_classic_text_cumulative_buckets(self):
        text = self._registry().prometheus()
        assert "# TYPE podmortem_step_duration_milliseconds histogram" in text
        assert 'podmortem_step_duration_milliseconds_bucket{le="0.5"} 1' in text
        assert 'podmortem_step_duration_milliseconds_bucket{le="5"} 2' in text
        assert 'podmortem_step_duration_milliseconds_bucket{le="50"} 4' in text
        assert 'podmortem_step_duration_milliseconds_bucket{le="+Inf"} 5' in text
        assert "podmortem_step_duration_milliseconds_count 5" in text
        assert "podmortem_step_duration_milliseconds_sum 9063.400" in text

    def test_openmetrics_flavour_carries_same_histogram(self):
        text = self._registry().prometheus(openmetrics=True)
        assert 'podmortem_step_duration_milliseconds_bucket{le="+Inf"} 5' in text
        assert text.rstrip().endswith("# EOF")

    def test_metrics_json_snapshot(self):
        snapshot = self._registry().snapshot()
        hist = snapshot["histograms"]["step_duration_milliseconds"]
        assert hist["count"] == 5
        assert hist["sum"] == pytest.approx(9063.4)
        assert hist["buckets"]["+Inf"] == 5
        # cumulative monotonicity in the JSON twin too
        counts = [hist["buckets"][le] for le in hist["buckets"]]
        assert counts == sorted(counts)


# ---------------------------------------------------------------------------
# timeline rendering + the obs.view --steps CLI
# ---------------------------------------------------------------------------


class TestStepView:
    def test_render_steps_table(self):
        table = render_steps([
            _decode_record(),
            StepRecord(seq=1, kind="prefill", tokens=16, slots=1,
                       occupancy=0.25, wall_ms=9.0, host_ms=0.0, wait_ms=9.0,
                       xfer_ms=0.0, kv_pages_walked=7, kv_blocks_walked=2,
                       prefill_tokens=16, q_tile_rows=64, state_rows=1,
                       sampled_rows=20, passes=4, block_rows=3, unmasked_tokens=6,
                       commit_tokens=8, moe_tokens=16, moe_experts_hit=31,
                       moe_assign_max=9, wakeups=1, wake_rows=5),
        ])
        lines = table.splitlines()
        assert lines[0].split() == [
            "seq", "kind", "tok", "pf_tok", "slots", "occ",
            "wall_ms", "host_ms", "wait_ms", "xfer_ms",
            "plan", "pack", "put", "launch", "commit", "turn",
            "wake", "wk_rows", "cpu", "proc", "gc", "comp", "lag",
            "blk_rows", "unmask", "cmt_tok", "moe_tok", "exp_hit", "exp_max", "passes",
            "st_rows", "smp_rows", "kv_pg", "pg_blk", "q_fill", "mfu",
        ]
        # the snapshots the commit's hand-over carried, beside its time
        assert lines[3].split()[17] == "5" and lines[2].split()[17] == "-"
        # a denoising step's rows, what they kept and its commit tokens; the
        # tokens routed to experts and the two counts the device brings back
        assert lines[3].split()[-13:-7] == ["3", "6", "8", "16", "31", "9"]
        assert lines[2].split()[-13:-7] == ["-"] * 6
        # passes a token took through the layer stack; "-" where not said
        assert lines[3].split()[-7] == "4" and lines[2].split()[-7] == "-"
        # slots whose recurrent state the step touched; "-" without such state
        assert lines[3].split()[-6] == "1" and lines[2].split()[-6] == "-"
        # logit rows the head and the sampler worked; "-" where not counted
        assert lines[3].split()[-5] == "20" and lines[2].split()[-5] == "-"
        assert lines[3].split()[-4] == "7" and lines[2].split()[-4] == "-"
        # pages a flash update folded in: 7 pages in 2 blocks
        assert lines[3].split()[-3] == "3.50" and lines[2].split()[-3] == "-"
        # q_fill = tokens / q_tile_rows: 16 of the chunk's 64 rows
        assert lines[3].split()[-2] == "0.250" and lines[2].split()[-2] == "-"
        assert lines[3].split()[3] == "16" and lines[2].split()[3] == "-"
        assert len(lines) == 4  # header + rule + 2 rows
        assert "prefill" in lines[3]

    def test_view_steps_cli(self, tmp_path, capsys):
        from operator_tpu.obs import view

        journal = tmp_path / "steps.jsonl"
        raw = _decode_record(seq_tokens=3).to_dict()
        blackbox = {"recordedAt": 1.0, "reason": "stall",
                    "extra": {"steps": [
                        StepRecord(seq=1, kind="mixed", tokens=2, slots=2,
                                   occupancy=0.5, wall_ms=2.0, host_ms=1.0,
                                   wait_ms=1.0, xfer_ms=0.0).to_dict()
                    ]}}
        journal.write_text(
            json.dumps(raw) + "\n"
            + "not json at all\n"      # skipped, never fatal
            + "42\n"                    # valid JSON, not an object
            + json.dumps(blackbox) + "\n"
        )
        assert view.main(["--steps", str(journal)]) == 0
        out = capsys.readouterr().out
        assert "kind" in out and "mixed" in out
        assert "2 steps" in out
        assert "host=" in out and "wait=" in out and "host parts (ms)" in out

    def test_view_steps_cli_empty(self, tmp_path, capsys):
        from operator_tpu.obs import view

        journal = tmp_path / "empty.jsonl"
        journal.write_text("")
        assert view.main(["--steps", str(journal)]) == 0
        assert "no step records" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# live engines: step records, span agreement, replay identity
# ---------------------------------------------------------------------------


class _ListRecorder:
    def __init__(self):
        self.traces = []

    def record(self, trace):
        self.traces.append(trace)


class TestEngineStepClock:
    def test_wave_engine_span_agrees_with_step_clock(self, params):
        generator = make_generator(params)
        engine = ServingEngine(generator)
        recorder = _ListRecorder()
        tracer = Tracer(recorder=recorder)

        async def scenario():
            await engine.start()
            with tracer.trace("analysis"):
                result = await engine.generate(
                    "pod failed with exit code 137",
                    SamplingParams(max_tokens=6, temperature=0.0,
                                   stop_on_eos=False),
                )
            load = engine.load_report()
            await engine.close()
            return result, load

        result, load = run(scenario())
        records = generator.step_clock.ring.records()
        kinds = {r.kind for r in records}
        assert kinds <= set(STEP_KINDS)
        assert "prefill" in kinds and "decode" in kinds
        # fractions total 1.0 by construction
        summary = generator.step_clock.summary()
        assert sum(summary["fractions"].values()) == pytest.approx(1.0, abs=0.02)
        # the analytic flops model rode along (a tiny model on the CPU
        # legitimately rounds to 0.0) — but the CPU has no row in the
        # peak table, so no MFU is made up for it
        assert summary["achieved_tflops"] is not None
        assert summary["decode_mfu"] is None
        # the ONLY request on a fresh clock decoded the whole decode
        # window, so its decode_ms IS the cumulative decode wall
        assert result.decode_ms == pytest.approx(
            generator.step_clock.decode_cum_ms
        )
        # span timings are copied from the same clock — byte-equal after
        # the span's own rounding (the satellite-2 agreement contract)
        [trace] = recorder.traces
        span = next(s for s in trace.spans if s.name == "engine.generate")
        assert span.attributes["decode_ms"] == round(result.decode_ms, 3)
        assert span.attributes["prefill_ms"] == round(result.prefill_ms, 3)
        assert span.attributes["queue_wait_ms"] == round(result.queue_wait_ms, 3)
        # latency histograms fed from the same numbers
        histograms = generator.metrics.snapshot()["histograms"]
        for name in ("queue_wait_milliseconds", "ttft_milliseconds",
                     "token_latency_milliseconds",
                     "step_duration_milliseconds",
                     "step_host_gap_milliseconds"):
            assert histograms[name]["count"] >= 1, name
        # /healthz load report carries the step summary for /fleet
        assert load.steps == summary["steps"] > 0
        assert load.decode_mfu == summary["decode_mfu"]
        assert load.occupancy is not None

    def test_sched_engine_records_and_queue_wait(self, params):
        generator = make_generator(params)
        sched = Scheduler(generator, chunk=16, token_budget=32)
        engine = ServingEngine(generator, scheduler=sched)

        async def scenario():
            await engine.start()
            sampling = SamplingParams(max_tokens=5, temperature=0.0,
                                      stop_on_eos=False)
            results = await asyncio.gather(
                engine.generate("one", sampling),
                engine.generate("a longer second prompt", sampling),
                engine.generate("three", sampling),
            )
            await engine.close()
            return results

        results = run(scenario())
        records = generator.step_clock.ring.records()
        kinds = {r.kind for r in records}
        assert kinds <= set(STEP_KINDS)
        assert kinds & {"decode", "mixed"}  # decode-bearing steps recorded
        summary = generator.step_clock.summary()
        assert sum(summary["fractions"].values()) == pytest.approx(1.0, abs=0.02)
        for result in results:
            assert result.completion_tokens > 0
            assert result.decode_ms > 0.0
            assert result.queue_wait_ms >= 0.0
        # the continuous loop feeds the same queue-wait histogram
        histograms = generator.metrics.snapshot()["histograms"]
        assert histograms["queue_wait_milliseconds"]["count"] >= 3
        assert histograms["step_duration_milliseconds"]["count"] == len(records)


# ---------------------------------------------------------------------------
# the continuous scheduler on an injected clock and a fake device
# ---------------------------------------------------------------------------

STEP_S = 0.100  # the fake device's time for one mixed program
TICK_S = 0.0002  # every read of the clock costs the host this much
TURN_S = 0.003  # the event loop's turn between two step() calls


class _TickingClock:
    """The injected ``StepClock.now``: time moves by ``TICK_S`` at every
    read (host work), and otherwise only when the test or the fake
    device says so."""

    def __init__(self):
        self.t = 50.0

    def __call__(self):
        self.t += TICK_S
        return self.t


class _DeviceHandle:
    """Stands in for the device array of sampled tokens: ready at
    ``ready_at``, so blocking on it moves the clock there."""

    def __init__(self, tokens, ready_at, clock):
        self._tokens, self._ready_at, self._clock = tokens, ready_at, clock

    def block_until_ready(self):
        self._clock.t = max(self._clock.t, self._ready_at)
        return self

    def __array__(self, dtype=None, copy=None):
        import numpy as np

        return np.asarray(self._tokens, dtype=dtype)


def _drive_on_fake_device(params, depth, requests, **sched_kw):
    """Run ``requests`` ([(prompt, max_tokens)]) to the end through the
    real mixed program, whose results a fake device hands back
    ``STEP_S`` after the later of their dispatch and the previous
    program's end.  Returns (generator, elapsed seconds with work,
    {req_id: (first token t, last token t, result)})."""
    costs = sched_kw.pop("costs", {})  # seconds the host spends in a part
    generator = make_generator(params, **sched_kw.pop("generator_kw", {}))
    sched = Scheduler(generator, chunk=16, token_budget=32,
                      pipeline_depth=depth, **sched_kw)
    sched.precompile()
    clock = _TickingClock()
    generator.step_clock.now = clock
    real = sched._get_fn()
    device = {"free_at": 0.0}

    def on_fake_device(*args):
        clock.t += costs.get("launch", 0.0)
        new_paged, toks, accept, latest, rng = real(*args)
        device["free_at"] = max(device["free_at"], clock.t) + STEP_S
        handle = _DeviceHandle(toks, device["free_at"], clock)
        return new_paged, handle, accept, latest, rng

    sched._fn = on_fake_device
    if "pack" in costs:
        real_pack = sched._pack

        def slow_pack(plan):
            clock.t += costs["pack"]
            return real_pack(plan)

        sched._pack = slow_pack
    if "put" in costs:
        class SlowPuts:
            """``jax.numpy`` whose every host-to-device put takes time."""

            def __getattr__(self, name):
                return getattr(jnp, name)

            def asarray(self, *args, **kw):
                clock.t += costs["put"]
                return jnp.asarray(*args, **kw)

        generator._jnp = SlowPuts()
    first, seen = {}, {}
    #: each call of the hook: the (req_id, token_ids) pairs it carried
    sched.handed = []

    def heard(handed):
        clock.t += costs.get("wake", 0.0)
        for req_id, _ in handed:
            first.setdefault(req_id, clock.t)
        sched.handed.append([(req_id, list(ids)) for req_id, ids in handed])

    sched.partial_hook = heard
    ids = [
        sched.enqueue(prompt, SamplingParams(
            max_tokens=n, temperature=0.0, stop_on_eos=False))
        for prompt, n in requests
    ]
    started = clock.t
    for _ in range(400):
        for outcome in sched.step():
            assert outcome.error is None
            seen[outcome.req_id] = (
                first.get(outcome.req_id, clock.t), clock.t, outcome.result,
            )
        if len(seen) == len(ids):
            break
        clock.t += TURN_S
    assert len(seen) == len(ids)
    return generator, clock.t - started, seen, sched


_FAKE_DEVICE_REQUESTS = [
    ("pod crashed with exit code 137", 12),
    ("a longer second prompt that takes two chunks of prefill", 9),
    ("three", 14),
]


class TestSchedulerOnAnHonestClock:
    @pytest.mark.parametrize("depth", [1, 2])
    def test_walls_tile_the_elapsed_time(self, params, depth):
        generator, elapsed_s, _, _ = _drive_on_fake_device(
            params, depth, _FAKE_DEVICE_REQUESTS
        )
        records = generator.step_clock.ring.records()
        assert len(records) > 10
        # no interval overlaps another and none is missing: the walls sum
        # to the time the engine had work (to within the reads of the
        # clock around the first and the last step() call)
        assert sum(r.wall_ms for r in records) == pytest.approx(
            elapsed_s * 1e3, abs=5 * TICK_S * 1e3
        )
        ring = generator.step_clock.ring
        assert sum(ring.cum_ms.values()) == pytest.approx(
            sum(r.wall_ms for r in records)
        )
        # the device sets the pace: a step's wall is its program's time,
        # at either depth, plus what the host adds when nothing overlaps
        steady = records[3:-3]
        host_serial = TURN_S + 30 * TICK_S
        for r in steady:
            if depth == 2:
                assert r.wall_ms == pytest.approx(STEP_S * 1e3, abs=1.0)
            else:
                assert STEP_S * 1e3 <= r.wall_ms <= (STEP_S + host_serial) * 1e3

    @pytest.mark.parametrize("depth", [1, 2])
    def test_every_record_splits_its_wall_three_ways(self, params, depth):
        generator, _, _, _ = _drive_on_fake_device(
            params, depth, _FAKE_DEVICE_REQUESTS
        )
        for r in generator.step_clock.ring.records():
            assert r.host_ms + r.wait_ms + r.xfer_ms == pytest.approx(r.wall_ms)
            # the six parts tile the host's time: no glue is left unnamed
            parts = sum(getattr(r, f"{part}_ms") for part in HOST_PARTS)
            assert 0 < parts == pytest.approx(r.host_ms, abs=1e-9)
            assert 0 <= r.prefill_tokens <= r.tokens
        steady = generator.step_clock.ring.records()[3:-3]
        # between two steps the loop took its turn while work was pending
        assert all(
            r.turn_ms == pytest.approx(TURN_S * 1e3, abs=3 * TICK_S * 1e3)
            for r in steady
        )
        if depth == 2:
            # the host hides under the device: most of the wall is slack
            assert all(r.wait_ms > 0.8 * r.wall_ms for r in steady)

    @pytest.mark.parametrize("depth", [1, 2])
    def test_decode_ms_is_last_token_less_first(self, params, depth):
        """At depth 2 the old stamps (dispatch -> ready, which spans two
        device steps) made this about twice the truth."""
        generator, _, seen, _ = _drive_on_fake_device(
            params, depth, _FAKE_DEVICE_REQUESTS
        )
        one_step_ms = (STEP_S + TURN_S) * 1e3 + 5.0
        for first_t, last_t, result in seen.values():
            truth_ms = (last_t - first_t) * 1e3
            assert truth_ms > 5 * STEP_S * 1e3
            assert result.decode_ms == pytest.approx(truth_ms, abs=one_step_ms)

    def test_admission_reads_the_steps_wall_as_seconds_per_token(self, params):
        """``decode_step`` clamps deadlines (admission.deadline_policy);
        at depth 2 it used to read dispatch -> fetch, two device steps."""
        generator, _, _, _ = _drive_on_fake_device(
            params, 2, [("pod crashed with exit code 137", 24)]
        )
        per_token_s = generator.decode_token_estimate_s()
        assert per_token_s == pytest.approx(STEP_S, rel=0.05)
        now = generator._clock()
        clamped, outcome = generator.deadline_policy(
            SamplingParams(max_tokens=64, deadline=now + 20.5 * STEP_S), now=now
        )
        assert outcome == "truncated"
        assert clamped.max_tokens in (19, 20)  # was ~10 at two steps a token

    def test_step_numbers_on_the_spans_are_the_records(self, params, monkeypatch):
        """plan/pack/dispatch spans carry the seq of the record their step
        will write, wait/commit the seq of the one being written."""
        spans = []
        real_annotation = BatchedGenerator._annotation

        def spy(self, name, params_list=None, **args):
            spans.append((name, dict(args)))
            return real_annotation(self, name, params_list, **args)

        monkeypatch.setattr(BatchedGenerator, "_annotation", spy)
        generator, _, _, _ = _drive_on_fake_device(
            params, 2, _FAKE_DEVICE_REQUESTS
        )
        records = {r.seq: r for r in generator.step_clock.ring.records()}
        names = {name for name, _ in spans}
        assert names == {
            "podmortem.sched.plan", "podmortem.sched.pack",
            "podmortem.sched.dispatch", "podmortem.sched.put",
            "podmortem.sched.launch", "podmortem.sched.wait",
            "podmortem.sched.commit",
        }
        dispatched = [a for name, a in spans if name == "podmortem.sched.dispatch"]
        assert [a["step"] for a in dispatched] == sorted(records)
        # the two halves of a dispatch open inside it, in order, with its step
        inside = [
            (name.rsplit(".", 1)[1], a["step"]) for name, a in spans
            if name.endswith((".dispatch", ".put", ".launch"))
        ]
        # (precompile's dispatch came first, under no span of the loop's)
        assert inside[:2] == [("put", 0), ("launch", 0)]
        assert inside[2:] == [
            (name, seq) for seq in sorted(records)
            for name in ("dispatch", "put", "launch")
        ]
        for args in dispatched:
            record = records[args["step"]]
            assert args["kv_pages"] == record.kv_pages_walked
            assert 0 < args["kv_blocks"] == record.kv_blocks_walked <= args["kv_pages"]
            assert args["qk_pairs"] >= args["tokens"] == record.tokens
            assert args["q_tile_rows"] == record.q_tile_rows >= record.tokens
        committed = [a["step"] for name, a in spans if name == "podmortem.sched.commit"]
        assert committed == sorted(records)


class TestThePartsOfHostMs:
    """The step clock names every part of ``host_ms`` (PR 38)."""

    COSTS = {"pack": 0.007, "put": 0.0005, "launch": 0.002, "wake": 0.0004}

    @pytest.mark.parametrize("depth", [1, 2])
    def test_six_parts_sum_to_host_ms(self, params, depth):
        generator, _, _, _ = _drive_on_fake_device(
            params, depth, _FAKE_DEVICE_REQUESTS, costs=self.COSTS
        )
        records = generator.step_clock.ring.records()
        assert len(records) > 10
        for r in records:
            parts = [getattr(r, f"{part}_ms") for part in HOST_PARTS]
            assert all(ms >= 0.0 for ms in parts)
            assert sum(parts) == pytest.approx(r.host_ms, abs=1e-9)
            assert r.host_ms + r.wait_ms + r.xfer_ms == pytest.approx(r.wall_ms)

    @pytest.mark.parametrize("depth", [1, 2])
    def test_pack_no_longer_holds_the_puts_nor_the_launch(self, params, depth):
        generator, _, _, _ = _drive_on_fake_device(
            params, depth, _FAKE_DEVICE_REQUESTS, costs=self.COSTS
        )
        few_ticks = 6 * TICK_S * 1e3
        for r in generator.step_clock.ring.records()[3:-3]:
            # one _pack a step; thirteen packed arrays at least go over
            assert r.pack_ms == pytest.approx(7.0, abs=few_ticks)
            assert r.put_ms >= 13 * 0.5
            assert r.put_ms == pytest.approx(13 * 0.5, abs=2 * 0.5 + few_ticks)
            # the compiled step's call and the bookkeeping after it
            assert r.launch_ms == pytest.approx(2.0, abs=few_ticks)

    def test_wake_is_inside_commit_and_counts_the_hooks_calls(self, params):
        generator, _, _, sched = _drive_on_fake_device(
            params, 2, _FAKE_DEVICE_REQUESTS, costs=self.COSTS
        )
        records = generator.step_clock.ring.records()
        assert sum(r.wakeups for r in records) == len(sched.handed) > 10
        for r in records:
            assert r.wake_ms <= r.commit_ms
            # a call costs what the hook took and the two reads around it
            assert r.wake_ms == pytest.approx(
                r.wakeups * (0.4 + TICK_S * 1e3), abs=1e-6
            )

    @pytest.mark.parametrize("depth", [1, 2])
    def test_one_hand_over_a_commit_carries_every_row_that_streamed(
        self, params, depth
    ):
        """The commit hands its streaming rows to the event loop in ONE
        call: ``wakeups`` is at most 1 a record, ``wake_rows`` the rows
        that call carried, and a row streams at every commit that gave it
        a token and did not finish it."""
        generator, _, seen, sched = _drive_on_fake_device(
            params, depth, _FAKE_DEVICE_REQUESTS, costs=self.COSTS
        )
        records = generator.step_clock.ring.records()
        assert all(r.wakeups in (0, 1) for r in records)
        assert all(r.wake_rows == 0 for r in records if not r.wakeups)
        assert [r.wake_rows for r in records if r.wakeups] == [
            len(handed) for handed in sched.handed
        ]
        assert all(handed for handed in sched.handed)  # never an empty call
        # several rows streamed in one commit, and went over in one call
        assert max(r.wake_rows for r in records) == len(_FAKE_DEVICE_REQUESTS)
        lengths = {}
        for handed in sched.handed:
            ids = [req_id for req_id, _ in handed]
            assert len(set(ids)) == len(ids)
            for req_id, tokens in handed:
                lengths.setdefault(req_id, []).append(len(tokens))
        for req_id, (_, _, result) in seen.items():
            # one token a commit, streamed at each but the last
            assert lengths[req_id] == list(range(1, len(result.token_ids)))

    def test_parts_tile_after_an_idle_spell(self):
        clock = _fake_clock()
        _one_step(clock, busy=False)  # the last request finished here
        clock.now.advance(60_000.0)  # a minute with nothing to do
        clock.enter()
        clock.now.advance(0.5)  # plan
        clock.begin("pack")
        clock.now.advance(1.5)
        clock.begin("put")
        clock.now.advance(0.25)
        clock.begin("launch")
        clock.now.advance(0.75)
        clock.begin("wait")
        clock.now.advance(9.0)
        clock.begin("xfer")
        clock.now.advance(0.125)
        clock.begin("commit")
        clock.now.advance(2.0)
        record = clock.observe(kind="decode", tokens=1, slots=1)
        assert (record.plan_ms, record.pack_ms, record.put_ms, record.launch_ms,
                record.commit_ms, record.turn_ms) == (
            pytest.approx(0.5), pytest.approx(1.5), pytest.approx(0.25),
            pytest.approx(0.75), pytest.approx(2.0), 0.0,
        )
        assert record.host_ms == pytest.approx(5.0)
        assert (record.wait_ms, record.xfer_ms) == (
            pytest.approx(9.0), pytest.approx(0.125)
        )
        # the commit runs on to the next stamp: the glue after the record
        # is the next interval's commit, then the loop's turn, then plan
        clock.now.advance(0.3)
        clock.leave(busy=True)
        clock.now.advance(4.0)
        following = _one_step(clock, host=1.0, wait=2.0, xfer=0.0)
        assert (following.commit_ms, following.turn_ms, following.plan_ms) == (
            pytest.approx(0.3), pytest.approx(4.0), pytest.approx(1.0),
        )
        assert following.host_ms == pytest.approx(5.3)

    def test_a_wait_the_loop_timed_itself_comes_out_of_its_part(self):
        """The wave engine's admission prefill hands its own wait in."""
        clock = _fake_clock()
        _one_step(clock)
        clock.enter()
        clock.now.advance(8.0)  # planning, 6 ms of it a prefill's compute
        record = clock.observe(kind="prefill", tokens=8, slots=1, wait_ms=6.0)
        assert (record.wait_ms, record.host_ms, record.plan_ms) == (
            pytest.approx(6.0), pytest.approx(2.0), pytest.approx(2.0),
        )


class _FakeCpu:
    """A CPU clock (seconds) that moves only when told to."""

    def __init__(self):
        self.t = 5.0

    def __call__(self):
        return self.t


class TestWhatElseTheProcessDid:
    def test_cpu_time_leaves_out_a_wait_that_spins(self):
        clock = _fake_clock()
        thread, process = _FakeCpu(), _FakeCpu()
        clock.thread_cpu, clock.process_cpu = thread, process
        clock.enter()
        clock.now.advance(3.0)
        thread.t += 0.002  # the worker worked 2 of its 3 ms
        process.t += 0.005
        clock.begin("wait")
        clock.now.advance(10.0)
        thread.t += 0.009  # a wait that spins is not work
        process.t += 0.030
        clock.begin("xfer")
        clock.begin("commit")
        clock.now.advance(1.0)
        thread.t += 0.001
        process.t += 0.001
        record = clock.observe(kind="decode", tokens=1, slots=1)
        assert record.cpu_ms == pytest.approx(3.0)
        assert record.proc_cpu_ms == pytest.approx(36.0)
        # 4 ms of host time, 3 of them on the CPU: 1 ms it was not running
        assert record.host_ms + record.xfer_ms - record.cpu_ms == pytest.approx(1.0)
        clock.leave(busy=True)
        following = _one_step(clock)
        assert following.cpu_ms == following.proc_cpu_ms == 0.0

    def test_a_forced_collection_lands_in_its_interval_alone(self):
        import gc

        from operator_tpu.serving.perf import GcWatch

        metrics = MetricsRegistry()
        clock = _fake_clock(metrics=metrics)
        clock.gc_watch = GcWatch().install()
        was_enabled = gc.isenabled()
        gc.disable()  # only the forced collection runs
        try:
            before = _one_step(clock)
            clock.enter()
            gc.collect()  # the oldest generation
            gc.collect(0)  # and a young one
            hit = clock.observe(kind="decode", tokens=1, slots=1)
            clock.leave(busy=True)
            after = _one_step(clock)
        finally:
            if was_enabled:
                gc.enable()
            clock.gc_watch.remove()
        assert hit.gc_ms > 0.0 and hit.gc_gen2 == 1
        assert hit.gc_ms == pytest.approx(clock.gc_watch.pause_ms)
        for other in (before, after):
            assert other.gc_ms == 0.0 and other.gc_gen2 == 0
        pauses = metrics.histogram("gc_pause_milliseconds")
        assert pauses.count == 2 and pauses.sum == pytest.approx(hit.gc_ms)
        assert clock.gc_watch not in gc.callbacks

    def test_compile_ms_follows_a_compile_watcher_event(self):
        import logging

        from operator_tpu.utils.compilewatch import CompileWatcher

        clock = _fake_clock()
        clock.compile_watch = watcher = CompileWatcher()
        try:
            before = _one_step(clock)
            clock.enter()
            jax_log = logging.getLogger("jax")
            jax_log.warning("Compiling jit(cascade) with global shapes and types []")
            jax_log.warning("Finished XLA compilation of jit(cascade) in 0.25 sec")
            hit = clock.observe(kind="decode", tokens=1, slots=1)
            clock.leave(busy=True)
            after = _one_step(clock)
        finally:
            watcher.close()
        assert watcher.compile_seconds == pytest.approx(0.25)
        assert hit.compile_ms == pytest.approx(250.0)
        assert before.compile_ms == after.compile_ms == 0.0

    def test_the_collectors_hook_is_gone_after_close(self, params):
        import gc

        generator = make_generator(params)
        engine = ServingEngine(
            generator, scheduler=Scheduler(generator, chunk=16, token_budget=32)
        )
        hooks_before = list(gc.callbacks)

        async def scenario():
            await engine.start()
            await engine.start()  # a supervised restart starts it again
            installed = [h for h in gc.callbacks if h not in hooks_before]
            assert installed == [generator.step_clock.gc_watch]
            await engine.generate("one", SamplingParams(
                max_tokens=4, temperature=0.0, stop_on_eos=False))
            gc.collect()
            await engine.close()

        run(scenario())
        assert gc.callbacks == hooks_before
        assert generator.step_clock.gc_watch.gen2 >= 1

    def test_deliveries_land_in_the_interval_the_loop_took_them_in(self):
        clock = _fake_clock()
        _one_step(clock)
        clock.enter()
        clock.begin("commit")
        committed = clock.mark  # what the worker's hook hands over
        clock.now.advance(1.0)
        clock.delivered(committed)  # the event loop's side
        clock.now.advance(2.0)
        clock.delivered(committed)
        record = clock.observe(kind="decode", tokens=2, slots=2)
        assert record.delivered == 2
        assert record.deliver_lag_ms == pytest.approx(4.0)
        assert record.deliver_lag_max_ms == pytest.approx(3.0)
        clock.leave(busy=True)
        following = _one_step(clock)
        assert (following.delivered, following.deliver_lag_ms) == (0, 0.0)


class TestStalls:
    def _steps(self, clock, count, **kw):
        return [_one_step(clock, **kw) for _ in range(count)]

    def test_a_200ms_interval_among_10ms_ones_is_kept_and_named(self, tmp_path, capsys):
        from operator_tpu.obs import view
        from operator_tpu.obs.steptrace import grown_part, render_stalls

        metrics = MetricsRegistry()
        clock = _fake_clock(metrics=metrics)  # a ring of 8
        # nothing is a stall until 64 walls say what is usual
        early = _one_step(clock, host=2.0, wait=197.0, xfer=1.0)
        assert not early.stall
        ordinary = self._steps(clock, 70, host=2.0, wait=7.0, xfer=1.0)
        # four times the median, but under 50 ms: slow, not a stall
        slow = _one_step(clock, host=2.0, wait=37.0, xfer=1.0)
        assert not slow.stall and not any(r.stall for r in ordinary)
        stalled = _one_step(clock, host=2.0, wait=197.0, xfer=1.0)
        assert stalled.stall and stalled.wall_ms == pytest.approx(200.0)
        later = self._steps(clock, 10, host=2.0, wait=7.0, xfer=1.0)
        assert not any(r.stall for r in later)
        # the ring evicted it; the clock kept it
        assert stalled not in clock.ring.records()
        assert list(clock.stalls) == [stalled]
        assert metrics.counter("step_stall") == 1
        summary = clock.summary()
        assert (summary["stalls"], summary["stalls_kept"]) == (0, 1)
        assert summary["last_stall"]["seq"] == stalled.seq
        assert grown_part(stalled, later) == "wait"
        # a dump of the kept stall beside ordinary steps, as obs.view reads it
        journal = tmp_path / "steps.jsonl"
        journal.write_text("".join(
            json.dumps(r.to_dict()) + "\n" for r in [*later, stalled]
        ))
        assert view.main(["--stalls", str(journal)]) == 0
        out = capsys.readouterr().out
        assert "1 stalls in 11 steps" in out
        [row] = [line for line in out.splitlines() if line.lstrip().startswith(
            str(stalled.seq))]
        assert row.split()[2:5] == ["200.000", "wait", "197.000"]
        assert view.main(["--steps", str(journal)]) == 0
        marked = [line for line in capsys.readouterr().out.splitlines()
                  if line.lstrip().startswith(f"{stalled.seq}*")]
        assert len(marked) == 1
        assert render_stalls(later).count("\n") == 1  # header and rule alone

    def test_the_part_that_grew_is_the_one_furthest_over_its_median(self):
        from operator_tpu.obs.steptrace import grown_part

        def record(**parts):
            host = sum(parts.values())
            return StepRecord(seq=0, kind="decode", tokens=1, slots=1,
                              occupancy=1.0, wall_ms=host + 5.0, host_ms=host,
                              wait_ms=5.0, xfer_ms=0.0, **parts)

        usual = [record(plan_ms=1.0, commit_ms=8.0, turn_ms=2.0)] * 5
        # commit is the largest part, turn the one that grew
        stalled = record(plan_ms=1.5, commit_ms=30.0, turn_ms=120.0)
        assert grown_part(stalled, usual) == "turn"
        assert grown_part(record(plan_ms=90.0, commit_ms=9.0), usual) == "plan"

    def test_a_reset_forgets_the_stalls_and_what_is_usual(self):
        clock = _fake_clock()
        self._steps(clock, 64, host=2.0, wait=7.0, xfer=1.0)
        assert _one_step(clock, host=2.0, wait=300.0, xfer=0.0).stall
        clock.reset()
        assert not clock.stalls
        assert not _one_step(clock, host=2.0, wait=300.0, xfer=0.0).stall

    def test_a_dump_from_before_the_fields_still_loads(self):
        old = {"seq": 3, "kind": "decode", "tokens": 4, "slots": 2,
               "occupancy": 0.5, "wall_ms": 4.0, "host_ms": 1.0, "wait_ms": 2.0,
               "xfer_ms": 1.0, "plan_ms": 0.5, "pack_ms": 0.25,
               "commit_ms": 0.125, "turn_ms": 0.0, "accepted": 4}
        record = StepRecord.from_dict(old)
        assert (record.put_ms, record.launch_ms, record.wake_ms, record.cpu_ms,
                record.gc_ms, record.compile_ms, record.deliver_lag_ms) == (0.0,) * 7
        assert (record.wakeups, record.wake_rows, record.delivered,
                record.gc_gen2) == (None,) * 4
        assert not record.stall and "stall" not in record.to_dict()
        new = StepRecord.from_dict({**old, "put_ms": 0.75, "wakeups": 1,
                                    "wake_rows": 128, "delivered": 120,
                                    "stall": True})
        assert StepRecord.from_dict(new.to_dict()) == new
        assert new.to_dict()["stall"] is True


def _walk_by_the_references_rule(kv_len, q_count, page_size, window=None):
    """Pages and query-key pairs by ``ragged_attention_reference``'s own
    mask, written out position by position: a page is walked when any
    live query of the row may attend to a position on it."""
    import numpy as np

    pages = pairs = 0
    for kv, count in zip(kv_len.tolist(), q_count.tolist()):
        if count <= 0:
            continue  # rows without queries produce garbage nobody reads
        kv_pos = np.arange(max(kv, 1))[None, :]
        q_pos = (kv - count + np.arange(count))[:, None]
        mask = (kv_pos <= q_pos) & (kv_pos < kv)
        if window is not None:
            mask &= kv_pos > q_pos - window
        touched = np.unique(np.nonzero(mask.any(axis=0))[0] // page_size)
        pages += len(touched)
        # the kernel scores every query against every position of the
        # pages it walks, up to kv_len
        first = touched.min() * page_size if len(touched) else 0
        pairs += count * (kv - first)
    return pages, pairs


class TestKvPagesWalked:
    @pytest.mark.parametrize("window", [None, 24, 100])
    def test_count_matches_the_references_rule(self, window):
        import numpy as np

        from operator_tpu.serving.sched.scheduler import _kv_walk

        #            decode  chunk  whole-prompt  verify  unscheduled  empty  page-edge
        kv_len = np.array([130, 48, 16, 77, 200, 0, 64], np.int32)
        q_count = np.array([1, 16, 16, 5, 0, 0, 1], np.int32)
        by_slot, pairs = _kv_walk(kv_len, q_count, 16, window)
        got = (int(by_slot.sum()), pairs)
        assert got == _walk_by_the_references_rule(kv_len, q_count, 16, window)
        if window is None:
            # 9 + 3 + 1 + 5 + 0 + 0 + 4 pages; the unscheduled row's 13 not walked
            assert got == (22, 130 + 16 * 48 + 16 * 16 + 5 * 77 + 64)

    @pytest.mark.parametrize("window, spec", [
        (None, False), (24, False), (None, True),
    ])
    def test_records_count_what_the_program_was_given(
        self, params, window, spec, monkeypatch
    ):
        """Every step: the record's ``kv_pages_walked`` and
        ``q_tile_rows`` and the dispatch span's ``qk_pairs`` and
        ``q_tile_rows`` against a count over the very ``kv_len`` /
        ``q_count`` the mixed program got; and its ``sampled_rows``
        against the ``spec_len`` it got (with the verify width compiled:
        that width a slot in a step with a draft, one a slot otherwise)."""
        import dataclasses

        import numpy as np

        from operator_tpu.ops import ragged_attention
        from operator_tpu.ops.ragged_attention import (
            kv_block_pages,
            query_tile_rows,
        )

        span_pairs, span_rows, span_blocks = {}, {}, {}
        real_annotation = BatchedGenerator._annotation

        def spy_annotation(self, name, params_list=None, **args):
            if name == "podmortem.sched.dispatch":
                span_pairs[args["step"]] = args["qk_pairs"]
                span_rows[args["step"]] = args["q_tile_rows"]
                span_blocks[args["step"]] = args["kv_blocks"]
            return real_annotation(self, name, params_list, **args)

        monkeypatch.setattr(BatchedGenerator, "_annotation", spy_annotation)
        # a budget at which this geometry walks two pages a block (the
        # rule is read where the scheduler counts, step by step)
        geometry = dict(
            q_per_kv=TINY_TEST.num_heads // TINY_TEST.num_kv_heads,
            kv_heads=TINY_TEST.num_kv_heads, head_dim=TINY_TEST.head_dim,
            page_size=16, itemsize=4,
        )
        monkeypatch.setattr(ragged_attention, "VMEM_BLOCK_BUDGET", 16_384)
        block_of = {tile: kv_block_pages(tile, **geometry) for tile in (1, 8, 16)}
        assert block_of == {1: 2, 8: 2, 16: 2}

        config = dataclasses.replace(TINY_TEST, sliding_window=window)
        generator = BatchedGenerator(
            params, config, ByteTokenizer(), paged=True, max_slots=4,
            max_seq=128, page_size=16, cache_dtype=jnp.float32,
            metrics=MetricsRegistry(),
        )
        sched = Scheduler(
            generator, chunk=16, token_budget=32, pipeline_depth=2, spec_decode=spec,
        )
        real = sched._get_fn()
        given, drafted = [], []

        def spy(*args):
            given.append((np.asarray(args[9]), np.asarray(args[8])))  # kv_len, q_count
            drafted.append(bool(np.asarray(args[13]).any()))  # spec_len
            return real(*args)

        sched._fn = spy
        sampling = SamplingParams(max_tokens=20, temperature=0.0, stop_on_eos=False)
        for prompt in ("pod crashed with exit code 137 after the node ran out "
                       "of memory and the kubelet evicted it", "short",
                       "oom oom oom oom oom oom" if spec else "oom"):
            sched.enqueue(prompt, sampling)
        finished = 0
        for _ in range(200):
            finished += len(sched.step())
            if finished == 3:
                break
        assert finished == 3
        records = generator.step_clock.ring.records()
        assert len(records) == len(given)
        kinds, tiles, several = set(), set(), False
        for record, (kv_len, q_count) in zip(records, given):
            pages, pairs = _walk_by_the_references_rule(kv_len, q_count, 16, window)
            assert (record.kv_pages_walked, span_pairs[record.seq]) == (pages, pairs)
            assert record.tokens == int(q_count.sum())
            rows = query_tile_rows(q_count, 16)
            assert record.q_tile_rows == span_rows[record.seq] == int(rows.sum())
            # flash updates: each walking slot's pages, counted by the
            # reference's rule, in blocks of what its rung takes
            blocks = sum(
                -(-_walk_by_the_references_rule(
                    kv_len[i:i + 1], q_count[i:i + 1], 16, window
                )[0] // block_of[int(tile)])
                for i, tile in enumerate(rows) if tile
            )
            assert record.kv_blocks_walked == span_blocks[record.seq] == blocks
            several |= blocks < pages
            tiles.update(rows.tolist())
            kinds.add(record.kind)
            kinds.update(
                "unscheduled" for kv, c in zip(kv_len, q_count) if kv > 0 and c == 0
            )
        assert {"decode", "mixed"} <= kinds
        # idle slots, decode rows, prompt chunks (and the rows of a few
        # queries: a verify row, a short prompt's chunk)
        assert {0, 1, 16} <= tiles <= {0, 1, 8, 16}
        assert several  # some block held more than one page
        assert [r.sampled_rows for r in records] == [
            4 * (sched.width if wide else 1) for wide in drafted
        ]
        assert sched.width == (5 if spec else 1)
        assert generator.metrics.counter("sample_wide_steps") == sum(drafted)
        assert any(drafted) is spec
        last = records[-1]
        rendered = render_steps(records).splitlines()[-1].split()
        assert f"{last.tokens / last.q_tile_rows:.3f}" in rendered
        assert rendered[-3] == (
            f"{last.kv_pages_walked / last.kv_blocks_walked:.2f}"
        )


class TestKvBlocksWalked:
    """``kv_blocks_walked``: the flash updates one layer's kernel call
    makes, by the rule the kernel sizes its KV blocks with
    (``ops/ragged_attention.kv_block_pages``)."""

    GEOMETRY = dict(q_per_kv=7, kv_heads=4, head_dim=128, page_size=64, itemsize=2)

    @pytest.mark.parametrize("name, pages, q_count, blocks", [
        # decode rows on the small tile, 8 pages a block: 6 pages are one
        # update, 8 one, 9 two, 17 three
        ("decode", [6, 8, 9, 17], [1, 1, 1, 5], 1 + 1 + 2 + 3),
        # whole chunks, 4 pages a block: 1, 4, 5 and 16 pages
        ("chunks", [1, 4, 5, 16], [64, 9, 64, 64], 1 + 1 + 2 + 4),
        # a slot without queries walks nothing whatever its pages
        ("idle-between", [6, 13, 0, 9], [1, 0, 0, 64], 1 + 3),
        ("empty", [0, 0], [0, 0], 0),
    ])
    def test_count_is_the_kernels_rule(self, name, pages, q_count, blocks):
        import numpy as np

        from operator_tpu.ops.ragged_attention import (
            kv_block_pages,
            kv_blocks_walked,
            query_tile_rows,
        )

        # the 7B cells' geometry: the rungs' blocks the cases are written for
        block_of = {t: kv_block_pages(t, **self.GEOMETRY) for t in (1, 8, 64)}
        assert block_of == {1: 8, 8: 8, 64: 4}
        tile_rows = query_tile_rows(np.asarray(q_count, np.int32), 64)
        assert kv_blocks_walked(np.asarray(pages), tile_rows, block_of) == blocks

    @pytest.mark.parametrize("name, geometry", [
        ("qwen2.5-1.5b", dict(q_per_kv=6, kv_heads=2)),
        ("qwen2.5-7b", dict(q_per_kv=7, kv_heads=4)),
        ("falcon-h1-34b", dict(q_per_kv=5, kv_heads=4)),
        ("sdar-30b-a3b", dict(q_per_kv=8, kv_heads=4)),
        ("ouro-2.6b", dict(q_per_kv=1, kv_heads=16)),
        ("olmo-hybrid-7b", dict(q_per_kv=1, kv_heads=30)),
    ])
    def test_the_rung_of_one_query_walks_the_small_tiles_blocks(self, name, geometry):
        """A row of one query takes a rung of its own at every geometry;
        the flash updates a call makes are the same as if it took the
        small tile's, at every served geometry: the rung's block is the
        small tile's."""
        import numpy as np

        from operator_tpu.ops.ragged_attention import (
            SMALL_TILE,
            kv_block_pages,
            kv_blocks_walked,
            query_tile_rows,
            query_tiles,
        )

        shapes = dict(head_dim=128, page_size=64, itemsize=2, **geometry)
        tiles = query_tiles(64)
        block_of = {t: kv_block_pages(t, **shapes) for t in tiles}
        pages = np.asarray([1, 6, 9, 17, 24, 3, 0, 11])
        q_count = np.asarray([1, 1, 1, 5, 64, 8, 0, 9], np.int32)
        rows = query_tile_rows(q_count, 64)
        # the GQA rule: the small tile for every row of at most 8 queries
        small = np.where(q_count > SMALL_TILE, 64, np.where(q_count > 0, SMALL_TILE, 0))
        assert kv_blocks_walked(pages, rows, block_of) == kv_blocks_walked(
            pages, small, {t: kv_block_pages(t, **shapes) for t in (8, 64)}
        )
        assert tiles[0] == 1 and rows.tolist()[:3] == [1, 1, 1]
        assert block_of[1] == block_of[SMALL_TILE]

    @pytest.mark.parametrize("name, geometry, small, chunk", [
        ("qwen2.5-1.5b", dict(q_per_kv=6, kv_heads=2), 8, 8),
        ("qwen2.5-7b", dict(q_per_kv=7, kv_heads=4), 8, 4),
        ("falcon-h1-34b", dict(q_per_kv=5, kv_heads=4), 8, 4),
        ("ouro-2.6b", dict(q_per_kv=1, kv_heads=16), 2, 2),
        # 30 heads held 32 a page: K and V of one page fill the budget
        ("olmo-hybrid-7b", dict(q_per_kv=1, kv_heads=30), 1, 1),
    ])
    def test_the_cells_blocks(self, name, geometry, small, chunk):
        """The block each rung takes at the served geometries: a change
        of the budget or of the rule shows here first.  The rung of one
        query leads at every geometry and walks the small tile's block."""
        from operator_tpu.ops.ragged_attention import kv_block_pages, query_tiles

        shapes = dict(head_dim=128, page_size=64, itemsize=2, **geometry)
        tiles = query_tiles(64)
        assert tiles == (1, 8, 64)
        assert [kv_block_pages(t, **shapes) for t in tiles] == [small, small, chunk]

    @pytest.mark.parametrize("name, geometry, one, small", [
        # the cells' groups: the page buffers decide every rung's block
        ("qwen2.5-1.5b", dict(q_per_kv=6, kv_heads=2), 8, 8),
        ("qwen2.5-7b", dict(q_per_kv=7, kv_heads=4), 8, 8),
        ("falcon-h1-34b", dict(q_per_kv=5, kv_heads=4), 8, 8),
        ("ouro-2.6b", dict(q_per_kv=1, kv_heads=16), 2, 2),
        # 512 query heads over 16 KV heads: the rung of one query's score
        # block, [512, 64 x 16] float32, is 2 MiB a page where the small
        # tile's, [8 x 512, 64], is 1 MiB: the rung takes one page
        ("wide-group", dict(q_per_kv=32, kv_heads=16), 1, 2),
    ])
    def test_the_rung_of_one_query_counts_its_own_score_block(
        self, name, geometry, one, small
    ):
        """The rung of one query scores ``[query heads, N x page x held
        heads]``, not ``[tile x query heads, N x page]``: its block is the
        largest ``N`` at which neither that float32 block nor the page
        buffers pass the budget."""
        from operator_tpu.ops.ragged_attention import (
            KV_BLOCK_PAGES,
            VMEM_BLOCK_BUDGET,
            kv_block_pages,
            pool_heads,
        )

        shapes = dict(head_dim=128, page_size=64, itemsize=2, **geometry)
        heads = geometry["q_per_kv"] * geometry["kv_heads"]
        held = pool_heads(geometry["kv_heads"])
        per_page = max(4 * 64 * held * 128 * 2, heads * 64 * held * 4)
        assert one == max(
            n for n in KV_BLOCK_PAGES if n * per_page <= VMEM_BLOCK_BUDGET
        )
        assert kv_block_pages(1, **shapes) == one
        assert kv_block_pages(8, **shapes) == small

    def test_a_block_never_outgrows_the_budget_and_one_page_always_goes(self):
        from operator_tpu.ops import ragged_attention
        from operator_tpu.ops.ragged_attention import kv_block_pages

        huge = dict(q_per_kv=8, kv_heads=32, head_dim=256, page_size=128, itemsize=4)
        assert kv_block_pages(64, **huge) == 1  # nothing fits: still one page
        tiny = dict(q_per_kv=1, kv_heads=1, head_dim=128, page_size=8, itemsize=2)
        assert kv_block_pages(8, **tiny) == max(ragged_attention.KV_BLOCK_PAGES)


class TestQTileRows:
    """``q_tile_rows``: the query-tile rows one layer's kernel call works,
    by the rule the kernel branches on (``ops/ragged_attention.py``)."""

    @pytest.mark.parametrize("name, q_count, chunk, rows", [
        # 3 decode rows and a verify row, each on the small tile of 8
        ("decode-only", [1, 1, 0, 1, 5, 0], 64, 3 * 1 + 8),
        # the small tile's edge, one past it, a whole chunk, two idle
        ("mixed", [1, 8, 9, 64, 0, 0], 64, 1 + 8 + 64 + 64),
        ("empty", [0, 0, 0, 0], 64, 0),
        # a chunk no larger than the small tile has two rungs: one query,
        # and the chunk
        ("tiny-chunk", [1, 4, 0], 4, 1 + 4),
    ])
    def test_count_is_the_kernels_rule(self, name, q_count, chunk, rows):
        import numpy as np

        from operator_tpu.ops.ragged_attention import SMALL_TILE, query_tile_rows

        assert SMALL_TILE == 8
        counts = np.asarray(q_count, np.int32)
        # nothing, the rung of one query, the small tile, or the chunk,
        # slot by slot, whatever the group of query heads a kv head
        small = min(SMALL_TILE, chunk)
        got = query_tile_rows(counts, chunk)
        assert int(got.sum()) == rows
        assert got.tolist() == [
            0 if c == 0 else 1 if c == 1 else small if c <= small else chunk
            for c in q_count
        ]

    @pytest.mark.parametrize("name, q_count, chunk, tiles", [
        # decode rows take the rung of one; a verify row the small tile
        ("decode-only", [1, 1, 0, 1, 5, 0], 64, [1, 1, 0, 1, 8, 0]),
        # 2 to 8 queries the small tile, more the chunk, as at GQA
        ("mixed", [1, 2, 8, 9, 64, 0], 64, [1, 8, 8, 64, 64, 0]),
        ("empty", [0, 0, 0, 0], 64, [0, 0, 0, 0]),
        ("tiny-chunk", [1, 4, 0, 2], 4, [1, 4, 0, 4]),
        # a chunk of one: the rung of one is the only rung
        ("chunk-of-one", [1, 0, 1], 1, [1, 0, 1]),
    ])
    def test_a_row_of_one_query_at_mha_counts_one(self, name, q_count, chunk, tiles):
        """A row of one query is worked on the rung of one at MHA as at
        GQA (``q_tile_rows`` counts 1 for it, where the small tile counted
        8); a row of more queries takes what it took."""
        import numpy as np

        from operator_tpu.ops.ragged_attention import query_tile_rows, query_tiles

        counts = np.asarray(q_count, np.int32)
        assert query_tile_rows(counts, chunk).tolist() == tiles
        assert query_tiles(chunk)[0] == 1
        # ten decode rows count ten rows, not ten small tiles of 8
        ten = np.ones(10, np.int32)
        assert int(query_tile_rows(ten, 64).sum()) == 10


class TestChaosReplayStepRecords:
    def test_seeded_fault_plan_replays_identical_step_sequence(self, params):
        """Two fresh engines under the same seeded fault plan must record
        the same step SEQUENCE (seq/kind/tokens/slots/occupancy) — the
        structural projection of the ring; wall-clock timings are the
        only fields allowed to differ between replays."""
        from operator_tpu.utils.faultinject import OK, FaultPlan, sleep_

        def run_once():
            generator = make_generator(params)
            sched = Scheduler(generator, chunk=16, token_budget=32)
            plan = FaultPlan(seed=13)
            plan.rule("engine.step", [OK, OK, sleep_(0.02)])
            generator.fault_plan = plan
            sampling = SamplingParams(max_tokens=6, temperature=0.0,
                                      stop_on_eos=False)
            arrivals = {
                0: ["pod crashed with exit code 137"],
                2: ["a longer second prompt", "third"],
            }
            finished = 0
            for step_i in range(60):
                for prompt in arrivals.get(step_i, ()):
                    sched.enqueue(prompt, sampling)
                finished += len(sched.step())
                if finished == 3:
                    break
            generator.fault_plan = None
            assert finished == 3
            return [
                (r.seq, r.kind, r.tokens, r.slots, round(r.occupancy, 4))
                for r in generator.step_clock.ring.records()
            ]

        first = run_once()
        second = run_once()
        assert first and first == second


# ---------------------------------------------------------------------------
# fleet roll-up: weighted aggregation, /healthz feed, GET /fleet gate
# ---------------------------------------------------------------------------


class TestFleetRollup:
    def test_step_weighted_means_hand_value(self):
        replicas = {
            "r1": {"ready": True, "queueDepth": 2, "inflight": 1,
                   "decodeMfu": 0.2, "hostGapFrac": 0.8, "occupancy": 0.5,
                   "steps": 10},
            "r2": {"ready": True, "queueDepth": 3, "inflight": 0,
                   "decodeMfu": 0.4, "hostGapFrac": 0.4, "occupancy": 1.0,
                   "steps": 30},
            # never decoded: contributes nothing to the means, not a zero
            "r3": {"ready": False, "queueDepth": 5, "inflight": 2,
                   "decodeMfu": None, "hostGapFrac": None, "occupancy": None,
                   "steps": 0},
        }
        fleet = fleet_rollup(replicas)
        assert fleet["replicaCount"] == 3
        assert fleet["readyCount"] == 2
        assert fleet["queueDepth"] == 10
        assert fleet["inflight"] == 3
        assert fleet["decodeMfu"] == pytest.approx((0.2 * 10 + 0.4 * 30) / 40)
        assert fleet["hostGapFrac"] == pytest.approx((0.8 * 10 + 0.4 * 30) / 40)
        assert fleet["occupancy"] == pytest.approx((0.5 * 10 + 1.0 * 30) / 40)

    def test_empty_fleet(self):
        fleet = fleet_rollup({})
        assert fleet["replicaCount"] == 0
        assert fleet["decodeMfu"] is None

    def test_replica_load_wire_roundtrip(self):
        load = ReplicaLoad(queue_depth=4, inflight=2, decode_token_s=0.01,
                           decode_mfu=0.123456789, host_gap_frac=0.9,
                           occupancy=0.75, steps=17)
        parsed = ReplicaLoad.parse(load.to_dict())
        assert parsed.decode_mfu == pytest.approx(0.123457)
        assert parsed.host_gap_frac == pytest.approx(0.9)
        assert parsed.occupancy == pytest.approx(0.75)
        assert parsed.steps == 17
        # pre-step-clock replicas and garbage degrade to None, never raise
        legacy = ReplicaLoad.parse({"queueDepth": 1, "decodeMfu": "bogus"})
        assert legacy.decode_mfu is None and legacy.steps == 0

    def test_health_board_fleet_view(self):
        board = HealthBoard()
        board.for_replica("r1").report_load(
            ReplicaLoad(queue_depth=1, decode_mfu=0.25, host_gap_frac=0.5,
                        occupancy=0.5, steps=8)
        )
        board.for_replica("r2").report_load(ReplicaLoad(queue_depth=2))
        view = board.fleet_view()
        assert set(view["replicas"]) == {"r1", "r2"}
        assert view["replicas"]["r1"]["decodeMfu"] == 0.25
        assert view["replicas"]["r1"]["breaker"] == "closed"
        assert view["fleet"]["decodeMfu"] == pytest.approx(0.25)
        assert view["fleet"]["queueDepth"] == 3


class TestFleetFromHealthPoll:
    """≥2 faked /healthz bodies → poll sweep → fleet_view roll-up."""

    def _healthz_opener(self, payloads: dict):
        import io
        import urllib.parse

        class _Resp(io.BytesIO):
            def __enter__(self):
                return self

            def __exit__(self, *a):
                return False

        def opener(req, timeout=None):
            url = req.full_url if hasattr(req, "full_url") else str(req)
            netloc = urllib.parse.urlsplit(url).netloc
            payload = payloads[netloc]
            if isinstance(payload, Exception):
                raise payload
            return _Resp(json.dumps(payload).encode())

        return opener

    def test_poll_feeds_token_gated_fleet_view(self):
        from operator_tpu.operator.httpserver import HealthServer
        from operator_tpu.operator.health import LivenessCheck, ReadinessCheck
        from operator_tpu.operator.providers import OpenAICompatProvider

        opener = self._healthz_opener({
            "r1:8000": {"status": "ok", "replica": "r1",
                        "load": {"queueDepth": 1, "inflight": 0,
                                 "decodeTokenS": 0.01, "gaveUp": False,
                                 "decodeMfu": 0.2, "hostGapFrac": 0.9,
                                 "occupancy": 0.25, "steps": 10}},
            "r2:8000": {"status": "ok", "replica": "r2",
                        "load": {"queueDepth": 3, "inflight": 1,
                                 "decodeTokenS": 0.02, "gaveUp": False,
                                 "decodeMfu": 0.4, "hostGapFrac": 0.5,
                                 "occupancy": 0.75, "steps": 30}},
        })
        provider = OpenAICompatProvider(opener, metrics=MetricsRegistry())
        provider.router_for([
            Replica(id=f"http://r{i}:8000/v1", url=f"http://r{i}:8000/v1")
            for i in (1, 2)
        ])
        assert run(provider.poll_replica_health(timeout_s=2.0)) == 2

        view = provider.fleet_view()
        assert len(view["replicas"]) == 2
        row = view["replicas"]["http://r1:8000/v1"]
        assert row["decodeMfu"] == pytest.approx(0.2)
        assert row["steps"] == 10
        fleet = view["fleet"]
        assert fleet["readyCount"] == 2
        assert fleet["queueDepth"] == 4
        assert fleet["decodeMfu"] == pytest.approx((0.2 * 10 + 0.4 * 30) / 40)

        # ...and the operator endpoint serves exactly this body, behind
        # the same bearer token as /incidents and /traces
        server = HealthServer(
            LivenessCheck(), ReadinessCheck(None),
            metrics=MetricsRegistry(), incidents_token="tok",
            fleet=provider.fleet_view,
        )

        async def routes():
            denied = await server._route("GET", "/fleet")
            granted = await server._route(
                "GET", "/fleet", authorization="Bearer tok"
            )
            return denied, granted

        (denied_status, _), (status, body) = run(routes())
        assert denied_status == 401
        assert status == 200
        assert body["fleet"]["decodeMfu"] == fleet["decodeMfu"]

    def test_fleet_404_without_routed_replicas(self):
        from operator_tpu.operator.httpserver import HealthServer
        from operator_tpu.operator.health import LivenessCheck, ReadinessCheck

        server = HealthServer(
            LivenessCheck(), ReadinessCheck(None), metrics=MetricsRegistry()
        )
        status, body = run(server._route("GET", "/fleet"))
        assert status == 404
        assert "replica" in body["error"]


# ---------------------------------------------------------------------------
# POST /profile: token-gated on-demand profiler capture
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def profile_server(params, tmp_path_factory):
    """Real HTTP server with profiling enabled (compiles the tiny model
    once for the module)."""
    from operator_tpu.serving.httpserver import CompletionServer

    profile_dir = str(tmp_path_factory.mktemp("xplane"))
    generator = make_generator(params, decode_block=2)
    started = {}

    async def serve():
        engine = ServingEngine(generator, admission_wait_s=0.005)
        server = CompletionServer(
            engine, model_id="tiny-test", host="127.0.0.1", port=0,
            api_token="sekrit", profile_enabled=True,
            profile_dir=profile_dir,
        )
        await server.start()
        started["port"] = server.bound_port
        started["server"] = server
        started["stop"] = asyncio.Event()
        started["ready"].set()
        await started["stop"].wait()
        await server.stop()
        await engine.close()

    import threading

    started["ready"] = threading.Event()
    loop = asyncio.new_event_loop()
    thread = threading.Thread(target=loop.run_forever, daemon=True)
    thread.start()
    future = asyncio.run_coroutine_threadsafe(serve(), loop)
    assert started["ready"].wait(timeout=60), "server failed to start"
    yield started["port"], profile_dir
    loop.call_soon_threadsafe(started["stop"].set)
    future.result(timeout=10)
    loop.call_soon_threadsafe(loop.stop)


def _request(port, method, path, body=None, token="sekrit", accept=None):
    """Plain-socket HTTP round-trip; returns (status, raw_body_bytes)."""

    async def go():
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        payload = json.dumps(body).encode() if body is not None else b""
        headers = [f"{method} {path} HTTP/1.1", "Host: t"]
        if token is not None:
            headers.append(f"Authorization: Bearer {token}")
        if accept is not None:
            headers.append(f"Accept: {accept}")
        if payload:
            headers.append(f"Content-Length: {len(payload)}")
        writer.write("\r\n".join(headers).encode() + b"\r\n\r\n" + payload)
        await writer.drain()
        response = await asyncio.wait_for(reader.read(), timeout=120)
        writer.close()
        head, _, body_bytes = response.partition(b"\r\n\r\n")
        return int(head.split()[1]), body_bytes

    return asyncio.run(go())


class TestProfileEndpoint:
    def test_capture_writes_artifact(self, profile_server):
        port, profile_dir = profile_server
        status, raw = _request(port, "POST", "/profile?seconds=0.2")
        assert status == 200
        body = json.loads(raw)
        assert body["object"] == "profile"
        assert body["seconds"] == pytest.approx(0.2)
        assert os.path.dirname(body["artifact"]) == profile_dir
        assert os.path.isdir(body["artifact"])  # the xplane dump landed

    def test_requires_bearer_token(self, profile_server):
        port, _ = profile_server
        status, raw = _request(port, "POST", "/profile?seconds=0.2",
                               token=None)
        assert status == 401
        assert json.loads(raw)["error"]["type"] == "authentication_error"

    def test_bad_seconds_is_client_error(self, profile_server):
        port, _ = profile_server
        status, raw = _request(port, "POST", "/profile?seconds=abc")
        assert status == 400
        assert "seconds" in json.loads(raw)["error"]["message"]

    def test_disabled_profile_is_404(self, profile_server):
        from operator_tpu.serving.httpserver import ApiError, CompletionServer

        port, _ = profile_server
        engine = ServingEngine.__new__(ServingEngine)  # routes only; no loop
        server = CompletionServer(engine, model_id="t", profile_enabled=False)
        with pytest.raises(ApiError) as excinfo:
            run(server._profile({"seconds": ["1"]}))
        assert excinfo.value.status == 404
        assert "PROFILE_ENABLED" in str(excinfo.value)

    def test_metrics_flavours_over_the_wire(self, profile_server):
        """One real generation, then the step/latency histograms are
        visible in the classic exposition, the OpenMetrics flavour, and
        the /metrics.json twin."""
        port, _ = profile_server
        status, _ = _request(
            port, "POST", "/v1/completions",
            {"prompt": "oom", "max_tokens": 4, "temperature": 0.0},
        )
        assert status == 200
        status, classic = _request(port, "GET", "/metrics")
        assert status == 200
        text = classic.decode()
        assert "# TYPE podmortem_step_duration_milliseconds histogram" in text
        assert "podmortem_ttft_milliseconds_bucket" in text
        status, om = _request(port, "GET", "/metrics",
                              accept="application/openmetrics-text")
        assert status == 200
        assert om.decode().rstrip().endswith("# EOF")
        status, raw = _request(port, "GET", "/metrics.json")
        assert status == 200
        histograms = json.loads(raw)["histograms"]
        for name in ("step_duration_milliseconds", "queue_wait_milliseconds",
                     "ttft_milliseconds", "token_latency_milliseconds"):
            assert histograms[name]["count"] >= 1, name
