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
    return StepRecord(
        seq=0, kind=kind, tokens=seq_tokens, slots=2, occupancy=0.5,
        host_gap_ms=gap, device_ms=dev, sample_xfer_ms=xfer,
    )


# ---------------------------------------------------------------------------
# the bounded ring
# ---------------------------------------------------------------------------


class TestStepRing:
    def test_bounded_eviction_keeps_newest(self):
        ring = StepRing(capacity=4)
        for i in range(10):
            ring.append(kind="decode", tokens=i, slots=1, occupancy=0.25,
                        host_gap_ms=1.0, device_ms=1.0, sample_xfer_ms=1.0)
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
                        host_gap_ms=1.0, device_ms=2.0, sample_xfer_ms=1.0)
        ring.append(kind="mixed", tokens=3, slots=2, occupancy=0.5,
                    host_gap_ms=0.0, device_ms=4.0, sample_xfer_ms=0.0)
        ring.append(kind="prefill", tokens=8, slots=1, occupancy=0.25,
                    host_gap_ms=0.0, device_ms=8.0, sample_xfer_ms=0.0)
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
                        host_gap_ms=1.0, device_ms=1.0, sample_xfer_ms=1.0)
        ring.reset()
        assert len(ring) == 0
        assert ring.evicted == 0
        assert ring.decode_cum_ms == 0.0
        record = ring.append(kind="decode", tokens=1, slots=1, occupancy=0.25,
                             host_gap_ms=0.0, device_ms=1.0, sample_xfer_ms=0.0)
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
                host_gap_ms=0.0, device_ms=1.0, sample_xfer_ms=0.0,
            )

    def test_record_dict_roundtrip(self):
        record = StepRecord(
            seq=3, kind="mixed", tokens=5, slots=2, occupancy=0.5,
            host_gap_ms=1.25, device_ms=2.5, sample_xfer_ms=0.25, mfu=0.125,
        )
        parsed = StepRecord.from_dict(record.to_dict())
        assert parsed == record
        assert StepRecord.from_dict({}).kind == "decode"  # tolerant default


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
        assert out["fractions"]["host_gap"] is None
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


class TestStepClock:
    def test_mfu_on_decode_records_only(self):
        clock = StepClock(capacity=8, flops_per_token=1000.0,
                          peak_tflops=1.0, max_slots=4)
        prefill = clock.observe(kind="prefill", tokens=16, slots=1,
                                host_gap_ms=0.0, device_ms=10.0,
                                sample_xfer_ms=0.0)
        assert prefill.mfu is None
        decode = clock.observe(kind="decode", tokens=4, slots=2,
                               host_gap_ms=1.0, device_ms=2.0,
                               sample_xfer_ms=1.0)
        assert decode.mfu == pytest.approx(1e-6)
        assert decode.occupancy == pytest.approx(0.5)
        summary = clock.summary()
        assert summary["decode_mfu"] == pytest.approx(1e-6)

    def test_host_gap_measured_from_previous_commit(self):
        clock = StepClock(capacity=8, max_slots=1)
        assert clock.host_gap_ms(123.0) == 0.0  # first step: no gap yet
        clock.observe(kind="decode", tokens=1, slots=1, host_gap_ms=0.0,
                      device_ms=1.0, sample_xfer_ms=0.0, commit_t=10.0)
        assert clock.host_gap_ms(10.005) == pytest.approx(5.0)
        clock.reset()
        assert clock.host_gap_ms(10.010) == 0.0  # reset forgets the commit

    def test_feeds_step_histograms(self):
        metrics = MetricsRegistry()
        clock = StepClock(capacity=8, max_slots=1, metrics=metrics)
        for _ in range(3):
            clock.observe(kind="decode", tokens=1, slots=1, host_gap_ms=2.0,
                          device_ms=3.0, sample_xfer_ms=1.0)
        duration = metrics.histogram("step_duration_milliseconds")
        gap = metrics.histogram("step_host_gap_milliseconds")
        assert duration is not None and duration.count == 3
        assert duration.sum == pytest.approx(18.0)
        assert gap is not None and gap.count == 3


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
                       occupancy=0.25, host_gap_ms=0.0, device_ms=9.0,
                       sample_xfer_ms=0.0),
        ])
        lines = table.splitlines()
        assert lines[0].split() == [
            "seq", "kind", "tok", "slots", "occ",
            "gap_ms", "dev_ms", "xfer_ms", "total", "mfu",
        ]
        assert len(lines) == 4  # header + rule + 2 rows
        assert "prefill" in lines[3]

    def test_view_steps_cli(self, tmp_path, capsys):
        from operator_tpu.obs import view

        journal = tmp_path / "steps.jsonl"
        raw = _decode_record(seq_tokens=3).to_dict()
        blackbox = {"recordedAt": 1.0, "reason": "stall",
                    "extra": {"steps": [
                        StepRecord(seq=1, kind="mixed", tokens=2, slots=2,
                                   occupancy=0.5, host_gap_ms=1.0,
                                   device_ms=1.0, sample_xfer_ms=0.0).to_dict()
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
        assert "host_gap=" in out

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
