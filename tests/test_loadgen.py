"""Open-loop storm harness + SLO ledger (ISSUE 12 acceptance surface).

Covers: seeded arrival determinism (byte-identical two-replay, including
a full storm under a composed FaultPlan), hand-valued attainment and
goodput-under-SLO on a synthetic ledger, the fleet roll-up carrying
sloAttainment/goodput over faked replicas (the ``GET /fleet`` payload),
and the open-loop smoke: one seeded storm through ``build_storm_stack``
/ ``run_storm`` must leave a populated report — non-null attainment,
replay-identical schedule, zero torn ledger lines — with no JAX in sight
(synthetic replicas only).
"""

import asyncio
import json

import pytest

from operator_tpu.loadgen import ArrivalProcess, ArrivalSpec
from operator_tpu.loadgen.storm import (
    SLO_CLASS_ANNOTATION,
    SyntheticReplica,
    build_storm_stack,
    run_storm,
    simulate_overload,
    storm_log,
    storm_pod,
)
from operator_tpu.obs.sloledger import (
    SLOBoard,
    SLOLedger,
    SLORecord,
    parse_slo_classes,
    summarize,
)
from operator_tpu.operator.kubeapi import ConflictError
from operator_tpu.utils.config import OperatorConfig
from operator_tpu.router.health import HealthBoard, ReplicaLoad, fleet_rollup
from operator_tpu.utils.faultinject import FaultPlan, raise_, times
from operator_tpu.utils.timing import MetricsRegistry


def run(coro):
    return asyncio.run(coro)


# ---------------------------------------------------------------------------
# seeded arrival determinism
# ---------------------------------------------------------------------------


class TestArrivalDeterminism:
    def test_two_materialisations_byte_identical(self):
        spec = ArrivalSpec(name="storm", rate_per_min=300.0, duration_s=20.0)
        first = ArrivalProcess(spec, seed=42)
        second = ArrivalProcess(spec, seed=42)
        blob_a = json.dumps(
            [e.to_dict() for e in first.materialize()], sort_keys=True
        ).encode()
        blob_b = json.dumps(
            [e.to_dict() for e in second.materialize()], sort_keys=True
        ).encode()
        assert blob_a == blob_b
        assert first.fingerprint() == second.fingerprint()
        assert len(first.materialize()) > 0

    def test_seed_changes_the_schedule(self):
        spec = ArrivalSpec(name="storm", rate_per_min=300.0, duration_s=20.0)
        assert (
            ArrivalProcess(spec, seed=1).fingerprint()
            != ArrivalProcess(spec, seed=2).fingerprint()
        )

    def test_every_shape_is_deterministic_and_in_window(self):
        for name in ("poisson", "storm", "diurnal"):
            spec = ArrivalSpec(name=name, rate_per_min=240.0, duration_s=15.0)
            events = ArrivalProcess(spec, seed=7).materialize()
            assert events, name
            assert all(0.0 <= e.at_s < spec.duration_s for e in events), name
            assert [e.at_s for e in events] == sorted(e.at_s for e in events)
            assert ArrivalProcess(spec, seed=7).fingerprint() == \
                ArrivalProcess(spec, seed=7).fingerprint()

    def test_storm_bursts_add_offered_load(self):
        base = ArrivalSpec(name="poisson", rate_per_min=120.0, duration_s=60.0)
        storm = ArrivalSpec(name="storm", rate_per_min=120.0, duration_s=60.0)
        assert (
            ArrivalProcess(storm, seed=3).offered_per_min()
            > ArrivalProcess(base, seed=3).offered_per_min()
        )

    def test_storm_replay_under_fault_plan_byte_identical(self, tmp_path):
        """The CI replay gate: the SAME seeded storm through the full
        stack twice, each under an equal-seeded 409-storm FaultPlan,
        must offer the identical schedule and settle every arrival —
        terminal accounting equal run to run."""

        async def one_run(tag: str) -> dict:
            plan = FaultPlan(seed=5)
            plan.rule(
                "kube.patch_status",
                times(2, raise_(lambda: ConflictError("injected 409"), "409")),
            )
            # deadline_factor keeps envelopes far above the ms-scale
            # service times: terminal outcomes then depend only on the
            # schedule + plan, not on CPU contention during the test run.
            # The overload ladder keys off LIVE queue pressure — a
            # contention signal by design — so its thresholds are pushed
            # out of reach here; ladder determinism is proven on its own
            # decision log in tests/test_value.py, where pressure is an
            # input, not a measurement.
            stack = await build_storm_stack(
                replicas=[SyntheticReplica("r0", time_scale=0.05)],
                config=OperatorConfig(
                    pattern_cache_directory="/nonexistent",
                    conflict_backoff_base_s=0.001,
                    memory_enabled=True,
                    shed_pressure=10**9,
                ),
                ledger_path=str(tmp_path / f"{tag}.jsonl"),
                time_scale=0.05,
                deadline_factor=200.0,
                fault_plan=plan,
            )
            process = ArrivalProcess(
                ArrivalSpec(name="storm", rate_per_min=600.0, duration_s=2.0),
                seed=9,
            )
            report = await run_storm(stack, process, drain_s=30.0)
            stack.close()
            return report

        first = run(one_run("a"))
        second = run(one_run("b"))
        assert first["fingerprint"] == second["fingerprint"]
        assert first["arrivals"] == second["arrivals"] > 0
        for report in (first, second):
            total = report["slo"]["total"]
            assert report["slo"]["pending"] == 0  # every arrival settled
            assert total["admitted"] == report["arrivals"]
        # outcome accounting is wall-clock independent here (generous
        # envelopes, deterministic service times): equal run to run
        for key in ("admitted", "completed", "shed",
                    "deadline_exceeded", "failed"):
            assert first["slo"]["total"][key] == second["slo"]["total"][key]

    def test_storm_pod_and_log_are_deterministic(self):
        events = ArrivalProcess(
            ArrivalSpec(rate_per_min=300.0, duration_s=5.0), seed=1
        ).materialize()
        cold = next(e for e in events if not e.recall_hot)
        hot = next(e for e in events if e.recall_hot)
        assert storm_log(cold) == storm_log(cold)
        assert storm_log(hot) == storm_log(hot)
        assert storm_log(cold) != storm_log(hot)
        pod = storm_pod(cold)
        assert pod.metadata.annotations[SLO_CLASS_ANNOTATION] == cold.slo_class
        state = pod.status.container_statuses[0].state.terminated
        assert state.exit_code == 137


# ---------------------------------------------------------------------------
# hand-valued attainment / goodput on a synthetic ledger
# ---------------------------------------------------------------------------


class TestLedgerHandValues:
    def _ledger(self, tmp_path=None, metrics=None):
        now = [0.0]
        ledger = SLOLedger(
            {"interactive": 2.0, "batch": 120.0},
            default_class="interactive",
            path=str(tmp_path / "ledger.jsonl") if tmp_path else None,
            metrics=metrics,
            clock=lambda: now[0],
        )
        return ledger, now

    def _settle_four(self, ledger, now):
        """t=0: admit four. interactive: 1s hit, 3s miss, one shed;
        batch: 10s hit with 50 tokens.  All hand-checkable."""
        ledger.admit("t1", cls="interactive")
        ledger.admit("t2", cls="interactive")
        ledger.admit("t3", cls="interactive")
        ledger.admit("t4", cls="batch")
        now[0] = 1.0
        ledger.finish("t1", outcome="completed", tokens=100, replica="a")
        now[0] = 3.0
        ledger.finish("t2", outcome="completed", tokens=40, replica="a")
        now[0] = 3.5
        ledger.finish("t3", outcome="shed")
        now[0] = 10.0
        ledger.finish("t4", outcome="completed", tokens=50, replica="b",
                      stages={"explain": 9000.0, "collect": 1000.0})

    def test_attainment_and_goodput_exact(self):
        ledger, now = self._ledger()
        self._settle_four(ledger, now)
        snap = ledger.snapshot()
        total = snap["total"]
        assert total["admitted"] == 4
        assert total["completed"] == 3
        assert total["attained"] == 2  # t1 (1s<=2s) and t4 (10s<=120s)
        assert total["attainment"] == pytest.approx(0.5)
        assert total["shed"] == 1
        assert total["deadline_exceeded"] == 0
        assert total["failed"] == 0
        # span = last completion (10s) - first admit (0s) = 10s
        assert total["tokens_attained"] == 150
        assert total["goodput_tokens_s"] == pytest.approx(15.0)
        assert total["goodput_analyses_per_min"] == pytest.approx(12.0)
        # nearest-rank percentiles over completed latencies [1, 3, 10]
        assert total["p50_s"] == pytest.approx(3.0)
        assert total["p95_s"] == pytest.approx(10.0)

        inter = snap["classes"]["interactive"]
        assert inter["admitted"] == 3
        assert inter["attained"] == 1
        assert inter["attainment"] == pytest.approx(1.0 / 3.0)
        assert inter["target_s"] == pytest.approx(2.0)
        assert inter["p50_s"] == pytest.approx(1.0)  # [1, 3] rank 1

        assert snap["classes"]["batch"]["attainment"] == pytest.approx(1.0)
        assert snap["replicas"]["a"]["admitted"] == 2
        assert snap["replicas"]["b"]["tokens_attained"] == 50
        assert snap["pending"] == 0

    def test_pending_by_class_tracks_open_requests(self):
        ledger, now = self._ledger()
        ledger.admit("t1", cls="interactive")
        ledger.admit("t2", cls="unknown-class")  # falls to default
        assert ledger.pending == 2
        assert ledger.pending_by_class() == {"interactive": 2}
        now[0] = 0.5
        ledger.finish("t1", outcome="completed")
        assert ledger.pending == 1

    def test_journal_round_trips_and_counters_fire(self, tmp_path):
        metrics = MetricsRegistry()
        ledger, now = self._ledger(tmp_path, metrics)
        self._settle_four(ledger, now)
        ledger.close()
        records = SLOLedger.load_records(str(tmp_path / "ledger.jsonl"))
        assert len(records) == 4
        assert all(isinstance(r, SLORecord) for r in records)
        # offline summarize over the journal == the live snapshot rows
        offline = summarize(records)
        live = ledger.snapshot()
        assert offline["total"] == live["total"]
        assert offline["classes"] == live["classes"]
        counters = metrics.snapshot()["counters"]
        assert counters["slo_admitted"] == 4
        assert counters["slo_attained"] == 2
        assert counters["slo_missed"] == 2
        assert counters["slo_shed"] == 1
        assert "slo_deadline_exceeded" not in counters

    def test_parse_slo_classes_tolerates_garbage(self):
        assert parse_slo_classes("a:1,b:junk,c:-3,d:30") == {
            "a": 1.0, "d": 30.0,
        }
        # fully garbage spec falls back to defaults, never classless
        assert "interactive" in parse_slo_classes("nonsense")

    def test_board_matches_ledger_arithmetic(self):
        board = SLOBoard()
        board.submitted("interactive")
        board.submitted("interactive")
        board.finished("interactive", attained=True, tokens=10)
        board.finished("interactive", attained=False)
        assert board.attainment() == pytest.approx(0.5)
        assert board.per_class()["interactive"]["completed"] == 2
        assert board.tokens_attained == 10


# ---------------------------------------------------------------------------
# fleet roll-up: sloAttainment / goodput over faked replicas
# ---------------------------------------------------------------------------


class TestFleetSLORollup:
    def test_fleet_view_weights_attainment_by_completed(self):
        board = HealthBoard()
        board.for_replica("engine-a").report_load(ReplicaLoad(
            queue_depth=1, slo_attainment=1.0, slo_completed=30,
            goodput_tokens_s=100.0,
            slo_classes={"interactive": {"queued": 1}},
        ))
        board.for_replica("engine-b").report_load(ReplicaLoad(
            slo_attainment=0.5, slo_completed=10, goodput_tokens_s=50.0,
        ))
        view = board.fleet_view()
        fleet = view["fleet"]
        # (1.0 * 30 + 0.5 * 10) / 40
        assert fleet["sloAttainment"] == pytest.approx(0.875)
        assert fleet["goodput"] == pytest.approx(150.0)
        assert view["replicas"]["engine-a"]["sloAttainment"] == 1.0
        assert view["replicas"]["engine-a"]["sloClasses"] == {
            "interactive": {"queued": 1},
        }

    def test_replicas_without_slo_reports_do_not_skew_the_mean(self):
        rows = {
            "a": {"ready": True, "sloAttainment": 0.8, "sloCompleted": 10,
                  "goodput": 20.0},
            "b": {"ready": True},  # never reported SLO state
        }
        fleet = fleet_rollup(rows)
        assert fleet["sloAttainment"] == pytest.approx(0.8)
        assert fleet["goodput"] == pytest.approx(20.0)
        # nobody reporting at all -> None, not a fake 0.0
        empty = fleet_rollup({"a": {"ready": True}})
        assert empty["sloAttainment"] is None
        assert empty["goodput"] is None

    def test_replica_load_wire_round_trip_preserves_slo_fields(self):
        load = ReplicaLoad(
            queue_depth=3, inflight=2, slo_attainment=0.75,
            goodput_tokens_s=12.5, slo_completed=8,
            slo_classes={"batch": {"queued": 2}},
        )
        parsed = ReplicaLoad.parse(load.to_dict())
        assert parsed.slo_attainment == pytest.approx(0.75)
        assert parsed.goodput_tokens_s == pytest.approx(12.5)
        assert parsed.slo_completed == 8
        assert parsed.slo_classes == {"batch": {"queued": 2}}


# ---------------------------------------------------------------------------
# bench smoke: populated open_loop record, no JAX required
# ---------------------------------------------------------------------------


async def open_loop_storm(
    replicas, tmp_path, *, rate_per_min, duration_s, seed, time_scale, drain_s
):
    """One seeded open-loop storm through the FULL stack — operator
    pipeline -> router -> replicas — and what it left behind: the storm's
    report, whether a second materialisation of the schedule is identical,
    and the ledger journal's line count and torn lines."""
    spec = ArrivalSpec(
        name="storm", rate_per_min=rate_per_min, duration_s=duration_s,
    )
    process = ArrivalProcess(spec, seed=seed)
    replay = ArrivalProcess(spec, seed=seed)
    replay_identical = (
        process.fingerprint() == replay.fingerprint()
        and [e.to_dict() for e in process.materialize()]
        == [e.to_dict() for e in replay.materialize()]
    )
    ledger_path = tmp_path / "slo-ledger.jsonl"
    stack = await build_storm_stack(
        replicas=replicas, time_scale=time_scale, ledger_path=str(ledger_path),
    )
    try:
        report = await run_storm(stack, process, drain_s=drain_s)
    finally:
        stack.close()
    lines = [l for l in ledger_path.read_text().splitlines() if l.strip()]
    torn = 0
    for line in lines:
        try:
            json.loads(line)
        except ValueError:
            torn += 1
    return report, replay_identical, len(lines), torn


class TestOpenLoopSmoke:
    def test_record_is_populated_and_replay_identical(self, tmp_path):
        replicas = [
            SyntheticReplica(f"bench-replica-{i}", concurrency=2,
                             time_scale=0.05)
            for i in range(2)
        ]
        report, replay_identical, ledger_lines, torn = run(open_loop_storm(
            replicas, tmp_path, rate_per_min=600.0, duration_s=2.0, seed=4,
            time_scale=0.05, drain_s=30.0,
        ))
        total = report["slo"]["total"]
        assert report["arrivals"] > 0
        assert replay_identical is True
        assert torn == 0
        assert total["attainment"] is not None
        assert total["p50_s"] is not None
        assert report["slo"]["classes"]  # per-class breakdown present
        assert report["fingerprint"]
        # conservation: every offered arrival reached a terminal outcome
        terminal = (total["completed"] + total.get("degraded", 0)
                    + total["shed"] + total["deadline_exceeded"]
                    + total["failed"])
        assert terminal == ledger_lines == report["arrivals"]
        fleet = report["fleet"]["fleet"]
        assert fleet["sloAttainment"] is None or \
            0.0 <= fleet["sloAttainment"] <= 1.0

    def test_overloaded_synthetic_storm_records_misses_or_sheds(self, tmp_path):
        """One replica, concurrency 1, service time far above the
        interarrival gap: an open-loop storm MUST show the overload in
        the ledger (attainment < 1 via sheds/misses) instead of quietly
        slowing the offered rate — that is the open-loop point."""
        replicas = [SyntheticReplica(
            "slow", concurrency=1, base_ms=400.0, time_scale=1.0,
        )]
        report, _, _, _ = run(open_loop_storm(
            replicas, tmp_path, rate_per_min=1200.0, duration_s=1.5, seed=6,
            time_scale=1.0, drain_s=10.0,
        ))
        total = report["slo"]["total"]
        assert report["arrivals"] > 3
        assert total["attainment"] is not None
        assert total["attainment"] < 1.0
        assert (total["shed"] + total["deadline_exceeded"]
                + total["failed"]) > 0


class TestOverloadSimulation:
    """The deterministic 2x-collapse proof surface (storm.simulate_overload):
    virtual clock, seeded arrivals, the production OverloadPolicy deciding
    every admission — so the CI overload gates are machine-independent."""

    def test_same_seed_replays_byte_identical(self):
        a = simulate_overload(1800.0, seed=3, duration_s=30.0)
        b = simulate_overload(1800.0, seed=3, duration_s=30.0)
        assert a == b  # full row, decision log text and sha included
        assert a["decision_log"] == b["decision_log"]
        c = simulate_overload(1800.0, seed=4, duration_s=30.0)
        assert a["decision_log_sha256"] != c["decision_log_sha256"]

    def test_sweep_decays_smoothly_and_never_sheds_protected(self):
        rows = [
            simulate_overload(900.0 * f, seed=0, duration_s=60.0)
            for f in (0.5, 0.75, 1.0, 1.5, 2.0)
        ]
        for prev, cur in zip(rows, rows[1:]):
            pairs = [(prev["attainment"], cur["attainment"])] + [
                (att, cur["attainment_by_class"].get(cls))
                for cls, att in prev["attainment_by_class"].items()
            ]
            for a, b in pairs:
                if a is not None and b is not None:
                    assert a - b <= 0.15, (prev, cur)
        peak = rows[-1]
        assert peak["shed_total"] or peak["degraded_total"]
        assert all(row["protected_shed"] == 0 for row in rows)
        # interactive (highest value) is never the one shed while cheaper
        # classes exist to shed first
        assert "interactive" not in peak["shed_by_class"]

    def test_recalled_shed_only_after_cold_of_equal_or_lower_class(self):
        """ISSUE acceptance, re-proven on the sim's decision log: at any
        cutoff where a RECALLED request of class c was shed, every COLD
        request of class <= c deciding at that same cutoff was also shed
        (the 1/expected-cost factor structurally outranks recall hits)."""
        row = simulate_overload(2400.0, seed=0, duration_s=60.0)
        weight = {"batch": 0, "standard": 1, "interactive": 2}
        decided = []
        for line in row["decision_log"].splitlines():
            kv = dict(part.split("=", 1) for part in line.split())
            if kv["reason"] in ("below-cutoff", "above-cutoff"):
                decided.append(kv)
        sheds = [d for d in decided if d["action"] == "shed"]
        assert sheds, "storm never reached the shed rung"
        for shed in sheds:
            if shed["recalled"] != "1":
                continue
            for other in decided:
                if (other["cutoff"] == shed["cutoff"]
                        and other["recalled"] == "0"
                        and weight[other["cls"]] <= weight[shed["cls"]]
                        and other["protected"] == "0"):
                    assert other["action"] == "shed", (shed, other)
