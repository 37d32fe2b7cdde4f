"""The step-tracing metrics of the benchmark, CPU only: the kernel's cost
from shapes against hand values, the roofline reader on a hand-made
trace, the readers of the step records on hand-made records (and on
records that carry none of the new fields), and a traced rehearsal of
``benchmark/run.py`` under a manifest of its own
(``rehearsal-tracing.json``: the rehearsal's two tiny cells with the new
per-layer entries).
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness.manifest import Manifest  # noqa: E402
from benchmark.layer_metrics import (  # noqa: E402
    attn_kernel_roofline_share,
    attn_kernel_share,
    step_host_ms,
    step_host_wait_share,
    step_kv_pages_mean,
    step_prefill_token_share,
    step_tokens_mean,
)
from benchmark.trace import kernel_cost, steps  # noqa: E402

MANIFEST = "tests/benchmark/rehearsal-tracing.json"
COUNTERS_AND_SPANS = {
    "step_kv_pages_mean", "step_tokens_mean", "step_prefill_token_share",
    "step_host_ms", "step_host_wait_share",
}
NEW = COUNTERS_AND_SPANS | {"attn_kernel_roofline_share"}
REAL_CELLS = ["qwen2.5-1.5b-int8.storm", "qwen2.5-1.5b-int8.decode"]

#: the 1.5B configuration's kernel shapes: 28 layers, 64-token pages of 2 KV
#: heads x 128 in bf16, 12 query heads
SHAPES = {
    "layers": 28, "page_size": 64, "kv_heads": 2, "q_heads": 12, "head_dim": 128,
    "kv_itemsize": 2, "q_itemsize": 2,
}
PEAKS = {"hbm_gbps": 819.0, "bf16_tflops": 197.0}


# -- the manifests -----------------------------------------------------------


def check_the_counter_metrics(manifest):
    """What stays true of the six however the manifest grows: each is an
    entry, agrees with its reader, and lists only cells that report the
    metric it moves.  Found by name (``test_falcon_h1_benchmark.py`` runs
    this on a manifest that a later PR has appended to)."""
    by_name = {m["name"]: m for m in manifest.doc["per_layer"]}
    assert NEW <= set(by_name)
    gap = next(m for m in manifest.doc["end_to_end"] if m["name"] == "token_gap_mean_ms")
    gap_cells = set(gap.get("workloads", [c["name"] for c in manifest.doc["workloads"]]))
    for name in NEW:
        entry = by_name[name]
        assert set(REAL_CELLS) <= set(entry["workloads"]) <= gap_cells, name
        assert entry["moves"] == "token_gap_mean_ms"
        reader = manifest.module("layer_metrics", name)
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
            name, entry["unit"], entry["layer"], entry["moves"], entry["source"],
        )


def test_the_new_metrics_are_manifest_entries_of_the_two_1p5b_cells():
    """(The name is of the day they were written: the 7B cells list them
    since PR 27 and PR 33.)"""
    check_the_counter_metrics(Manifest(os.path.join(ROOT, "BENCHMARK.json")))


def test_the_attention_pattern_names_attention_kernels_only():
    """The convention of ``benchmark/README.md``: an attention kernel's name
    ends in ``attention_kernel``; any other Pallas kernel is not counted."""
    assert attn_kernel_share.PATTERN.search("ragged_attention_kernel.8 bf16[128,64,12,128]")
    assert attn_kernel_share.PATTERN.search("latent_attention_kernel.2 bf16[64,128,512]")
    for other in (
        "grouped_expert_matmul_pallas.3 bf16[1024,2048]", "fusion.104 f32[640,64]",
        "pallas_call.7", "copy.108 bf16[28,3456,64,2,128]",
    ):
        assert not attn_kernel_share.PATTERN.search(other), other

    class Traced:
        trace = {"op_self_s": {
            "ragged_attention_kernel.8 bf16[128,64,12,128]": 0.2,
            "grouped_expert_matmul_pallas.3 bf16[1024,2048]": 0.3, "fusion.1": 0.5,
        }}

    assert attn_kernel_share.read(Traced()) == pytest.approx(0.2)


def test_the_rehearsal_manifest_is_the_rehearsal_plus_the_new_entries():
    base = Manifest(os.path.join(ROOT, "tests/benchmark/rehearsal.json")).doc
    mine = Manifest(os.path.join(ROOT, MANIFEST)).doc
    assert {k: v for k, v in mine.items() if k != "per_layer"} == {
        k: v for k, v in base.items() if k != "per_layer"
    }
    assert mine["per_layer"][: len(base["per_layer"])] == base["per_layer"]
    added = mine["per_layer"][len(base["per_layer"]):]
    assert {m["name"] for m in added} == NEW
    assert all(m["workloads"] == ["tiny-test.storm", "tiny-test.decode"] for m in added)


# -- the kernel's cost from shapes -------------------------------------------


def test_page_bytes_is_k_and_v_of_one_page_in_one_layer():
    assert kernel_cost.page_bytes(64, 2, 128, 2) == 65_536
    # 28 layers of it: the 1.8 MB a page that PERF.md speaks of
    assert 28 * kernel_cost.page_bytes(64, 2, 128, 2) == 1_835_008


def test_cost_of_one_decode_row_by_hand():
    """One row decoding at position 130: 3 pages of 64, one query."""
    one_layer = {**SHAPES, "layers": 1}
    moved, operations = kernel_cost.ragged_attention_cost(
        kv_pages=3, qk_pairs=130, tokens=1, **one_layer
    )
    assert moved == 3 * 65_536 + 12 * 128 * (2 + 4) == 205_824
    assert operations == 4 * 128 * 12 * 130 == 798_720
    seconds, bound = kernel_cost.least_seconds(moved, operations, PEAKS)
    assert bound == "bandwidth"
    assert seconds == pytest.approx(205_824 / 819e9)
    assert kernel_cost.ragged_attention_cost(
        kv_pages=3, qk_pairs=130, tokens=1, **SHAPES
    ) == (28 * moved, 28 * operations)


def test_cost_of_one_64_token_chunk_by_hand():
    """A prompt's first chunk: 64 queries over the one page they wrote."""
    one_layer = {**SHAPES, "layers": 1}
    moved, operations = kernel_cost.ragged_attention_cost(
        kv_pages=1, qk_pairs=64 * 64, tokens=64, **one_layer
    )
    assert moved == 65_536 + 64 * 12 * 128 * 6 == 655_360
    assert operations == 4 * 128 * 12 * 64 * 64 == 25_165_824
    # 0.80 us of bytes against 0.13 us of operations
    assert kernel_cost.least_seconds(moved, operations, PEAKS)[1] == "bandwidth"
    # a late chunk of a long prompt on a chip with little compute is not
    assert kernel_cost.least_seconds(
        moved, operations, {"hbm_gbps": 819.0, "bf16_tflops": 1.0}
    ) == (pytest.approx(25_165_824 / 1e12), "compute")


# -- the roofline reader on a hand-made trace --------------------------------

MS = 1e6


def hand_made_trace(kernel_ms=(40.0, 40.0, 40.0)):
    """Three steps of 100 ms on the device; each run holds 28 kernel events
    (one a layer) that share ``kernel_ms`` of it.  The host dispatched each
    step during the run before it (pipeline depth 2)."""
    device, modules, spans = [], [], []
    for k, total in enumerate(kernel_ms):
        run_start = (100 + 100 * k) * MS
        modules.append((f"jit_mixed_fn({7})", run_start, 100 * MS))
        modules.append((f"jit_scatter({k})", run_start - 2 * MS, 0.5 * MS))
        device.append(("while.3 f32[1]", run_start, 99 * MS))
        for layer in range(28):
            at = run_start + (1 + 3.5 * layer) * MS
            device.append(("ragged_attention_kernel.6 f32[128,64,12,128]", at, total / 28 * MS))
            device.append(("fusion.9 bf16[256,1536]", at + total / 28 * MS, 1 * MS))
        spans.append((
            "tpu-decode_0", "podmortem.sched.dispatch", run_start - 60 * MS, 2 * MS,
            {"step": 40 + k, "kv_pages": 700 + k, "qk_pairs": 45_000, "tokens": 128},
        ))
        spans.append(("tpu-decode_0", "podmortem.sched.wait", run_start - 50 * MS, 40 * MS, {"step": 39 + k}))
    return {
        "device": {"/device:TPU:0": device},
        "modules": {"/device:TPU:0": modules},
        "host": [("python", "bench.trace_slice", 30 * MS, 400 * MS)],
        "spans": sorted(spans, key=lambda s: s[2]),
    }


def test_dispatch_spans_join_the_step_programs_runs_by_order():
    events = hand_made_trace()
    joined = steps.kernel_steps(events, attn_kernel_share.PATTERN)
    assert [s["step"] for s in joined] == [40, 41, 42]
    assert [s["kv_pages"] for s in joined] == [700, 701, 702]
    assert [s["kernel_s"] for s in joined] == [pytest.approx(0.040)] * 3
    # the small program that ran between the steps is not the step
    assert len(steps.step_runs(events, steps.window_of(events))) == 3
    # a run cut by the slice's edge is left out, and the join is by order
    events["host"] = [("python", "bench.trace_slice", 150 * MS, 300 * MS)]
    joined = steps.kernel_steps(events, attn_kernel_share.PATTERN)
    assert [s["step"] for s in joined] == [42]  # one span left, first whole run
    # a program that writes no such span: nothing to join
    events["spans"] = [
        ("tpu-decode_0", "podmortem.sched_step", 90 * MS, 2 * MS, {"trace": "a"})
    ]
    assert steps.kernel_steps(events, attn_kernel_share.PATTERN) == []


def test_roofline_share_by_hand():
    joined = steps.kernel_steps(hand_made_trace(), attn_kernel_share.PATTERN)
    value, least = attn_kernel_roofline_share.share(joined, SHAPES, PEAKS)
    pages = 700 + 701 + 702
    moved = 28 * (pages * 65_536 + 3 * 128 * 12 * 128 * 6)
    assert least["compute"] == 0.0
    assert least["bandwidth"] == pytest.approx(moved / 819e9)
    assert value == pytest.approx(moved / 819e9 / 0.120)
    assert 0.04 < value < 0.041  # 4.8 ms of bytes in 120 ms of kernel


def test_roofline_share_is_one_when_the_kernel_takes_the_least_time():
    joined = steps.kernel_steps(hand_made_trace(), attn_kernel_share.PATTERN)
    for step in joined:  # a kernel exactly on its roofline
        moved, operations = kernel_cost.ragged_attention_cost(
            kv_pages=step["kv_pages"], qk_pairs=step["qk_pairs"],
            tokens=step["tokens"], **SHAPES,
        )
        step["kernel_s"] = kernel_cost.least_seconds(moved, operations, PEAKS)[0]
    value, _ = attn_kernel_roofline_share.share(joined, SHAPES, PEAKS)
    assert value == pytest.approx(1.0) and value <= 1.0 + 1e-12
    for step in joined:
        step["kernel_s"] = 0.0
    assert attn_kernel_roofline_share.share(joined, SHAPES, PEAKS)[0] is None


def test_roofline_reader_reads_nothing_off_the_chip():
    class Window:
        trace_dir = "/nonexistent"

    class Run:
        trace = None
        peaks = None
        window = Window()

    assert attn_kernel_roofline_share.read(Run()) is None


# -- the readers of the step records ------------------------------------------


@dataclasses.dataclass
class Record:
    tokens: int
    wall_ms: float
    host_ms: float
    wait_ms: float
    prefill_tokens: int
    kv_pages_walked: int


@dataclasses.dataclass
class OldRecord:
    """A step record of a program from before these fields."""

    tokens: int
    host_gap_ms: float = 0.0
    device_ms: float = 200.0


class Steps:
    def __init__(self, steps_):
        self.steps = steps_


def test_step_record_readers_by_hand():
    run = Steps([
        Record(tokens=128, wall_ms=100.0, host_ms=6.0, wait_ms=93.0, prefill_tokens=0, kv_pages_walked=700),
        Record(tokens=192, wall_ms=150.0, host_ms=10.0, wait_ms=138.0, prefill_tokens=64, kv_pages_walked=760),
    ])
    assert step_kv_pages_mean.read(run) == pytest.approx(730.0)
    assert step_tokens_mean.read(run) == pytest.approx(160.0)
    assert step_prefill_token_share.read(run) == pytest.approx(64 / 320)
    assert step_host_ms.read(run) == pytest.approx(8.0)
    assert step_host_wait_share.read(run) == pytest.approx(231.0 / 250.0)


@pytest.mark.parametrize("reader", [
    step_kv_pages_mean, step_prefill_token_share, step_host_ms, step_host_wait_share,
])
def test_readers_leave_their_metric_out_for_a_program_without_the_fields(reader):
    assert reader.read(Steps([OldRecord(tokens=128), OldRecord(tokens=64)])) is None
    assert reader.read(Steps([])) is None


def test_tokens_were_always_counted():
    assert step_tokens_mean.read(Steps([OldRecord(tokens=128), OldRecord(tokens=64)])) == 96.0
    assert step_tokens_mean.read(Steps([])) is None


# -- rehearsal: a traced run of the command, on the CPU -----------------------


def _run(workload, seconds):
    env = {k: v for k, v in os.environ.items() if not k.startswith("BENCH_")}
    env["OPERATOR_TPU_PLATFORM"] = "cpu"
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--manifest", MANIFEST,
         "--workload", workload, "--seed", "2147483659", "--seconds", seconds,
         "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )


@pytest.fixture(scope="module")
def traced():
    lines = {}
    for cell, seconds in (("tiny-test.storm", "4"), ("tiny-test.decode", "3")):
        proc = _run(cell, seconds)
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines[cell] = json.loads(proc.stdout.strip().splitlines()[-1])
    return lines


@pytest.mark.parametrize("cell", ["tiny-test.storm", "tiny-test.decode"])
def test_traced_rehearsal_prints_the_counters_and_spans_in_range(traced, cell):
    line = traced[cell]
    assert line["correct"] is True and line["failed"] == 0
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert COUNTERS_AND_SPANS <= set(metrics)
    assert all(math.isfinite(metrics[name]) for name in COUNTERS_AND_SPANS)
    assert 0.0 <= metrics["step_prefill_token_share"] <= 1.0
    assert 0.0 <= metrics["step_host_wait_share"] <= 1.0
    assert metrics["step_host_ms"] > 0.0
    assert metrics["step_kv_pages_mean"] > 0.0
    assert 0.0 < metrics["step_tokens_mean"] <= 32  # the tiny engine's budget
    # the host's part of a step cannot exceed the step
    assert metrics["step_host_ms"] <= metrics["step_ms_mean"] * 1.02
    units = {k: v["unit"] for k, v in line["metrics"].items()}
    assert (units["step_host_ms"], units["step_kv_pages_mean"]) == ("ms", "count")


def test_traced_rehearsal_prints_no_roofline_share_off_the_chip(traced):
    for line in traced.values():
        assert "attn_kernel_roofline_share" not in line["metrics"]
        assert line["device"]["platform"] == "cpu"


def test_the_storm_cell_spends_tokens_on_prompts(traced):
    storm = traced["tiny-test.storm"]["metrics"]
    assert storm["step_prefill_token_share"]["value"] > 0.05


def test_no_stretch_of_a_busy_serve_loop_is_without_a_span(traced):
    """Between the first and the last dispatch of the saturated cell's
    traced slice, the program's own spans (both threads) cover the wall:
    an idle gap of the device there has a ``podmortem.*`` span to be put
    down to, the thread hand-offs and the event loop's turn included."""
    from benchmark.trace import reduce as trace_reduce

    assert traced["tiny-test.decode"]["correct"] is True
    path = trace_reduce.newest_xplane(os.path.join(ROOT, "benchmark/out/trace/tiny-test.decode"))
    spans = steps.load(path)["spans"]
    assert {
        "podmortem.sched.plan", "podmortem.sched.pack", "podmortem.sched.dispatch",
        "podmortem.sched.wait", "podmortem.sched.commit", "podmortem.serve.step",
        "podmortem.serve.outcomes", "podmortem.serve.turn",
    } <= {name for _, name, _, _, _ in spans}
    dispatches = [s for s in spans if s[1] == steps.DISPATCH_SPAN]
    lo, hi = dispatches[0][2], dispatches[-1][2]
    covered, _ = trace_reduce._union_ns(
        (max(start, lo), min(start + dur, hi))
        for _, _, start, dur, _ in spans if start < hi and start + dur > lo
    )
    # what is left is the few statements between one span's end and the
    # next one's start, microseconds each
    assert covered >= 0.99 * (hi - lo)
