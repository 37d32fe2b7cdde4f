"""The host-clock metrics of the benchmark (PR 38), CPU only: the eleven
readers of the step clock's host side on hand-made records and on
records of a program from before the fields, their entries in
``BENCHMARK.json`` found by name, a rehearsal of ``benchmark/run.py``
under a manifest of its own (``rehearsal-hostclock.json``: the tracing
rehearsal's two tiny cells and entries with the eleven), and a traced run of
the engine whose slice shows the two halves of a dispatch inside it and a
forced collection.
"""

from __future__ import annotations

import asyncio
import dataclasses
import gc
import json
import math
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import manifest as manifest_mod  # noqa: E402
from benchmark.harness.manifest import Manifest  # noqa: E402

MANIFEST = "tests/benchmark/rehearsal-hostclock.json"
PARTS = ["plan", "pack", "put", "launch", "commit", "turn"]
#: name -> (unit, better, source, layer), as ISSUE 38's table has them
NEW = {
    **{f"step_{part}_ms": ("ms", "lower", "program_span", "admission and scheduler")
       for part in [*PARTS, "wake"]},
    "step_host_cpu_share": ("share", "higher", "program_counter", "admission and scheduler"),
    "step_gc_share": ("share", "lower", "program_counter", "admission and scheduler"),
    "step_stalls": ("count", "lower", "program_counter", "admission and scheduler"),
    "token_delivery_lag_mean_ms": ("ms", "lower", "program_span", "service"),
}
SEVEN_CELLS = [
    "qwen2.5-1.5b-int8.storm", "qwen2.5-1.5b-int8.decode", "qwen2.5-7b-int8.decode",
    "falcon-h1-34b-int8.decode", "qwen2.5-7b-int8.storm", "ouro-2.6b-int8.decode",
    "sdar-30b-a3b-int8.decode",
]


@pytest.fixture
def in_root():
    before = os.getcwd()
    os.chdir(ROOT)  # a manifest finds its files from the checkout's root
    yield ROOT
    os.chdir(before)


# -- the readers on hand-made records ----------------------------------------


@dataclasses.dataclass
class Record:
    """The fields of a step record that the eleven read."""

    wall_ms: float
    host_ms: float
    xfer_ms: float = 1.0
    plan_ms: float = 0.0
    pack_ms: float = 0.0
    put_ms: float = 0.0
    launch_ms: float = 0.0
    commit_ms: float = 0.0
    turn_ms: float = 0.0
    wake_ms: float = 0.0
    cpu_ms: float = 0.0
    gc_ms: float = 0.0
    delivered: int = 0
    deliver_lag_ms: float = 0.0
    stall: bool = False


@dataclasses.dataclass
class OldRecord:
    """A step record of PR 37's program: four parts, of which ``pack_ms``
    held the puts and the launch too, and none of the other fields."""

    wall_ms: float = 40.0
    host_ms: float = 19.0
    wait_ms: float = 20.0
    xfer_ms: float = 1.0
    plan_ms: float = 2.0
    pack_ms: float = 6.0
    commit_ms: float = 8.0
    turn_ms: float = 2.5


class Steps:
    def __init__(self, steps_):
        self.steps = steps_


BY_HAND = Steps([
    Record(wall_ms=40.0, host_ms=19.0, plan_ms=2.0, pack_ms=3.0, put_ms=2.5,
           launch_ms=1.5, commit_ms=8.0, turn_ms=2.0, wake_ms=3.0, cpu_ms=16.0,
           gc_ms=0.5, delivered=128, deliver_lag_ms=256.0),
    Record(wall_ms=160.0, host_ms=29.0, plan_ms=4.0, pack_ms=5.0, put_ms=3.5,
           launch_ms=2.5, commit_ms=10.0, turn_ms=4.0, wake_ms=5.0, cpu_ms=24.0,
           gc_ms=99.5, delivered=72, deliver_lag_ms=344.0, stall=True),
])
WANT = {
    "step_plan_ms": 3.0, "step_pack_ms": 4.0, "step_put_ms": 3.0,
    "step_launch_ms": 2.0, "step_commit_ms": 9.0, "step_turn_ms": 3.0,
    "step_wake_ms": 4.0,
    "step_host_cpu_share": 40.0 / 50.0,  # of host + xfer: 20 and 30
    "step_gc_share": 100.0 / 200.0,
    "step_stalls": 1,
    "token_delivery_lag_mean_ms": 600.0 / 200,
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_by_hand_and_on_records_without_the_fields(in_root, name):
    reader = Manifest(os.path.join(ROOT, "BENCHMARK.json")).module("layer_metrics", name)
    assert reader.read(BY_HAND) == pytest.approx(WANT[name])
    # PR 37's records have plan, pack, commit and turn under other
    # meanings: not one of the eleven reads them
    assert reader.read(Steps([OldRecord(), OldRecord()])) is None
    assert reader.read(Steps([])) is None
    # a window that streamed nothing has no delivery to age
    quiet = Steps([Record(wall_ms=10.0, host_ms=4.0, commit_ms=4.0)])
    if name == "token_delivery_lag_mean_ms":
        assert reader.read(quiet) is None
    else:
        assert math.isfinite(reader.read(quiet))


def test_the_six_parts_read_by_hand_tile_the_host_mean(in_root):
    from benchmark.layer_metrics import step_host_ms

    manifest = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    parts = sum(
        manifest.module("layer_metrics", f"step_{part}_ms").read(BY_HAND) for part in PARTS
    )
    assert parts == pytest.approx(step_host_ms.read(BY_HAND)) == pytest.approx(24.0)


# -- the manifests ------------------------------------------------------------


def check_the_entries(manifest, cells):
    """Each of the eleven is an entry, found by name, that agrees with its
    reader and with ISSUE 38's table, names a layer the manifest already
    had, and lists ``cells``, each of which reports the metric it moves."""
    from operator_tpu.obs.steptrace import StepRecord

    by_name = {m["name"]: m for m in manifest.doc["per_layer"]}
    others = {m["layer"] for m in manifest.doc["per_layer"] if m["name"] not in NEW}
    for name, (unit, better, source, layer) in NEW.items():
        reader = manifest.module("layer_metrics", name)
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "token_gap_mean_ms", "workloads": cells,
        }, name
        assert (reader.NAME, reader.UNIT, reader.SOURCE, reader.LAYER, reader.MOVES) == (
            name, unit, source, layer, "token_gap_mean_ms",
        )
        assert manifest_mod.NAME.match(name) and manifest_mod.UNIT.match(unit)
        assert layer in others
        for cell in cells:
            assert "token_gap_mean_ms" in {
                m["name"] for m in manifest.metrics_for("end_to_end", cell)
            }
    fields = {f.name for f in dataclasses.fields(StepRecord)}
    assert {f.name for f in dataclasses.fields(Record)} <= fields


def test_the_eleven_are_manifest_entries_of_all_seven_cells(in_root):
    manifest = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    check_the_entries(manifest, SEVEN_CELLS)
    assert SEVEN_CELLS == [c["name"] for c in manifest.doc["workloads"]][:7]


def test_the_rehearsal_manifest_is_the_tracing_rehearsal_plus_the_eleven(in_root):
    """(The tracing rehearsal is the rehearsal with ``step_host_ms``, which
    the six parts are held against.)"""
    base = Manifest(os.path.join(ROOT, "tests/benchmark/rehearsal-tracing.json")).doc
    mine = Manifest(os.path.join(ROOT, MANIFEST))
    assert {k: v for k, v in mine.doc.items() if k != "per_layer"} == {
        k: v for k, v in base.items() if k != "per_layer"
    }
    assert mine.doc["per_layer"][: len(base["per_layer"])] == base["per_layer"]
    added = mine.doc["per_layer"][len(base["per_layer"]):]
    assert sorted(m["name"] for m in added) == sorted(NEW)
    check_the_entries(mine, ["tiny-test.storm", "tiny-test.decode"])


# -- rehearsal: a traced run of the command, on the CPU -----------------------


def _run(workload, seconds):
    env = {k: v for k, v in os.environ.items() if not k.startswith("BENCH_")}
    env["OPERATOR_TPU_PLATFORM"] = "cpu"
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--manifest", MANIFEST,
         "--workload", workload, "--seed", "2147483693", "--seconds", seconds,
         "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )


@pytest.fixture(scope="module")
def rehearsed():
    lines = {}
    for cell, seconds in (("tiny-test.storm", "4"), ("tiny-test.decode", "3")):
        proc = _run(cell, seconds)
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines[cell] = json.loads(proc.stdout.strip().splitlines()[-1])
    return lines


@pytest.mark.parametrize("cell", ["tiny-test.storm", "tiny-test.decode"])
def test_rehearsal_prints_the_eleven_and_the_parts_tile_the_host(rehearsed, cell):
    line = rehearsed[cell]
    assert line["correct"] is True and line["failed"] == 0
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(metrics)
    assert all(math.isfinite(metrics[name]) for name in NEW)
    assert {name: line["metrics"][name]["unit"] for name in NEW} == {
        name: unit for name, (unit, _, _, _) in NEW.items()
    }
    parts = sum(metrics[f"step_{part}_ms"] for part in PARTS)
    assert parts == pytest.approx(metrics["step_host_ms"], rel=0.02)
    assert all(metrics[f"step_{part}_ms"] >= 0.0 for part in PARTS)
    assert 0.0 < metrics["step_put_ms"] and 0.0 < metrics["step_launch_ms"]
    assert 0.0 < metrics["step_wake_ms"] <= metrics["step_commit_ms"]
    assert 0.0 < metrics["step_host_cpu_share"] <= 1.05  # two clocks: a little play
    assert 0.0 <= metrics["step_gc_share"] < 1.0
    assert metrics["step_stalls"] >= 0 and metrics["step_stalls"] == int(metrics["step_stalls"])
    assert metrics["token_delivery_lag_mean_ms"] >= 0.0


# -- a traced slice of the engine, on the CPU ---------------------------------


@pytest.fixture(scope="module")
def traced_spans(tmp_path_factory):
    """The program's own spans from a profiler trace of the tiny engine
    serving three requests, with one collection forced while it served."""
    import jax
    import jax.numpy as jnp

    from benchmark.trace import reduce as trace_reduce
    from benchmark.trace import steps
    from operator_tpu.models import TINY_TEST, init_params
    from operator_tpu.models.tokenizer import ByteTokenizer
    from operator_tpu.serving.engine import BatchedGenerator, SamplingParams, ServingEngine
    from operator_tpu.serving.sched import Scheduler
    from operator_tpu.utils.timing import MetricsRegistry

    params = init_params(TINY_TEST, jax.random.PRNGKey(0), dtype=jnp.float32)
    generator = BatchedGenerator(
        params, TINY_TEST, ByteTokenizer(), paged=True, cache_dtype=jnp.float32,
        metrics=MetricsRegistry(), max_slots=4, max_seq=128, page_size=16,
    )
    sched = Scheduler(generator, chunk=16, token_budget=32)
    sched.precompile()
    engine = ServingEngine(generator, scheduler=sched)
    trace_dir = str(tmp_path_factory.mktemp("hostclock-trace"))

    async def scenario():
        await engine.start()
        sampling = SamplingParams(max_tokens=12, temperature=0.0, stop_on_eos=False)
        seen = []
        with jax.profiler.trace(trace_dir):
            serving = asyncio.gather(*[
                engine.generate(prompt, sampling, on_partial=seen.append)
                for prompt in ("pod crashed with exit code 137", "oom", "three")
            ])
            await asyncio.sleep(0.02)
            gc.collect()
            await serving
        await engine.close()

    asyncio.run(scenario())
    spans = steps.load(trace_reduce.newest_xplane(trace_dir))["spans"]
    return spans, generator.step_clock.ring.records()


def test_put_and_launch_lie_inside_their_dispatch_with_its_step(traced_spans):
    spans, records = traced_spans
    dispatches = [s for s in spans if s[1] == "podmortem.sched.dispatch"]
    assert len(dispatches) >= 5
    for thread, _, start, dur, stats in dispatches:
        inside = [
            (name, s, d) for t, name, s, d, st in spans
            if t == thread and name in ("podmortem.sched.put", "podmortem.sched.launch")
            and st["step"] == stats["step"]
        ]
        assert [name for name, _, _ in inside] == [
            "podmortem.sched.put", "podmortem.sched.launch",
        ]
        (_, put_start, put_dur), (_, launch_start, launch_dur) = inside
        assert start <= put_start and put_start + put_dur <= launch_start
        assert launch_start + launch_dur <= start + dur
    # the commit's span says at its end what its hand-overs took
    commits = [s for s in spans if s[1] == "podmortem.sched.commit"]
    wakeups = {r.seq: r.wakeups for r in records}
    assert commits and all(
        st["wakeups"] == wakeups[st["step"]] and st["wake_us"] >= 0
        for _, _, _, _, st in commits if st["step"] in wakeups
    )
    assert sum(st["wakeups"] for _, _, _, _, st in commits) > 0


def test_a_forced_collection_is_a_span_and_in_the_records(traced_spans):
    spans, records = traced_spans
    collections = [s for s in spans if s[1] == "podmortem.gc"]
    oldest = [s for s in collections if s[4]["gen"] == 2]
    assert oldest, sorted({s[1] for s in spans})
    assert all(dur > 0 for _, _, _, dur, _ in oldest)
    # and the step records hold it
    assert sum(r.gc_gen2 for r in records) >= 1
    assert sum(r.gc_ms for r in records) > 0.0
