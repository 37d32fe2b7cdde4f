"""What PR 34 adds to the benchmark, CPU only: the Ouro configuration's
file against the guide's catalog and the program, the manifest's new
entries found by name (on the real manifest and on the rehearsal of a later
append), the two new readers on hand-made runs (and on a program without
the count), the reference's own weights against the program's tree, and a
rehearsal of ``benchmark/run.py`` under a manifest of its own
(``rehearsal-ouro.json``: the family's tiny preset read through
``ouro_f32``, and through the same reference told of one pass too few),
added as files only.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness.manifest import Manifest, load_json  # noqa: E402
from benchmark.layer_metrics import (  # noqa: E402
    step_pass_stream_floor_share,
    step_passes_mean,
    step_weight_floor_share,
)
from benchmark.trace import steps  # noqa: E402

# the rehearsal of a later PR's append, and its by-name helper (their
# directory is on the path: pytest put it there to import this file)
from test_falcon_h1_benchmark import appended, by_name, in_root  # noqa: E402,F401

MANIFEST = "tests/benchmark/rehearsal-ouro.json"
CONFIG = "ouro-2.6b-int8"
CELL = "ouro-2.6b-int8.decode"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = {"step_passes_mean", "step_pass_stream_floor_share"}
#: the per-layer metrics that list the ``.decode`` cells
DECODE_METRICS = {
    "attn_kernel_roofline_share", "step_kv_pages_mean", "step_tokens_mean",
    "step_prefill_token_share", "step_host_ms", "step_host_wait_share",
    "step_sampled_rows_mean",
}


# -- the configuration's file and the manifest --------------------------------


def test_the_configuration_file_holds_the_published_config_whole(in_root):
    from operator_tpu.models import get_config
    from operator_tpu.models.configs import OuroConfig

    manifest = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    doc = manifest.config(CONFIG)
    arch = doc["architecture"]
    assert doc["reduced"] == [] and "published" not in doc
    # `architecture` repeats top-level keys, never another value
    assert all(doc[key] == value for key, value in arch.items())
    assert (arch["num_hidden_layers"], arch["total_ut_steps"]) == (48, 4)
    for key in (
        "bias", "norms", "final norm", "plane order", "rope", "kv dtype",
        "max positions", "kv_pages", "exit gate",
    ):
        assert len(doc["assumed"][key]) > 40, key
    engine = doc["engine"]
    slots, pages = engine["max_batch_size"], engine["kv_pages"]
    # as many rows as the pool holds at the mix's longest request, 11 pages
    assert pages % 8 == 0 and slots == (pages - 1) // 11
    assert "spec_decode" not in engine and "kv_prefix_cache" not in engine  # both stay on
    assert "one chip holds the whole model" in doc["deployment"]
    # the program is held to `architecture` through the weights module's table
    reference = manifest.module("reference", doc["reference"])
    table = manifest.module("reference", reference.WEIGHTS).PROGRAM_CONFIG
    program = get_config(doc["model_id"])
    assert isinstance(program, OuroConfig) and set(arch) == set(table)
    assert all(arch[key] == getattr(program, attribute) for key, attribute in table.items())
    assert program.kv_planes == arch["total_ut_steps"] * arch["num_hidden_layers"] == 192
    probe = doc["probe"]
    assert "PROVISIONAL" not in json.dumps(probe) and probe["readings"]["measured_by"]
    if not os.path.isfile(CATALOG):
        pytest.skip("the guides' catalog is not on this machine")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    assert doc["source"] == row["source_url"]
    assert {k: doc.get(k, "absent") for k in row["config"]} == row["config"]


def check_the_ouro_entries(manifest):
    """What PR 34 added, wherever in its sections it stands today."""
    doc = manifest.doc
    config = by_name(doc["configs"])[CONFIG]
    assert config["reduced"] == [] and config["file"] == "benchmark/configs/ouro-2.6b-int8.json"
    cell = by_name(doc["workloads"])[CELL]
    assert cell == {**cell, "config": CONFIG, "traffic": "decode-ouro", "chips": 1}
    per_layer = by_name(doc["per_layer"])
    assert NEW <= set(per_layer)
    mine = {m["name"] for m in manifest.metrics_for("per_layer", CELL)}
    assert NEW | DECODE_METRICS | {
        "attn_kernel_share", "step_weight_floor_share", "step_device_ms",
        "device_idle_share", "peak_hbm_gb", "midrun_compiles",
    } <= mine
    assert not mine & {"ssm_kernel_share", "ssm_kernel_roofline_share", "step_state_rows_mean"}
    assert {m["name"] for m in manifest.metrics_for("end_to_end", CELL)} == {
        "token_gap_mean_ms", "out_tokens_per_s", "setup_s",
    }
    for name in sorted(DECODE_METRICS):
        assert CELL in per_layer[name]["workloads"]
    for entry in (per_layer[name] for name in sorted(NEW)):
        assert entry["workloads"] == [CELL] and entry["layer"] == "mixed step"
        reader = manifest.module("layer_metrics", entry["name"])
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
            entry["name"], entry["unit"], entry["layer"], entry["moves"], entry["source"],
        )
    # the mix is the `.decode` cells' with one key changed
    theirs, mix = manifest.traffic("decode"), manifest.traffic("decode-ouro")
    assert mix["greedy"]["every"] == 2 and theirs["greedy"]["every"] == 8
    mix["greedy"]["every"] = 8
    assert mix == theirs


def test_the_manifest_gains_one_configuration_one_cell_and_two_metrics(in_root):
    check_the_ouro_entries(Manifest(os.path.join(ROOT, "BENCHMARK.json")))


def test_a_later_append_leaves_the_ouro_entries_where_a_name_finds_them(appended):
    check_the_ouro_entries(appended)
    mine = {m["name"] for m in appended.metrics_for("per_layer", "tiny-test.decode")}
    assert not mine & NEW


# -- the two readers, by hand ---------------------------------------------------


@dataclasses.dataclass
class Record:
    tokens: int
    passes: int | None = None


@dataclasses.dataclass
class OldRecord:
    """A step record of a program from before the field."""

    tokens: int


class Steps:
    def __init__(self, steps_):
        self.steps = steps_


def test_passes_reader_by_hand():
    assert step_passes_mean.read(Steps([Record(10, 4), Record(64, 4), Record(3, 4)])) == 4.0
    assert step_passes_mean.read(Steps([Record(10, 1), Record(64, 1)])) == 1.0
    # an engine that does not say writes None; an older program has no field
    assert step_passes_mean.read(Steps([Record(10), Record(64)])) is None
    assert step_passes_mean.read(Steps([OldRecord(10)])) is None
    assert step_passes_mean.read(Steps([])) is None


def dispatch(start, **stats):
    return ("python3", steps.DISPATCH_SPAN, float(start), 10.0, stats)


def test_the_pass_stream_floor_by_hand(monkeypatch, tmp_path):
    import numpy as np

    spans = [
        dispatch(0, step=1, kv_pages=50, passes=4), dispatch(100, step=2, kv_pages=51, passes=4),
        ("python3", "podmortem.sched.plan", 50.0, 5.0, {"passes": 9}),  # not a dispatch
    ]
    assert step_pass_stream_floor_share.passes_of(spans) == 4.0
    # a program from before the argument: nothing to read
    assert step_pass_stream_floor_share.passes_of([dispatch(0, step=1, kv_pages=50)]) is None
    assert step_pass_stream_floor_share.passes_of([]) is None
    peaks = {"hbm_gbps": 819.0, "bf16_tflops": 197.0}
    # 2.47 GB of layers four times and 0.40 GB once: 12.55 ms
    floor = step_pass_stream_floor_share.floor_ms(4.0, 2_470_000_000, 400_000_000, peaks)
    assert floor == pytest.approx((4 * 2.47e9 + 0.4e9) / 819e9 * 1e3) == pytest.approx(12.552, abs=1e-3)

    params = {
        "embed": np.zeros((100, 8), np.float16),
        "layers": {"wq": {"q": np.zeros((3, 8, 8), np.int8), "s": np.zeros((3, 8), np.float32)}},
    }
    layer_bytes, other_bytes = 3 * 64 + 3 * 8 * 4, 100 * 8 * 2

    class Handle:
        def parameters(self):
            return params

        def param_bytes(self):
            return layer_bytes + other_bytes

    class Window:
        trace_dir = str(tmp_path)

    class Run:
        handle, window, peaks = Handle(), Window(), {"hbm_gbps": 819.0}
        trace = {"programs": [("jit_mixed_fn", 50, 4.0)]}  # 80 ms a step

    trace_file = tmp_path / "made_up.xplane.pb"
    trace_file.write_bytes(b"")
    monkeypatch.setattr(
        step_pass_stream_floor_share.trace_reduce, "newest_xplane", lambda _: str(trace_file)
    )
    monkeypatch.setattr(step_pass_stream_floor_share.steps, "load", lambda _: {"spans": spans})
    want = (4 * layer_bytes + other_bytes) / 819e9 * 1e3 / 80.0
    assert step_pass_stream_floor_share.read(Run()) == pytest.approx(want)
    # "the parameters once" reads the same tree lower, by its own definition
    once = step_weight_floor_share.read(Run())
    assert once == pytest.approx((layer_bytes + other_bytes) / 819e9 * 1e3 / 80.0) and once < want
    # the parent's program: a dispatch span without `passes` -> left out
    monkeypatch.setattr(
        step_pass_stream_floor_share.steps, "load",
        lambda _: {"spans": [dispatch(0, step=1, kv_pages=50)]},
    )
    assert step_pass_stream_floor_share.read(Run()) is None
    Run.peaks = None  # off the chip
    assert step_pass_stream_floor_share.read(Run()) is None


# -- the reference's own weights -------------------------------------------------


def test_the_references_own_int8_weights_are_the_programs_bit_for_bit(in_root):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from operator_tpu.models import get_config
    from operator_tpu.models.quant import init_params_quantized

    mine_manifest = Manifest(os.path.join(ROOT, MANIFEST))
    doc = mine_manifest.config("tiny-ouro")
    own = mine_manifest.module("reference", "ouro_f32_weights")
    mine = own.make(doc)
    program = init_params_quantized(get_config(doc["model_id"]), jax.random.PRNGKey(0))
    theirs = own.adapt(program, doc)
    assert set(mine.layers) == set(theirs.layers) == set(own.MATRICES + own.VECTORS)
    for name, leaf in mine.layers.items():
        other = theirs.layers[name]
        pairs = (
            [(leaf["q"], other["q"]), (leaf["s"], other["s"])]
            if isinstance(leaf, dict) else [(leaf, other)]
        )
        for a, b in pairs:
            assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b)), name
    # every leaf outside the layers, the exit gate's two among them
    assert set(theirs.leaves) - {"layers"} == {"embed", "lm_head"} | set(own.TOP)
    for name in ("embed", "lm_head") + own.TOP:
        a, b = mine.leaves[name], theirs.leaves[name]
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b)), name
    # no norm of the recipe is all ones: a dropped or a swapped one cannot hide
    for name in own.VECTORS:
        assert float(jnp.abs(mine.layers[name].astype(jnp.float32) - 1.0).max()) > 0.1


# -- the rehearsal ---------------------------------------------------------------


def test_the_rehearsal_adds_files_only_under_the_tests(in_root):
    mine = Manifest(os.path.join(ROOT, MANIFEST))
    assert [c["name"] for c in mine.doc["workloads"]] == [
        "tiny-ouro.decode", "tiny-ouro-two-passes.decode",
    ]
    for item in mine.doc["configs"]:
        assert item["file"].startswith("tests/benchmark/configs/")
        config = mine.config(item["name"])
        assert config["model_id"] == "tiny-ouro" and config["reference"] == "ouro_f32"
        assert config["engine"]["spec_decode"] and config["engine"]["kv_prefix_cache"]
        assert 0 < config["probe"]["limit"] < 1.0
    right = mine.module("reference", "ouro_f32")
    assert right.__file__ == os.path.join(ROOT, "benchmark/reference/ouro_f32.py")
    # the one the program is held to says three passes, the wrong one two
    from operator_tpu.models import get_config

    assert mine.config("tiny-ouro")["architecture"]["total_ut_steps"] == 3
    assert get_config("tiny-ouro").total_ut_steps == 3
    assert mine.config("tiny-ouro-two-passes")["architecture"]["total_ut_steps"] == 2


def _run(workload, trace):
    env = {k: v for k, v in os.environ.items() if k != "OPERATOR_TPU_MODEL"}
    env["OPERATOR_TPU_PLATFORM"] = "cpu"
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--manifest", MANIFEST,
         # 6 s, not the 3 s the limits were read at: beside the whole suite a window of 3 s
         # finished 6 requests, fewer greedy ones than the 4 the reference samples
         "--workload", workload, "--seed", "11", "--seconds", "6", "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )


@pytest.fixture(scope="module")
def rehearsals():
    lines = {}
    for key, workload, trace in (
        ("right", "tiny-ouro.decode", 0), ("traced", "tiny-ouro.decode", 1),
        ("wrong", "tiny-ouro-two-passes.decode", 0),
    ):
        proc = _run(workload, trace)
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines[key] = json.loads(proc.stdout.strip().splitlines()[-1])
    return lines


@pytest.mark.parametrize("key, correct", [("right", True), ("traced", True), ("wrong", False)])
def test_the_ouro_reference_decides_correct(rehearsals, key, correct):
    line = rehearsals[key]
    assert line["correct"] is correct
    assert line["failed"] == 0 and line["attempted"] > 0  # the run itself is whole
    gap = line["compared"]["served_gap_max"]
    assert (gap["value"] <= gap["limit"]) is correct
    assert line["compared"]["served_requests_missing"]["value"] == 0
    assert line["compared"]["window_requests_wrong"]["value"] == 0
    if not correct:  # a pass left out is an order-one fault, not a near miss
        assert gap["value"] > 5 * gap["limit"]


def test_the_traced_rehearsal_counts_passes_and_writes_no_device_metric(rehearsals):
    metrics = rehearsals["traced"]["metrics"]
    assert metrics["step_passes_mean"] == {"value": 3.0, "unit": "count"}
    assert "step_pass_stream_floor_share" not in metrics  # a device number: not on the CPU
    assert metrics["midrun_compiles"]["value"] == 0
