"""What PR 29 adds to the benchmark, CPU only: the Falcon-H1 configuration's
file against the guide's catalog and the program, the scan kernel's cost
from shapes against hand values, the three new readers on a hand-made
trace and hand-made step records (and on a program without the fields),
and a rehearsal of ``benchmark/run.py`` under a manifest of its own
(``rehearsal-falcon-h1.json``: the family's tiny preset read through
``falcon_h1_f32``, and through a deliberately wrong reference), added as
files only.
"""

from __future__ import annotations

import copy
import dataclasses
import inspect
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
    attn_kernel_share,
    ssm_kernel_roofline_share,
    ssm_kernel_share,
    step_state_rows_mean,
)
from benchmark.trace import kernel_cost, ssm_cost  # noqa: E402

# the benchmark's other test files, as modules (their directory is on the
# path: pytest put it there to import this file)
import test_benchmark as rules  # noqa: E402
import test_step_sampled_rows  # noqa: E402
import test_step_tracing  # noqa: E402

MANIFEST = "tests/benchmark/rehearsal-falcon-h1.json"
CELL = "falcon-h1-34b-int8.decode"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = {"ssm_kernel_share", "ssm_kernel_roofline_share", "step_state_rows_mean"}
#: the configuration's scan shapes: six layers, 32 heads x 128 x 256 in
#: float32, two groups, bfloat16 tokens
SHAPES = {
    "layers": 6, "heads": 32, "head_dim": 128, "d_state": 256, "groups": 2,
    "state_itemsize": 4, "token_itemsize": 2,
}
PEAKS = {"hbm_gbps": 819.0, "bf16_tflops": 197.0}


@pytest.fixture(scope="module")
def in_root():
    before = os.getcwd()
    os.chdir(ROOT)
    yield ROOT
    os.chdir(before)


# -- the configuration's file and the manifest --------------------------------


def test_the_configuration_file_holds_the_published_config_cut_in_depth_only(in_root):
    doc = load_json(os.path.join(ROOT, "benchmark/configs/falcon-h1-34b-int8.json"))
    arch = doc["architecture"]
    # `architecture` repeats top-level keys, never another value
    assert all(doc[key] == value for key, value in arch.items())
    assert doc["reduced"] == ["num_hidden_layers"] and doc["published"] == {"num_hidden_layers": 72}
    assert arch["num_hidden_layers"] == 6 >= 4  # the guide's floor
    assert "layers shared over 1 chip" in doc["deployment"]
    for key in ("segment order", "key_multiplier", "state dtype", "slots"):
        assert len(doc["assumed"][key]) > 40
    engine = doc["engine"]
    assert engine["max_batch_size"] == 128 and engine["kv_pages"] == 1536
    assert engine["spec_decode"] is False and engine["kv_prefix_cache"] is False
    if not os.path.isfile(CATALOG):
        pytest.skip("the guides' catalog is not on this machine")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Falcon-H1-34B-Instruct")
    assert doc["source"] == row["source_url"]
    differing = {k for k, v in row["config"].items() if doc.get(k, "absent") != v}
    assert differing == {"num_hidden_layers"}  # every other key as published


def by_name(entries):
    """A manifest section's entries by ``name``: a later PR appends to
    ``configs``, ``workloads`` and ``per_layer``, so a test finds an entry
    by its name and never by its position."""
    return {entry["name"]: entry for entry in entries}


def check_the_falcon_entries(manifest):
    """What PR 29 added, wherever in its sections it stands today."""
    doc = manifest.doc
    assert "falcon-h1-34b-int8" in by_name(doc["configs"])
    cell = by_name(doc["workloads"])[CELL]
    assert cell == {**cell, "name": CELL, "traffic": "decode", "chips": 1}
    per_layer = by_name(doc["per_layer"])
    assert NEW <= set(per_layer)
    mine = {m["name"] for m in manifest.metrics_for("per_layer", CELL)}
    assert NEW | {
        "attn_kernel_roofline_share", "step_kv_pages_mean", "step_tokens_mean",
        "step_prefill_token_share", "step_host_ms", "step_host_wait_share",
        "attn_kernel_share", "device_idle_share", "peak_hbm_gb", "midrun_compiles",
    } <= mine
    assert {m["name"] for m in manifest.metrics_for("end_to_end", CELL)} == {
        "token_gap_mean_ms", "out_tokens_per_s", "setup_s",
    }
    for entry in (per_layer[name] for name in sorted(NEW)):
        assert entry["workloads"] == [CELL] and entry["layer"] == "kernels"
        reader = manifest.module("layer_metrics", entry["name"])
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
            entry["name"], entry["unit"], entry["layer"], entry["moves"], entry["source"],
        )
    # the traffic file is the other `.decode` cells', unedited
    assert manifest.traffic("decode")["greedy"]["every"] == 8


def test_the_manifest_gains_one_configuration_one_cell_and_three_metrics(in_root):
    check_the_falcon_entries(Manifest(os.path.join(ROOT, "BENCHMARK.json")))


# -- the contract a later PR adds under: append, and nothing that is here moves --

#: what a later PR brings, as the rehearsal's files: one configuration, one
#: cell, one per-layer metric with a reader of its own
APPENDED_CONFIG = {
    "name": "tiny-test", "source": "operator_tpu/models/configs.py TINY_TEST (a test preset, never a cell)",
    "file": "tests/benchmark/configs/tiny-test.json", "reduced": [],
    "why": "stands for the configuration a later PR appends",
}
APPENDED_CELL = {
    "name": "tiny-test.decode", "config": "tiny-test", "traffic": "tiny-decode", "chips": 1,
    "why": "stands for the cell a later PR appends",
}
APPENDED_METRIC = {
    "name": "appended_requests_finished", "unit": "count", "better": "higher",
    "source": "host_clock", "layer": "service", "moves": "token_gap_mean_ms",
    "workloads": ["tiny-test.decode"],
}
#: every rule of ``test_benchmark.py`` that takes a manifest and nothing
#: else, by name: a rule added there later is held here too
MANIFEST_RULES = sorted(
    name for name, rule in vars(rules).items()
    if name.startswith("test_") and list(inspect.signature(rule).parameters) == ["manifest"]
)


@pytest.fixture(scope="module")
def appended(in_root, tmp_path_factory):
    """``BENCHMARK.json`` as a later PR leaves it: a copy, in a temporary
    directory, with one more configuration, cell and per-layer entry
    **appended**; its files are found from the checkout's root, as the
    real one's are."""
    before = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    doc = copy.deepcopy(before)
    doc["configs"].append(APPENDED_CONFIG)
    doc["workloads"].append(APPENDED_CELL)
    doc["per_layer"].append(APPENDED_METRIC)
    path = tmp_path_factory.mktemp("appended") / "BENCHMARK.json"
    path.write_text(json.dumps(doc, indent=2))
    manifest = Manifest(str(path))
    # appending moved nothing that was there
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        assert manifest.doc[section][:len(before[section])] == before[section]
    return manifest


@pytest.mark.parametrize("rule", MANIFEST_RULES)
def test_appending_to_the_manifest_breaks_no_manifest_rule(appended, rule):
    getattr(rules, rule)(appended)


def test_appending_to_the_manifest_breaks_no_test_that_finds_by_name(appended):
    check_the_falcon_entries(appended)
    test_step_tracing.check_the_counter_metrics(appended)
    test_step_sampled_rows.check_the_entry(appended)
    cell = appended.cell("tiny-test.decode")
    assert cell == APPENDED_CELL and appended.config("tiny-test")["model_id"] == "tiny-test"
    mine = {m["name"] for m in appended.metrics_for("per_layer", "tiny-test.decode")}
    assert "appended_requests_finished" in mine and not mine & NEW
    reader = appended.module("layer_metrics", "appended_requests_finished")
    assert reader.NAME == APPENDED_METRIC["name"] and callable(reader.read)


def test_the_kernels_names_keep_the_two_shares_apart():
    name = "ssm_scan_kernel.13 f32[4,256,8,128]"
    assert ssm_kernel_share.PATTERN.search(name)
    assert not attn_kernel_share.PATTERN.search(name)
    assert not ssm_kernel_share.PATTERN.search("ragged_attention_kernel.13 bf16[128,64,20,128]")
    from operator_tpu.ops.ssm_scan import KERNEL_NAME

    assert ssm_kernel_share.PATTERN.search(KERNEL_NAME)


# -- the scan's cost from shapes ----------------------------------------------


def test_cost_of_one_decode_step_by_hand():
    """128 slots each with one token, six layers: every slot's 4.19 MB of
    state in and out, and a token's x, z, y (4096 each), B, C (512 each) in
    bfloat16 and dt (32) in float32."""
    assert ssm_cost.state_bytes(32, 128, 256) == 4_194_304
    moved, operations = ssm_cost.ssm_scan_cost(state_rows=128, tokens=128, **SHAPES)
    per_token = (3 * 4096 + 2 * 512) * 2 + 32 * 4
    assert per_token == 26_752
    assert moved == 6 * (128 * 2 * 4_194_304 + 128 * per_token) == 6_462_996_480
    assert operations == 6 * 5 * 32 * 128 * 256 * 128
    seconds, bound = kernel_cost.least_seconds(moved, operations, PEAKS)
    assert bound == "bandwidth" and seconds == pytest.approx(moved / 819e9)
    assert 7.8e-3 < seconds < 7.9e-3  # the state update: 7.9 ms of a step


def test_state_bytes_follow_the_rows_not_the_tokens():
    one_chunk, _ = ssm_cost.ssm_scan_cost(state_rows=1, tokens=64, **SHAPES)
    one_token, _ = ssm_cost.ssm_scan_cost(state_rows=1, tokens=1, **SHAPES)
    assert one_chunk - one_token == 6 * 63 * 26_752
    assert ssm_cost.ssm_scan_cost(state_rows=0, tokens=0, **SHAPES) == (0, 0)


# -- the readers on a hand-made trace and hand-made records ----------------------

MS = 1e6


def hand_made_trace(kernel_ms=(12.0, 12.0, 12.0)):
    """Three steps of 40 ms on the device; each run holds six scan-kernel
    events (one a layer) that share ``kernel_ms`` of it, and six attention
    kernel events beside them."""
    device, modules, spans = [], [], []
    for k, total in enumerate(kernel_ms):
        run_start = (100 + 50 * k) * MS
        modules.append((f"jit_mixed_fn({7})", run_start, 40 * MS))
        device.append(("while.3 f32[1]", run_start, 39 * MS))
        for layer in range(6):
            at = run_start + (1 + 6 * layer) * MS
            device.append(("ssm_scan_kernel.13 f32[4,256,8,128]", at, total / 6 * MS))
            device.append(("ragged_attention_kernel.13 bf16[128,64,20,128]", at + 3 * MS, 1 * MS))
        spans.append((
            "tpu-decode_0", "podmortem.sched.dispatch", run_start - 30 * MS, 2 * MS,
            {"step": 40 + k, "kv_pages": 400, "qk_pairs": 30_000, "tokens": 130 + k,
             "state_rows": 126 + k},
        ))
    return {
        "device": {"/device:TPU:0": device},
        "modules": {"/device:TPU:0": modules},
        "host": [("python", "bench.trace_slice", 30 * MS, 400 * MS)],
        "spans": sorted(spans, key=lambda s: s[2]),
    }


def test_the_scan_join_and_its_roofline_share_by_hand():
    joined = ssm_kernel_roofline_share.scan_steps(hand_made_trace())
    assert [s["state_rows"] for s in joined] == [126, 127, 128]
    assert [s["tokens"] for s in joined] == [130, 131, 132]
    assert [s["kernel_s"] for s in joined] == [pytest.approx(0.012)] * 3  # not the attention's
    value, least = ssm_kernel_roofline_share.share(joined, SHAPES, PEAKS)
    moved = 6 * ((126 + 127 + 128) * 2 * 4_194_304 + (130 + 131 + 132) * 26_752)
    assert least["compute"] == 0.0 and least["bandwidth"] == pytest.approx(moved / 819e9)
    assert value == pytest.approx(moved / 819e9 / 0.036)
    assert 0.65 < value < 0.66  # 23.5 ms of bytes in 36 ms of kernel
    # a kernel exactly on its roofline reads 1, never more
    for step in joined:
        step["kernel_s"] = kernel_cost.least_seconds(
            *ssm_cost.ssm_scan_cost(state_rows=step["state_rows"], tokens=step["tokens"], **SHAPES),
            PEAKS,
        )[0]
    assert ssm_kernel_roofline_share.share(joined, SHAPES, PEAKS)[0] == pytest.approx(1.0)


def test_a_program_without_the_span_argument_gives_an_empty_join():
    events = hand_made_trace()
    events["spans"] = [
        (thread, name, start, dur, {k: v for k, v in stats.items() if k != "state_rows"})
        for thread, name, start, dur, stats in events["spans"]
    ]
    assert ssm_kernel_roofline_share.scan_steps(events) == []


class Handle:
    """An entry's handle around a generator with the given cache."""

    def __init__(self, cache, groups=2):
        config = type("Config", (), {"mamba_n_groups": groups})()
        generator = type("Generator", (), {"paged_cache": cache, "config": config})()
        self.engine = type("Engine", (), {"generator": generator})()


def test_the_readers_read_nothing_off_the_chip_or_from_the_parents_cache():
    class Window:
        trace_dir = "/nonexistent"

    class Run:
        trace = None
        peaks = None
        window = Window()
        handle = Handle(None)

    assert ssm_kernel_roofline_share.read(Run()) is None
    assert ssm_kernel_share.read(Run()) is None
    # the parent's cache object has no such attribute at all
    parents = type("PagedKVCache", (), {"k_pages": object()})()
    assert ssm_kernel_roofline_share.state_shapes(Handle(parents)) is None
    assert ssm_kernel_roofline_share.state_shapes(Handle(None)) is None


def test_state_shapes_come_from_the_engines_own_cache():
    import numpy as np

    cache = type("PagedKVCache", (), {
        "ssm_state": np.zeros((6, 2, 32, 256, 128), np.float32)[:, :, :, :1, :1].repeat(256, 3).repeat(128, 4),
        "conv_state": np.zeros((6, 2, 3, 8), np.float16),
    })()
    assert ssm_kernel_roofline_share.state_shapes(Handle(cache)) == SHAPES


def test_the_share_of_busy_time_by_hand():
    class Run:
        trace = {"op_self_s": {
            "ssm_scan_kernel.13 f32[4,256,8,128]": 0.9, "ragged_attention_kernel.13": 0.4,
            "fusion.7": 2.7,
        }}

    assert ssm_kernel_share.read(Run()) == pytest.approx(0.9 / 4.0)
    Run.trace = {"op_self_s": {"ragged_attention_kernel.13": 0.4, "fusion.7": 2.7}}
    assert ssm_kernel_share.read(Run()) is None  # a program that runs no such kernel


@dataclasses.dataclass
class Record:
    tokens: int
    state_rows: int | None = None


@dataclasses.dataclass
class OldRecord:
    """A step record of a program from before the field."""

    tokens: int


class Steps:
    def __init__(self, steps_):
        self.steps = steps_


def test_state_rows_reader_by_hand():
    assert step_state_rows_mean.read(
        Steps([Record(128, 128), Record(190, 126), Record(64, 1)])
    ) == pytest.approx(85.0)
    # a model without recurrent state writes None; an older program no field
    assert step_state_rows_mean.read(Steps([Record(128), Record(64)])) is None
    assert step_state_rows_mean.read(Steps([OldRecord(128)])) is None
    assert step_state_rows_mean.read(Steps([])) is None


# -- the rehearsal ---------------------------------------------------------------


def test_the_rehearsal_adds_files_only_under_the_tests(in_root):
    mine = Manifest(os.path.join(ROOT, MANIFEST))
    assert [c["name"] for c in mine.doc["workloads"]] == [
        "tiny-falcon-h1.decode", "tiny-falcon-h1-no-skip.decode",
    ]
    for item in mine.doc["configs"]:
        assert item["file"].startswith("tests/benchmark/configs/")
        config = mine.config(item["name"])
        assert config["model_id"] == "tiny-falcon-h1"
        # asked for, and switched off by the program itself
        assert config["engine"]["spec_decode"] and config["engine"]["kv_prefix_cache"]
    wrong = mine.module("reference", "falcon_h1_no_skip")
    assert wrong.__file__.startswith(os.path.join(ROOT, "tests/benchmark/reference/"))
    right = mine.module("reference", "falcon_h1_f32")
    assert right.__file__ == os.path.join(ROOT, "benchmark/reference/falcon_h1_f32.py")
    assert wrong.WEIGHTS == right.WEIGHTS == "falcon_h1_f32_weights"


def test_the_rehearsals_configuration_matches_the_program(in_root):
    from operator_tpu.models import get_config

    mine = Manifest(os.path.join(ROOT, MANIFEST))
    for item in mine.doc["configs"]:
        config = mine.config(item["name"])
        reference = mine.module("reference", config["reference"])
        table = mine.module("reference", reference.WEIGHTS).PROGRAM_CONFIG
        arch, program = config["architecture"], get_config(config["model_id"])
        assert set(arch) == set(table)
        assert all(arch[key] == getattr(program, attribute) for key, attribute in table.items())
        probe = config["probe"]
        assert 0 < probe["limit"] < 1.0 and "TODO" not in json.dumps(probe)


def _run(workload, trace):
    env = {k: v for k, v in os.environ.items() if k != "OPERATOR_TPU_MODEL"}
    env["OPERATOR_TPU_PLATFORM"] = "cpu"
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--manifest", MANIFEST,
         "--workload", workload, "--seed", "11", "--seconds", "3", "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )


@pytest.fixture(scope="module")
def rehearsals():
    lines = {}
    for key, workload, trace in (
        ("right", "tiny-falcon-h1.decode", 0), ("traced", "tiny-falcon-h1.decode", 1),
        ("wrong", "tiny-falcon-h1-no-skip.decode", 0),
    ):
        proc = _run(workload, trace)
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines[key] = json.loads(proc.stdout.strip().splitlines()[-1])
        lines[key + ":stderr"] = proc.stderr
    return lines


@pytest.mark.parametrize("key, correct", [("right", True), ("traced", True), ("wrong", False)])
def test_the_falcon_reference_decides_correct(rehearsals, key, correct):
    line = rehearsals[key]
    assert line["correct"] is correct
    assert line["failed"] == 0 and line["attempted"] > 0  # the run itself is whole
    gap = line["compared"]["served_gap_max"]
    assert (gap["value"] <= gap["limit"]) is correct
    assert line["compared"]["served_requests_missing"]["value"] == 0
    assert line["compared"]["window_requests_wrong"]["value"] == 0
    if not correct:  # an order-one fault, not a near miss
        assert gap["value"] > 5 * gap["limit"]


def test_the_rehearsal_says_what_the_program_switched_off(rehearsals):
    log = rehearsals["right:stderr"]
    assert "kv_prefix_cache is OFF for model 'tiny-falcon-h1' (falcon_h1 family)" in log
    assert "spec_decode is OFF for model 'tiny-falcon-h1' (falcon_h1 family)" in log


def test_the_traced_rehearsal_counts_state_rows_and_writes_no_device_metric(rehearsals):
    metrics = rehearsals["traced"]["metrics"]
    assert 0 < metrics["step_state_rows_mean"]["value"] <= 4  # the rehearsal's slots
    assert metrics["step_state_rows_mean"]["unit"] == "count"
    assert "ssm_kernel_share" not in metrics and "ssm_kernel_roofline_share" not in metrics
    assert metrics["midrun_compiles"]["value"] == 0
