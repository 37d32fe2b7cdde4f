"""What the Olmo Hybrid configuration adds to the benchmark, CPU only: its
file against the guide's catalog and the program, the manifest's new
entries found by name (on the real manifest and on the rehearsal of a later
append), the delta-rule kernel's cost from shapes against hand values, the
two new readers on a hand-made trace (and on a program without the span's
argument), and a rehearsal of ``benchmark/run.py`` under a manifest of its
own (``rehearsal-olmo-hybrid.json``: the family's tiny preset read through
``olmo_hybrid_f32``), added as files only.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness.manifest import Manifest  # noqa: E402
from benchmark.layer_metrics import (  # noqa: E402
    attn_kernel_share,
    delta_kernel_roofline_share,
    delta_kernel_share,
    ssm_kernel_share,
)
from benchmark.trace import delta_cost, kernel_cost  # noqa: E402

# the rehearsal of a later PR's append, and its by-name helper (their
# directory is on the path: pytest put it there to import this file)
from test_falcon_h1_benchmark import CATALOG, appended, by_name, in_root  # noqa: E402,F401

MANIFEST = "tests/benchmark/rehearsal-olmo-hybrid.json"
CONFIG = "olmo-hybrid-7b-int8"
CELL = "olmo-hybrid-7b-int8.decode"
NEW = {"delta_kernel_share", "delta_kernel_roofline_share"}
#: the per-layer metrics that list every serving cell, this one appended
SERVING_METRICS = {
    "attn_kernel_roofline_share", "step_kv_pages_mean", "step_tokens_mean",
    "step_prefill_token_share", "step_host_ms", "step_host_wait_share",
    "step_sampled_rows_mean",
}
#: listed metrics whose own tests pin their ``workloads`` to the cells they
#: were first listed for, so this cell is not appended to them: the eleven
#: host-clock parts and the state-rows count (the roofline reader takes the
#: state rows from the dispatch span itself)
PINNED = {
    "step_plan_ms", "step_pack_ms", "step_put_ms", "step_launch_ms", "step_commit_ms",
    "step_wake_ms", "step_turn_ms", "step_host_cpu_share", "step_gc_share", "step_stalls",
    "token_delivery_lag_mean_ms", "step_state_rows_mean",
}
#: the cell's state: 24 linear layers of 30 heads x 96 x 192 float32
SHAPES = {"heads": 30, "key_dim": 96, "value_dim": 192, "state_itemsize": 4}
PEAKS = {"hbm_gbps": 819.0, "bf16_tflops": 197.0}


# -- the configuration's file and the manifest --------------------------------


def test_the_configuration_file_holds_the_published_config_whole(in_root):
    from operator_tpu.models import get_config
    from operator_tpu.models.configs import OlmoHybridConfig

    manifest = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    doc = manifest.config(CONFIG)
    arch = doc["architecture"]
    assert doc["reduced"] == [] and "published" not in doc
    # `architecture` repeats top-level keys, never another value; the two
    # the published config does not give as keys are named as assumed
    assert all(doc[key] == value for key, value in arch.items() if key in doc)
    assert set(arch) - set(doc) == {"head_dim", "rope_theta"}
    assert arch["head_dim"] * arch["num_attention_heads"] == arch["hidden_size"]
    assert arch["rope_theta"] is None and doc["rope_parameters"] == {"rope_theta": None}
    for key in (
        "linear layer", "beta", "no rope", "norm order", "state dtype", "convolution",
        "head_dim", "slots", "kv_pages", "sched_token_budget",
        "spec_decode and kv_prefix_cache", "weights", "tokenizer",
    ):
        assert len(doc["assumed"][key]) > 40, key
    assert "layers shared over 1 chip" in doc["deployment"]
    engine = doc["engine"]
    assert (engine["max_batch_size"], engine["sched_token_budget"], engine["kv_pages"]) == (48, 96, 544)
    assert engine["spec_decode"] is False and engine["kv_prefix_cache"] is False
    # 544 pages hold 48 rows of the mix's longest request (11 pages) and the trash page
    assert engine["kv_pages"] >= 48 * 11 + 1
    reference = manifest.module("reference", doc["reference"])
    table = manifest.module("reference", reference.WEIGHTS).PROGRAM_CONFIG
    program = get_config(doc["model_id"])
    assert isinstance(program, OlmoHybridConfig) and set(arch) == set(table)
    assert all(arch[key] == getattr(program, attribute) for key, attribute in table.items())
    probe = doc["probe"]
    assert "TO BE SET" not in json.dumps(doc) and probe["readings"]["measured_by"]
    if not os.path.isfile(CATALOG):
        pytest.skip("the guides' catalog is not on this machine")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Olmo-Hybrid-7B")
    assert doc["source"] == row["source_url"]
    assert {k for k, v in row["config"].items() if doc.get(k, "absent") != v} == set()


def check_the_olmo_entries(manifest):
    """What this configuration added, wherever in its sections it stands
    today."""
    doc = manifest.doc
    config = by_name(doc["configs"])[CONFIG]
    assert config["reduced"] == [] and config["file"] == "benchmark/configs/olmo-hybrid-7b-int8.json"
    cell = by_name(doc["workloads"])[CELL]
    assert cell == {**cell, "config": CONFIG, "traffic": "decode", "chips": 1}
    per_layer = by_name(doc["per_layer"])
    assert NEW <= set(per_layer)
    mine = {m["name"] for m in manifest.metrics_for("per_layer", CELL)}
    assert NEW | SERVING_METRICS | {
        "attn_kernel_share", "step_weight_floor_share",
        "step_device_ms", "device_idle_share", "peak_hbm_gb", "midrun_compiles",
    } <= mine
    assert not mine & (PINNED | {
        "ssm_kernel_share", "ssm_kernel_roofline_share", "step_passes_mean",
        "moe_kernel_share", "prefix_hit_share",
    })
    assert {m["name"] for m in manifest.metrics_for("end_to_end", CELL)} == {
        "token_gap_mean_ms", "out_tokens_per_s", "setup_s",
    }
    for name in sorted(NEW):
        entry = per_layer[name]
        assert entry["workloads"] == [CELL] and entry["layer"] == "kernels"
        reader = manifest.module("layer_metrics", name)
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
            name, entry["unit"], entry["layer"], entry["moves"], entry["source"],
        )
    # the traffic file is the other `.decode` cells', unedited
    assert manifest.traffic("decode")["greedy"]["every"] == 8


def test_the_manifest_gains_one_configuration_one_cell_and_two_metrics(in_root):
    check_the_olmo_entries(Manifest(os.path.join(ROOT, "BENCHMARK.json")))


def test_a_later_append_leaves_the_olmo_entries_where_a_name_finds_them(appended):
    check_the_olmo_entries(appended)
    mine = {m["name"] for m in appended.metrics_for("per_layer", "tiny-test.decode")}
    assert not mine & NEW


# -- the kernel's cost from shapes ---------------------------------------------


def test_cost_of_one_decode_step_by_hand():
    """48 slots each with one token, 24 linear layers: every slot's 2.21 MB
    of state in and out, a token's q, k (2,880 each), v and o (5,760 each)
    in bfloat16 and g, beta (30 each) in float32."""
    assert delta_cost.state_bytes(30, 96, 192) == 2_211_840
    moved, operations = delta_cost.gated_delta_cost(state_rows=48, tokens=48, layers=24, **SHAPES)
    per_token = (2 * 2880 + 2 * 5760) * 2 + 2 * 30 * 4
    assert per_token == 34_800
    assert moved == 24 * (48 * 2 * 2_211_840 + 48 * per_token) == 5_136_168_960
    assert operations == 24 * 7 * 30 * 96 * 192 * 48
    seconds, bound = kernel_cost.least_seconds(moved, operations, PEAKS)
    assert bound == "bandwidth" and 6.2e-3 < seconds < 6.3e-3  # 6.3 ms of a step


def test_state_bytes_follow_the_rows_not_the_tokens():
    one_chunk, _ = delta_cost.gated_delta_cost(state_rows=1, tokens=64, layers=24, **SHAPES)
    one_token, _ = delta_cost.gated_delta_cost(state_rows=1, tokens=1, layers=24, **SHAPES)
    assert one_chunk - one_token == 24 * 63 * 34_800
    assert delta_cost.gated_delta_cost(state_rows=0, tokens=0, layers=24, **SHAPES) == (0, 0)


def test_the_kernels_names_keep_the_three_shares_apart():
    from operator_tpu.ops.gated_delta import KERNEL_NAME

    name = KERNEL_NAME + ".18 f32[3,96,5,384]"
    assert delta_kernel_share.PATTERN.search(name)
    assert not attn_kernel_share.PATTERN.search(name) and not ssm_kernel_share.PATTERN.search(name)
    assert not delta_kernel_share.PATTERN.search("ragged_attention_kernel.13")
    assert not delta_kernel_share.PATTERN.search("ssm_scan_kernel.13")


# -- the readers on a hand-made trace ---------------------------------------------

MS = 1e6


def hand_made_trace(kernel_ms=(6.0, 6.0, 6.0), layers=24):
    """Three steps of 30 ms on the device; each run holds 24 delta-kernel
    events (one a linear layer) that share ``kernel_ms`` of it, and eight
    attention kernel events beside them."""
    device, modules, spans = [], [], []
    for k, total in enumerate(kernel_ms):
        run_start = (100 + 50 * k) * MS
        modules.append(("jit_mixed_fn(7)", run_start, 30 * MS))
        device.append(("while.3 f32[1]", run_start, 29 * MS))
        for layer in range(layers):
            at = run_start + (1 + layer) * MS
            device.append(("gated_delta_kernel.18 f32[3,96,5,384]", at, total / layers * MS))
            if layer % 3 == 2:
                device.append(("ragged_attention_kernel.13 bf16[48,64,32,128]", at + 0.5 * MS, 0.2 * MS))
        spans.append((
            "tpu-decode_0", "podmortem.sched.dispatch", run_start - 30 * MS, 2 * MS,
            {"step": 40 + k, "kv_pages": 400, "qk_pairs": 30_000, "tokens": 48 + k,
             "state_rows": 46 + k, "delta_layers": layers},
        ))
    return {
        "device": {"/device:TPU:0": device},
        "modules": {"/device:TPU:0": modules},
        "host": [("python", "bench.trace_slice", 30 * MS, 400 * MS)],
        "spans": sorted(spans, key=lambda s: s[2]),
    }


def test_the_delta_join_and_its_roofline_share_by_hand():
    joined = delta_kernel_roofline_share.delta_steps(hand_made_trace())
    assert [s["state_rows"] for s in joined] == [46, 47, 48]
    assert [s["tokens"] for s in joined] == [48, 49, 50]
    assert [s["layers"] for s in joined] == [24] * 3
    assert [s["kernel_s"] for s in joined] == [pytest.approx(0.006)] * 3  # not the attention's
    value, least = delta_kernel_roofline_share.share(joined, SHAPES, PEAKS)
    moved = 24 * ((46 + 47 + 48) * 2 * 2_211_840 + (48 + 49 + 50) * 34_800)
    assert least["compute"] == 0.0 and least["bandwidth"] == pytest.approx(moved / 819e9)
    assert value == pytest.approx(moved / 819e9 / 0.018)
    # a kernel exactly on its roofline reads 1, never more
    for step in joined:
        step["kernel_s"] = kernel_cost.least_seconds(*delta_cost.gated_delta_cost(
            state_rows=step["state_rows"], tokens=step["tokens"], layers=24, **SHAPES,
        ), PEAKS)[0]
    assert delta_kernel_roofline_share.share(joined, SHAPES, PEAKS)[0] == pytest.approx(1.0)


def test_a_program_without_the_span_argument_gives_an_empty_join():
    """The parent's spans, and the Falcon cell's, carry no ``delta_layers``."""
    events = hand_made_trace()
    events["spans"] = [
        (thread, name, start, dur, {k: v for k, v in stats.items() if k != "delta_layers"})
        for thread, name, start, dur, stats in events["spans"]
    ]
    assert delta_kernel_roofline_share.delta_steps(events) == []


class Handle:
    """An entry's handle around a generator with the given cache."""

    def __init__(self, cache):
        generator = type("Generator", (), {"paged_cache": cache})()
        self.engine = type("Engine", (), {"generator": generator})()


def test_the_readers_read_nothing_off_the_chip_or_from_a_cache_without_state():
    class Window:
        trace_dir = "/nonexistent"

    class Run:
        trace = None
        peaks = None
        window = Window()
        handle = Handle(None)

    assert delta_kernel_roofline_share.read(Run()) is None
    assert delta_kernel_share.read(Run()) is None
    parents = type("PagedKVCache", (), {"k_pages": object()})()
    assert delta_kernel_roofline_share.state_shapes(Handle(parents)) is None
    assert delta_kernel_roofline_share.state_shapes(Handle(None)) is None


def test_state_shapes_come_from_the_engines_own_paired_pool():
    cache = type("PagedKVCache", (), {
        "ssm_state": np.broadcast_to(np.zeros((), np.float32), (24, 2, 15, 96, 384)),
    })()
    assert delta_kernel_roofline_share.state_shapes(Handle(cache)) == SHAPES


def test_the_share_of_busy_time_by_hand():
    class Run:
        trace = {"op_self_s": {
            "gated_delta_kernel.18 f32[3,96,5,384]": 0.6, "ragged_attention_kernel.13": 0.4,
            "fusion.7": 3.0,
        }}

    assert delta_kernel_share.read(Run()) == pytest.approx(0.6 / 4.0)
    Run.trace = {"op_self_s": {"ragged_attention_kernel.13": 0.4, "fusion.7": 2.7}}
    assert delta_kernel_share.read(Run()) is None  # a program that runs no such kernel


# -- the rehearsal ---------------------------------------------------------------


def test_the_rehearsal_adds_files_only_under_the_tests_and_matches_the_program(in_root):
    from operator_tpu.models import get_config

    mine = Manifest(os.path.join(ROOT, MANIFEST))
    assert [c["name"] for c in mine.doc["workloads"]] == ["tiny-olmo-hybrid.decode"]
    for item in mine.doc["configs"]:
        assert item["file"].startswith("tests/benchmark/configs/")
        config = mine.config(item["name"])
        # asked for, and switched off by the program itself
        assert config["engine"]["spec_decode"] and config["engine"]["kv_prefix_cache"]
        reference = mine.module("reference", config["reference"])
        table = mine.module("reference", reference.WEIGHTS).PROGRAM_CONFIG
        arch, program = config["architecture"], get_config(config["model_id"])
        assert set(arch) == set(table)
        assert all(arch[key] == getattr(program, attribute) for key, attribute in table.items())
        probe = config["probe"]
        # the tiny preset's logits reach 4 to 5 and its control reads 3 and
        # more: its limit lies between, over one
        assert 0 < probe["soft"]["limit"] < probe["limit"] < 2.0
        assert "TO BE SET" not in json.dumps(probe)


@pytest.fixture(scope="module")
def rehearsals():
    env = {k: v for k, v in os.environ.items() if k != "OPERATOR_TPU_MODEL"}
    env["OPERATOR_TPU_PLATFORM"] = "cpu"
    lines = {}
    for key, trace in (("right", 0), ("traced", 1)):
        proc = subprocess.run(
            [sys.executable, "benchmark/run.py", "--manifest", MANIFEST,
             "--workload", "tiny-olmo-hybrid.decode", "--seed", "4000003099",
             "--seconds", "3", "--trace", str(trace)],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines[key] = json.loads(proc.stdout.strip().splitlines()[-1])
        lines[key + ":stderr"] = proc.stderr
    return lines


@pytest.mark.parametrize("key", ["right", "traced"])
def test_the_olmo_reference_decides_correct(rehearsals, key):
    line = rehearsals[key]
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    gap = line["compared"]["served_gap_max"]
    assert gap["value"] <= gap["limit"]
    assert line["compared"]["served_requests_missing"]["value"] == 0
    assert line["compared"]["window_requests_wrong"]["value"] == 0


def test_the_traced_rehearsal_counts_state_rows_and_writes_no_device_metric(rehearsals):
    log = rehearsals["right:stderr"]
    assert "kv_prefix_cache is OFF for model 'tiny-olmo-hybrid' (olmo_hybrid family)" in log
    assert "spec_decode is OFF for model 'tiny-olmo-hybrid' (olmo_hybrid family)" in log
    metrics = rehearsals["traced"]["metrics"]
    assert 0 < metrics["step_state_rows_mean"]["value"] <= 4  # the rehearsal's slots
    assert not set(metrics) & NEW  # off the chip: no device number
    assert metrics["midrun_compiles"]["value"] == 0
