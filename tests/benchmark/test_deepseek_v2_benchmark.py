"""What the DeepSeek-V2-Lite configuration adds to the benchmark, CPU only:
its file against the guide's catalog and the program, the manifest's new
entries found by name (on the real manifest and on the rehearsal of a later
append), the long-prompt mix's token lengths, the latent attention kernel's
cost from shapes against hand values, the new reader on a hand-made trace
(and on a program without the span's argument), and a rehearsal of
``benchmark/run.py`` under a manifest of its own
(``rehearsal-deepseek-v2.json``: the family's tiny preset read through
``deepseek_v2_f32``), added as files only.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness.manifest import Manifest  # noqa: E402
from benchmark.layer_metrics import (  # noqa: E402
    attn_kernel_share,
    delta_kernel_share,
    mla_kernel_roofline_share,
    moe_kernel_share,
)
from benchmark.trace import kernel_cost, mla_cost  # noqa: E402

# the rehearsal of a later PR's append, and its by-name helper (their
# directory is on the path: pytest put it there to import this file)
from test_falcon_h1_benchmark import CATALOG, appended, by_name, in_root  # noqa: E402,F401

MANIFEST = "tests/benchmark/rehearsal-deepseek-v2.json"
CONFIG = "deepseek-v2-lite-int8"
CELL = "deepseek-v2-lite-int8.decode-long"
NEW = {"mla_kernel_roofline_share"}
#: the per-layer metrics that list every serving cell, this one appended
LISTED = {
    "step_kv_pages_mean", "step_tokens_mean", "step_prefill_token_share", "step_host_ms",
    "step_host_wait_share", "step_sampled_rows_mean",
}
#: listed metrics this cell is not appended to: the ragged kernel's and the
#: expert kernel's roofline shares count K/V pages of heads x head_dim and
#: every layer as an expert layer, and the other two expert metrics and the
#: host-clock parts are pinned to the cells first listed by their own tests
#: (PERF.md section 7 names the edit a `benchmark` PR would make); the other
#: families' own
NOT_LISTED = {
    "attn_kernel_roofline_share", "moe_kernel_roofline_share", "moe_kernel_share",
    "moe_expert_load_max_share", "step_plan_ms",
    "step_pack_ms", "step_put_ms", "step_launch_ms", "step_commit_ms", "step_wake_ms",
    "step_turn_ms", "step_host_cpu_share", "step_gc_share", "step_stalls",
    "token_delivery_lag_mean_ms", "ssm_kernel_share", "ssm_kernel_roofline_share",
    "step_state_rows_mean", "step_passes_mean", "delta_kernel_share",
    "step_unmasked_tokens_mean", "step_block_rows_mean", "prefix_hit_share",
}
#: the cell's latent geometry: 16 heads, latent 512, rope 64, pages of 64
SHAPES = {"page_size": 64, "heads": 16, "rank": 512, "rope_dim": 64, "itemsize": 2}
PEAKS = {"hbm_gbps": 819.0, "bf16_tflops": 197.0}


# -- the configuration's file and the manifest --------------------------------


def test_the_configuration_file_holds_the_published_config_cut_in_depth_only(in_root):
    from operator_tpu.models import get_config
    from operator_tpu.models.configs import DeepseekV2Config

    manifest = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    doc = manifest.config(CONFIG)
    arch = doc["architecture"]
    # `architecture` repeats top-level keys, never another value
    assert all(doc[key] == value for key, value in arch.items())
    assert doc["reduced"] == ["num_hidden_layers"] and doc["published"] == {"num_hidden_layers": 27}
    # the dense layer and 13 expert layers: over the guide's floor of four
    assert arch["num_hidden_layers"] - arch["first_k_dense_replace"] == 13 >= 4
    assert "layers shared over 1 chip" in doc["deployment"]
    for key in (
        "the top-level keys", "shared experts", "rotary convention", "head_dim",
        "latent pool", "router", "max_batch_size", "sched_token_budget", "kv_pages",
        "spec_decode and kv_prefix_cache", "max positions", "weights", "tokenizer",
    ):
        assert len(doc["assumed"][key]) > 40, key
    engine = doc["engine"]
    assert (engine["max_batch_size"], engine["sched_token_budget"], engine["kv_pages"]) == (
        128, 1024, 4096,
    )
    assert engine["spec_decode"] is False and engine["kv_prefix_cache"] is False
    reference = manifest.module("reference", doc["reference"])
    table = manifest.module("reference", reference.WEIGHTS).PROGRAM_CONFIG
    program = get_config(doc["model_id"])
    assert isinstance(program, DeepseekV2Config) and set(arch) == set(table)
    assert all(arch[key] == getattr(program, attribute) for key, attribute in table.items())
    probe = doc["probe"]
    assert "TO BE SET" not in json.dumps(doc) and probe["readings"]["measured_by"]
    if not os.path.isfile(CATALOG):
        pytest.skip("the architecture catalog is not present")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "DeepSeek-V2-Lite")
    assert doc["source"] == row["source_url"]
    differing = {k for k, v in row["config"].items() if doc.get(k, "absent") != v}
    assert differing == {"num_hidden_layers"}  # every other key as published
    assert (row["expert_width"], row["dense_width"]) == (
        arch["moe_intermediate_size"], arch["intermediate_size"],
    )


def check_the_deepseek_entries(manifest):
    """What this configuration added, wherever in its sections it stands
    today."""
    doc = manifest.doc
    config = by_name(doc["configs"])[CONFIG]
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["file"] == "benchmark/configs/deepseek-v2-lite-int8.json"
    cell = by_name(doc["workloads"])[CELL]
    assert cell == {**cell, "config": CONFIG, "traffic": "decode-long", "chips": 1}
    per_layer = by_name(doc["per_layer"])
    assert NEW <= set(per_layer)
    mine = {m["name"] for m in manifest.metrics_for("per_layer", CELL)}
    assert NEW | LISTED | {
        "attn_kernel_share", "step_weight_floor_share", "step_device_ms",
        "device_idle_share", "peak_hbm_gb", "midrun_compiles",
    } <= mine
    assert not mine & NOT_LISTED
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
    traffic = manifest.traffic("decode-long")
    decode = manifest.traffic("decode")
    # the `.decode` cells' mix with longer prompts, and nothing else changed
    assert {k: v for k, v in traffic.items() if k not in ("why", "prompts")} == {
        k: v for k, v in decode.items() if k != "prompts"
    }
    assert (traffic["prompts"]["chars_low"], traffic["prompts"]["chars_high"]) == (3700, 3950)


def test_the_manifest_gains_one_configuration_one_cell_and_one_metric(in_root):
    check_the_deepseek_entries(Manifest(os.path.join(ROOT, "BENCHMARK.json")))


def test_a_later_append_leaves_the_deepseek_entries_where_a_name_finds_them(appended):
    check_the_deepseek_entries(appended)
    mine = {m["name"] for m in appended.metrics_for("per_layer", "tiny-test.decode")}
    assert not mine & NEW


def test_the_long_prompts_fit_the_position_cap_with_the_longest_answer(in_root):
    """The mix's prompts under the committed tokenizer, as the engine
    tokenizes them without a checkpoint: the file's 1,090 to 1,530 tokens
    on these seeds, and a 500-token answer still under 2,048 positions."""
    from benchmark.harness import cell
    from operator_tpu.models.tokenizer import load_tokenizer
    from operator_tpu.serving.types import prompt_budget

    manifest = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    spec = cell.Spec.load(manifest, CELL)
    generator = manifest.module("generators", spec.traffic["prompts"]["generator"])
    tokenizer = load_tokenizer("builtin-bpe")
    lengths = [
        len(tokenizer.encode(prompt))
        for seed in (4200000001, 4200000002)
        for prompt in generator.make(seed, spec.traffic["prompts"], [0.0] * 128)
    ]
    assert 1090 <= min(lengths) and max(lengths) <= 1530
    assert 1250 < sum(lengths) / len(lengths) < 1350
    assert max(lengths) <= prompt_budget(2048, spec.traffic["max_tokens"]["high"])


# -- the kernel's cost from shapes ---------------------------------------------


def test_cost_of_one_decode_step_by_hand():
    """128 slots each with one token at 1,510 positions (24 pages), 14
    layers: a page is 64 x (512 + 64) x 2 B, a token's absorbed queries in
    and latent outputs out 16 x (512 + 64 + 512) x 2 B."""
    assert mla_cost.page_bytes(64, 512, 64, 2) == 73_728 == 64 * 1152
    moved, operations = mla_cost.latent_attention_cost(
        latent_pages=128 * 24, qk_pairs=128 * 1510, tokens=128, layers=14, **SHAPES,
    )
    assert moved == 14 * (128 * 24 * 73_728 + 128 * 16 * 1088 * 2)
    assert operations == 14 * 2 * 16 * 1088 * 128 * 1510
    seconds, bound = kernel_cost.least_seconds(moved, operations, PEAKS)
    # the latent walk of a decode step: 3.2 GB, bound by bandwidth
    assert 3.2e9 < moved < 3.3e9
    assert bound == "bandwidth" and 3.9e-3 < seconds < 4.0e-3


def test_a_prompt_chunk_is_bound_by_compute():
    moved, operations = mla_cost.latent_attention_cost(
        latent_pages=12, qk_pairs=64 * 700, tokens=64, layers=1, **SHAPES,
    )
    assert kernel_cost.least_seconds(moved, operations, PEAKS)[1] == "compute"
    assert mla_cost.latent_attention_cost(
        latent_pages=0, qk_pairs=0, tokens=0, layers=14, **SHAPES,
    ) == (0, 0)


def test_the_kernels_names_keep_the_shares_apart():
    from operator_tpu.ops.latent_attention import KERNEL_NAME

    name = KERNEL_NAME + ".11 bf16[1024,16,512]"
    assert mla_kernel_roofline_share.PATTERN.search(name)
    assert attn_kernel_share.PATTERN.search(name)  # an attention kernel, counted so
    assert not moe_kernel_share.PATTERN.search(name) and not delta_kernel_share.PATTERN.search(name)
    assert not mla_kernel_roofline_share.PATTERN.search("ragged_attention_kernel.13")
    assert not mla_kernel_roofline_share.PATTERN.search("moe_experts_kernel.10")


# -- the reader on a hand-made trace ---------------------------------------------

MS = 1e6


def hand_made_trace(kernel_ms=(4.0, 4.0, 4.0), layers=14):
    """Three steps of 40 ms on the device; each run holds 14 latent
    kernel events (one a layer) that share ``kernel_ms`` of it, and 13
    expert kernel events beside them."""
    device, modules, spans = [], [], []
    for k, total in enumerate(kernel_ms):
        run_start = (100 + 50 * k) * MS
        modules.append(("jit_mixed_fn(7)", run_start, 40 * MS))
        device.append(("while.3 f32[1]", run_start, 39 * MS))
        for layer in range(layers):
            at = run_start + (1 + 2 * layer) * MS
            device.append(("latent_attention_kernel.11 bf16[1024,16,512]", at, total / layers * MS))
            if layer:
                device.append(("moe_experts_kernel.10 f32[8192,2048]", at + 0.5 * MS, 1.0 * MS))
        spans.append((
            "tpu-decode_0", "podmortem.sched.dispatch", run_start - 30 * MS, 2 * MS,
            {"step": 40 + k, "kv_pages": 3000 + k, "qk_pairs": 190_000 + k, "tokens": 1000 + k,
             "latent_layers": layers, "moe_layers": 13, "moe_tokens": 1000 + k},
        ))
    return {
        "device": {"/device:TPU:0": device},
        "modules": {"/device:TPU:0": modules},
        "host": [("python", "bench.trace_slice", 30 * MS, 400 * MS)],
        "spans": sorted(spans, key=lambda s: s[2]),
    }


def test_the_latent_join_and_its_roofline_share_by_hand():
    joined = mla_kernel_roofline_share.latent_steps(hand_made_trace())
    assert [s["pages"] for s in joined] == [3000, 3001, 3002]
    assert [s["tokens"] for s in joined] == [1000, 1001, 1002]
    assert [s["layers"] for s in joined] == [14] * 3
    assert [s["kernel_s"] for s in joined] == [pytest.approx(0.004)] * 3  # not the experts'
    value, least = mla_kernel_roofline_share.share(joined, SHAPES, PEAKS)
    want = sum(
        kernel_cost.least_seconds(*mla_cost.latent_attention_cost(
            latent_pages=3000 + k, qk_pairs=190_000 + k, tokens=1000 + k, layers=14, **SHAPES,
        ), PEAKS)[0]
        for k in range(3)
    )
    assert sum(least.values()) == pytest.approx(want)
    assert value == pytest.approx(want / 0.012)
    # a kernel exactly on its roofline reads 1, never more
    for step in joined:
        step["kernel_s"] = kernel_cost.least_seconds(*mla_cost.latent_attention_cost(
            latent_pages=step["pages"], qk_pairs=step["pairs"], tokens=step["tokens"],
            layers=14, **SHAPES,
        ), PEAKS)[0]
    assert mla_kernel_roofline_share.share(joined, SHAPES, PEAKS)[0] == pytest.approx(1.0)


def test_a_program_without_the_span_argument_gives_an_empty_join():
    """The parent's spans, and every other cell's, carry no
    ``latent_layers``."""
    events = hand_made_trace()
    events["spans"] = [
        (thread, name, start, dur, {k: v for k, v in stats.items() if k != "latent_layers"})
        for thread, name, start, dur, stats in events["spans"]
    ]
    assert mla_kernel_roofline_share.latent_steps(events) == []


class Handle:
    """An entry's handle around a generator with the given cache."""

    def __init__(self, cache, config=None):
        generator = type("Generator", (), {"paged_cache": cache, "config": config})()
        self.engine = type("Engine", (), {"generator": generator})()


def test_the_reader_reads_nothing_off_the_chip_or_from_a_pool_that_is_not_latent():
    import numpy as np

    class Window:
        trace_dir = "/nonexistent"

    class Run:
        trace = None
        peaks = None
        window = Window()
        handle = Handle(None)

    assert mla_kernel_roofline_share.read(Run()) is None
    kv = type("PagedKVCache", (), {
        "k_pages": np.zeros((2, 3, 64, 4, 128), np.float16), "rope_pages": None,
    })()
    assert mla_kernel_roofline_share.latent_shapes(Handle(kv)) is None
    assert mla_kernel_roofline_share.latent_shapes(Handle(None)) is None


def test_the_shapes_come_from_the_engines_own_latent_pool():
    import numpy as np

    from operator_tpu.models import get_config

    cache = type("PagedKVCache", (), {
        "k_pages": np.broadcast_to(np.zeros((), np.float16), (14, 4096, 64, 512)),
        "rope_pages": np.broadcast_to(np.zeros((), np.float16), (14, 4096, 32, 128)),
    })()
    got = mla_kernel_roofline_share.latent_shapes(Handle(cache, get_config("deepseek-v2-lite-14l")))
    assert got == SHAPES


# -- the rehearsal ---------------------------------------------------------------


def test_the_rehearsal_adds_files_only_under_the_tests_and_matches_the_program(in_root):
    from operator_tpu.models import get_config

    mine = Manifest(os.path.join(ROOT, MANIFEST))
    assert [c["name"] for c in mine.doc["workloads"]] == ["tiny-deepseek-v2.decode"]
    for item in mine.doc["configs"]:
        assert item["file"].startswith("tests/benchmark/configs/")
        config = mine.config(item["name"])
        reference = mine.module("reference", config["reference"])
        table = mine.module("reference", reference.WEIGHTS).PROGRAM_CONFIG
        arch, program = config["architecture"], get_config(config["model_id"])
        assert set(arch) == set(table)
        assert all(arch[key] == getattr(program, attribute) for key, attribute in table.items())
        probe = config["probe"]
        # the tiny preset's logits reach 4 to 5 and its control reads 2 and
        # more: its limit lies between, under one
        assert 0 < probe["soft"]["limit"] < probe["limit"] < 1.0
        assert "TO BE SET" not in json.dumps(probe)


@pytest.fixture(scope="module")
def rehearsals():
    env = {k: v for k, v in os.environ.items() if k != "OPERATOR_TPU_MODEL"}
    env["OPERATOR_TPU_PLATFORM"] = "cpu"
    lines = {}
    for key, trace in (("right", 0), ("traced", 1)):
        proc = subprocess.run(
            [sys.executable, "benchmark/run.py", "--manifest", MANIFEST,
             "--workload", "tiny-deepseek-v2.decode", "--seed", "4200003099",
             "--seconds", "3", "--trace", str(trace)],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines[key] = json.loads(proc.stdout.strip().splitlines()[-1])
    return lines


@pytest.mark.parametrize("key", ["right", "traced"])
def test_the_deepseek_reference_decides_correct(rehearsals, key):
    line = rehearsals[key]
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    gap = line["compared"]["served_gap_max"]
    assert gap["value"] <= gap["limit"]
    assert line["compared"]["served_requests_missing"]["value"] == 0
    assert line["compared"]["window_requests_wrong"]["value"] == 0


def test_the_traced_rehearsal_counts_experts_and_writes_no_device_metric(rehearsals):
    metrics = rehearsals["traced"]["metrics"]
    assert metrics["moe_expert_load_max_share"]["value"] >= 1.0
    assert metrics["step_kv_pages_mean"]["value"] > 0
    assert not set(metrics) & NEW  # off the chip: no device number
    assert metrics["midrun_compiles"]["value"] == 0
