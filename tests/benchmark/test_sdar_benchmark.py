"""What PR 36 adds to the benchmark, CPU only: the SDAR configuration's
file against the guide's catalog and the program, the manifest's new
entries found by name (on the real manifest and on the rehearsal of a later
append), the five new readers and the expert kernel's cost on hand-made
runs (and on a program without the counts), the reference's own weights
against the program's tree, the control refused by the harness's own
comparison, and a rehearsal of ``benchmark/run.py`` under a manifest of its
own (``rehearsal-sdar.json``: the family's tiny preset read through
``sdar_f32``, and through the same reference told of another mask id than
the program's), added as files only.
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

from benchmark.harness import cell as harness  # noqa: E402
from benchmark.harness.manifest import Manifest  # noqa: E402
from benchmark.layer_metrics import (  # noqa: E402
    moe_expert_load_max_share,
    moe_kernel_roofline_share,
    moe_kernel_share,
    step_block_rows_mean,
    step_unmasked_tokens_mean,
)
from benchmark.trace import moe_cost, steps  # noqa: E402

# the rehearsal of a later PR's append, and its by-name helper (their
# directory is on the path: pytest put it there to import this file)
from test_falcon_h1_benchmark import appended, by_name, in_root  # noqa: E402,F401

MANIFEST = "tests/benchmark/rehearsal-sdar.json"
CONFIG = "sdar-30b-a3b-int8"
CELL = "sdar-30b-a3b-int8.decode"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = {
    "moe_kernel_share": "kernels", "moe_kernel_roofline_share": "kernels",
    "moe_expert_load_max_share": "kernels", "step_unmasked_tokens_mean": "mixed step",
    "step_block_rows_mean": "mixed step",
}
#: the per-layer metrics that list the ``.decode`` cells
DECODE_METRICS = {
    "attn_kernel_roofline_share", "step_kv_pages_mean", "step_tokens_mean",
    "step_prefill_token_share", "step_host_ms", "step_host_wait_share",
    "step_sampled_rows_mean",
}


# -- the configuration's file and the manifest --------------------------------


def test_the_configuration_file_holds_the_published_config_cut_in_depth_only(in_root):
    from operator_tpu.models import get_config
    from operator_tpu.models.configs import SdarConfig

    manifest = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    doc = manifest.config(CONFIG)
    arch = doc["architecture"]
    assert doc["reduced"] == ["num_hidden_layers"]
    assert doc["published"] == {"num_hidden_layers": 48} and arch["num_hidden_layers"] == 12
    # `architecture` repeats top-level keys, never another value; the two
    # keys the published config does not have are named as assumed
    assert all(doc[key] == value for key, value in arch.items() if key in doc)
    assert set(arch) - set(doc) == {"block_length", "mask_token_id"}
    assert (arch["num_experts"], arch["num_experts_per_tok"], arch["moe_intermediate_size"]) == (128, 8, 768)
    for key in (
        "qk norm", "router", "block_length", "mask_token_id", "no logit shift",
        "generation", "remask", "denoise_steps", "a finished block's keys",
        "kv_pages", "sched_token_budget", "spec_decode and kv_prefix_cache",
    ):
        assert len(doc["assumed"][key]) > 40, key
    assert "layers shared over 1 chip" in doc["deployment"] and "four pipeline stages" in doc["deployment"]
    engine = doc["engine"]
    assert engine["max_batch_size"] == 128 and engine["sched_token_budget"] == 128 * 8
    assert engine["kv_pages"] % 512 == 0 and engine["kv_pages"] >= 128 * 11 + 1
    assert "spec_decode" not in engine and "kv_prefix_cache" not in engine  # the scheduler's to switch off
    # the judged rows' schedule is the greedy group's, and the cell's rows run the other rule
    mix = manifest.traffic("decode-blocks")
    assert doc["generation"] == {
        key: mix["greedy"]["sampling"][key] for key in ("denoise_steps", "remask")
    } == {"denoise_steps": 2, "remask": "sequential"}
    assert mix["sampling"]["remask"] == "low_confidence" and mix["sampling"]["denoise_steps"] == 2
    # the program is held to `architecture` through the weights module's table
    reference = manifest.module("reference", doc["reference"])
    table = manifest.module("reference", reference.WEIGHTS).PROGRAM_CONFIG
    program = get_config(doc["model_id"])
    assert isinstance(program, SdarConfig) and set(arch) == set(table)
    assert all(arch[key] == getattr(program, attribute) for key, attribute in table.items())
    probe = doc["probe"]
    assert "TO BE SET" not in json.dumps(doc) and probe["readings"]["measured_by"]
    if not os.path.isfile(CATALOG):
        pytest.skip("the guides' catalog is not on this machine")
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "SDAR-30B-A3B-Chat")
    assert doc["source"] == row["source_url"]
    differing = {k for k, v in row["config"].items() if doc.get(k, "absent") != v}
    assert differing == {"num_hidden_layers"}  # every other key as published
    assert (row["expert_width"], row["hidden_size"], row["head_dim"]) == (
        arch["moe_intermediate_size"], arch["hidden_size"], arch["head_dim"],
    )


def check_the_sdar_entries(manifest):
    """What PR 36 added, wherever in its sections it stands today."""
    doc = manifest.doc
    config = by_name(doc["configs"])[CONFIG]
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["file"] == "benchmark/configs/sdar-30b-a3b-int8.json"
    cell = by_name(doc["workloads"])[CELL]
    assert cell == {**cell, "config": CONFIG, "traffic": "decode-blocks", "chips": 1}
    per_layer = by_name(doc["per_layer"])
    assert set(NEW) <= set(per_layer)
    mine = {m["name"] for m in manifest.metrics_for("per_layer", CELL)}
    assert set(NEW) | DECODE_METRICS | {
        "attn_kernel_share", "step_weight_floor_share", "step_device_ms",
        "device_idle_share", "peak_hbm_gb", "midrun_compiles",
    } <= mine
    assert not mine & {
        "ssm_kernel_share", "ssm_kernel_roofline_share", "step_state_rows_mean",
        "step_passes_mean", "step_pass_stream_floor_share", "prefix_hit_share",
    }
    assert {m["name"] for m in manifest.metrics_for("end_to_end", CELL)} == {
        "token_gap_mean_ms", "out_tokens_per_s", "setup_s",
    }
    for name in sorted(DECODE_METRICS):
        assert CELL in per_layer[name]["workloads"]
    for name, layer in sorted(NEW.items()):
        entry = per_layer[name]
        assert entry["workloads"] == [CELL] and entry["layer"] == layer
        assert entry["moves"] == "token_gap_mean_ms"
        reader = manifest.module("layer_metrics", name)
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
            name, entry["unit"], entry["layer"], entry["moves"], entry["source"],
        )
    # the mix is the `.decode` cells' with two keys added to each sampling
    theirs, mix = manifest.traffic("decode"), manifest.traffic("decode-blocks")
    for group in (mix["sampling"], mix["greedy"]["sampling"]):
        assert group.pop("denoise_steps") == 2 and group.pop("remask")
    assert mix == theirs


def test_the_manifest_gains_one_configuration_one_cell_and_five_metrics(in_root):
    check_the_sdar_entries(Manifest(os.path.join(ROOT, "BENCHMARK.json")))


def test_a_later_append_leaves_the_sdar_entries_where_a_name_finds_them(appended):
    check_the_sdar_entries(appended)
    mine = {m["name"] for m in appended.metrics_for("per_layer", "tiny-test.decode")}
    assert not mine & set(NEW)


# -- the readers and the cost, by hand ------------------------------------------


@dataclasses.dataclass
class Record:
    seq: int
    tokens: int
    block_rows: int | None = None
    unmasked_tokens: int | None = None
    moe_tokens: int | None = None
    moe_experts_hit: int | None = None
    moe_assign_max: int | None = None


@dataclasses.dataclass
class OldRecord:
    """A step record of a program from before the fields."""

    seq: int
    tokens: int


class Config:
    num_experts, num_experts_per_tok, num_layers = 128, 8, 12
    hidden_size, moe_intermediate_size = 2048, 768


class Leaf:
    def __init__(self, itemsize):
        self.dtype = type("dtype", (), {"itemsize": itemsize})


def handle_of(config, layers):
    generator = type("Generator", (), {"config": config, "params": {"layers": layers}})
    return type("Handle", (), {"engine": type("Engine", (), {"generator": generator})})


def test_the_three_counter_readers_by_hand():
    class Run:
        handle = handle_of(Config, {})
        steps = [
            Record(0, 64),  # a step of prompt chunks alone: no denoising row
            Record(1, 768, block_rows=128, unmasked_tokens=256, moe_tokens=768, moe_assign_max=72),
            Record(2, 760, block_rows=126, unmasked_tokens=250, moe_tokens=760, moe_assign_max=95),
        ]

    assert step_block_rows_mean.read(Run) == 127.0
    assert step_unmasked_tokens_mean.read(Run) == 253.0
    # two tokens a row a step, less what a first or a last block falls short
    assert 1.98 < step_unmasked_tokens_mean.read(Run) / step_block_rows_mean.read(Run) <= 2.0
    # the fullest expert over an even share: 768 x 8 / 128 = 48 a step
    want = (72 / 48 + 95 / (760 * 8 / 128)) / 2
    assert moe_expert_load_max_share.read(Run) == pytest.approx(want)
    # a program from before the fields, and a model without experts: left out
    Run.steps = [OldRecord(0, 5), Record(1, 5)]
    assert step_block_rows_mean.read(Run) is None
    assert step_unmasked_tokens_mean.read(Run) is None
    assert moe_expert_load_max_share.read(Run) is None
    Run.handle = handle_of(type("Dense", (), {}), {})
    Run.steps = [Record(1, 768, moe_tokens=768, moe_assign_max=72)]
    assert moe_expert_load_max_share.read(Run) is None


def test_the_expert_cost_from_shapes():
    # one expert's three int8 matrices and their scales
    assert moe_cost.expert_bytes(2048, 768, 1, True) == 3 * 2048 * 768 + (2 * 768 + 2048) * 4
    moved, operations = moe_cost.moe_experts_cost(
        experts_hit=1536, tokens=768, layers=12, experts_per_token=8, hidden=2048, inner=768,
    )
    assignments = 12 * 768 * 8
    assert operations == 6 * 2048 * 768 * assignments
    assert moved == 1536 * moe_cost.expert_bytes(2048, 768, 1, True) + assignments * 2 * 2048 * 2
    # 7.25 GB of experts and 0.6 GB of rows: 9.6 ms, against 3.5 ms of products
    assert moved / 819e9 == pytest.approx(9.6e-3, rel=0.02)
    assert operations / 197e12 == pytest.approx(3.5e-3, rel=0.02)


def dispatch(start, **stats):
    return ("python3", steps.DISPATCH_SPAN, float(start), 10.0, stats)


def test_the_kernel_readers_by_hand(monkeypatch, tmp_path):
    layers = {
        "w_gate": {"q": Leaf(1), "s": Leaf(4)}, "w_router": Leaf(2),
    }
    shapes = moe_kernel_roofline_share.expert_shapes(handle_of(Config, layers))
    assert shapes == {
        "layers": 12, "experts_per_token": 8, "hidden": 2048, "inner": 768,
        "weight_itemsize": 1, "scaled": True, "token_itemsize": 2,
    }
    assert moe_kernel_roofline_share.expert_shapes(handle_of(type("Dense", (), {}), layers)) is None
    assert moe_kernel_roofline_share.expert_shapes(handle_of(Config, {})) is None
    name = "moe_experts_kernel.11 bf16[16384,2048]"
    assert moe_kernel_share.PATTERN.search(name)
    assert not moe_kernel_share.PATTERN.search("ragged_attention_kernel.11 bf16[128,64,32,128]")
    events = {
        "host": [("t", "bench.trace_slice", 0.0, 1e9)],
        "spans": [
            dispatch(100, step=7, kv_pages=600, moe_tokens=768),
            dispatch(200, step=8, kv_pages=600, moe_tokens=760),
            dispatch(300, step=9, kv_pages=600),  # no experts in it: not joined
        ],
        "modules": {"/device:TPU:0": [
            ("jit_mixed_fn(1)", 1e6, 60e6), ("jit_mixed_fn(1)", 70e6, 60e6),
        ]},
        "device": {"/device:TPU:0": [
            (name, 2e6, 12e6), (name, 20e6, 12e6), ("fusion.3", 40e6, 5e6),
            (name, 80e6, 25e6),
        ]},
    }
    records = [
        Record(7, 768, moe_experts_hit=1536), Record(8, 760, moe_experts_hit=1500),
    ]
    joined = moe_kernel_roofline_share.expert_steps(events, records)
    assert joined == [
        {"tokens": 768, "experts_hit": 1536, "kernel_s": pytest.approx(0.024)},
        {"tokens": 760, "experts_hit": 1500, "kernel_s": pytest.approx(0.025)},
    ]
    peaks = {"hbm_gbps": 819.0, "bf16_tflops": 197.0}
    value, least = moe_kernel_roofline_share.share(joined, shapes, peaks)
    bytes_a_step = [
        moe_cost.moe_experts_cost(experts_hit=s["experts_hit"], tokens=s["tokens"], **shapes)[0]
        for s in joined
    ]
    assert least["compute"] == 0.0 and value == pytest.approx(sum(bytes_a_step) / 819e9 / 0.049)
    assert 0.3 < value < 0.5
    # a program whose records carry no count of the experts hit: nothing joined
    assert moe_kernel_roofline_share.expert_steps(events, [OldRecord(7, 768)]) == []

    class Window:
        trace_dir = str(tmp_path)

    class Run:
        handle, window = handle_of(Config, layers), Window()
        steps = records
        trace = {"op_self_s": {name: 0.049, "fusion.3": 0.005, "top_k.1": 0.046}}

    Run.peaks = peaks
    assert moe_kernel_share.read(Run) == pytest.approx(0.49)
    trace_file = tmp_path / "made_up.xplane.pb"
    trace_file.write_bytes(b"")
    monkeypatch.setattr(
        moe_kernel_roofline_share.trace_reduce, "newest_xplane", lambda _: str(trace_file)
    )
    monkeypatch.setattr(moe_kernel_roofline_share.steps, "load", lambda _: events)
    assert moe_kernel_roofline_share.read(Run) == pytest.approx(value)
    Run.trace = {"op_self_s": {"fusion.3": 0.005}}  # a program without the kernel
    assert moe_kernel_share.read(Run) is None
    Run.peaks = None  # off the chip
    assert moe_kernel_roofline_share.read(Run) is None


# -- the reference's own weights, and the control --------------------------------


@pytest.fixture(scope="module")
def tiny(in_root):
    mine = Manifest(os.path.join(ROOT, MANIFEST))
    doc = mine.config("tiny-sdar")
    return mine, doc, mine.module("reference", "sdar_f32"), mine.module("reference", "sdar_f32_weights")


def test_the_references_own_int8_weights_are_the_programs_bit_for_bit(tiny):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from operator_tpu.models import get_config
    from operator_tpu.models.quant import init_params_quantized

    _, doc, _, own = tiny
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
        assert isinstance(leaf, dict) == (name in own.ATTENTION + own.EXPERTS)
        for a, b in pairs:
            assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b)), name
    assert set(theirs.leaves) - {"layers"} == {"embed", "lm_head", "ln_final"}
    for name in ("embed", "lm_head", "ln_final"):
        a, b = mine.leaves[name], theirs.leaves[name]
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b)), name
    # no norm of the recipe is all ones: a dropped or a swapped one cannot hide
    for name in own.VECTORS:
        assert float(jnp.abs(mine.layers[name].astype(jnp.float32) - 1.0).max()) > 0.1


def test_the_control_is_refused_by_the_harness_own_comparison(tiny):
    """Greedy sequential rows served by the program at int8 read inside the
    rehearsal's limit; the same reference at int4 in the program's place,
    on the same prompts and tokens, does not."""
    import jax
    import jax.numpy as jnp

    from operator_tpu.models import get_config
    from operator_tpu.models.quant import init_params_quantized
    from operator_tpu.models.tokenizer import ByteTokenizer
    from operator_tpu.serving.engine import BatchedGenerator, SamplingParams
    from operator_tpu.serving.sched import Scheduler
    from operator_tpu.utils.timing import MetricsRegistry

    _, doc, reference, own = tiny
    config = get_config(doc["model_id"])
    generator = BatchedGenerator(
        init_params_quantized(config, jax.random.PRNGKey(0)), config, ByteTokenizer(),
        paged=True, max_slots=4, max_seq=128, page_size=16, metrics=MetricsRegistry(),
    )
    sched = Scheduler(generator, chunk=8, token_budget=32, pipeline_depth=2)
    prompts = ["pod crashed: OOMKilled", "readiness probe failed on :8080", "ImagePullBackOff x3", "evicted"]
    sent = {}
    for prompt, max_tokens in zip(prompts, (14, 9, 11, 6)):
        sent[sched.enqueue(prompt, SamplingParams(
            max_tokens=max_tokens, temperature=1e-4, top_p=1e-6, stop_on_eos=False,
            **doc["generation"],
        ))] = list(generator.tokenizer.encode(prompt))
    done = {}
    for _ in range(300):
        for outcome in sched.step():
            done[outcome.req_id] = outcome
        if len(done) == len(sent):
            break
    sequences = [(sent[r], done[r].result.token_ids) for r in sent]
    weights = own.make(doc)
    sound = harness.judge(reference.greedy_gaps(doc, weights, sequences), doc["probe"])
    assert all(entry["value"] <= entry["limit"] for entry in sound.values()), sound
    control = harness.judge(reference.control_gaps(doc, weights, sequences), doc["probe"])
    assert control["served_gap_max"]["value"] > 5 * control["served_gap_max"]["limit"], control


# -- the rehearsal ---------------------------------------------------------------


def test_the_rehearsal_adds_files_only_under_the_tests(tiny):
    mine, doc, reference, _ = tiny
    assert [c["name"] for c in mine.doc["workloads"]] == [
        "tiny-sdar.decode", "tiny-sdar-other-mask.decode",
    ]
    for item in mine.doc["configs"]:
        assert item["file"].startswith("tests/benchmark/configs/")
        config = mine.config(item["name"])
        assert config["model_id"] == "tiny-sdar" and config["reference"] == "sdar_f32"
        assert 0 < config["probe"]["limit"] < 1.0
    assert reference.__file__ == os.path.join(ROOT, "benchmark/reference/sdar_f32.py")
    assert mine.traffic("tiny-decode-blocks")["greedy"]["sampling"]["denoise_steps"] == 2
    assert doc["generation"]["denoise_steps"] == 2
    # the one the program is held to names the program's mask id, the wrong one another
    from operator_tpu.models import get_config

    assert doc["architecture"]["mask_token_id"] == get_config("tiny-sdar").mask_token_id == 511
    assert mine.config("tiny-sdar-other-mask")["architecture"]["mask_token_id"] == 510


def _run(workload, trace):
    env = {k: v for k, v in os.environ.items() if k != "OPERATOR_TPU_MODEL"}
    env["OPERATOR_TPU_PLATFORM"] = "cpu"
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--manifest", MANIFEST,
         "--workload", workload, "--seed", "11", "--seconds", "6", "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )


@pytest.fixture(scope="module")
def rehearsals():
    lines = {}
    for key, workload, trace in (
        ("right", "tiny-sdar.decode", 0), ("traced", "tiny-sdar.decode", 1),
        ("wrong", "tiny-sdar-other-mask.decode", 0),
    ):
        proc = _run(workload, trace)
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines[key] = json.loads(proc.stdout.strip().splitlines()[-1])
    return lines


@pytest.mark.parametrize("key, correct", [("right", True), ("traced", True), ("wrong", False)])
def test_the_sdar_reference_decides_correct(rehearsals, key, correct):
    line = rehearsals[key]
    assert line["correct"] is correct
    assert line["failed"] == 0 and line["attempted"] > 0  # the run itself is whole
    gap = line["compared"]["served_gap_max"]
    assert (gap["value"] <= gap["limit"]) is correct
    assert line["compared"]["served_requests_missing"]["value"] == 0
    assert line["compared"]["window_requests_wrong"]["value"] == 0
    if not correct:  # another token at every masked position is an order-one fault, not a near miss
        assert gap["value"] > 5 * gap["limit"]


def test_the_traced_rehearsal_counts_the_schedule_and_writes_no_device_metric(rehearsals):
    metrics = rehearsals["traced"]["metrics"]
    rows, kept = metrics["step_block_rows_mean"]["value"], metrics["step_unmasked_tokens_mean"]["value"]
    # two positions a row a step, less what short answers' first and last blocks leave
    assert 1.5 < kept / rows <= 2.0
    assert metrics["moe_expert_load_max_share"]["unit"] == "ratio"
    assert metrics["moe_expert_load_max_share"]["value"] >= 1.0
    for name in ("moe_kernel_share", "moe_kernel_roofline_share"):
        assert name not in metrics  # device numbers: not on the CPU
    assert metrics["midrun_compiles"]["value"] == 0
