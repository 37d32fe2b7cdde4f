"""The benchmark's own checks, CPU only: the manifest against its files, the
generators' determinism, the percentile and attainment rules, the float32
reference against the program's forward pass, the trace reducer on a
recorded trace, and a rehearsal of ``benchmark/run.py`` on a tiny
configuration that is added the way ``benchmark/README.md`` tells a later
PR to add one (files of its own and a manifest, nothing edited).
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

from benchmark.generators import arrivals, log_prompts, storm_prompts  # noqa: E402
from benchmark.harness import loops, stats  # noqa: E402
from benchmark.harness.manifest import NAME, UNIT, Manifest, load_json  # noqa: E402
from benchmark.trace import reduce as trace_reduce  # noqa: E402

MANIFESTS = (
    "BENCHMARK.json", "tests/benchmark/rehearsal.json",
    "tests/benchmark/rehearsal-reference.json",
)
#: keys `reduced` may never name (the contract: no width is ever cut)
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "head_dim", "expand")
#: and: a key that ends so, or the number of experts a token is sent to
WIDTH_ENDINGS = ("_dim", "_rank", "_per_tok", "_per_token")


def is_width(key):
    """A count of layers is depth (``num_hidden_layers``), whatever words
    its name holds; everything else that names a width word is a width."""
    if key.endswith("_layers"):
        return False
    return any(word in key for word in WIDTH_WORDS) or key.endswith(WIDTH_ENDINGS)


@pytest.fixture(scope="module")
def in_root():
    before = os.getcwd()
    os.chdir(ROOT)
    yield ROOT
    os.chdir(before)


@pytest.fixture(scope="module", params=MANIFESTS)
def manifest(request, in_root):
    return Manifest(os.path.join(ROOT, request.param))


# -- the manifest agrees with the files -------------------------------------


def test_manifest_has_exactly_the_contract_keys(manifest):
    assert set(manifest.doc) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= manifest.doc["run_seconds"] <= 51
    assert manifest.doc["command"][-1] == "benchmark/run.py"
    assert len(json.dumps(manifest.doc)) < 64 * 1024


def test_every_cell_finds_its_files(manifest):
    for cell in manifest.doc["workloads"]:
        config = manifest.config(cell["config"])
        traffic = manifest.traffic(cell["traffic"])
        assert hasattr(manifest.module("entries", config["entry"]), "build")
        assert hasattr(
            manifest.module("generators", traffic["prompts"]["generator"]), "make"
        )
        if traffic["loop"] == "open":
            assert hasattr(
                manifest.module("generators", traffic["arrivals"]["generator"]), "make"
            )
            assert traffic["arrivals"]["rate_per_s"] > 0
        else:
            assert traffic["loop"] == "closed"
        assert config["chips"] == cell["chips"]


def test_engine_keys_are_operator_config_fields(manifest):
    from operator_tpu.utils.config import OperatorConfig

    from benchmark.entries import engine as entry

    for item in manifest.doc["configs"]:
        config = manifest.config(item["name"])
        built = entry.operator_config(
            {"model_id": config["model_id"], **config["engine"]}
        )
        assert isinstance(built, OperatorConfig)
        assert built.sched_mode == "continuous"
    with pytest.raises(ValueError, match="not OperatorConfig fields"):
        entry.operator_config({"no_such_knob": 1})


def reference_modules(manifest, config):
    """``(reference, its weights module)`` as the harness finds them for a file."""
    reference = manifest.module("reference", config["reference"])
    return reference, manifest.module("reference", reference.WEIGHTS)


def cut_problems(item, config):
    """What is wrong with how a configuration states its cut.  Every key in
    ``reduced`` is a key of ``architecture`` (the size held, which is what
    the program is built with), has its published value beside it in a
    ``published`` group, names no width, and the file states the
    deployment the share is of.  With ``reduced`` empty nothing is asked."""
    problems = []
    if sorted(item["reduced"]) != sorted(config.get("reduced", [])):
        problems.append("the manifest's `reduced` is not the file's")
    published = config.get("published", {})
    for key in item["reduced"]:
        if is_width(key):
            problems.append(f"{key} is a width: no width is ever cut")
        if key not in config["architecture"]:
            problems.append(f"{key} is reduced but `architecture` does not hold its size")
        if key not in published:
            problems.append(f"{key} is reduced but `published` does not give the source's value")
        elif published[key] == config["architecture"].get(key):
            problems.append(f"{key} is listed as reduced but equals the published value")
    if item["reduced"] and len(config.get("deployment", "")) < 20:
        problems.append("a cut configuration states the deployment its share is of")
    if set(published) - set(item["reduced"]):
        problems.append(f"`published` gives {sorted(set(published) - set(item['reduced']))}, not in `reduced`")
    return problems


def test_configuration_files_match_the_program_and_cut_no_width(manifest):
    """Every configuration through its own table: which ``architecture``
    keys are held to which attribute of the program's model configuration
    is the adapter's ``PROGRAM_CONFIG``, beside the reference the file
    names.  Every key of ``architecture`` is held to something."""
    from operator_tpu.models import get_config

    used = {cell["config"] for cell in manifest.doc["workloads"]}
    files = [item["file"] for item in manifest.doc["configs"]]
    assert len(set(files)) == len(files)
    for item in manifest.doc["configs"]:
        assert item["name"] in used
        assert any(item["file"].startswith(p + "/") for p in manifest.doc["paths"])
        assert 1 <= len(item["source"]) <= 200 and 1 <= len(item["why"]) <= 200
        config = manifest.config(item["name"])
        assert cut_problems(item, config) == [], item["name"]
        _, adapter = reference_modules(manifest, config)
        arch, program = config["architecture"], get_config(config["model_id"])
        assert set(arch) == set(adapter.PROGRAM_CONFIG), item["name"]
        for key, attribute in adapter.PROGRAM_CONFIG.items():
            assert arch[key] == getattr(program, attribute), (item["name"], key)


CUT = {
    "architecture": {"num_hidden_layers": 5, "n_routed_experts": 16, "vocab_size": 19200,
                     "hidden_size": 7680},
    "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
    "published": {"num_hidden_layers": 61, "n_routed_experts": 256, "vocab_size": 153600},
    "deployment": "one of 16 chips that share each layer: experts 16 ways, attention whole",
}


@pytest.mark.parametrize(
    "change, problem",
    [
        ({}, None),
        ({"reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size", "hidden_size"],
          "published": {**CUT["published"], "hidden_size": 15360}}, "is a width"),
        ({"reduced": CUT["reduced"] + ["num_experts_per_tok"],
          "published": {**CUT["published"], "num_experts_per_tok": 8}}, "is a width"),
        ({"reduced": CUT["reduced"] + ["kv_lora_rank"],
          "published": {**CUT["published"], "kv_lora_rank": 512}}, "is a width"),
        ({"published": {"num_hidden_layers": 61, "vocab_size": 153600}}, "does not give the source's value"),
        ({"published": {**CUT["published"], "vocab_size": 19200}}, "equals the published value"),
        ({"architecture": {"num_hidden_layers": 5, "vocab_size": 19200}}, "does not hold its size"),
        ({"deployment": "none"}, "states the deployment"),
        ({"reduced": ["num_hidden_layers", "n_routed_experts"]}, "not in `reduced`"),
    ],
)
def test_a_cut_states_the_size_held_and_the_published_size(change, problem):
    config = {**CUT, **change}
    item = {"reduced": list(config["reduced"])}
    problems = cut_problems(item, config)
    if problem is None:
        assert problems == []
    else:
        assert any(problem in p for p in problems), problems
    # the manifest's list and the file's are one list
    assert cut_problems({"reduced": []}, CUT)[0].startswith("the manifest's `reduced`")


def test_every_configuration_names_its_reference_and_its_probe(manifest):
    for item in manifest.doc["configs"]:
        config = manifest.config(item["name"])
        reference, own = reference_modules(manifest, config)
        assert callable(reference.greedy_gaps) and callable(own.make) and callable(own.adapt)
        # the recipe the reference makes its own weights from, and nothing of the program's
        assert {"seed", "init", "dtype", "bits"} <= set(config["weights"]), item["name"]
        probe = config["probe"]
        assert probe["requests"] >= 4  # served greedy requests compared, the longest among them
        assert 0 < probe["limit"] < 1.0  # far under what an order-one fault reads: 2 to 5
        for key in ("origin", "why"):
            assert len(probe[key]) > 20 and "TODO" not in probe[key], (item["name"], key)
        assert {"sound", "control", "measured_by"} <= set(probe["readings"]), item["name"]
        if "soft" in probe:
            assert probe["soft"]["limit"] < probe["limit"] and 50 <= probe["soft"]["percentile"] < 100


def test_a_configuration_that_names_no_reference_is_an_error(in_root):
    from benchmark.harness import cell

    rehearsal = Manifest(os.path.join(ROOT, "tests/benchmark/rehearsal.json"))
    spec = cell.Spec.load(rehearsal, "tiny-test.decode")
    nameless = {k: v for k, v in spec.config.items() if k != "reference"}
    with pytest.raises(ValueError, match="names no reference"):
        cell.probe_group(dataclasses.replace(spec, config=nameless))
    unsized = {k: v for k, v in spec.config.items() if k != "probe"}
    with pytest.raises(ValueError, match="needs a \"probe\" group"):
        cell.probe_group(dataclasses.replace(spec, config=unsized))
    with pytest.raises(FileNotFoundError, match="no reference/nowhere.py"):
        cell.probe_group(dataclasses.replace(spec, config={**spec.config, "reference": "nowhere"}))
    reference, own, group = cell.probe_group(spec)
    assert (reference.__name__, own.__name__) == (
        "benchmark.reference.decoder_f32", "benchmark.reference.decoder_f32_weights",
    )
    assert group is spec.config["probe"]


def test_the_harness_imports_no_reference_by_name(in_root):
    for folder in ("benchmark/harness", "benchmark/entries"):
        for name in sorted(os.listdir(os.path.join(ROOT, folder))):
            if name.endswith(".py"):
                with open(os.path.join(ROOT, folder, name), encoding="utf-8") as f:
                    text = f.read()
                assert "decoder_f32" not in text, (folder, name)
                assert "PROBE_PROMPTS" not in text and "LOGIT_TOLERANCE" not in text
                # what decides `correct` reads no weight the program made
                if folder == "benchmark/harness":
                    assert ".parameters(" not in text and ".adapt(" not in text, name
    with open(os.path.join(ROOT, "benchmark/run.py"), encoding="utf-8") as f:
        assert "decoder_f32" not in f.read()


@pytest.mark.parametrize(
    "gaps, group, names, ok",
    [
        ([[0.0, 0.01], [0.04]], {"limit": 0.05}, ["served_gap_max"], True),
        ([[0.0, 0.01], [0.06]], {"limit": 0.05}, ["served_gap_max"], False),
        ([[0.0] * 99 + [0.3]], {"limit": 0.5, "soft": {"percentile": 95, "limit": 0.1}},
         ["served_gap_max", "served_gap_p95"], True),
        ([[0.2] * 10 + [0.0] * 90], {"limit": 0.5, "soft": {"percentile": 95, "limit": 0.1}},
         ["served_gap_max", "served_gap_p95"], False),
        ([], {"limit": 0.05}, [], True),  # nothing came back: the failed count says so
    ],
)
def test_the_probe_judges_each_number_against_its_own_limit(gaps, group, names, ok):
    from benchmark.harness import cell

    compared = cell.judge(gaps, group)
    assert list(compared) == names
    assert all(entry["value"] <= entry["limit"] for entry in compared.values()) is ok
    for entry in compared.values():
        assert set(entry) == {"value", "limit"}


def test_names_units_and_text_use_the_permitted_characters(manifest):
    doc = manifest.doc
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        section_names = [entry["name"] for entry in doc[section]]
        assert len(set(section_names)) == len(section_names)
        names += section_names
    names += [c["config"] for c in doc["workloads"]]
    names += [c["traffic"] for c in doc["workloads"]]
    assert all(NAME.match(n) for n in names), names
    metric_names = [m["name"] for m in doc["end_to_end"] + doc["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for metric in doc["end_to_end"] + doc["per_layer"]:
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
    for cell in doc["workloads"]:
        assert 1 <= len(cell["why"]) <= 200, (cell["name"], len(cell["why"]))
        assert "\n" not in cell["why"] and "\t" not in cell["why"]
    pairs = [(c["config"], c["traffic"]) for c in doc["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_cells_metrics_and_chips_follow_the_rules(manifest):
    doc = manifest.doc
    cells = [c["name"] for c in doc["workloads"]]
    assert 2 <= len(cells) <= 24
    four = [c for c in doc["workloads"] if c["chips"] == 4]
    assert all(c["chips"] in (1, 4) for c in doc["workloads"])
    assert len(four) <= max(1, len(cells) // 4)
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for metric in doc["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
        assert set(metric) <= {"name", "unit", "better", "bound", "source", "workloads"}

    def cells_of(metric):
        return set(metric.get("workloads", cells))

    for metric in doc["per_layer"]:
        assert set(metric) <= {
            "name", "unit", "better", "source", "layer", "moves", "workloads",
        }
        assert metric["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock",
        )
        assert cells_of(metric) <= cells_of(e2e[metric["moves"]]), metric["name"]
    for cell in cells:
        mine = [m["name"] for m in manifest.metrics_for("end_to_end", cell)]
        assert "setup_s" in mine and len(mine) >= 2
        assert manifest.metrics_for("per_layer", cell)


def test_each_layer_metric_is_a_file_that_agrees_with_the_manifest(manifest):
    for metric in manifest.doc["per_layer"]:
        reader = manifest.module("layer_metrics", metric["name"])
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
            metric["name"], metric["unit"], metric["layer"], metric["moves"],
            metric["source"],
        )
        assert callable(reader.read)


def test_peaks_name_their_source(in_root):
    peaks = load_json(os.path.join(ROOT, "benchmark", "peaks.json"))
    row = peaks["TPU v5 lite"]
    assert (row["bf16_tflops"], row["int8_tops"], row["hbm_gbps"], row["hbm_gb"]) == (
        197.0, 393.0, 819.0, 16.0,
    )
    assert "TPU v5e" in row["source"]


# -- generators ------------------------------------------------------------

STORM = {
    "name": "storm", "rate_per_s": 3.0, "burst_factor": 4.0,
    "burst_every_s": 10.0, "burst_len_s": 2.0,
}


@pytest.mark.parametrize("name", ["poisson", "storm"])
def test_arrivals_repeat_for_a_seed_and_keep_the_mean_rate(name):
    params = {**STORM, "name": name}
    first = arrivals.make(7, params, 400.0)
    assert first == arrivals.make(7, params, 400.0)
    assert first != arrivals.make(8, params, 400.0)
    assert first == sorted(first) and 0 <= first[0] and first[-1] < 400.0
    assert len(first) / 400.0 == pytest.approx(3.0, rel=0.1)


def test_fixed_counts_offer_the_same_work_in_the_same_bursts_for_every_seed():
    params = {**STORM, "rate_per_s": 1.0, "counts": "fixed"}
    schedules = [arrivals.make(seed, params, 51.0) for seed in (1, 2, 3)]
    assert schedules[0] == arrivals.make(1, params, 51.0)
    assert schedules[0] != schedules[1]
    for due in schedules:
        # 25 between bursts, 25 in five whole bursts, 2.5 in the last second
        assert due == sorted(due) and len(due) == 53
        # base 0.625/s, bursts 2.5/s: 5 in each burst of 2 s, 5 in each 8 s between,
        # one in each fifth of the stretch
        assert [sum(1 for t in due if 10 * k <= t < 10 * k + 2) for k in range(5)] == [5] * 5
        assert [int((t - 10) / 0.4) for t in due if 10 <= t < 12] == [0, 1, 2, 3, 4]
    with pytest.raises(ValueError):
        arrivals.make(0, {**params, "name": "diurnal"}, 9.0)  # the original's; not copied


def test_answer_lengths_are_stratified():
    import random

    spec = {"dist": "loguniform", "low": 64, "high": 500}
    a = stats.draw_ints(random.Random(1), spec, 50)
    b = stats.draw_ints(random.Random(2), spec, 50)
    assert a == stats.draw_ints(random.Random(1), spec, 50) and a != b
    assert 64 <= min(a) and max(a) <= 500
    assert abs(sum(a) - sum(b)) / sum(a) < 0.02  # the same work from every seed
    assert sorted(a)[25] == pytest.approx(math.sqrt(64 * 500), rel=0.05)  # log-uniform median
    uniform = stats.draw_ints(random.Random(1), {"dist": "uniform", "low": 256, "high": 500}, 64)
    assert sum(uniform) / 64 == pytest.approx(378, abs=3)
    with pytest.raises(ValueError):
        stats.draw_ints(random.Random(1), {"dist": "zipf", "low": 1, "high": 2}, 3)


def test_storm_bursts_are_four_times_the_base_rate():
    due = arrivals.make(3, STORM, 2000.0)
    in_burst = sum(1 for t in due if t % 10.0 < 2.0)
    ratio = (in_burst / 2.0) / ((len(due) - in_burst) / 8.0)
    assert ratio == pytest.approx(4.0, rel=0.1)
    assert arrivals.rate_at(STORM, 1.0) == pytest.approx(4 * arrivals.rate_at(STORM, 5.0))
    with pytest.raises(ValueError):
        arrivals.make(0, {**STORM, "name": "tidal"}, 10.0)


def test_storm_prompts_share_the_preamble_and_reask():
    due = arrivals.make(5, STORM, 50.0)
    params = {"reask_share": 0.3, "reask_delay_s": [5.0, 20.0]}
    prompts = storm_prompts.make(5, params, due)
    assert prompts == storm_prompts.make(5, params, due)
    assert prompts != storm_prompts.make(6, params, due)
    preamble = storm_prompts.TEMPLATE.split("{")[0]
    assert all(p.startswith(preamble) for p in prompts)
    seen: dict = {}
    reasked = 0
    for t, p in zip(due, prompts):
        if p in seen:
            reasked += 1
            assert any(5.0 <= t - earlier <= 20.0 for earlier in seen[p])
        seen.setdefault(p, []).append(t)
    assert reasked == round(0.3 * len(prompts))  # exact: the same work from every seed
    # the fresh prompts of any seed come to the same length: a re-ask takes no
    # length out of the spread
    fresh = [sum(len(p) for p in set(storm_prompts.make(k, params, due))) for k in (5, 6, 7)]
    assert max(fresh) - min(fresh) < 0.02 * min(fresh)
    assert not storm_prompts.make(5, {}, due[:5])[0] in prompts[5:]  # no share, no re-ask


def test_a_structure_seed_fixes_the_storms_shape_and_the_seed_makes_the_words():
    due = arrivals.make(5, {**STORM, "counts": "fixed"}, 50.0)
    params = {"reask_share": 0.3, "reask_delay_s": [5.0, 20.0], "structure_seed": 9}
    a, b = storm_prompts.make(1, params, due), storm_prompts.make(2, params, due)
    assert a == storm_prompts.make(1, params, due)
    assert not set(a) & set(b)  # other words in every prompt
    # the same arrivals re-ask the same earlier arrivals
    assert [a.index(p) for p in a] == [b.index(p) for p in b]
    # and every arrival has the same length but for the names drawn
    assert max(abs(len(x) - len(y)) for x, y in zip(a, b)) < 40
    other = storm_prompts.make(1, {**params, "structure_seed": 10}, due)
    assert [a.index(p) for p in a] != [other.index(p) for p in other]
    # without the key the shape follows the seed, as before
    free = {k: v for k, v in params.items() if k != "structure_seed"}
    c, d = storm_prompts.make(1, free, due), storm_prompts.make(2, free, due)
    assert [c.index(p) for p in c] != [d.index(p) for p in d]


def test_the_storm_cell_sends_one_schedule_under_every_seed(in_root):
    from benchmark.harness import cell

    real = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    spec = cell.Spec.load(real, "qwen2.5-1.5b-int8.storm")
    assert "structure_seed" in spec.traffic
    a, b = cell.build_open(spec, 1, 51.0), cell.build_open(spec, 2, 51.0)
    assert len(a) == 252  # 4.8 requests/s in the mean, the window's sixth burst cut at 51 s
    assert [(r.due_t, r.max_tokens) for r in a] == [(r.due_t, r.max_tokens) for r in b]
    assert not {r.prompt for r in a} & {r.prompt for r in b}
    prompts_a, prompts_b = [r.prompt for r in a], [r.prompt for r in b]
    assert [prompts_a.index(p) for p in prompts_a] == [prompts_b.index(p) for p in prompts_b]
    assert len(set(prompts_a)) == 176  # 30% of 252 re-ask
    # a closed-loop mix has no such key: its shape is the seed's
    decode = cell.Spec.load(real, "qwen2.5-1.5b-int8.decode")
    assert decode.structure_seed(7) == 7
    assert spec.structure_seed(7) == spec.traffic["structure_seed"]


def test_the_storm_model_says_what_the_arrangement_does(in_root):
    """``tools/storm_model.py``: the scheduler's budget rule replayed on the
    CPU.  Under the mix's fixed shape the model's numbers hardly move with
    the seed; where the shape follows the seed the gap moves several times
    as far and the mean TTFT twice.  Its milliseconds are a model's and are
    held to nothing here."""
    from benchmark.tools import storm_model

    real = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    cell_name = "qwen2.5-1.5b-int8.storm"
    seeds = range(1, 13)
    fixed = [storm_model.model_cell(real, cell_name, seed, 51.0) for seed in seeds]
    free = [
        storm_model.model_cell(real, cell_name, seed, 51.0, structure=seed) for seed in seeds
    ]
    assert fixed[0] == storm_model.model_cell(real, cell_name, 1, 51.0)
    assert all(row["first_tokens"] == 252 for row in fixed + free)
    for name, times in (("token_gap_mean_ms", 4.0), ("ttft_mean_ms", 2.0)):
        moved_fixed = max(r[name] for r in fixed) - min(r[name] for r in fixed)
        moved_free = max(r[name] for r in free) - min(r[name] for r in free)
        assert moved_fixed * times < moved_free, name
    assert storm_model.spread([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx(2.0 / 3.0)
    # a hand-made case: one row alone, 100 prompt tokens in two chunks of 64
    # and 36, then two more tokens, each step walking the pages it has
    out = storm_model.replay(
        [(0.0, tuple(range(100)), 3)], page=64, chunk=64, budget=256, seconds=10.0,
        step_ms=(90.0, 0.11, 0.05),
    )

    def step(pages, tokens):
        return (90.0 + 0.11 * pages + 0.05 * tokens) / 1e3

    assert out["ttft_mean_ms"] == pytest.approx((step(1, 64) + step(2, 36)) * 1e3)
    assert out["token_gap_mean_ms"] == pytest.approx(step(2, 1) * 1e3)
    assert out["out_tokens_per_s"] == pytest.approx(0.3)


def test_storm_prompt_is_a_frozen_copy_of_the_products_template():
    from operator_tpu.serving import prompts as product

    assert storm_prompts.TEMPLATE == product.DEFAULT_TEMPLATE
    assert (
        storm_prompts.MAX_EVIDENCE_CHARS, storm_prompts.MAX_TAIL_CHARS,
        storm_prompts.MAX_PRIOR_INCIDENT_CHARS,
    ) == (
        product.MAX_EVIDENCE_CHARS, product.MAX_TAIL_CHARS,
        product.MAX_PRIOR_INCIDENT_CHARS,
    )


def test_prompt_lengths_in_tokens_are_what_the_cells_say(in_root):
    from operator_tpu.models.tokenizer import load_tokenizer

    tokenizer = load_tokenizer("builtin-bpe")
    due = arrivals.make(1, STORM, 50.0)
    storm = [
        len(tokenizer.encode(p))
        for p in storm_prompts.make(1, {"reask_share": 0.3, "reask_delay_s": [5, 20]}, due)
    ]
    assert 650 <= min(storm) and max(storm) <= 1548  # 2,048 less 500: never truncated
    decode = load_json(os.path.join(ROOT, "benchmark/traffic/decode.json"))["prompts"]
    prompts = log_prompts.make(1, decode, [0.0] * 200)
    assert prompts == log_prompts.make(1, decode, [0.0] * 200)
    lengths = sorted(len(tokenizer.encode(p)) for p in prompts)
    assert 80 <= lengths[0] and lengths[-1] <= 190
    # no two share a prefix as long as a page of 64 tokens
    heads = {tuple(tokenizer.encode(p)[:64]) for p in prompts}
    assert len(heads) == len(prompts)


# -- percentile, attainment, request arithmetic ------------------------------


@pytest.mark.parametrize(
    "n, expected", [(19, None), (20, 50), (46, 50), (47, 80), (91, 80),
                    (92, 90), (181, 90), (182, 95), (901, 95), (902, 99)],
)
def test_highest_percentile_with_ten_samples_beyond(n, expected):
    assert stats.highest_supported_percentile(n) == expected


def test_percentile_is_linear_and_a_failed_request_ranks_last():
    values = [float(v) for v in range(1, 102)]  # 1..101
    assert stats.percentile(values, 50) == 51.0
    assert stats.percentile(values, 90) == 91.0
    assert stats.percentile([1.0, 2.0], 50) == 1.5
    assert stats.percentile([], 50) is None
    with_failures = values[:-11] + [math.inf] * 11  # 11 of 101 failed
    assert stats.percentile(with_failures, 50) == 51.0
    assert stats.percentile(with_failures, 90) == math.inf
    assert stats.samples_beyond(101, 90) == 10 and stats.samples_beyond(91, 90) == 9


def test_attainment_counts_failures_as_misses():
    ttft = [100.0, 2500.0, math.inf, 1500.0]
    gaps = [50.0, 50.0, None, 150.0]
    assert stats.attainment(ttft, gaps, 2000.0, 100.0) == 0.25
    assert stats.attainment([100.0], [None], 2000.0, 100.0) == 1.0
    assert stats.attainment([], [], 2000.0, 100.0) is None


def test_request_record_arithmetic():
    req = loops.Request(index=0, prompt="p", max_tokens=20, due_t=10.0)
    assert req.failed and req.ttft_ms is None and req.gap_ms is None
    req.first_t, req.last_t, req.tokens = 10.5, 11.4, 10
    assert req.ttft_ms == pytest.approx(500.0)
    assert req.gap_ms == pytest.approx(100.0)
    assert not req.failed
    req.tokens = loops.MIN_GAP_TOKENS - 1
    assert req.gap_ms is None
    req.error = "refused"
    assert req.failed


def _finished(max_tokens, completion, eos_seen, reason="length", in_vocab=True):
    return loops.Request(
        index=0, prompt="p", max_tokens=max_tokens, finished=True,
        completion_tokens=completion, eos_seen=eos_seen, finish_reason=reason,
        ids_in_vocab=in_vocab, first_t=1.0,
    )


@pytest.mark.parametrize(
    "request_, ok",
    [
        (_finished(64, 64, 0), True),
        (_finished(64, 62, 2), True),  # two EOS ids streamed, filtered from the result
        (_finished(64, 63, 0), True),  # the unstreamed last token was an EOS
        (_finished(64, 60, 0), False),  # tokens dropped
        (_finished(64, 64, 0, reason="stop"), False),
        (_finished(64, 64, 0, in_vocab=False), False),
    ],
)
def test_window_correctness_rule(request_, ok):
    from benchmark.harness import cell

    class Handle:
        def engine_resets(self):
            return 0

    window = loops.Window(0.0, 1.0, [request_], 0, 0, 0, [])
    assert cell.window_correct(Handle(), window)[0] is ok


def test_an_engine_reset_is_incorrect():
    from benchmark.harness import cell

    class Handle:
        def engine_resets(self):
            return 1

    window = loops.Window(0.0, 1.0, [_finished(8, 8, 0)], 0, 0, 0, [])
    assert cell.window_correct(Handle(), window)[0] is False


# -- the greedy requests of a mix, and the sample the reference reads ---------


def test_every_mix_sends_greedy_requests_through_the_sampler(manifest):
    """What decides ``correct`` is read from requests of the window itself,
    so every mix names which of them are greedy: at the sampler's floor
    temperature with a top-p that keeps the first candidate alone, the
    argmax through the same sampler as every other row (``temperature
    <= 0`` would switch on the host's prompt-lookup drafting, a path no
    other row of the mix takes)."""
    from benchmark.harness import cell

    for item in manifest.doc["workloads"]:
        spec = cell.Spec.load(manifest, item["name"])
        every, sampling = spec.greedy()
        assert 2 <= every <= 16, item["name"]
        assert 0 < sampling["temperature"] <= 1e-4 and 0 < sampling["top_p"] <= 1e-6
        assert sampling["stop_on_eos"] is spec.traffic["sampling"]["stop_on_eos"]
        if spec.traffic["loop"] == "open":
            requests = cell.build_open(spec, 5, 20.0)
            marked = [r.index for r in requests if r.sampling is not None]
            assert marked == list(range(0, len(requests), every))
        else:
            pools = cell.build_closed(spec, 5, 16)
            for c, pool in enumerate(pools):
                assert all((r.sampling is not None) == (c % every == 0) for r in pool)
        assert requests[0].sampling == sampling if spec.traffic["loop"] == "open" else True


def _served(index, finished_at, prompt_tokens, ids, sampling={"temperature": 1e-4}, done=True):  # noqa: B006
    return loops.Request(
        index=index, prompt=f"prompt {index}", max_tokens=len(ids), finished=done,
        last_t=finished_at, first_t=0.1, prompt_tokens=prompt_tokens, token_ids=ids,
        sampling=sampling,
    )


def test_the_served_sample_is_of_the_windows_own_greedy_requests(in_root):
    """Drawn from the seed, the longest among them, and only requests that
    the window finished: not one that finished in the ramp or the drain,
    not one that was sampled, not one still running."""
    from benchmark.harness import cell

    class Handle:
        def prompt_ids(self, prompt, max_tokens):
            return [int(prompt.split()[1])] * 3

    spec = cell.Spec.load(Manifest(os.path.join(ROOT, "tests/benchmark/rehearsal.json")),
                          "tiny-test.decode")
    assert spec.config["probe"]["requests"] == 4
    sent = [_served(i, 10.0 + i, 20 + i, [i] * (5 + i)) for i in range(8)]
    sent += [
        _served(8, 9.0, 90, [8] * 50),  # finished in the ramp
        _served(9, 61.5, 90, [9] * 50),  # finished in the drain
        _served(10, 30.0, 90, [10] * 50, sampling=None),  # sampled, ids not kept
        _served(11, 30.0, 90, [11] * 50, done=False),  # still running at the close
    ]
    window = loops.Window(10.0, 61.0, sent[:4], 0, 0, 0, [], sent=sent)
    first = cell.served_sample(spec, Handle(), window, 7)
    assert len(first) == 4 and all(served[0] < 8 for _, served in first)
    assert ([7] * 3, [7] * 12) in first  # the longest
    assert first == cell.served_sample(spec, Handle(), window, 7)  # the same seed, the same sample
    others = {tuple(s[0] for _, s in cell.served_sample(spec, Handle(), window, k)) for k in range(12)}
    assert len(others) > 1 and all(7 in picked for picked in others)
    # fewer finished than asked: the run says so, and cannot read correct
    window = loops.Window(10.0, 61.0, sent[:2], 0, 0, 0, [], sent=sent[:2])
    compared = cell.compare_served(spec, [])
    assert compared["served_requests_missing"] == {"value": 4, "limit": 0}
    assert list(compared) == ["served_requests_missing"]


@pytest.mark.parametrize(
    "result_ids, streamed, kept",
    [
        ([5, 6, 7, 8], [5, 6, 7], [5, 6, 7, 8]),  # whole: the answer as the user got it
        ([5, 7, 8], [5, 2, 7], [5, 2, 7]),  # an EOS id filtered out: the ids as streamed
    ],
)
def test_a_greedy_requests_served_ids_keep_their_positions(result_ids, streamed, kept):
    import asyncio
    import types

    class Handle:
        eos_id, vocab_size = 2, 100

        async def generate(self, prompt, max_tokens, sampling, on_partial=None):
            assert sampling == {"temperature": 1e-4}
            for k in range(1, len(streamed) + 1):
                on_partial(streamed[:k])
            return types.SimpleNamespace(
                token_ids=result_ids, completion_tokens=len(result_ids), prompt_tokens=3,
                finish_reason="length", queue_wait_ms=0.0,
            )

    req = loops.Request(index=0, prompt="p", max_tokens=4, sampling={"temperature": 1e-4})
    asyncio.run(loops.send(Handle(), req, {"temperature": 0.3}, loops.TokenMeter()))
    assert req.finished and req.token_ids == kept
    plain = loops.Request(index=0, prompt="p", max_tokens=4)

    class Sampled(Handle):
        async def generate(self, prompt, max_tokens, sampling, on_partial=None):
            assert sampling == {"temperature": 0.3}  # the mix's own
            return await Handle.generate(self, prompt, max_tokens, {"temperature": 1e-4}, on_partial)

    asyncio.run(loops.send(Sampled(), plain, {"temperature": 0.3}, loops.TokenMeter()))
    assert plain.finished and plain.token_ids is None  # kept for greedy requests only


# -- the float32 reference against the program's forward pass ----------------


def tiny_model(bias=False, tied=False, seed=3):
    """The tiny preset's quantised parameters, its configuration document
    as a file would state it, and the program's ``ModelConfig``."""
    import jax
    import jax.numpy as jnp

    from operator_tpu.models import get_config
    from operator_tpu.models.llama import init_params
    from operator_tpu.models.quant import quantize_params

    config = dataclasses.replace(
        get_config("tiny-test"), attention_bias=bias, tie_embeddings=tied
    )
    params = init_params(config, jax.random.PRNGKey(seed), dtype=jnp.float32)
    if bias:  # the seeded init leaves biases zero: a dropped one must show
        keys = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
        for key, name in zip(keys, ("bq", "bk", "bv")):
            shape = params["layers"][name].shape
            params["layers"][name] = 0.5 * jax.random.normal(key, shape, jnp.float32)
    params = quantize_params(params, config)
    doc = {"architecture": {
        "num_hidden_layers": config.num_layers, "hidden_size": config.hidden_size,
        "num_attention_heads": config.num_heads,
        "num_key_value_heads": config.num_kv_heads, "head_dim": config.head_dim,
        "vocab_size": config.vocab_size, "tie_word_embeddings": tied,
        "rope_theta": config.rope_theta, "rms_norm_eps": config.rms_norm_eps,
    }}
    return params, doc, config


def program_logits(params, config, ids):
    import jax.numpy as jnp

    from operator_tpu.models.llama import forward

    theirs, _ = forward(
        params, config, jnp.asarray([ids], jnp.int32),
        jnp.arange(len(ids), dtype=jnp.int32)[None],
    )
    return theirs[0]


def tiny_limit():
    return load_json(os.path.join(ROOT, "tests/benchmark/configs/tiny-test.json"))["probe"]["limit"]


def tiny_doc():
    """The tiny configuration's file: sizes, the weights' recipe, the limits."""
    return load_json(os.path.join(ROOT, "tests/benchmark/configs/tiny-test.json"))


def reference_logits(decoder, weights, arch, ids):
    """Full logits of one sequence, in the test's own arithmetic: the
    reference's hidden states through the head in numpy float64."""
    import numpy as np

    hidden = decoder.hidden_states(weights, arch, [ids])
    assert hidden.shape[:2] == (1, 256)  # one sequence, padded
    head = weights.embed.T if weights.head is None else weights.head
    return np.asarray(hidden[0, :len(ids)], np.float64) @ np.asarray(head, np.float64)


@pytest.mark.parametrize("bias, tied", [(False, False), (True, False), (True, True)])
def test_reference_matches_the_programs_forward(bias, tied):
    """The reference's arithmetic against the program's forward pass on one
    tree (mapped by ``adapt``, layout only; biases set, which no init
    makes).  What a run compares is on weights of the reference's own: the
    next test."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import decoder_f32, decoder_f32_weights

    params, doc, config = tiny_model(bias, tied)
    arch = doc["architecture"]
    ids = [int(t) for t in jax.random.randint(jax.random.PRNGKey(5), (37,), 0, 512)]
    weights = decoder_f32_weights.adapt(params, doc)
    ours = reference_logits(decoder_f32, weights, arch, ids)
    theirs = np.asarray(program_logits(params, config, ids))
    assert ours.shape == (37, 512)
    assert float(np.max(np.abs(ours - theirs))) < 2e-4
    # teacher-forced on the program's own greedy choices, every gap is ~0
    chosen = [int(t) for t in jnp.argmax(theirs[20:36], axis=-1)]
    (gaps,) = decoder_f32.greedy_gaps(doc, weights, [(ids[:21], ids[21:37])])
    assert len(gaps) == 16 and all(g >= 0 for g in gaps)
    assert gaps == pytest.approx(
        [float(ours[20 + j].max() - ours[20 + j, ids[21 + j]]) for j in range(16)], abs=1e-4
    )
    (first,) = decoder_f32.greedy_gaps(doc, weights, [(ids[:21], chosen[:1])])
    assert max(first) < 1e-3
    # all the sampled sequences at once, of unequal lengths: each reads what it
    # reads alone (padding and the other rows are invisible to it)
    together = decoder_f32.greedy_gaps(
        doc, weights, [(ids[:21], ids[21:37]), (ids[:9], ids[9:12]), (ids[:21], chosen[:1])]
    )
    assert [len(row) for row in together] == [16, 3, 1]
    assert together[0] == pytest.approx(gaps, abs=1e-4)
    assert together[2] == pytest.approx(first, abs=1e-4)
    if bias:  # without the biases the logits move by far more than the limit
        for name in ("bq", "bk", "bv"):
            params["layers"][name] = jnp.zeros_like(params["layers"][name])
        dropped = reference_logits(
            decoder_f32, decoder_f32_weights.adapt(params, doc), arch, ids
        )
        assert float(np.max(np.abs(dropped - ours))) > tiny_limit()


def differing_leaves(mine, theirs):
    """Names of the leaves of two ``decoder_f32_weights.Weights`` that are
    not equal bit for bit."""
    import numpy as np

    flat = lambda w: {  # noqa: E731
        **{k: v for k, v in w.leaves.items() if k != "layers"},
        **{
            f"{name}.{part}": value
            for name, leaf in w.layers.items()
            for part, value in (leaf.items() if isinstance(leaf, dict) else [("", leaf)])
        },
    }
    mine, theirs = flat(mine), flat(theirs)
    assert set(mine) == set(theirs)
    return sorted(
        name for name in mine
        if np.asarray(mine[name]).dtype != np.asarray(theirs[name]).dtype
        or not np.array_equal(np.asarray(mine[name]), np.asarray(theirs[name]))
    )


def test_the_references_own_weights_are_the_programs_bit_for_bit():
    """``decoder_f32_weights.make`` reads a recipe and nothing of the
    program, and what it makes is the program's seeded init to the bit
    (``tools/weights_check.py`` says the same on the chip at both sizes);
    a program that quantised with another scale would differ in every
    ``q``, which is what a reference fed the program's own ``q`` and ``s``
    could never see."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import decoder_f32_weights
    from operator_tpu.models import get_config
    from operator_tpu.models.quant import init_params_quantized

    doc = tiny_doc()
    with open(decoder_f32_weights.__file__, encoding="utf-8") as f:
        assert "operator_tpu" not in f.read().replace("``operator_tpu", "")
    mine = decoder_f32_weights.make(doc)
    program = init_params_quantized(
        get_config("tiny-test"), jax.random.PRNGKey(doc["weights"]["seed"]), dtype=jnp.bfloat16
    )
    assert differing_leaves(mine, decoder_f32_weights.adapt(program, doc)) == []
    # a wrong scale in the program (126 levels in place of 127) is another model
    wrong = dict(program["layers"])
    w32 = wrong["wq"]["q"].astype(jnp.float32) * wrong["wq"]["s"][:, None, :]
    scale = jnp.max(jnp.abs(w32), axis=-2) / 126.0
    wrong["wq"] = {"q": jnp.round(w32 / scale[:, None, :]).astype(jnp.int8), "s": scale}
    assert differing_leaves(
        mine, decoder_f32_weights.adapt({**program, "layers": wrong}, doc)
    ) == ["wq.q", "wq.s"]


def test_the_rehearsals_own_reference_agrees_and_the_wrong_one_does_not(in_root):
    """``tests/benchmark/reference/``: numpy float64, written apart, found by
    the name in a configuration file, on weights it makes itself; the
    interleaved rotary is another model."""
    import numpy as np

    rehearsal = Manifest(os.path.join(ROOT, "tests/benchmark/rehearsal-reference.json"))
    config = rehearsal.config("tiny-own-reference")
    own, weights_module = reference_modules(rehearsal, config)
    wrong, _ = reference_modules(rehearsal, rehearsal.config("tiny-wrong-reference"))
    for module in (own, wrong, weights_module):
        assert module.__file__.startswith(os.path.join(ROOT, "tests/benchmark/reference/"))
    params, doc, program = tiny_model(bias=True)
    ids = [int(t) for t in np.random.default_rng(5).integers(0, 512, 37)]
    theirs = np.asarray(program_logits(params, program, ids))
    weights = weights_module.adapt(params, doc)
    assert np.max(np.abs(own.forward(doc["architecture"], weights, ids) - theirs)) < 2e-4
    sequences = [(ids[:21], [int(theirs[20].argmax())])]  # the program's own choice
    assert max(own.greedy_gaps(doc, weights, sequences)[0]) < 1e-3
    # the same gaps as the benchmark's reference reads, on arbitrary tokens
    from benchmark.reference import decoder_f32, decoder_f32_weights

    arbitrary = [(ids[:21], ids[21:37])]
    assert own.greedy_gaps(doc, weights, arbitrary)[0] == pytest.approx(
        decoder_f32.greedy_gaps(doc, decoder_f32_weights.adapt(params, doc), arbitrary)[0],
        abs=1e-3,
    )
    # and on the weights each makes for itself from the file's recipe: two
    # readers of one recipe, written apart, hold the same model
    assert own.greedy_gaps(config, weights_module.make(config), arbitrary)[0] == pytest.approx(
        decoder_f32.greedy_gaps(config, decoder_f32_weights.make(config), arbitrary)[0],
        abs=5e-3,  # float64 against float32, on gaps of 3 to 6
    )
    assert max(wrong.greedy_gaps(doc, weights, arbitrary)[0]) != pytest.approx(
        max(own.greedy_gaps(doc, weights, arbitrary)[0]), abs=0.1
    )
    worst = max(
        wrong.greedy_gaps(doc, weights, [(ids[:k], [int(theirs[k - 1].argmax())])])[0][0]
        for k in range(8, 37)
    )
    assert worst > 3 * tiny_limit()  # the program's own choices, under the wrong rotary


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_comes_out_not_correct(seed):
    """The control (``decoder_f32.control_gaps``: the recipe's init at int4
    in place of int8, the reference put in the program's place) at a size a
    test can hold, through the harness's own comparison (``cell.judge``
    under the tiny configuration's own limits): not correct, where the
    int8 model's own choices are."""
    import numpy as np

    from benchmark.harness import cell
    from benchmark.reference import decoder_f32, decoder_f32_weights

    doc = tiny_doc()
    weights = decoder_f32_weights.make(doc)
    rng = np.random.default_rng(seed)
    prompts = [[int(t) for t in rng.integers(0, 512, 40 + 5 * k)] for k in range(4)]
    forced = [(ids[:-16], ids[-16:]) for ids in prompts]  # 16 scored positions each

    def verdict(gaps):
        compared = cell.judge(gaps, doc["probe"])
        assert compared
        return all(entry["value"] <= entry["limit"] for entry in compared.values())

    lowered = decoder_f32.control_gaps(doc, weights, forced)
    assert [len(row) for row in lowered] == [16] * 4 and min(map(min, lowered)) >= 0.0
    assert verdict(lowered) is False
    assert sum(1 for row in lowered for g in row if g > 0) >= 16  # int4 moves a quarter of the choices
    # the int8 model's own first choices at the same positions read nothing
    rows, padded = decoder_f32._rows(weights, doc["architecture"], forced)
    _, first, _ = decoder_f32.head_reduce(
        weights, doc["architecture"], rows, np.zeros(rows.shape[0], np.int32)
    )
    own_choice = [
        (ids[:-16], [int(first[i * padded + len(ids) - 17])]) for i, ids in enumerate(prompts)
    ]
    assert verdict(decoder_f32.greedy_gaps(doc, weights, own_choice)) is True


# -- the trace reducer -------------------------------------------------------


def test_reducer_on_a_hand_made_trace():
    ms = 1e6
    events = {
        "device": {"/device:TPU:0": [
            ("while.1", 10 * ms, 40 * ms),  # holds the two below
            ("fusion.2", 12 * ms, 10 * ms),
            ("custom-call.3", 30 * ms, 15 * ms),
            ("fusion.2", 60 * ms, 10 * ms),
        ]},
        "host": [
            ("python", "bench.trace_slice", 0.0, 100 * ms),
            ("tpu-decode_0", "podmortem.sched_step", 50 * ms, 9 * ms),
            ("tpu-decode_0", "podmortem.sched_step", 0.0, 200 * ms),  # longer: less specific
            ("python", "bench.submit", 75 * ms, 5 * ms),
        ],
    }
    out = trace_reduce.reduce(events)
    assert out["window_s"] == pytest.approx(0.100)
    assert out["busy_s"] == pytest.approx(0.050)
    assert out["idle_share"] == pytest.approx(0.5)
    ops = dict(out["device_ops"])
    assert ops["fusion.2"] == pytest.approx(0.020)
    assert ops["custom-call.3"] == pytest.approx(0.015)
    assert ops["while.1"] == pytest.approx(0.015)  # 40 less its children's 25
    assert out["device_ops"][0][0] == "fusion.2"
    gaps = dict(out["idle_gaps"])
    # 0-10, 50-60 and 70-100 ms are idle.  The middle of the second lies in the
    # short dispatch span; the other two only in the long one of the same name
    assert sum(gaps.values()) == pytest.approx(0.050)
    assert gaps == {"tpu-decode_0:podmortem.sched_step": pytest.approx(0.050)}
    events["host"][2] = ("tpu-decode_0", "tpu-decode.run", 0.0, 200 * ms)
    events["host"][3] = ("python", "bench.submit", 80 * ms, 10 * ms)
    gaps = dict(trace_reduce.reduce(events)["idle_gaps"])
    assert gaps == {
        "tpu-decode_0:tpu-decode.run": pytest.approx(0.010),  # 0-10: nothing shorter
        "tpu-decode_0:podmortem.sched_step": pytest.approx(0.010),  # 50-60
        "python:bench.submit": pytest.approx(0.030),  # 70-100, middle 85
    }
    with pytest.raises(ValueError):
        trace_reduce.reduce({"device": {}, "host": []})


def test_reducer_counts_the_programs_that_ran_inside_the_window():
    ms = 1e6
    events = {
        "device": {"/device:TPU:0": [("fusion.2", 10 * ms, 80 * ms)]},
        "modules": {"/device:TPU:0": [
            ("jit_mixed_fn(7)", -5 * ms, 20 * ms),  # began before the window: left out
            ("jit_mixed_fn(7)", 20 * ms, 30 * ms),
            ("jit_mixed_fn(7)", 50 * ms, 34 * ms),
            ("jit_scatter(9)", 85 * ms, 1 * ms),
        ]},
        "host": [("python", "bench.trace_slice", 0.0, 100 * ms)],
    }
    out = trace_reduce.reduce(events)
    assert out["programs"] == [
        ["jit_mixed_fn", 2, pytest.approx(0.064)], ["jit_scatter", 1, pytest.approx(0.001)],
    ]
    from benchmark.layer_metrics import step_device_ms, step_weight_floor_share

    class Handle:
        def param_bytes(self):
            return 8_190_000  # 0.01 ms at 819 GB/s

    class Traced:
        trace = out
        peaks = {"hbm_gbps": 819.0}
        handle = Handle()

    assert step_device_ms.read(Traced()) == pytest.approx(32.0)
    assert step_weight_floor_share.read(Traced()) == pytest.approx(0.01 / 32.0)
    Traced.peaks = None  # off the chip there is no roofline
    assert step_weight_floor_share.read(Traced()) is None
    kept = trace_reduce.cut(events, 15 * ms, 90 * ms)
    assert [m[0] for m in kept["modules"]["/device:TPU:0"]] == [
        "jit_mixed_fn(7)", "jit_mixed_fn(7)", "jit_scatter(9)",
    ]


def test_pool_readers_take_the_fullest_sample():
    from benchmark.layer_metrics import kv_pool_fill_share, kv_pool_rows_share

    class Window:
        pool = [(10, 40, 100), (30, 90, 100), (20, 100, 100)]

    class Sampled:
        window = Window()

    assert kv_pool_rows_share.read(Sampled()) == pytest.approx(0.3)
    assert kv_pool_fill_share.read(Sampled()) == pytest.approx(1.0)
    Window.pool = []  # an untraced run samples nothing
    assert kv_pool_rows_share.read(Sampled()) is None


def test_reducer_reproduces_the_recorded_chip_trace(in_root):
    """250 ms (three steps) cut from the first traced chip run of
    qwen2.5-1.5b-int8.decode (TPU v5 lite, PR 22) by tools/record_trace.py."""
    events = load_json(
        os.path.join(ROOT, "benchmark/trace/recorded_v5e_decode_steps.json")
    )
    assert sum(len(ops) for ops in events["device"].values()) == 5531
    out = trace_reduce.reduce(events)
    assert out["window_s"] == pytest.approx(0.25)
    assert out["busy_s"] == pytest.approx(0.249803976, rel=1e-6)
    assert out["idle_share"] == pytest.approx(0.000784096, rel=1e-4)
    assert out["planes"] == ["/device:TPU:0"]
    top = out["device_ops"][:4]
    assert [name for name, _ in top] == [
        "_ragged_attention_pallas.6 f32[64,64,12,128]",
        "fusion.113 f32[320,64]",
        "dynamic-slice_bitcast_fusion.5 bf16[2049,64,2,128]",
        "bitcast_dynamic-update-slice_fusion.5 bf16[28,2049,64,2,128]",
    ]
    assert [s for _, s in top] == pytest.approx(
        [0.130609418, 0.021292871, 0.01857918, 0.018525683], rel=1e-6
    )
    assert out["idle_gaps"] == [["no host span", pytest.approx(0.000165777, rel=1e-4)]]
    assert out["programs"] == []  # recorded before the reducer read the programs' line
    # the kernel's share as layer_metrics/attn_kernel_share.py reads it, under
    # the name the kernel had that day; the reader's pattern is for the name it
    # has had since PR 25 and finds nothing to read here: no metric, never a 0
    from benchmark.layer_metrics import attn_kernel_share

    class Traced:
        trace = out

    kernel_s = sum(
        seconds for name, seconds in out["op_self_s"].items()
        if "_ragged_attention_pallas" in name
    )
    assert kernel_s / sum(out["op_self_s"].values()) == pytest.approx(0.5228, abs=1e-3)
    assert attn_kernel_share.read(Traced()) is None


def test_short_names_keep_the_instruction_and_its_result_type():
    assert trace_reduce.short_name(
        "%copy.95 = bf16[28,2049,64,2,128]{4,3,2,1,0:T(2,128)(2,1)} copy(bf16[28,2049,64] %x)"
    ) == "copy.95 bf16[28,2049,64,2,128]"
    assert trace_reduce.short_name(
        "%fusion.115 = (f32[320,64]{1,0:T(8,128)S(1)}, s32[320,64]{1,0}) fusion(f32[320,151936] %r)"
    ) == "fusion.115 f32[320,64]"
    assert trace_reduce.short_name("dot_general.1") == "dot_general.1"


def test_reducer_without_the_window_span_uses_first_to_last_event():
    ms = 1e6
    events = {
        "device": {"/device:TPU:0": [("a", 5 * ms, 5 * ms), ("b", 20 * ms, 10 * ms)]},
        "host": [],
    }
    out = trace_reduce.reduce(events)
    assert out["window_s"] == pytest.approx(0.025)
    assert out["busy_s"] == pytest.approx(0.015)
    assert dict(out["idle_gaps"]) == {"no host span": pytest.approx(0.010)}
    cut = trace_reduce.cut(events, 4 * ms, 12 * ms)
    assert cut["device"]["/device:TPU:0"] == [("a", 5 * ms, 5 * ms)]
    assert trace_reduce.reduce(cut)["window_s"] == pytest.approx(0.008)


# -- rehearsal: the command itself, on the CPU -------------------------------

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}
SETUP_PARTS = ["engine_build", "warm_up", "traffic", "ramp"]
#: after the window, and in no metric: closing the engine, then the reference
AFTER_PARTS = ["window", "reduce_and_close", "reference_weights", "reference_forward"]
DEVICE_ONLY = {
    "device_idle_share", "attn_kernel_share", "step_weight_floor_share",
    "step_device_ms", "peak_hbm_gb",
}


def _run(workload, trace, platform="cpu", seconds="3", manifest="tests/benchmark/rehearsal.json"):
    env = {k: v for k, v in os.environ.items() if k != "OPERATOR_TPU_PLATFORM"}
    env["OPERATOR_TPU_MODEL"] = "qwen2.5-7b"  # must be scrubbed, or this would not fit
    env["BENCH_MODEL"] = "qwen2.5-7b"
    if platform:
        env["OPERATOR_TPU_PLATFORM"] = platform
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--manifest", manifest,
         "--workload", workload, "--seed", "11", "--seconds", seconds,
         "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )


@pytest.fixture(scope="module")
def rehearsals():
    """One run per loop kind, and each traced: under a minute in all."""
    runs = {
        "open": _run("tiny-test.storm", 0),
        "closed": _run("tiny-test.decode", 0),
        "traced": _run("tiny-test.storm", 1, seconds="4"),
        "traced_closed": _run("tiny-test.decode", 1),
    }
    lines = {}
    for key, proc in runs.items():
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines[key] = json.loads(proc.stdout.strip().splitlines()[-1])
        lines[key + ":stderr"] = proc.stderr
    return lines


@pytest.mark.parametrize("kind", ["open", "closed", "traced", "traced_closed"])
def test_rehearsal_prints_the_contracts_last_line(rehearsals, kind):
    line = rehearsals[kind]
    assert set(line) == CONTRACT_KEYS  # no breakdown: nothing ran on a device
    assert list(line)[-1] == "compared"  # each number compared, beside its limit, last
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    compared = line["compared"]
    assert list(compared) == [
        "served_gap_max", "served_requests_missing", "window_requests_wrong",
    ]
    assert all(entry["value"] <= entry["limit"] for entry in compared.values())
    assert compared["served_gap_max"]["limit"] == tiny_limit()
    # and as the last lines on standard error
    tail = rehearsals[kind + ":stderr"].strip().splitlines()[-len(compared):]
    assert [t.split()[2].rstrip(":") for t in tail] == list(compared)
    assert all(t.startswith("[benchmark] compared ") and "(limit " in t for t in tail)
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"
    for metric in line["metrics"].values():
        assert set(metric) == {"value", "unit"} and math.isfinite(metric["value"])


def test_rehearsal_reports_each_cells_own_metrics(rehearsals):
    # no TTFT of the open loop is judged: the mean is recorded per layer
    assert set(rehearsals["open"]["metrics"]) == {"token_gap_mean_ms", "setup_s"}
    assert set(rehearsals["closed"]["metrics"]) == {
        "token_gap_mean_ms", "out_tokens_per_s", "setup_s",
    }
    traced = set(rehearsals["traced"]["metrics"])
    assert {"gen_lateness_p95_ms", "queue_wait_p50_ms", "step_ms_mean",
            "kv_pool_rows_share", "kv_pool_fill_share", "midrun_compiles",
            "slo_attainment", "ttft_mean_ms", "ttft_p50_ms", "token_gap_p50_ms"} <= traced
    # 4 s hold too few requests for a tail: ten samples must lie beyond it
    assert "ttft_p80_ms" not in traced
    # a CPU run writes nothing under a device metric's name
    assert not traced & DEVICE_ONLY
    assert not traced & set(rehearsals["open"]["metrics"])
    # the closed loop's per-layer list is the rehearsal's own for that cell,
    # less the device's: the real manifest may grow without this test
    own = Manifest(os.path.join(ROOT, "tests/benchmark/rehearsal.json"))
    cells = {m["name"] for m in own.metrics_for("per_layer", "tiny-test.decode")}
    assert set(rehearsals["traced_closed"]["metrics"]) == cells - DEVICE_ONLY
    pool = rehearsals["traced_closed"]["metrics"]
    assert 0 < pool["kv_pool_rows_share"]["value"] <= pool["kv_pool_fill_share"]["value"] <= 1


@pytest.mark.parametrize("kind, cell", [("open", "tiny-test.storm"), ("closed", "tiny-test.decode")])
def test_rehearsal_logs_the_seconds_of_each_part_of_set_up(rehearsals, kind, cell):
    saved = load_json(os.path.join(ROOT, f"benchmark/out/setup/{cell}.seed11.json"))
    assert list(saved["parts"]) == SETUP_PARTS + AFTER_PARTS
    assert all(seconds >= 0 for seconds in saved["parts"].values())
    # the reference runs after the window: its seconds are in no metric
    assert sum(saved["parts"][k] for k in SETUP_PARTS) == pytest.approx(saved["setup_s"], abs=1e-6)
    assert "[benchmark] set-up parts (s): {" in rehearsals[kind + ":stderr"]
    if kind == "closed":  # the mix's ramp of 0.5 s is set-up, and is named
        assert saved["parts"]["ramp"] == pytest.approx(0.5, abs=0.2)
    # the comparison's log line says where its limit comes from
    origin = load_json(os.path.join(ROOT, "tests/benchmark/configs/tiny-test.json"))["probe"]["origin"]
    assert f"(limit {tiny_limit()}: {origin})" in rehearsals[kind + ":stderr"]


REFERENCE_MANIFEST = "tests/benchmark/rehearsal-reference.json"


@pytest.fixture(scope="module")
def reference_rehearsals():
    """A configuration of its own reference, one of a wrong reference and
    one on a broken entry, each through the command."""
    lines = {}
    for config in ("tiny-own-reference", "tiny-wrong-reference", "tiny-token-altered"):
        proc = _run(config + ".decode", 0, manifest=REFERENCE_MANIFEST)
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines[config] = json.loads(proc.stdout.strip().splitlines()[-1])
    return lines


@pytest.mark.parametrize(
    "config, correct",
    [("tiny-own-reference", True), ("tiny-wrong-reference", False), ("tiny-token-altered", False)],
)
def test_the_reference_a_configuration_names_decides_correct(reference_rehearsals, config, correct):
    line = reference_rehearsals[config]
    assert line["correct"] is correct
    assert line["failed"] == 0 and line["attempted"] > 0  # the run itself is whole
    gap = line["compared"]["served_gap_max"]
    assert (gap["value"] <= gap["limit"]) is correct
    if not correct:  # an order-one fault, not a near miss
        assert gap["value"] > 5 * gap["limit"]
        assert line["compared"]["window_requests_wrong"]["value"] == 0


def test_the_second_rehearsal_adds_files_only_under_the_tests(in_root):
    """Every file the reference rehearsal resolves that the first rehearsal
    does not is under ``tests/benchmark/``: nothing under ``benchmark/``
    was edited or added for it."""
    mine = Manifest(os.path.join(ROOT, REFERENCE_MANIFEST))
    added = []
    for item in mine.doc["configs"]:
        config = mine.config(item["name"])
        reference, weights_module = reference_modules(mine, config)
        entry = mine.module("entries", config["entry"])
        added += [os.path.join(ROOT, item["file"])]
        added += [m.__file__ for m in (reference, weights_module, entry)]
    shared = {
        os.path.join(ROOT, "benchmark/entries/engine.py"),
        os.path.join(ROOT, "benchmark/reference/decoder_f32.py"),
        os.path.join(ROOT, "benchmark/reference/decoder_f32_weights.py"),
    }
    new = set(added) - shared
    assert len(new) == 3 + 3 + 1  # configurations; reference, wrong reference, their weights; entry
    assert all(path.startswith(os.path.join(ROOT, "tests/benchmark/")) for path in new)
    assert os.path.join(ROOT, "tests/benchmark/reference/numpy_f64.py") in new


def test_without_a_named_backend_the_command_fails_and_prints_no_line():
    proc = _run("tiny-test.storm", 0, platform=None)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "NoAccelerator" in proc.stderr
