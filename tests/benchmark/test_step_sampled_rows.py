"""The reader of ``step_sampled_rows_mean`` (PR 32), CPU only: on
hand-made step records, on a program without the field, and its
entry in ``BENCHMARK.json`` (appended by PR 33, once the benchmark's
tests found their entries by name) against the reader and the step
record's field.
"""

from __future__ import annotations

import dataclasses
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import manifest as manifest_mod  # noqa: E402
from benchmark.layer_metrics import step_sampled_rows_mean  # noqa: E402

#: every cell whose entry is the serving engine (all of them today)
SERVING_CELLS = [
    "qwen2.5-1.5b-int8.storm", "qwen2.5-1.5b-int8.decode",
    "qwen2.5-7b-int8.decode", "falcon-h1-34b-int8.decode",
    "qwen2.5-7b-int8.storm",
]


@dataclasses.dataclass
class Record:
    tokens: int
    sampled_rows: int | None = None


@dataclasses.dataclass
class OldRecord:
    """A step record of a program from before the field."""

    tokens: int


class Steps:
    def __init__(self, steps_):
        self.steps = steps_


@pytest.mark.parametrize("records, want", [
    # 128 slots: two steps at one row a slot, one that verified at width 5
    ([Record(128, 128), Record(130, 128), Record(140, 640)], 896 / 3),
    ([Record(128, 128)] * 4, 128.0),
    # an engine that does not count writes None; an older program no field
    ([Record(128), Record(64)], None),
    ([OldRecord(128)], None),
    ([], None),
], ids=["mixed-widths", "narrow-only", "none", "no-field", "no-steps"])
def test_sampled_rows_reader_by_hand(records, want):
    got = step_sampled_rows_mean.read(Steps(records))
    assert got is None if want is None else got == pytest.approx(want)


def check_the_entry(manifest):
    """The manifest's entry, found by name, against the reader: a layer
    the manifest names elsewhere too, an end-to-end metric every serving
    cell reports, every serving cell listed, and the step record's field."""
    from operator_tpu.obs.steptrace import StepRecord

    reader = step_sampled_rows_mean
    entry = next(m for m in manifest.doc["per_layer"] if m["name"] == reader.NAME)
    assert manifest_mod.NAME.match(reader.NAME) and manifest_mod.UNIT.match(reader.UNIT)
    assert entry == {
        "name": reader.NAME, "unit": "count", "better": "lower",
        "source": "program_counter", "layer": reader.LAYER, "moves": reader.MOVES,
        "workloads": entry["workloads"],
    }
    assert (reader.UNIT, reader.SOURCE) == (entry["unit"], entry["source"])
    assert reader.LAYER in {
        m["layer"] for m in manifest.doc["per_layer"] if m["name"] != reader.NAME
    }
    assert set(SERVING_CELLS) <= set(entry["workloads"])
    for cell in entry["workloads"]:
        assert reader.MOVES in {m["name"] for m in manifest.metrics_for("end_to_end", cell)}
        assert reader.NAME in {m["name"] for m in manifest.metrics_for("per_layer", cell)}
        assert manifest.config(manifest.cell(cell)["config"])["entry"] == "engine"
    assert "sampled_rows" in {f.name for f in dataclasses.fields(StepRecord)}


@pytest.fixture
def in_root():
    before = os.getcwd()
    os.chdir(ROOT)  # a manifest finds its files from the checkout's root
    yield ROOT
    os.chdir(before)


def test_the_manifests_entry_holds_the_reader_in_every_serving_cell(in_root):
    check_the_entry(manifest_mod.Manifest(os.path.join(ROOT, "BENCHMARK.json")))
