"""The reader of ``step_sampled_rows_mean`` (PR 32), CPU only: on
hand-made step records, on a program without the field, and its
constants against the manifest.  ``BENCHMARK.json`` has no entry for it
yet: ``test_falcon_h1_benchmark.py`` holds the manifest's last three
``per_layer`` entries by position, so appending one takes a ``benchmark``
PR that also makes that test find its entries by name (PERF.md, Open
questions).
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

SERVING_CELLS = [
    "qwen2.5-1.5b-int8.storm", "qwen2.5-1.5b-int8.decode",
    "qwen2.5-7b-int8.decode", "falcon-h1-34b-int8.decode",
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


def test_the_reader_is_ready_for_an_entry_in_the_four_serving_cells():
    """What an appended ``per_layer`` entry would have to agree with: a
    layer the manifest already names, an end-to-end metric every serving
    cell reports, and the step record's field."""
    from operator_tpu.obs.steptrace import StepRecord

    reader = step_sampled_rows_mean
    real = manifest_mod.Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    assert manifest_mod.NAME.match(reader.NAME) and manifest_mod.UNIT.match(reader.UNIT)
    assert (reader.UNIT, reader.SOURCE) == ("count", "program_counter")
    assert reader.LAYER in {m["layer"] for m in real.doc["per_layer"]}
    for cell in SERVING_CELLS:
        assert reader.MOVES in {m["name"] for m in real.metrics_for("end_to_end", cell)}
    assert "sampled_rows" in {f.name for f in dataclasses.fields(StepRecord)}
