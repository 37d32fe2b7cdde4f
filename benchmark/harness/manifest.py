"""Reading ``BENCHMARK.json`` and finding a cell's files by name.

Whatever belongs to one configuration, traffic mix, generator, entry or
per-layer metric is a file of its own under one of the manifest's
``paths``: ``<path>/traffic/<mix>.json``, ``<path>/generators/<name>.py``,
``<path>/entries/<name>.py``, ``<path>/layer_metrics/<name>.py``.  A
configuration's file is named by the manifest itself.  A later PR adds
files and manifest entries and edits nothing that is here.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from types import ModuleType
from typing import Any

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load_json(path: str) -> Any:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


class Manifest:
    """The manifest and the checkout it was read from."""

    def __init__(self, path: str) -> None:
        self.path = os.path.abspath(path)
        self.doc = load_json(self.path)
        self.root = os.getcwd()
        self.paths = [os.path.join(self.root, p) for p in self.doc["paths"]]

    # -- lookup -------------------------------------------------------
    def find(self, kind: str, filename: str) -> str:
        """``<path>/<kind>/<filename>`` in the first of ``paths`` that has it."""
        for base in self.paths:
            candidate = os.path.join(base, kind, filename)
            if os.path.isfile(candidate):
                return candidate
        raise FileNotFoundError(
            f"no {kind}/{filename} under any of {self.doc['paths']}"
        )

    def module(self, kind: str, name: str) -> ModuleType:
        """The Python file ``<kind>/<name>.py``, imported under the package
        of the path that holds it (so that its relative imports work)."""
        path = self.find(kind, name + ".py")
        relative = os.path.relpath(path, self.root)
        dotted = relative[:-3].replace(os.sep, ".")
        if all(part.isidentifier() for part in dotted.split(".")):
            return importlib.import_module(dotted)
        spec = importlib.util.spec_from_file_location(
            f"benchmark_{kind}_{name}".replace("-", "_").replace(".", "_"), path
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def cell(self, name: str) -> dict:
        for cell in self.doc["workloads"]:
            if cell["name"] == name:
                return cell
        known = [c["name"] for c in self.doc["workloads"]]
        raise KeyError(f"no workload {name!r} in {self.path}; it has {known}")

    def config(self, name: str) -> dict:
        for entry in self.doc["configs"]:
            if entry["name"] == name:
                return load_json(os.path.join(self.root, entry["file"]))
        raise KeyError(f"no configuration {name!r} in {self.path}")

    def traffic(self, name: str) -> dict:
        return load_json(self.find("traffic", name + ".json"))

    def metrics_for(self, section: str, cell_name: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports:
        those without a ``workloads`` list, and those that list it."""
        return [
            m for m in self.doc[section]
            if "workloads" not in m or cell_name in m["workloads"]
        ]
