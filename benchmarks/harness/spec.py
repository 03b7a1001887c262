"""Where everything the benchmark is made of is found, by name.

``BENCHMARK.json`` names configurations, cells (``workloads``) and metrics.
Whatever belongs to one of them sits in a file of its own under one of the
directories in ``paths``; this module maps a name to that file and imports
or parses it. Nothing here knows a particular cell, configuration or metric:
a later PR adds files and entries, and edits no file that is there.

    <path>/workloads/<cell>.json        the cell's parameters
    <path>/traffic/<generator>.py       make(config, cell, seed) -> dataset
    <path>/references/<config>.py       the plain reference and its limits
    <path>/flops/<config>.py            required operations and bytes
    <path>/metrics/<metric>.py          read(ctx) -> number or None
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Optional

#: the checkout root: the directory that holds ``BENCHMARK.json``
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class SpecError(RuntimeError):
    """A name in ``BENCHMARK.json`` has no file, or a file lacks a key."""


class Spec:
    """``BENCHMARK.json`` and the files it names, read on demand."""

    def __init__(self, path: Optional[str] = None, root: str = ROOT):
        self.root = root
        self.path = path or os.path.join(root, "BENCHMARK.json")
        with open(self.path) as f:
            self.doc = json.load(f)
        self.paths = [os.path.join(root, p) for p in self.doc["paths"]]
        self._modules: dict = {}

    # -- entries -----------------------------------------------------------

    def _entry(self, section: str, name: str) -> dict:
        for e in self.doc[section]:
            if e["name"] == name:
                return e
        known = [e["name"] for e in self.doc[section]]
        raise SpecError(f"{name!r} is not in {section} of {self.path}; "
                        f"known: {known}")

    def workload(self, name: str) -> dict:
        return self._entry("workloads", name)

    def metric_entries(self, section: str, workload: str) -> list:
        """The metrics of ``section`` that ``workload`` reports: those with
        no ``workloads`` key, and those whose key lists it."""
        return [m for m in self.doc[section]
                if "workloads" not in m or workload in m["workloads"]]

    # -- files -------------------------------------------------------------

    def find(self, kind: str, name: str, exts=(".json",)) -> str:
        for p in self.paths:
            for ext in exts:
                cand = os.path.join(p, kind, name + ext)
                if os.path.isfile(cand):
                    return cand
        raise SpecError(f"no {kind}/{name}{'|'.join(exts)} under "
                        f"{self.doc['paths']}")

    def cell(self, name: str) -> dict:
        """The cell's file, with the ``BENCHMARK.json`` entry's keys laid
        over it (``config``, ``traffic``, ``chips`` are stated once there)."""
        with open(self.find("workloads", name)) as f:
            cell = json.load(f)
        entry = self.workload(name)
        for k in ("config", "chips"):
            if k in cell and cell[k] != entry[k]:
                raise SpecError(f"cell {name}: {k} is {cell[k]!r} in its "
                                f"file and {entry[k]!r} in BENCHMARK.json")
        return {**cell, **entry}

    def config(self, name: str) -> dict:
        entry = self._entry("configs", name)
        with open(os.path.join(self.root, entry["file"])) as f:
            return {**json.load(f), "name": name}

    def module(self, kind: str, name: str) -> Any:
        """Import ``<path>/<kind>/<name>.py`` once, under a name of its own
        (the directories need no ``__init__.py`` and may lie anywhere)."""
        key = (kind, name)
        if key not in self._modules:
            file = self.find(kind, name, exts=(".py",))
            spec = importlib.util.spec_from_file_location(
                f"_bench_{kind}_{name.replace('.', '_')}", file)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._modules[key] = mod
        return self._modules[key]

    def peaks(self, device_kind: str) -> dict:
        """The published peaks of ``device_kind``; an unknown kind is an
        error, never a default."""
        with open(self.find(".", "peaks")) as f:
            table = json.load(f)
        if device_kind not in table:
            raise SpecError(f"no peaks for device kind {device_kind!r} in "
                            f"peaks.json (known: {sorted(table)})")
        return table[device_kind]

