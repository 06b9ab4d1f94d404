"""Find a cell's files by the names in ``BENCHMARK.json``.

Layout, one file per thing, so that a later PR adds files and entries and
edits none: ``configs/<config>.json``, ``traffic/<traffic>.json``,
``workloads/<cell>.json``, ``metrics/<metric>.json``.
"""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(*parts: str) -> dict:
    path = os.path.join(HERE, *parts)
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        return json.load(fh)


def cells() -> list[str]:
    return [w["name"] for w in benchmark()["workloads"]]


def resolve(dotted: str):
    """``package.module:function`` → the function. Whatever a data file
    names as code (a generator, a reference, a traffic law, a reader, a
    roofline count) it names this way, so a later PR brings a module of its
    own and edits none that is there."""
    module, _, attr = dotted.partition(":")
    return getattr(importlib.import_module(module), attr)


class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    def __init__(self, name: str):
        self.bench = benchmark()
        entries = [w for w in self.bench["workloads"] if w["name"] == name]
        if not entries:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        # configuration, traffic, chips and why live in the entry alone; the
        # cell's own file holds what BENCHMARK.json has no key for
        self.entry = entries[0]
        self.workload = _load("workloads", name + ".json")
        self.name = name
        self.chips = int(self.entry["chips"])
        self.config = _load("configs", self.entry["config"] + ".json")
        self.mix = _load("traffic", self.entry["traffic"] + ".json")
        self.peaks = _load("peaks.json")

    def _metrics(self, group: str) -> list[dict]:
        return [
            m for m in self.bench[group]
            if "workloads" not in m or self.name in m["workloads"]
        ]

    def end_to_end(self) -> list[dict]:
        return self._metrics("end_to_end")

    def per_layer(self) -> list[tuple[dict, dict]]:
        """→ [(BENCHMARK.json entry, the metric's own file)]."""
        return [(m, _load("metrics", m["name"] + ".json")) for m in self._metrics("per_layer")]
