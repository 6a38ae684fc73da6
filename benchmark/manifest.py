"""BENCHMARK.json and the data files it names, loaded and checked.

Everything that belongs to one configuration, one traffic mix, one cell or one
per-layer metric sits in a file of its own under the data root and is found by
the name in ``BENCHMARK.json``:

- ``configs/<config>.json`` (the manifest's ``file`` says where),
- ``traffic/<traffic>.json`` -- names its ``driver``, found as
  ``drivers/<driver>.py``,
- ``cells/<workload>.json`` -- the limits that decide ``correct`` in that cell,
- ``metrics/<name>.json`` -- names its ``reader``, found as
  ``readers/<reader>.py``.

A later PR adds a cell, a configuration, a traffic mix or a metric by adding
files and entries; nothing here is edited (``tests/test_manifest.py`` proves it).
"""

from __future__ import annotations

import importlib
import json
import re
from pathlib import Path
from typing import Any

CODE_DIR = Path(__file__).resolve().parent
ROOT = CODE_DIR.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    """BENCHMARK.json or a file it names breaks the benchmark's rules."""


def _line(text: Any, what: str) -> None:
    if not isinstance(text, str) or not 1 <= len(text) <= 200 or "\n" in text or "\t" in text:
        raise ManifestError(f"{what} must be 1 to 200 characters on one line, got {text!r}")


def _name(text: Any, what: str) -> None:
    if not isinstance(text, str) or not NAME_RE.match(text):
        raise ManifestError(f"{what} {text!r} is not a name (letters, digits, '_', '.', '-'; at most 64)")


class Manifest:
    """The manifest at ``root/BENCHMARK.json`` with its data files under
    ``root/<paths[0]>``. Code (drivers, readers) is always this package's."""

    def __init__(self, root: Path | str = ROOT) -> None:
        self.root = Path(root)
        self.doc = json.loads((self.root / "BENCHMARK.json").read_text())
        self.data = self.root / self.doc["paths"][0]
        self.configs = {c["name"]: c for c in self.doc["configs"]}
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.end_to_end = {m["name"]: m for m in self.doc["end_to_end"]}
        self.per_layer = {m["name"]: m for m in self.doc["per_layer"]}
        self.check()

    # -- which metrics a cell reports ---------------------------------------
    def cell_end_to_end(self, cell: str) -> list[str]:
        return [
            n for n, m in self.end_to_end.items()
            if "workloads" not in m or cell in m["workloads"]
        ]

    def cell_per_layer(self, cell: str) -> list[str]:
        e2e = set(self.cell_end_to_end(cell))
        return [
            n for n, m in self.per_layer.items()
            if (cell in m["workloads"] if "workloads" in m else m["moves"] in e2e)
        ]

    # -- files found by name -------------------------------------------------
    def _json(self, path: Path) -> dict[str, Any]:
        if not path.is_file():
            raise ManifestError(f"{path.relative_to(self.root)} is named by BENCHMARK.json and missing")
        return json.loads(path.read_text())

    def config(self, name: str) -> dict[str, Any]:
        return self._json(self.root / self.configs[name]["file"])

    def traffic(self, name: str) -> dict[str, Any]:
        return self._json(self.data / "traffic" / f"{name}.json")

    def cell_file(self, cell: str) -> dict[str, Any]:
        return self._json(self.data / "cells" / f"{cell}.json")

    def metric_file(self, name: str) -> dict[str, Any]:
        return self._json(self.data / "metrics" / f"{name}.json")

    @staticmethod
    def driver(name: str) -> Any:
        _name(name, "driver")
        return importlib.import_module(f"benchmark.drivers.{name}")

    @staticmethod
    def reader(name: str) -> Any:
        _name(name, "reader")
        return importlib.import_module(f"benchmark.readers.{name}")

    # -- the rules -----------------------------------------------------------
    def check(self) -> None:
        doc = self.doc
        if len(self.configs) != len(doc["configs"]) or len(self.cells) != len(doc["workloads"]):
            raise ManifestError("two configurations or two cells share a name")
        if len(self.end_to_end) + len(self.per_layer) != len(
            {*self.end_to_end, *self.per_layer}
        ) or len(self.end_to_end) != len(doc["end_to_end"]) or len(self.per_layer) != len(doc["per_layer"]):
            raise ManifestError("two metrics share a name")
        if "setup_s" not in self.end_to_end:
            raise ManifestError("end_to_end lacks setup_s")
        for c in doc["configs"]:
            _name(c["name"], "configuration")
            _line(c["source"], f"source of {c['name']}")
            _line(c["why"], f"why of {c['name']}")
            for key in c["reduced"]:
                _name(key, f"reduced key of {c['name']}")
            stated = self.config(c["name"])
            if sorted(stated.get("reduced", [])) != sorted(c["reduced"]):
                raise ManifestError(f"{c['file']} and BENCHMARK.json disagree on what {c['name']} reduces")
            if stated.get("source") != c["source"]:
                raise ManifestError(f"{c['file']} and BENCHMARK.json disagree on the source of {c['name']}")
        pairs = set()
        for w in doc["workloads"]:
            _name(w["name"], "cell")
            _name(w["traffic"], "traffic")
            _line(w["why"], f"why of {w['name']}")
            if w["config"] not in self.configs:
                raise ManifestError(f"cell {w['name']} names no configuration of the manifest")
            if w["chips"] not in (1, 4):
                raise ManifestError(f"cell {w['name']} asks for {w['chips']} chips")
            if (w["config"], w["traffic"]) in pairs:
                raise ManifestError(f"{w['config']} under {w['traffic']} appears twice")
            pairs.add((w["config"], w["traffic"]))
            if "driver" not in self.traffic(w["traffic"]):
                raise ManifestError(f"traffic {w['traffic']} names no driver")
            self.cell_file(w["name"])
        four = sum(w["chips"] == 4 for w in doc["workloads"])
        if four > max(1, len(doc["workloads"]) // 4):
            raise ManifestError(f"{four} of {len(doc['workloads'])} cells ask for four chips")
        for m in [*doc["end_to_end"], *doc["per_layer"]]:
            _name(m["name"], "metric")
            if not isinstance(m["unit"], str) or not UNIT_RE.match(m["unit"]):
                raise ManifestError(f"unit {m['unit']!r} of {m['name']} is not 1 to 16 of letters, digits, _ / % . -")
            if m["better"] not in ("lower", "higher"):
                raise ManifestError(f"better of {m['name']} is {m['better']!r}")
            if m["source"] not in SOURCES:
                raise ManifestError(f"source of {m['name']} is {m['source']!r}")
            for cell in m.get("workloads", ()):
                if cell not in self.cells:
                    raise ManifestError(f"{m['name']} lists the cell {cell!r}, which is none")
        for m in doc["end_to_end"]:
            if m["source"] not in ("host_clock", "device_trace"):
                raise ManifestError(f"end-to-end {m['name']} takes its number from {m['source']}")
            if not 0 < m["bound"] <= 0.1:
                raise ManifestError(f"bound of {m['name']} is {m['bound']}")
        for m in doc["per_layer"]:
            _line(m["layer"], f"layer of {m['name']}")
            if m["moves"] not in self.end_to_end:
                raise ManifestError(f"{m['name']} moves {m['moves']!r}, which is no end-to-end metric")
            stated = self.metric_file(m["name"])
            for key in ("unit", "layer", "moves", "source"):
                if stated.get(key) != m[key]:
                    raise ManifestError(f"metrics/{m['name']}.json and BENCHMARK.json disagree on {key}")
            if stated.get("workloads") != m.get("workloads"):
                raise ManifestError(f"metrics/{m['name']}.json and BENCHMARK.json disagree on workloads")
            if "reader" not in stated:
                raise ManifestError(f"metrics/{m['name']}.json names no reader")
        for cell in self.cells:
            e2e = self.cell_end_to_end(cell)
            if len(e2e) < 2:
                raise ManifestError(f"cell {cell} reports no end-to-end metric besides setup_s")
            for name in self.cell_per_layer(cell):
                if self.per_layer[name]["moves"] not in e2e:
                    raise ManifestError(
                        f"{name} is read in {cell}, which does not report {self.per_layer[name]['moves']}"
                    )
            if not self.cell_per_layer(cell):
                raise ManifestError(f"cell {cell} reports no per-layer metric")
