"""``BENCHMARK.json`` and the files it names.

A later PR adds a cell, a configuration, a traffic mix or a per-layer
metric by adding files and one manifest entry; nothing here enumerates
them. Names resolve to paths:

- configuration  → the entry's ``file`` (``benchmark/configs/<name>.json``)
- traffic mix    → ``benchmark/traffic/<traffic>.json``; its ``runner`` key
  names a module ``benchmark/runners/<runner>.py``
- per-layer metric → ``benchmark/layer_metrics/<name>.json``; its
  ``reader`` key names a module ``benchmark/readers/<reader>.py``. A metric
  has one ``moves``, so the same reading in a cell with other end-to-end
  metrics is another entry, ``<name>.<variant>``, read by the same file
- a configuration's ``family`` key names ``benchmark/families/<family>.py``
"""

from __future__ import annotations

import importlib
import json
from pathlib import Path
from typing import Any

#: the checkout this package sits in
ROOT = Path(__file__).resolve().parent.parent
PACKAGE = "benchmark"


class Manifest:
    def __init__(self, root: Path | str = ROOT):
        self.root = Path(root)
        self.doc: dict[str, Any] = json.loads(
            (self.root / "BENCHMARK.json").read_text()
        )

    # -- entries ------------------------------------------------------- #

    def cell(self, name: str) -> dict[str, Any]:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        known = [w["name"] for w in self.doc["workloads"]]
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {known}")

    def config(self, name: str) -> dict[str, Any]:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return self._json(self.root / c["file"])
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict[str, Any]:
        return self._json(self.root / PACKAGE / "traffic" / f"{name}.json")

    def layer_metric(self, name: str) -> dict[str, Any]:
        stem = name.split(".", 1)[0]        # ``<name>.<variant>``: one file
        return self._json(self.root / PACKAGE / "layer_metrics" / f"{stem}.json")

    def metrics_of(self, cell: str, kind: str) -> list[dict[str, Any]]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports: a
        metric with no ``workloads`` key belongs to every cell. A per-layer
        metric is reported only where the metric it moves is."""
        mine = [
            m for m in self.doc[kind]
            if "workloads" not in m or cell in m["workloads"]
        ]
        if kind == "per_layer":
            e2e = {m["name"] for m in self.metrics_of(cell, "end_to_end")}
            mine = [m for m in mine if m["moves"] in e2e]
        return mine

    @staticmethod
    def _json(path: Path) -> dict[str, Any]:
        try:
            return json.loads(path.read_text())
        except FileNotFoundError:
            raise FileNotFoundError(
                f"{path} is named by BENCHMARK.json but does not exist"
            ) from None


def plugin(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` — runners, readers and families are
    found by name, so adding one is adding a file."""
    if not name.replace("_", "").isalnum():
        raise ValueError(f"bad {kind} name {name!r}")
    return importlib.import_module(f"{PACKAGE}.{kind}.{name}")
