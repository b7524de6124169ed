"""Finding a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one kind
of traffic or one per-layer metric sits in a file of its own:

    <path>/configs/<config>.json     (``configs[].file`` names it)
    <path>/traffic/<traffic>.json    parameters; names its driver
    <path>/drivers/<driver>.py       one per KIND of traffic
    <path>/layers/<metric>.py        one reader per per-layer metric

``<path>`` is any entry of ``paths``, looked up first beside the
``BENCHMARK.json`` in use and then in this checkout, so a later PR adds a
cell by adding files and one entry, and edits nothing that exists.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


class Spec:
    """One ``BENCHMARK.json`` and the directory it sits in."""

    def __init__(self, bench_file: str):
        self.file = os.path.abspath(bench_file)
        self.root = os.path.dirname(self.file)
        with open(self.file) as f:
            self.doc = json.load(f)
        self.paths = list(self.doc["paths"])

    def _by_name(self, section: str, name: str) -> dict:
        for entry in self.doc[section]:
            if entry["name"] == name:
                return entry
        known = ", ".join(e["name"] for e in self.doc[section])
        raise SystemExit(
            f"chipbench: no {section[:-1]} named {name!r} in {self.file} "
            f"(have: {known})"
        )

    def cell(self, name: str) -> dict:
        return self._by_name("workloads", name)

    def find(self, rel: str) -> str:
        """``rel`` under any of ``paths``: beside the BENCHMARK.json in
        use first, then in this checkout."""
        tried = []
        for root in dict.fromkeys((self.root, CHECKOUT)):
            for p in self.paths:
                cand = os.path.join(root, p, rel)
                if os.path.isfile(cand):
                    return cand
                tried.append(cand)
        raise SystemExit(
            f"chipbench: {rel!r} not found; looked at: " + ", ".join(tried)
        )

    def config(self, name: str) -> dict:
        entry = self._by_name("configs", name)
        path = os.path.join(self.root, entry["file"])
        if not os.path.isfile(path):
            path = os.path.join(CHECKOUT, entry["file"])
        with open(path) as f:
            cfg = json.load(f)
        cfg["name"] = name
        cfg["_file"] = path
        return cfg

    def traffic(self, name: str) -> dict:
        with open(self.find(os.path.join("traffic", name + ".json"))) as f:
            mix = json.load(f)
        mix["name"] = name
        return mix

    def metrics_for(self, cell_name: str, section: str) -> list[dict]:
        """The metrics of ``section`` this cell reports (an entry with a
        ``workloads`` list exists only in those cells)."""
        return [
            m for m in self.doc[section]
            if "workloads" not in m or cell_name in m["workloads"]
        ]

    def cache_dir(self) -> str:
        """Where built graphs are kept between runs: a fixed path inside
        the checkout that holds the BENCHMARK.json in use."""
        return os.path.join(self.root, self.paths[0], ".cache")

    def load_module(self, kind: str, name: str):
        """``<path>/<kind>/<name>.py`` as a module, loaded by file."""
        path = self.find(os.path.join(kind, name + ".py"))
        spec = importlib.util.spec_from_file_location(
            f"chipbench_{kind}_{name.replace('-', '_').replace('.', '_')}",
            path,
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


def resolve(dotted: str):
    """``"pkg.mod:attr"`` -> the object (a mix names the program's entry
    points as data)."""
    import importlib

    mod, _, attr = dotted.partition(":")
    return getattr(importlib.import_module(mod), attr)
