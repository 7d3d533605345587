"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell ``<config>.<traffic>`` reads

- its configuration from the ``file`` of its ``configs`` entry,
- what depends on the configuration's block (weights, the program's
  service, the plain reference, the counts of operations and bytes)
  from ``<paths[0]>/models/<model>.py``, ``model`` being a key of the
  configuration's file,
- its traffic mix from ``<paths[0]>/traffic/<traffic>.json``,
- optionally ``<paths[0]>/cells/<cell>.json``, whose keys override the
  traffic file's top-level keys (an open-loop cell's rate is the
  cell's own: each configuration has its own knee),
- each metric it reports from ``<paths[0]>/metrics/<metric>.json``,
  which names the reader (``<paths[0]>/readers/<reader>.py``).

Nothing here knows the name of any cell, configuration or mix.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    def __init__(self, name: str, root: str = ROOT):
        bench = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                             f"(has: {', '.join(sorted(cells))})")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        base = os.path.join(root, bench["paths"][0])
        cfg_entry = {c["name"]: c for c in bench["configs"]}[
            self.entry["config"]]
        self.config = load_json(os.path.join(root, cfg_entry["file"]))
        self.model = load_module("models", self.config["model"])
        self.traffic = load_json(os.path.join(
            base, "traffic", self.entry["traffic"] + ".json"))
        own = os.path.join(base, "cells", name + ".json")
        if os.path.exists(own):
            self.traffic.update(load_json(own))
        self.end_to_end = self._metrics(bench["end_to_end"])
        self.per_layer = self._metrics(bench["per_layer"])

    def _metrics(self, entries) -> list:
        """The metrics this cell reports: those that list it under
        ``workloads``, or list nothing.  ``setup_s`` is the harness's
        own and has no file."""
        out = []
        for m in entries:
            if "workloads" in m and self.name not in m["workloads"]:
                continue
            path = os.path.join(BENCH_DIR, "metrics", m["name"] + ".json")
            spec = load_json(path) if os.path.exists(path) else {}
            out.append({**m, "spec": spec})
        return out


@functools.lru_cache(maxsize=None)
def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` as a module: a reader
    (``readers``, with a ``read``) or a configuration's block
    (``models``)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"no {path}: a file under {kind}/ is named "
                         "by a metric's or a configuration's file")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_" + name.replace("-", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
