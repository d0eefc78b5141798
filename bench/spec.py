"""Finds everything a cell needs by the names ``BENCHMARK.json`` gives.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
The configuration's ``file`` holds its sizes and names its plain reference
(``bench/references/<reference>.py``); the traffic mix is
``bench/traffic/<traffic>.json`` and names its protocol, whose least-bytes
count and plain reference are ``bench/protocols/<protocol>.{json,py}``; the
limits of the cell's correctness check are ``bench/limits/<cell>.json``; a
per-layer metric is read by ``bench/metrics/<metric>.py``.  Adding a cell
adds such files and entries; no code here changes.
"""

from __future__ import annotations

import importlib.util
import json
import os

__all__ = ["BENCH_DIR", "Cell", "load_module"]

BENCH_DIR = "bench"


def _json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_module(path: str):
    """Import the Python file at ``path`` as a fresh module."""
    name = "bench_" + os.path.splitext(
        os.path.relpath(path).replace(os.sep, "_").replace("-", "_"))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with its files resolved."""

    def __init__(self, root: str, name: str):
        self.root = root
        bench = _json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(known: {sorted(cells)})")
        self.name = name
        self.workload = cells[name]
        self.chips = int(self.workload["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        self.config = _json(os.path.join(
            root, configs[self.workload["config"]]["file"]))
        self.model = self.config["model"]
        here = os.path.join(root, BENCH_DIR)
        self.traffic = _json(os.path.join(
            here, "traffic", self.workload["traffic"] + ".json"))
        proto = self.traffic["protocol"]
        self.protocol = _json(os.path.join(here, "protocols", proto + ".json"))
        self.protocol_ref_path = os.path.join(here, "protocols", proto + ".py")
        self.model_ref_path = os.path.join(
            here, "references", self.config["reference"] + ".py")
        self.limits = _json(os.path.join(here, "limits", name + ".json"))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]
        self.metric_paths = {m["name"]: os.path.join(
            here, "metrics", m["name"] + ".py") for m in self.per_layer}
