"""Fixtures of the benchmark's CPU tests: a checkout in a temporary
directory whose ``BENCHMARK.json`` holds small cells, built only from data
files (``bench/tests/data``), with the real ``bench/`` code beside them."""

from __future__ import annotations

import json
import os
import shutil

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(REPO, "bench", "tests", "data")


def make_checkout(root: str, cells: dict) -> str:
    """A checkout under ``root`` with the repo's ``src`` and ``bench`` and a
    ``BENCHMARK.json`` whose workloads are ``cells``: name -> (config file in
    the test data, traffic file in the test data, chips, limits)."""
    os.makedirs(root, exist_ok=True)
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    bench = os.path.join(root, "bench")
    shutil.copytree(os.path.join(REPO, "bench"), bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__",
                                                  ".jax_cache"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    spec["configs"], spec["workloads"] = [], []
    for name, (config, traffic, chips, limits) in cells.items():
        cfg_name = os.path.splitext(config)[0]
        if cfg_name not in {c["name"] for c in spec["configs"]}:
            shutil.copy(os.path.join(DATA, config),
                        os.path.join(bench, "configs", config))
            spec["configs"].append({
                "name": cfg_name, "source": "test", "reduced": [],
                "file": f"bench/configs/{config}", "why": "test"})
        shutil.copy(os.path.join(DATA, traffic),
                    os.path.join(bench, "traffic", traffic))
        with open(os.path.join(bench, "limits", name + ".json"), "w") as fh:
            json.dump(limits, fh)
        spec["workloads"].append({
            "name": name, "config": cfg_name,
            "traffic": os.path.splitext(traffic)[0], "chips": chips,
            "why": "test"})
    spec["per_layer"] = [m for m in spec["per_layer"] if "workloads" not in m]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(spec, fh)
    return root


@pytest.fixture
def no_gate(monkeypatch):
    """Let the harness run on the CPU: the device gate reports a TPU."""
    from bench import run

    def gate(chips):
        return {"platform": "cpu", "kind": "cpu", "count": chips}

    monkeypatch.setattr(run, "device_gate", gate)
    return run
