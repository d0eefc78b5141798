"""The harness end to end on the CPU at smoke size, with the device gate
lifted: a cell made of data files alone runs, and the program's round agrees
with the plain reference."""

import json
import os

import pytest

from bench.tests.conftest import DATA, make_checkout


def smoke_limits():
    with open(os.path.join(DATA, "smoke-limits.json")) as fh:
        return json.load(fh)


def run_cell(run, root, name, seed, trace=0):
    args = run.parse(["--workload", name, "--seed", str(seed),
                      "--seconds", "0.5", "--trace", str(trace)])
    return run.run(args, root=root)


def test_a_new_traffic_file_is_a_runnable_cell(tmp_path, no_gate):
    """Only data files are added (a configuration, a traffic mix, limits and
    the BENCHMARK.json entry); the result has the contract's keys."""
    root = make_checkout(str(tmp_path / "co"), {
        "smoke-fedavg": ("smoke-lm.json", "smoke-fedavg.json", 1,
                         smoke_limits())})
    res = run_cell(no_gate, root, "smoke-fedavg", 3000000011)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"round_s", "peak_hbm_gib", "setup_s"}
    assert all(m["value"] > 0 for k, m in res["metrics"].items()
               if k != "peak_hbm_gib")
    assert set(res["checks"]) == {"loss_gap", "update_gap", "change_gap"}


@pytest.mark.parametrize("seed", [5, 4000000003])
def test_reference_agrees_with_the_round_at_smoke_size(tmp_path, no_gate,
                                                       seed):
    root = make_checkout(str(tmp_path / "co"), {
        "smoke-stc": ("smoke-lm.json", "smoke-stc.json", 1, smoke_limits())})
    res = run_cell(no_gate, root, "smoke-stc", seed)
    assert res["correct"] is True, res["checks"]
    for c in res["checks"].values():
        assert 0 <= c["value"] <= c["limit"]

