"""The trace reduction on a small recorded trace: one client of the smoke
model under STC on one TPU v5e, a window of a few rounds traced by
``bench/run.py --trace 1``, with the compiled round's ``op_name`` map
(``bench/tests/data/smoke-stc.*``)."""

import json
import os

import pytest

from bench import trace
from bench.tests.conftest import DATA, make_checkout

XPLANE = os.path.join(DATA, "smoke-stc.xplane.pb.gz")
NAMES = os.path.join(DATA, "smoke-stc.hlo_op_names.json")
RECORDED = os.path.join(DATA, "smoke-stc.result.json")


@pytest.fixture(scope="module")
def recorded():
    return trace.load_trace(XPLANE)


@pytest.fixture
def trace_dir(tmp_path):
    """The recorded trace laid out as the profiler writes it."""
    prof = tmp_path / "plugins" / "profile" / "run"
    prof.mkdir(parents=True)
    os.symlink(XPLANE, prof / "host.xplane.pb.gz")
    os.symlink(NAMES, tmp_path / trace.OP_NAMES)
    return str(tmp_path)


def test_the_recorded_trace_has_a_chip_and_the_harness_spans(recorded):
    assert list(recorded.ops) == [0]
    assert len(recorded.ops[0]) > 100
    names = {n for _, _, n in recorded.spans}
    assert names == {"bench.dispatch", "bench.fetch"}


@pytest.mark.parametrize("stack,collective,layer", [
    ("jit(wrapped)/transpose(jvp())/dot_general", False, "local_step"),
    ("jit(wrapped)/jvp(jit(silu))/mul", False, "local_step"),
    ("jit(wrapped)/while/body/closed_call/add", False, "local_step"),
    ("jit(wrapped)/cond/branch_1_fun/while/body/ge", False, "codec"),
    ("jit(wrapped)/abs", False, "codec"),
    ("", False, "codec"),
    ("jit(wrapped)/psum", True, "exchange"),
    (None, False, "other"),
])
def test_layer_rule(stack, collective, layer):
    assert trace.layer_of(stack, collective) == layer


def test_self_times_subtract_nested_ops():
    ops = [(0, 10, "while", False), (1, 3, "a", False), (2, 3, "b", False),
           (4, 6, "c", False), (12, 13, "d", False)]
    assert trace.self_times(ops) == [6, 1, 1, 2, 1]


def test_the_reduction_of_the_recorded_trace(trace_dir, tmp_path):
    root = make_checkout(str(tmp_path / "co"), {
        "smoke-stc": ("smoke-lm.json", "smoke-stc.json", 1,
                      {"loss_gap": 1, "update_gap": 1, "change_gap": 1})})
    from bench.spec import Cell
    cell = Cell(root, "smoke-stc")
    with open(RECORDED) as fh:
        rec = json.load(fh)
    red = trace.reduce_trace(trace_dir, cell, rec["device"], rec["attempted"])
    assert red.metrics == rec["metrics"]
    assert red.busy_s == pytest.approx(rec["device"]["busy_s"])
    assert 0 < red.busy_s <= red.window_s
    # every op of the window is in one layer: the self times add up to the
    # time some op ran
    assert sum(red.layer_s.values()) == pytest.approx(red.busy_s, rel=1e-9)
    m = {k: v["value"] for k, v in red.metrics.items()}
    assert 0 < m["mfu"] <= 100
    assert 0 <= m["device_idle_share"] < 100
    assert m["local_step_ms"] > 0 and m["codec_ms"] > 0
    assert 0 < m["codec_roofline"] <= 100
    assert "exchange_ms" not in m
    assert len(red.breakdown["device_ops"]) == 10
    assert all(s > 0 for _, s in red.breakdown["idle_gaps"])
