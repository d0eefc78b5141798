"""The ``select_ms`` reader (``bench/metrics/select_ms.py``): the device time
of the tree selection's ``select`` scope, read through the trace reduction
as the harness reads it."""

import json
import os

import pytest

from bench import trace
from bench.spec import Cell, load_module
from bench.tests.conftest import DATA, REPO, make_checkout
from bench.tests.test_scopes import LIMITS, reduce_recorded

US = 1000           # ns


def test_a_round_without_the_select_scope_reads_nothing(tmp_path):
    """The recorded scoped round selects by histogram: no ``select``."""
    red, rec = reduce_recorded(tmp_path, "smoke-stc-scoped",
                               "smoke-stc-scoped.json")
    assert "select_ms" not in red.metrics
    assert "codec_ms" in red.metrics


def _checkout(tmp_path):
    """A checkout of the smoke cell whose metrics include ``select_ms``."""
    root = make_checkout(str(tmp_path / "co"), {
        "smoke-stc-scoped": ("smoke-lm.json", "smoke-stc-scoped.json", 1,
                             LIMITS)})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        spec = json.load(fh)
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        (select,) = [m for m in json.load(fh)["per_layer"]
                     if m["name"] == "select_ms"]
    select.pop("workloads")
    spec["per_layer"].append(select)
    with open(path, "w") as fh:
        json.dump(spec, fh)
    return root


def test_select_ms_reads_the_select_ops(tmp_path, monkeypatch):
    """Two rounds of a round whose upload and server selections run under
    ``select``: the metric is their self time per round, loop bodies and
    nested ops included, and nothing outside the scope."""
    names = {"g.1": "jit(f)/local_step/jvp(f)/dot_general",
             "m.1": "jit(f)/encode/select/reduce_max",
             "w.1": "jit(f)/encode/select/while",
             "r.1": "jit(f)/encode/select/while/body/reduce_sum",
             "t.1": "jit(f)/encode/mul",
             "r.2": "jit(f)/decode/select/while/body/closed_call/reduce_sum",
             "t.2": "jit(f)/decode/mul"}
    one = [(0, 100, "g.1"), (100, 110, "m.1"), (110, 200, "w.1"),
           (115, 195, "r.1"), (200, 205, "t.1"), (205, 245, "r.2"),
           (245, 250, "t.2")]
    ops, spans = [], []
    for r in range(2):
        t0 = 1000 * US + r * 300 * US
        ops += [(t0 + s * US, t0 + e * US, n, False) for s, e, n in one]
        spans += [(t0 - US, t0 + 250 * US, "bench.dispatch"),
                  (t0 + 250 * US, t0 + 260 * US, "bench.fetch")]
    fake = trace.Trace(ops={0: ops}, spans=spans)
    monkeypatch.setattr(trace, "load_trace", lambda _: fake)
    trace_dir = tmp_path / "trace"
    trace_dir.mkdir()
    with open(trace_dir / trace.OP_NAMES, "w") as fh:
        json.dump(names, fh)
    with open(os.path.join(DATA, "smoke-stc-scoped.result.json")) as fh:
        device = json.load(fh)["device"]
    root = _checkout(tmp_path)
    red = trace.reduce_trace(str(trace_dir), Cell(root, "smoke-stc-scoped"),
                             device, 2)
    # max 10 + loop 90 - 80 nested + its body 80 + the server's 40 (us)
    assert red.metrics["select_ms"]["value"] == pytest.approx(0.140)
    assert red.metrics["select_ms"]["unit"] == "ms"


def test_select_ms_outside_a_reduction_reads_nothing():
    reader = load_module(os.path.join(REPO, "bench", "metrics",
                                      "select_ms.py"))
    t = trace.Reading(rounds=4, chips=1, window_s=1.0, busy_s_by_device=[1.0],
                      ms_per_round={"codec": 3.0}, flops_per_round=0.0,
                      least_bytes=0.0, peaks={})
    assert reader.read(t) is None
