"""The attention readers (``bench/metrics/attention_ms.py`` and
``attention_kernel_share.py``): the device time of the local step's
``attention`` scope and the share of it under ``attention_kernel``, read
through the trace reduction as the harness reads them."""

import json
import os

import pytest

from bench import trace
from bench.spec import Cell, load_module
from bench.tests.conftest import DATA, REPO, make_checkout
from bench.tests.test_scopes import LIMITS, reduce_recorded

US = 1000           # ns
METRICS = ("attention_ms", "attention_kernel_share")


@pytest.mark.parametrize("stem,traffic", [
    ("smoke-stc", "smoke-stc.json"),
    ("smoke-stc-scoped", "smoke-stc-scoped.json")])
def test_a_round_without_the_attention_scope_reads_nothing(tmp_path, stem,
                                                           traffic):
    """The recorded rounds predate the ``attention`` scope."""
    red, _ = reduce_recorded(tmp_path, stem, traffic)
    assert not set(METRICS) & set(red.metrics)
    assert "local_step_ms" in red.metrics


def _checkout(tmp_path):
    """A checkout of the smoke cell whose metrics include the two."""
    root = make_checkout(str(tmp_path / "co"), {
        "smoke-stc-scoped": ("smoke-lm.json", "smoke-stc-scoped.json", 1,
                             LIMITS)})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        spec = json.load(fh)
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        listed = [m for m in json.load(fh)["per_layer"]
                  if m["name"] in METRICS]
    for m in listed:
        m.pop("workloads")
    spec["per_layer"] += listed
    with open(path, "w") as fh:
        json.dump(spec, fh)
    return root


def _reduce(tmp_path, monkeypatch, names, one):
    """Two rounds of the ops ``one`` (start, end, HLO name; us) reduced with
    the ``op_name`` map ``names``."""
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
    return trace.reduce_trace(str(trace_dir),
                              Cell(_checkout(tmp_path), "smoke-stc-scoped"),
                              device, 2)


def test_the_readers_read_the_attention_ops(tmp_path, monkeypatch):
    """A round whose forward attention ran the jnp scan (its scope folded
    into ``jvp(attention)``) and whose backward ran the kernel: a splash
    kernel op without an ``op_name`` counts under both scopes."""
    names = {"g.1": "jit(f)/local_step/jvp()/dot_general",
             "a.1": "jit(f)/local_step/jvp(attention)/closed_call/while",
             "a.2": "jit(f)/local_step/jvp(attention)/closed_call/while/"
                    "body/dot_general",
             "splash_mha_dkv_no_residuals.3": "",
             "t.1": "jit(f)/local_step/transpose(jvp(local_step))/jvp()/"
                    "checkpoint/attention/attention_kernel/transpose",
             "c.1": "jit(f)/encode/select/reduce_max",
             "copy.1": ""}
    one = [(0, 50, "g.1"), (50, 90, "a.1"), (55, 85, "a.2"),
           (90, 120, "splash_mha_dkv_no_residuals.3"), (120, 130, "t.1"),
           (130, 200, "c.1"), (200, 210, "copy.1")]
    red = _reduce(tmp_path, monkeypatch, names, one)
    # the scan's loop 40 (us, its body nested) + the kernel 30 + transpose 10
    assert red.metrics["attention_ms"]["value"] == pytest.approx(0.080)
    assert red.metrics["attention_ms"]["unit"] == "ms"
    assert red.metrics["attention_kernel_share"]["value"] == pytest.approx(
        100 * 40 / 80)
    assert red.metrics["attention_kernel_share"]["unit"] == "%"


def test_every_attention_on_the_kernel_reads_100(tmp_path, monkeypatch):
    names = {"f.1": "jit(f)/local_step/jvp(attention)/attention_kernel/"
                    "vmap(jit(_splash_attention))/broadcast_in_dim",
             "splash_mha_fwd_residuals.1": "",
             "splash_mha_dq_no_residuals.1": ""}
    one = [(0, 5, "f.1"), (5, 40, "splash_mha_fwd_residuals.1"),
           (40, 100, "splash_mha_dq_no_residuals.1")]
    red = _reduce(tmp_path, monkeypatch, names, one)
    assert red.metrics["attention_ms"]["value"] == pytest.approx(0.100)
    assert red.metrics["attention_kernel_share"]["value"] == pytest.approx(
        100.0)


@pytest.mark.parametrize("metric", METRICS)
def test_outside_a_reduction_reads_nothing(metric):
    reader = load_module(os.path.join(REPO, "bench", "metrics",
                                      metric + ".py"))
    t = trace.Reading(rounds=4, chips=1, window_s=1.0, busy_s_by_device=[1.0],
                      ms_per_round={"local_step": 3.0}, flops_per_round=0.0,
                      least_bytes=0.0, peaks={})
    assert reader.read(t) is None
