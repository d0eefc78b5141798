"""The scope readers (``bench/scopes.py``) on two recorded traces of the
smoke model on one TPU v5e: ``smoke-stc`` from a round without named scopes,
and ``smoke-stc-scoped`` from the scoped round at p = 1/8, so that the
selection takes the histogram path and the one-client server's selection
falls back.  The scoped recording's ``counts`` are the program's own
counters (``fallback_up``, ``fallback_down``) summed over its window."""

import json
import os

import pytest

from bench import scopes, trace
from bench.spec import Cell, load_module
from bench.tests.conftest import DATA, REPO, make_checkout

NEW_METRICS = ("encode_ms", "decode_ms", "histogram_ms", "refine_ms",
               "fallback_ms", "fallback_share")
LIMITS = {"loss_gap": 1, "update_gap": 1, "change_gap": 1}


def lay_out(path, stem):
    """A recorded trace laid out under ``path`` as the profiler writes it."""
    prof = path / "plugins" / "profile" / "run"
    prof.mkdir(parents=True)
    os.symlink(os.path.join(DATA, stem + ".xplane.pb.gz"),
               prof / "host.xplane.pb.gz")
    os.symlink(os.path.join(DATA, stem + ".hlo_op_names.json"),
               path / trace.OP_NAMES)
    return str(path)


def reduce_recorded(tmp_path, stem, traffic):
    """The recorded window reduced with every per-layer metric of the
    repo's ``BENCHMARK.json``, those that list their cells too."""
    root = make_checkout(str(tmp_path / "co"), {
        stem: ("smoke-lm.json", traffic, 1, LIMITS)})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        spec = json.load(fh)
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        listed = [m for m in json.load(fh)["per_layer"] if "workloads" in m]
    for m in listed:
        m.pop("workloads")
    spec["per_layer"] += listed
    with open(path, "w") as fh:
        json.dump(spec, fh)
    with open(os.path.join(DATA, stem + ".result.json")) as fh:
        rec = json.load(fh)
    red = trace.reduce_trace(lay_out(tmp_path / "trace", stem),
                             Cell(root, stem), rec["device"], rec["attempted"])
    return red, rec


def test_the_scoped_trace_splits_the_codec(tmp_path):
    red, rec = reduce_recorded(tmp_path, "smoke-stc-scoped",
                               "smoke-stc-scoped.json")
    m = {k: v["value"] for k, v in red.metrics.items()}
    assert m == pytest.approx({k: v["value"]
                               for k, v in rec["metrics"].items()}, rel=1e-9)
    assert all(m[k] > 0 for k in NEW_METRICS)
    assert (m["histogram_ms"] + m["refine_ms"] + m["fallback_ms"]
            <= m["encode_ms"] + m["decode_ms"])
    assert m["encode_ms"] + m["decode_ms"] <= m["codec_ms"]
    # the trace's runs of the fallback branches are the program's counters:
    # the one-client server's selection falls back in every round
    counts = rec["counts"]
    assert counts["fallback_down"] == rec["attempted"]
    assert m["fallback_share"] == pytest.approx(
        100.0 * (counts["fallback_up"] + counts["fallback_down"])
        / (2 * rec["attempted"]))
    # the scopes change no op's layer: the layers still add up to the busy
    # time
    assert sum(red.layer_s.values()) == pytest.approx(red.busy_s, rel=1e-9)


def test_an_unscoped_trace_reads_no_new_metric(tmp_path):
    """A round without the scopes (the program before them) leaves every
    new metric out and reads the others as recorded."""
    red, rec = reduce_recorded(tmp_path, "smoke-stc", "smoke-stc.json")
    assert red.metrics == rec["metrics"]
    assert not set(NEW_METRICS) & set(red.metrics)


def test_the_scoped_times_hold_the_nested_ops(tmp_path):
    tr = trace.load_trace(os.path.join(DATA, "smoke-stc-scoped.xplane.pb.gz"))
    with open(os.path.join(DATA, "smoke-stc-scoped.hlo_op_names.json")) as fh:
        names = json.load(fh)
    lo = min(s for s, _, n in tr.spans if n == "bench.dispatch")
    hi = max(e for _, e, n in tr.spans if n == "bench.fetch")
    s = scopes.measure(tr, names, [0], lo, hi, 5)
    # one client exchanges nothing, so no op carries ``exchange``
    assert {"local_step", "encode", "decode", "histogram", "refine",
            "fallback"} <= s.declared
    # a scope holds the scopes nested in it
    assert s.ms["cond"] >= s.ms["fallback"]
    assert s.ms["local_step"] > 0
    assert s.fallback == (8, 10)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_outside_a_reduction_reads_nothing(name):
    reader = load_module(os.path.join(REPO, "bench", "metrics", name + ".py"))
    t = trace.Reading(rounds=4, chips=1, window_s=1.0, busy_s_by_device=[1.0],
                      ms_per_round={"codec": 3.0}, flops_per_round=0.0,
                      least_bytes=0.0, peaks={})
    assert reader.read(t) is None


@pytest.mark.parametrize("stack,parts", [
    ("jit(wrapped)/shard_map/decode/cond/branch_1_fun/fallback/while/body/"
     "closed_call/while/body/add",
     ["decode", "cond", "branch_1_fun", "fallback", "while", "body",
      "closed_call", "while", "body", "add"]),
    ("jit(wrapped)/encode/histogram/scatter-add",
     ["encode", "histogram", "scatter-add"]),
    ("", []),
    (None, []),
])
def test_components_drop_the_wrappers(stack, parts):
    assert scopes.components(stack) == parts


def test_fallback_runs_are_the_fewest_events_under_a_branch():
    b_up = "jit(f)/encode/cond/branch_1_fun/fallback"
    b_down = "jit(f)/decode/cond/branch_1_fun/fallback"
    names = {"w.1": b_up + "/while", "r.1": b_up + "/while/body/reduce_sum",
             "w.2": b_down + "/while", "r.2": b_down + "/while/body/add",
             "a.1": "jit(f)/encode/abs"}
    # the upload's branch ran twice (its loop body 33 times a run), the
    # server's never
    ops = ([(0, 1, "w.1", False)] * 2 + [(0, 1, "r.1", False)] * 66
           + [(0, 1, "a.1", False)] * 2)
    assert scopes.fallback_runs(ops, names) == {
        "jit(f)/encode/cond/branch_1_fun": 2,
        "jit(f)/decode/cond/branch_1_fun": 0}
    assert scopes.fallback_runs(ops, {"a.1": "jit(f)/encode/abs"}) == {}


@pytest.mark.parametrize("stack,collective,layer", [
    ("jit(wrapped)/local_step/transpose(jvp())/dot_general", False,
     "local_step"),
    ("jit(wrapped)/local_step/mul", False, "codec"),
    ("jit(wrapped)/decode/cond/branch_1_fun/fallback/while/body/closed_call/"
     "reduce_sum", False, "codec"),
    ("jit(wrapped)/encode/histogram/scatter-add", False, "codec"),
    ("jit(wrapped)/exchange/psum", True, "exchange"),
])
def test_the_scopes_leave_every_op_in_its_layer(stack, collective, layer):
    assert trace.layer_of(stack, collective) == layer
