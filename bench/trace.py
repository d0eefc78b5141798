"""Reduction of a traced window to the cell's per-layer metrics.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Each TPU has a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one
event per executed HLO op (the ops of a loop body or a conditional branch
nest inside the loop's or the conditional's own event), named by the op's
HLO text: ``%fusion.12 = f32[...] fusion(...)``.  The events carry no JAX
name stack, so the harness writes beside the trace the compiled round's
``op_name`` metadata per HLO op (``hlo_op_names.json``, from the same
executable's HLO text), and the reduction joins the two by op name.  The
host's lines hold the harness's own ``TraceAnnotation`` spans
(``bench.dispatch``, ``bench.fetch``) on the same clock.

Every device op of the round falls in one layer by its ``op_name`` (the rule
is written down in PERF.md), after the ``jit(...)`` and ``shard_map``
wrappers are dropped from the front:

* ``exchange``: collective ops (all-reduce, all-gather, reduce-scatter,
  all-to-all, collective-permute, with their async start/done halves);
* ``local_step``: ops under automatic differentiation (a ``jvp(`` or
  ``transpose(`` component), or inside the round's top-level loop (the
  local-iteration scan: the first component is ``while``);
* ``codec``: every other op of the round (encode, decode, apply);
* ``other``: ops of any other program.

Each op counts its self time: its duration less the ops nested in it.  The
window is the host's, from the start of the first ``bench.dispatch`` to the
end of the last ``bench.fetch``; a device is busy where the union of its op
intervals covers the window.
"""

from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import dataclass, field

__all__ = ["OP_NAMES", "Trace", "hlo_op_names", "layer_of", "load_trace",
           "reduce_trace", "Reading"]

COLLECTIVE = re.compile(r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute)(-start|-done)?\(")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = ')
OP_NAME = re.compile(r'op_name="([^"]*)"')
OPS_LINE = "XLA Ops"
SPANS = ("bench.dispatch", "bench.fetch")
OP_NAMES = "hlo_op_names.json"


def hlo_op_names(hlo_text: str) -> dict:
    """``{HLO op name: JAX op_name}`` of every op in a compiled module's HLO
    text; ``""`` for an op the compiler made without one."""
    out = {}
    for line in hlo_text.splitlines():
        m = HLO_OP.match(line)
        if m:
            name = OP_NAME.search(line)
            out[m.group(1)] = name.group(1) if name else ""
    return out


def layer_of(stack: str | None, collective: bool = False) -> str:
    """The layer of a device op from its ``op_name`` (module docstring);
    ``stack`` is None for an op of another program."""
    if stack is None:
        return "other"
    if collective:
        return "exchange"
    parts = [p for p in stack.split("/") if p]
    while parts and (parts[0].startswith("jit(") or parts[0] == "shard_map"):
        parts.pop(0)
    if any(p.startswith(("jvp(", "transpose(")) for p in parts):
        return "local_step"
    if parts and parts[0] == "while":
        return "local_step"
    return "codec"


@dataclass
class Trace:
    """Device ops per chip ``(start_ns, end_ns, hlo_name, collective)`` and
    the harness's host spans ``(start_ns, end_ns, name)``."""
    ops: dict = field(default_factory=dict)
    spans: list = field(default_factory=list)


def _find(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb*")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_trace(path: str) -> Trace:
    """Read an ``.xplane.pb`` (or ``.xplane.pb.gz``) file, or the directory
    the profiler wrote."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = _find(path)
    with open(path, "rb") as fh:
        data = fh.read()
    if path.endswith(".gz"):
        import gzip
        data = gzip.decompress(data)
    pd = ProfileData.from_serialized_xspace(data)
    out = Trace()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    text = e.name
                    ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                text.split(" ", 1)[0].lstrip("%"),
                                bool(COLLECTIVE.search(text))))
            out.ops[int(m.group(1))] = sorted(ops, key=lambda o: (o[0], -o[1]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        out.spans.append((e.start_ns,
                                          e.start_ns + e.duration_ns, e.name))
    out.spans.sort()
    return out


def self_times(ops):
    """Each op's duration less the time of the ops nested in it (ops sorted
    by start, longest first)."""
    selfs = [e - s for s, e, *_ in ops]
    stack = []
    for i, (s, e, *_) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1]:
            selfs[stack[-1]] -= e - s
        stack.append(i)
    return selfs


def _union(intervals, lo, hi) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals
                       if e > lo and s < hi):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(intervals, lo, hi):
    """Idle ``(start, end)`` stretches of ``[lo, hi]`` between ops."""
    out, t = [], lo
    for s, e, *_ in intervals:
        if e <= t:
            continue
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [g for g in out if g[1] > g[0]]


@dataclass
class Reading:
    """What the per-layer readers get."""
    rounds: int
    chips: int
    window_s: float
    busy_s_by_device: list
    ms_per_round: dict
    flops_per_round: float
    least_bytes: float
    peaks: dict


@dataclass
class Reduced:
    metrics: dict
    busy_s: float
    window_s: float
    breakdown: dict
    layer_s: dict


def _label(stack: str | None, name: str) -> str:
    """A short stable name for an op: the tail of its name stack without the
    jit wrapper, or its HLO opcode where it has none."""
    parts = [p for p in (stack or "").split("/")
             if p and not p.startswith("jit(")]
    return "/".join(parts[-4:]) if parts else re.sub(r"\.\d+$", "", name)


def reduce_trace(trace_dir: str, cell, device: dict, rounds: int,
                 top: int = 10) -> Reduced:
    """The cell's per-layer metrics from a traced window of ``rounds``
    rounds, with ``busy_s``, ``window_s`` and the breakdown."""
    from bench import flops
    from bench.peaks import chip_peaks
    from bench.spec import load_module

    tr = load_trace(trace_dir)
    with open(os.path.join(trace_dir, OP_NAMES)) as fh:
        names = json.load(fh)
    chips = sorted(tr.ops)[:cell.chips]
    if not chips or not any(tr.ops[c] for c in chips):
        raise RuntimeError(f"no device op in the trace under {trace_dir}")
    lo = min(s for s, _, n in tr.spans if n == "bench.dispatch")
    hi = max(e for _, e, n in tr.spans if n == "bench.fetch")
    window_s = (hi - lo) / 1e9
    busy, per_layer, by_label = [], {}, {}
    for c in chips:
        ops = [o for o in tr.ops[c] if lo <= o[0] and o[1] <= hi]
        busy.append(_union(ops, lo, hi) / 1e9)
        for (s, e, name, coll), d in zip(ops, self_times(ops)):
            stack = names.get(name)
            layer = layer_of(stack, coll)
            per_layer[layer] = per_layer.get(layer, 0.0) + d / 1e9
            label = _label(stack, name)
            by_label[label] = by_label.get(label, 0.0) + d / 1e9
    n = len(chips)
    reading = Reading(
        rounds=rounds, chips=n, window_s=window_s, busy_s_by_device=busy,
        ms_per_round={k: v * 1e3 / n / rounds for k, v in per_layer.items()},
        flops_per_round=flops.round_flops(cell.model, cell.traffic),
        least_bytes=flops.codec_least_bytes(cell.model, cell.protocol),
        peaks=chip_peaks(device["kind"]))
    metrics = {}
    for m in cell.per_layer:
        value = load_module(cell.metric_paths[m["name"]]).read(reading)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    busiest = chips[max(range(n), key=lambda i: busy[i])]
    gaps = sorted(_gaps(tr.ops[busiest], lo, hi),
                  key=lambda g: g[0] - g[1])[:top]
    breakdown = {
        "device_ops": [[k, v / n] for k, v in sorted(
            by_label.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_host_doing(tr.spans, g), (g[1] - g[0]) / 1e9]
                      for g in gaps]}
    return Reduced(metrics=metrics, busy_s=sum(busy) / n, window_s=window_s,
                   breakdown=breakdown, layer_s=per_layer)


def _host_doing(spans, gap) -> str:
    """The harness span that covers the middle of an idle gap."""
    mid = (gap[0] + gap[1]) / 2
    for s, e, name in spans:
        if s <= mid <= e:
            return name
    return "host (no harness span)"
