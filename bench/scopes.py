"""Times of the program's named scopes in a traced window, and the runs of
the tree selection's bisection fallback.

The round runs under named scopes (``local_step``, ``encode``, ``exchange``,
``decode``; the selection's ``histogram``, ``refine``, ``fallback``), and
each scope is a component of its ops' ``op_name``.  Apart from the layers of
``bench/trace.py``, each op's self time counts once under every distinct
component of its ``op_name`` (after the ``jit(...)`` and ``shard_map``
wrappers), so a scope's time holds the ops nested in it.

A run of the fallback branch executes every op of that branch at least once,
and the branch's loop body more often, so the runs of one ``lax.cond``'s
fallback are the fewest events of any op under it.  Each selection that can
fall back is one such branch in the compiled round (its ``op_name`` prefix
before ``fallback``), run once a round on every chip.

The harness's ``Reading`` carries the layer times, not the trace.  A reader
of a scope therefore takes the loaded trace, the ``op_name`` map and the
window from the ``bench.trace.reduce_trace`` call that is reading it
(``of``); called in any other way, or on a program without the scope, it
reads nothing.
"""

from __future__ import annotations

import sys
from dataclasses import dataclass, field

__all__ = ["Scopes", "components", "fallback_runs", "measure", "of",
           "scope_ms"]

FALLBACK = "fallback"


def components(stack: str | None) -> list:
    """The components of an ``op_name`` after the ``jit(...)`` and
    ``shard_map`` wrappers at its front; none for an op without one."""
    parts = [p for p in (stack or "").split("/") if p]
    while parts and (parts[0].startswith("jit(") or parts[0] == "shard_map"):
        parts.pop(0)
    return parts


def _branch(stack: str | None) -> str | None:
    """The fallback branch an op belongs to: its ``op_name`` up to the
    ``fallback`` component, or None outside every such branch."""
    parts = (stack or "").split("/")
    if FALLBACK not in parts:
        return None
    return "/".join(parts[:parts.index(FALLBACK)])


@dataclass
class Scopes:
    """What a traced window says of the scopes: ``declared``, every
    component in the compiled round; ``ms``, each component's device ms per
    round, mean over the chips; ``fallback``, the fallback branches' runs
    and the selections made (``(runs, made)``, None in a round without a
    fallback branch)."""
    declared: set = field(default_factory=set)
    ms: dict = field(default_factory=dict)
    fallback: tuple | None = None


def fallback_runs(ops, names: dict) -> dict:
    """``{branch: runs}`` of every fallback branch of the compiled round
    (``names``) over a chip's ``ops`` (module docstring)."""
    branches = {b for b in map(_branch, names.values()) if b is not None}
    events = {}
    for _, _, name, _ in ops:
        b = _branch(names.get(name))
        if b is not None:
            events.setdefault(b, {})
            events[b][name] = events[b].get(name, 0) + 1
    return {b: min(events[b].values()) if b in events else 0
            for b in branches}


def measure(tr, names: dict, chips, lo: int, hi: int, rounds: int) -> Scopes:
    """The scopes of the window ``[lo, hi]`` of ``rounds`` rounds on
    ``chips`` of a loaded trace ``tr`` (``bench.trace.Trace``)."""
    from bench.trace import self_times

    out = Scopes(declared={p for s in names.values() for p in components(s)})
    total, runs, made = {}, 0, 0
    for c in chips:
        ops = [o for o in tr.ops[c] if lo <= o[0] and o[1] <= hi]
        for (_, _, name, _), d in zip(ops, self_times(ops)):
            for scope in set(components(names.get(name))):
                total[scope] = total.get(scope, 0.0) + d
        per_branch = fallback_runs(ops, names)
        runs += sum(per_branch.values())
        made += rounds * len(per_branch)
    n = len(chips)
    out.ms = {k: v / 1e6 / n / rounds for k, v in total.items()}
    if made:
        out.fallback = (runs, made)
    return out


_last: tuple = (None, None)   # (reading, its Scopes): one trace per reading
# what ``of`` takes from ``reduce_trace``'s frame
WINDOW = ("tr", "names", "chips", "lo", "hi", "rounds")


def of(reading) -> Scopes | None:
    """The scopes of the window that ``bench.trace.reduce_trace`` is reading
    into ``reading``, or None outside such a call."""
    global _last
    if _last[0] is reading:
        return _last[1]
    from bench import trace
    f = sys._getframe(1)
    while f is not None and f.f_code is not trace.reduce_trace.__code__:
        f = f.f_back
    if f is None or f.f_locals.get("reading") is not reading:
        return None
    v = f.f_locals
    if not all(k in v for k in WINDOW):
        return None
    scopes = measure(*(v[k] for k in WINDOW))
    _last = (reading, scopes)
    return scopes


def scope_ms(reading, scope: str) -> float | None:
    """Device ms per round under ``scope``; None where the compiled round
    has no such scope or no trace is being read."""
    s = of(reading)
    if s is None or scope not in s.declared:
        return None
    return s.ms.get(scope, 0.0)
