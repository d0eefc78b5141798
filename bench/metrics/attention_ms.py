"""attention_ms: device milliseconds per round of the local step's
attention, the ops under ``models/attention._attention``'s ``attention``
scope (forward, rematerialized forward and backward), mean over the chips
used.

Two things of the trace make this more than ``bench.scopes.scope_ms``:

* under ``jax.value_and_grad`` the forward's name stack folds the scope into
  the transform's label (``jvp(attention)/...``); a component counts for the
  scope it wraps;
* a Pallas kernel that carries kernel metadata (the splash kernels do) is
  printed over several lines of the compiled HLO, so ``hlo_op_names`` finds
  no ``op_name`` for it; such an op named after a splash kernel
  (``splash_mha_fwd``, ``splash_mha_dq``, ``splash_mha_dkv``) counts under
  ``attention`` and ``attention_kernel``, where the program calls it.

Reads nothing outside a ``bench.trace.reduce_trace`` call, or where the
compiled round has no ``attention`` scope.
"""

import sys

from bench import scopes

SCOPE, KERNEL = "attention", "attention_kernel"
KERNEL_OPS = "splash_mha_"


def _unwrap(part: str) -> str:
    """A name-stack component without its transform labels:
    ``jvp(attention)`` -> ``attention``."""
    while part.endswith(")") and "(" in part:
        part = part[part.index("(") + 1:-1]
    return part


def _window(reading):
    """The locals of the ``reduce_trace`` call reading ``reading``."""
    from bench import trace
    f = sys._getframe(1)
    while f is not None and f.f_code is not trace.reduce_trace.__code__:
        f = f.f_back
    if f is None or f.f_locals.get("reading") is not reading:
        return None
    v = f.f_locals
    return v if all(k in v for k in scopes.WINDOW) else None


def measure(reading):
    """``{"attention": ms, "attention_kernel": ms}`` per round, or None."""
    from bench.trace import self_times

    v = _window(reading)
    if v is None:
        return None
    tr, names, chips, lo, hi, rounds = (v[k] for k in scopes.WINDOW)
    labels = {}
    for name, stack in names.items():
        if not stack and name.startswith(KERNEL_OPS):
            labels[name] = {SCOPE, KERNEL}
        else:
            labels[name] = {_unwrap(p) for p in scopes.components(stack)}
    if not any(SCOPE in s for s in labels.values()):
        return None
    total = {SCOPE: 0.0, KERNEL: 0.0}
    for c in chips:
        ops = [o for o in tr.ops[c] if lo <= o[0] and o[1] <= hi]
        for (_, _, name, _), d in zip(ops, self_times(ops)):
            for s in labels.get(name, ()):
                if s in total:
                    total[s] += d
    return {k: t / 1e6 / len(chips) / rounds for k, t in total.items()}


def read(t):
    m = measure(t)
    return None if m is None else m[SCOPE]
