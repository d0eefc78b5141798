"""select_ms: device milliseconds per round of the ops under the tree
selection's ``select`` scope (the max sweep, the count bisection and its
final count), upload and server together, mean over the chips used
(``bench/scopes.py``)."""

from bench.scopes import scope_ms


def read(t):
    return scope_ms(t, "select")
