"""fallback_ms: device milliseconds per round of the ops under the tree
selection's ``fallback`` scope (the bisection that runs when the candidate
bin overflows the refine's capacity), mean over the chips used; 0 in a
window where no selection fell back (``bench/scopes.py``)."""

from bench.scopes import scope_ms


def read(t):
    return scope_ms(t, "fallback")
