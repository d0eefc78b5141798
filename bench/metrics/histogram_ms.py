"""histogram_ms: device milliseconds per round of the ops under the tree
selection's ``histogram`` scope (the 256-bin sweep over every leaf, its psum
and the bin search), upload and server together, mean over the chips used
(``bench/scopes.py``)."""

from bench.scopes import scope_ms


def read(t):
    return scope_ms(t, "histogram")
