"""encode_ms: device milliseconds per round of the ops under the round's
``encode`` scope (the clients' ``tree_encode``, its selection included),
mean over the chips used (``bench/scopes.py``)."""

from bench.scopes import scope_ms


def read(t):
    return scope_ms(t, "encode")
