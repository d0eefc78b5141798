"""decode_ms: device milliseconds per round of the ops under the round's
``decode`` scope (the server's ``tree_decode``, its selection included, and
the zero-arrival gate of the masked mode), mean over the chips used
(``bench/scopes.py``)."""

from bench.scopes import scope_ms


def read(t):
    return scope_ms(t, "decode")
