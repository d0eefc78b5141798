"""refine_ms: device milliseconds per round of the ops under the tree
selection's ``refine`` scope (the per-leaf masked top_k of the candidate bin,
the concatenate and its all_gather), upload and server together, mean over
the chips used (``bench/scopes.py``)."""

from bench.scopes import scope_ms


def read(t):
    return scope_ms(t, "refine")
