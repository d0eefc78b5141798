"""fallback_share: the share of the window's selections that ran the
bisection fallback, in percent: the runs of the selections' ``fallback``
branches over the selections made, each branch once a round on every chip
(``bench/scopes.py``)."""

from bench.scopes import of


def read(t):
    s = of(t)
    if s is None or s.fallback is None:
        return None
    runs, made = s.fallback
    return 100.0 * runs / made
