"""Per-client token streams for the benchmark's traffic.

A copy of the program's Markov-modulated Zipf generator, kept here so the
yardstick cannot move with the program.  Each client draws from its own
stream, seeded by ``(seed, client)``: its own Zipf permutations and Markov
chain, so the clients' data are non-IID by construction.
"""

from __future__ import annotations

import numpy as np

__all__ = ["make_lm_tokens", "client_batches"]


def make_lm_tokens(seed, n_tokens: int, vocab: int,
                   n_states: int = 8) -> np.ndarray:
    """Markov-modulated Zipf token stream: learnable bigram structure.

    ``seed`` is anything ``np.random.default_rng`` takes, e.g. a list of
    whole numbers of any size.
    """
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    base = 1.0 / ranks ** 1.1
    perms = [rng.permutation(vocab) for _ in range(n_states)]
    probs = np.stack([base[np.argsort(p)] for p in perms])
    probs /= probs.sum(axis=1, keepdims=True)
    trans = rng.dirichlet(np.ones(n_states) * 0.3, size=n_states)
    out = np.empty(n_tokens, dtype=np.int32)
    state = 0
    block = 64                       # one Markov state per block of 64 tokens
    for i in range(0, n_tokens, block):
        state = rng.choice(n_states, p=trans[state])
        m = min(block, n_tokens - i)
        out[i:i + m] = rng.choice(vocab, size=m, p=probs[state])
    return out


def client_batches(seed: int, clients: int, n_batches: int, rows: int,
                   seq: int, vocab: int) -> list[dict]:
    """``n_batches`` global batches of ``clients * rows`` rows of ``seq``
    tokens, client-major (rows ``[c*rows, (c+1)*rows)`` are client ``c``'s),
    with next-token labels.  Every row of every batch is distinct text."""
    per = n_batches * rows * (seq + 1)
    streams = [make_lm_tokens([seed, c], per, vocab).reshape(
        n_batches, rows, seq + 1) for c in range(clients)]
    out = []
    for i in range(n_batches):
        rows_i = np.concatenate([s[i] for s in streams])
        out.append({"tokens": rows_i[:, :-1].copy(),
                    "labels": rows_i[:, 1:].copy()})
    return out
