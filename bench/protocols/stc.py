"""Plain reference of STC over a parameter tree (the paper's Algorithm 2).

Sparse ternary compression with error feedback, up and down: add the
residual, keep every value whose magnitude is at least the k-th largest over
the whole tree (k = floor(numel * p), at least 1), replace each kept value by
its sign times the mean kept magnitude, and carry what was not sent.

The k-th largest magnitude is found exactly by bisection on the float32 bit
patterns (for non-negative floats they order as integers): 31 counting
sweeps over the leaves.  Nothing of the program is used.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["CLIENT_STATE", "SERVER_STATE", "client", "server", "compress"]

CLIENT_STATE = SERVER_STATE = True


def _kth_largest(leaves, k):
    """The k-th largest magnitude over all leaves: the largest bit pattern
    ``t`` with ``#{|x| >= t} >= k``, one counting sweep per bit."""
    bits = [jax.lax.bitcast_convert_type(jnp.abs(x), jnp.uint32)
            for x in leaves]

    def body(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2
        count = sum(jnp.sum((b >= mid).astype(jnp.int32)) for b in bits)
        ok = count >= k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo, _ = jax.lax.fori_loop(0, 31, body,
                              (jnp.uint32(0), jnp.uint32(1 << 31)))
    return jax.lax.bitcast_convert_type(lo, jnp.float32)


def compress(tree, p: float):
    """Returns ``(ternary tree, kept count)``."""
    leaves, treedef = jax.tree.flatten(tree)
    numel = sum(x.size for x in leaves)
    thresh = _kth_largest(leaves, max(int(numel * p), 1))
    kept = [jnp.abs(x) >= thresh for x in leaves]
    count = sum(jnp.sum(m.astype(jnp.int32)) for m in kept)
    total = sum(jnp.sum(jnp.where(m, jnp.abs(x), 0.0))
                for m, x in zip(kept, leaves))
    mu = total / count.astype(jnp.float32)
    out = [jnp.where(m, mu * jnp.sign(x), 0.0) for m, x in zip(kept, leaves)]
    return jax.tree.unflatten(treedef, out), count


def _with_feedback(update, residual, p):
    carried = jax.tree.map(jnp.add, update, residual)
    sent, _ = compress(carried, p)
    return sent, jax.tree.map(jnp.subtract, carried, sent)


def client(delta, residual, traffic):
    """One client's upload and new residual."""
    return _with_feedback(delta, residual, traffic["sparsity_up"])


def server(mean_msg, residual, traffic):
    """The global update sent down and the server's new residual."""
    return _with_feedback(mean_msg, residual, traffic["sparsity_down"])
