"""Plain float32 reference of the first rounds of a cell, on one device.

Each client takes its rows of the round's batch, runs ``local_iters`` SGD
steps on them through the configuration's plain model reference (one step
over all its rows when ``local_iters`` is 1), and hands its update
``params_end - params`` (``-lr * grad`` for one step) to the protocol's
plain reference; the server averages the clients' messages, applies its own
side of the protocol and adds the result to the params.  Clients run one
after the other, each program jitted once.

Readings, the same the harness takes from the program: each round's loss
(the clients' mean; with local steps, the mean over them), the norm of every
leaf of the clients' mean update in round 1, and the norm of every leaf's
change of the params after the last round.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["readings", "tree_norms"]


def tree_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x)))
                      for x in jax.tree.leaves(tree)])


def readings(model_ref, proto_ref, m: dict, traffic: dict, key, batches,
             rounds: int, precision: str = "f32", fault: str | None = None
             ) -> dict:
    """Readings of ``rounds`` rounds over ``batches`` (host arrays, one per
    round).  ``fault`` plants one in the reference: ``"half_batch"`` trains
    each client on half its rows, ``"no_exchange"`` gives the server the
    first client's message alone, as a chip that skipped the exchange
    would see it."""
    if fault not in (None, "half_batch", "no_exchange"):
        raise ValueError(f"unknown fault {fault!r}")
    n, rows = traffic["clients"], traffic["batch_per_client"]
    li, lr = traffic["local_iters"], traffic["lr"]
    keep = max(rows // 2, li) if fault == "half_batch" else rows

    def loss(p, tokens, labels):
        return model_ref.loss(p, m, tokens, labels, precision)

    grad = jax.value_and_grad(loss)

    def local(params, tokens, labels):
        if li == 1:
            value, g = grad(params, tokens, labels)
            return value, jax.tree.map(lambda x: -lr * x, g)
        micro = lambda x: x.reshape((li, x.shape[0] // li) + x.shape[1:])

        def sgd(p, tl):
            value, g = grad(p, *tl)
            return jax.tree.map(lambda a, b: a - lr * b, p, g), value

        end, values = jax.lax.scan(sgd, params, (micro(tokens), micro(labels)))
        return jnp.mean(values), jax.tree.map(jnp.subtract, end, params)

    def server(params, msg_sum, res):
        down, res = proto_ref.server(
            jax.tree.map(lambda x: x / n, msg_sum), res, traffic)
        return jax.tree.map(jnp.add, params, down), res

    local = jax.jit(local)
    client = jax.jit(lambda d, r: proto_ref.client(d, r, traffic),
                     donate_argnums=(0, 1))
    server = jax.jit(server, donate_argnums=(0, 1, 2))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))
    copy = jax.jit(lambda t: jax.tree.map(jnp.copy, t))
    norms = jax.jit(lambda t, s: tree_norms(jax.tree.map(lambda x: x * s, t)))
    init = jax.jit(lambda k: model_ref.init_params(m, k))
    change = jax.jit(lambda p, k: tree_norms(jax.tree.map(
        jnp.subtract, p, model_ref.init_params(m, k))))
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))

    params = init(key)
    c_res = [zeros(params) if proto_ref.CLIENT_STATE else None
             for _ in range(n)]
    s_res = zeros(params) if proto_ref.SERVER_STATE else None
    losses, first = [], None
    for r in range(rounds):
        batch, total, values, upd = batches[r], None, [], None
        for c in range(n):
            sl = slice(c * rows, c * rows + keep)
            value, delta = local(params, jnp.asarray(batch["tokens"][sl]),
                                 jnp.asarray(batch["labels"][sl]))
            values.append(value)
            if r == 0 and n == 1:
                first = np.asarray(norms(delta, 1.0))
            elif r == 0:
                upd = copy(delta) if upd is None else add(upd, delta)
            msg, c_res[c] = client(delta, c_res[c])
            if fault == "no_exchange" and c > 0:
                continue
            total = msg if total is None else add(total, msg)
        if r == 0 and n > 1:
            first = np.asarray(norms(upd, 1.0 / n))
            del upd
        losses.append(float(np.mean([float(v) for v in values])))
        params, s_res = server(params, total, s_res)
    return {"losses": losses, "update_norms": first,
            "change_norms": np.asarray(change(params, key))}
