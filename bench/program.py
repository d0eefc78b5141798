"""The system under test, built from a cell: the program's mesh round
(``repro.launch.train.make_train_step``) on a mesh of the cell's chips, its
state holding the benchmark's weights, and the cell's token batches on the
device.

The weights come from the configuration's plain reference (``init_params``),
made on the device in one jitted call from the seed, so the reference never
takes anything the program made.  The program's own state layout
(``init_train_state``, ``state_shardings``) holds them.
"""

from __future__ import annotations

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from bench.tokens import client_batches

__all__ = ["seed_key", "Program"]


def seed_key(seed: int) -> np.ndarray:
    """A raw threefry key holding all 64 bits of ``seed``."""
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


def _tree_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


class Program:
    """Builds the round of one cell; ``step(state, batch)`` is what the
    window drives."""

    def __init__(self, cell, seed: int, model_ref):
        sys.path.insert(0, os.path.join(cell.root, "src"))
        from repro.launch.mesh import make_mesh
        from repro.launch.train import (TrainConfig, batch_shardings,
                                        init_train_state, make_train_step,
                                        state_shardings)
        from repro.models.config import ModelConfig

        t, m = cell.traffic, cell.model
        n = t["clients"]
        model = {"arch_type": "dense", **m}
        model["block_pattern"] = tuple(m.get("block_pattern", ("attn",)))
        cfg = ModelConfig(name=cell.config["name"], **model)
        tc_fields = {f.name for f in dataclasses.fields(TrainConfig)}
        kw = {k: v for k, v in t.items() if k in tc_fields}
        kw["compute_dtype"] = jnp.dtype(t["compute_dtype"])
        tc = TrainConfig(**kw)
        self.mesh = make_mesh(data=n)
        self.key = seed_key(seed)

        def make_state(key):
            state = init_train_state(cfg, tc, n, key)
            state["params"] = model_ref.init_params(m, key)
            return state

        abstract = jax.eval_shape(make_state, self.key)
        self.make_state = jax.jit(
            make_state, out_shardings=state_shardings(abstract, self.mesh))
        rows = n * t["batch_per_client"]
        host = client_batches(seed, n, t["batches"], t["batch_per_client"],
                              t["seq_len"], m["vocab_size"])
        self.host_batches = host
        sh = batch_shardings(host[0], self.mesh, rows)
        self.batches = [jax.device_put(b, sh) for b in host]
        self.step_fn = make_train_step(cfg, self.mesh, tc)

        def params0(key):
            return model_ref.init_params(m, key)

        def first_update(state, key):
            """Mean over clients of the update the codec got in round 1:
            what the residuals kept plus what the params moved."""
            upd = jax.tree.map(jnp.subtract, state["params"], params0(key))
            if "server_res" in state:
                upd = jax.tree.map(jnp.add, upd, state["server_res"])
            if "client_res" in state:
                upd = jax.tree.map(lambda u, r: u + jnp.mean(r, axis=0), upd,
                                   state["client_res"])
            return _tree_norms(upd)

        def change(params, key):
            return _tree_norms(jax.tree.map(jnp.subtract, params,
                                            params0(key)))

        self.first_update_norms = jax.jit(first_update)
        self.change_norms = jax.jit(change)

    def compile(self, state):
        """Lower and compile the round for this state and batch."""
        with jax.set_mesh(self.mesh):
            return self.step_fn.lower(state, self.batches[0]).compile()

    def devices(self):
        return list(self.mesh.devices.flat)
