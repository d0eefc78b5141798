#!/usr/bin/env python3
"""Smoke run of the STC federated round on the attached TPU chip(s).

    python3 chip_smoke.py              # one chip: phases A and B
    python3 chip_smoke.py --chips 4    # four chips: the 4-client mesh round
                                       # against a one-chip reference, only

Phase A drives ``repro.launch.train.make_train_step`` on a one-chip mesh with
the full registered ``smollm-135m`` (30 layers at published widths, random
weights from ``--seed``), protocol ``stc`` at p = 1/400 up and down, on one
fixed batch of 8 x 2048 tokens for 5 rounds.  It prints the compile time
(set-up), each round's time after ``block_until_ready``, the device's
``peak_bytes_in_use`` and every round's loss, and checks that the losses are
finite and fall, that every round uploads at least k = floor(numel / 400)
entries, and that on round 1's delta the chip's count selection finds
exactly the threshold ``jax.lax.top_k`` finds.

Phase B runs the flat server path (``repro.fed.FederatedTrainer``, fused
ingest) on the paper's MLP with 8 clients for 3 rounds, once with the Pallas
STC and wire kernels and once with the jnp / numpy backends: measured bits
must be equal, accuracy within 1e-3, and the compiled encode phase must hold
a ``tpu_custom_call`` (the kernels ran compiled, not interpreted).

``--chips 4`` runs smollm-135m at published widths, cut to ``MESH_LAYERS``
layers, with 4 clients on a ``data=4`` mesh for 2 rounds in float32 at
``highest`` matmul precision, checks that state and
batch land sharded one client per chip, and compares the params after round 1
with a plain reference on chip 0: each client's float32 grads on its own
batch slice, the codec's ``tree_encode`` per client, the mean, then
``tree_decode``.  Params must agree within ``REF_RTOL * |want| + REF_ATOL``
at all but ``REF_FLIP_SHARE * k`` coordinates: a reordered float sum may
break a tie at the top-k boundary differently and move that coordinate by
one step µ.

The script fails (non-zero exit, no result line) when JAX finds no TPU or
any check fails.  Its last line on success is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

P_STC = 1 / 400          # the paper's STC sparsity, up and down
LR = 0.05                # learning rate of the falling-loss check
BATCH, SEQ = 8, 2048     # tokens per round (split over the clients)
REF_RTOL = 1e-5          # --chips 4: per-coordinate agreement with the
REF_ATOL = 1e-7          # one-chip reference ...
REF_FLIP_SHARE = 1e-3    # ... at all but this share of k coordinates
# --chips 4 cuts the depth: the mesh, shardings and collectives it checks are
# the same at any depth, and a four-chip call is billed per chip for the
# compile, which grows with the layers unrolled
MESH_LAYERS = 4


class CheckFailed(AssertionError):
    """A smoke check did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def log(**kw) -> None:
    print(json.dumps(kw, default=float), flush=True)


def device_gate(chips: int) -> dict:
    """The attached devices as JAX reports them; exits unless they are TPUs."""
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"device_kind={info['kind']} count={info['count']}", flush=True)
    if info["platform"] != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{info['platform']!r}); nothing was run")
    if info["count"] < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, "
                 f"found {info['count']}")
    return info


def lm_batch(cfg, batch: int, seq: int, seed: int) -> dict:
    from repro.data import make_lm_tokens
    toks = make_lm_tokens(seed=seed, n_tokens=batch * seq + 1,
                          vocab=cfg.vocab_size)
    return {"tokens": jnp.asarray(toks[:-1].reshape(batch, seq)),
            "labels": jnp.asarray(toks[1:].reshape(batch, seq))}


def train_config(**kw):
    from repro.launch.train import TrainConfig
    return TrainConfig(protocol="stc", sparsity_up=P_STC,
                       sparsity_down=P_STC, **kw)


# ---------------------------------------------------------------------------
# Phase A: the model round on one chip
# ---------------------------------------------------------------------------


def round1_thresholds(cfg, tc):
    """Jitted ``(params, batch) -> (threshold of the selection branch the
    backend runs, lax.top_k's k-th magnitude, nnz)`` on round 1's upstream
    delta ΔW = -lr · ∇L (the client residual starts at zero)."""
    from repro.core.distributed import stc_compress_tree
    from repro.models import lm_loss
    numel = cfg.param_count()
    k = max(int(numel * tc.sparsity_up), 1)

    def thresholds(params, batch):
        g = jax.grad(lambda p: lm_loss(p, cfg, batch["tokens"],
                                       batch["labels"],
                                       compute_dtype=tc.compute_dtype))(params)
        delta = jax.tree.map(lambda u: -tc.lr * u.astype(jnp.float32), g)
        _, st = stc_compress_tree(delta, tc.sparsity_up, numel=numel)
        flat = jnp.concatenate([jnp.abs(x).reshape(-1)
                                for x in jax.tree.leaves(delta)])
        return st.thresh, jax.lax.top_k(flat, k)[0][k - 1], st.nnz

    return jax.jit(thresholds)


def compile_all(lowered: dict):
    """Compile the lowered programs side by side (XLA compiles without the
    GIL); returns ``(compiled, seconds per program)``."""
    def one(item):
        t0 = time.perf_counter()
        return item[0], item[1].compile(), time.perf_counter() - t0
    with ThreadPoolExecutor(len(lowered)) as pool:
        done = list(pool.map(one, lowered.items()))
    return ({k: c for k, c, _ in done}, {k: t for k, _, t in done})


def phase_model_round(cfg, *, batch: int, seq: int, rounds: int, lr: float,
                      seed: int) -> dict:
    from repro.launch.mesh import make_mesh
    from repro.launch.train import (batch_shardings, init_train_state,
                                    make_train_step, state_shardings)
    t_setup = time.perf_counter()
    mesh = make_mesh(data=1)
    tc = train_config(lr=lr)
    numel = cfg.param_count()
    k = numel // 400
    data = lm_batch(cfg, batch, seq, seed)
    state = init_train_state(cfg, tc, n_clients=1,
                             key=jax.random.PRNGKey(seed))
    state = jax.device_put(state, state_shardings(state, mesh))
    data = jax.device_put(data, batch_shardings(data, mesh, batch))
    step = make_train_step(cfg, mesh, tc)
    with jax.set_mesh(mesh):
        progs, compile_s = compile_all({
            "step": step.lower(state, data),
            "round1_thresholds": round1_thresholds(cfg, tc).lower(
                state["params"], data)})
    setup_s = time.perf_counter() - t_setup
    log(phase="A", what="setup", arch=cfg.name, params=numel,
        batch=[batch, seq], compile_s=compile_s, setup_s=setup_s)

    t_sel, t_ref, nnz1 = [np.asarray(v) for v in
                          progs["round1_thresholds"](state["params"], data)]
    log(phase="A", what="round1_threshold", select=t_sel, top_k=t_ref,
        nnz=int(nnz1), k=k)
    check(t_sel == t_ref, f"round-1 threshold {t_sel!r} != top_k {t_ref!r}")

    step = progs["step"]
    losses, times = [], []
    for r in range(rounds):
        t0 = time.perf_counter()
        state, metrics = step(state, data)
        jax.block_until_ready(state)
        times.append(time.perf_counter() - t0)
        losses.append(float(metrics["loss"]))
        nnz_up = int(metrics["nnz_up"])
        log(phase="A", round=r + 1, loss=losses[-1], round_s=times[-1],
            nnz_up=nnz_up, nnz_down=int(metrics["nnz_down"]))
        check(nnz_up >= k, f"round {r + 1}: nnz_up {nnz_up} < k {k}")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(phase="A", what="summary", compile_s=compile_s, round_s=times,
        losses=losses, peak_bytes_in_use=peak, lr=lr)
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(losses[-1] < losses[0], f"loss did not fall at lr={lr}: {losses}")
    return {"losses": losses, "round_s": times, "compile_s": compile_s,
            "peak_bytes_in_use": peak}


# ---------------------------------------------------------------------------
# Phase B: the flat server path with the Pallas kernels
# ---------------------------------------------------------------------------


def run_flat_server(*, backend: str, wire_backend: str, rounds: int,
                    n_clients: int, seed: int, n_train: int = 8000):
    from repro.core import make_protocol
    from repro.data import make_classification
    from repro.fed import FedEnvironment, FederatedTrainer, TrainerConfig
    from repro.models.paper_models import MODEL_ZOO
    train, test = make_classification(seed=seed, n=n_train, n_test=2000)
    env = FedEnvironment(n_clients=n_clients, participation=1.0,
                         classes_per_client=2, batch_size=20)
    proto = make_protocol("stc", sparsity_up=P_STC, sparsity_down=P_STC,
                          backend=backend, wire_backend=wire_backend)
    tr = FederatedTrainer(MODEL_ZOO["mlp"], train, test, env, proto,
                          TrainerConfig(ingest=True, seed=seed))
    t0 = time.perf_counter()
    rec = tr.run(rounds, eval_every=rounds)[-1]
    return tr, rec, time.perf_counter() - t0


def encode_phase_hlo(tr) -> str:
    """Optimized HLO of the trainer's compiled encode phase."""
    from repro.core.residual import take_states
    sel = np.arange(tr.env.participants_per_round)
    xs, ys = tr._sample_batches(sel, tr.protocol.local_iters)
    return tr._encode_fn.lower(
        tr.params_vec, tr.client_mom[sel], take_states(tr.client_state, sel),
        xs, ys).compile().as_text()


def phase_flat_server(*, rounds: int, n_clients: int, seed: int,
                      n_train: int = 8000) -> dict:
    out = {}
    for backend, wire_backend in (("kernel", "kernel"), ("jnp", "numpy")):
        tr, rec, secs = run_flat_server(
            backend=backend, wire_backend=wire_backend, rounds=rounds,
            n_clients=n_clients, seed=seed, n_train=n_train)
        out[backend] = (tr, rec)
        log(phase="B", backend=backend, wire_backend=wire_backend,
            rounds=rounds, acc=rec["acc"], bits_up=rec["bits_up"],
            bits_down=rec["bits_down"], measured=bool(rec["measured"]),
            wall_s=secs)
    (tr_k, k), (_, j) = out["kernel"], out["jnp"]
    check(k["measured"] and j["measured"], "bits were not measured")
    check(k["bits_up"] == j["bits_up"] and k["bits_down"] == j["bits_down"],
          f"measured bits differ: kernel {k['bits_up']}/{k['bits_down']} vs "
          f"jnp {j['bits_up']}/{j['bits_down']}")
    check(abs(k["acc"] - j["acc"]) <= 1e-3,
          f"accuracy differs: kernel {k['acc']} vs jnp {j['acc']}")
    return {"kernel": k, "jnp": j, "encode_hlo": encode_phase_hlo(tr_k)}


# ---------------------------------------------------------------------------
# --chips 4: the 4-client mesh round against a one-chip reference
# ---------------------------------------------------------------------------


def reference_programs(cfg, tc, n_clients: int):
    """The plain round on one device, as two jitted programs: a client's
    float32 grads on its batch slice through ``tree_encode`` (``client``),
    and the mean of the clients' messages through ``tree_decode`` applied to
    the params (``server``)."""
    from repro.launch.train import codec_for
    from repro.models import lm_loss
    codec = codec_for(tc)
    numel = cfg.param_count()
    zeros = lambda t: jax.tree.map(
        lambda x: jnp.zeros(x.shape, jnp.float32), t)

    def client(params, tokens, labels):
        g = jax.grad(lambda p: lm_loss(p, cfg, tokens, labels,
                                       compute_dtype=jnp.float32))(params)
        delta = jax.tree.map(lambda u: -tc.lr * u.astype(jnp.float32), g)
        msg, _, _ = codec.tree_encode(delta, zeros(delta), numel=numel)
        return msg

    def server(params, msgs):
        total = msgs[0]
        for m in msgs[1:]:
            total = jax.tree.map(jnp.add, total, m)
        mean = jax.tree.map(lambda t: t / n_clients, total)
        down, _, _ = codec.tree_decode(mean, zeros(mean), numel=numel)
        return jax.tree.map(lambda p, d: (p.astype(jnp.float32) + d)
                            .astype(p.dtype), params, down)

    return jax.jit(client), jax.jit(server)


def check_sharded(tree, n_clients: int, what: str) -> None:
    """Every leaf splits its leading axis over ``n_clients`` devices."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        devs = {s.device for s in leaf.addressable_shards}
        rows = {s.data.shape[0] for s in leaf.addressable_shards}
        check(len(devs) == n_clients and rows == {leaf.shape[0] // n_clients},
              f"{what}{jax.tree_util.keystr(path)} is not split over "
              f"{n_clients} devices: {leaf.sharding}")


def phase_mesh_round(cfg, *, n_clients: int, batch: int, seq: int,
                     rounds: int, lr: float, seed: int) -> dict:
    from repro.launch.mesh import make_mesh
    from repro.launch.train import (batch_shardings, init_train_state,
                                    make_train_step, state_shardings)
    t_setup = time.perf_counter()
    mesh = make_mesh(data=n_clients)
    tc = train_config(lr=lr, compute_dtype=jnp.float32)
    numel = cfg.param_count()
    k = max(int(numel * P_STC), 1)
    b = batch // n_clients
    data0 = lm_batch(cfg, batch, seq, seed)
    state0 = init_train_state(cfg, tc, n_clients=n_clients,
                              key=jax.random.PRNGKey(seed))
    params0 = state0["params"]                    # on chip 0
    state = jax.device_put(state0, state_shardings(state0, mesh))
    data = jax.device_put(data0, batch_shardings(data0, mesh, batch))
    check_sharded(state["client_res"], n_clients, "state client_res")
    check_sharded(data, n_clients, "batch")

    with jax.default_matmul_precision("highest"):
        client, server = reference_programs(cfg, tc, n_clients)
        msg = jax.eval_shape(client, params0, data0["tokens"][:b],
                             data0["labels"][:b])
        lowered = {"client": client.lower(params0, data0["tokens"][:b],
                                          data0["labels"][:b]),
                   "server": server.lower(params0, [msg] * n_clients)}
        step = make_train_step(cfg, mesh, tc)
        with jax.set_mesh(mesh):            # the reference stays on chip 0
            lowered["step"] = step.lower(state, data)
    progs, compile_s = compile_all(lowered)
    log(phase="mesh", what="setup", clients=n_clients, mesh=dict(mesh.shape),
        batch=[batch, seq], compile_s=compile_s,
        setup_s=time.perf_counter() - t_setup)

    msgs = [progs["client"](params0, data0["tokens"][c * b:(c + 1) * b],
                            data0["labels"][c * b:(c + 1) * b])
            for c in range(n_clients)]
    want = jax.tree.map(np.asarray, progs["server"](params0, msgs))
    del msgs

    for r in range(rounds):
        t0 = time.perf_counter()
        state, metrics = progs["step"](state, data)
        jax.block_until_ready(state)
        secs = time.perf_counter() - t0
        loss = float(metrics["loss"])
        log(phase="mesh", round=r + 1, loss=loss, round_s=secs,
            nnz_up=int(metrics["nnz_up"]), nnz_down=int(metrics["nnz_down"]))
        check(math.isfinite(loss), f"round {r + 1}: loss {loss}")
        check_sharded(state["client_res"], n_clients,
                      "state client_res after the step ")
        if r == 0:
            got = jax.tree.map(np.asarray, state["params"])
    bad, worst = 0, 0.0
    for a, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        a, g = a.astype(np.float32), g.astype(np.float32)
        err = np.abs(g - a)
        bad += int(np.sum(err > REF_RTOL * np.abs(a) + REF_ATOL))
        worst = max(worst, float(err.max()))
    log(phase="mesh", what="vs_reference", mismatched=bad, k=k,
        allowed=int(REF_FLIP_SHARE * k), max_abs_diff=worst,
        rtol=REF_RTOL, atol=REF_ATOL)
    check(bad <= REF_FLIP_SHARE * k,
          f"{bad} params differ from the one-chip reference "
          f"(allowed {int(REF_FLIP_SHARE * k)})")
    return {"mismatched": bad, "max_abs_diff": worst}


# ---------------------------------------------------------------------------


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache

    info = device_gate(args.chips)
    log(compile_cache=use_compile_cache())
    cfg = get_config("smollm-135m")
    if args.chips == 4:
        cfg = dataclasses.replace(cfg, n_layers=MESH_LAYERS)
        phase_mesh_round(cfg, n_clients=4, batch=BATCH, seq=SEQ, rounds=2,
                         lr=LR, seed=args.seed)
    else:
        phase_model_round(cfg, batch=BATCH, seq=SEQ, rounds=5, lr=LR,
                          seed=args.seed)
        res = phase_flat_server(rounds=3, n_clients=8, seed=args.seed)
        check("tpu_custom_call" in res["encode_hlo"],
              "the encode phase's compiled HLO holds no tpu_custom_call")
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
