"""Distributed federated train_step for the production mesh.

Mapping of the paper's protocol onto the pod (DESIGN.md §2):

* manual mesh axes ("pod", "data") carry the CLIENTS -- one client cohort per
  data-parallel block, via ``jax.shard_map`` (auto axis "model" = tensor
  parallelism inside a client, handled by GSPMD);
* each client computes grads on its own batch shard ONLY (no gradient psum --
  that is the point of federated learning);
* upstream: the codec's ``tree_encode`` (per-client, with error feedback
  where the codec keeps one -- Eqs. 8-11);
* aggregation + downstream: the codec's ``tree_reduce`` collective over the
  client axes (the only protocol-level collective), then ``tree_decode`` with
  the server residual (Eqs. 10/12) -- computed identically on every block, so
  the broadcast is implicit;
* supported protocols: every codec registered in
  :mod:`repro.core.protocols` (stc / topk / signsgd / fedavg / baseline /
  ternquant / any third-party registration) -- there is no protocol dispatch
  in this module.

Momentum defaults OFF per the paper's lesson (6) (stale client momentum harms
non-iid + partial-participation training); pass momentum>0 to enable
per-client buffers.

Run as a script for a demo on a mesh of the attached chips (one client per
chip; on the CPU, ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
gives four virtual ones):
    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.protocols import Codec, get_protocol_class
from repro.models import init_model, lm_loss
from repro.models.config import ModelConfig
from repro.sharding.rules import batch_spec, fit_spec, param_specs

__all__ = ["TrainConfig", "WireLedger", "codec_for", "init_train_state",
           "make_train_step", "state_shardings", "batch_shardings"]


class WireLedger:
    """Host-side measured-bits accounting for the mesh trainer.

    Feed it the ``(msgs_tree, global_delta_tree)`` extra output of a
    ``measure_wire=True`` train step; it serializes every client's message
    and the downstream update through the codec's wire format
    (:mod:`repro.core.wire`) and accumulates EXACT bits, alongside the
    analytic Eq. 1 model as a cross-check.  Codecs without a wire format
    fall back to analytic in both columns.
    """

    def __init__(self, codec: Codec, numel: int):
        self.codec, self.numel = codec, numel
        self.rounds = 0
        self.bits_up = self.bits_down = 0.0
        self.bits_up_analytic = self.bits_down_analytic = 0.0

    def record_round(self, msgs_tree, global_delta_tree, mask=None) -> None:
        """Account one round.  ``mask`` (per-client 0/1, masked/async mode)
        keeps the ledger honest under dropped shards: only messages that
        actually reached the server count as upstream bits."""
        import numpy as np
        leaves = [np.asarray(leaf) for leaf in jax.tree.leaves(msgs_tree)]
        n_clients = leaves[0].shape[0]
        msgs = np.concatenate(
            [leaf.reshape(n_clients, -1).astype(np.float32)
             for leaf in leaves], axis=1)
        if mask is not None:
            keep = np.asarray(mask, dtype=bool).reshape(-1)
            msgs = msgs[keep]
            n_clients = int(keep.sum())
        gd = np.concatenate(
            [np.asarray(leaf).reshape(-1).astype(np.float32)
             for leaf in jax.tree.leaves(global_delta_tree)])
        if n_clients:
            self.bits_up += self.codec.measured_upload_bits(msgs)
        self.bits_down += self.codec.measured_download_bits(
            gd, n_participating=max(n_clients, 1))
        self.bits_up_analytic += n_clients * self.codec.upload_bits(self.numel)
        self.bits_down_analytic += self.codec.download_bits(
            self.numel, n_participating=max(n_clients, 1))
        self.rounds += 1

    def summary(self) -> dict:
        return {"rounds": self.rounds, "bits_up": self.bits_up,
                "bits_down": self.bits_down,
                "bits_up_analytic": self.bits_up_analytic,
                "bits_down_analytic": self.bits_down_analytic}


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    protocol: str = "stc"           # any codec registered in core.protocols
    lr: float = 0.1
    momentum: float = 0.0           # paper lesson (6): keep 0 in fed settings
    sparsity_up: float = 1 / 400
    sparsity_down: float = 1 / 400
    sign_step: float = 2e-4
    local_iters: int = 1            # fedavg delay period n
    compute_dtype: Any = jnp.bfloat16
    stc_iters: int = 32             # k-selection bisection rounds (§Perf lever)
    chunks: int | None = None       # chunked (leaf, chunk) selection: each
                                    # leaf splits into ceil(size/chunks)
                                    # blocks with independent k-selection/µ,
                                    # all through the STC backend registry --
                                    # no global collective, so the selection
                                    # sweeps shard + pipeline across the mesh
    p_fn: Any = None                # per-layer sparsity schedule hook:
                                    # p_fn(layer_name, depth) -> p | None
    controller: Any = None          # adaptive per-chunk sparsity controller
                                    # (repro.core.adaptive name or instance)
                                    # for the chunked tree path
    measure_wire: bool = False      # also return (msgs, global_delta) trees
                                    # so a host WireLedger can account the
                                    # REAL serialized bits per round
    rule: Any = None                # server AggregationRule (name or
                                    # instance, core.aggregation); None =
                                    # the codec default ("mean")
    masked: bool = False            # async mode: train_step takes per-client
                                    # (mask, staleness) vectors; a masked-out
                                    # client's message gets zero weight in the
                                    # tree_reduce collective and its residual/
                                    # momentum stay frozen -- a dropped shard
                                    # no longer stalls (or skews) the step


def codec_for(tc: TrainConfig) -> Codec:
    """Instantiate the registered codec named by ``tc.protocol``, forwarding
    exactly the TrainConfig hyperparameters the codec declares as fields."""
    cls = get_protocol_class(tc.protocol)
    fields = {f.name for f in dataclasses.fields(cls)}
    kw = dict(sparsity_up=tc.sparsity_up, sparsity_down=tc.sparsity_down,
              sign_step=tc.sign_step, local_iters=tc.local_iters,
              chunk_size=tc.chunks, p_fn=tc.p_fn, controller=tc.controller)
    kw = {k: v for k, v in kw.items() if k in fields}
    if tc.rule is not None:
        kw["rule"] = tc.rule
    return cls(**kw)


def init_train_state(cfg: ModelConfig, tc: TrainConfig, n_clients: int, key):
    """TrainState pytree. Residuals/momentum are fp32, client-major."""
    codec = codec_for(tc)
    params = init_model(cfg, key)
    state = {"params": params, "step": jnp.zeros((), jnp.int32)}
    f32_like = lambda p: jnp.zeros(p.shape, jnp.float32)
    stacked = lambda p: jnp.zeros((n_clients,) + p.shape, jnp.float32)
    if codec.has_client_state():
        state["client_res"] = jax.tree.map(stacked, params)
    if codec.has_server_state():
        state["server_res"] = jax.tree.map(f32_like, params)
    if tc.momentum > 0:
        state["momentum"] = jax.tree.map(stacked, params)
    return state


# ---------------------------------------------------------------------------
# shardings
# ---------------------------------------------------------------------------


def _client_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def state_shardings(state, mesh):
    """NamedShardings for the TrainState: params/server_res model-sharded,
    client-major buffers additionally split over the client axes."""
    ca = _client_axes(mesh)
    pspecs = param_specs(state["params"])

    def stack_spec(s: P) -> P:
        return P(ca, *s)

    def shard(leaf, s):
        return NamedSharding(mesh, fit_spec(s, leaf.shape, mesh))

    def shard_stacked(leaf, s):
        return NamedSharding(mesh, fit_spec(stack_spec(s), leaf.shape, mesh))

    sh = {
        "params": jax.tree.map(shard, state["params"], pspecs),
        "step": NamedSharding(mesh, P()),
    }
    if "client_res" in state:
        sh["client_res"] = jax.tree.map(shard_stacked, state["client_res"],
                                        pspecs)
    if "server_res" in state:
        sh["server_res"] = jax.tree.map(shard, state["server_res"], pspecs)
    if "momentum" in state:
        sh["momentum"] = jax.tree.map(shard_stacked, state["momentum"],
                                      pspecs)
    return sh


def batch_shardings(batch, mesh, global_batch: int):
    bs = batch_spec(mesh, global_batch)
    return jax.tree.map(lambda _: NamedSharding(mesh, bs), batch)


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------


def make_train_step(cfg: ModelConfig, mesh, tc: TrainConfig):
    """Returns the jitted ``train_step(state, batch) -> (state, metrics)``:
    shard_map over the client axes (auto axis: "model"), or the plain step on
    a mesh without client axes."""
    ca = _client_axes(mesh)
    n_clients = math.prod(mesh.shape[a] for a in ca) if ca else 1
    numel = cfg.param_count()
    codec = codec_for(tc)

    def loss_of(params, batch):
        return lm_loss(params, cfg, batch["tokens"], batch["labels"],
                       prefix=batch.get("prefix"), frames=batch.get("frames"),
                       compute_dtype=tc.compute_dtype)

    def local_delta(params, mom, batch):
        """One client's update ΔW (and new momentum). A codec with a
        communication-delay period runs ``local_iters`` sequential SGD steps
        over microbatches."""
        if codec.local_iters > 1:
            n = tc.local_iters
            b_local = batch["tokens"].shape[0]
            assert b_local % n == 0, (b_local, n)
            micro = {k: v.reshape((n, b_local // n) + v.shape[1:])
                     for k, v in batch.items()}

            def step(carry, mb):
                p, v = carry
                loss, g = jax.value_and_grad(loss_of)(p, mb)
                if tc.momentum > 0:
                    v = jax.tree.map(
                        lambda vv, gg: tc.momentum * vv +
                        gg.astype(jnp.float32), v, g)
                    upd = v
                else:
                    upd = g
                p = jax.tree.map(
                    lambda pp, uu: (pp.astype(jnp.float32) -
                                    tc.lr * uu.astype(jnp.float32)
                                    ).astype(pp.dtype), p, upd)
                return (p, v), loss

            (p_end, mom), losses = jax.lax.scan(step, (params, mom), micro)
            delta = jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                p_end, params)
            return delta, mom, jnp.mean(losses)

        loss, g = jax.value_and_grad(loss_of)(params, batch)
        if tc.momentum > 0:
            mom = jax.tree.map(
                lambda vv, gg: tc.momentum * vv + gg.astype(jnp.float32),
                mom, g)
            upd = mom
        else:
            upd = g
        delta = jax.tree.map(lambda u: -tc.lr * u.astype(jnp.float32), upd)
        return delta, mom, loss

    def step_fn(state, batch, mask=None, staleness=None):
        params = state["params"]
        mom = None
        if "momentum" in state:
            mom = jax.tree.map(lambda x: x[0], state["momentum"])

        delta, mom, loss = local_delta(params, mom, batch)
        metrics = {"loss": jax.lax.pmean(loss, ca) if ca else loss}
        new_state = dict(state)
        new_state["step"] = state["step"] + 1
        # a masked-out (dropped) client's local state must not advance: its
        # message never reached the server, so momentum/residual stay frozen
        # until it participates again (mirrors the buffered fed trainer)
        arrived = None if mask is None else jnp.sum(mask) > 0
        if mom is not None:
            if arrived is not None:
                mom = jax.tree.map(
                    lambda new, old: jnp.where(arrived, new, old[0]),
                    mom, state["momentum"])
            new_state["momentum"] = jax.tree.map(lambda x: x[None], mom)

        # ---- the entire protocol: three codec calls, zero dispatch ---------
        cres = (jax.tree.map(lambda x: x[0], state["client_res"])
                if "client_res" in state else None)
        msg, new_cres, m_up = codec.tree_encode(delta, cres, numel=numel,
                                                iters=tc.stc_iters)
        if "client_res" in state:
            if arrived is not None:
                new_cres = jax.tree.map(
                    lambda new, old: jnp.where(arrived, new, old[0]),
                    new_cres, state["client_res"])
            new_state["client_res"] = jax.tree.map(lambda x: x[None], new_cres)
        # ---- upload: the ONLY protocol-level collective --------------------
        combined = codec.tree_reduce(msg, ca, n_clients, mask=mask,
                                     staleness=staleness)
        global_delta, new_sres, m_down = codec.tree_decode(
            combined, state.get("server_res"), numel=numel, iters=tc.stc_iters)
        if mask is not None:
            # zero-arrival step: the server must not move either -- without
            # this gate a stateful codec (stc) would still drain its server
            # residual into a parameter update off the all-zero combined tree
            total = jnp.sum(mask)
            if ca:
                total = jax.lax.psum(total, ca)
            any_arrived = total > 0
            global_delta = jax.tree.map(
                lambda d: jnp.where(any_arrived, d, 0.0), global_delta)
            if new_sres is not None:
                new_sres = jax.tree.map(
                    lambda new, old: jnp.where(any_arrived, new, old),
                    new_sres, state.get("server_res"))
        if "server_res" in state:
            new_state["server_res"] = new_sres
        metrics.update(m_up)
        metrics.update(m_down)

        new_state["params"] = jax.tree.map(
            lambda p, d: (p.astype(jnp.float32) +
                          d.astype(jnp.float32)).astype(p.dtype),
            params, global_delta)
        if tc.measure_wire:
            # per-client message (leading client axis) + the replicated
            # downstream update, for host-side WireLedger accounting
            wire_out = (jax.tree.map(lambda x: x[None], msg), global_delta)
            return new_state, metrics, wire_out
        return new_state, metrics

    if not ca:
        def single(state, batch, mask=None, staleness=None):
            if not tc.masked and (mask is not None or staleness is not None):
                raise ValueError(
                    "train_step got mask/staleness but TrainConfig.masked is "
                    "False; rebuild the step with TrainConfig(masked=True)")
            return step_fn(state, batch, mask, staleness)
        return jax.jit(single)

    state_specs_in = {
        "params": P(), "step": P(),
    }
    out_specs_state = {"params": P(), "step": P()}
    if codec.has_client_state():
        state_specs_in["client_res"] = P(ca)
        out_specs_state["client_res"] = P(ca)
    if codec.has_server_state():
        state_specs_in["server_res"] = P()
        out_specs_state["server_res"] = P()
    # momentum specs are added dynamically at call time (same prefix trick)

    def wrapped(state, batch, mask=None, staleness=None):
        if not tc.masked and (mask is not None or staleness is not None):
            raise ValueError(
                "train_step got mask/staleness but TrainConfig.masked is "
                "False; rebuild the step with TrainConfig(masked=True)")
        specs_in = dict(state_specs_in)
        specs_out = dict(out_specs_state)
        if "momentum" in state:
            specs_in["momentum"] = P(ca)
            specs_out["momentum"] = P(ca)
        outs = ((specs_out, P(), (P(ca), P())) if tc.measure_wire
                else (specs_out, P()))
        # masked/async mode: the per-client participation mask + staleness
        # vectors ride in split over the client axes, one slice per shard
        in_specs = ((specs_in, P(ca), P(ca), P(ca)) if tc.masked
                    else (specs_in, P(ca)))
        args = (state, batch, mask, staleness) if tc.masked \
            else (state, batch)
        # NOTE: partial-manual shard_map must run through jit (the eager impl
        # path mishandles check_vma=False with auto axes).
        f = jax.shard_map(step_fn, mesh=mesh, in_specs=in_specs,
                          out_specs=outs, axis_names=set(ca), check_vma=False)
        return f(*args)

    return jax.jit(wrapped)


# ---------------------------------------------------------------------------
# demo driver
# ---------------------------------------------------------------------------


def main():
    import argparse
    from repro.configs import get_smoke_config
    from repro.data import make_lm_tokens
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.mesh import make_mesh

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--protocol", default="stc")
    ap.add_argument("--measure-wire", action="store_true",
                    help="serialize every message through the real wire "
                         "format and print measured vs analytic bits")
    ap.add_argument("--chunks", type=int, default=None,
                    help="chunked per-(leaf, chunk) selection block size "
                         "(default: one global flat selection)")
    args = ap.parse_args()

    use_compile_cache()
    mesh = make_mesh()                   # every attached chip on "data"
    n_clients = mesh.shape["data"]
    cfg = get_smoke_config(args.arch)
    tc = TrainConfig(protocol=args.protocol, lr=0.05, sparsity_up=1 / 50,
                     sparsity_down=1 / 50, measure_wire=args.measure_wire,
                     chunks=args.chunks)
    state = init_train_state(cfg, tc, n_clients=n_clients,
                             key=jax.random.PRNGKey(0))

    b = 2 * n_clients
    toks = make_lm_tokens(n_tokens=b * 128 + 1, vocab=cfg.vocab_size)
    batch = {"tokens": jnp.asarray(toks[:-1].reshape(b, 128)),
             "labels": jnp.asarray(toks[1:].reshape(b, 128))}
    if cfg.encoder is not None:
        batch["frames"] = jnp.zeros((b, cfg.encoder.n_frames, cfg.d_model),
                                    jnp.float32)
    if cfg.n_prefix_tokens:
        batch["prefix"] = jnp.zeros((b, cfg.n_prefix_tokens, cfg.d_model),
                                    jnp.float32)
    state = jax.device_put(state, state_shardings(state, mesh))
    batch = jax.device_put(batch, batch_shardings(batch, mesh, b))

    ledger = WireLedger(codec_for(tc), cfg.param_count())
    with jax.set_mesh(mesh):
        step = make_train_step(cfg, mesh, tc)
        for i in range(args.steps):
            if tc.measure_wire:
                state, metrics, (msgs, gd) = step(state, batch)
                ledger.record_round(msgs, gd)
            else:
                state, metrics = step(state, batch)
            print(f"step {i}: loss={float(metrics['loss']):.4f}",
                  {k: int(v) for k, v in metrics.items() if k != "loss"})
    if tc.measure_wire:
        s = ledger.summary()
        print(f"wire ledger over {s['rounds']} rounds: "
              f"up {s['bits_up']/8e6:.3f} MB (analytic "
              f"{s['bits_up_analytic']/8e6:.3f}), down "
              f"{s['bits_down']/8e6:.3f} MB (analytic "
              f"{s['bits_down_analytic']/8e6:.3f})")


if __name__ == "__main__":
    main()
