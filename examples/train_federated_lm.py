"""End-to-end driver: federated STC training of a transformer LM on the
distributed train_step (shard_map over client axes, GSPMD tensor parallelism),
on a mesh of the attached chips, one client per chip.

    PYTHONPATH=src python examples/train_federated_lm.py \
        [--arch smollm-135m] [--steps 200] [--protocol stc] [--full]

Default trains a reduced (~10M-param) variant of the chosen architecture for a
few hundred steps on synthetic token data -- small enough for CPU, while
exercising the REAL production code path (the same make_train_step the
chip runs).  --full uses the full assigned config (TPU-sized).  On the CPU,
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` gives N virtual
devices and so N clients.
"""

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.data import make_lm_tokens
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.train import (TrainConfig, batch_shardings,
                                init_train_state, make_train_step,
                                state_shardings)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=200)
    from repro.core.protocols import get_protocol_class, registered_protocols
    ap.add_argument("--protocol", default="stc",
                    choices=registered_protocols())
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--full", action="store_true",
                    help="use the full assigned config (TPU-sized)")
    ap.add_argument("--eval-every", type=int, default=25)
    args = ap.parse_args()

    use_compile_cache()
    mesh = make_mesh()                  # every attached chip on "data"
    n_clients = mesh.shape["data"]
    if args.batch % n_clients:
        raise SystemExit(f"--batch {args.batch} must split over "
                         f"{n_clients} clients")

    if args.full:
        cfg = get_config(args.arch)
    else:
        # reduced variant: same family, a few more layers than the smoke
        # config (keeps head/dim divisibility of the family intact)
        smoke = get_smoke_config(args.arch)
        cfg = dataclasses.replace(smoke, n_layers=min(smoke.n_layers * 2, 6))
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"protocol={args.protocol} mesh={dict(mesh.shape)}")

    # demo-scale communication delay: cap the codec's default period at 4
    delay = min(get_protocol_class(args.protocol)().local_iters, 4)
    tc = TrainConfig(protocol=args.protocol, lr=args.lr,
                     sparsity_up=1 / 100, sparsity_down=1 / 100,
                     local_iters=delay)
    state = init_train_state(cfg, tc, n_clients=n_clients,
                             key=jax.random.PRNGKey(0))
    state = jax.device_put(state, state_shardings(state, mesh))
    step = make_train_step(cfg, mesh, tc)

    tokens = make_lm_tokens(seed=0, n_tokens=1 << 22, vocab=cfg.vocab_size)
    rng = np.random.default_rng(0)

    def sample_batch():
        b, s = args.batch, args.seq
        starts = rng.integers(0, len(tokens) - s - 1, size=b)
        toks = np.stack([tokens[i : i + s] for i in starts])
        labs = np.stack([tokens[i + 1 : i + s + 1] for i in starts])
        batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labs)}
        if cfg.encoder is not None:
            batch["frames"] = jnp.zeros((b, cfg.encoder.n_frames, cfg.d_model),
                                        jnp.float32)
        if cfg.n_prefix_tokens:
            batch["prefix"] = jnp.zeros((b, cfg.n_prefix_tokens, cfg.d_model),
                                        jnp.float32)
        return jax.device_put(batch, batch_shardings(batch, mesh, b))

    t0 = time.time()
    losses = []
    for i in range(args.steps):
        state, metrics = step(state, sample_batch())
        losses.append(float(metrics["loss"]))
        if (i + 1) % args.eval_every == 0 or i == 0:
            window = np.mean(losses[-args.eval_every:])
            extras = {k: int(v) for k, v in metrics.items() if k != "loss"}
            print(f"step {i+1:4d}  loss {window:.4f}  {extras}  "
                  f"({(time.time()-t0)/(i+1):.2f}s/step)")

    print(f"\nfinal loss {np.mean(losses[-20:]):.4f} "
          f"(started {np.mean(losses[:5]):.4f}) in {time.time()-t0:.0f}s")
    assert np.mean(losses[-20:]) < np.mean(losses[:5]), "training must learn"


if __name__ == "__main__":
    main()
