"""Distributed launch tests (subprocess: these need fake multi-device XLA,
which must not leak into the rest of the suite -- the main process keeps 1
CPU device per the dry-run isolation rule)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ,
       "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
       "PYTHONPATH": os.path.join(REPO, "src")}


def _run(code: str, timeout=900):
    return subprocess.run([sys.executable, "-c", code], env=ENV, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_train_step_loss_decreases_all_protocols():
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.launch.mesh import make_mesh
from repro.launch.train import TrainConfig, init_train_state, make_train_step
from repro.data import make_lm_tokens

mesh = make_mesh(data=2, model=2)
cfg = get_smoke_config("smollm-135m")
toks = make_lm_tokens(n_tokens=4*128+1, vocab=cfg.vocab_size)
batch = {"tokens": jnp.asarray(toks[:-1].reshape(4,128)),
         "labels": jnp.asarray(toks[1:].reshape(4,128))}
for proto in ("stc", "topk", "signsgd", "fedavg", "baseline"):
    tc = TrainConfig(protocol=proto, lr=0.05, sparsity_up=1/50,
                     sparsity_down=1/50, local_iters=2 if proto=="fedavg" else 1)
    state = init_train_state(cfg, tc, n_clients=2, key=jax.random.PRNGKey(0))
    step = make_train_step(cfg, mesh, tc)
    losses = []
    for _ in range(4):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses)), (proto, losses)
    assert losses[-1] < losses[0], (proto, losses)
    print(proto, "OK", losses[0], "->", losses[-1])
print("ALL_PROTOCOLS_OK")
"""
    r = _run(code)
    assert "ALL_PROTOCOLS_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_masked_step_drops_client_without_stalling():
    """TrainConfig(masked=True): with mask=(1,0) the dropped client's message
    gets zero weight -- the step equals the arrived client's update alone
    (baseline codec: global delta == client 0's delta) and the masked-out
    client's state never advances."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.launch.mesh import make_mesh
from repro.launch.train import TrainConfig, init_train_state, make_train_step
from repro.models import lm_loss

mesh = make_mesh(data=2, model=2)
cfg = get_smoke_config("qwen2-0.5b")
tc = TrainConfig(protocol="baseline", lr=0.1, compute_dtype=jnp.float32,
                 masked=True)
state = init_train_state(cfg, tc, n_clients=2, key=jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)), jnp.int32)
batch = {"tokens": toks, "labels": toks}
step = make_train_step(cfg, mesh, tc)
mask = jnp.asarray([1.0, 0.0]); stal = jnp.zeros(2)
new_state, metrics = step(state, batch, mask, stal)

# host reference: ONLY client 0 (batch rows 0:2) contributes, full weight
params = state["params"]
def loss_of(p): return lm_loss(p, cfg, toks[0:2], toks[0:2],
                               compute_dtype=jnp.float32)
g = jax.grad(loss_of)(params)
want = jax.tree.map(lambda p, gg: p - tc.lr * gg.astype(jnp.float32), params, g)
for (pa, a), (pb, b) in zip(
        jax.tree_util.tree_flatten_with_path(want)[0],
        jax.tree_util.tree_flatten_with_path(new_state["params"])[0]):
    np.testing.assert_allclose(np.asarray(b, np.float32),
                               np.asarray(a, np.float32),
                               rtol=5e-3, atol=5e-5, err_msg=str(pa))

# zero-weight round: nothing arrives, params must not move
frozen, _ = step(state, batch, jnp.zeros(2), stal)
for (pa, a), (pb, b) in zip(
        jax.tree_util.tree_flatten_with_path(state["params"])[0],
        jax.tree_util.tree_flatten_with_path(frozen["params"])[0]):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=str(pa))

# stateful codec (stc): a zero-arrival step must also freeze the server
# residual, not drain it into a parameter update
tc2 = TrainConfig(protocol="stc", lr=0.1, sparsity_up=1/20, sparsity_down=1/20,
                  compute_dtype=jnp.float32, masked=True)
state2 = init_train_state(cfg, tc2, n_clients=2, key=jax.random.PRNGKey(0))
state2["server_res"] = jax.tree.map(
    lambda p: 0.01 * jnp.ones(p.shape, jnp.float32), state2["params"])
step2 = make_train_step(cfg, mesh, tc2)
frozen2, _ = step2(state2, batch, jnp.zeros(2), stal)
for key in ("params", "server_res"):
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_flatten_with_path(state2[key])[0],
            jax.tree_util.tree_flatten_with_path(frozen2[key])[0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=key + str(pa))
print("MASKED_STEP_OK")
"""
    r = _run(code)
    assert "MASKED_STEP_OK" in r.stdout, r.stdout[-3000:] + r.stderr[-3000:]


@pytest.mark.slow
def test_distributed_stc_matches_single_device_semantics():
    """2-client distributed STC == hand-computed reference on the host:
    per-client grad -> STC(EF) -> mean -> server STC(EF) -> apply."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.launch.mesh import make_mesh
from repro.launch.train import TrainConfig, init_train_state, make_train_step
from repro.core.distributed import stc_compress_tree, tree_add
from repro.models import lm_loss

mesh = make_mesh(data=2, model=2)
cfg = get_smoke_config("qwen2-0.5b")
tc = TrainConfig(protocol="stc", lr=0.1, sparsity_up=1/20, sparsity_down=1/20,
                 compute_dtype=jnp.float32)
state = init_train_state(cfg, tc, n_clients=2, key=jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)), jnp.int32)
batch = {"tokens": toks, "labels": toks}
step = make_train_step(cfg, mesh, tc)
new_state, metrics = step(state, batch)

# host reference
params = state["params"]
numel = cfg.param_count()
def loss_of(p, sl): return lm_loss(p, cfg, toks[sl], toks[sl], compute_dtype=jnp.float32)
msgs = []
for ci, sl in enumerate((slice(0,2), slice(2,4))):
    g = jax.grad(loss_of)(params, sl)
    delta = jax.tree.map(lambda u: -tc.lr*u.astype(jnp.float32), g)
    tern, _ = stc_compress_tree(delta, tc.sparsity_up, numel=numel)
    msgs.append(tern)
mean = jax.tree.map(lambda a,b: (a+b)/2, *msgs)
down, _ = stc_compress_tree(mean, tc.sparsity_down, numel=numel)
want = jax.tree.map(lambda p,d: p+d, params, down)
got = new_state["params"]
for (pa, a), (pb, b) in zip(
        jax.tree_util.tree_flatten_with_path(want)[0],
        jax.tree_util.tree_flatten_with_path(got)[0]):
    np.testing.assert_allclose(np.asarray(b, np.float32),
                               np.asarray(a, np.float32),
                               rtol=5e-3, atol=5e-5, err_msg=str(pa))
print("DIST_MATCHES_REFERENCE")
"""
    r = _run(code)
    assert "DIST_MATCHES_REFERENCE" in r.stdout, r.stdout[-3000:] + r.stderr[-3000:]
