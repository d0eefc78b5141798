"""Named scopes of the mesh round and of the tree selection, and the runtime
count of the selection's bisection fallback (``TreeStats.fallback``, the
round's ``fallback_up`` / ``fallback_down``)."""

import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.compression import flatten_pytree, stc_compress
from repro.core.distributed import (stc_compress_tree,
                                    stc_compress_tree_chunked,
                                    ternary_quantize_tree)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = 1 << 20
P = 1 / 64          # k = 16384 > DEFAULT_CAP: the histogram path on the CPU
K = N // 64
ROUND_SCOPES = {"local_step", "encode", "exchange", "decode"}
SELECTION_SCOPES = {"histogram", "refine", "fallback"}


def _components(hlo_text: str) -> set:
    """Every name-stack component of the ``op_name``s in an HLO text."""
    out = set()
    for name in re.findall(r'op_name="([^"]*)"', hlo_text):
        out.update(name.split("/"))
    return out


def _tree(values):
    """2^20 values in two leaves."""
    values = jnp.asarray(values, jnp.float32)
    return {"w": values[:3 * N // 4].reshape(768, 1024),
            "b": values[3 * N // 4:]}


def _server_message(seed=7, mu=0.0123):
    """The one-client server's carried tree: the upload message, K nonzeros
    all of magnitude mu."""
    rng = np.random.default_rng(seed)
    v = np.zeros(N, np.float32)
    v[rng.choice(N, K, replace=False)] = mu * rng.choice([-1.0, 1.0], K)
    return v


def _uniform(seed=8):
    return np.random.default_rng(seed).uniform(-1, 1, N).astype(np.float32)


def test_selection_scopes_are_in_the_compiled_program():
    tree = _tree(np.zeros(N, np.float32))
    text = jax.jit(lambda t: stc_compress_tree(t, P)).lower(tree) \
        .compile().as_text()
    assert SELECTION_SCOPES <= _components(text)


def test_round_scopes_are_in_the_compiled_round():
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_mesh
    from repro.launch.train import (TrainConfig, init_train_state,
                                    make_train_step)
    mesh = make_mesh(data=1)
    cfg = get_smoke_config("smollm-135m")
    tc = TrainConfig(protocol="stc", lr=0.05)
    state = init_train_state(cfg, tc, n_clients=1, key=jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 32), jnp.int32)
    with jax.set_mesh(mesh):
        step = make_train_step(cfg, mesh, tc)
        text = step.lower(state, {"tokens": toks, "labels": toks}) \
            .compile().as_text()
    comps = _components(text)
    assert ROUND_SCOPES <= comps
    # the local step's ops keep their autodiff components under the scope
    names = re.findall(r'op_name="([^"]*)"', text)
    assert any(n.split("/")[1:2] == ["local_step"] and "jvp(" in n
               for n in names)


@pytest.mark.parametrize("case,ran", [("server_message", 1), ("uniform", 0)])
def test_fallback_counts_the_bisection(case, ran):
    """The one-client server's message (K equal magnitudes, all in the top
    bin, which overflows the refine's capacity) runs the bisection; a
    uniform tree (about N/256 values a bin) does not.  Both select what the
    flat oracle selects."""
    v = _server_message() if case == "server_message" else _uniform()
    tree = _tree(v)
    tern, st = jax.jit(lambda t: stc_compress_tree(t, P))(tree)
    assert st.fallback.dtype == jnp.int32 and int(st.fallback) == ran
    assert np.float32(st.thresh) == np.sort(np.abs(v))[-K]
    vec, _ = flatten_pytree(tree)
    tern_j, stats_j = stc_compress(vec, P)
    assert int(st.nnz) == int(stats_j.nnz)
    np.testing.assert_allclose(float(st.mu), float(stats_j.mu), rtol=1e-6)
    got, _ = flatten_pytree(tern)
    np.testing.assert_allclose(np.asarray(got), np.asarray(tern_j),
                               atol=1e-7)


@pytest.mark.parametrize("path", ["shortcut", "chunked", "ternquant"])
def test_fallback_reads_zero_where_no_bisection_exists(path):
    rng = np.random.default_rng(9)
    tree = {"w": jnp.asarray(rng.standard_normal((64, 50)), jnp.float32)}
    if path == "shortcut":                  # k = 32 <= cap on the CPU
        _, st = stc_compress_tree(tree, 0.01)
    elif path == "chunked":
        _, st = stc_compress_tree_chunked(tree, 0.01, chunk_size=1024)
    else:
        _, st = ternary_quantize_tree(tree, 0.7)
    assert int(st.fallback) == 0


def test_codecs_report_the_fallback():
    from repro.core.protocols import make_protocol
    tree = _tree(_server_message())
    zeros = jax.tree.map(jnp.zeros_like, tree)
    for name in ("stc", "topk"):
        codec = make_protocol(name, sparsity_up=P, sparsity_down=P)
        _, _, m_up = codec.tree_encode(tree, zeros, numel=N)
        assert int(m_up["fallback_up"]) == 1, name
    stc = make_protocol("stc", sparsity_up=P, sparsity_down=P)
    _, _, m_down = stc.tree_decode(tree, zeros, numel=N)
    assert int(m_down["fallback_down"]) == 1


TWO_CLIENTS = """
import functools, json
import jax, jax.numpy as jnp
import repro.core.distributed as dist
from repro.configs import get_smoke_config
from repro.launch.mesh import make_mesh
from repro.launch.train import TrainConfig, init_train_state, make_train_step
if {small_cap!r}:
    # a capacity of one candidate: every selection past the CPU shortcut
    # overflows it and runs the bisection
    dist.stc_compress_tree = functools.partial(dist.stc_compress_tree, cap=1)
mesh = make_mesh(data=2)
cfg = get_smoke_config("smollm-135m")
tc = TrainConfig(protocol="stc", lr=0.05)
state = init_train_state(cfg, tc, n_clients=2, key=jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)
with jax.set_mesh(mesh):
    step = make_train_step(cfg, mesh, tc)
    _, m = step(state, {{"tokens": toks, "labels": toks}})
print(json.dumps({{k: int(v) for k, v in m.items() if k != "loss"}}))
"""


@pytest.mark.parametrize("small_cap,up,down", [(False, 0, 0), (True, 2, 1)],
                         ids=["shortcut", "bisection"])
def test_fallback_up_counts_both_clients(small_cap, up, down):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run(
        [sys.executable, "-c", TWO_CLIENTS.format(small_cap=small_cap)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    import json
    m = json.loads(proc.stdout.strip().splitlines()[-1])
    assert m["fallback_up"] == up and m["fallback_down"] == down, m
