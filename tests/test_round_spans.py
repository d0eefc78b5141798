"""Named scopes of the mesh round and of the tree selection, and the count
route of the selection (max sweep, count bisection, final count): exact
against the flat oracle, and free of scatters and top-k sorts."""

import json
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
P = 1 / 64          # k = 16384 > DEFAULT_CAP: the count route on the CPU
K = N // 64
ROUND_SCOPES = {"local_step", "encode", "exchange", "decode"}
OLD_SELECTION_SCOPES = {"histogram", "refine", "fallback"}


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


def _smoke_round_text(monkeypatch=None):
    """The compiled one-client smoke round; with ``monkeypatch``, on the
    selection route a TPU takes."""
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_mesh
    from repro.launch.train import (TrainConfig, init_train_state,
                                    make_train_step)
    if monkeypatch is not None:
        import repro.core.distributed as dist
        monkeypatch.setattr(dist, "resolve_interpret", lambda _: False)
    mesh = make_mesh(data=1)
    cfg = get_smoke_config("smollm-135m")
    tc = TrainConfig(protocol="stc", lr=0.05)
    state = init_train_state(cfg, tc, n_clients=1, key=jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 32), jnp.int32)
    with jax.set_mesh(mesh):
        step = make_train_step(cfg, mesh, tc)
        return step.lower(state, {"tokens": toks, "labels": toks}) \
            .compile().as_text()


def _round_at(seq, tpu, monkeypatch):
    """The one-client smoke round at ``seq`` tokens, ``_attention`` seeing a
    TPU (the kernel interpreted) where ``tpu``: its compiled text and its
    loss after one round."""
    import repro.models.attention as attention
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_mesh
    from repro.launch.train import (TrainConfig, init_train_state,
                                    make_train_step)
    monkeypatch.setattr(attention, "_on_tpu", lambda: tpu)
    mesh = make_mesh(data=1)
    cfg = get_smoke_config("smollm-135m")
    tc = TrainConfig(protocol="stc", lr=0.05)
    state = init_train_state(cfg, tc, n_clients=1, key=jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, seq), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    with jax.set_mesh(mesh):
        step = make_train_step(cfg, mesh, tc)
        text = step.lower(state, batch).compile().as_text()
        _, metrics = step(state, batch)
    return text, float(metrics["loss"])


def _unwrapped(components: set) -> set:
    """Components without their transform labels: the forward's name stack
    folds a scope into ``jvp(attention)``."""
    out = set()
    for part in components:
        while part.endswith(")") and "(" in part:
            part = part[part.index("(") + 1:-1]
        out.add(part)
    return out


def test_attention_scope_is_in_the_compiled_round():
    """On the CPU every attention of the round runs the jnp scan under
    ``attention``, none under ``attention_kernel``."""
    comps = _unwrapped(_components(_smoke_round_text()))
    assert "attention" in comps
    assert "attention_kernel" not in comps


def test_a_tpu_round_runs_the_kernel_under_attention_kernel(monkeypatch):
    """With the TPU condition stubbed, the round's attention (causal bf16
    self-attention over 128 tokens) runs the kernel, under both scopes, and
    the round's loss is the jnp scan's to bf16 rounding."""
    text, loss = _round_at(128, True, monkeypatch)
    assert {"attention", "attention_kernel"} <= _unwrapped(_components(text))
    _, want = _round_at(128, False, monkeypatch)
    assert loss == pytest.approx(want, rel=1e-3)


def test_selection_scopes_are_in_the_compiled_program():
    tree = _tree(np.zeros(N, np.float32))
    text = jax.jit(lambda t: stc_compress_tree(t, P)).lower(tree) \
        .compile().as_text()
    comps = _components(text)
    assert "select" in comps
    assert not OLD_SELECTION_SCOPES & comps


def test_round_scopes_are_in_the_compiled_round():
    text = _smoke_round_text()
    assert ROUND_SCOPES <= _components(text)
    # the local step's ops keep their autodiff components under the scope
    names = re.findall(r'op_name="([^"]*)"', text)
    assert any(n.split("/")[1:2] == ["local_step"] and "jvp(" in n
               for n in names)


def test_the_compiled_round_has_no_scatter_or_top_k(monkeypatch):
    """On the TPU's route both selections of the round (upload and server)
    run under ``select``, and nothing under ``encode`` or ``decode`` is a
    scatter, a sort or a top-k."""
    text = _smoke_round_text(monkeypatch)
    codec_ops = []
    for line in text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if m and {"encode", "decode"} & set(m.group(1).split("/")):
            codec_ops.append((line, m.group(1)))
    for scope in ("encode", "decode"):
        assert any(scope in n.split("/") and "select" in n.split("/")
                   for _, n in codec_ops), scope
    for line, name in codec_ops:
        assert not re.search(r"\b(scatter|sort)\(", line), line
        assert not re.search(r"top_?k|scatter|bincount", name,
                             re.IGNORECASE), name
        assert "TopK" not in line, line


@pytest.mark.parametrize("case", ["server_message", "uniform"])
def test_count_route_matches_the_flat_oracle(case):
    """The one-client server's message (K equal magnitudes: every kept value
    ties the threshold) and a uniform tree: ``thresh`` is the exact k-th
    magnitude, and nnz, µ and the ternary output are the flat oracle's."""
    v = _server_message() if case == "server_message" else _uniform()
    tree = _tree(v)
    tern, st = jax.jit(lambda t: stc_compress_tree(t, P))(tree)
    assert np.float32(st.thresh) == np.sort(np.abs(v))[-K]
    vec, _ = flatten_pytree(tree)
    tern_j, stats_j = stc_compress(vec, P)
    assert int(st.nnz) == int(stats_j.nnz) == K
    np.testing.assert_allclose(float(st.mu), float(stats_j.mu), rtol=1e-6)
    got, _ = flatten_pytree(tern)
    np.testing.assert_allclose(np.asarray(got), np.asarray(tern_j),
                               atol=1e-7)


def test_an_all_zero_tree_sends_nothing():
    """Every magnitude is 0: the threshold is 0 (not the least kept
    magnitude's +inf start), µ is 0 and the message all zeros."""
    tree = _tree(np.zeros(N, np.float32))
    tern, st = jax.jit(lambda t: stc_compress_tree(t, P))(tree)
    assert float(st.thresh) == 0.0
    assert float(st.mu) == 0.0
    got, _ = flatten_pytree(tern)
    assert not np.any(np.asarray(got))
    vec, _ = flatten_pytree(tree)
    tern_j, stats_j = stc_compress(vec, P)
    assert float(stats_j.mu) == 0.0 and not np.any(np.asarray(tern_j))


@pytest.mark.parametrize("path", ["shortcut", "chunked", "ternquant"])
def test_select_is_absent_off_the_count_route(path):
    rng = np.random.default_rng(9)
    tree = {"w": jnp.asarray(rng.standard_normal((64, 50)), jnp.float32)}
    if path == "shortcut":                  # k = 32 <= cap on the CPU
        fn = lambda t: stc_compress_tree(t, 0.01)           # noqa: E731
    elif path == "chunked":
        fn = lambda t: stc_compress_tree_chunked(           # noqa: E731
            t, 0.01, chunk_size=1024)
    else:
        fn = lambda t: ternary_quantize_tree(t, 0.7)        # noqa: E731
    comps = _components(jax.jit(fn).lower(tree).compile().as_text())
    assert not ({"select"} | OLD_SELECTION_SCOPES) & comps


def test_codecs_encode_the_server_message_exactly():
    """The stc codec's upload and server re-compression, and the topk
    codec's upload, of the server-message tree keep exactly its K
    nonzeros."""
    from repro.core.protocols import make_protocol
    tree = _tree(_server_message())
    want, _ = flatten_pytree(tree)
    zeros = jax.tree.map(jnp.zeros_like, tree)
    stc = make_protocol("stc", sparsity_up=P, sparsity_down=P)
    topk = make_protocol("topk", sparsity_up=P, sparsity_down=P)
    tern_up, res_up, m_up = stc.tree_encode(tree, zeros, numel=N)
    tern_down, res_down, m_down = stc.tree_decode(tree, zeros, numel=N)
    msg, res_k, m_k = topk.tree_encode(tree, zeros, numel=N)
    assert int(m_up["nnz_up"]) == int(m_down["nnz_down"]) == K
    assert int(m_k["nnz_up"]) == K
    for tern, res in ((tern_up, res_up), (tern_down, res_down), (msg, res_k)):
        got, _ = flatten_pytree(tern)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)
        # what a µ off by its float32 mean's rounding leaves behind
        left, _ = flatten_pytree(res)
        np.testing.assert_allclose(np.asarray(left), 0.0,
                                   atol=1e-6 * float(np.max(np.abs(want))))


TWO_CLIENTS = """
import json
import jax, jax.numpy as jnp, numpy as np
import repro.core.distributed as dist
# the route a TPU takes, past the CPU's small-k shortcut
dist.resolve_interpret = lambda _: False


def kept(x, k):
    a = np.abs(np.asarray(x, np.float32)).reshape(-1)
    v = np.sort(a)[-k]
    return int(np.sum((a >= v) & (a > 0))), float(v)


if {mode!r} == "round":
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_mesh
    from repro.launch.train import (TrainConfig, init_train_state,
                                    make_train_step)
    mesh = make_mesh(data=2)
    cfg = get_smoke_config("smollm-135m")
    tc = TrainConfig(protocol="stc", lr=0.05, compute_dtype=jnp.float32,
                     measure_wire=True)
    state = init_train_state(cfg, tc, n_clients=2, key=jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                              cfg.vocab_size)
    with jax.set_mesh(mesh):
        step = make_train_step(cfg, mesh, tc)
        _, m, (msgs, down) = step(state, {{"tokens": toks, "labels": toks}})
    flat = lambda t: np.concatenate(
        [np.asarray(x, np.float32).reshape(x.shape[0], -1)
         for x in jax.tree.leaves(t)], axis=1)
    msgs = flat(msgs)
    down = np.concatenate([np.asarray(x, np.float32).reshape(-1)
                           for x in jax.tree.leaves(down)])
    numel = cfg.param_count()
    k = max(int(numel * tc.sparsity_up), 1)
    combined = (msgs[0] + msgs[1]) / np.float32(2)
    out = {{"k": k, "nnz_up": int(m["nnz_up"]),
           "msg_nnz": [int(np.count_nonzero(r)) for r in msgs],
           "nnz_down": int(m["nnz_down"]),
           "down_nnz": int(np.count_nonzero(down)),
           "oracle_down": kept(combined, max(int(numel * tc.sparsity_down),
                                             1))[0]}}
else:
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    mesh = make_mesh(data=2)
    rng = np.random.default_rng(3)
    w = rng.standard_normal((2, 4096, 64)).astype(np.float32)
    b = (rng.standard_normal((2, 3000)) * 2).astype(np.float32)
    numel = w.size + b.size
    p = 1 / 64

    def sel(tree):
        tree = jax.tree.map(lambda x: x[0], tree)
        tern, st = dist.stc_compress_tree(tree, p, manual_axes=("data",),
                                          numel=numel)
        return jax.tree.map(lambda x: x[None], tern), st.nnz, st.mu, st.thresh

    f = jax.jit(jax.shard_map(sel, mesh=mesh, in_specs=P("data"),
                              out_specs=(P("data"), P(), P(), P())))
    tern, nnz, mu, thresh = f({{"w": w, "b": b}})
    k = max(int(numel * p), 1)
    a = np.concatenate([w.reshape(-1), b.reshape(-1)])
    n_kept, v = kept(a, k)
    keep = np.abs(a) >= v
    mu_o = float(np.abs(a[keep]).astype(np.float64).mean())
    got = np.concatenate([np.asarray(tern["w"]).reshape(-1),
                          np.asarray(tern["b"]).reshape(-1)])
    want = np.where(keep, np.float32(mu_o) * np.sign(a), 0).astype(np.float32)
    out = {{"k": k, "nnz": int(nnz), "oracle_nnz": n_kept,
           "thresh": float(thresh), "oracle_thresh": v,
           "mu_rel": abs(float(mu) - mu_o) / mu_o,
           "tern_err": float(np.max(np.abs(got - want)))}}
print(json.dumps(out))
"""


@pytest.mark.parametrize("mode", ["round", "sharded"])
def test_two_clients_select_on_the_count_route(mode):
    """On two virtual devices, the count route (forced past the CPU's
    shortcut): in the two-client round each upload keeps the k of its tree
    and the server keeps what the flat oracle keeps of the clients' mean;
    over a tree sharded on the client axis (``manual_axes``: psum, pmax and
    pmin) the selection is the flat oracle's."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run(
        [sys.executable, "-c", TWO_CLIENTS.format(mode=mode)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    m = json.loads(proc.stdout.strip().splitlines()[-1])
    if mode == "round":
        assert m["nnz_up"] == m["k"] and m["msg_nnz"] == [m["k"]] * 2, m
        assert m["nnz_down"] == m["down_nnz"] == m["oracle_down"] >= m["k"], m
    else:
        assert m["nnz"] == m["oracle_nnz"] == m["k"], m
        assert m["thresh"] == m["oracle_thresh"], m
        assert m["mu_rel"] < 1e-6 and m["tern_err"] < 1e-6, m
