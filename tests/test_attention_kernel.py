"""The Pallas flash kernel of the causal GQA self-attention
(``kernels/attention.py``, interpreted on the CPU) against the jnp scan of
``models/flash.py``, and the rule by which ``models/attention._attention``
takes it: every other shape keeps the jnp scan bit for bit."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, AxisType

import repro.models.attention as attention
from repro.kernels.attention import causal_gqa_attention, fits_mesh
from repro.models.flash import flash_attention

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HD = 64


def _qkv(b, sq, skv, h, kv, hd=HD, hdv=None, dtype=jnp.bfloat16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, sq, h, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (b, skv, kv, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (b, skv, kv, hdv or hd)).astype(dtype)
    do = jax.random.normal(ks[3], (b, sq, h, hdv or hd)).astype(dtype)
    return q, k, v, do


def _scan(q, k, v):
    return flash_attention(q, k, v, True, 0, 1024)


def _value_and_grads(f, q, k, v, do):
    out, pullback = jax.vjp(f, q, k, v)
    return out, pullback(do)


def _gaps(got, want):
    """Forward max abs gap; each gradient's relative gap in norm."""
    (o, g), (o_ref, g_ref) = got, want
    f32 = lambda x: np.asarray(x, np.float32)
    fwd = float(np.max(np.abs(f32(o) - f32(o_ref))))
    rel = [float(np.linalg.norm(f32(a) - f32(r)) / np.linalg.norm(f32(r)))
           for a, r in zip(g, g_ref)]
    return fwd, rel


@pytest.mark.parametrize("s", [256, 384])
@pytest.mark.parametrize("h,kv", [(9, 3), (14, 2)])
def test_kernel_matches_the_jnp_scan(h, kv, s):
    """bf16 inputs, causal: the kernel's output within about one bf16 ulp
    of the scan's, and its q, k and v gradients within 1%."""
    q, k, v, do = _qkv(2, s, s, h, kv)
    fwd, rel = _gaps(_value_and_grads(causal_gqa_attention, q, k, v, do),
                     _value_and_grads(_scan, q, k, v, do))
    assert fwd <= 4e-3
    assert max(rel) <= 1e-2, rel


@pytest.fixture
def kernel_calls(monkeypatch):
    """Counts the calls ``_attention`` makes to the kernel."""
    calls = []

    def spy(q, k, v):
        calls.append(q.shape)
        return causal_gqa_attention(q, k, v)

    monkeypatch.setattr(attention, "causal_gqa_attention", spy)
    return calls


def _on_tpu(monkeypatch):
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)


# (args of _qkv, causal, window); the TPU condition is stubbed in all but
# the last case
JNP_CASES = {
    "window": ((1, 256, 256, 9, 3), True, 64),
    "cross": ((1, 256, 384, 9, 3), False, 0),
    "hd_neq_hdv": ((1, 256, 256, 4, 2, 192, 128), True, 0),
    "s_not_128": ((1, 200, 200, 9, 3), True, 0),
    "float32": ((1, 256, 256, 9, 3, HD, None, jnp.float32), True, 0),
    "not_causal": ((1, 256, 256, 9, 3), False, 0),
    "cpu": ((1, 256, 256, 9, 3), True, 0),
}


@pytest.mark.parametrize("case", list(JNP_CASES))
def test_other_shapes_keep_the_jnp_scan(case, kernel_calls, monkeypatch):
    args, causal, window = JNP_CASES[case]
    if case != "cpu":
        _on_tpu(monkeypatch)
    q, k, v, _ = _qkv(*args)
    got = attention._attention(q, k, v, causal=causal, window=window)
    want = flash_attention(q, k, v, causal, window, 1024)
    assert kernel_calls == []
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_smollm_heads_take_the_kernel(kernel_calls, monkeypatch):
    """smollm-135m's head layout (9 query heads of 64 over 3 kv heads) at a
    128-multiple length, causal bf16 self-attention on a stubbed TPU."""
    _on_tpu(monkeypatch)
    q, k, v, _ = _qkv(2, 256, 256, 9, 3)
    got = attention._attention(q, k, v, causal=True)
    assert kernel_calls == [q.shape]
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(causal_gqa_attention(q, k, v)))


def test_a_partitioned_axis_keeps_the_jnp_scan(kernel_calls, monkeypatch):
    """A mesh axis of size 2 left to the partitioner, which cannot split a
    Mosaic kernel: the jnp scan; axes of size 1 do not stop the kernel."""
    _on_tpu(monkeypatch)
    q, k, v, _ = _qkv(1, 256, 256, 9, 3)
    assert fits_mesh()
    one = AbstractMesh((1, 1), ("data", "model"), (AxisType.Auto,) * 2)
    two = AbstractMesh((1, 2), ("data", "model"), (AxisType.Auto,) * 2)
    with jax.sharding.use_abstract_mesh(one):
        assert fits_mesh()
        assert attention._takes_kernel(q, k, v, True, 0)
    with jax.sharding.use_abstract_mesh(two):
        assert not fits_mesh()
        assert not attention._takes_kernel(q, k, v, True, 0)
    assert kernel_calls == []


UNDER_THE_ROUND_MESH = r"""
import json, sys
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.kernels.attention import causal_gqa_attention
from repro.models.flash import flash_attention

mesh = jax.make_mesh((2, 1), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
ks = jax.random.split(jax.random.PRNGKey(1), 4)
q = jax.random.normal(ks[0], (4, 256, 9, 64)).astype(jnp.bfloat16)
k = jax.random.normal(ks[1], (4, 256, 3, 64)).astype(jnp.bfloat16)
v = jax.random.normal(ks[2], (4, 256, 3, 64)).astype(jnp.bfloat16)
do = jax.random.normal(ks[3], (4, 256, 9, 64)).astype(jnp.bfloat16)

def grads(f):
    def body(q, k, v, do):
        out, pullback = jax.vjp(f, q, k, v)
        return out, pullback(do)
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("data"),) * 4,
                                 out_specs=P("data"), axis_names={{"data"}},
                                 check_vma=False))

with jax.set_mesh(mesh):
    got = grads(causal_gqa_attention)(q, k, v, do)
    want = grads(lambda q, k, v: flash_attention(q, k, v, True, 0, 1024))(
        q, k, v, do)
f32 = lambda x: np.asarray(x, np.float32)
fwd = float(np.max(np.abs(f32(got[0]) - f32(want[0]))))
rel = [float(np.linalg.norm(f32(a) - f32(b)) / np.linalg.norm(f32(b)))
       for a, b in zip(got[1], want[1])]
print(json.dumps({{"fwd": fwd, "rel": rel}}))
"""


def test_kernel_under_the_round_mesh():
    """Two clients on ``data`` (manual, as in the round's ``shard_map``)
    with ``model`` of size 1 left to the partitioner: the kernel runs under
    a fully manual ``shard_map`` and each client's values and gradients are
    its own."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    res = subprocess.run(
        [sys.executable, "-c",
         UNDER_THE_ROUND_MESH.format(src=os.path.join(REPO, "src"))],
        env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    gaps = json.loads(res.stdout.strip().splitlines()[-1])
    assert gaps["fwd"] <= 4e-3
    assert max(gaps["rel"]) <= 1e-2, gaps
