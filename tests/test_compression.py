"""Unit + property tests for the core STC compression operators."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.compression import (flatten_pytree, majority_vote_sign,
                                    sign_compress, stc_compress,
                                    stc_compress_pytree,
                                    top_k_mask, top_k_sparsify,
                                    unflatten_pytree)

jax.config.update("jax_platform_name", "cpu")


def _rand(n, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(n),
                       jnp.float32)


class TestTopK:
    def test_mask_keeps_largest(self):
        x = jnp.asarray([0.1, -5.0, 0.3, 2.0, -0.2])
        mask = top_k_mask(x, 2)
        assert list(np.asarray(mask)) == [False, True, False, True, False]

    def test_sparsify_exact_k(self):
        x = _rand(1000)
        out, stats = top_k_sparsify(x, 0.01)
        assert int(stats.nnz) == 10
        kept = np.flatnonzero(np.asarray(out))
        top = np.argsort(-np.abs(np.asarray(x)))[:10]
        assert set(kept) == set(top)
        # kept values unchanged
        np.testing.assert_allclose(np.asarray(out)[kept],
                                   np.asarray(x)[kept])

    def test_k_floor_one(self):
        x = _rand(5)
        out, stats = top_k_sparsify(x, 1e-9)  # np < 1 -> k = 1
        assert int(stats.nnz) == 1

    @given(st.integers(10, 500), st.floats(0.005, 0.5),
           st.integers(0, 2**31 - 1))
    @settings(max_examples=25, deadline=None)
    def test_property_topk(self, n, p, seed):
        x = _rand(n, seed)
        out, stats = top_k_sparsify(x, p)
        k = max(int(n * p), 1)
        assert int(stats.nnz) == k  # continuous data: ties measure-zero
        # every kept magnitude >= every dropped magnitude
        a = np.abs(np.asarray(x))
        o = np.asarray(out)
        kept_min = a[np.flatnonzero(o)].min()
        dropped = a[o == 0]
        if dropped.size:
            assert kept_min >= dropped.max() - 1e-7


class TestTernarize:
    def test_algorithm1(self):
        """Exact Algorithm 1 semantics on a hand-computed example."""
        x = jnp.asarray([3.0, -1.0, 0.5, -4.0, 0.1])
        out, stats = stc_compress(x, 0.4)  # k = 2 -> keep 3.0, -4.0
        mu = (3.0 + 4.0) / 2
        np.testing.assert_allclose(np.asarray(out),
                                   [mu, 0.0, 0.0, -mu, 0.0], rtol=1e-6)
        assert float(stats.mu) == pytest.approx(mu)

    @given(st.integers(20, 400), st.floats(0.01, 0.3),
           st.integers(0, 2**31 - 1))
    @settings(max_examples=25, deadline=None)
    def test_property_ternary_values(self, n, p, seed):
        x = _rand(n, seed)
        out, stats = stc_compress(x, p)
        o = np.asarray(out)
        mu = float(stats.mu)
        vals = np.unique(o)
        assert all(np.isclose(v, 0.0) or np.isclose(abs(v), mu, rtol=1e-5)
                   for v in vals)
        # sign preserved on kept entries
        kept = np.flatnonzero(o)
        assert np.all(np.sign(o[kept]) == np.sign(np.asarray(x)[kept]))
        # mu == mean magnitude of kept population of the INPUT
        np.testing.assert_allclose(mu, np.abs(np.asarray(x)[kept]).mean(),
                                   rtol=1e-5)

    def test_all_zero_input(self):
        out, stats = stc_compress(jnp.zeros(64), 0.1)
        assert float(jnp.sum(jnp.abs(out))) == 0.0


class TestSign:
    def test_sign_compress(self):
        x = jnp.asarray([1.5, -0.2, 0.0])
        out, _ = sign_compress(x, 0.01)
        np.testing.assert_allclose(np.asarray(out), [0.01, -0.01, 0.0])

    def test_majority_vote(self):
        s = jnp.asarray([[1.0, -1.0], [1.0, 1.0], [-1.0, -1.0]])
        out = majority_vote_sign(s, 0.5)
        np.testing.assert_allclose(np.asarray(out), [0.5, -0.5])


class TestPytree:
    def test_flatten_roundtrip(self):
        tree = {"a": _rand(10, 1).reshape(2, 5),
                "b": [_rand(3, 2), _rand(4, 3).astype(jnp.bfloat16)]}
        vec, spec = flatten_pytree(tree)
        back = unflatten_pytree(vec, spec)
        for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            assert x.dtype == y.dtype
            np.testing.assert_allclose(np.asarray(x, np.float32),
                                       np.asarray(y, np.float32), rtol=1e-2)

    def test_global_topk_across_leaves(self):
        """top-k must compete globally, not per-leaf."""
        tree = {"small": jnp.asarray([0.001, 0.002]),
                "big": jnp.asarray([10.0, 20.0, 30.0, 40.0])}
        out, stats = stc_compress_pytree(tree, 3 / 6)
        assert float(jnp.sum(jnp.abs(out["small"]))) == 0.0
        assert int(jnp.sum(out["big"] != 0)) == 3
