"""Histogram k-selection: exactness vs the lax.top_k oracle on adversarial
inputs, batched-vs-per-client equivalence, streaming-pass budget, and the
three-way (kernel / jnp operator / tree) oracle agreement."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.compression import flatten_pytree, stc_compress
from repro.core.distributed import stc_compress_tree
from repro.kernels import (PASSES, hist_topk_threshold,
                           hist_topk_threshold_batched, magnitude_histogram,
                           magnitude_histogram_batched, stc_compress_batch,
                           stc_compress_kernel, topk_threshold)
from repro.kernels import ref as kref

jax.config.update("jax_platform_name", "cpu")

SHAPES = [64, 1000, 4096, 100_003]   # incl. n not a multiple of block*128


def _rand(n, seed=0, scale=1.0):
    x = np.random.default_rng(seed).standard_normal(n) * scale
    return jnp.asarray(x, jnp.float32)


def _sort_oracle(x, k):
    """(v_k, count, sum) with lax.top_k semantics: mask = |x| >= kth value."""
    a = np.abs(np.asarray(x, np.float32))
    vk = np.sort(a)[-k]
    m = a >= vk
    return vk, int(m.sum()), float(a[m].sum())


class TestHistogramKernel:
    @pytest.mark.parametrize("n", SHAPES)
    def test_vs_ref(self, n):
        x = _rand(n, seed=n)
        a_max = jnp.max(jnp.abs(x))
        scale = jnp.float32(256.0) / a_max
        cnt_k, sum_k = magnitude_histogram(x, scale, block_rows=64)
        cnt_r, sum_r = kref.magnitude_histogram_ref(x, scale)
        np.testing.assert_array_equal(np.asarray(cnt_k), np.asarray(cnt_r))
        np.testing.assert_allclose(np.asarray(sum_k), np.asarray(sum_r),
                                   rtol=1e-5)
        assert int(jnp.sum(cnt_k)) == n   # padding must not leak into bin 0

    def test_batched_vs_single(self):
        xs = jnp.stack([_rand(4096, seed=i, scale=1 + i) for i in range(4)])
        a_max = jnp.max(jnp.abs(xs), axis=1)
        scale = jnp.float32(256.0) / a_max
        cnt_b, sum_b = magnitude_histogram_batched(xs, scale, block_rows=16)
        for i in range(4):
            cnt_i, sum_i = magnitude_histogram(xs[i], scale[i], block_rows=16)
            np.testing.assert_array_equal(np.asarray(cnt_b[i]),
                                          np.asarray(cnt_i))
            np.testing.assert_allclose(np.asarray(sum_b[i]),
                                       np.asarray(sum_i), rtol=1e-5)


class TestBlockHistChunking:
    def test_chunked_equals_single_shot(self):
        """The kernel bins one (8, 128) tile at a time and carries its
        partials across blocks: one tile per block, many tiles per block and
        the whole input in one block must all equal the one-shot oracle."""
        x = _rand(64 * 128 - 77, seed=9)
        scale = jnp.float32(256.0) / jnp.max(jnp.abs(x))
        cnt_r, sum_r = kref.magnitude_histogram_ref(x, scale)
        for block_rows in (8, 16, 64):
            cnt, sums = magnitude_histogram(x, scale, block_rows=block_rows)
            np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_r))
            np.testing.assert_allclose(np.asarray(sums), np.asarray(sum_r),
                                       rtol=1e-5)


class TestExactSelection:
    @pytest.mark.parametrize("n", SHAPES)
    @pytest.mark.parametrize("p", [0.001, 0.01, 0.1])
    def test_matches_sort_oracle(self, n, p):
        x = _rand(n, seed=n + int(p * 1e4))
        k = max(int(n * p), 1)
        t, cnt, s = hist_topk_threshold(x, k, block_rows=64)
        vk, cnt_o, sum_o = _sort_oracle(x, k)
        assert np.float32(t) == np.float32(vk)   # EXACT kth magnitude
        assert int(cnt) == cnt_o
        np.testing.assert_allclose(float(s), sum_o, rtol=1e-5)

    @pytest.mark.parametrize("cap", [64, 8192])
    def test_heavy_ties_at_threshold(self, cap):
        """Half the entries tie at the kth magnitude: mask must keep all ties
        (lax.top_k >= semantics), through the exact path AND the overflow
        fallback (cap=64 forces it)."""
        rng = np.random.default_rng(0)
        n, k = 4000, 100
        vals = np.where(rng.random(n) < 0.5, 1.0,
                        rng.uniform(0.0, 0.5, n)).astype(np.float32)
        x = jnp.asarray(vals * np.sign(rng.standard_normal(n)))
        t, cnt, s = hist_topk_threshold(x, k, block_rows=8, cap=cap)
        vk, cnt_o, sum_o = _sort_oracle(x, k)
        assert np.float32(t) == np.float32(vk) == np.float32(1.0)
        assert int(cnt) == cnt_o == int((vals == 1.0).sum())
        np.testing.assert_allclose(float(s), sum_o, rtol=1e-5)

    def test_all_zero_vector(self):
        x = jnp.zeros(5000, jnp.float32)
        t, cnt, s = hist_topk_threshold(x, 50, block_rows=8)
        assert float(t) == 0.0 and float(s) == 0.0
        tern, res, mu, _, _ = stc_compress_kernel(x, x, 0.01, block_rows=8)
        assert float(mu) == 0.0
        np.testing.assert_array_equal(np.asarray(tern), 0.0)
        np.testing.assert_array_equal(np.asarray(res), 0.0)

    def test_extreme_dynamic_range(self):
        """Magnitudes spanning 1e-30..1e30 concentrate nearly everything in
        histogram bin 0; selection must stay exact via the fallback."""
        rng = np.random.default_rng(7)
        n = 20_000
        mags = 10.0 ** rng.uniform(-30, 30, n)
        x = jnp.asarray(mags * np.sign(rng.standard_normal(n)), jnp.float32)
        for k in (37, 5000, 19_000):
            t, cnt, s = hist_topk_threshold(x, k, block_rows=16)
            vk, cnt_o, sum_o = _sort_oracle(x, k)
            assert np.float32(t) == np.float32(vk), k
            assert int(cnt) == cnt_o, k
            np.testing.assert_allclose(float(s), sum_o, rtol=1e-4)

    def test_single_spike(self):
        """k=1 with one dominant value."""
        x = jnp.zeros(3000, jnp.float32).at[1234].set(-7.5)
        t, cnt, _ = hist_topk_threshold(x, 1, block_rows=8)
        assert float(t) == 7.5 and int(cnt) == 1


class TestBatchedSelection:
    def test_batched_vs_per_client(self):
        """One (client, block)-grid launch == independent per-client calls."""
        rng = np.random.default_rng(3)
        B, n, k = 6, 4096, 41
        xs = jnp.asarray(rng.standard_normal((B, n)) *
                         (1 + np.arange(B))[:, None], jnp.float32)
        tb, cb, sb = hist_topk_threshold_batched(xs, k, block_rows=16)
        for i in range(B):
            ti, ci, si = hist_topk_threshold(xs[i], k, block_rows=16)
            assert np.float32(tb[i]) == np.float32(ti)
            assert int(cb[i]) == int(ci)
            np.testing.assert_allclose(float(sb[i]), float(si), rtol=1e-5)

    def test_batched_mixed_overflow(self):
        """Rows that overflow the gather cap (ties) next to rows that don't:
        the per-row fallback mix must stay exact for every row."""
        rng = np.random.default_rng(4)
        n, k = 3000, 64
        tied = np.where(rng.random(n) < 0.5, 2.0,
                        rng.uniform(0, 1, n)).astype(np.float32)
        smooth = rng.standard_normal(n).astype(np.float32)
        xs = jnp.asarray(np.stack([tied, smooth]))
        tb, cb, sb = hist_topk_threshold_batched(xs, k, block_rows=8, cap=128)
        for i in range(2):
            vk, cnt_o, sum_o = _sort_oracle(xs[i], k)
            assert np.float32(tb[i]) == np.float32(vk), i
            assert int(cb[i]) == cnt_o, i
            np.testing.assert_allclose(float(sb[i]), sum_o, rtol=1e-5)

    def test_compress_batch_vs_single(self):
        rng = np.random.default_rng(5)
        B, n = 4, 8192
        ds = jnp.asarray(rng.standard_normal((B, n)), jnp.float32)
        rs = jnp.asarray(rng.standard_normal((B, n)) * 0.1, jnp.float32)
        tb, rb, mb, thb, cb = stc_compress_batch(ds, rs, 0.01, block_rows=16)
        for i in range(B):
            ti, ri, mi, thi, ci = stc_compress_kernel(ds[i], rs[i], 0.01,
                                                      block_rows=16)
            np.testing.assert_allclose(np.asarray(tb[i]), np.asarray(ti),
                                       atol=1e-6)
            np.testing.assert_allclose(np.asarray(rb[i]), np.asarray(ri),
                                       atol=1e-6)
            assert int(cb[i]) == int(ci)


class TestStreamingPassBudget:
    """Acceptance: ≤3 streaming passes per selection vs 33 for bisection."""

    def test_hist_passes(self):
        x = _rand(65_536, seed=11)
        PASSES.reset()
        hist_topk_threshold(x, 655, block_rows=64)
        assert PASSES.total() <= 3, PASSES.counts
        # on CPU the small-k shortcut does it in ONE gather pass
        assert PASSES.counts == {"topk_gather": 1}

    def test_hist_passes_general_path(self):
        """cap < k forces the histogram route: exactly max+histogram+refine."""
        x = _rand(65_536, seed=15)
        PASSES.reset()
        t, cnt, _ = hist_topk_threshold(x, 655, block_rows=64, cap=64)
        assert PASSES.counts == {"max": 1, "histogram": 1, "refine": 1}
        vk, cnt_o, _ = _sort_oracle(x, 655)
        assert np.float32(t) == np.float32(vk) and int(cnt) == cnt_o

    def test_hist_batched_passes(self):
        xs = jnp.stack([_rand(8192, seed=i) for i in range(3)])
        PASSES.reset()
        hist_topk_threshold_batched(xs, 81, block_rows=16)
        assert PASSES.total() <= 3, PASSES.counts

    def test_bisect_passes(self):
        x = _rand(65_536, seed=12)
        PASSES.reset()
        topk_threshold(x, 655, block_rows=64)
        assert PASSES.total() == 33, PASSES.counts

    def test_tree_passes(self):
        tree = {"w": _rand(65_536, seed=13), "b": _rand(1000, seed=14)}
        PASSES.reset()
        stc_compress_tree(tree, 0.01)
        assert PASSES.total() <= 3, PASSES.counts


class TestTreeForcedPaths:
    """On CPU a default-cap tree call takes the small-k shortcut; a ``cap``
    below k skips it, so the count route the TPU always takes (max sweep,
    ``iters`` count sweeps, one final count) stays covered."""

    def _tree(self, seed=21):
        rng = np.random.default_rng(seed)
        return {
            "w": jnp.asarray(rng.standard_normal(100_000), jnp.float32),
            "b": jnp.asarray(rng.standard_normal((40, 25)), jnp.float32),
        }

    def test_histogram_refine_branch(self):
        """cap < k skips the shortcut: the count route runs and is exact."""
        tree = self._tree()
        p = 0.02                                  # k = 2020 > cap
        PASSES.reset()
        tern_t, st = stc_compress_tree(tree, p, cap=1000)
        assert PASSES.counts == {"max": 1, "count": 33}
        vec, _ = flatten_pytree(tree)
        tern_j, stats_j = stc_compress(vec, p)
        got, _ = flatten_pytree(tern_t)
        assert int(st.nnz) == int(stats_j.nnz)
        np.testing.assert_allclose(float(st.mu), float(stats_j.mu), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(tern_j),
                                   atol=1e-6)

    def test_bisection_fallback_branch(self):
        """cap tiny: the same count route, exact."""
        tree = self._tree(22)
        p = 0.02
        PASSES.reset()
        tern_t, st = stc_compress_tree(tree, p, cap=8)
        assert PASSES.counts == {"max": 1, "count": 33}
        vec, _ = flatten_pytree(tree)
        tern_j, stats_j = stc_compress(vec, p)
        got, _ = flatten_pytree(tern_t)
        assert int(st.nnz) == int(stats_j.nnz)
        np.testing.assert_allclose(np.asarray(got), np.asarray(tern_j),
                                   atol=1e-6)


class TestTreeChipBranch:
    """The selection branch ``stc_compress_tree`` takes on a TPU, run here by
    making ``resolve_interpret`` report the chip: the count route, whatever
    the cap.  It must find lax.top_k's k-th magnitude."""

    @pytest.fixture
    def on_chip(self, monkeypatch):
        import repro.core.distributed as dist
        monkeypatch.setattr(dist, "resolve_interpret", lambda _: False)

    def _tree(self, seed):
        rng = np.random.default_rng(seed)
        return {"w": jnp.asarray(rng.standard_normal((300, 200)), jnp.float32),
                "b": jnp.asarray(rng.standard_normal(5000) * 3, jnp.float32)}

    @pytest.mark.parametrize("seed", [8192, 8])
    def test_matches_top_k(self, on_chip, seed):
        tree = self._tree(seed)
        p = 0.01
        vec, _ = flatten_pytree(tree)
        k = max(int(vec.size * p), 1)
        PASSES.reset()
        tern_t, st = stc_compress_tree(tree, p)
        assert PASSES.counts == {"max": 1, "count": 33}
        want = jax.lax.top_k(jnp.abs(vec), k)[0][k - 1]
        assert np.float32(st.thresh) == np.float32(want)
        tern_j, stats_j = stc_compress(vec, p)
        got, _ = flatten_pytree(tern_t)
        assert int(st.nnz) == int(stats_j.nnz)
        np.testing.assert_allclose(float(st.mu), float(stats_j.mu), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(tern_j),
                                   atol=1e-6)


class TestThreeWayOracle:
    """Acceptance: kernel path, stc_compress (jnp), and stc_compress_tree
    agree on (masked nnz, µ, ternary output) on randomized pytrees."""

    @pytest.mark.parametrize("seed", [0, 1, 2])
    @pytest.mark.parametrize("p", [0.005, 0.02, 0.1])
    def test_agreement(self, seed, p):
        rng = np.random.default_rng(seed)
        tree = {
            "w": jnp.asarray(rng.standard_normal((129, 33)), jnp.float32),
            "layers": [jnp.asarray(rng.standard_normal(517), jnp.float32),
                       jnp.asarray(rng.standard_normal((3, 111)) * 5,
                                   jnp.float32)],
        }
        vec, _ = flatten_pytree(tree)

        tern_j, stats_j = stc_compress(vec, p)
        tern_k, _, mu_k, _, nnz_k = stc_compress_kernel(
            vec, jnp.zeros_like(vec), p, block_rows=8)
        tern_t, stats_t = stc_compress_tree(tree, p)
        tern_t_flat, _ = flatten_pytree(tern_t)

        assert int(nnz_k) == int(stats_j.nnz) == int(stats_t.nnz)
        np.testing.assert_allclose(float(mu_k), float(stats_j.mu), rtol=1e-5)
        np.testing.assert_allclose(float(stats_t.mu), float(stats_j.mu),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(tern_k), np.asarray(tern_j),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(tern_t_flat),
                                   np.asarray(tern_j), atol=1e-6)
