"""k-selection accuracy contracts on the tree path.

Past the CPU's small-k shortcut (k > ``cap``, and always on the TPU) the
tree selection is a bisection of ``iters`` count sweeps, so ``iters`` sets
its accuracy (the §Perf A3 contract): 12 rounds keep the selected count
within 1% of k on Gaussian-like updates; 32 rounds are exact.
"""

import jax.numpy as jnp
import numpy as np

from repro.core.distributed import stc_compress_tree


def test_histogram_selection_exact():
    rng = np.random.default_rng(0)
    tree = {"w": jnp.asarray(rng.standard_normal(500_000), jnp.float32)}
    k = max(int(500_000 / 400), 1)
    _, st = stc_compress_tree(tree, 1 / 400)
    assert int(st.nnz) == k


def test_bisection_fallback_iteration_accuracy():
    rng = np.random.default_rng(0)
    tree = {"w": jnp.asarray(rng.standard_normal(500_000), jnp.float32)}
    k = max(int(500_000 / 400), 1)
    # k = 1250 <= the default cap would take the CPU's top-k shortcut;
    # cap=8 routes to the count bisection with the given iters budget
    _, st32 = stc_compress_tree(tree, 1 / 400, iters=32, cap=8)
    _, st12 = stc_compress_tree(tree, 1 / 400, iters=12, cap=8)
    assert int(st32.nnz) == k
    assert abs(int(st12.nnz) - k) / k < 0.01
