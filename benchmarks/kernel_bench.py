"""STC compression micro-benchmarks: kernel path (interpret=True reference
timing on CPU -- the TPU numbers come from the roofline, not wall-clock) and
the pure-jnp operator path, plus the no-flatten tree path used by the
distributed train_step.

Rows (n = flat update length):
  stc_jnp_topk      -- core operator, lax.top_k sort path
  stc_bisect_ref    -- pure-jnp 33-pass bisection oracle
  stc_pallas_interp -- OLD kernel path: 33-pass bisection selection
  stc_hist          -- NEW selector path (≤3 passes; on CPU this times the
                       small-k top_k shortcut, not the Pallas histogram —
                       the histogram kernel itself only pays off on TPU)
  stc_hist_batch8   -- batched (client, block)-grid path over 8 clients of
                       the SAME n; TOTAL launch time, /8 for per-client
  stc_tree          -- no-flatten tree path (count bisection past the
                       small-k shortcut)
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression import stc_compress
from repro.core.distributed import stc_compress_tree
from repro.kernels import (stc_compress_batch, stc_compress_kernel,
                           stc_compress_ref)


def _timeit(fn, *args, iters=5):
    fn(*args)  # compile
    jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e6 * (time.time() - t0) / iters


def run(verbose=True):
    rows = []
    rng = np.random.default_rng(0)
    for n in (1 << 16, 1 << 20):
        d = jnp.asarray(rng.standard_normal(n), jnp.float32)
        r = jnp.asarray(rng.standard_normal(n) * 0.1, jnp.float32)

        us = _timeit(lambda a, b: stc_compress(a + b, 1 / 400)[0], d, r)
        rows.append((f"stc_jnp_topk/n{n}", us, "lax.top_k sort path"))

        us = _timeit(lambda a, b: stc_compress_ref(a, b, 1 / 400)[0], d, r)
        rows.append((f"stc_bisect_ref/n{n}", us, "bisection oracle"))

        us = _timeit(
            lambda a, b: stc_compress_kernel(a, b, 1 / 400,
                                             selector="bisect")[0], d, r)
        rows.append((f"stc_pallas_interp/n{n}", us,
                     "33-pass bisection (CPU reference, not TPU perf)"))

        us = _timeit(
            lambda a, b: stc_compress_kernel(a, b, 1 / 400)[0], d, r)
        rows.append((f"stc_hist/n{n}", us,
                     "<=3-pass hist selector (CPU: small-k top_k shortcut)"))

        bsz = 8
        db = jnp.asarray(rng.standard_normal((bsz, n)), jnp.float32)
        rb = jnp.asarray(rng.standard_normal((bsz, n)) * 0.1, jnp.float32)
        us = _timeit(
            lambda a, b: stc_compress_batch(a, b, 1 / 400)[0], db, rb)
        rows.append((f"stc_hist_batch{bsz}/n{n}", us,
                     f"batched client axis, one launch, total for {bsz}"
                     " clients of n"))

        tree = {"a": d.reshape(-1, 256), "b": r}
        tree_fn = jax.jit(lambda t: stc_compress_tree(t, 1 / 400,
                                                      numel=2 * n)[0]["a"])
        us = _timeit(tree_fn, tree)
        rows.append((f"stc_tree/n{2*n}", us, "no-flatten train_step path"))
    if verbose:
        for row in rows:
            print(f"{row[0]},{row[1]:.1f},{row[2]}")
    return rows


if __name__ == "__main__":
    run()
