"""Pure-jnp building blocks of histogram k-selection, shared across layers.

These helpers are used both by the Pallas kernels (:mod:`repro.kernels`) and
by the tree-level compressor (:mod:`repro.core.distributed`).  They live here
— below the kernels — so that core modules never import
``jax.experimental.pallas``: the layering is kernels -> core, never the
reverse (see the lazy "kernel" backend lookup in :mod:`.compression`).

* ``bin_index`` / ``locate_bin`` -- the 256-bin linear magnitude binning and
  the cumulative-sum bin/rank search of the histogram selector.  The binning
  expression MUST stay bit-identical everywhere it is evaluated (histogram
  kernel, refinement pass, tree sweep), so there is exactly one definition.
* ``resolve_interpret`` -- backend autodetect for the kernels' ``interpret``
  flag: compiled on a TPU, interpreted on the CPU backend, and an error on
  any other backend (a kernel never silently interprets off the CPU).
* ``PASSES`` -- trace-time streaming-pass counter: every logical full sweep
  over the data records itself here, and tests assert the histogram selector
  stays within its ≤3-pass budget where bisection spends 33.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["NBINS", "DEFAULT_CAP", "bin_index", "locate_bin",
           "resolve_interpret", "PASSES", "PassCounter"]

NBINS = 256         # histogram bins (one-hot matmul lane group on TPU)
DEFAULT_CAP = 8192  # static refinement-gather capacity (candidate bin size)


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> compiled on a TPU, interpreted on the CPU backend.

    Any other backend raises: the Pallas kernels here are written for the
    TPU, and interpreting them on an accelerator would hide the device.
    """
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels compile for 'tpu' and interpret on 'cpu'; the "
        f"default backend is {backend!r} (pass interpret= explicitly)")


def bin_index(a: jnp.ndarray, scale: jnp.ndarray, bins: int) -> jnp.ndarray:
    """Linear magnitude binning; MUST be bit-identical everywhere it is used
    (histogram kernel, refinement pass, tree path)."""
    return jnp.clip((a * scale).astype(jnp.int32), 0, bins - 1)


def locate_bin(cnt, sums, k, bins):
    """Candidate bin + above-bin partials from a (bins,) histogram."""
    rc = jnp.cumsum(cnt[::-1])[::-1]             # rc[j] = #{bin >= j}
    rs = jnp.cumsum(sums[::-1])[::-1]
    iota = jnp.arange(bins, dtype=jnp.int32)
    b = jnp.max(jnp.where(rc >= k, iota, -1))    # largest bin with rc >= k
    rc_pad = jnp.concatenate([rc, jnp.zeros((1,), rc.dtype)])
    rs_pad = jnp.concatenate([rs, jnp.zeros((1,), rs.dtype)])
    cnt_gt = jnp.take(rc_pad, b + 1, mode="clip")
    sum_gt = jnp.take(rs_pad, b + 1, mode="clip")
    cnt_b = jnp.take(cnt, b, mode="clip")
    return b, cnt_gt, sum_gt, cnt_b


class PassCounter:
    """Counts logical streaming passes over the full input vector.

    Recording happens at Python level (trace time under jit, every call when
    eager), so tests exercise the un-jitted selection functions directly.
    """

    def __init__(self):
        self.counts: dict[str, int] = {}

    def reset(self) -> None:
        self.counts.clear()

    def record(self, name: str, n: int = 1) -> None:
        self.counts[name] = self.counts.get(name, 0) + n

    def total(self) -> int:
        return sum(self.counts.values())


PASSES = PassCounter()
