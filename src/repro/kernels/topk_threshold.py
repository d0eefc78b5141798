"""Pallas TPU kernel: threshold statistics for k-selection by bisection.

Global top-k over 10^6..10^10 gradient elements is the hot-spot of STC's
compression step.  A full sort (`jax.lax.top_k`) is MXU-hostile and O(n log n)
in VPU ops; instead we do TPU-friendly *k-selection by threshold bisection*:
each bisection round is one streaming pass that counts elements with
``|x| >= t`` (and sums their magnitudes, which the final round reuses as the
ternary µ numerator).

NOTE: bisection costs ``iters + 1`` (default 33) full streaming passes over
HBM per selection.  It is kept as (a) the reference selector and (b) the
rare-case fallback of the single-pass histogram selector in
:mod:`.hist_select`, which replaces it on the hot path (≤3 passes).

The kernel tiles the (padded, reshaped to (M, 128)) input into VMEM blocks of
``(block_rows, 128)`` and accumulates scalar partials across the sequential
TPU grid into (1, 1) SMEM outputs (same output for every grid step — the
canonical Pallas reduction pattern; VMEM cannot hold a scalar store).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._util import (DEFAULT_BLOCK_ROWS, LANE, PASSES, pad_2d,
                    resolve_block_rows, resolve_interpret)

__all__ = ["threshold_stats", "topk_threshold", "LANE", "DEFAULT_BLOCK_ROWS"]


def _stats_kernel(x_ref, t_ref, cnt_ref, sum_ref, *, block_rows: int, n: int):
    i = pl.program_id(0)
    x = x_ref[...]                       # (block_rows, LANE) fp32
    t = t_ref[0, 0]

    # global element index of each lane slot, to mask the zero padding
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    gidx = (i * block_rows + row) * LANE + col
    valid = gidx < n

    a = jnp.abs(x)
    m = (a >= t) & valid
    c = jnp.sum(m.astype(jnp.int32))
    s = jnp.sum(jnp.where(m, a, jnp.zeros_like(a)))

    @pl.when(i == 0)
    def _init():
        cnt_ref[0, 0] = jnp.zeros((), jnp.int32)
        sum_ref[0, 0] = jnp.zeros((), jnp.float32)

    cnt_ref[0, 0] += c
    sum_ref[0, 0] += s


def threshold_stats(
    x_flat: jnp.ndarray,
    thresh: jnp.ndarray,
    *,
    block_rows: int | None = None,
    interpret: bool | None = None,
):
    """(count, sum|x|) over entries of ``x_flat`` with ``|x| >= thresh``.

    x_flat: flat fp32 vector (any length); thresh: scalar fp32.
    """
    interpret = resolve_interpret(interpret)
    block_rows = resolve_block_rows(block_rows, interpret)
    PASSES.record("threshold_stats")
    n = x_flat.size
    x2 = pad_2d(x_flat.astype(jnp.float32), block_rows)
    m_rows = x2.shape[0]
    grid = (m_rows // block_rows,)
    t2 = thresh.reshape(1, 1).astype(jnp.float32)

    kernel = functools.partial(_stats_kernel, block_rows=block_rows, n=n)
    cnt, s = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x2, t2)
    return cnt[0, 0], s[0, 0]


def topk_threshold(
    x_flat: jnp.ndarray,
    k: int,
    *,
    iters: int = 32,
    block_rows: int | None = None,
    interpret: bool | None = None,
):
    """Bisection k-selection driving the stats kernel (``iters + 1`` passes).

    Returns ``(thresh, count, sum_abs)`` where ``count = #{|x| >= thresh} >= k``
    and ``sum_abs`` is the magnitude mass above the threshold (the µ numerator).
    """
    interpret = resolve_interpret(interpret)
    # fori_loop traces the body once; record the logical pass count explicitly
    # (iters bisection rounds; the final stats call records itself).
    PASSES.record("bisect_round", iters - 1)
    a_max = jnp.max(jnp.abs(x_flat)).astype(jnp.float32)
    hi0 = a_max * jnp.float32(1.0 + 1e-6) + jnp.float32(1e-30)
    lo0 = jnp.float32(0.0)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        cnt, _ = threshold_stats(
            x_flat, mid, block_rows=block_rows, interpret=interpret
        )
        keep = cnt >= k
        return jnp.where(keep, mid, lo), jnp.where(keep, hi, mid)

    lo, _ = jax.lax.fori_loop(0, iters, body, (lo0, hi0))
    cnt, s = threshold_stats(x_flat, lo, block_rows=block_rows,
                             interpret=interpret)
    return lo, cnt, s
