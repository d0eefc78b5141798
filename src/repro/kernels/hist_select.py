"""Pallas TPU kernel: single-pass histogram k-selection for STC.

Design note (histogram selection)
---------------------------------
Bisection k-selection (:mod:`.topk_threshold`) does 33 full streaming passes
over HBM per compression.  This module replaces it with a *one-pass* 256-bin
magnitude histogram:

1. ``a_max = max|x|``                                   (pass 1)
2. one streaming histogram pass accumulating per-bin (count, Σ|x|) with the
   canonical sequential-grid reduction; binning is linear on ``[0, a_max]``
   with ``bin = clip(int(|x| · 256/a_max), 0, 255)``     (pass 2)
3. a jnp top-inclusive cumulative sum locates the bin ``b`` holding the k-th
   largest magnitude and its within-bin rank ``r``; ONE refinement pass
   gathers the (typically n/256 ≪ n) candidates of bin ``b`` and reads the
   exact k-th magnitude out of the top-``cap`` candidates  (pass 3)

Total: ≤3 passes, and the selection is *exact* (identical mask to
``jax.lax.top_k``'s ``|x| >= v_k`` rule, ties included) whenever the candidate
bin holds at most ``cap`` elements.  On adversarial inputs that concentrate
>``cap`` elements into one bin (heavy ties at the threshold, extreme dynamic
range) a ``lax.cond`` falls back to an exact sort-based selection, so results
are exact on every input; the fallback never runs on well-scaled gradient
noise.  Per-bin sums let µ be assembled from the histogram (bins above ``b``)
plus the gathered candidates — no extra stats pass.

Backend note: the histogram is the *general* path and the TPU path (the
one-hot binning matmul rides the MXU).  On non-TPU backends the Pallas
interpreter adds ~256× vector-op amplification that a CPU cannot hide, while
XLA's native ``top_k`` streams the input once with an O(cap) heap — so when
``k <= cap`` (every realistic sparsity at CPU-simulation sizes) the selector
short-circuits to ONE direct top-k gather pass plus a rare tie-spill stats
pass: 1-2 passes, and ~4× faster than even the pure-jnp bisection at n=2^20.
Both routes honour the same exact-selection contract and the ≤3-pass budget.

The kernel computes the per-block histogram as a one-hot (elements × bins)
matmul — the MXU-friendly TPU histogram idiom — chunked over sub-blocks of
``chunk_rows`` rows to bound VMEM when compiled (interpret mode runs a single
full-block one-hot, which XLA:CPU fuses efficiently).

``magnitude_histogram_batched`` / ``hist_topk_threshold_batched`` add a
leading client axis (grid ``(client, block)``) so a federated round's
P-client selection is ONE kernel launch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.selection import (DEFAULT_CAP, NBINS, PASSES, bin_index,
                                  locate_bin, resolve_interpret)
from ._util import LANE, pad_3d, resolve_block_rows

__all__ = [
    "NBINS",
    "bin_index",
    "locate_bin",
    "DEFAULT_CAP",
    "magnitude_histogram",
    "magnitude_histogram_batched",
    "hist_topk_threshold",
    "hist_topk_threshold_batched",
]

SUB = 8  # sublanes per chunk: the kernel bins one (8, LANE) tile at a time


def magnitude_histogram(
    x_flat: jnp.ndarray,
    scale: jnp.ndarray,
    *,
    bins: int = NBINS,
    block_rows: int | None = None,
    interpret: bool | None = None,
):
    """One streaming pass -> per-bin ``(count, Σ|x|)`` with linear binning.

    ``scale`` is the precomputed ``bins / max|x|`` scalar (0 for an all-zero
    vector, putting everything in bin 0).  Returns ``(counts, sums)`` of shape
    ``(bins,)``.  Thin wrapper over the batched kernel with a client axis of 1.
    """
    cnt, s = magnitude_histogram_batched(
        x_flat.reshape(1, -1), scale.reshape(1), bins=bins,
        block_rows=block_rows, interpret=interpret)
    return cnt[0], s[0]


def _hist_kernel_batched(x_ref, scale_ref, cnt_ref, sum_ref, cnt_acc,
                         sum_acc, *, block_rows: int, n: int, bins: int):
    c, i = pl.program_id(0), pl.program_id(1)
    scale = scale_ref[c]

    @pl.when(i == 0)
    def _init():
        cnt_acc[...] = jnp.zeros(cnt_acc.shape, jnp.int32)
        sum_acc[...] = jnp.zeros(sum_acc.shape, jnp.float32)

    # per-(bin, sublane, lane) partials: the one-hot of each (SUB, LANE) tile
    # is added elementwise, so no reshape or cross-lane op runs per element
    bin_iota = jax.lax.broadcasted_iota(jnp.int32, (bins, SUB, LANE), 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (SUB, LANE), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (SUB, LANE), 1)

    def body(j, carry):
        r0 = pl.multiple_of(j * SUB, SUB)
        a = jnp.abs(x_ref[0, pl.ds(r0, SUB), :].astype(jnp.float32))
        valid = (i * block_rows + r0 + row) * LANE + col < n
        idx = jnp.where(valid, bin_index(a, scale, bins), bins)  # pad: no bin
        oh = idx[None] == bin_iota
        cnt_acc[...] += oh.astype(jnp.int32)
        sum_acc[...] += jnp.where(oh, a[None], 0.0)
        return carry

    jax.lax.fori_loop(0, block_rows // SUB, body, 0)

    @pl.when(i == pl.num_programs(1) - 1)
    def _emit():
        cnt_ref[0] = jnp.sum(jnp.sum(cnt_acc[...], axis=1), axis=1,
                             keepdims=True)
        sum_ref[0] = jnp.sum(jnp.sum(sum_acc[...], axis=1), axis=1,
                             keepdims=True)


def magnitude_histogram_batched(
    x: jnp.ndarray,
    scale: jnp.ndarray,
    *,
    bins: int = NBINS,
    block_rows: int | None = None,
    interpret: bool | None = None,
):
    """Batched histogram over a (clients, n) matrix -> (B, bins) each.

    ``scale``: (B,) per-client ``bins / max|x_b|``, read from SMEM.  One
    kernel launch with grid ``(client, block)`` instead of a vmap of
    per-client launches.
    """
    interpret = resolve_interpret(interpret)
    block_rows = resolve_block_rows(block_rows, interpret)
    assert block_rows % SUB == 0, block_rows
    PASSES.record("histogram")
    b, n = x.shape
    x3 = pad_3d(x, block_rows)
    grid = (b, x3.shape[1] // block_rows)

    kernel = functools.partial(_hist_kernel_batched, block_rows=block_rows,
                               n=n, bins=bins)
    cnt, s = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_rows, LANE), lambda c, i: (c, i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bins, 1), lambda c, i: (c, 0, 0)),
            pl.BlockSpec((1, bins, 1), lambda c, i: (c, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, bins, 1), jnp.int32),
            jax.ShapeDtypeStruct((b, bins, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bins, SUB, LANE), jnp.int32),
                        pltpu.VMEM((bins, SUB, LANE), jnp.float32)],
        interpret=interpret,
    )(x3, scale.reshape(b).astype(jnp.float32))
    return cnt[..., 0], s[..., 0]


# ---------------------------------------------------------------------------
# selection driver (histogram -> cumsum -> one refinement pass)
# ---------------------------------------------------------------------------


def hist_topk_threshold(
    x_flat: jnp.ndarray,
    k: int,
    *,
    bins: int = NBINS,
    cap: int = DEFAULT_CAP,
    block_rows: int | None = None,
    interpret: bool | None = None,
):
    """Exact k-selection in ≤3 streaming passes (histogram + refinement).

    Returns ``(thresh, count, sum_abs)`` with ``thresh`` the exact k-th
    largest magnitude (``count = #{|x| >= thresh} >= k``, ties included) and
    ``sum_abs`` the magnitude mass above the threshold (the µ numerator).
    Drop-in replacement for :func:`.topk_threshold.topk_threshold`.
    Thin wrapper over the batched driver with a client axis of 1.
    """
    t, cnt, sums = hist_topk_threshold_batched(
        x_flat.reshape(1, -1), k, bins=bins, cap=cap, block_rows=block_rows,
        interpret=interpret)
    return t[0], cnt[0], sums[0]


def _direct_topk_select_batched(a: jnp.ndarray, k, cap_eff: int):
    """Batched form of the non-TPU small-k shortcut (per-row tie-spill mix).

    ``k`` may be a scalar or a (B,) per-row vector (the chunked codecs give
    every (client, chunk) row its own k)."""
    _, n = a.shape
    k = jnp.asarray(k, jnp.int32).reshape(-1, 1)               # (1|B, 1)
    PASSES.record("topk_gather")                               # pass 1
    topc = jax.lax.top_k(a, cap_eff)[0]
    # masked-min instead of topc[:, k-1]: see _direct_topk_select
    v = jnp.min(jnp.where(jnp.arange(cap_eff)[None, :] < k, topc, jnp.inf),
                axis=1)
    ge = topc >= v[:, None]
    cnt_g = jnp.sum(ge.astype(jnp.int32), axis=1)
    sum_g = jnp.sum(jnp.where(ge, topc, 0.0), axis=1)
    spill = (cap_eff < n) & (jnp.min(topc, axis=1) >= v)

    def _from_gather(_):
        return v, cnt_g, sum_g

    def _tie_spill(_):                                         # rare pass 2
        m = a >= v[:, None]
        cnt_s = jnp.sum(m.astype(jnp.int32), axis=1)
        sum_s = jnp.sum(jnp.where(m, a, 0.0), axis=1)
        return (v, jnp.where(spill, cnt_s, cnt_g),
                jnp.where(spill, sum_s, sum_g))

    return jax.lax.cond(jnp.any(spill), _tie_spill, _from_gather, None)


def hist_topk_threshold_batched(
    x: jnp.ndarray,
    k,
    *,
    bins: int = NBINS,
    cap: int = DEFAULT_CAP,
    block_rows: int | None = None,
    interpret: bool | None = None,
):
    """Batched exact k-selection over (clients, n); same contract per row.

    ``k`` is static: an int shared by every row, or a (B,) array giving each
    row its own k (the chunked ``(layer, chunk)`` block path -- one launch
    selects every chunk of every client).  Returns ``(thresh, count,
    sum_abs)`` vectors of shape (B,).
    """
    bsz, n = x.shape
    k_arr = np.broadcast_to(np.asarray(k, np.int64), (bsz,))
    assert 1 <= int(k_arr.min(initial=1)) and int(k_arr.max(initial=1)) <= n, \
        (k, n)
    k_max = int(k_arr.max(initial=1))
    x = x.astype(jnp.float32)
    cap_eff = min(cap, n)
    interpret = resolve_interpret(interpret)

    if interpret and k_max <= cap_eff:  # non-TPU small-k shortcut: 1-2 passes
        return _direct_topk_select_batched(jnp.abs(x), k_arr, cap_eff)

    PASSES.record("max")                                       # pass 1
    a = jnp.abs(x)
    a_max = jnp.max(a, axis=1)
    scale = jnp.where(a_max > 0, jnp.float32(bins) / a_max, 0.0)

    kj = jnp.asarray(k_arr, jnp.int32)
    cnt, sums = magnitude_histogram_batched(                   # pass 2
        x, scale, bins=bins, block_rows=block_rows, interpret=interpret)
    b, cnt_gt, sum_gt, cnt_b = jax.vmap(
        lambda c, s, kk: locate_bin(c, s, kk, bins))(cnt, sums, kj)
    r = kj - cnt_gt

    PASSES.record("refine")                                    # pass 3
    in_bin = bin_index(a, scale[:, None], bins) == b[:, None]
    topc = jax.lax.top_k(jnp.where(in_bin, a, jnp.float32(-1.0)), cap_eff)[0]
    v = jnp.take_along_axis(topc, (r - 1)[:, None], axis=1)[:, 0]
    ge = (topc >= 0.0) & (topc >= v[:, None])
    cnt_ex = cnt_gt + jnp.sum(ge.astype(jnp.int32), axis=1)
    sum_ex = sum_gt + jnp.sum(jnp.where(ge, topc, 0.0), axis=1)

    overflow = cnt_b > cap_eff

    def _exact(_):
        return v, cnt_ex, sum_ex

    def _mixed(_):
        srt = jnp.sort(a, axis=1)
        vs = jnp.take_along_axis(srt, (n - kj)[:, None], axis=1)[:, 0]
        m = a >= vs[:, None]
        cnt_s = jnp.sum(m.astype(jnp.int32), axis=1)
        sum_s = jnp.sum(jnp.where(m, a, 0.0), axis=1)
        return (jnp.where(overflow, vs, v),
                jnp.where(overflow, cnt_s, cnt_ex),
                jnp.where(overflow, sum_s, sum_ex))

    return jax.lax.cond(jnp.any(overflow), _mixed, _exact, None)
