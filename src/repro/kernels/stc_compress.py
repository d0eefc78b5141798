"""Pallas TPU kernel: fused STC apply (mask → ternarize → error-feedback).

Naively, one STC round over the flat parameter vector does

    mask    = |carried| >= t  (read n)
    tern    = µ·sign·mask     (read n, write n)
    A'      = carried - tern  (read n, write n)

This kernel fuses everything into ONE pass: read ``carried`` once, write
``(T*, A')`` once — 3n fp32 HBM moves.  The caller threads the carried vector
``ΔW + A`` (already materialized by the k-selection step) straight through, so
the delta/residual pair is never re-read and the add never recomputed.
Inputs are tiled to (block_rows, 128) VMEM blocks; the threshold t and
magnitude µ are per-client scalars in SMEM, computed by the histogram selector
in :mod:`.hist_select` (or the bisection fallback in :mod:`.topk_threshold`).

``stc_apply_batched`` adds a leading client axis: grid ``(client, block)``
with per-client (t, µ) scalars, so compressing P participants is ONE kernel
launch instead of a vmap of P launches.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._util import (LANE, PASSES, pad_3d, resolve_block_rows,
                    resolve_interpret)

__all__ = ["stc_apply", "stc_apply_batched"]


def stc_apply(
    carried: jnp.ndarray,
    thresh: jnp.ndarray,
    mu: jnp.ndarray,
    *,
    block_rows: int | None = None,
    interpret: bool | None = None,
):
    """Fused  tern = µ·sign(carried)·[|carried| >= t];  A' = carried - tern.

    carried: flat fp32 vector (= ΔW + A); thresh/mu scalars.
    Returns ``(tern, new_residual)`` flat fp32 vectors of the input length.
    Thin wrapper over the batched kernel with a client axis of 1.
    """
    tern, res = stc_apply_batched(
        carried.reshape(1, -1), thresh.reshape(1), mu.reshape(1),
        block_rows=block_rows, interpret=interpret)
    return tern[0], res[0]


def _fused_kernel(c_ref, t_ref, mu_ref, tern_ref, res_ref,
                  *, block_rows: int, n: int):
    c, i = pl.program_id(0), pl.program_id(1)  # client, block within client
    carried = c_ref[0].astype(jnp.float32)     # (block_rows, LANE)
    t = t_ref[c]
    mu = mu_ref[c]

    row = jax.lax.broadcasted_iota(jnp.int32, carried.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, carried.shape, 1)
    gidx = (i * block_rows + row) * LANE + col
    valid = gidx < n

    m = (jnp.abs(carried) >= t) & valid
    tern = jnp.where(m, mu * jnp.sign(carried), jnp.zeros_like(carried))
    tern_ref[0] = tern
    res_ref[0] = carried - tern


def stc_apply_batched(
    carried: jnp.ndarray,
    thresh: jnp.ndarray,
    mu: jnp.ndarray,
    *,
    block_rows: int | None = None,
    interpret: bool | None = None,
):
    """Batched fused apply over a (clients, n) carried matrix.

    carried: (B, n) fp32; thresh/mu: (B,) per-client scalars.
    Returns ``(tern, new_residual)`` of shape (B, n).
    """
    interpret = resolve_interpret(interpret)
    block_rows = resolve_block_rows(block_rows, interpret)
    PASSES.record("stc_apply")
    b, n = carried.shape
    c3 = pad_3d(carried, block_rows)
    grid = (b, c3.shape[1] // block_rows)

    kernel = functools.partial(_fused_kernel, block_rows=block_rows,
                               n=n)
    tern, res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_rows, LANE), lambda c, i: (c, i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_rows, LANE), lambda c, i: (c, i, 0)),
            pl.BlockSpec((1, block_rows, LANE), lambda c, i: (c, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(c3.shape, jnp.float32),
            jax.ShapeDtypeStruct(c3.shape, jnp.float32),
        ],
        interpret=interpret,
    )(c3, thresh.reshape(b).astype(jnp.float32),
      mu.reshape(b).astype(jnp.float32))
    return tern.reshape(b, -1)[:, :n], res.reshape(b, -1)[:, :n]
