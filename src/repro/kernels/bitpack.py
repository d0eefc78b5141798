"""Pallas TPU kernel: bitstream word packing for the wire-format subsystem.

The vectorized Golomb encoder (:mod:`repro.core.wire`) reduces a sparse
ternary message to a dense 0/1 bit tensor (or to (value, length) chunks that
expand into one); the remaining dense work -- assembling 32 consecutive
stream bits into each uint32 word -- is exactly the kind of regular,
reduction-over-a-minor-axis computation the VPU eats:

    word[w] = sum_j bits[32w + j] << (31 - j)

The host lays the bit tensor out as ``(32, rows, LANE)`` with word
``r * LANE + c`` owning column ``[:, r, c]``, so each grid step reads a
``(32, block_rows, LANE)`` block and writes a ``(block_rows, LANE)`` uint32
block: the shift-and-sum runs over the leading 32-axis, lanes stay 128-wide,
and the summands are disjoint powers of two (no carries), so an integer sum
IS the bitwise OR.  The TPU reduces only signed integers, so the kernel sums
in int32 (bit 31 is the sign bit, and with no carries nothing wraps) and the
words are bitcast to uint32 outside it.

``pack_bits_words_batched`` reduces a uniform-length ``(B, nbits)`` batch to
ONE launch by word-aligning each row and flattening -- per-row word slices
of the result are exact because rows are padded to whole words.

Like every kernel in this package, ``interpret=None`` autodetects the
backend (compiled on TPU, interpreter elsewhere), and the pure-jnp
``pack_bits_ref`` oracle is exported for the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._util import LANE, PASSES, _cdiv, resolve_interpret

__all__ = ["pack_bits_words", "pack_bits_words_batched", "pack_bits_ref"]

# words per VMEM block: 32*block_rows*128 input bits (int32) = 2 MiB at 128
DEFAULT_BLOCK_ROWS = 32
INTERPRET_BLOCK_ROWS = 1024


def _resolve_rows(block_rows: int | None, interpret: bool) -> int:
    if block_rows is not None:
        return block_rows
    return INTERPRET_BLOCK_ROWS if interpret else DEFAULT_BLOCK_ROWS


def pack_bits_ref(bits: jnp.ndarray) -> jnp.ndarray:
    """Pure-jnp oracle: pack a flat 0/1 vector into MSB-first uint32 words."""
    m = bits.size
    w = _cdiv(m, 32)
    b = jnp.pad(bits.astype(jnp.uint32), (0, 32 * w - m)).reshape(w, 32)
    weights = (jnp.uint32(1) << (31 - jnp.arange(32, dtype=jnp.uint32)))
    return jnp.sum(b * weights[None, :], axis=1, dtype=jnp.uint32)


def _pack_kernel(b_ref, out_ref):
    b = b_ref[...]                               # (32, block_rows, LANE) int32
    j = jax.lax.broadcasted_iota(jnp.int32, b.shape, 0)
    # disjoint powers of two per j: the integer sum is the bitwise OR
    out_ref[...] = jnp.sum(b << (31 - j), axis=0)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def pack_bits_words(
    bits: jnp.ndarray,
    *,
    block_rows: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Pack a flat 0/1 vector into the canonical uint32 word stream.

    ``bits``: (m,) integer/bool array of 0/1.  Returns ``ceil(m/32)`` words;
    stream bit ``t`` lands in word ``t >> 5`` at bit ``31 - (t & 31)``.
    """
    interpret = resolve_interpret(interpret)
    block_rows = _resolve_rows(block_rows, interpret)
    PASSES.record("pack_bits")
    m = int(bits.size)
    n_words = _cdiv(m, 32)
    rows = _cdiv(n_words, block_rows * LANE) * block_rows
    padded_words = rows * LANE
    b = jnp.pad(bits.astype(jnp.int32).reshape(-1),
                (0, 32 * padded_words - m))
    # bit j of word w at [j, w // LANE, w % LANE]
    b3 = b.reshape(padded_words, 32).T.reshape(32, rows, LANE)
    out = pl.pallas_call(
        _pack_kernel,
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((32, block_rows, LANE), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.int32),
        interpret=interpret,
    )(b3)
    return jax.lax.bitcast_convert_type(out.reshape(-1)[:n_words], jnp.uint32)


def pack_bits_words_batched(
    bits: jnp.ndarray,
    *,
    block_rows: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Pack a uniform-length ``(B, nbits)`` bit batch in ONE kernel launch.

    Each row is padded to a whole number of words, so the flattened stream's
    word ``i * words_per_row + w`` is exactly row ``i``'s word ``w``.
    Returns ``(B, ceil(nbits/32))`` uint32.
    """
    bsz, m = bits.shape
    wpr = _cdiv(m, 32)
    padded = jnp.pad(bits.astype(jnp.int32), ((0, 0), (0, 32 * wpr - m)))
    words = pack_bits_words(padded.reshape(-1), block_rows=block_rows,
                            interpret=interpret)
    return words.reshape(bsz, wpr)
