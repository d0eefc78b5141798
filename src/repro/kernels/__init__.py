"""Pallas TPU kernels for the STC compression hot-spot.

* ``hist_select``    -- single-pass 256-bin histogram k-selection (counts +
  per-bin |x| sums accumulated across the sequential grid), located by a jnp
  cumulative sum plus ONE exact refinement pass: ≤3 streaming passes per
  selection vs 33 for bisection, with batched ``(client, block)`` variants so
  a federated round's P-client compression is one kernel launch.  See the
  module docstring for the full design note.
* ``topk_threshold`` -- k-selection by threshold bisection (streaming counting
  kernel; 33 passes).  Kept as the reference selector and the exactness
  fallback for pathological inputs.
* ``stc_compress``   -- fused mask → ternarize → error-feedback single-pass
  kernel over the carried vector (single + batched client axis).
* ``bitpack``        -- wire-format word packing: 32 stream bits → one uint32
  word per VPU shift-and-sum, the device half of the ``"kernel"`` wire
  backend in :mod:`repro.core.wire` (single + uniform-length batched).
* ``wiredecode``     -- the decode inverse: each uint32 stream word explodes
  into its 32 MSB-first bits plus a fused per-word zero count (the seed of
  the decoder's run-length prefix scan), the device half of the ``"kernel"``
  wire DECODE backend.
* ``attention``      -- causal GQA self-attention as a VMEM-tiled flash
  kernel (the splash kernel that ships with JAX, skipping the tiles above the
  diagonal), the local step's attention on the TPU.
* ``ops``            -- jit'd public wrappers; ``ref`` -- pure-jnp oracles.

All entry points take ``interpret: bool | None = None`` and autodetect the
backend (compiled on TPU, interpreter elsewhere), so call sites are TPU-ready
unchanged.  Tests sweep shapes & dtypes and assert_allclose against the
oracles; ``core.selection.PASSES`` counts logical streaming passes for the
perf tests.
"""

from repro.core.selection import PASSES, resolve_interpret
from .bitpack import pack_bits_ref, pack_bits_words, pack_bits_words_batched
from .wiredecode import (unpack_bits_ref, unpack_bits_words,
                         unpack_words_with_counts)
from .hist_select import (hist_topk_threshold, hist_topk_threshold_batched,
                          magnitude_histogram, magnitude_histogram_batched)
from .ops import (stc_compress_batch, stc_compress_kernel, stc_compress_ref,
                  threshold_stats, topk_threshold)
from .stc_compress import stc_apply, stc_apply_batched

__all__ = [
    "stc_compress_kernel",
    "stc_compress_batch",
    "stc_compress_ref",
    "threshold_stats",
    "topk_threshold",
    "hist_topk_threshold",
    "hist_topk_threshold_batched",
    "magnitude_histogram",
    "magnitude_histogram_batched",
    "stc_apply",
    "stc_apply_batched",
    "pack_bits_words",
    "pack_bits_words_batched",
    "pack_bits_ref",
    "unpack_bits_words",
    "unpack_words_with_counts",
    "unpack_bits_ref",
    "PASSES",
    "resolve_interpret",
]
