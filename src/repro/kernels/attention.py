"""Causal GQA self-attention as a VMEM-tiled Pallas flash kernel.

A thin adapter over the splash-attention kernel that ships with JAX
(``jax.experimental.pallas.ops.tpu.splash_attention``): a forward kernel and
separate dq and dkv backward kernels, each keeping its score tile in VMEM and
skipping the tiles above the diagonal, which the causal mask removes whole.
GQA comes from the shapes: ``H`` query heads over ``KV`` key/value heads.

Arithmetic: q·kᵀ, dP, dQ, dK and dV take their operands in the inputs' dtype
(bf16 for the models' compute) and accumulate in float32; the softmax
statistics (running max, sum, logsumexp, ``rowsum(dO ⊙ O)``) stay float32;
the forward's p·v runs in float32.  Splash applies no softmax scale, so q is
scaled by ``1/sqrt(hd)`` before the kernel (exact for a power-of-two scale,
as at hd 64).

Block sizes follow one rule of the sequence length S: ``min(512, S)`` for
the query and key/value tiles of the forward, ``min(1024, S)`` for those of
the dq and dkv kernels.

Mosaic kernels cannot be partitioned automatically, and the federated round
leaves its ``model`` axis to the partitioner.  Where mesh axes are left to it
here (all of size 1: ``fits_mesh``), the kernel's forward and its backward
each run under a ``shard_map`` that takes every axis manual.  They do so
inside a custom VJP, so that JAX never transposes that ``shard_map``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as splash, splash_attention_mask as mask_lib)
from jax.sharding import PartitionSpec as P

from repro.core.selection import resolve_interpret

__all__ = ["causal_gqa_attention", "fits_mesh"]

FWD_BLOCK = 512
BWD_BLOCK = 1024


def _auto_axes(mesh) -> set:
    return set(mesh.axis_names) - set(mesh.manual_axes)


def fits_mesh() -> bool:
    """Whether the kernel can run under the mesh in context: every axis that
    is not manual here has size 1 (or there is no mesh)."""
    mesh = jax.sharding.get_abstract_mesh()
    return all(mesh.shape[a] == 1 for a in _auto_axes(mesh))


def _manual(f):
    """``f`` under a ``shard_map`` that takes every mesh axis manual, where
    some are not yet; the arguments and results are whole on every device."""
    mesh = jax.sharding.get_abstract_mesh()
    if not _auto_axes(mesh):
        return f
    return lambda *args: jax.shard_map(
        f, in_specs=(P(),) * len(args), out_specs=P(),
        axis_names=set(mesh.axis_names), check_vma=False)(*args)


@functools.lru_cache(maxsize=None)
def _attend(s: int, h: int, interpret: bool):
    """The splash kernel for causal attention over ``h`` query heads at
    sequence length ``s``, vmapped over the batch: one build, with its block
    masks, per shape.  Takes and returns ``(B, heads, S, hd)``."""
    bf, bb = min(FWD_BLOCK, s), min(BWD_BLOCK, s)
    blocks = splash.BlockSizes(block_q=bf, block_kv=bf, block_kv_compute=bf,
                               block_q_dkv=bb, block_kv_dkv=bb,
                               block_kv_dkv_compute=bb, block_q_dq=bb,
                               block_kv_dq=bb)
    mask = mask_lib.MultiHeadMask([mask_lib.CausalMask((s, s))] * h)
    with jax.ensure_compile_time_eval():
        kernel = splash.make_splash_mha(
            mask, block_sizes=blocks, head_shards=1, q_seq_shards=1,
            interpret=interpret)
    # host copies of the block masks: constants of whatever mesh calls it
    kernel = jax.vmap(jax.tree.map(np.asarray, kernel))

    @jax.custom_vjp
    def attend(q, k, v):
        return _manual(kernel)(q, k, v)

    def fwd(q, k, v):
        return _manual(lambda q, k, v: jax.vjp(kernel, q, k, v))(q, k, v)

    def bwd(pullback, do):
        return _manual(lambda pb, do: pb(do))(pullback, do)

    attend.defvjp(fwd, bwd)
    return attend


def causal_gqa_attention(q, k, v, interpret: bool | None = None):
    """q: (B, S, H, hd); k, v: (B, S, KV, hd). Returns (B, S, H, hd).

    Causal self-attention with ``H // KV`` query heads per key/value head,
    softmax scale ``1/sqrt(hd)``; ``fits_mesh()`` must hold."""
    _, s, h, hd = q.shape
    attend = _attend(s, h, resolve_interpret(interpret))
    q = (q.astype(jnp.float32) / float(hd) ** 0.5).astype(q.dtype)
    heads_first = lambda x: x.transpose(0, 2, 1, 3)
    return heads_first(attend(heads_first(q), heads_first(k),
                              heads_first(v)))
