"""Attention: GQA (+ optional QKV bias), causal / sliding-window / cross,
memory-efficient chunked online-softmax, and single-token decode with KV cache.

The chunked formulation (lax.scan over KV chunks with an online softmax) keeps
the materialized score block at (B, KV, rep, Sq, C) instead of (B, H, Sq, Skv),
which is what lets the 4k/32k dry-runs fit HBM -- and it lowers on any backend
(the dry-run compiles on CPU, where a Mosaic kernel would not).  On the TPU,
``_attention`` runs causal bf16 self-attention at a length that is a multiple
of 128, with one head size for q, k and v, as the Pallas flash kernel of
``repro.kernels.attention``; every other shape keeps the jnp scan.  The whole
of ``_attention`` runs under the scope ``attention``, the kernel under
``attention_kernel`` inside it.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels.attention import causal_gqa_attention, fits_mesh

from .flash import flash_attention
from .layers import apply_rope, dense_init, rope_freqs

__all__ = ["KVCache", "attn_init", "attn_apply", "attn_decode", "init_kv_cache",
           "chunked_attention"]

NEG_INF = -1e30

# "flash": custom-VJP O(S·d)-residual attention (default).
# "chunked": naive online-softmax scan (reference; O(S²) bwd residuals).
ATTN_IMPL = "flash"

# §Perf hook (decode): when set (by launch.serve), applied to q/k/v/scores in
# attn_decode to pin the attention computation to a chosen layout -- used to
# force fully-local decode attention when head counts don't divide the model
# axis (see launch/serve.make_decode_step cache_mode="local").
DECODE_SHARD_HINT = None


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _takes_kernel(q, k, v, causal, window) -> bool:
    """Causal bf16 self-attention on the TPU at a 128-multiple length with
    one head size for q, k and v, where no mesh axis of size > 1 is left to
    the partitioner: what the Pallas flash kernel runs."""
    s, hd = q.shape[1], q.shape[3]
    return (_on_tpu() and causal and window == 0 and k.shape[1] == s
            and s % 128 == 0 and v.shape[3] == hd
            and all(x.dtype == jnp.bfloat16 for x in (q, k, v))
            and fits_mesh())


def _attention(q, k, v, *, causal, window=0, chunk=1024, impl=None):
    impl = impl or ATTN_IMPL
    with jax.named_scope("attention"):
        if impl != "flash":
            return chunked_attention(q, k, v, causal=causal, window=window,
                                     chunk=chunk)
        if _takes_kernel(q, k, v, causal, window):
            with jax.named_scope("attention_kernel"):
                return causal_gqa_attention(q, k, v)
        return flash_attention(q, k, v, causal, window, chunk)


class KVCache(NamedTuple):
    k: jnp.ndarray      # (B, S_cache, KV, hd)
    v: jnp.ndarray      # (B, S_cache, KV, hd)
    idx: jnp.ndarray    # scalar int32: number of valid positions written
    ring: bool = False  # True -> S_cache is a sliding window ring buffer


def attn_init(key, d_model: int, n_heads: int, n_kv_heads: int, head_dim: int,
              bias: bool = False):
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d_model, n_heads * head_dim),
        "wk": dense_init(ks[1], d_model, n_kv_heads * head_dim),
        "wv": dense_init(ks[2], d_model, n_kv_heads * head_dim),
        "wo": dense_init(ks[3], n_heads * head_dim, d_model),
    }
    if bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), jnp.float32)
        p["bk"] = jnp.zeros((n_kv_heads * head_dim,), jnp.float32)
        p["bv"] = jnp.zeros((n_kv_heads * head_dim,), jnp.float32)
    return p


def chunked_attention(
    q: jnp.ndarray,            # (B, Sq, H, hd)
    k: jnp.ndarray,            # (B, Skv, KV, hd)
    v: jnp.ndarray,            # (B, Skv, KV, hd)
    *,
    causal: bool,
    window: int = 0,           # 0 = unbounded
    q_offset: jnp.ndarray | int = 0,   # absolute position of q[0]
    chunk: int = 1024,
) -> jnp.ndarray:
    """Online-softmax attention, scanning KV in chunks. Returns (B,Sq,H,hd)."""
    b, sq, h, hd = q.shape
    skv, kv = k.shape[1], k.shape[2]
    hdv = v.shape[3]                                 # may differ from hd (MLA)
    rep = h // kv
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    chunk = min(chunk, skv)
    n_chunks = -(-skv // chunk)
    pad = n_chunks * chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    qg = q.reshape(b, sq, kv, rep, hd).astype(jnp.float32) * scale
    kc = k.reshape(b, n_chunks, chunk, kv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, kv, hdv).transpose(1, 0, 2, 3, 4)

    q_pos = q_offset + jnp.arange(sq)                    # (Sq,)

    def body(carry, inputs):
        m, l, acc = carry
        ci, kch, vch = inputs                            # kch: (B, C, KV, hd)
        kv_pos = ci * chunk + jnp.arange(chunk)          # (C,)
        s = jnp.einsum("bqgrd,bcgd->bgrqc", qg, kch.astype(jnp.float32))
        valid = (kv_pos[None, :] < skv)                  # mask the zero padding
        if causal:
            valid = valid & (kv_pos[None, :] <= q_pos[:, None])
        if window:
            valid = valid & (kv_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(valid[None, None, None, :, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bgrqc,bcgd->bgrqd", p, vch.astype(jnp.float32))
        acc = acc * corr[..., None] + pv
        return (m_new, l, acc), None

    m0 = jnp.full((b, kv, rep, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kv, rep, sq), jnp.float32)
    a0 = jnp.zeros((b, kv, rep, sq, hdv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (jnp.arange(n_chunks), kc, vc)
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hdv).astype(q.dtype)


def _project_qkv(params, x, n_heads, n_kv_heads, head_dim):
    b, s, _ = x.shape
    q = x @ params["wq"].astype(x.dtype)
    k = x @ params["wk"].astype(x.dtype)
    v = x @ params["wv"].astype(x.dtype)
    if "bq" in params:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    return (q.reshape(b, s, n_heads, head_dim),
            k.reshape(b, s, n_kv_heads, head_dim),
            v.reshape(b, s, n_kv_heads, head_dim))


def attn_apply(
    params, x: jnp.ndarray, *, n_heads: int, n_kv_heads: int, head_dim: int,
    rope_theta: float = 10000.0, causal: bool = True, window: int = 0,
    memory: Optional[jnp.ndarray] = None, chunk: int = 1024,
    positions: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Full-sequence attention. ``memory`` switches to cross-attention
    (k/v projected from memory, no causal mask, no RoPE on memory keys)."""
    b, s, _ = x.shape
    if memory is None:
        q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim)
        pos = positions if positions is not None else jnp.arange(s)
        cos, sin = rope_freqs(pos, head_dim, rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        out = _attention(q, k, v, causal=causal, window=window, chunk=chunk)
    else:
        sm = memory.shape[1]
        q = (x @ params["wq"].astype(x.dtype)).reshape(b, s, n_heads, head_dim)
        k = (memory @ params["wk"].astype(x.dtype)).reshape(b, sm, n_kv_heads, head_dim)
        v = (memory @ params["wv"].astype(x.dtype)).reshape(b, sm, n_kv_heads, head_dim)
        out = _attention(q, k, v, causal=False, window=0, chunk=chunk)
    return out.reshape(b, s, n_heads * head_dim) @ params["wo"].astype(x.dtype)


def init_kv_cache(batch: int, s_cache: int, n_kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16, ring: bool = False) -> KVCache:
    shape = (batch, s_cache, n_kv_heads, head_dim)
    return KVCache(
        k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
        idx=jnp.zeros((), jnp.int32), ring=ring,
    )


def attn_decode(
    params, x: jnp.ndarray, cache: KVCache, *, n_heads: int, n_kv_heads: int,
    head_dim: int, rope_theta: float = 10000.0, window: int = 0,
    memory: Optional[jnp.ndarray] = None,
) -> tuple[jnp.ndarray, KVCache]:
    """One-token decode: x is (B, 1, d). Returns (out (B,1,d), new_cache).

    Full cache: write at idx. Sliding window (``cache.ring``): write at
    idx % S_cache; positions beyond the window are never attended because the
    ring only holds the last S_cache = window tokens.
    """
    b = x.shape[0]
    if memory is not None:
        sm = memory.shape[1]
        q = (x @ params["wq"].astype(x.dtype)).reshape(b, 1, n_heads, head_dim)
        k = (memory @ params["wk"].astype(x.dtype)).reshape(b, sm, n_kv_heads, head_dim)
        v = (memory @ params["wv"].astype(x.dtype)).reshape(b, sm, n_kv_heads, head_dim)
        out = _dense_decode_attn(q, k, v, jnp.ones((sm,), bool))
        return out.reshape(b, 1, n_heads * head_dim) @ params["wo"].astype(x.dtype), cache

    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim)
    pos = cache.idx[None]                                     # absolute position
    cos, sin = rope_freqs(pos, head_dim, rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    if DECODE_SHARD_HINT is not None:
        q = DECODE_SHARD_HINT(q)
        k = DECODE_SHARD_HINT(k)
        v = DECODE_SHARD_HINT(v)

    s_cache = cache.k.shape[1]
    slot = jnp.where(cache.ring, cache.idx % s_cache, cache.idx)
    new_k = jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype), (0, slot, 0, 0))
    new_v = jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype), (0, slot, 0, 0))
    n_valid = jnp.minimum(cache.idx + 1, s_cache)
    valid = (jnp.arange(s_cache) < n_valid)
    out = _dense_decode_attn(q, new_k, new_v, valid)
    out = out.reshape(b, 1, n_heads * head_dim) @ params["wo"].astype(x.dtype)
    return out, KVCache(k=new_k, v=new_v, idx=cache.idx + 1, ring=cache.ring)


def _dense_decode_attn(q, k, v, valid):
    """q: (B,1,H,hd); k/v: (B,S,KV,hd); valid: (S,) bool."""
    b, _, h, hd = q.shape
    kv = k.shape[2]
    rep = h // kv
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    qg = q.reshape(b, kv, rep, hd).astype(jnp.float32) * scale
    s = jnp.einsum("bgrd,bcgd->bgrc", qg, k.astype(jnp.float32))
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrc,bcgd->bgrd", p, v.astype(jnp.float32))
    return o.reshape(b, 1, h, hd).astype(q.dtype)
