"""Flash-style attention with a custom VJP, in pure jnp.

The naive chunked online-softmax (attention.chunked_attention) is numerically
fine but its ``lax.scan`` saves every per-chunk probability block for the
backward pass -- ~O(S^2) residuals per layer (measured ~22 GiB/layer on the
phi3 train_4k dry-run).  This implementation saves only ``(q, k, v, out,
lse)`` -- O(S·d) -- and recomputes the probability blocks chunk-by-chunk in a
hand-written backward, exactly like the FlashAttention backward:

    D    = rowsum(dO ⊙ O)
    p_c  = exp(q·k_cᵀ·scale - lse)
    dV_c = p_cᵀ · dO
    dP_c = dO · v_cᵀ
    dS_c = p_c ⊙ (dP_c - D)
    dQ  += scale · dS_c · k_c ;   dK_c = scale · dS_cᵀ · q

Supports GQA grouping, causal masks, sliding windows, cross attention, and
v-head-dim != qk-head-dim (MLA), and lowers on any backend.  It is not fast on
the TPU: each kv chunk writes a (queries x chunk) float32 score block to HBM
and reads it back, so the chunk matmuls wait on bandwidth, and the causal
scan computes the chunks above the diagonal too.  There, causal bf16
self-attention at a length that is a multiple of 128 with one head size for
q, k and v runs the Pallas flash kernel of ``repro.kernels.attention``
instead (``models/attention._attention``).  This scan serves the other
shapes: sliding windows, cross attention, MLA, float32 inputs, other
lengths, and every shape on the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["flash_attention"]

NEG_INF = -1e30

# §Perf lever (A4): dtype of the recomputed probability blocks in fwd/bwd.
# bf16 halves the dominant score-chain HBM traffic; the softmax statistics
# (m, l, lse, D) and accumulators stay fp32.
P_BLOCK_DTYPE = jnp.float32


def _chunk_kv(x, chunk):
    """(B, Skv, KV, h) -> (n_chunks, B, chunk, KV, h), zero-padded."""
    b, skv, kv, h = x.shape
    n_chunks = -(-skv // chunk)
    pad = n_chunks * chunk - skv
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return x.reshape(b, n_chunks, chunk, kv, h).transpose(1, 0, 2, 3, 4)


def _mask(q_pos, kv_pos, skv, causal, window):
    valid = kv_pos[None, :] < skv
    if causal:
        valid = valid & (kv_pos[None, :] <= q_pos[:, None])
    if window:
        valid = valid & (kv_pos[None, :] > q_pos[:, None] - window)
    return valid                                    # (Sq, C)


def _fwd_scan(q, k, v, causal, window, chunk, q_offset):
    b, sq, h, hd = q.shape
    skv, kv = k.shape[1], k.shape[2]
    hdv = v.shape[3]
    rep = h // kv
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    chunk = min(chunk, skv)

    qg = q.reshape(b, sq, kv, rep, hd).astype(jnp.float32) * scale
    kc = _chunk_kv(k, chunk)
    vc = _chunk_kv(v, chunk)
    q_pos = q_offset + jnp.arange(sq)

    def body(carry, inp):
        m, l, acc = carry
        ci, kch, vch = inp
        kv_pos = ci * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqgrd,bcgd->bgrqc", qg, kch.astype(jnp.float32))
        valid = _mask(q_pos, kv_pos, skv, causal, window)
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bgrqc,bcgd->bgrqd", p.astype(P_BLOCK_DTYPE),
                        vch.astype(P_BLOCK_DTYPE),
                        preferred_element_type=jnp.float32)
        acc = acc * corr[..., None] + pv
        return (m_new, l, acc), None

    m0 = jnp.full((b, kv, rep, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kv, rep, sq), jnp.float32)
    a0 = jnp.zeros((b, kv, rep, sq, hdv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (jnp.arange(kc.shape[0]), kc, vc))
    lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), jnp.float32(1e30))
    out = acc / jnp.maximum(l, 1e-30)[..., None]    # (b,kv,rep,sq,hdv)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    chunk: int = 1024, q_offset: int = 0):
    """q: (B,Sq,H,hd); k/v: (B,Skv,KV,hd[v]). Returns (B,Sq,H,hdv)."""
    out, _ = _fwd_scan(q, k, v, causal, window, chunk, q_offset)
    b, sq, h, hd = q.shape
    return (out.transpose(0, 3, 1, 2, 4)
               .reshape(b, sq, h, v.shape[3]).astype(q.dtype))


def _flash_fwd(q, k, v, causal, window, chunk, q_offset):
    out, lse = _fwd_scan(q, k, v, causal, window, chunk, q_offset)
    b, sq, h, hd = q.shape
    o = (out.transpose(0, 3, 1, 2, 4)
            .reshape(b, sq, h, v.shape[3]).astype(q.dtype))
    return o, (q, k, v, out, lse)


def _flash_bwd(causal, window, chunk, q_offset, res, do):
    q, k, v, out, lse = res                          # out: (b,kv,rep,sq,hdv)
    b, sq, h, hd = q.shape
    skv, kv = k.shape[1], k.shape[2]
    hdv = v.shape[3]
    rep = h // kv
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    chunk_sz = min(chunk, skv)

    qg = q.reshape(b, sq, kv, rep, hd).astype(jnp.float32)
    dog = (do.reshape(b, sq, kv, rep, hdv)
             .transpose(0, 2, 3, 1, 4).astype(jnp.float32))  # (b,kv,rep,sq,hdv)
    dmass = jnp.sum(dog * out, axis=-1)              # D: (b,kv,rep,sq)

    kc = _chunk_kv(k, chunk_sz)
    vc = _chunk_kv(v, chunk_sz)
    n_chunks = kc.shape[0]
    q_pos = q_offset + jnp.arange(sq)

    def body(dq_acc, inp):
        ci, kch, vch = inp
        kv_pos = ci * chunk_sz + jnp.arange(chunk_sz)
        s = jnp.einsum("bqgrd,bcgd->bgrqc", qg * scale,
                       kch.astype(jnp.float32))
        valid = _mask(q_pos, kv_pos, skv, causal, window)
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        p = jnp.exp(s - lse[..., None]).astype(P_BLOCK_DTYPE)  # recomputed
        dv_c = jnp.einsum("bgrqc,bgrqd->bcgd", p,
                          dog.astype(P_BLOCK_DTYPE),
                          preferred_element_type=jnp.float32)
        dp = jnp.einsum("bgrqd,bcgd->bgrqc", dog.astype(P_BLOCK_DTYPE),
                        vch.astype(P_BLOCK_DTYPE),
                        preferred_element_type=jnp.float32)
        ds = (p.astype(jnp.float32) * (dp - dmass[..., None]) *
              scale).astype(P_BLOCK_DTYPE)
        dq_acc = dq_acc + jnp.einsum("bgrqc,bcgd->bqgrd", ds,
                                     kch.astype(P_BLOCK_DTYPE),
                                     preferred_element_type=jnp.float32)
        dk_c = jnp.einsum("bgrqc,bqgrd->bcgd", ds,
                          qg.astype(P_BLOCK_DTYPE),
                          preferred_element_type=jnp.float32)
        return dq_acc, (dk_c, dv_c)

    dq0 = jnp.zeros((b, sq, kv, rep, hd), jnp.float32)
    dq, (dk_c, dv_c) = jax.lax.scan(
        body, dq0, (jnp.arange(n_chunks), kc, vc))
    dk = dk_c.transpose(1, 0, 2, 3, 4).reshape(b, -1, kv, hd)[:, :skv]
    dv = dv_c.transpose(1, 0, 2, 3, 4).reshape(b, -1, kv, hdv)[:, :skv]
    dq = dq.reshape(b, sq, h, hd)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_flash_fwd, _flash_bwd)
