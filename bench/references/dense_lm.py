"""Plain reference of a dense decoder-only LM: RMSNorm, GQA attention with
rotary embeddings and an optional QKV bias, SwiGLU MLP, a tied or untied
head, mean next-token cross-entropy.  The configurations ``smollm-135m``
(Llama architecture) and ``qwen2-0.5b`` (Qwen2: QKV bias, rope theta 1e6)
name it.

Written from the published architecture in straightforward ``jax.numpy``,
float32 at ``highest`` matmul precision, with nothing of the program under
test imported.  Its parameter tree has the program's layout (the
benchmark hands the same tree to both), and ``init_params`` makes it from
the seed on the device in one jitted call.

``precision="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 with a per-tensor scale (the backward passes the cotangent
straight through), everything else as above.

Departures from a textbook forward, all shared with the program: rotary
pairs are the two halves of each head (the Hugging Face Llama/Qwen2
layout); the loss is the mean over every position of every row.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["init_params", "loss"]

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                     # largest finite float8_e4m3fn
HEAD_CHUNK = 1024                  # tokens per block of the LM head + loss


def _head_dim(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def init_params(m: dict, key) -> dict:
    """Random weights from ``key`` in the program's tree layout: matrices
    with variance about 1/d_in, the embedding (and an untied head) and
    biases about 0.02^2, norm gains 1."""
    d, hd, f = m["d_model"], _head_dim(m), m["d_ff"]
    h, kv, v = m["n_heads"], m["n_kv_heads"], m["vocab_size"]
    counter = iter(range(1 << 20))

    def normal(shape, std):
        """Uniform on (-w, w), w the power of two nearest sqrt(3) * std (so
        the variance is within a factor 2 of std^2).  Every step from the
        random bits is exact, so any program that makes these weights, fused
        as it likes, gets the same bits."""
        bits = jax.random.bits(jax.random.fold_in(key, next(counter)), shape,
                               jnp.uint32)
        u = jax.lax.bitcast_convert_type((bits >> 9) | 0x3F800000,
                                         jnp.float32) - 1.0
        w = 2.0 ** round(math.log2(std * 3.0 ** 0.5))
        return (2.0 * u - 1.0) * jnp.float32(w)

    def dense(d_in, d_out):
        return normal((d_in, d_out), d_in ** -0.5)

    blocks = []
    for _ in range(m["n_layers"]):
        mix = {"wq": dense(d, h * hd), "wk": dense(d, kv * hd),
               "wv": dense(d, kv * hd), "wo": dense(h * hd, d)}
        if m["attn_bias"]:
            mix.update(bq=normal((h * hd,), 0.02), bk=normal((kv * hd,), 0.02),
                       bv=normal((kv * hd,), 0.02))
        blocks.append({
            "norm1": {"g": jnp.ones((d,), jnp.float32)}, "mix": mix,
            "norm2": {"g": jnp.ones((d,), jnp.float32)},
            "mlp": {"w_gate": dense(d, f), "w_up": dense(d, f),
                    "w_down": dense(f, d)}})
    params = {"embed": normal((v, d), 0.02), "blocks": blocks,
              "final_norm": {"g": jnp.ones((d,), jnp.float32)}}
    if not m["tie_embeddings"]:
        params["lm_head"] = normal((v, d), 0.02)
    return params


@jax.custom_vjp
def _round_fp8(x):
    scale = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


_round_fp8.defvjp(lambda x: (_round_fp8(x), None), lambda _, g: (g,))


def _einsum(precision: str):
    if precision == "f32":
        return functools.partial(jnp.einsum, precision=HIGHEST)
    if precision == "fp8":
        return lambda spec, a, b: jnp.einsum(spec, _round_fp8(a),
                                             _round_fp8(b), precision=HIGHEST)
    raise ValueError(f"unknown precision {precision!r}")


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: (B, S, H, hd); rotate the two halves of each head."""
    s, hd = x.shape[1], x.shape[3]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _block(m, es, x, blk):
    b, s, d = x.shape
    h, kv, hd = m["n_heads"], m["n_kv_heads"], _head_dim(m)
    mix = blk["mix"]
    a = _rms_norm(x, blk["norm1"]["g"], m["norm_eps"])
    q = es("bsd,de->bse", a, mix["wq"])
    k = es("bsd,de->bse", a, mix["wk"])
    v = es("bsd,de->bse", a, mix["wv"])
    if m["attn_bias"]:
        q, k, v = q + mix["bq"], k + mix["bk"], v + mix["bv"]
    q = _rope(q.reshape(b, s, h, hd), m["rope_theta"])
    k = _rope(k.reshape(b, s, kv, hd), m["rope_theta"])
    v = v.reshape(b, s, kv, hd)
    q = q.reshape(b, s, kv, h // kv, hd)            # head = kv_head * rep + r
    scores = es("bqgrd,bkgd->bgrqk", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = es("bgrqk,bkgd->bqgrd", p, v).reshape(b, s, h * hd)
    x = x + es("bse,ed->bsd", o, mix["wo"])
    a = _rms_norm(x, blk["norm2"]["g"], m["norm_eps"])
    mlp = blk["mlp"]
    u = (jax.nn.silu(es("bsd,df->bsf", a, mlp["w_gate"]))
         * es("bsd,df->bsf", a, mlp["w_up"]))
    return x + es("bsf,fd->bsd", u, mlp["w_down"])


def loss(params, m: dict, tokens, labels, precision: str = "f32"):
    """Mean next-token cross-entropy of ``tokens`` (B, S) against
    ``labels``; one layer's activations live at a time in the backward."""
    es = _einsum(precision)
    x = jnp.take(params["embed"], tokens, axis=0)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *params["blocks"])
    layer = jax.checkpoint(lambda x, blk: (_block(m, es, x, blk), None))
    x, _ = jax.lax.scan(layer, x, stacked)
    x = _rms_norm(x, params["final_norm"]["g"], m["norm_eps"])
    head = params.get("lm_head", params["embed"])
    b, s, d = x.shape
    n = b * s
    chunk = HEAD_CHUNK if n % HEAD_CHUNK == 0 else n
    xs = x.reshape(n // chunk, chunk, d)
    ys = labels.reshape(n // chunk, chunk)

    @jax.checkpoint
    def ce(tot, xy):
        xc, yc = xy
        logits = es("td,vd->tv", xc, head)
        gold = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
        return tot + jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold), None

    total, _ = jax.lax.scan(ce, jnp.zeros((), jnp.float32), (xs, ys))
    return total / n
