"""Tree-level STC for the distributed train_step (no flatten, no gathers).

Global top-k over the whole model == per-leaf masking with ONE global
magnitude threshold, and µ == the global mean magnitude of kept entries.
The threshold is found by counting: a max sweep bounds the magnitudes, a
bisection on the threshold counts ``|x| >= mid`` over every leaf ``iters``
times (one sweep each), and a final sweep takes the count, the kept sum and
the least kept magnitude: the k-th magnitude once the bisection has
separated it from the next smaller one, which 32 halvings do on the float32
trees of the tests.  Each sweep is a streaming reduction over the leaves in
place: no concatenation, no resharding, no all-gather of the parameter
vector, no scatter and no sort.

Reductions over the tensor-parallel ("model") axis happen automatically via
GSPMD (jnp.sum of a sharded leaf is a global sum); reductions over manual
(shard_map) axes are explicit: each sweep's scalars are ``psum``-ed (the
least kept magnitude ``pmin``-ed).  Off the TPU, a k no larger than ``cap``
takes a per-leaf top-k shortcut instead (:func:`_direct_tree_select`).

This module is the distributed twin of core.compression / kernels.ops, and is
oracle-checked against them in tests.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.selection import DEFAULT_CAP, PASSES, resolve_interpret

__all__ = ["TreeStats", "tree_numel", "stc_compress_tree",
           "stc_compress_tree_chunked", "ternary_quantize_tree",
           "sign_compress_tree", "tree_add", "tree_scale"]


class TreeStats(NamedTuple):
    nnz: jnp.ndarray
    numel: int
    mu: jnp.ndarray
    thresh: jnp.ndarray


def tree_numel(tree) -> int:
    return sum(x.size for x in jax.tree.leaves(tree))


def tree_add(a, b):
    return jax.tree.map(lambda x, y: x + y, a, b)


def tree_scale(a, s):
    return jax.tree.map(lambda x: x * s, a)


def _psum(x, manual_axes):
    return jax.lax.psum(x, manual_axes) if manual_axes else x


def _pmax(x, manual_axes):
    return jax.lax.pmax(x, manual_axes) if manual_axes else x


def _count_and_sum(tree, t):
    """(#|x|>=t, Σ|x| and least |x| over that set) across all leaves (one
    sweep; a caller that drops the sum or the least lets XLA drop it)."""
    cnt = jnp.zeros((), jnp.int32)
    s = jnp.zeros((), jnp.float32)
    least = jnp.float32(jnp.inf)
    for leaf in jax.tree.leaves(tree):
        a = jnp.abs(leaf.astype(jnp.float32))
        m = a >= t
        cnt = cnt + jnp.sum(m.astype(jnp.int32))
        s = s + jnp.sum(jnp.where(m, a, 0.0))
        least = jnp.minimum(least, jnp.min(jnp.where(m, a, jnp.inf)))
    return cnt, s, least


def _direct_tree_select(tree, k, cap, manual_axes):
    """Non-TPU small-k shortcut: per-leaf top-k gathers, one sweep (1-2 total).

    Every element ≥ the global k-th magnitude is inside its leaf's top-
    ``min(cap, size)`` gather (there are at most k ≤ cap of them per leaf), so
    the k-th largest of the concatenated gathers is exact; a per-leaf
    tie-spill (a full gather whose tail ties the threshold) falls back to one
    counting sweep via lax.cond.
    """
    PASSES.record("topk_gather")                               # sweep 1
    cands, full = [], []
    for leaf in jax.tree.leaves(tree):
        a = jnp.abs(leaf.astype(jnp.float32)).reshape(-1)
        cap_leaf = min(cap, a.size)
        cands.append(jax.lax.top_k(a, cap_leaf)[0])
        full.append(a.size > cap_leaf)
    # gathered tail == min (descending); NOT c[-1], whose static slice of a
    # top_k XLA:CPU rewrites into a full sort of the leaf
    tails = jnp.stack([jnp.min(c) for c in cands])
    fulls = jnp.asarray(full)
    cands = jnp.concatenate(cands)
    if manual_axes:
        cands = jax.lax.all_gather(cands, manual_axes).reshape(-1)
        tails = jax.lax.all_gather(tails, manual_axes).reshape(-1)
        fulls = jax.lax.all_gather(fulls, manual_axes).reshape(-1)

    srt = jnp.sort(cands)[::-1]
    v = srt[k - 1]
    spill = jnp.any(fulls & (tails >= v))

    def _from_gather(_):
        ge = cands >= v
        return (v, jnp.sum(ge.astype(jnp.int32)),
                jnp.sum(jnp.where(ge, cands, 0.0)))

    def _tie_spill(_):                                         # rare sweep 2
        cnt, s, _ = _count_and_sum(tree, v)
        return v, _psum(cnt, manual_axes), _psum(s, manual_axes)

    return jax.lax.cond(spill, _tie_spill, _from_gather, None)


def _bisect_threshold(tree, k, a_max, manual_axes, iters):
    """``iters`` count sweeps bisecting for the k-th magnitude, then one
    sweep for (least kept magnitude, count, Σ|x|) at the bisection's
    lower end, which keeps at least k values."""
    hi0 = a_max * jnp.float32(1.0 + 1e-6) + jnp.float32(1e-30)
    lo0 = jnp.float32(0.0)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        cnt, _, _ = _count_and_sum(tree, mid)
        cnt = _psum(cnt, manual_axes)
        keep = cnt >= k
        return jnp.where(keep, mid, lo), jnp.where(keep, hi, mid)

    lo, _ = jax.lax.fori_loop(0, iters, body, (lo0, hi0))
    cnt, s, least = _count_and_sum(tree, lo)
    least = jax.lax.pmin(least, manual_axes) if manual_axes else least
    return least, _psum(cnt, manual_axes), _psum(s, manual_axes)


def stc_compress_tree(tree, p: float, *, manual_axes=(), iters: int = 32,
                      numel: int | None = None, cap: int = DEFAULT_CAP):
    """STC over a pytree: returns (ternary_tree, stats).

    ``manual_axes``: shard_map axis names the leaves are *sharded over* (the
    server stage when state is scattered); () when each caller holds the full
    (possibly GSPMD-sharded) tree.

    The selection counts (module docstring): a max sweep, ``iters`` count
    sweeps of the bisection and a final sweep, all under the named scope
    ``select`` so that a device trace can time it.  ``stats.thresh`` is the
    least kept magnitude, so ``|x| >= thresh`` keeps what the count kept.
    Off the TPU, ``k <= cap`` takes the per-leaf top-k shortcut instead.
    """
    numel = numel if numel is not None else tree_numel(tree)
    k = max(int(numel * p), 1)

    if resolve_interpret(None) and k <= cap:
        # non-TPU small-k shortcut (see _direct_tree_select / hist_select)
        thresh, cnt_tot, sum_tot = _direct_tree_select(tree, k, cap,
                                                       manual_axes)
        return _finish_tree(tree, thresh, cnt_tot, sum_tot, numel)

    PASSES.record("max")
    PASSES.record("count", iters + 1)
    with jax.named_scope("select"):
        a_max = jnp.zeros((), jnp.float32)
        for leaf in jax.tree.leaves(tree):
            a_max = jnp.maximum(a_max,
                                jnp.max(jnp.abs(leaf.astype(jnp.float32))))
        a_max = _pmax(a_max, manual_axes)
        thresh, cnt_tot, sum_tot = _bisect_threshold(tree, k, a_max,
                                                     manual_axes, iters)
    return _finish_tree(tree, thresh, cnt_tot, sum_tot, numel)


def _finish_tree(tree, thresh, cnt_tot, sum_tot, numel):
    """µ + per-leaf ternarization from the selected (thresh, count, sum)."""
    mu = sum_tot / jnp.maximum(cnt_tot, 1).astype(jnp.float32)

    def tern_leaf(x):
        xf = x.astype(jnp.float32)
        m = jnp.abs(xf) >= thresh
        return jnp.where(m, mu * jnp.sign(xf), 0.0).astype(x.dtype)

    tern = jax.tree.map(tern_leaf, tree)
    return tern, TreeStats(nnz=cnt_tot, numel=numel, mu=mu, thresh=thresh)


def stc_compress_tree_chunked(tree, p: float, chunk_size: int, *,
                              p_fn=None, backend: str = "jnp",
                              controller=None):
    """Per-``(leaf, chunk)`` STC: independent selection + µ per block.

    The chunked twin of :func:`stc_compress_tree`: instead of ONE global
    threshold (which serializes every leaf behind a collective selection),
    each leaf is cut into ``ceil(size / chunk_size)`` blocks and every block
    gets its own exact k-selection and ternary magnitude through the STC
    backend registry (``"jnp"`` top-k gather / ``"kernel"`` = the batched
    Pallas histogram selector, one launch per leaf covering all its chunks).
    No collectives anywhere: under shard_map each shard selects over its own
    blocks only, so the sweeps pipeline across the mesh.

    ``p_fn(layer_name, depth) -> p | None`` is the per-layer sparsity
    schedule hook (None keeps ``p``; every schedule-produced p is validated
    -- finite, in (0, 1] -- with a ValueError naming the layer).
    ``controller`` (a :mod:`repro.core.adaptive` name or instance) switches
    per-chunk k from the static schedule to the controller's in-jit policy;
    the tree path is stateless, so stateful controllers run their
    instantaneous rule (``state=None``).  Returns ``(ternary_tree, stats)``
    with aggregate nnz/µ across all blocks.
    """
    from repro.core.adaptive import make_controller, validate_sparsity
    from repro.core.compression import stc_compress_blocks

    if chunk_size < 1:
        raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
    ctrl = make_controller(controller) if controller is not None else None
    if ctrl is not None and not ctrl.adapts:
        ctrl = None                      # "fixed": exactly the static path
    flat_leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out_leaves = []
    nnz_tot = jnp.zeros((), jnp.int32)
    mu_num = jnp.zeros((), jnp.float32)     # Σ per-block µ·count (global µ)
    numel = 0
    for depth, (path, leaf) in enumerate(flat_leaves):
        numel += leaf.size
        if leaf.size == 0:
            out_leaves.append(leaf)
            continue
        lname = jax.tree_util.keystr(path)
        p_leaf = None if p_fn is None else p_fn(lname, depth)
        p_leaf = p if p_leaf is None \
            else validate_sparsity(p_leaf, lname, depth)
        flat = leaf.astype(jnp.float32).reshape(-1)
        w = min(chunk_size, flat.size)
        n_chunks = -(-flat.size // w)
        pad = n_chunks * w - flat.size
        blocks = jnp.pad(flat, (0, pad)).reshape(n_chunks, w)
        valid = np.full(n_chunks, w, np.int64)
        valid[-1] = flat.size - (n_chunks - 1) * w
        ks = np.maximum((valid * p_leaf).astype(np.int64), 1)
        if ctrl is not None:
            caps = ctrl.caps(ks, valid)
            dyn_ks, _ = ctrl.chunk_ks(blocks[None], None, base_ks=ks,
                                      caps=caps)
            tern, cnt, mu = stc_compress_blocks(
                blocks, jnp.asarray(dyn_ks).reshape(n_chunks),
                backend=backend, k_cap=int(caps.max()))
        else:
            tern, cnt, mu = stc_compress_blocks(blocks, ks, backend=backend)
        out_leaves.append(
            tern.reshape(-1)[: flat.size].reshape(leaf.shape)
            .astype(leaf.dtype))
        nnz_tot = nnz_tot + jnp.sum(cnt)
        mu_num = mu_num + jnp.sum(mu * cnt.astype(jnp.float32))
    out = jax.tree_util.tree_unflatten(treedef, out_leaves)
    mu = mu_num / jnp.maximum(nnz_tot, 1).astype(jnp.float32)
    return out, TreeStats(nnz=nnz_tot, numel=numel, mu=mu,
                          thresh=jnp.zeros((), jnp.float32))


def ternary_quantize_tree(tree, theta: float, *, manual_axes=(),
                          numel: int | None = None):
    """Dense ternary quantization over a pytree (tree twin of
    ``compression.ternary_quantize``): Δ = θ·mean|x| globally across leaves,
    µ = mean kept magnitude.  Two sweeps, no gathers."""
    numel = numel if numel is not None else tree_numel(tree)
    s_all = jnp.zeros((), jnp.float32)                          # sweep 1
    for leaf in jax.tree.leaves(tree):
        s_all = s_all + jnp.sum(jnp.abs(leaf.astype(jnp.float32)))
    s_all = _psum(s_all, manual_axes)
    delta = theta * s_all / jnp.float32(numel)

    cnt = jnp.zeros((), jnp.int32)                              # sweep 2
    s_kept = jnp.zeros((), jnp.float32)
    for leaf in jax.tree.leaves(tree):
        a = jnp.abs(leaf.astype(jnp.float32))
        m = a > delta
        cnt = cnt + jnp.sum(m.astype(jnp.int32))
        s_kept = s_kept + jnp.sum(jnp.where(m, a, 0.0))
    cnt = _psum(cnt, manual_axes)
    s_kept = _psum(s_kept, manual_axes)
    mu = s_kept / jnp.maximum(cnt, 1).astype(jnp.float32)

    def tern_leaf(x):
        xf = x.astype(jnp.float32)
        return jnp.where(jnp.abs(xf) > delta, mu * jnp.sign(xf), 0.0
                         ).astype(x.dtype)

    tern = jax.tree.map(tern_leaf, tree)
    return tern, TreeStats(nnz=cnt, numel=numel, mu=mu, thresh=delta)


def sign_compress_tree(tree, step: float):
    return jax.tree.map(
        lambda x: (step * jnp.sign(x.astype(jnp.float32))).astype(x.dtype),
        tree)
