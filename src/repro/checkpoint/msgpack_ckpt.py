"""Msgpack pytree checkpointing with zstd compression."""

from __future__ import annotations

import os
import tempfile
from typing import Optional

import jax
import jax.numpy as jnp
import msgpack
import numpy as np
import zstandard

__all__ = ["save_checkpoint", "restore_checkpoint",
           "save_state", "restore_state"]

_BF16 = "bfloat16"


def _pack_leaf(x):
    arr = np.asarray(x)
    if str(arr.dtype) == _BF16:
        return {"d": _BF16, "s": list(arr.shape),
                "b": arr.view(np.uint16).tobytes()}
    return {"d": str(arr.dtype), "s": list(arr.shape), "b": arr.tobytes()}


def _unpack_leaf(rec):
    if rec["d"] == _BF16:
        arr = np.frombuffer(rec["b"], np.uint16).reshape(rec["s"])
        return jnp.asarray(arr.view(jnp.bfloat16))
    arr = np.frombuffer(rec["b"], np.dtype(rec["d"])).reshape(rec["s"])
    return jnp.asarray(arr)


def _write_compressed(path: str, payload: bytes) -> None:
    """zstd-compress ``payload`` and replace ``path`` atomically."""
    comp = zstandard.ZstdCompressor(level=3).compress(payload)
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(os.path.abspath(path)))
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(comp)
        os.replace(tmp, path)
    except BaseException:
        os.unlink(tmp)
        raise


def _read_compressed(path: str) -> bytes:
    with open(path, "rb") as f:
        return zstandard.ZstdDecompressor().decompress(f.read())


def save_checkpoint(path: str, tree) -> None:
    leaves, treedef = jax.tree.flatten(tree)
    _write_compressed(path, msgpack.packb({
        "treedef": str(treedef),  # structural fingerprint for validation
        "leaves": [_pack_leaf(x) for x in leaves],
    }))


def restore_checkpoint(path: str, like):
    """Restore into the structure of ``like`` (validates leaf count +
    treedef fingerprint)."""
    obj = msgpack.unpackb(_read_compressed(path))
    leaves, treedef = jax.tree.flatten(like)
    if len(obj["leaves"]) != len(leaves):
        raise ValueError(
            f"checkpoint has {len(obj['leaves'])} leaves, template has "
            f"{len(leaves)}")
    if obj["treedef"] != str(treedef):
        raise ValueError("checkpoint tree structure mismatch")
    return jax.tree.unflatten(treedef, [_unpack_leaf(r)
                                        for r in obj["leaves"]])


# ---------------------------------------------------------------------------
# template-free tagged state serialization
# ---------------------------------------------------------------------------
# ``save_checkpoint`` needs a matching template pytree on restore, which the
# event-driven trainer's crash-resume path cannot supply (the in-flight
# buffer, event logs and RNG states have data-dependent shape).  The tagged
# codec below round-trips an arbitrary composite of Python scalars, numpy
# arrays, lists/tuples/sets/dicts and registered NamedTuples without a
# template.  Scalars small enough for msgpack pass through raw; everything
# else is a ``[tag, ...]`` list:
#
#   "I"  big int (hex string -- PCG64 carries 128-bit state words)
#   "a"  ndarray      "a0" numpy scalar     "l" list     "t" tuple
#   "nt" NamedTuple (by registered class name)           "s" set (sorted)
#   "d"  dict with non-string or tagged keys
#
# Restore returns numpy arrays (callers re-device with jnp.asarray where
# needed): bit-exactness of the resumed trainer must not depend on any
# device round-trip.

_MSGPACK_INT_MAX = (1 << 64) - 1
_MSGPACK_INT_MIN = -(1 << 63)


def _tag_state(x, classes: dict):
    if x is None or isinstance(x, (bool, float, str, bytes)):
        return x
    if isinstance(x, int):
        if _MSGPACK_INT_MIN <= x <= _MSGPACK_INT_MAX:
            return x
        return ["I", hex(x)]
    if isinstance(x, np.ndarray):
        return ["a", _pack_leaf(x)]
    if isinstance(x, np.generic):
        return ["a0", _pack_leaf(x)]
    if isinstance(x, tuple) and hasattr(x, "_fields"):
        cname = type(x).__name__
        classes.setdefault(cname, type(x))
        return ["nt", cname, [_tag_state(v, classes) for v in x]]
    if isinstance(x, tuple):
        return ["t", [_tag_state(v, classes) for v in x]]
    if isinstance(x, list):
        return ["l", [_tag_state(v, classes) for v in x]]
    if isinstance(x, (set, frozenset)):
        return ["s", [_tag_state(v, classes) for v in sorted(x)]]
    if isinstance(x, dict):
        if all(isinstance(k, str) and k not in ("I", "a", "a0", "nt", "t",
                                                "l", "s", "d")
               for k in x):
            return {k: _tag_state(v, classes) for k, v in x.items()}
        return ["d", [[_tag_state(k, classes), _tag_state(v, classes)]
                      for k, v in x.items()]]
    raise TypeError(f"save_state cannot serialize {type(x).__name__}")


def _untag_state(x, classes: dict):
    if isinstance(x, dict):
        return {k: _untag_state(v, classes) for k, v in x.items()}
    if not isinstance(x, list):
        return x
    tag = x[0]
    if tag == "I":
        return int(x[1], 16)
    if tag == "a":
        return np.asarray(_unpack_leaf(x[1]))
    if tag == "a0":
        return np.asarray(_unpack_leaf(x[1])).reshape(())[()]
    if tag == "nt":
        cls = classes.get(x[1])
        if cls is None:
            raise KeyError(
                f"restore_state needs the NamedTuple class {x[1]!r} in "
                "`classes` to rebuild this checkpoint")
        return cls(*[_untag_state(v, classes) for v in x[2]])
    if tag == "t":
        return tuple(_untag_state(v, classes) for v in x[1])
    if tag == "l":
        return [_untag_state(v, classes) for v in x[1]]
    if tag == "s":
        return set(_untag_state(v, classes) for v in x[1])
    if tag == "d":
        return {_untag_state(k, classes): _untag_state(v, classes)
                for k, v in x[1]}
    raise ValueError(f"unknown state tag {tag!r}")


def save_state(path: str, obj) -> None:
    """Serialize an arbitrary tagged-codec state object (see above) with
    the same compression + atomic-replace discipline as
    :func:`save_checkpoint`."""
    classes: dict = {}
    _write_compressed(path, msgpack.packb(_tag_state(obj, classes)))


def restore_state(path: str, classes: Optional[dict] = None):
    """Inverse of :func:`save_state`.  ``classes`` maps NamedTuple class
    names to their classes (needed to rebuild "nt" records)."""
    return _untag_state(msgpack.unpackb(_read_compressed(path),
                                        strict_map_key=False),
                        classes or {})
