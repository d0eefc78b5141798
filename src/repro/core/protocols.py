"""Pluggable communication codecs for federated optimization (paper Table I).

Every protocol is a :class:`Codec`: a frozen dataclass holding the protocol's
hyperparameters and implementing a small, jit-able interface.  The federated
trainer (:mod:`repro.fed.loop`) and the distributed mesh trainer
(:mod:`repro.launch.train`) call ONLY this interface -- there is no string
dispatch anywhere outside the registry lookup, so a new compressor drops in
without touching either trainer.

The interface (flat-vector path, used by :class:`repro.fed.FederatedTrainer`):

* ``init_client_state(numel)`` / ``init_server_state(numel)`` -- per-client /
  server codec state as a pytree (or ``None`` for stateless codecs); the
  trainer carries it through jit and stacks client states along a leading
  ``(n_clients,)`` axis (see ``residual.stack_states``).
* ``encode_batch(deltas, states)`` -- **batched-first** client-side
  compression of a whole ``(P, numel)`` round; returns ``(msgs, states,
  stats)`` with a leading client axis on every output.  The default
  implementation vmaps the single-vector :meth:`Codec.encode`; codecs with a
  genuinely batched implementation (STC's Pallas kernels) override it.
* ``aggregate(msgs, server_state, mask=None, staleness=None)`` -- server
  aggregation of the stacked ``(P, numel)`` messages plus downstream
  compression; returns ``(global_delta, server_state, stats)``.  ``mask`` is
  a per-message participation mask and ``staleness`` the per-message age in
  rounds (both ``(P,)``), used by the buffered/async trainer.  The combine
  estimator itself is the codec's pluggable ``rule``
  (:mod:`repro.core.aggregation`): the default ``mean`` rule is the
  staleness-decayed weighted mean of :meth:`Codec.combine` (``signsgd``
  then instead casts a weighted majority vote); ``coordinate_median`` /
  ``trimmed_mean`` / ``norm_screened_mean`` trade statistical efficiency
  for Byzantine robustness.  ``mask=None`` (the synchronous trainer) is
  the plain mean.
* ``upload_bits(numel)`` / ``download_bits(numel, n_participating)`` --
  analytic bit ledger (Eq. 1), host-side floats.
* ``encode_wire`` / ``decode_wire`` / ``encode_wire_batch`` +
  ``measured_upload_bits`` / ``measured_download_bits`` -- the REAL
  bitstream (host-side, :mod:`repro.core.wire`): codecs that set
  ``wire_format = True`` get exact measured bits in the trainers' ledgers,
  with the analytic formulas kept as a cross-check (``wire_bound_bits`` is
  the deterministic per-message ceiling asserted in tests).

The tree path (``tree_encode`` / ``tree_reduce`` / ``tree_decode``) is the
same protocol expressed over a parameter *pytree* for the shard_map trainer,
where flattening would force an all-gather; states there are bare residual
pytrees allocated by the trainer.

Codecs self-register::

    @register_protocol
    @dataclasses.dataclass(frozen=True)
    class MyCodec(Codec):
        name = "mine"
        def encode(self, delta, state): ...
        def upload_bits(self, numel): ...

``make_protocol(name, **overrides)`` stays the factory (paper defaults are
the dataclass field defaults).  Implemented codecs: the paper's comparison
set (``baseline`` / ``fedavg`` / ``signsgd`` / ``topk`` / ``stc``) plus
``ternquant`` -- dense ternary quantization in the style of T-FedAvg (Xu et
al., 2020) -- as the proof that third-party codecs are drop-in.
"""

from __future__ import annotations

import dataclasses
import inspect
import math
import warnings
from typing import ClassVar, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import golomb, wire
from .aggregation import AggregationRule, MeanRule, NormScreenedMeanRule, \
    make_rule
from .ingest import IngestAccumulator
from .registry import lookup as _registry_lookup, resolve as _registry_resolve
from .compression import (
    CompressionStats,
    get_stc_backend,
    majority_vote_sign,
    sign_compress,
    stc_compress_blocks,
    ternary_quantize,
    top_k_sparsify,
)
from .residual import ResidualState, compress_with_feedback, init_residual

__all__ = [
    "Codec", "Protocol", "make_protocol", "register_protocol",
    "registered_protocols", "get_protocol_class", "PROTOCOLS",
    "BaselineCodec", "FedAvgCodec", "SignSGDCodec", "TopKCodec", "StcCodec",
    "TernQuantCodec",
]


def _identity(x: jnp.ndarray) -> tuple[jnp.ndarray, CompressionStats]:
    stats = CompressionStats(
        nnz=jnp.asarray(x.size), numel=jnp.asarray(x.size), mu=jnp.asarray(0.0)
    )
    return x, stats


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, type["Codec"]] = {}


def register_protocol(cls=None, *, name: Optional[str] = None,
                      override: bool = False):
    """Register a :class:`Codec` subclass under ``name`` (default:
    ``cls.name``).  Usable as a bare decorator or with a name override.
    Re-registering an existing name with a *different* class raises unless
    ``override=True`` (typo-collisions with builtins should be loud)."""

    def _register(c):
        key = name if name is not None else getattr(c, "name", None)
        if not key:
            raise ValueError(f"codec {c!r} needs a `name` class attribute")
        prior = _REGISTRY.get(key)
        if prior is not None and prior is not c and not override:
            raise ValueError(
                f"protocol {key!r} is already registered to {prior.__name__}; "
                f"pass register_protocol(..., override=True) to replace it")
        _REGISTRY[key] = c
        return c

    return _register(cls) if cls is not None else _register


def registered_protocols() -> tuple[str, ...]:
    """Names of every registered codec (sorted)."""
    return tuple(sorted(_REGISTRY))


def get_protocol_class(name: str) -> type["Codec"]:
    return _registry_lookup("protocol", name, _REGISTRY)


# the pre-registry Protocol dataclass carried EVERY protocol's fields; for
# backward compatibility the factory still accepts this set on any codec,
# dropping the ones a codec does not declare (they were functionally inert)
_LEGACY_FIELDS = frozenset({"sparsity_up", "sparsity_down", "sign_step",
                            "error_feedback", "backend", "local_iters"})


def _instantiate_protocol(cls: type["Codec"], overrides: dict) -> "Codec":
    """``make_protocol``'s kwarg handling: declared fields pass through,
    legacy monolithic-Protocol fields drop silently when inert (loudly when
    they contradict a ClassVar), anything else is a typo."""
    fields = {f.name for f in dataclasses.fields(cls)}
    kwargs = {}
    for k, v in overrides.items():
        if k in fields:
            kwargs[k] = v
        elif k in _LEGACY_FIELDS:
            # inert on this codec in the old API too -- but refuse a value
            # that contradicts a ClassVar (e.g. error_feedback=False on stc)
            cur = getattr(cls, k, None)
            if cur is not None and cur != v:
                raise ValueError(
                    f"{cls.name!r} fixes {k}={cur!r}; "
                    f"override is not supported")
        else:
            raise TypeError(
                f"{cls.name!r} codec has no field {k!r}; declared fields: "
                f"{sorted(fields)}")
    return cls(**kwargs)


def make_protocol(name, **overrides) -> "Codec":
    """Factory with the paper's default hyperparameters (Section VI).
    Accepts a registered name (plus field overrides) or an already-built
    :class:`Codec` instance, which passes through untouched."""
    return _registry_resolve("protocol", name, _REGISTRY, Codec,
                             instantiate=_instantiate_protocol, **overrides)


# ---------------------------------------------------------------------------
# the abstract base
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Codec:
    """A (possibly stateful via explicit pytree state) compression protocol."""

    name: ClassVar[str] = ""
    error_feedback: ClassVar[bool] = False

    local_iters: int = 1                    # n (communication delay period)
    # staleness-weighted combining (buffered/async aggregation): an update
    # that is s rounds old enters the weighted mean with weight (1+s)^-decay
    # (FedBuff-style polynomial decay; 0.0 = ignore staleness entirely)
    staleness_decay: float = 0.5
    # DEPRECATED norm-bound screen (PR 8): forwarded to
    # ``rule=norm_screened_mean(bound=, policy=)`` with a DeprecationWarning;
    # setting them alongside an explicit ``rule`` raises.
    norm_bound: Optional[float] = None
    norm_policy: str = "clip"               # "clip" | "reject"
    # the server-side combine estimator: a registered AggregationRule name
    # or instance (see repro.core.aggregation).  ``None`` -> "mean", the
    # participation-weighted mean, bit-identical to the pre-rule combine.
    rule: Optional[AggregationRule] = None

    def __post_init__(self):
        if self.norm_policy not in ("clip", "reject"):
            raise ValueError(
                f"norm_policy must be 'clip' or 'reject', "
                f"got {self.norm_policy!r}")
        if self.norm_bound is not None and not self.norm_bound > 0.0:
            raise ValueError(
                f"norm_bound must be > 0 (or None), got {self.norm_bound}")
        rule = self.rule
        if self.norm_bound is not None:
            shim = NormScreenedMeanRule(bound=float(self.norm_bound),
                                        policy=self.norm_policy)
            if rule is None:
                warnings.warn(
                    "Codec(norm_bound=, norm_policy=) is deprecated; use "
                    "rule=make_rule('norm_screened_mean', bound=..., "
                    "policy=...) -- the shim forwards bit-identically for "
                    "one release", DeprecationWarning, stacklevel=3)
                rule = shim
            elif rule != shim:
                # (an equal rule instance means dataclasses.replace() of an
                # already-shimmed codec: re-normalizing is not a conflict)
                raise ValueError(
                    "norm_bound/norm_policy cannot be combined with an "
                    "explicit aggregation rule; fold the screen into "
                    "rule=make_rule('norm_screened_mean', bound=..., "
                    "policy=...)")
        object.__setattr__(
            self, "rule", make_rule(rule if rule is not None else "mean"))

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        # the pre-PR-4 2-arg aggregate/tree_reduce spelling is gone: fail
        # loudly at class-definition time, naming the migration, instead of
        # silently mis-aggregating masked rounds at runtime
        for meth in ("aggregate", "tree_reduce"):
            fn = cls.__dict__.get(meth)
            if fn is None or not callable(fn):
                continue
            try:
                params = inspect.signature(fn).parameters
            except (TypeError, ValueError):
                continue
            if any(p.kind is inspect.Parameter.VAR_KEYWORD
                   for p in params.values()):
                continue
            if "mask" not in params or "staleness" not in params:
                raise TypeError(
                    f"{cls.__name__}.{meth} predates the masked aggregation "
                    f"API: every codec now implements {meth}(..., mask=None, "
                    "staleness=None); the legacy 2-arg compatibility path "
                    "was removed with the AggregationRule redesign (see "
                    "README 'Migration notes')")

    # -- state ------------------------------------------------------------
    def init_client_state(self, numel: int):
        """One client's codec state pytree (None = stateless)."""
        return None

    def init_server_state(self, numel: int):
        return None

    # -- client side (upstream) --------------------------------------------
    def encode(self, delta: jnp.ndarray, state):
        """Compress ONE flat client update. Returns (msg, new_state, stats)."""
        raise NotImplementedError(type(self).__name__)

    def encode_batch(self, deltas: jnp.ndarray, states):
        """Compress a whole (P, numel) round. Returns (msgs, states, stats),
        every output carrying the leading client axis."""
        return jax.vmap(lambda d, s: self.encode(d, s))(deltas, states)

    # -- chunked (layer, chunk) block path ------------------------------------
    # A codec with ``chunk_blocks = True`` compresses a zero-padded
    # (P, n_chunks, chunk_numel) block tensor in ONE fused call with a static
    # per-chunk k vector, instead of the generic per-group loop of
    # :class:`repro.core.chunking.ChunkedCodec`.  Semantics contract: each
    # block is compressed EXACTLY as the flat codec would compress its
    # unpadded slice (padding is zero and must never be selected).

    chunk_blocks: ClassVar[bool] = False

    def encode_chunk_blocks(self, blocks, states, *, ks):
        """Fused chunked upstream compression; see ``chunk_blocks`` above."""
        raise NotImplementedError(
            f"{type(self).__name__} has no fused chunk-blocks path")

    def aggregate_chunk_blocks(self, blocks, server_state, *, ks, mask=None,
                               staleness=None):
        """Fused chunked aggregation + downstream compression."""
        raise NotImplementedError(
            f"{type(self).__name__} has no fused chunk-blocks path")

    # Adaptive-controller variants (repro.core.adaptive): per-chunk k is no
    # longer static -- the controller observes the error-feedback pre-image
    # inside the jitted round and its (optional) state threads through the
    # call.  Only meaningful for ``chunk_blocks = True`` codecs.

    def encode_chunk_blocks_adaptive(self, blocks, states, controller,
                                     ctrl_state, *, base_ks, caps):
        """Fused upstream compression with controller-chosen per-chunk k.

        Returns ``(tern, new_states, new_ctrl_state, stats)``."""
        raise NotImplementedError(
            f"{type(self).__name__} has no adaptive chunk-blocks path")

    def aggregate_chunk_blocks_adaptive(self, blocks, server_state,
                                        controller, ctrl_state, *, base_ks,
                                        caps, mask=None, staleness=None):
        """Fused aggregation + downstream compression with controller-chosen
        per-chunk k.  Returns ``(out, new_state, new_ctrl_state, stats)``."""
        raise NotImplementedError(
            f"{type(self).__name__} has no adaptive chunk-blocks path")

    # -- server side (aggregation + downstream) -----------------------------
    def participation_weights(self, mask, staleness=None) -> jnp.ndarray:
        """Per-message combining weights ``w_i = mask_i * (1+s_i)^-decay``.

        ``mask`` is the (P,) participation mask (1 = arrived, 0 = absent /
        padding) and ``staleness`` the (P,) per-message age in rounds; with
        ``staleness=None`` (or all zeros) the weights are exactly the mask,
        so an all-ones mask reproduces the synchronous combine bit for bit.
        """
        w = jnp.asarray(mask, jnp.float32)
        if staleness is not None:
            decay = (1.0 + jnp.asarray(staleness, jnp.float32)) \
                ** (-self.staleness_decay)
            w = w * decay
        return w

    def combine(self, msgs: jnp.ndarray, mask=None, staleness=None):
        """Combine (P, ...) messages over the client axis through the
        codec's :class:`AggregationRule`: the rule's screen runs on the raw
        mask (a rejected message loses its weight BEFORE staleness decay),
        then the rule combines under ``participation_weights``.  With the
        default ``mean`` rule this is bit-identical to the historical
        combine -- the plain mean when unmasked, otherwise the
        staleness-weighted mean (weight mass 0 combines to zero)."""
        msgs, mask = self.rule.screen(msgs, mask)
        if mask is None and staleness is None:
            return self.rule.combine_weighted(msgs, None)
        if mask is None:
            mask = jnp.ones(msgs.shape[0], jnp.float32)
        w = self.participation_weights(mask, staleness)
        return self.rule.combine_weighted(msgs, w)

    def aggregate(self, msgs: jnp.ndarray, server_state, mask=None,
                  staleness=None):
        """Aggregate (P, numel) messages. Returns (global_delta, state, stats).

        ``mask`` / ``staleness`` (both (P,), optional) come from the buffered
        trainer: only ``mask>0`` rows count, each weighted by the codec's
        staleness decay (see :meth:`combine`).  ``None`` = synchronous round.
        """
        mean = self.combine(msgs, mask, staleness)
        out, stats = _identity(mean)
        return out, server_state, stats

    # -- bit ledger ----------------------------------------------------------
    def upload_bits(self, numel: int) -> float:
        raise NotImplementedError(type(self).__name__)

    def download_bits(self, numel: int, n_participating: int = 1) -> float:
        raise NotImplementedError(type(self).__name__)

    # -- wire format (host-side measured ledger) -----------------------------
    # A codec with ``wire_format = True`` can serialize its messages to the
    # REAL bitstream, so trainers account measured bits (exact stream length
    # + ``wire_header_bits`` of side information per message) instead of the
    # analytic expectations above -- which are then kept as a cross-check.

    wire_format: ClassVar[bool] = False
    wire_header_bits: ClassVar[float] = 0.0
    # True when the wire size is statically known (measured == analytic by
    # construction, e.g. a dense 1-bit sign plane): trainers then skip the
    # per-round device->host transfer + serialization unless explicitly
    # asked to measure anyway.
    wire_static_size: ClassVar[bool] = False

    def encode_wire(self, msg: np.ndarray, *,
                    direction: str = "up") -> wire.WireMessage:
        """Serialize ONE already-compressed message to its wire bitstream."""
        raise NotImplementedError(
            f"{type(self).__name__} has no wire format")

    def decode_wire(self, msg: wire.WireMessage, *,
                    direction: str = "up") -> np.ndarray:
        """Inverse of :meth:`encode_wire`, exact up to the wire format's
        resolution (STC's position stream is lossless; a 1-bit sign plane
        cannot represent exact zeros -- see :func:`wire.pack_sign_words`)."""
        raise NotImplementedError(
            f"{type(self).__name__} has no wire format")

    def validate_wire(self, msg: wire.WireMessage, *,
                      direction: str = "up") -> None:
        """Admission-control validation of ONE arriving wire message:
        raises :class:`wire.WireDecodeError` on any corruption class the
        decoder can detect (truncated words, dangling unary runs, position
        or nnz overflow), returns None on success.  The default decodes the
        full message and discards it; codecs with a cheaper structural
        check (STC's fields-only parse, signSGD's size check) override it.
        """
        self.decode_wire(msg, direction=direction)

    def wire_norm(self, msg: wire.WireMessage) -> float:
        """Cheap l2-norm estimate of ONE encoded message, from its wire
        side information alone (no decode) -- the ingest paths' input to
        a screening rule's ``screen_weight``."""
        raise NotImplementedError(
            f"{type(self).__name__} has no wire-norm estimate; norm "
            "screening on the wire ingest path needs wire_norm()")

    def encode_wire_batch(self, msgs: np.ndarray, *,
                          direction: str = "up") -> wire.WireBatch:
        """Serialize a stacked (P, numel) round of messages.  Codecs with a
        genuinely batched packer (STC) override this fallback."""
        return wire.concat_messages([
            self.encode_wire(m, direction=direction)
            for m in np.asarray(msgs)])

    def measured_batch_bits(self, batch: wire.WireBatch) -> float:
        """Total size of an already-encoded batch (override for codecs with
        non-constant per-message side information)."""
        return batch.total_bits() + batch.n_msgs * self.wire_header_bits

    def measured_message_bits(self, msg: wire.WireMessage) -> float:
        """Total size of ONE already-encoded message (stream + header)."""
        return msg.bit_len + self.wire_header_bits

    def measured_upload_bits(self, msgs: np.ndarray) -> float:
        """EXACT upstream bits for a (P, numel) stack of compressed client
        messages; falls back to the analytic model for wire-less codecs."""
        msgs = np.asarray(msgs)
        if not self.wire_format:
            return msgs.shape[0] * self.upload_bits(msgs.shape[-1])
        return self.measured_batch_bits(
            self.encode_wire_batch(msgs, direction="up"))

    def measured_download_bits(self, msg: np.ndarray,
                               n_participating: int = 1) -> float:
        """EXACT bits of ONE downstream (global update) message.

        ``n_participating`` only matters for the analytic fallback of
        wire-less codecs (whose downstream density can grow with the
        cohort, e.g. topk); a real wire stream is measured as-is."""
        msg = np.asarray(msg)
        if not self.wire_format:
            return self.download_bits(msg.size,
                                      n_participating=n_participating)
        return self.measured_message_bits(self.encode_wire(msg,
                                                           direction="down"))

    def wire_bound_bits(self, numel: int, nnz: int,
                        direction: str = "up") -> Optional[float]:
        """Deterministic per-message ceiling on the measured size (stream
        PLUS header bits; None = no bound known); trainers log it so tests
        can assert ``measured <= bound`` round by round."""
        return None

    # -- fused decode→aggregate ingestion (repro.core.ingest) ----------------
    # A codec with ``supports_ingest = True`` can consume a round as a STREAM
    # of arriving messages: each upload scatters into one O(numel)
    # :class:`IngestAccumulator` at arrival time (``ingest_wire`` /
    # ``ingest_dense``), and ``aggregate_ingest`` finalizes the round from
    # the accumulator alone -- the dense (P, numel) message block never
    # exists.  Contract (property-tested): ``ingest_wire*`` is bit-identical
    # to decoding every message dense and feeding it through
    # ``ingest_dense`` (the oracle), and both share ``finalize_ingest``.

    supports_ingest: ClassVar[bool] = False

    def make_ingest(self, numel: int) -> IngestAccumulator:
        """A fresh per-round accumulator sized for the flat message vector."""
        if not self.supports_ingest:
            raise NotImplementedError(
                f"{type(self).__name__} has no ingest path")
        if not self.rule.supports_streaming:
            raise NotImplementedError(
                f"aggregation rule {self.rule.name!r} needs every client's "
                "coordinates at once and cannot stream through "
                "IngestAccumulator; use the dense aggregate path (trainers "
                "asked for ingest=True fall back automatically)")
        return IngestAccumulator(numel)

    def ingest_dense(self, acc: IngestAccumulator, vec: np.ndarray,
                     weight: float) -> None:
        """One dense (decoded, or never wire-encoded) message into the
        accumulator -- the fused wire paths' bit-exactness oracle."""
        if self.rule.screens:
            norm = float(np.linalg.norm(np.asarray(vec, np.float64)))
            scale, rejected = self.rule.screen_weight(norm)
            if rejected:
                acc.begin_message(0.0)
                acc.note_screened()
                return
            acc.begin_message(weight)
            acc.add_dense(vec, weight * scale)
            return
        acc.begin_message(weight)
        acc.add_dense(vec, weight)

    def ingest_wire_chunk(self, acc: IngestAccumulator, msg, weight: float,
                          *, direction: str = "up", offset: int = 0) -> None:
        """Scatter ONE wire sub-stream at flat ``offset`` (no per-message
        bookkeeping: chunked codecs call this once per chunk)."""
        raise NotImplementedError(
            f"{type(self).__name__} has no wire ingest path")

    def ingest_wire(self, acc: IngestAccumulator, msg, weight: float, *,
                    direction: str = "up") -> None:
        """One arriving wire message: account its weight + measured bits,
        then scatter its decoded fields into the accumulator.  Under a
        screening rule, the message's wire-side norm estimate is screened
        first -- a rejected message still bills its bits but enters the
        aggregate with zero weight."""
        bits = self.measured_message_bits(msg)
        if self.rule.screens:
            scale, rejected = self.rule.screen_weight(self.wire_norm(msg))
            if rejected:
                acc.begin_message(0.0, bits=bits)
                acc.note_screened()
                return
            acc.begin_message(weight, bits=bits)
            self.ingest_wire_chunk(acc, msg, weight * scale,
                                   direction=direction)
            return
        acc.begin_message(weight, bits=bits)
        self.ingest_wire_chunk(acc, msg, weight, direction=direction)

    def ingest_wire_batch(self, acc: IngestAccumulator, batch, weights, *,
                          direction: str = "up") -> None:
        """A whole encoded round, message-major.  The default loops
        :meth:`ingest_wire`; codecs with a batched field decoder (STC)
        override it with one fused decode + scatter."""
        for i, w in enumerate(np.asarray(weights, np.float64)):
            self.ingest_wire(acc, batch.message(i), float(w),
                             direction=direction)

    def finalize_ingest(self, combined, server_state):
        """Downstream compression of the accumulator's weighted mean; the
        ingest twin of the tail of :meth:`aggregate`.  Returns
        ``(global_delta, new_server_state, stats)``."""
        raise NotImplementedError(
            f"{type(self).__name__} has no ingest path")

    def aggregate_ingest(self, acc: IngestAccumulator, server_state):
        """Finalize a round straight from the accumulator (both the fused
        wire path and the dense oracle end here, so they agree bitwise)."""
        return self.finalize_ingest(acc.combined(), server_state)

    # -- tree path (distributed shard_map trainer) ---------------------------
    def has_client_state(self) -> bool:
        return self.init_client_state(0) is not None

    def has_server_state(self) -> bool:
        return self.init_server_state(0) is not None

    def tree_encode(self, delta, residual, *, numel: int, iters: int = 32):
        """Client-side compression over a parameter pytree.  ``residual`` is a
        bare fp32 pytree (or None). Returns (msg_tree, new_residual, metrics).
        """
        return delta, residual, {}

    def _tree_reduce_gather(self, msgs, axes, mask, staleness):
        """Order-statistic rules need every shard's coordinates at once:
        all_gather the per-shard message trees plus their weight mass, then
        run the rule once per leaf.  O(n_shards * numel) on the interconnect
        where the mean-family psum is O(numel) -- the price of a nonlinear
        estimator, paid only when such a rule is configured."""
        rule = self.rule
        if mask is None:
            mask = jnp.ones((1,), jnp.float32)
        w = jnp.sum(self.participation_weights(mask, staleness))
        if not axes:
            return jax.tree.map(lambda t: rule.combine(t[None], w[None]),
                                msgs)
        ws = jax.lax.all_gather(w, axes)
        return jax.tree.map(
            lambda t: rule.combine(jax.lax.all_gather(t, axes), ws), msgs)

    def tree_reduce(self, msgs, axes, n_clients: int, mask=None,
                    staleness=None):
        """The one protocol-level collective: combine per-client message trees
        over the manual mesh axes ``axes``.

        Mean-family rules reduce via the historical (bit-identical) psum
        paths below; other rules route through the gathered
        :meth:`_tree_reduce_gather`.  ``mask`` / ``staleness`` are THIS
        shard's slice of the per-client participation mask and staleness
        vectors (shape ``(local_clients,)`` inside shard_map): a masked-out
        shard contributes zero weight, so a dropped client no longer stalls
        or skews the step, and the weighted psum renormalizes by the total
        arrived weight mass.
        """
        if not isinstance(self.rule, MeanRule):
            return self._tree_reduce_gather(msgs, axes, mask, staleness)
        if mask is None and staleness is None:
            if axes:
                return jax.tree.map(
                    lambda t: jax.lax.psum(t, axes) / n_clients, msgs)
            return msgs
        if mask is None:
            mask = jnp.ones((1,), jnp.float32)
        w = jnp.sum(self.participation_weights(mask, staleness))
        if axes:
            total = jax.lax.psum(w, axes)
            denom = jnp.where(total > 0, total, 1.0)
            return jax.tree.map(
                lambda t: jax.lax.psum(w * t, axes) / denom, msgs)
        denom = jnp.where(w > 0, w, 1.0)
        return jax.tree.map(lambda t: w * t / denom, msgs)

    def tree_decode(self, combined, residual, *, numel: int, iters: int = 32):
        """Server-side downstream compression of the combined tree.  Returns
        (global_delta_tree, new_server_residual, metrics)."""
        return combined, residual, {}


# Deprecated alias: `Protocol` was the pre-registry monolithic class.
Protocol = Codec


# ---------------------------------------------------------------------------
# error-feedback mixin: EF codecs share state init + the carried-vector step
# ---------------------------------------------------------------------------


class _ErrorFeedbackMixin:
    error_feedback: ClassVar[bool] = True

    def init_client_state(self, numel: int) -> ResidualState:
        return init_residual(jnp.zeros((numel,), jnp.float32))


# ---------------------------------------------------------------------------
# the paper's comparison set (Table I)
# ---------------------------------------------------------------------------


@register_protocol
@dataclasses.dataclass(frozen=True)
class BaselineCodec(Codec):
    """Uncompressed distributed SGD: dense fp32 both ways."""

    name: ClassVar[str] = "baseline"

    def encode(self, delta, state):
        msg, stats = _identity(delta)
        return msg, state, stats

    def upload_bits(self, numel: int) -> float:
        return golomb.fedavg_message_bits(numel)

    def download_bits(self, numel: int, n_participating: int = 1) -> float:
        return golomb.fedavg_message_bits(numel)


@register_protocol
@dataclasses.dataclass(frozen=True)
class FedAvgCodec(BaselineCodec):
    """Federated Averaging: dense messages every ``local_iters`` iterations."""

    name: ClassVar[str] = "fedavg"

    local_iters: int = 400


@register_protocol
@dataclasses.dataclass(frozen=True)
class SignSGDCodec(Codec):
    """signSGD with majority vote (Bernstein et al. '18); δ = ``sign_step``."""

    name: ClassVar[str] = "signsgd"

    sign_step: float = 2e-4
    wire_backend: str = "numpy"             # wire packer: "numpy" | "kernel"

    wire_format: ClassVar[bool] = True      # dense sign plane, 1 bit/coord
    wire_static_size: ClassVar[bool] = True  # numel bits, exactly, always
    supports_ingest: ClassVar[bool] = True

    def encode(self, delta, state):
        msg, stats = sign_compress(delta, self.sign_step)
        return msg, state, stats

    def encode_wire(self, msg, *, direction="up"):
        return wire.pack_sign_words(msg, self.sign_step,
                                    backend=self.wire_backend)

    def decode_wire(self, msg, *, direction="up"):
        return wire.unpack_sign_words(msg)

    def validate_wire(self, msg, *, direction="up"):
        # a sign plane is exactly numel bits; anything else is truncation
        # or padding corruption, by construction
        if int(msg.bit_len) != int(msg.numel):
            raise wire.WireDecodeError(
                "corrupt sign plane: bit_len != numel")
        wire.sign_plane_bits(msg, backend=self.wire_backend)

    def wire_norm(self, msg):
        # every coordinate is exactly ±sign_step, so the norm is constant
        # (the screen is inert here unless the bound is set below it)
        return self.sign_step * math.sqrt(int(msg.numel))

    def wire_bound_bits(self, numel, nnz, direction="up"):
        return float(numel)                 # measured == analytic, exactly

    # ---- fused ingest: the vote tally IS the weighted plane sum ----
    def ingest_wire_chunk(self, acc, msg, weight, *, direction="up",
                          offset=0):
        bits01 = wire.sign_plane_bits(msg, backend=self.wire_backend)
        acc.add_sign_plane(bits01, self.sign_step, weight, offset=offset)

    def finalize_ingest(self, combined, server_state):
        # sign(weighted mean) == sign(weighted vote tally): the arrived
        # mass is positive, and the wire planes are exactly ±step.  Ingest
        # aggregates the WIRE truth (a dense message's exact zeros were
        # already -step on the wire -- see wire.pack_sign_words).
        out = self.sign_step * jnp.sign(jnp.asarray(combined))
        _, stats = _identity(out)
        stats = stats._replace(mu=jnp.asarray(self.sign_step))
        return out, server_state, stats

    def aggregate(self, msgs, server_state, mask=None, staleness=None):
        if not isinstance(self.rule, MeanRule):
            # order-statistic rules: combine the ±step messages through the
            # rule, then re-quantize to the sign plane the downstream wire
            # format requires (a coordinate's median of ±step values lies
            # in {-step, 0, +step} already)
            out = self.sign_step * jnp.sign(
                self.combine(msgs, mask, staleness))
            _, stats = _identity(out)
            stats = stats._replace(mu=jnp.asarray(self.sign_step))
            return out, server_state, stats
        # mean family: the weighted majority vote (its own robust estimator
        # over sign planes), bit-identical to the pre-rule aggregate
        weights = None
        if mask is not None or staleness is not None:
            if mask is None:
                mask = jnp.ones(msgs.shape[0], jnp.float32)
            weights = self.participation_weights(mask, staleness)
        out = majority_vote_sign(msgs, self.sign_step, weights=weights)
        _, stats = _identity(out)
        stats = stats._replace(mu=jnp.asarray(self.sign_step))
        return out, server_state, stats

    def upload_bits(self, numel: int) -> float:
        return golomb.signsgd_message_bits(numel)

    def download_bits(self, numel: int, n_participating: int = 1) -> float:
        return golomb.signsgd_message_bits(numel)

    # ---- tree path ----
    def tree_encode(self, delta, residual, *, numel, iters=32):
        from .distributed import sign_compress_tree
        return sign_compress_tree(delta, self.sign_step), residual, {}

    def tree_reduce(self, msgs, axes, n_clients, mask=None, staleness=None):
        if not isinstance(self.rule, MeanRule):
            # gathered rule over the ±step trees; tree_decode's sign()
            # re-quantizes the combined tree either way
            return self._tree_reduce_gather(msgs, axes, mask, staleness)
        if mask is None and staleness is None:
            if axes:
                return jax.tree.map(
                    lambda t: jax.lax.psum(jnp.sign(t), axes), msgs)
            return jax.tree.map(jnp.sign, msgs)
        # weighted vote: an absent shard casts no vote (weight 0); no
        # renormalization -- tree_decode takes the sign of the tally anyway
        if mask is None:
            mask = jnp.ones((1,), jnp.float32)
        w = jnp.sum(self.participation_weights(mask, staleness))
        if axes:
            return jax.tree.map(
                lambda t: jax.lax.psum(w * jnp.sign(t), axes), msgs)
        return jax.tree.map(lambda t: w * jnp.sign(t), msgs)

    def tree_decode(self, combined, residual, *, numel, iters=32):
        out = jax.tree.map(
            lambda v: self.sign_step * jnp.sign(v), combined)
        return out, residual, {}


# topk wire format: naive 16-bit distance coding per position (the paper's
# comparison baseline, Appx. A) + one fp32 value per surviving entry.
_TOPK_POSITION_BITS = 16.0
_TOPK_VALUE_BITS = 32.0


@register_protocol
@dataclasses.dataclass(frozen=True)
class TopKCodec(_ErrorFeedbackMixin, Codec):
    """Upload-only top-k sparsification + error feedback (Aji/Lin)."""

    name: ClassVar[str] = "topk"

    sparsity_up: float = 1 / 400

    def encode(self, delta, state):
        return compress_with_feedback(
            delta, state, lambda v: top_k_sparsify(v, self.sparsity_up))

    def _message_bits(self, numel: int, nnz: int) -> float:
        """Sparse message cost shared by the up/down ledger entries: 16-bit
        positions + 32-bit values, densifying to plain fp32 when full."""
        if nnz >= numel:
            return golomb.fedavg_message_bits(numel)
        return nnz * (_TOPK_POSITION_BITS + _TOPK_VALUE_BITS)

    def upload_bits(self, numel: int) -> float:
        k = max(int(numel * self.sparsity_up), 1)
        return self._message_bits(numel, k)

    def download_bits(self, numel: int, n_participating: int = 1) -> float:
        # upload-only compression: downstream density grows with clients
        # (Section V-A) until the update is effectively dense.
        k = max(int(numel * self.sparsity_up), 1)
        return self._message_bits(numel, min(k * n_participating, numel))

    # ---- tree path ----
    def tree_encode(self, delta, residual, *, numel, iters=32):
        from .distributed import stc_compress_tree, tree_add
        carried = tree_add(delta, residual)
        _, st = stc_compress_tree(carried, self.sparsity_up, numel=numel,
                                  iters=iters)
        # pure top-k keeps magnitudes: mask = |x| >= thresh
        msg = jax.tree.map(
            lambda x: jnp.where(jnp.abs(x) >= st.thresh, x, 0.0), carried)
        new_res = jax.tree.map(lambda c, t: c - t, carried, msg)
        return msg, new_res, {"nnz_up": st.nnz}


@register_protocol
@dataclasses.dataclass(frozen=True)
class StcCodec(_ErrorFeedbackMixin, Codec):
    """The paper's contribution: bidirectional sparse ternary compression +
    error feedback + Golomb-coded messages."""

    name: ClassVar[str] = "stc"

    sparsity_up: float = 1 / 400
    sparsity_down: float = 1 / 400
    backend: str = "jnp"                    # STC impl: "jnp" | "kernel"
    wire_backend: str = "numpy"             # wire packer: "numpy" | "kernel"
    # tree-path chunking (the mesh trainer's TrainConfig.chunks): when set,
    # tree_encode/tree_decode select per (leaf, chunk) block through the
    # backend registry instead of one global flat top-k -- selection then
    # stays local to each shard and pipelines across the mesh.  ``p_fn``
    # is the per-layer sparsity schedule hook (p_fn(layer_name, depth)).
    # The FLAT trainers chunk by wrapping (see repro.core.chunking); this
    # field only drives the tree path.
    chunk_size: Optional[int] = None
    p_fn: Optional[object] = None
    # adaptive per-chunk sparsity controller (repro.core.adaptive): a
    # registered name or SparsityController instance.  Like ``p_fn``, this
    # field only drives the TREE path; the flat trainers thread their
    # controller through chunk_codec(..., controller=) instead.
    controller: Optional[object] = None

    wire_format: ClassVar[bool] = True      # Golomb position stream (Alg. 3)
    wire_header_bits: ClassVar[float] = 32.0  # fp32 µ per message (Eq. 15)
    chunk_blocks: ClassVar[bool] = True     # fused (P, chunk, W) block path
    supports_ingest: ClassVar[bool] = True

    def init_server_state(self, numel: int) -> ResidualState:
        return init_residual(jnp.zeros((numel,), jnp.float32))

    def _wire_p(self, direction: str) -> float:
        return self.sparsity_up if direction == "up" else self.sparsity_down

    def encode_wire(self, msg, *, direction="up"):
        return wire.encode_ternary_words(msg, self._wire_p(direction),
                                         backend=self.wire_backend)

    def decode_wire(self, msg, *, direction="up"):
        return wire.decode_ternary_words(msg, self._wire_p(direction))

    def validate_wire(self, msg, *, direction="up"):
        # fields-only parse: every decoder corruption check fires without
        # materializing the dense vector
        wire.decode_ternary_fields(msg, self._wire_p(direction),
                                   backend=self.wire_backend)

    def wire_norm(self, msg):
        # a ternary message is nnz coordinates of magnitude |µ| exactly;
        # abs() matters: a Byzantine sign-flip negates µ on an otherwise
        # valid stream, and a negative "norm" would sail past the screen
        return abs(float(msg.mu)) * math.sqrt(max(int(msg.nnz), 0))

    def encode_wire_batch(self, msgs, *, direction="up"):
        return wire.encode_ternary_words_batch(
            np.asarray(msgs), self._wire_p(direction),
            backend=self.wire_backend)

    def wire_bound_bits(self, numel, nnz, direction="up"):
        return golomb.stc_stream_bound_bits(numel, nnz,
                                            self._wire_p(direction))

    def encode(self, delta, state):
        be = get_stc_backend(self.backend)
        msg, new_res, stats = be.compress_with_residual(
            delta, state.residual, self.sparsity_up)
        return msg, ResidualState(residual=new_res), stats

    def encode_batch(self, deltas, states):
        # one batched backend call (a single kernel launch per stage on the
        # "kernel" backend) instead of a vmap of selections
        be = get_stc_backend(self.backend)
        msgs, new_res, stats = be.compress_with_residual_batch(
            deltas, states.residual, self.sparsity_up)
        return msgs, ResidualState(residual=new_res), stats

    def aggregate(self, msgs, server_state, mask=None, staleness=None):
        be = get_stc_backend(self.backend)
        mean = self.combine(msgs, mask, staleness)
        out, new_res, stats = be.compress_with_residual(
            mean, server_state.residual, self.sparsity_down)
        return out, ResidualState(residual=new_res), stats

    # ---- fused ingest: Golomb fields -> accumulator scatter ----
    def ingest_wire_chunk(self, acc, msg, weight, *, direction="up",
                          offset=0):
        pos, signs = wire.decode_ternary_fields(
            msg, self._wire_p(direction), backend=self.wire_backend)
        acc.scatter_ternary(pos, signs, msg.mu, weight, offset=offset)

    #: fused-ingest decode block: rows are grouped so each multi-segment
    #: decode pass touches at most this many stream words, keeping the
    #: decode workspace bounded regardless of how many clients arrive.
    ingest_block_words: ClassVar[int] = 1 << 16

    def ingest_wire_batch(self, acc, batch, weights, *, direction="up"):
        # multi-segment field decode + one scatter per bounded word block
        # (bitwise the sequential ingest_wire loop: np.add.at applies in
        # element order, and the fields come out message-major)
        if self.rule.screens:
            # screened rounds take the per-message path: the screen is
            # per-message anyway, and this keeps batch == oracle bitwise
            # (a rejected row must not scatter or count nnz)
            return Codec.ingest_wire_batch(self, acc, batch, weights,
                                           direction=direction)
        w = np.asarray(weights, np.float64)
        for i in range(batch.n_msgs):
            acc.begin_message(float(w[i]),
                              bits=float(batch.bit_len[i])
                              + self.wire_header_bits)
        p = self._wire_p(direction)
        i0, P = 0, batch.n_msgs
        while i0 < P:
            i1, words = i0, 0
            while i1 < P and (i1 == i0
                              or words + int(batch.word_count[i1])
                              <= self.ingest_block_words):
                words += int(batch.word_count[i1])
                i1 += 1
            sub = batch.rows(i0, i1)
            seg, pos, signs = wire.decode_ternary_fields_batch(
                sub, p, backend=self.wire_backend)
            acc.scatter_ternary_batch(seg, pos, signs, sub.mu, w[i0:i1])
            i0 = i1

    def finalize_ingest(self, combined, server_state):
        be = get_stc_backend(self.backend)
        out, new_res, stats = be.compress_with_residual(
            jnp.asarray(combined), server_state.residual, self.sparsity_down)
        return out, ResidualState(residual=new_res), stats

    # ---- fused chunked block path (repro.core.chunking) ----
    def encode_chunk_blocks(self, blocks, states, *, ks):
        """One ``select_batch`` launch over every (client, chunk) row."""
        P, C, W = blocks.shape
        carried = (blocks.astype(jnp.float32)
                   + states.residual.astype(jnp.float32))
        tern, cnt, mu = stc_compress_blocks(
            carried.reshape(P * C, W), np.tile(np.asarray(ks), P),
            backend=self.backend)
        tern = tern.reshape(P, C, W)
        stats = CompressionStats(nnz=cnt.reshape(P, C).sum(axis=1),
                                 numel=jnp.full(P, C * W),
                                 mu=mu.reshape(P, C).mean(axis=1))
        return tern, ResidualState(residual=carried - tern), stats

    def aggregate_chunk_blocks(self, blocks, server_state, *, ks, mask=None,
                               staleness=None):
        mean = self.combine(blocks, mask, staleness)        # (C, W)
        carried = mean + server_state.residual.astype(jnp.float32)
        tern, cnt, mu = stc_compress_blocks(carried, ks, backend=self.backend)
        stats = CompressionStats(nnz=jnp.sum(cnt),
                                 numel=jnp.asarray(carried.size),
                                 mu=jnp.mean(mu))
        return tern, ResidualState(residual=carried - tern), stats

    # ---- adaptive-controller chunked path (repro.core.adaptive) ----
    def encode_chunk_blocks_adaptive(self, blocks, states, controller,
                                     ctrl_state, *, base_ks, caps):
        """Controller-chosen per-(client, chunk) k: the controller observes
        the carried (update + residual) blocks and picks traced ks, bounded
        by the static ``caps``, then one dynamic ``select_batch`` sweep
        compresses every row."""
        P, C, W = blocks.shape
        carried = (blocks.astype(jnp.float32)
                   + states.residual.astype(jnp.float32))
        ks, new_ctrl = controller.chunk_ks(carried, ctrl_state,
                                           base_ks=base_ks, caps=caps)
        tern, cnt, mu = stc_compress_blocks(
            carried.reshape(P * C, W), jnp.asarray(ks).reshape(P * C),
            backend=self.backend, k_cap=int(np.asarray(caps).max()))
        tern = tern.reshape(P, C, W)
        stats = CompressionStats(nnz=cnt.reshape(P, C).sum(axis=1),
                                 numel=jnp.full(P, C * W),
                                 mu=mu.reshape(P, C).mean(axis=1))
        return (tern, ResidualState(residual=carried - tern), new_ctrl,
                stats)

    def aggregate_chunk_blocks_adaptive(self, blocks, server_state,
                                        controller, ctrl_state, *, base_ks,
                                        caps, mask=None, staleness=None):
        mean = self.combine(blocks, mask, staleness)        # (C, W)
        carried = mean + server_state.residual.astype(jnp.float32)
        ks, new_ctrl = controller.chunk_ks(carried[None], ctrl_state,
                                           base_ks=base_ks, caps=caps)
        tern, cnt, mu = stc_compress_blocks(
            carried, jnp.asarray(ks).reshape(carried.shape[0]),
            backend=self.backend, k_cap=int(np.asarray(caps).max()))
        stats = CompressionStats(nnz=jnp.sum(cnt),
                                 numel=jnp.asarray(carried.size),
                                 mu=jnp.mean(mu))
        return (tern, ResidualState(residual=carried - tern), new_ctrl,
                stats)

    def upload_bits(self, numel: int) -> float:
        return golomb.stc_message_bits(numel, self.sparsity_up)

    def download_bits(self, numel: int, n_participating: int = 1) -> float:
        return golomb.stc_message_bits(numel, self.sparsity_down)

    # ---- tree path ----
    def tree_encode(self, delta, residual, *, numel, iters=32):
        from .distributed import (stc_compress_tree,
                                  stc_compress_tree_chunked, tree_add)
        carried = tree_add(delta, residual)
        if self.chunk_size:
            tern, st = stc_compress_tree_chunked(
                carried, self.sparsity_up, self.chunk_size, p_fn=self.p_fn,
                backend=self.backend, controller=self.controller)
        else:
            tern, st = stc_compress_tree(carried, self.sparsity_up,
                                         numel=numel, iters=iters)
        new_res = jax.tree.map(lambda c, t: c - t, carried, tern)
        return tern, new_res, {"nnz_up": st.nnz}

    def tree_decode(self, combined, residual, *, numel, iters=32):
        from .distributed import (stc_compress_tree,
                                  stc_compress_tree_chunked, tree_add)
        carried = tree_add(combined, residual)
        if self.chunk_size:
            down, st = stc_compress_tree_chunked(
                carried, self.sparsity_down, self.chunk_size, p_fn=self.p_fn,
                backend=self.backend, controller=self.controller)
        else:
            down, st = stc_compress_tree(carried, self.sparsity_down,
                                         numel=numel, iters=iters)
        new_res = jax.tree.map(lambda c, t: c - t, carried, down)
        return down, new_res, {"nnz_down": st.nnz}


@register_protocol
@dataclasses.dataclass(frozen=True)
class TernQuantCodec(_ErrorFeedbackMixin, Codec):
    """Dense ternary quantization à la T-FedAvg (Xu et al., 2020).

    Every coordinate is quantized to {-µ, 0, +µ} with TWN thresholding
    (Δ = θ·mean|x|) and error feedback on both sides; the wire format is an
    uncoded dense ternary stream (log2(3) bits/weight -- no position coding).
    Ships as the registry's proof that third-party codecs are drop-in.
    """

    name: ClassVar[str] = "ternquant"

    theta: float = 0.75                     # TWN threshold factor

    supports_ingest: ClassVar[bool] = True  # dense ingest only (no wire)

    def init_server_state(self, numel: int) -> ResidualState:
        return init_residual(jnp.zeros((numel,), jnp.float32))

    def encode(self, delta, state):
        return compress_with_feedback(
            delta, state, lambda v: ternary_quantize(v, self.theta))

    def aggregate(self, msgs, server_state, mask=None, staleness=None):
        mean = self.combine(msgs, mask, staleness)
        return compress_with_feedback(
            mean, server_state, lambda v: ternary_quantize(v, self.theta))

    def finalize_ingest(self, combined, server_state):
        return compress_with_feedback(
            jnp.asarray(combined), server_state,
            lambda v: ternary_quantize(v, self.theta))

    def upload_bits(self, numel: int) -> float:
        return golomb.ternary_dense_bits(numel)

    def download_bits(self, numel: int, n_participating: int = 1) -> float:
        return golomb.ternary_dense_bits(numel)

    # ---- tree path ----
    def tree_encode(self, delta, residual, *, numel, iters=32):
        from .distributed import ternary_quantize_tree, tree_add
        carried = tree_add(delta, residual)
        tern, st = ternary_quantize_tree(carried, self.theta, numel=numel)
        new_res = jax.tree.map(lambda c, t: c - t, carried, tern)
        return tern, new_res, {"nnz_up": st.nnz}

    def tree_decode(self, combined, residual, *, numel, iters=32):
        from .distributed import ternary_quantize_tree, tree_add
        carried = tree_add(combined, residual)
        down, st = ternary_quantize_tree(carried, self.theta, numel=numel)
        new_res = jax.tree.map(lambda c, t: c - t, carried, down)
        return down, new_res, {"nnz_down": st.nnz}


# The paper's comparison set (Table I); the live registry may hold more.
PROTOCOLS = ("baseline", "fedavg", "signsgd", "topk", "stc")
