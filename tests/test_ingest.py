"""Fused decode→aggregate ingest path: bitwise-identity properties.

The server-side contract under test (core/ingest.py + the codec ingest
API): feeding a round's WIRE messages through ``ingest_wire_batch`` into
one O(numel) accumulator produces EXACTLY -- bit for bit -- the state the
dense oracle produces (``decode_wire`` each message, ``ingest_dense`` it),
for every registered codec with an ingest path, under masked/staleness-
weighted rounds, ragged chunk boundaries and empty clients.  Plus: the
streaming kernel-backend decode is bit-identical to the per-bit oracle on
adversarial streams (>= 32-one unary runs, mu = 0), corrupted payloads
raise typed ``WireDecodeError`` on both backends, and the trainers' opt-in
ingest mode reproduces the dense aggregation path end to end.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (WireDecodeError, chunk_codec, chunk_spec_from_sizes,
                        make_protocol, registered_protocols, wire)

DEMO = {"stc": dict(sparsity_up=1 / 8, sparsity_down=1 / 8)}


def _codec(name):
    return make_protocol(name, **DEMO.get(name, {}))


def _ingest_codecs():
    return [n for n in registered_protocols() if _codec(n).supports_ingest]


def _round_msgs(codec, P, numel, seed):
    """One round of REAL client messages (codec-compressed updates); client
    P-1 is empty (all-zero update -- an empty wire message for stc)."""
    rng = np.random.default_rng(seed)
    deltas = rng.standard_normal((P, numel)).astype(np.float32)
    deltas[P - 1] = 0.0
    states = codec.init_client_state(numel)
    if states is not None:
        import jax
        states = jax.tree.map(
            lambda leaf: jnp.stack([leaf] * P), states)
    msgs, _, _ = codec.encode_batch(jnp.asarray(deltas), states)
    return np.asarray(msgs)


def _weights(codec, P, seed):
    """Masked + staleness-decayed combining weights, fp64 host-side."""
    rng = np.random.default_rng(seed + 7)
    mask = (rng.random(P) < 0.7).astype(np.float32)
    mask[0] = 1.0                       # at least one arrival
    stal = rng.integers(0, 4, size=P)
    w = codec.participation_weights(jnp.asarray(mask), jnp.asarray(stal))
    return np.asarray(w, np.float64)


def _assert_fused_is_oracle(codec, numel, seed, P=4):
    msgs = _round_msgs(codec, P, numel, seed)
    w = _weights(codec, P, seed)
    state = codec.init_server_state(numel)

    if codec.wire_format:
        batch = codec.encode_wire_batch(msgs, direction="up")
        fused = codec.make_ingest(numel)
        codec.ingest_wire_batch(fused, batch, w, direction="up")
        dense_rows = [codec.decode_wire(batch.message(i), direction="up")
                      for i in range(P)]
    else:
        fused = codec.make_ingest(numel)
        for i in range(P):
            codec.ingest_dense(fused, msgs[i], float(w[i]))
        dense_rows = list(msgs)

    oracle = codec.make_ingest(numel)
    for i in range(P):
        codec.ingest_dense(oracle, dense_rows[i], float(w[i]))

    assert np.array_equal(fused.sum, oracle.sum)
    assert fused.weight_mass == oracle.weight_mass
    gd_f, st_f, _ = codec.aggregate_ingest(fused, state)
    gd_o, st_o, _ = codec.aggregate_ingest(oracle, state)
    assert np.array_equal(np.asarray(gd_f), np.asarray(gd_o))
    if st_f is not None:
        import jax
        for a, b in zip(jax.tree.leaves(st_f), jax.tree.leaves(st_o)):
            assert np.array_equal(np.asarray(a), np.asarray(b))


class TestFusedMatchesOracle:
    @pytest.mark.parametrize("name", sorted(set(_ingest_codecs())))
    def test_registry_codecs(self, name):
        _assert_fused_is_oracle(_codec(name), numel=257, seed=0)

    @given(st.integers(40, 400), st.integers(0, 10 ** 6))
    @settings(max_examples=15, deadline=None)
    def test_stc_property(self, numel, seed):
        _assert_fused_is_oracle(_codec("stc"), numel, seed)

    def test_empty_round(self):
        codec = _codec("stc")
        acc = codec.make_ingest(64)
        gd, _, _ = codec.aggregate_ingest(acc, codec.init_server_state(64))
        # no arrivals: the combined mean is zero (guarded denominator)
        assert np.all(np.isfinite(np.asarray(gd)))

    def test_unsupported_codec_is_loud(self):
        codec = _codec("topk")
        assert not codec.supports_ingest
        with pytest.raises(NotImplementedError):
            codec.finalize_ingest(jnp.zeros(8), None)

    def test_blocked_decode_matches_one_shot(self):
        # the bounded-workspace block loop must not change anything: force
        # single-message blocks and compare against one big block
        codec = _codec("stc")
        msgs = _round_msgs(codec, 4, 300, 3)
        w = _weights(codec, 4, 3)
        batch = codec.encode_wire_batch(msgs, direction="up")
        one = codec.make_ingest(300)
        codec.ingest_wire_batch(one, batch, w, direction="up")
        small = codec.make_ingest(300)
        try:
            type(codec).ingest_block_words = 1
            codec.ingest_wire_batch(small, batch, w, direction="up")
        finally:
            type(codec).ingest_block_words = 1 << 16
        assert np.array_equal(one.sum, small.sum)


class TestChunkedIngest:
    # ragged everything: uneven layers, chunk boundary mid-layer, empty layer
    @pytest.mark.parametrize("sizes,chunk", [
        ([40, 0, 33, 27], 13), ([7, 19, 5], 31), ([64], 64), ([2, 61], 1),
    ])
    @pytest.mark.parametrize("name", ["stc", "signsgd"])
    def test_ragged_chunks(self, name, sizes, chunk):
        spec = chunk_spec_from_sizes(sizes, chunk_size=chunk)
        codec = chunk_codec(_codec(name), spec)
        assert codec.supports_ingest
        _assert_fused_is_oracle(codec, spec.numel, seed=5)

    def test_single_message_path(self):
        spec = chunk_spec_from_sizes([40, 0, 33, 27], chunk_size=13)
        codec = chunk_codec(_codec("stc"), spec)
        msgs = _round_msgs(codec, 3, spec.numel, 1)
        w = _weights(codec, 3, 1)
        batch = codec.encode_wire_batch(msgs, direction="up")
        a = codec.make_ingest(spec.numel)
        codec.ingest_wire_batch(a, batch, w, direction="up")
        b = codec.make_ingest(spec.numel)
        for i in range(3):
            codec.ingest_wire(b, batch.message(i), float(w[i]),
                              direction="up")
        assert np.array_equal(a.sum, b.sum)
        assert a.stream_bits == b.stream_bits


class TestKernelDecode:
    @given(st.integers(64, 2048), st.integers(0, 10 ** 6))
    @settings(max_examples=10, deadline=None)
    def test_bit_identity_vs_numpy(self, numel, seed):
        rng = np.random.default_rng(seed)
        x = np.zeros(numel, np.float32)
        k = max(numel // 20, 1)
        x[rng.choice(numel, size=k, replace=False)] = \
            rng.choice((-1.0, 1.0), size=k)
        msg = wire.encode_ternary_words(x, 0.05)
        pa, sa = wire.decode_ternary_fields(msg, 0.05, backend="numpy")
        pb, sb = wire.decode_ternary_fields(msg, 0.05, backend="kernel")
        assert np.array_equal(pa, pb) and np.array_equal(sa, sb)

    @pytest.mark.parametrize("backend", ["numpy", "kernel"])
    def test_long_unary_run(self, backend):
        # a single nonzero at the very end of a big tensor forces a unary
        # run far past one 32-bit word (q >= 32 ones before the terminator)
        n = 1 << 15
        x = np.zeros(n, np.float32)
        x[n - 1] = 1.0
        p = 1 / 400
        msg = wire.encode_ternary_words(x, p)
        out = wire.decode_ternary_words(msg, p, backend=backend)
        assert np.array_equal(out, np.sign(x) * np.float32(msg.mu))

    @pytest.mark.parametrize("backend", ["numpy", "kernel"])
    def test_mu_zero(self, backend):
        x = np.zeros(128, np.float32)
        x[[3, 77]] = (1.0, -1.0)
        msg = wire.encode_ternary_words(x, 1 / 8)._replace(mu=0.0)
        pos, signs = wire.decode_ternary_fields(msg, 1 / 8, backend=backend)
        assert np.array_equal(pos, [3, 77])
        assert np.array_equal(wire.decode_ternary_words(msg, 1 / 8,
                                                        backend=backend),
                              np.zeros(128, np.float32))


class TestWireDecodeError:
    def _msg(self):
        x = np.zeros(200, np.float32)
        x[[5, 60, 150]] = (1.0, -1.0, 1.0)
        return wire.encode_ternary_words(x, 1 / 16)

    @pytest.mark.parametrize("backend", ["numpy", "kernel"])
    def test_truncated_codeword(self, backend):
        msg = self._msg()._replace(bit_len=3)
        with pytest.raises(WireDecodeError):
            wire.decode_ternary_fields(msg, 1 / 16, backend=backend)

    @pytest.mark.parametrize("backend", ["numpy", "kernel"])
    def test_no_terminator(self, backend):
        msg = self._msg()
        bad = msg._replace(
            words=np.full_like(msg.words, np.uint32(0xFFFFFFFF)))
        with pytest.raises(WireDecodeError):
            wire.decode_ternary_fields(bad, 1 / 16, backend=backend)

    @pytest.mark.parametrize("backend", ["numpy", "kernel"])
    def test_position_overflow(self, backend):
        msg = self._msg()._replace(numel=32)
        with pytest.raises(WireDecodeError):
            wire.decode_ternary_fields(msg, 1 / 16, backend=backend)

    def test_bit_len_past_buffer(self):
        msg = self._msg()
        bad = msg._replace(bit_len=32 * msg.words.size + 1)
        with pytest.raises(WireDecodeError):
            wire.decode_ternary_fields(bad, 1 / 16)

    def test_batch_raises_too(self):
        batch = wire.concat_messages([self._msg(), self._msg()])
        bad = batch._replace(bit_len=np.asarray([batch.bit_len[0], 3]))
        with pytest.raises(WireDecodeError):
            wire.decode_ternary_fields_batch(bad, 1 / 16)

    def test_error_is_a_valueerror(self):
        assert issubclass(WireDecodeError, ValueError)


class TestTrainerIngestMode:
    """Opt-in ``TrainerConfig(ingest=True)`` reproduces dense aggregation."""

    def _parts(self):
        from repro.data import make_classification
        from repro.fed import FedEnvironment
        from repro.models.paper_models import MODEL_ZOO
        data = make_classification(seed=0, n=600, n_test=160)
        env = FedEnvironment(n_clients=6, participation=0.5,
                             classes_per_client=2, batch_size=10)
        return MODEL_ZOO["logreg"], data, env

    @pytest.mark.parametrize("name", ["stc", "signsgd"])
    def test_sync_matches_dense(self, name):
        from repro.fed import FederatedTrainer, TrainerConfig
        model, (train, test), env = self._parts()
        accs, bits = [], []
        for ingest in (False, True):
            tr = FederatedTrainer(model, train, test, env, _codec(name),
                                  TrainerConfig(lr=0.05, seed=0,
                                                ingest=ingest))
            hist = tr.run(2, eval_every=2)
            accs.append(hist[-1]["acc"])
            bits.append(tr.bits_up)
        assert accs[0] == accs[1]
        assert bits[0] == bits[1]

    def test_buffered_matches_dense(self):
        from repro.fed import (BufferedFederatedTrainer, LatencyModel,
                               TrainerConfig)
        model, (train, test), env = self._parts()
        lat = LatencyModel(mean=0.4, sigma=0.4, hetero=0.3,
                           straggler_frac=0.2, straggler_scale=3.0)
        accs, bits = [], []
        for ingest in (False, True):
            tr = BufferedFederatedTrainer(
                model, train, test, env, _codec("stc"),
                TrainerConfig(lr=0.05, seed=0, ingest=ingest),
                latency=lat, deadline=0.8, max_staleness=4)
            hist = tr.run(3, eval_every=3)
            accs.append(hist[-1]["acc"])
            bits.append(tr.bits_up)
        assert accs[0] == pytest.approx(accs[1], abs=1e-6)
        assert bits[0] == pytest.approx(bits[1])

    def test_ingest_true_on_unsupported_codec_is_loud(self):
        from repro.fed import FederatedTrainer, TrainerConfig
        model, (train, test), env = self._parts()
        with pytest.raises(ValueError, match="no ingest path"):
            FederatedTrainer(model, train, test, env, _codec("topk"),
                             TrainerConfig(ingest=True))
