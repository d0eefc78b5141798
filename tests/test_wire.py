"""Wire-format subsystem: packed codec vs per-bit oracle (bit-identical),
adversarial round-trips, batched-vs-per-client equivalence, the Pallas
word-packing kernel, the UpdateCache prefix cache, and the measured-bits
ledger cross-check (measured <= Eq. 13/15-style bound) in a real fed run."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import UpdateCache, golomb, make_protocol, wire


def _random_ternary(n, p, seed):
    rng = np.random.default_rng(seed)
    x = np.zeros(n, np.float32)
    k = max(int(n * p), 1)
    idx = rng.choice(n, size=k, replace=False)
    mu = abs(float(rng.standard_normal())) + 0.1
    x[idx] = mu * rng.choice([-1.0, 1.0], size=k)
    return x


def _assert_stream_identical(msg: wire.WireMessage, x, p):
    """The packed stream must equal the per-bit oracle's, bit for bit."""
    payload, bit_len, mu, _ = golomb.encode_ternary(x, p)
    assert msg.bit_len == bit_len
    np.testing.assert_array_equal(msg.payload_bytes(), payload)
    if msg.nnz:
        assert msg.mu == pytest.approx(mu, rel=1e-6)


class TestPackedVsOracle:
    @given(st.integers(1, 3000), st.floats(0.005, 0.25),
           st.integers(0, 2**31 - 1))
    @settings(max_examples=40, deadline=None)
    def test_bit_identical_and_roundtrip(self, n, p, seed):
        x = _random_ternary(n, p, seed)
        msg = wire.encode_ternary_words(x, p)
        _assert_stream_identical(msg, x, p)
        np.testing.assert_allclose(wire.decode_ternary_words(msg, p), x,
                                   atol=1e-6)

    def test_mismatched_b_star(self):
        """Encoding with a p far from the realized sparsity exercises long
        unary runs (multi-chunk codewords) and the b*=0 edge."""
        for p_data, p_wire in [(0.001, 0.9), (0.3, 0.002), (0.05, 0.9),
                               (0.9, 0.005)]:
            x = _random_ternary(4096, p_data, seed=7)
            msg = wire.encode_ternary_words(x, p_wire)
            _assert_stream_identical(msg, x, p_wire)
            np.testing.assert_allclose(wire.decode_ternary_words(msg, p_wire),
                                       x, atol=1e-6)

    def test_empty_and_all_zero(self):
        for n in (0, 1, 100):
            msg = wire.encode_ternary_words(np.zeros(n, np.float32), 0.01)
            assert msg.bit_len == 0 and msg.words.size == 0 and msg.nnz == 0
            np.testing.assert_array_equal(wire.decode_ternary_words(msg, 0.01),
                                          np.zeros(n, np.float32))

    def test_mu_zero_stream(self):
        """µ=0 decodes every coded position to 0 without corrupting state."""
        x = _random_ternary(500, 0.02, seed=3)
        msg = wire.encode_ternary_words(x, 0.02)
        zeroed = wire.WireMessage(msg.words, msg.bit_len, 0.0, msg.numel,
                                  msg.nnz)
        out = wire.decode_ternary_words(zeroed, 0.02)
        np.testing.assert_array_equal(out, np.zeros_like(x))

    def test_odd_tail_lengths(self):
        """bit_len deliberately not a multiple of 8/32: trailing wire bits
        must be zero padding and survive the byte/word round-trip."""
        for n in (33, 63, 65, 129):
            x = np.zeros(n, np.float32)
            x[n - 1] = 0.5           # one maximal gap -> odd stream length
            msg = wire.encode_ternary_words(x, 0.05)
            assert msg.bit_len % 32 != 0
            _assert_stream_identical(msg, x, 0.05)
            np.testing.assert_allclose(wire.decode_ternary_words(msg, 0.05),
                                       x, atol=1e-6)

    def test_single_element_tensor(self):
        x = np.asarray([-0.25], np.float32)
        msg = wire.encode_ternary_words(x, 0.5)
        _assert_stream_identical(msg, x, 0.5)
        np.testing.assert_allclose(wire.decode_ternary_words(msg, 0.5), x)

    def test_b_star_overflow_is_loud(self):
        with pytest.raises(ValueError, match="b\\*"):
            wire.encode_ternary_words(np.zeros(8, np.float32), 1e-12)

    @pytest.mark.slow
    def test_oracle_roundtrip_large(self):
        """Per-bit oracle at n=2^20 (slow lane: the per-bit loop is the
        thing the vectorized packer replaces)."""
        n, p = 1 << 20, 1 / 400
        x = _random_ternary(n, p, seed=0)
        payload, bit_len, mu, n_out = golomb.encode_ternary(x, p)
        dec = golomb.decode_ternary(payload, bit_len, mu, n_out, p)
        np.testing.assert_allclose(dec, x, atol=1e-6)
        msg = wire.encode_ternary_words(x, p)
        assert msg.bit_len == bit_len
        np.testing.assert_array_equal(msg.payload_bytes(), payload)


class TestBatched:
    @given(st.integers(1, 6), st.integers(1, 400), st.floats(0.01, 0.3),
           st.integers(0, 2**31 - 1))
    @settings(max_examples=25, deadline=None)
    def test_batch_equals_per_client(self, P, n, p, seed):
        X = np.stack([_random_ternary(n, p, seed + i) for i in range(P)])
        if P > 1:
            X[seed % P] = 0.0        # always exercise an empty client
        batch = wire.encode_ternary_words_batch(X, p)
        assert batch.n_msgs == P
        for i in range(P):
            single = wire.encode_ternary_words(X[i], p)
            m = batch.message(i)
            assert m.bit_len == single.bit_len
            np.testing.assert_array_equal(
                wire.words_to_bits(m.words, m.bit_len),
                wire.words_to_bits(single.words, single.bit_len))
            assert m.mu == pytest.approx(single.mu, rel=1e-6, abs=1e-12)
            assert m.nnz == single.nnz
        np.testing.assert_allclose(
            wire.decode_ternary_words_batch(batch, p), X, atol=1e-6)

    def test_dense_regime_fallback_identical(self):
        """Above the fused-nnz crossover the batch falls back to per-client
        packs; the resulting WireBatch must be indistinguishable."""
        P, n, p = 4, 40_000, 0.25    # 40k nnz total > _FUSED_NNZ_MAX
        X = np.stack([_random_ternary(n, p, i) for i in range(P)])
        assert int(np.count_nonzero(X)) > wire._FUSED_NNZ_MAX
        batch = wire.encode_ternary_words_batch(X, p)
        for i in range(P):
            _assert_stream_identical(batch.message(i), X[i], p)

    def test_all_clients_empty(self):
        batch = wire.encode_ternary_words_batch(np.zeros((3, 50), np.float32),
                                                0.1)
        assert batch.words.size == 0
        assert all(batch.message(i).bit_len == 0 for i in range(3))


class TestBackends:
    def test_kernel_backend_bit_identical(self):
        for n, p in [(257, 0.03), (1000, 0.01), (64, 0.9)]:
            x = _random_ternary(n, p, seed=5)
            a = wire.encode_ternary_words(x, p, backend="numpy")
            b = wire.encode_ternary_words(x, p, backend="kernel")
            assert a.bit_len == b.bit_len
            np.testing.assert_array_equal(a.words, b.words)
        X = np.stack([_random_ternary(500, 0.02, i) for i in range(4)])
        ba = wire.encode_ternary_words_batch(X, 0.02, backend="numpy")
        bb = wire.encode_ternary_words_batch(X, 0.02, backend="kernel")
        np.testing.assert_array_equal(ba.words, bb.words)
        np.testing.assert_array_equal(ba.bit_len, bb.bit_len)

    def test_pack_bits_kernel_vs_ref(self):
        import jax.numpy as jnp
        from repro.kernels import (pack_bits_ref, pack_bits_words,
                                   pack_bits_words_batched)
        rng = np.random.default_rng(0)
        for m in (1, 31, 32, 33, 127, 128, 4097, 65536):
            bits = rng.integers(0, 2, m).astype(np.uint8)
            ref = np.asarray(pack_bits_ref(jnp.asarray(bits)))
            ker = np.asarray(pack_bits_words(jnp.asarray(bits)))
            np.testing.assert_array_equal(ker, ref)
            np.testing.assert_array_equal(
                wire.get_wire_backend("numpy").pack_bits(bits), ref)
        B = 5
        bb = rng.integers(0, 2, (B, 777)).astype(np.uint8)
        out = np.asarray(pack_bits_words_batched(jnp.asarray(bb)))
        for i in range(B):
            np.testing.assert_array_equal(
                out[i], np.asarray(pack_bits_ref(jnp.asarray(bb[i]))))

    def test_unknown_backend_is_loud(self):
        with pytest.raises(ValueError, match="unknown wire backend"):
            wire.get_wire_backend("nope")


class TestSignWire:
    def test_roundtrip_and_exact_size(self):
        rng = np.random.default_rng(0)
        for n in (1, 31, 777):
            x = rng.standard_normal(n).astype(np.float32)
            msg = wire.pack_sign_words(x, 2e-4)
            assert msg.bit_len == n     # exactly 1 bit per coordinate
            back = wire.unpack_sign_words(msg)
            np.testing.assert_allclose(
                back, np.where(x > 0, 2e-4, -2e-4).astype(np.float32))

    def test_codec_measured_equals_analytic(self):
        proto = make_protocol("signsgd")
        msgs = np.sign(np.random.default_rng(1).standard_normal((3, 500))
                       ).astype(np.float32) * proto.sign_step
        assert proto.measured_upload_bits(msgs) == 3 * proto.upload_bits(500)
        assert proto.measured_download_bits(msgs[0]) == proto.download_bits(500)


class TestCodecWireAPI:
    def test_stc_measured_below_bound(self):
        proto = make_protocol("stc", sparsity_up=0.02, sparsity_down=0.02)
        msgs = np.stack([_random_ternary(5000, 0.02, i) for i in range(4)])
        measured = proto.measured_upload_bits(msgs)
        bound = sum(proto.wire_bound_bits(5000, int(np.count_nonzero(m)),
                                          "up") for m in msgs)
        assert 0 < measured <= bound
        gd = _random_ternary(5000, 0.02, 99)
        assert (proto.measured_download_bits(gd)
                <= proto.wire_bound_bits(5000, int(np.count_nonzero(gd)),
                                         "down"))

    def test_wireless_codec_falls_back_to_analytic(self):
        proto = make_protocol("fedavg")
        msgs = np.ones((2, 100), np.float32)
        assert not proto.wire_format
        assert proto.measured_upload_bits(msgs) == 2 * proto.upload_bits(100)
        assert proto.measured_download_bits(msgs[0]) == proto.download_bits(100)

    def test_generic_batch_fallback(self):
        """Codec.encode_wire_batch default (concat of singles) matches the
        per-message streams -- third-party wire codecs get batching free."""
        proto = make_protocol("signsgd")
        msgs = np.sign(np.random.default_rng(2).standard_normal((3, 100))
                       ).astype(np.float32)
        batch = proto.encode_wire_batch(msgs)
        for i in range(3):
            single = proto.encode_wire(msgs[i])
            m = batch.message(i)
            assert m.bit_len == single.bit_len
            np.testing.assert_array_equal(m.words, single.words)


class TestUpdateCachePrefix:
    def test_partial_sum_matches_loop(self):
        rng = np.random.default_rng(0)
        cache = UpdateCache(numel=64, max_rounds=8)
        ups = [rng.standard_normal(64).astype(np.float32) for _ in range(11)]
        for u in ups:
            cache.push(u)
        kept = list(cache._updates)          # newest first, len 8
        for s in range(0, 9):
            got = cache.partial_sum(s)
            want = np.zeros(64, np.float32)
            for t in range(s):
                want += kept[t]
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
        assert cache.partial_sum(9) is None   # staler than the ring buffer

    def test_prefix_cache_invalidated_on_push(self):
        cache = UpdateCache(numel=4, max_rounds=4)
        cache.push(np.ones(4))
        first = cache.partial_sum(1)
        cache.push(2 * np.ones(4))
        np.testing.assert_array_equal(cache.partial_sum(1), 2 * np.ones(4))
        np.testing.assert_array_equal(cache.partial_sum(2), 3 * np.ones(4))
        np.testing.assert_array_equal(first, np.ones(4))  # copy, not a view

    def test_lazy_depth_growth_out_of_order(self):
        """The prefix cache grows to the deepest staleness queried, in any
        query order, without recomputing shallow rows."""
        rng = np.random.default_rng(1)
        cache = UpdateCache(numel=16, max_rounds=8)
        ups = [rng.standard_normal(16).astype(np.float32) for _ in range(6)]
        for u in ups:
            cache.push(u)
        kept = list(cache._updates)
        for s in (3, 1, 5, 2, 6):
            want = np.sum(kept[:s], axis=0, dtype=np.float32)
            np.testing.assert_allclose(cache.partial_sum(s), want,
                                       rtol=1e-5, atol=1e-5)
        assert cache._cum.shape[0] == 6   # grown to the max depth, not 8

    def test_partial_sum_returns_copy(self):
        cache = UpdateCache(numel=4, max_rounds=4)
        cache.push(np.ones(4))
        out = cache.partial_sum(1)
        out += 100.0
        np.testing.assert_array_equal(cache.partial_sum(1), np.ones(4))


class TestMeasuredLedgerIntegration:
    def test_fed_run_measured_within_bounds(self):
        """Full fed/loop.py STC run: measured upload/download bits per round
        satisfy measured <= the deterministic Eq. 13 / Eq. 15-style analytic
        bound, and stay within sanity range of the Eq. 17 expectation."""
        from repro.data import make_classification
        from repro.fed import FedEnvironment, FederatedTrainer, TrainerConfig
        from repro.models.paper_models import MODEL_ZOO

        train, test = make_classification(seed=0, n=1500, n_test=300)
        env = FedEnvironment(n_clients=8, participation=0.5,
                             classes_per_client=2, batch_size=10)
        proto = make_protocol("stc", sparsity_up=1 / 20, sparsity_down=1 / 20)
        tr = FederatedTrainer(MODEL_ZOO["logreg"], train, test, env, proto,
                              TrainerConfig(lr=0.05))
        assert tr.measure_bits           # auto-on: stc has a wire format
        tr.run(6, eval_every=3)

        assert len(tr.wire_log) == 6
        for row in tr.wire_log:
            assert 0 < row["bits_up"] <= row["bits_up_bound"]
            assert (0 < row["bits_down_per_update"]
                    <= row["bits_down_per_update_bound"])
        # totals: measured tracks the analytic expectation (loose sanity)
        assert tr.bits_up == pytest.approx(tr.bits_up_analytic, rel=0.5)
        assert tr.bits_down > 0 and tr.bits_down_analytic > 0
        h = tr.history[-1]
        assert h["measured"] and h["bits_up"] == tr.bits_up

    def test_measure_bits_off_reproduces_analytic_ledger(self):
        from repro.data import make_classification
        from repro.fed import FedEnvironment, FederatedTrainer, TrainerConfig
        from repro.models.paper_models import MODEL_ZOO

        train, test = make_classification(seed=0, n=800, n_test=200)
        env = FedEnvironment(n_clients=4, participation=0.5,
                             classes_per_client=2, batch_size=10)
        proto = make_protocol("stc", sparsity_up=1 / 20, sparsity_down=1 / 20)
        tr = FederatedTrainer(MODEL_ZOO["logreg"], train, test, env, proto,
                              TrainerConfig(lr=0.05, measure_bits=False))
        tr.run(3, eval_every=3)
        assert tr.wire_log == []
        assert tr.bits_up == tr.bits_up_analytic
        assert tr.bits_down == tr.bits_down_analytic

    def test_mesh_trainer_wire_ledger(self):
        """launch/train.py: measure_wire threads (msgs, global_delta) out of
        the step and the WireLedger accounts measured bits (no-mesh path)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.configs import get_smoke_config
        from repro.data import make_lm_tokens
        from repro.launch.train import (TrainConfig, WireLedger, codec_for,
                                        init_train_state, make_train_step)

        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("model",))
        cfg = get_smoke_config("smollm-135m")
        tc = TrainConfig(protocol="stc", lr=0.05, sparsity_up=1 / 50,
                         sparsity_down=1 / 50, measure_wire=True)
        state = init_train_state(cfg, tc, n_clients=1,
                                 key=jax.random.PRNGKey(0))
        toks = make_lm_tokens(n_tokens=2 * 128 + 1, vocab=cfg.vocab_size)
        batch = {"tokens": jnp.asarray(toks[:-1].reshape(2, 128)),
                 "labels": jnp.asarray(toks[1:].reshape(2, 128))}
        step = make_train_step(cfg, mesh, tc)
        ledger = WireLedger(codec_for(tc), cfg.param_count())
        for _ in range(2):
            state, metrics, (msgs, gd) = step(state, batch)
            ledger.record_round(msgs, gd)
        s = ledger.summary()
        assert s["rounds"] == 2
        assert 0 < s["bits_up"] < s["bits_up_analytic"] * 1.5
        assert 0 < s["bits_down"] < s["bits_down_analytic"] * 1.5
