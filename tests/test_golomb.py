"""Golomb codec: roundtrip + analytic model (Eqs. 15-17) validation."""


import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import golomb


class TestAnalytic:
    def test_b_star_paper_value(self):
        # Paper quotes b̄_pos = 8.38 at p = 0.01, which corresponds to b* = 7;
        # the paper's own b* formula (Eq. 17) yields b* = 6 -> b̄ = 8.108,
        # which is strictly BETTER (fewer bits).  We follow the formula; the
        # ~x1.9 compression-vs-16-bit-distance claim still holds.
        assert golomb.golomb_b_star(0.01) == 6
        assert golomb.golomb_position_bits(0.01) == pytest.approx(8.108, abs=0.01)
        assert 16.0 / golomb.golomb_position_bits(0.01) == pytest.approx(1.9, abs=0.1)

    def test_entropy_gain_paper_value(self):
        # paper: ternarization gain H_sparse/H_STC = 4.414 at p = 0.01
        gain = golomb.entropy_sparse(0.01) / golomb.entropy_sparse_ternary(0.01)
        assert gain == pytest.approx(4.414, abs=0.01)

    def test_message_sizes_ordering(self):
        n = 100_000
        stc = golomb.stc_message_bits(n, 1 / 400)
        dense = golomb.fedavg_message_bits(n)
        sign = golomb.signsgd_message_bits(n)
        assert stc < sign < dense
        # x1050 compression claim at p=1/400 (within 15%)
        assert dense / stc == pytest.approx(1050, rel=0.15)


class TestCodec:
    def _random_ternary(self, n, p, seed):
        rng = np.random.default_rng(seed)
        x = np.zeros(n, np.float32)
        k = max(int(n * p), 1)
        idx = rng.choice(n, size=k, replace=False)
        mu = abs(float(rng.standard_normal())) + 0.1
        x[idx] = mu * rng.choice([-1.0, 1.0], size=k)
        return x, mu

    @given(st.integers(16, 3000), st.floats(0.005, 0.2),
           st.integers(0, 2**31 - 1))
    @settings(max_examples=30, deadline=None)
    def test_roundtrip(self, n, p, seed):
        x, _ = self._random_ternary(n, p, seed)
        payload, bit_len, mu, n_out = golomb.encode_ternary(x, p)
        dec = golomb.decode_ternary(payload, bit_len, mu, n_out, p)
        np.testing.assert_allclose(dec, x, atol=1e-6)

    def test_empty_tensor(self):
        x = np.zeros(100, np.float32)
        payload, bit_len, mu, n = golomb.encode_ternary(x, 0.01)
        assert bit_len == 0 and len(payload) == 0
        dec = golomb.decode_ternary(payload, bit_len, mu, n, 0.01)
        np.testing.assert_array_equal(dec, x)

    def test_payload_is_packed_bytes(self):
        """Satellite fix: payload is bit-packed uint8 bytes (ceil(bits/8)),
        not the old one-BIT-per-uint8 blowup."""
        x, _ = self._random_ternary(4096, 0.05, seed=11)
        payload, bit_len, _, _ = golomb.encode_ternary(x, 0.05)
        assert payload.dtype == np.uint8
        assert len(payload) == (bit_len + 7) // 8
        # MSB-first convention: re-unpacking must give bit_len used bits
        assert int(np.unpackbits(payload)[bit_len:].sum()) == 0

    def test_measured_bits_match_analytic(self):
        """Real bitstream length ≈ Eq. 17 expectation (random sparsity)."""
        n, p = 200_000, 0.01
        x, _ = self._random_ternary(n, p, seed=3)
        _, bit_len, _, _ = golomb.encode_ternary(x, p)
        k = int(n * p)
        expected = k * (golomb.golomb_position_bits(p) + 1.0)
        assert bit_len == pytest.approx(expected, rel=0.02)

    def test_stream_bound_holds(self):
        """stc_stream_bound_bits is a TRUE ceiling on the measured stream."""
        for seed, p in [(3, 0.01), (4, 0.05), (5, 0.2)]:
            n = 30_000
            x, _ = self._random_ternary(n, p, seed)
            _, bit_len, _, _ = golomb.encode_ternary(x, p)
            nnz = int(np.count_nonzero(x))
            assert bit_len + 32 <= golomb.stc_stream_bound_bits(n, nnz, p)

    def test_dense_edge(self):
        """p close to 1: gaps all 1, codec must still roundtrip."""
        x = np.ones(64, np.float32) * 0.5
        x[::7] *= -1
        payload, bit_len, mu, n = golomb.encode_ternary(x, 0.9)
        dec = golomb.decode_ternary(payload, bit_len, mu, n, 0.9)
        np.testing.assert_allclose(dec, x, atol=1e-6)
