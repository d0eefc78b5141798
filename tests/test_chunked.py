"""Chunked (layer, chunk) codec states: property-based round-trips against
the per-chunk flat oracle for EVERY registered codec, adversarial chunk
boundaries (chunk=1, chunk=numel, ragged tails, empty layers), bit-ledger
equality at chunk=whole-vector, the per-row-k selection primitive, the
chunked tree path, and the trainer-level flat-path bit-identity regression
(the acceptance criterion: chunk=whole reproduces the flat path bit for bit
-- params, measured + analytic ledgers, wire_log)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (Codec, chunk_codec, chunk_spec_from_sizes,
                        chunk_spec_from_tree, get_stc_backend, make_protocol,
                        registered_protocols, whole_vector_spec)
from repro.core.chunking import ChunkedCodec
from repro.core.protocols import _REGISTRY
from repro.core.residual import stack_states

# demo-scale hyperparameters so tiny test vectors keep a few non-zeros
DEMO = {"stc": dict(sparsity_up=1 / 8, sparsity_down=1 / 8),
        "topk": dict(sparsity_up=1 / 8)}

# adversarial layer layouts: single layer, empty layer in the middle,
# many tiny layers, ragged everything
LAYOUTS = ([64], [40, 0, 33, 27], [7, 19, 5], [1, 1, 1, 1], [2, 61])
P = 3


def _codec(name: str) -> Codec:
    return make_protocol(name, **DEMO.get(name, {}))


def _spec(layout_idx: int, chunk_mode: int):
    sizes = LAYOUTS[layout_idx % len(LAYOUTS)]
    numel = sum(sizes)
    mode = chunk_mode % 4
    if mode == 0:
        return chunk_spec_from_sizes(sizes, chunk_size=1)        # chunk = 1
    if mode == 1:
        return chunk_spec_from_sizes(sizes, chunk_size=numel)    # chunk=numel
    if mode == 2:
        return chunk_spec_from_sizes(sizes, chunk_size=13)       # ragged
    return whole_vector_spec(numel)                              # flat twin


def _deltas(numel: int, seed: int) -> jnp.ndarray:
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((P, numel)), jnp.float32)


def _oracle_round(cc: ChunkedCodec, deltas, chunk_states):
    """The flat oracle: the base codec applied to every chunk's UNPADDED
    slice independently.  ``chunk_states`` is a per-chunk list of per-client
    base states (threaded across rounds).  Returns (msgs, states)."""
    spec = cc.spec
    msgs = np.zeros((P, spec.numel), np.float32)
    for ci in range(spec.n_chunks):
        codec = cc.layer_codecs[spec.chunk_layer[ci]]
        s, v = spec.chunk_start[ci], spec.chunk_valid[ci]
        for pi in range(P):
            m, st1, _ = codec.encode(deltas[pi, s:s + v],
                                     chunk_states[ci][pi])
            msgs[pi, s:s + v] = np.asarray(m)
            chunk_states[ci][pi] = st1
    return msgs, chunk_states


def _oracle_aggregate(cc: ChunkedCodec, msgs, server_states, mask, stal):
    spec = cc.spec
    out = np.zeros(spec.numel, np.float32)
    for ci in range(spec.n_chunks):
        codec = cc.layer_codecs[spec.chunk_layer[ci]]
        s, v = spec.chunk_start[ci], spec.chunk_valid[ci]
        g, st1, _ = codec.aggregate(jnp.asarray(msgs[:, s:s + v]),
                                    server_states[ci], mask=mask,
                                    staleness=stal)
        out[s:s + v] = np.asarray(g)
        server_states[ci] = st1
    return out, server_states


def _valid_state_slices(cc: ChunkedCodec, states):
    """Unpadded per-chunk views of a stacked chunked client state."""
    spec = cc.spec
    return [
        jax.tree.map(lambda x, ci=ci, v=spec.chunk_valid[ci]:
                     np.asarray(x)[:, ci, :v], states)
        for ci in range(spec.n_chunks)]


# ---------------------------------------------------------------------------
# the headline property: chunked encode -> wire -> decode -> aggregate is
# the per-chunk flat oracle, for every registered codec
# ---------------------------------------------------------------------------


class TestChunkedVsFlatOracle:
    @given(st.integers(0, len(LAYOUTS) - 1), st.integers(0, 3),
           st.integers(0, 2 ** 31 - 1))
    @settings(max_examples=5, deadline=None)
    def test_roundtrip_matches_oracle(self, layout_idx, chunk_mode, seed):
        # EVERY registered codec (incl. third-party registrations) must obey
        # the per-chunk flat-oracle contract on adversarial boundaries
        for name in sorted(registered_protocols()):
            self._roundtrip_one(name, layout_idx, chunk_mode, seed)

    def _roundtrip_one(self, name, layout_idx, chunk_mode, seed):
        base = _codec(name)
        spec = _spec(layout_idx, chunk_mode)
        cc = chunk_codec(base, spec)
        deltas = _deltas(spec.numel, seed)

        states = stack_states(cc.init_client_state(spec.numel), P)
        oracle_states = [[base.init_client_state(spec.chunk_valid[ci])
                          for _ in range(P)] for ci in range(spec.n_chunks)]
        server = cc.init_server_state(spec.numel)
        oracle_server = [base.init_server_state(spec.chunk_valid[ci])
                         for ci in range(spec.n_chunks)]

        mask = jnp.asarray([1.0, 0.0, 1.0])
        stal = jnp.asarray([0.0, 0.0, 2.0])
        for rnd in range(2):            # two rounds: states must thread
            d = deltas if rnd == 0 else deltas * 0.5
            msgs, states, _ = cc.encode_batch(d, states)
            msgs = np.asarray(msgs)
            o_msgs, oracle_states = _oracle_round(cc, np.asarray(d),
                                                  oracle_states)
            np.testing.assert_allclose(msgs, o_msgs, rtol=1e-6, atol=1e-7)

            if cc.wire_format:          # wire round-trip is exact
                batch = cc.encode_wire_batch(msgs, direction="up")
                dec = cc.decode_wire_batch(batch, direction="up")
                np.testing.assert_allclose(dec, msgs, rtol=1e-6, atol=0)
                assert cc.measured_batch_bits(batch) >= 0.0

            g, server, _ = cc.aggregate(jnp.asarray(msgs), server,
                                        mask=mask, staleness=stal)
            o_g, oracle_server = _oracle_aggregate(cc, o_msgs, oracle_server,
                                                   mask, stal)
            np.testing.assert_allclose(np.asarray(g), o_g,
                                       rtol=1e-6, atol=1e-7)

        # client codec state threads identically (unpadded region)
        if states is not None:
            for ci, sl in enumerate(_valid_state_slices(cc, states)):
                for pi in range(P):
                    np.testing.assert_allclose(
                        np.asarray(jax.tree.leaves(sl)[0][pi])
                        if jax.tree.leaves(sl) else 0.0,
                        np.asarray(jax.tree.leaves(
                            oracle_states[ci][pi])[0])
                        if jax.tree.leaves(oracle_states[ci][pi]) else 0.0,
                        rtol=1e-6, atol=1e-7)

    @pytest.mark.parametrize("name", sorted(["baseline", "fedavg", "signsgd",
                                             "topk", "stc", "ternquant"]))
    def test_bit_ledger_equality_at_whole_vector(self, name):
        base = _codec(name)
        numel = 96
        cc = chunk_codec(base, whole_vector_spec(numel))
        assert cc.upload_bits(numel) == base.upload_bits(numel)
        for npart in (1, 4):
            assert cc.download_bits(numel, n_participating=npart) == \
                base.download_bits(numel, n_participating=npart)
        if not base.wire_format:
            return
        msgs, _, _ = cc.encode_batch(
            _deltas(numel, 7), stack_states(cc.init_client_state(numel), P))
        msgs = np.asarray(msgs)
        assert cc.measured_batch_bits(cc.encode_wire_batch(msgs)) == \
            base.measured_batch_bits(base.encode_wire_batch(msgs))
        m1, b1 = cc.encode_wire(msgs[0]), base.encode_wire(msgs[0])
        assert cc.measured_message_bits(m1) == base.measured_message_bits(b1)
        assert m1.nnz == b1.nnz and m1.bit_len == b1.bit_len
        assert cc.wire_bound_bits(numel, m1.nnz) == \
            base.wire_bound_bits(numel, b1.nnz)


# ---------------------------------------------------------------------------
# ChunkSpec geometry
# ---------------------------------------------------------------------------


class TestChunkSpec:
    @given(st.integers(0, len(LAYOUTS) - 1), st.integers(1, 70),
           st.integers(0, 2 ** 31 - 1))
    @settings(max_examples=15, deadline=None)
    def test_split_merge_roundtrip(self, layout_idx, chunk_size, seed):
        sizes = LAYOUTS[layout_idx % len(LAYOUTS)]
        spec = chunk_spec_from_sizes(sizes, chunk_size=chunk_size)
        x = np.random.default_rng(seed).standard_normal(
            (2, spec.numel)).astype(np.float32)
        blocks = spec.split(x)
        assert blocks.shape == (2, spec.n_chunks, spec.chunk_numel)
        # padding is exactly zero
        assert np.all(blocks[:, ~spec.valid_mask()] == 0.0) \
            if (~spec.valid_mask()).any() else True
        np.testing.assert_array_equal(spec.merge(blocks), x)
        # jnp view agrees
        np.testing.assert_array_equal(
            np.asarray(spec.merge(spec.split(jnp.asarray(x)))), x)

    def test_layer_boundaries_never_crossed(self):
        spec = chunk_spec_from_sizes([10, 0, 7], chunk_size=4)
        for ci in range(spec.n_chunks):
            li = spec.chunk_layer[ci]
            layer_start = sum(spec.layer_sizes[:li])
            s, v = spec.chunk_start[ci], spec.chunk_valid[ci]
            assert layer_start <= s
            assert s + v <= layer_start + spec.layer_sizes[li]
        assert spec.n_chunks == 3 + 2          # 10 -> 4+4+2, 0 -> none, 7 -> 4+3
        assert sum(spec.chunk_valid) == spec.numel == 17

    def test_whole_vector_spec_is_whole(self):
        spec = whole_vector_spec(33)
        assert spec.is_whole_vector() and spec.n_chunks == 1
        assert not chunk_spec_from_sizes([20, 13], chunk_size=16
                                         ).is_whole_vector()

    def test_chunk_ks_clamps_to_one(self):
        spec = chunk_spec_from_sizes([5, 3], chunk_size=2)
        ks = spec.chunk_ks(1e-6)
        assert np.all(ks == 1)
        ks2 = spec.chunk_ks([0.5] * spec.n_chunks)
        np.testing.assert_array_equal(
            ks2, np.maximum(np.asarray(spec.chunk_valid) // 2, 1))

    def test_from_tree_matches_flatten_order(self):
        tree = {"a": jnp.zeros((3, 4)), "b": jnp.zeros((0,)),
                "c": jnp.zeros((5,))}
        spec = chunk_spec_from_tree(tree, chunk_size=6)
        assert spec.numel == 17
        assert len(spec.layer_names) == 3 and spec.layer_sizes == (12, 0, 5)

    def test_rejects_bad_inputs(self):
        with pytest.raises(ValueError, match="chunk_size"):
            chunk_spec_from_sizes([4], chunk_size=0)
        with pytest.raises(ValueError, match="non-empty"):
            chunk_spec_from_sizes([0, 0], chunk_size=4)


# ---------------------------------------------------------------------------
# the per-row-k selection primitive behind the registry
# ---------------------------------------------------------------------------


class TestSelectBatch:
    @given(st.integers(0, 2 ** 31 - 1))
    @settings(max_examples=10, deadline=None)
    def test_backends_agree_per_row_k(self, seed):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.standard_normal((5, 64)), jnp.float32)
        ks = rng.integers(1, 64, size=5)
        tj, cj, sj = get_stc_backend("jnp").select_batch(x, ks)
        tk, ck, sk = get_stc_backend("kernel").select_batch(x, ks)
        np.testing.assert_array_equal(np.asarray(tj), np.asarray(tk))
        np.testing.assert_array_equal(np.asarray(cj), np.asarray(ck))
        np.testing.assert_allclose(np.asarray(sj), np.asarray(sk), rtol=1e-5)
        # the threshold IS the k-th largest magnitude, per row
        a = np.abs(np.asarray(x))
        for b in range(5):
            assert float(tj[b]) == float(np.sort(a[b])[::-1][ks[b] - 1])
            assert int(cj[b]) >= ks[b]          # ties kept

    def test_rejects_out_of_range_k(self):
        x = jnp.ones((2, 8))
        with pytest.raises(ValueError, match="out of range"):
            get_stc_backend("jnp").select_batch(x, [0, 3])


# ---------------------------------------------------------------------------
# chunked tree path (the mesh trainer's selection)
# ---------------------------------------------------------------------------


class TestChunkedTree:
    def _tree(self, seed=0):
        rng = np.random.default_rng(seed)
        return {"w": jnp.asarray(rng.standard_normal((10, 7)), jnp.float32),
                "b": jnp.asarray(rng.standard_normal((13,)), jnp.float32),
                "e": jnp.zeros((0,), jnp.float32)}

    def test_matches_per_chunk_flat_oracle(self):
        from repro.core.compression import stc_compress
        from repro.core.distributed import stc_compress_tree_chunked
        tree = self._tree()
        tern, stats = stc_compress_tree_chunked(tree, 1 / 5, chunk_size=16)
        for name, leaf in tree.items():
            flat = np.asarray(leaf, np.float32).reshape(-1)
            out = np.zeros_like(flat)
            for s in range(0, flat.size, 16):
                sl = flat[s:s + 16]
                m, _ = stc_compress(jnp.asarray(sl), 1 / 5)
                out[s:s + 16] = np.asarray(m)
            np.testing.assert_array_equal(out.reshape(leaf.shape),
                                          np.asarray(tern[name]))
        assert int(stats.nnz) > 0

    def test_p_fn_schedule_rescales_layers(self):
        from repro.core.distributed import stc_compress_tree_chunked
        tree = self._tree()
        _, base = stc_compress_tree_chunked(tree, 1 / 5, chunk_size=16)
        _, dense = stc_compress_tree_chunked(
            tree, 1 / 5, chunk_size=16,
            p_fn=lambda name, depth: 1.0 if "w" in name else None)
        assert int(dense.nnz) > int(base.nnz)

    def test_codec_tree_hooks_use_chunking(self):
        codec = make_protocol("stc", sparsity_up=1 / 5, sparsity_down=1 / 5,
                              chunk_size=16)
        tree = self._tree()
        res = jax.tree.map(jnp.zeros_like, tree)
        msg, new_res, m = codec.tree_encode(tree, res, numel=83)
        # error feedback: carried - msg
        for k in tree:
            np.testing.assert_allclose(
                np.asarray(new_res[k]),
                np.asarray(tree[k]) - np.asarray(msg[k]), rtol=1e-6)
        gd, _, md = codec.tree_decode(
            codec.tree_reduce(msg, (), 1), res, numel=83)
        assert int(m["nnz_up"]) > 0 and int(md["nnz_down"]) > 0


# ---------------------------------------------------------------------------
# wrapper contract
# ---------------------------------------------------------------------------


class TestChunkedCodecContract:
    def test_forwards_base_knobs(self):
        base = make_protocol("fedavg")
        cc = chunk_codec(base, whole_vector_spec(10))
        assert cc.local_iters == base.local_iters == 400
        assert cc.wire_format == base.wire_format
        assert cc.error_feedback == base.error_feedback

    def test_rejects_double_wrap_and_legacy(self):
        cc = chunk_codec(_codec("stc"), whole_vector_spec(10))
        with pytest.raises(TypeError, match="already-chunked"):
            chunk_codec(cc, whole_vector_spec(10))

        # a pre-mask 2-arg aggregate can no longer even be DEFINED, so
        # chunk_codec never sees one
        with pytest.raises(TypeError, match="masked aggregation API"):
            @dataclasses.dataclass(frozen=True)
            class LegacyAgg(Codec):
                name = "legacy-agg-chunk-test"

                def aggregate(self, msgs, server_state):    # pre-mask
                    return jnp.mean(msgs, axis=0), server_state, None

        assert "legacy-agg-chunk-test" not in _REGISTRY

    def test_p_fn_builds_per_layer_codecs(self):
        spec = chunk_spec_from_sizes([16, 16], names=["dense", "embed"],
                                     chunk_size=8)
        cc = chunk_codec(_codec("stc"), spec,
                         p_fn=lambda name, d: 0.5 if name == "embed" else None)
        assert cc.layer_codecs[0].sparsity_up == pytest.approx(1 / 8)
        assert cc.layer_codecs[1].sparsity_up == 0.5
        # per-chunk ks follow the schedule
        ks = cc.spec.chunk_ks(cc._chunk_ps("up"))
        np.testing.assert_array_equal(ks, [1, 1, 4, 4])


# ---------------------------------------------------------------------------
# THE acceptance regression: chunk=whole-vector trainers == flat trainers,
# bit for bit (params, measured + analytic ledgers, wire_log)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["stc", "signsgd"])
def test_trainer_whole_vector_chunking_is_flat_path(name):
    from repro.data import make_classification
    from repro.fed import FedEnvironment, FederatedTrainer, TrainerConfig
    from repro.models.paper_models import MODEL_ZOO

    train, test = make_classification(seed=0, n=600, n_test=120)
    env = FedEnvironment(n_clients=6, participation=0.5,
                         classes_per_client=2, batch_size=10)
    kw = {"stc": dict(sparsity_up=1 / 20, sparsity_down=1 / 20)}
    rounds = 3
    flat = FederatedTrainer(MODEL_ZOO["logreg"], train, test, env,
                            make_protocol(name, **kw.get(name, {})),
                            TrainerConfig(lr=0.05, seed=0))
    flat.run(rounds, eval_every=rounds)
    chunked = FederatedTrainer(MODEL_ZOO["logreg"], train, test, env,
                               make_protocol(name, **kw.get(name, {})),
                               TrainerConfig(lr=0.05, seed=0, chunks="whole"))
    chunked.run(rounds, eval_every=rounds)

    np.testing.assert_array_equal(np.asarray(flat.params_vec),
                                  np.asarray(chunked.params_vec))
    assert flat.bits_up == chunked.bits_up
    assert flat.bits_down == chunked.bits_down
    assert flat.bits_up_analytic == chunked.bits_up_analytic
    assert flat.bits_down_analytic == chunked.bits_down_analytic
    assert flat.wire_log == chunked.wire_log
    for hf, hc in zip(flat.history, chunked.history):
        assert hf == hc


def test_trainer_multi_chunk_trains_and_ledger_counts_headers():
    from repro.data import make_classification
    from repro.fed import FedEnvironment, FederatedTrainer, TrainerConfig
    from repro.models.paper_models import MODEL_ZOO

    train, test = make_classification(seed=0, n=600, n_test=120)
    env = FedEnvironment(n_clients=6, participation=0.5,
                         classes_per_client=2, batch_size=10)
    tr = FederatedTrainer(
        MODEL_ZOO["logreg"], train, test, env,
        make_protocol("stc", sparsity_up=1 / 20, sparsity_down=1 / 20),
        TrainerConfig(lr=0.05, seed=0, chunks=32,
                      p_fn=lambda name, d: 1 / 10 if "b" in name else None))
    hist = tr.run(3, eval_every=3)
    assert np.all(np.isfinite(np.asarray(tr.params_vec)))
    assert hist[-1]["acc"] > 0.0
    assert tr.bits_up > 0 and tr.bits_down > 0
    # every chunk pays its own 32-bit µ header in the measured ledger
    n_chunks = tr.protocol.spec.n_chunks
    assert n_chunks > 1
    for row in tr.wire_log:
        assert row["bits_up_bound"] is None or \
            row["bits_up"] <= row["bits_up_bound"]
