"""Every Pallas kernel, at real widths, compiled for a described v5e chip.

Nothing runs: the TPU compiler that ships with jaxlib compiles for a
``v5e:2x2`` topology that is described, not attached, and refuses what the
chip would refuse (unsupported primitives, block shapes off the (8, 128)
tiling, scalar stores to VMEM, reductions the TPU lacks).  Interpret-mode
tests cannot see any of that.  Each test asserts that the compiled program
holds the kernel (``tpu_custom_call``), i.e. that it was not interpreted.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, so a description at import would make the
test workers collect different tests.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (magnitude_histogram_batched, pack_bits_words,
                           stc_apply_batched, threshold_stats,
                           unpack_words_with_counts)
from repro.kernels.attention import causal_gqa_attention

N = 1 << 20            # one client update at real width
WIRE_BITS = 1 << 20    # a Golomb stream of ~2^20 bits (2^15 words)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU lib
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A sharding on one described chip, with the persistent compilation
    cache off: an entry written here could not be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("b", [1, 8])
def test_histogram_compiles(one_chip, b):
    _compile(lambda x, s: magnitude_histogram_batched(x, s, interpret=False),
             one_chip, ((b, N), jnp.float32), ((b,), jnp.float32))


@pytest.mark.parametrize("b", [1, 8])
def test_fused_apply_compiles(one_chip, b):
    _compile(lambda c, t, m: stc_apply_batched(c, t, m, interpret=False),
             one_chip, ((b, N), jnp.float32), ((b,), jnp.float32),
             ((b,), jnp.float32))


def test_threshold_stats_compiles(one_chip):
    _compile(lambda x, t: threshold_stats(x, t, interpret=False),
             one_chip, ((N,), jnp.float32), ((), jnp.float32))


def test_pack_bits_compiles(one_chip):
    _compile(lambda b: pack_bits_words(b, interpret=False),
             one_chip, ((WIRE_BITS,), jnp.uint8))


def test_unpack_words_compiles(one_chip):
    _compile(lambda w: unpack_words_with_counts(w, interpret=False),
             one_chip, ((WIRE_BITS // 32,), jnp.uint32))


@pytest.mark.parametrize("b,h,kv", [(8, 9, 3), (2, 14, 2)],
                         ids=["smollm-135m", "qwen2-0.5b"])
def test_attention_compiles(one_chip, b, h, kv):
    """The local step's attention at a cell's widths (2048 tokens, head size
    64), forward and backward: three kernels, none interpreted."""
    def fwd_bwd(q, k, v, do):
        out, pullback = jax.vjp(
            lambda q, k, v: causal_gqa_attention(q, k, v, interpret=False),
            q, k, v)
        return out, pullback(do)

    text = _compile(fwd_bwd, one_chip, ((b, 2048, h, 64), jnp.bfloat16),
                    ((b, 2048, kv, 64), jnp.bfloat16),
                    ((b, 2048, kv, 64), jnp.bfloat16),
                    ((b, 2048, h, 64), jnp.bfloat16))
    assert text.count('custom_call_target="tpu_custom_call"') == 3
