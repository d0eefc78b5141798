"""The FLOP and least-bytes functions against counts made by hand for
``smollm-135m`` (30 layers, d 576, 9 heads of 64, 3 KV heads, d_ff 1536,
vocabulary 49,152, tied head)."""

import json
import os

import pytest

from bench import flops

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def load(name):
    with open(os.path.join(CONFIGS, name + ".json")) as fh:
        return json.load(fh)


@pytest.fixture
def smollm():
    return load("smollm-135m")["model"]


def test_param_counts_match_the_published_totals():
    for name in ("smollm-135m", "qwen2-0.5b"):
        cfg = load(name)
        assert flops.param_count(cfg["model"]) == cfg["params"]


def test_matmul_params_by_hand(smollm):
    q = o = 576 * 576                     # 331,776 each
    k = v = 576 * 192                     # 110,592 each (3 KV heads of 64)
    mlp = 3 * 576 * 1536                  # 2,654,208
    head = 49152 * 576                    # 28,311,552 (tied embedding)
    assert flops.matmul_params(smollm) == 30 * (q + k + v + o + mlp) + head
    assert flops.matmul_params(smollm) == 134_479_872


def test_round_flops_by_hand(smollm):
    traffic = {"clients": 1, "batch_per_client": 8, "seq_len": 2048}
    dense = 6 * 134_479_872 * 8 * 2048    # 1.32199e13
    # causal attention, forward QK^T + PV over the lower triangle
    # 2 * S^2 * H * hd per row and layer, times 3 with the backward
    attn = 3 * 30 * 8 * 2 * 2048 ** 2 * 9 * 64
    assert flops.round_flops(smollm, traffic) == dense + attn
    assert flops.round_flops(smollm, traffic) == pytest.approx(1.669883e13,
                                                               rel=1e-6)
    four = {"clients": 4, "batch_per_client": 2, "seq_len": 2048}
    assert flops.round_flops(smollm, four) == dense + attn


@pytest.mark.parametrize("protocol,passes", [("stc", 11), ("fedavg", 3)])
def test_codec_least_bytes_by_hand(smollm, protocol, passes):
    with open(os.path.join(os.path.dirname(CONFIGS), "protocols",
                           protocol + ".json")) as fh:
        proto = json.load(fh)
    assert proto["tree_passes"] == passes
    assert flops.codec_least_bytes(smollm, proto) == passes * 4 * 134_515_008
