"""The work one federated round needs, counted from shapes alone.

``round_flops``: model FLOPs of one round, forward and backward, counted
without rematerialization: 6 FLOPs per matmul weight per token, plus causal
attention (QK^T and PV over the lower triangle, 2 * S^2 * H * hd per row
forward, times 3 with the backward).  The tied LM head counts as a matmul;
norms, biases, rotary and softmax are not matmul FLOPs and are left out.

``codec_least_bytes``: the fewest HBM bytes the round's protocol has to move
outside the local step, whatever the implementation: the protocol's number
of full passes over the float32 parameter tree (``bench/protocols/<name>``)
times the tree's bytes.
"""

from __future__ import annotations

__all__ = ["matmul_params", "param_count", "round_flops",
           "codec_least_bytes"]


def _head_dim(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def matmul_params(m: dict) -> int:
    """Weights that take part in a matmul per token (the embedding lookup is
    not one; the head is, tied or not)."""
    d, hd = m["d_model"], _head_dim(m)
    attn = d * m["n_heads"] * hd * 2 + d * m["n_kv_heads"] * hd * 2
    mlp = 3 * d * m["d_ff"]
    return m["n_layers"] * (attn + mlp) + m["vocab_size"] * d


def param_count(m: dict) -> int:
    """Every value of the parameter tree (the float32 tree the codec moves)."""
    d, hd = m["d_model"], _head_dim(m)
    bias = (m["n_heads"] + 2 * m["n_kv_heads"]) * hd if m["attn_bias"] else 0
    per_layer = (d * m["n_heads"] * hd * 2 + d * m["n_kv_heads"] * hd * 2
                 + bias + 3 * d * m["d_ff"] + 2 * d)
    embed = m["vocab_size"] * d * (1 if m["tie_embeddings"] else 2)
    return m["n_layers"] * per_layer + embed + d


def round_flops(m: dict, traffic: dict) -> float:
    """Model FLOPs of one round over every client's batch."""
    rows = traffic["clients"] * traffic["batch_per_client"]
    seq = traffic["seq_len"]
    dense = 6 * matmul_params(m) * rows * seq
    attn = 3 * m["n_layers"] * rows * 2 * seq * seq * m["n_heads"] * _head_dim(m)
    return float(dense + attn)


def codec_least_bytes(m: dict, protocol: dict) -> float:
    """Least HBM bytes of the protocol's encode + decode + apply."""
    return float(protocol["tree_passes"] * 4 * param_count(m))
