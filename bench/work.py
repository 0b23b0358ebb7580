"""Operations and bytes that the work requires, computed from shapes.

``dims`` holds a dense decoder's published sizes under their config keys
(``hidden_size``, ``intermediate_size``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``num_hidden_layers``,
``vocab_size``).
"""
from __future__ import annotations

# The program holds the embedding and the output head with their rows
# padded to a multiple of this.
VOCAB_PAD = 256


def layer_matmul_params(dims: dict) -> int:
    D, F = dims["hidden_size"], dims["intermediate_size"]
    q = dims["num_attention_heads"] * dims["head_dim"]
    kv = dims["num_key_value_heads"] * dims["head_dim"]
    return D * q + 2 * D * kv + q * D + 3 * D * F


def param_count(dims: dict) -> int:
    """Parameters as the program holds them: padded vocabulary, untied
    embedding and head, RMSNorm scales (two per block, q/k norms, final)."""
    D, L = dims["hidden_size"], dims["num_hidden_layers"]
    vp = -(-dims["vocab_size"] // VOCAB_PAD) * VOCAB_PAD
    per_layer = layer_matmul_params(dims) + 2 * D + 2 * dims["head_dim"]
    return 2 * vp * D + D + L * per_layer


def train_flops(dims: dict, rows: int, seq: int) -> float:
    """Operations that one forward and backward pass over ``rows``
    sequences of ``seq`` tokens require: 6 per matrix parameter per token
    (the embedding is a lookup and does none; the head counts the
    published vocabulary), plus causal attention, ``QK^T`` and ``PV`` over
    the lower triangle, three times for forward and backward."""
    L = dims["num_hidden_layers"]
    mm = (L * layer_matmul_params(dims)
          + dims["hidden_size"] * dims["vocab_size"])
    tokens = rows * seq
    attn = 3 * L * rows * 2 * seq * seq * dims["num_attention_heads"] \
        * dims["head_dim"]
    return float(6 * mm * tokens + attn)


def staging_bytes(pairs, itemsize: int) -> int:
    """Logical bytes the staging layer must move on the device: each
    element of each ``(input_elems, output_elems)`` pair, one pair per
    rank and collective, read once and written once in the write plan
    (inputs) and in the read plan (outputs)."""
    return int(sum(2 * (i + o) * itemsize for i, o in pairs))
