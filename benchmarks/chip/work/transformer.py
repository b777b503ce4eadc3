"""Operations and bytes a BERT encoder's useful work needs, from the layer
shapes of a configuration file and token counts alone.

Nothing here looks at how the program implements a layer: no tiles, no
padding, no bucket or group sizes. A later change to the kernels leaves
these counts as they are, so a roofline or mfu computed from them moves
only when the time does.
"""
from __future__ import annotations

OUT_BYTES = 4           # the integer matmuls hand float32 to the glue


def layer_bits(config: dict) -> list:
    L, k4 = config["num_hidden_layers"], config["plan"]["last_k_int4"]
    return [4 if l >= L - k4 else 8 for l in range(L)]


def linears(config: dict) -> list:
    """(K, N, weight bits) of every quantized linear of one forward:
    q, k, v, o and the two FFN matrices of each layer."""
    d, f = config["hidden_size"], config["intermediate_size"]
    per = [(d, d), (d, d), (d, d), (d, d), (d, f), (f, d)]
    return [(K, N, b) for b in layer_bits(config) for K, N in per]


def int_matmul_work(config: dict, rows: int, calls: int) -> tuple:
    """(ops, bytes) of ``calls`` forwards over ``rows`` useful rows in all:
    2MKN operations; weight codes and scales once per call, int8
    activation codes and outputs once per row."""
    ops = byts = 0
    for K, N, bits in linears(config):
        ops += 2 * rows * K * N
        byts += calls * (K * N * bits // 8 + 4 * N) + rows * (K + OUT_BYTES * N)
    return ops, byts


def encode_ops(config: dict, length: int) -> int:
    """Useful operations of one bidirectional encode of ``length`` tokens:
    the linears, the score and value products over ``length`` keys, and
    the pooler and classifier on one row."""
    d = config["hidden_size"]
    lin = sum(2 * K * N for K, N, _ in linears(config))
    attn = 4 * length * d * config["num_hidden_layers"]
    head = 2 * d * d + 2 * d * config["num_labels"]
    return length * (lin + attn) + head
