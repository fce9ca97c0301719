"""Operations and bytes that a layer's work needs, counted from sizes
alone, whatever implements it."""
from __future__ import annotations

from bench.models.dense_lm import Dims


def params(dm: Dims) -> int:
    """Parameters of the server: every leaf of the model."""
    attn = dm.d * dm.hd * (2 * dm.heads + 2 * dm.kv_heads)
    norms = dm.d * (2 if dm.layernorm else 1)
    block = attn + 3 * dm.d * dm.ff + 2 * norms
    return dm.layers * block + 2 * dm.vocab_padded * dm.d + norms


def server_bytes_per_chip(n_params: int, islands: int, chips: int) -> float:
    """Least bytes one chip moves for Eq. 2 and the redistribution, in
    float32, with the bus split evenly over the chips: read the server
    and every island, write the server and every island's copy."""
    return 4.0 * n_params * (2 + 2 * islands) / chips
