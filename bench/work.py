"""Bytes that the server's work needs, counted from sizes alone, whatever
implements it.  The parameters themselves are the model's to count
(``params`` of its module in ``bench/models``)."""
from __future__ import annotations


def server_bytes_per_chip(n_params: int, islands: int, chips: int) -> float:
    """Least bytes one chip moves for Eq. 2 and the redistribution, in
    float32, with the bus split evenly over the chips: read the server
    and every island, write the server and every island's copy."""
    return 4.0 * n_params * (2 + 2 * islands) / chips
