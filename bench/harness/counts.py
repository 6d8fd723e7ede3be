"""Operations and bytes the algorithms need, computed from shapes.

These are the yardstick for ``decode_mfu`` and the ``block_delta``
roofline; they count what the computation requires, not what an
implementation happens to do.
"""
from __future__ import annotations

from typing import Tuple

from harness.weights import dims

LANES = 128


def decode_flops(c: dict, context: int) -> float:
    """FLOPs of one decoded token that attends to ``context`` positions
    (itself included): every weight matrix once (2 per multiply-add),
    the LM head, and scores plus weighted values over the context."""
    d = dims(c)
    L, D = d["L"], d["D"]
    per_layer = 2 * D * (2 * d["H"] + 2 * d["KV"]) + 2 * 3 * D * d["F"]
    attn = 2 * 2 * d["heads"] * d["hd"] * context
    return L * (per_layer + attn) + 2 * D * d["V"]


def block_delta_cost(nblocks: int, block_elems: int) -> Tuple[float, float]:
    """``(flops, bytes)`` of one ``block_delta`` call over ``nblocks``
    float32 blocks: both inputs read; the int8 delta and three per-block
    lane rows written. Per element: difference, square and sum, absolute
    value and max, scale, round and clip (two), bit compare and count."""
    n = nblocks * block_elems
    flops = 10.0 * n
    nbytes = 2 * 4 * n + n + 3 * nblocks * LANES * 4
    return flops, float(nbytes)
