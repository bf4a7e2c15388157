"""The bytes each kernel must move, from the shapes of one call.

Each count is the least traffic the layer's semantics need, independent
of padding, tiling, rounds or the algorithm that implements it, so that a
roofline share stays meaningful when the implementation changes.
"""

from __future__ import annotations


def argmin_bytes(rows: int, pieces: int) -> int:
    """Rarest-argmin over ``rows`` peers: each row's have bits (one bit a
    piece) and float32 tie-break jitter read once, the replica counts
    (int32) read once, one int32 pick written per row."""
    return rows * pieces // 8 + rows * pieces * 4 + pieces * 4 + rows * 4


def waterfill_bytes(flows: int, nodes: int, links: int = 0) -> int:
    """Max-min water-fill of ``flows`` over ``nodes``: the unpadded flow
    table (int32 source and destination) read once, one float32 rate
    written per flow, each node's up and down capacity (float32) read
    once; a flow's link index and each link's capacity where links
    exist."""
    table = flows * (4 + 4) + flows * 4 + nodes * 8
    if links:
        table += flows * 4 + links * 4
    return table
