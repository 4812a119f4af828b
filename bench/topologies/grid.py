"""Peers on a ``side`` x ``side`` grid, each linked to its four neighbours:
the wireless-sensor-network topology of arXiv 1212.5880, Sec. VI-A.

Edges in the order of the monitor's ``topology.grid``: peers row-major,
the right neighbour before the one below.
"""

from __future__ import annotations

import numpy as np

from bench.adjacency import from_edge_arrays


def build(side: int) -> dict:
    r, c = np.divmod(np.arange(side * side), side)
    right = np.where(c + 1 < side, r * side + c + 1, -1)
    down = np.where(r + 1 < side, (r + 1) * side + c, -1)
    src = np.repeat(np.arange(side * side), 2)
    dst = np.stack([right, down], axis=1).ravel()
    ok = dst >= 0
    return from_edge_arrays(side * side, src[ok], dst[ok])
