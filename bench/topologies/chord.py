"""Symmetric Chord: a ring of ``n`` peers, each also linked both ways to
the peers 2, 4, ..., 2**(b-1) places further on, b = ceil(log2 n): the
structured peer-to-peer class of arXiv 1212.5880, Sec. VI-A.

Edges in the order of the monitor's ``topology.chord``: peer by peer, the
successor first, then the fingers by distance.  Its per-edge Python loop
takes about 11 s at 80,000 peers; this one takes well under a second.
"""

from __future__ import annotations

import numpy as np

from bench.adjacency import from_edge_arrays


def build(n: int) -> dict:
    b = max(1, int(np.ceil(np.log2(n))))
    hops = np.array([1] + [1 << j for j in range(1, b)], np.int64)
    src = np.repeat(np.arange(n, dtype=np.int64), hops.size)
    dst = (src.reshape(n, -1) + hops).ravel() % n
    return from_edge_arrays(n, src, dst)
