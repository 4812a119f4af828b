"""Padded adjacency from an undirected edge list, built with array code.

The layout is the one the monitor's ``topology.from_edges`` gives, slot
for slot: edges are taken in list order, self loops and repeats of an
unordered pair are skipped, and each peer's slots follow the order in
which its edges first appear.  Slot order fixes the order of every
per-peer sum, and so the bits of the result, so a builder here has to
reproduce it exactly (``test_bench_topologies.py`` checks it).

Returned as plain numpy arrays: ``nbr`` (n, D) int32, ``mask`` (n, D)
bool and ``rev`` (n, D) int32 with ``nbr[nbr[i, k], rev[i, k]] == i``.
"""

from __future__ import annotations

import numpy as np


def from_edge_arrays(n: int, a, b) -> dict:
    a = np.asarray(a, np.int64).ravel()
    b = np.asarray(b, np.int64).ravel()
    keep = a != b
    a, b = a[keep], b[keep]
    key = np.minimum(a, b) * n + np.maximum(a, b)
    _, first = np.unique(key, return_index=True)
    first.sort()
    a, b = a[first], b[first]
    e = a.size
    rows = np.concatenate([a, b])
    cols = np.concatenate([b, a])
    when = np.concatenate([np.arange(e), np.arange(e)])
    order = np.lexsort((when, rows))  # by row, then by edge position
    deg = np.bincount(rows, minlength=n)
    start = np.concatenate([[0], np.cumsum(deg)[:-1]])
    slot = np.empty(2 * e, np.int64)
    slot[order] = np.arange(2 * e) - start[rows[order]]
    dmax = int(deg.max()) if n else 0
    nbr = np.zeros((n, dmax), np.int32)
    mask = np.zeros((n, dmax), bool)
    rev = np.zeros((n, dmax), np.int32)
    partner = np.concatenate([np.arange(e, 2 * e), np.arange(e)])
    nbr[rows, slot] = cols
    mask[rows, slot] = True
    rev[rows, slot] = slot[partner]
    return {"nbr": nbr, "mask": mask, "rev": rev, "n": n, "max_deg": dmax}
