"""The plain reference: what every answer of the monitor has to be.

Numpy only; nothing of the monitor is imported and nothing it computed
is reused except the state read back from it, which is what is judged.

* ``Truth`` keeps its own copy of one tenant's data, applies the same
  bursts the run pushes, and gives the global mean and its region: the
  answer every peer has to reach (Problem 2 of arXiv 1212.5880).
* ``judged`` recomputes each peer's decision from a state read back
  from the monitor: the region of its knowledge
  ``S_i = X_ii + sum_k (X_ki - X_ik)`` over live slots, in float64,
  leaving out a peer within float32 rounding of a boundary.
* ``unsettled_links`` counts live slots whose message is delivered (not
  pending) but whose copy at the receiver differs from what was sent.
  With no message loss a delivered message is copied verbatim, so this
  is 0; any loss, or a delivery that went to the wrong slot, shows here.
"""

from __future__ import annotations

import numpy as np


def decide(t: dict, v: np.ndarray) -> np.ndarray:
    """Region ids of vectors ``v`` (..., d) under tenant ``t``."""
    v = np.asarray(v, np.float64)
    if t["kind"] == "voronoi":
        c = np.asarray(t["centers"], np.float64)
        dist = ((v[..., None, :] - c) ** 2).sum(-1)
        return np.argmin(dist, axis=-1)
    w = np.asarray(t["w"], np.float64)
    return (v @ w >= float(t["b"])).astype(np.int64)


# A decision is judged only where float32 arithmetic cannot flip it: the
# monitor forms each knowledge vector from a few float32 sums (relative
# error ~1e-6 at degree 34), so a vector this close to a boundary,
# relative to its scale, has two right answers.
AMBIGUOUS = 1e-5


def ambiguous(t: dict, v: np.ndarray) -> np.ndarray:
    """True where vectors ``v`` (..., d) lie within float32 rounding of a
    region boundary of tenant ``t``."""
    v = np.asarray(v, np.float64)
    if t["kind"] == "voronoi":
        c = np.asarray(t["centers"], np.float64)
        dist = np.sort(((v[..., None, :] - c) ** 2).sum(-1), axis=-1)
        scale = 1.0 + (v ** 2).sum(-1) + (c ** 2).sum(-1).max()
        return dist[..., 1] - dist[..., 0] <= AMBIGUOUS * scale
    w = np.asarray(t["w"], np.float64)
    scale = 1.0 + np.abs(v) @ np.abs(w) + abs(float(t["b"]))
    return np.abs(v @ w - float(t["b"])) <= AMBIGUOUS * scale


def boundary_distance(t: dict, v: np.ndarray) -> float:
    """Euclidean distance from ``v`` (d,) to the nearest region boundary."""
    v = np.asarray(v, np.float64)
    if t["kind"] == "voronoi":
        c = np.asarray(t["centers"], np.float64)
        own = int(decide(t, v))
        best = np.inf
        for j in range(c.shape[0]):
            if j != own:
                gap = np.linalg.norm(c[j] - c[own])
                dj = ((v - c[j]) ** 2).sum() - ((v - c[own]) ** 2).sum()
                best = min(best, dj / (2 * gap))
        return float(best)
    w = np.asarray(t["w"], np.float64)
    return float(abs(v @ w - float(t["b"])) / np.linalg.norm(w))


class Truth:
    """One tenant's data as the reference holds it, bursts applied."""

    def __init__(self, tenant: dict):
        self.t = tenant
        self.x = np.array(tenant["x"], np.float32)
        self._sum = self.x.astype(np.float64).sum(0)

    def apply(self, who: np.ndarray, values: np.ndarray) -> None:
        self._sum += (values.astype(np.float64).sum(0)
                      - self.x[who].astype(np.float64).sum(0))
        self.x[who] = values

    def mean(self) -> np.ndarray:
        return self._sum / self.x.shape[0]

    def region(self) -> int:
        return int(decide(self.t, self.mean()))


def knowledge(st: dict, topo: dict, eps: float) -> np.ndarray:
    """Each peer's knowledge vector ``vec(S_i)`` (float64), from a state
    read back as numpy arrays."""
    alive = st["alive"]
    nbr, mask = topo["nbr"], topo["mask"]
    live = mask & alive[:, None] & alive[nbr]
    s_m = st["x_m"].astype(np.float64) + np.where(
        live[..., None], st["in_m"].astype(np.float64)
        - st["out_m"].astype(np.float64), 0.0).sum(1)
    s_c = st["x_c"].astype(np.float64) + np.where(
        live, st["in_c"].astype(np.float64)
        - st["out_c"].astype(np.float64), 0.0).sum(1)
    ok = np.abs(s_c) > eps
    return np.where(ok[:, None], s_m / np.where(ok, s_c, 1.0)[:, None], 0.0)


def judged(t: dict, st: dict, topo: dict, eps: float, want: int):
    """Per live peer of a read-back state: (decides ``want`` beyond doubt,
    decides another region beyond doubt).  A peer within float32
    rounding of a boundary counts in neither."""
    vec = knowledge(st, topo, eps)
    got, near = decide(t, vec), ambiguous(t, vec)
    alive = st["alive"]
    return (got == want) & ~near & alive, (got != want) & ~near & alive


def unsettled_links(st: dict, topo: dict) -> int:
    nbr, rev, mask = topo["nbr"], topo["rev"], topo["mask"]
    alive = st["alive"]
    live = mask & alive[:, None] & alive[nbr]
    delivered = live & ~st["pending"]
    got_m = st["in_m"][nbr, rev]  # what the receiver holds from slot (i, k)
    got_c = st["in_c"][nbr, rev]
    bad = (np.any(got_m != st["out_m"], axis=-1) | (got_c != st["out_c"]))
    return int(np.sum(delivered & bad))


def input_mismatch(truth: Truth, st: dict) -> int:
    """Peers whose local input in the monitor differs from the reference's
    data (weights are 1, bursts set ``<v, 1>``): 0 when ingest is exact."""
    bad = (np.any(st["x_m"] != truth.x, axis=-1)) | (st["x_c"] != 1.0)
    return int(np.sum(bad & st["alive"]))
