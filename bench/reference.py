"""The plain reference: what every answer of the monitor has to be.

Numpy only; nothing of the monitor is imported and nothing it computed
is reused except the state read back from it, which is what is judged.

* ``Truth`` keeps its own copy of one tenant's data, applies the same
  bursts the run pushes, and gives the global mean and its region: the
  answer every peer has to reach (Problem 2 of arXiv 1212.5880).
* ``judged`` recomputes each peer's decision from a state read back
  from the monitor: the region of its knowledge
  ``S_i = X_ii + sum_k (X_ki - X_ik)`` over live slots, in float64,
  leaving out a peer whose float32 knowledge may lie on the other side
  of a boundary (``doubt``).
* ``unsettled_links`` counts live slots whose message is delivered (not
  pending) but whose copy at the receiver differs from what was sent.
  With no message loss a delivered message is copied verbatim, so this
  is 0; any loss, or a delivery that went to the wrong slot, shows here.
"""

from __future__ import annotations

import numpy as np

U = 2.0 ** -24  # unit roundoff of float32


def gamma(n: int) -> float:
    """``n u / (1 - n u)``: the relative error bound of n float32
    roundings (Higham, Accuracy and Stability of Numerical Algorithms,
    Lemma 3.1)."""
    return n * U / (1.0 - n * U)


def decide(t: dict, v: np.ndarray) -> np.ndarray:
    """Region ids of vectors ``v`` (..., d) under tenant ``t``."""
    v = np.asarray(v, np.float64)
    if t["kind"] == "voronoi":
        c = np.asarray(t["centers"], np.float64)
        dist = ((v[..., None, :] - c) ** 2).sum(-1)
        return np.argmin(dist, axis=-1)
    w = np.asarray(t["w"], np.float64)
    return (v @ w >= float(t["b"])).astype(np.int64)


# The decision's own rounding: the monitor compares float32 scores
# (``regions.coord_dot``, the squared distances to the centres), so a
# vector whose score gap is this small, relative to the scores' scale,
# decides either way.  The error of the knowledge vector itself, which
# can be far larger at a peer whose status sum cancels, is ``doubt``'s.
AMBIGUOUS = 1e-5


def boundary_distance(t: dict, v: np.ndarray) -> np.ndarray:
    """Euclidean distance from vectors ``v`` (..., d) to the nearest
    boundary of the region each lies in (float64)."""
    v = np.asarray(v, np.float64)
    if t["kind"] == "voronoi":
        c = np.asarray(t["centers"], np.float64)
        sq = ((v[..., None, :] - c) ** 2).sum(-1)  # (..., k)
        # To the bisector of the own centre and each other one.
        gap = np.linalg.norm(c[:, None, :] - c, axis=-1)[np.argmin(sq, -1)]
        lead = sq - sq.min(-1, keepdims=True)
        with np.errstate(divide="ignore", invalid="ignore"):
            return np.where(gap > 0, lead / (2.0 * gap), np.inf).min(-1)
    w = np.asarray(t["w"], np.float64)
    return np.abs(v @ w - float(t["b"])) / np.linalg.norm(w)


def _rounding(t: dict, v: np.ndarray) -> np.ndarray:
    """True where the float32 decision on ``v`` (..., d) may flip: its
    score gap is within ``AMBIGUOUS`` of the scores' scale."""
    if t["kind"] == "voronoi":
        c = np.asarray(t["centers"], np.float64)
        dist = np.sort(((v[..., None, :] - c) ** 2).sum(-1), axis=-1)
        scale = 1.0 + (v ** 2).sum(-1) + (c ** 2).sum(-1).max()
        return dist[..., 1] - dist[..., 0] <= AMBIGUOUS * scale
    w = np.asarray(t["w"], np.float64)
    scale = 1.0 + np.abs(v) @ np.abs(w) + abs(float(t["b"]))
    return np.abs(v @ w - float(t["b"])) <= AMBIGUOUS * scale


def ambiguous(t: dict, v: np.ndarray, radius=0.0) -> np.ndarray:
    """True where the region of vectors ``v`` (..., d) of tenant ``t`` is
    not beyond doubt: the float32 decision may flip (``AMBIGUOUS``), or
    the float32 knowledge vector, within ``radius`` of ``v`` (``doubt``),
    may lie in another region."""
    v = np.asarray(v, np.float64)
    return _rounding(t, v) | (boundary_distance(t, v) <= radius)


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


def _status(st: dict, topo: dict):
    """Per peer, in float64 over live slots: ``S_i``'s moment and weight
    (``s_m`` (n, d), ``s_c`` (n,)), and the sums of the magnitudes of the
    terms they add (``|x| + sum_k |in_k| + |out_k|``, same shapes)."""
    alive = st["alive"]
    live = topo["mask"] & alive[:, None] & alive[topo["nbr"]]
    out = []
    for x, i, o, lv in ((st["x_m"], st["in_m"], st["out_m"], live[..., None]),
                        (st["x_c"], st["in_c"], st["out_c"], live)):
        x, i, o = (a.astype(np.float64) for a in (x, i, o))
        out.append((x + np.where(lv, i - o, 0.0).sum(1),
                    np.abs(x) + np.where(lv, np.abs(i) + np.abs(o),
                                         0.0).sum(1)))
    (s_m, a_m), (s_c, a_c) = out
    return s_m, s_c, a_m, a_c


def knowledge(st: dict, topo: dict, eps: float) -> np.ndarray:
    """Each peer's knowledge vector ``vec(S_i)`` (float64), from a state
    read back as numpy arrays."""
    return _vec(*_status(st, topo)[:2], eps)


def _vec(s_m: np.ndarray, s_c: np.ndarray, eps: float) -> np.ndarray:
    ok = np.abs(s_c) > eps
    return np.where(ok[:, None], s_m / np.where(ok, s_c, 1.0)[:, None], 0.0)


def doubt(st: dict, topo: dict, eps: float):
    """Per peer: the knowledge vector (as ``knowledge`` gives it) and how
    far (Euclidean) the monitor's float32 one may lie from it; ``inf``
    where the monitor's guard ``|s_c| > eps`` may have gone either way.

    The monitor folds ``s = x + sum_k (in_k - out_k)`` in float32 over
    all D slots (a masked slot adds an exact 0): one rounding for each
    ``in_k - out_k``, D - 1 for the slot fold (the first add, to 0, is
    exact) and one for adding ``x``.  Recursive summation in any order
    gives ``|s' - s| <= gamma(n - 1) * sum |terms|`` for n terms
    (Higham, section 4.2); the differences' roundings join the same
    product of ``1 + delta`` factors, so with ``|in - out| <= |in| +
    |out|``, for the weight and for each moment component alike,

        E = gamma(D + 2) * (|x| + sum_live (|in_k| + |out_k|)),

    one rounding spare.  For ``v = s_m / s_c`` with ``|s_c| > E_c``,

        s_m'/s_c' - s_m/s_c = (e_m - v e_c) / s_c',
        |dv_i| <= (E_m,i + |v_i| E_c) / (|s_c| - E_c) = q_i,

    and the division adds ``gamma(2) * (|v_i| + q_i)`` (one rounding
    when correctly rounded, two for a reciprocal and a multiply).  The
    radius is the 2-norm over the components.  It is large only where
    the weight's terms cancel: at the ``beta`` floor, terms of 1 to 3
    sum to ~1e-3, and the bound is ~1000 times a well-conditioned
    peer's.  Where ``|s_c| <= eps - E_c`` both sides zero the vector
    (radius 0).  Where ``|s_c|`` lies within ``E_c`` of ``eps`` the
    guard may split them; with ``eps >= 0`` that takes in every other
    ``|s_c| <= E_c``, where the bound on the quotient fails.
    """
    s_m, s_c, a_m, a_c = _status(st, topo)
    g = gamma(topo["nbr"].shape[1] + 2)
    e_m, e_c = g * a_m, g * a_c
    c = np.abs(s_c)
    zeroed = c <= eps - e_c
    split = ~zeroed & (c <= eps + e_c)
    divided = ~(zeroed | split)
    v = np.abs(s_m) / np.where(divided, c, 1.0)[:, None]
    q = (e_m + v * e_c[:, None]) / np.where(divided, c - e_c, 1.0)[:, None]
    r = np.sqrt(((q + gamma(2) * (v + q)) ** 2).sum(-1))
    return _vec(s_m, s_c, eps), np.where(split, np.inf,
                                         np.where(zeroed, 0.0, r))


def judged(t: dict, st: dict, topo: dict, eps: float, want: int):
    """Per live peer of a read-back state: (decides ``want`` beyond doubt,
    decides another region beyond doubt).  A peer whose float32 decision
    may differ from the float64 one counts in neither."""
    return verdict(t, st, topo, eps, want)[:2]


def verdict(t: dict, st: dict, topo: dict, eps: float, want: int):
    """``judged``'s two masks, and a third: the live peers left out only
    for their status error (``doubt``), not for the decision's own
    rounding, which shows how far the error bound empties the check."""
    vec, radius = doubt(st, topo, eps)
    got, near = decide(t, vec), ambiguous(t, vec, radius)
    alive = st["alive"]
    return ((got == want) & ~near & alive, (got != want) & ~near & alive,
            near & ~_rounding(t, vec) & alive)


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
