"""The reference forgives a peer only where the monitor's float32 status
may put it on the other side of a boundary (``reference.doubt``), and
still judges every other peer.

The float32 side is the monitor's own: ``stopping.status`` folds the
slots, ``wvs.vec`` divides, and the query's region family decides."""

import json
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from repro.core import regions, stopping, wvs

DATA = pathlib.Path(__file__).parent / "data"
BETA, EPS = 1e-3, 1e-9

TENANTS = {
    "voronoi": {"kind": "voronoi",
                "centers": np.array([[-0.9, 0.2], [0.7, 0.4], [0.1, -1.1]],
                                    np.float32)},
    "halfspace": {"kind": "halfspace",
                  "w": np.array([0.6, 0.8], np.float32), "b": np.float32(0.3)},
}


def family(t: dict):
    if t["kind"] == "voronoi":
        return regions.VoronoiRegions(jnp.asarray(t["centers"]))
    return regions.HalfspaceRegions(w=jnp.asarray(t["w"]),
                                    b=jnp.float32(t["b"]))


def one_hop(n: int, slots: int) -> dict:
    """Every slot live: each points at peer 0, and every peer is alive."""
    return {"nbr": np.zeros((n, slots), np.int32),
            "mask": np.ones((n, slots), bool),
            "rev": np.zeros((n, slots), np.int32)}


def program_regions(t: dict, st: dict, topo: dict) -> np.ndarray:
    """Each peer's region as the monitor decides it, in float32."""
    s = stopping.status(*(jnp.asarray(st[k]) for k in
                          ("x_m", "x_c", "out_m", "out_c", "in_m", "in_c")),
                        jnp.asarray(topo["mask"]))
    return np.asarray(family(t).decide(wvs.vec(s, EPS)))


def near_boundary(t: dict, rng, n: int) -> np.ndarray:
    """Vectors (n, 2) at distances from 1e-6 to 1e-2 off a boundary of
    ``t``, on either side."""
    off = rng.choice([-1.0, 1.0], n) * 10.0 ** rng.uniform(-6, -2, n)
    if t["kind"] == "voronoi":
        a, b = t["centers"][:2].astype(np.float64)
        u = (b - a) / np.linalg.norm(b - a)
        along = np.array([-u[1], u[0]]) * rng.uniform(-0.2, 0.2, n)[:, None]
        return (a + b) / 2 + along + off[:, None] * u
    w = t["w"].astype(np.float64)
    u = w / np.linalg.norm(w)
    along = np.array([-u[1], u[0]]) * rng.uniform(-1, 1, n)[:, None]
    return u * float(t["b"]) / np.linalg.norm(w) + along + off[:, None] * u


def floor_states(t: dict, slots: int, n: int, seed: int) -> dict:
    """Peers whose weight's terms (1 to 3) cancel down to the ``beta``
    floor, each with its knowledge vector near a boundary: the last
    slot's outgoing message takes up the rest."""
    rng = np.random.default_rng(seed)
    s_c = BETA * rng.uniform(1.0, 1.5, n)
    s_m = near_boundary(t, rng, n) * s_c[:, None]
    x_m = rng.standard_normal((n, 2))
    x_c = np.ones(n)
    in_m = rng.uniform(-1.5, 1.5, (n, slots, 2))
    out_m = rng.uniform(-1.5, 1.5, (n, slots, 2))
    in_c = rng.uniform(1.0, 3.0, (n, slots))
    out_c = rng.uniform(1.0, 3.0, (n, slots))
    out_m[:, -1] = x_m + (in_m - out_m).sum(1) + out_m[:, -1] - s_m
    out_c[:, -1] = x_c + (in_c - out_c).sum(1) + out_c[:, -1] - s_c
    f32 = lambda a: a.astype(np.float32)
    return {"alive": np.ones(n, bool), "x_m": f32(x_m), "x_c": f32(x_c),
            "in_m": f32(in_m), "out_m": f32(out_m), "in_c": f32(in_c),
            "out_c": f32(out_c)}


@pytest.mark.parametrize("kind", ["voronoi", "halfspace"])
@pytest.mark.parametrize("slots", [4, 34])
def test_float32_disagreements_are_forgiven(slots, kind):
    """Soundness: wherever the monitor's float32 region differs from the
    float64 one, the peer is in doubt; and the old relative margin alone
    misses some of those peers, so the bound is what forgives them."""
    t = TENANTS[kind]
    st = floor_states(t, slots, 4000, seed=slots)
    topo = one_hop(4000, slots)
    vec, radius = reference.doubt(st, topo, EPS)
    assert np.abs(reference._status(st, topo)[1]).max() < 2 * BETA
    f64 = reference.decide(t, vec)
    f32 = program_regions(t, st, topo)
    differ = f32 != f64
    near = reference.ambiguous(t, vec, radius)
    assert near[differ].all()
    assert (differ & ~reference.ambiguous(t, vec)).sum() >= 1
    # judged leaves every such peer out, whatever the right answer is
    for want in np.unique(f64):
        right, wrong = reference.judged(t, st, topo, EPS, int(want))
        assert not (right | wrong)[differ].any()


def one_peer(v, s_c: float, terms: float, slots: int = 4) -> dict:
    """A peer with knowledge ``v`` and weight ``s_c``, whose slots carry
    messages of size ``terms`` that cancel: the weight's down to
    ``s_c``, the moments' exactly, leaving ``x_m = v s_c``."""
    v = np.asarray(v, np.float64)
    in_c = np.full((1, slots), terms)
    out_c = in_c.copy()
    out_c[0, -1] += 1.0 - s_c
    in_m = np.full((1, slots, 2), terms)
    out_m = in_m.copy()
    x_m = v[None] * s_c
    f32 = lambda a: np.asarray(a, np.float32)
    return {"alive": np.ones(1, bool), "x_m": f32(x_m), "x_c": f32([1.0]),
            "in_m": f32(in_m), "out_m": f32(out_m), "in_c": f32(in_c),
            "out_c": f32(out_c)}


# Both tenants split the plane at x = 0, region 1 on the right.
SPLIT = {"voronoi": {"kind": "voronoi",
                     "centers": np.array([[-1.0, 0.0], [1.0, 0.0]])},
         "halfspace": {"kind": "halfspace", "w": np.array([1.0, 0.0]),
                       "b": 0.0}}


@pytest.mark.parametrize("kind", ["voronoi", "halfspace"])
def test_a_peer_past_a_boundary_is_still_wrong(kind):
    """Teeth: 1e-4 on the wrong side of the boundary, a well-conditioned
    peer is judged wrong; at the ``beta`` floor the same vector is
    forgiven only when its status bound reaches the boundary."""
    t, v = SPLIT[kind], [-1e-4, 0.0]
    topo = one_hop(1, 4)

    def judge(st):
        vec, radius = reference.doubt(st, topo, EPS)
        assert vec[0] == pytest.approx(v, abs=1e-6)
        right, wrong = reference.judged(t, st, topo, EPS, want=1)
        assert not right[0]
        return bool(wrong[0]), float(radius[0])

    wrong, radius = judge(one_peer(v, s_c=0.75, terms=1.0))
    assert wrong and radius < 1e-5
    # At the floor with small terms the bound stays short of the boundary.
    wrong, radius = judge(one_peer(v, s_c=BETA, terms=1e-4))
    assert wrong and radius < 1e-4
    # At the floor with terms of 2 cancelling it reaches past it.
    wrong, radius = judge(one_peer(v, s_c=BETA, terms=2.0))
    assert not wrong and radius >= 1e-4


def test_recorded_grid_peer():
    """The peer of a ``grid80k.stream`` run on the chip whose float32
    decision was right and whose float64 one lay past the boundary: the
    old margin called it wrong, the status bound forgives it."""
    rec = json.loads((DATA / "grid_peer_2147492013.json").read_text())
    t = {k: v if k == "kind" else np.asarray(v)
         for k, v in rec["tenant"].items()}
    live, slots = len(rec["in_c"]), rec["D"]
    pad = lambda k, shape: np.concatenate(
        [np.asarray(rec[k], np.float32).reshape(shape),
         np.zeros((slots - live,) + shape[1:], np.float32)])[None]
    st = {"alive": np.ones(1, bool),
          "x_m": np.asarray(rec["x_m"], np.float32)[None],
          "x_c": np.asarray([rec["x_c"]], np.float32),
          "in_m": pad("in_m", (live, 2)), "out_m": pad("out_m", (live, 2)),
          "in_c": pad("in_c", (live,)), "out_c": pad("out_c", (live,))}
    topo = one_hop(1, slots)
    topo["mask"][0, live:] = False
    vec = reference.knowledge(st, topo, rec["eps"])
    want = rec["want"]
    assert reference.decide(t, vec)[0] != want
    assert not reference.ambiguous(t, vec)[0]  # the old rule: wrong
    assert program_regions(t, st, topo)[0] == want  # float32 decides right
    _, wrong = reference.judged(t, st, topo, rec["eps"], want)
    assert not wrong[0]
