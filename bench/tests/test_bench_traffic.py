"""Traffic: it repeats from the seed, and under the cell's own update
stream every tenant's global vector keeps the mix's margin from every
region boundary, at the cells' own sizes."""

import numpy as np
import pytest

from bench import generator, harness, reference

BURSTS = 200  # more than any run pushes: ~45 at 0.72 s a tick in 30 s


def _same(a, b):
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


def test_traffic_repeats_from_the_seed():
    mix = harness.Bench().mix("stream")
    seed = 2 ** 31 + 12345
    for a, b in zip(generator.make_tenants(500, 6, 2, mix, seed),
                    generator.make_tenants(500, 6, 2, mix, seed)):
        _same(a, b)
    for i in (0, 7):
        for x, y in zip(generator.burst(500, 2, mix, seed, i),
                        generator.burst(500, 2, mix, seed, i)):
            np.testing.assert_array_equal(x, y)
    other = generator.make_tenants(500, 6, 2, mix, seed + 1)
    assert not np.array_equal(other[0]["x"],
                              generator.make_tenants(500, 6, 2, mix,
                                                     seed)[0]["x"])
    w0, _ = generator.burst(500, 2, mix, seed, 0)
    w1, _ = generator.burst(500, 2, mix, seed, 1)
    assert not np.array_equal(w0, w1)
    assert w0.size == 5 and np.unique(w0).size == 5


def test_families_alternate():
    mix = harness.Bench().mix("stream")
    kinds = [t["kind"] for t in generator.make_tenants(50, 6, 2, mix, 1)]
    assert kinds == ["voronoi", "halfspace"] * 3


@pytest.mark.parametrize("workload", ["grid80k.stream"])
@pytest.mark.parametrize("seed", [0, 2 ** 31 + 7, 2 ** 32 + 3])
def test_margin_holds_under_the_stream(workload, seed):
    bench = harness.Bench()
    cell = bench.cell(workload)
    cfg, mix = bench.config(cell["config"]), bench.mix(cell["traffic"])
    n = bench.topology(cfg["topology"])["n"]
    q, d = cfg["service"]["capacity"], cfg["service"]["d"]
    truths = [reference.Truth(t)
              for t in generator.make_tenants(n, q, d, mix, seed)]
    worst = min(reference.boundary_distance(tr.t, tr.mean())
                for tr in truths)
    for i in range(BURSTS):
        who, vals = generator.burst(n, d, mix, seed, i)
        for tr in truths:
            tr.apply(who, vals)
            worst = min(worst, reference.boundary_distance(tr.t, tr.mean()))
    assert worst >= mix["margin"], worst


def test_boundary_distance_by_hand():
    half = {"kind": "halfspace", "w": np.array([0.6, 0.8]), "b": 1.0}
    assert reference.boundary_distance(half, np.array([0.0, 0.0])) == \
        pytest.approx(1.0)
    vor = {"kind": "voronoi", "centers": np.array([[0.0, 0.0], [2.0, 0.0],
                                                   [0.0, 4.0]])}
    assert reference.boundary_distance(vor, np.array([0.5, 0.0])) == \
        pytest.approx(0.5)
