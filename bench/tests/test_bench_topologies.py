"""The array-built topologies equal the monitor's own builders slot for
slot (slot order fixes the order of every per-peer sum, so the bits)."""

import numpy as np
import pytest

from bench import harness
from repro.core import topology


@pytest.mark.parametrize("n", [2, 3, 5, 16, 17, 100, 1000, 1025])
def test_chord_equals_topology_chord(n):
    got = harness.Bench().topology({"kind": "chord", "n": n})
    want = topology.chord(n)
    for key in ("nbr", "mask", "rev"):
        np.testing.assert_array_equal(got[key], getattr(want, key))
    assert (got["n"], got["max_deg"]) == (want.n, want.max_deg)
    topology.Topology(got["nbr"], got["mask"], got["rev"], got["n"],
                      got["max_deg"]).validate()


@pytest.mark.parametrize("side", [2, 3, 7, 20])
def test_grid_equals_topology_grid(side):
    got = harness.Bench().topology({"kind": "grid", "side": side})
    want = topology.grid(side * side)
    for key in ("nbr", "mask", "rev"):
        np.testing.assert_array_equal(got[key], getattr(want, key))
    topology.Topology(got["nbr"], got["mask"], got["rev"], got["n"],
                      got["max_deg"]).validate()


def test_full_size_shapes():
    b = harness.Bench()
    chord = b.topology({"kind": "chord", "n": 80000})
    assert chord["max_deg"] == 34 and int(chord["mask"].sum()) // 2 == 1360000
    grid = b.topology({"kind": "grid", "side": 283})
    assert grid["n"] == 80089 and grid["max_deg"] == 4
