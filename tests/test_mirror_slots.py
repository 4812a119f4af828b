"""``lss.mirror_slots``: the delivery gather, against plain numpy indexing.

Every message the cycle delivers goes through this gather, so it has to
be a bitwise permutation for every per-slot array the callers hand it:
flags, counters and moments with or without trailing component axes,
plain or under the service's query ``vmap``.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lss, topology

TOPOLOGIES = {
    "grid": lambda: topology.grid(36),
    # Degrees 2..~20: most rows padded, padding slots masked out.
    "ba_padded": lambda: topology.barabasi_albert(40, m=2, seed=3),
}


def _values(rng, shape, dtype):
    if dtype == np.bool_:
        return rng.random(shape) < 0.5
    if dtype == np.int32:
        return rng.integers(-2**31, 2**31 - 1, shape, dtype=np.int32)
    a = rng.standard_normal(shape).astype(np.float32)
    a.flat[::7] = -0.0
    a.flat[3::11] = np.inf
    return a


@pytest.mark.parametrize("vmapped", [False, True], ids=["plain", "vmap"])
@pytest.mark.parametrize("dtype", [np.bool_, np.int32, np.float32],
                         ids=["bool", "int32", "float32"])
@pytest.mark.parametrize("comp", [(), (2,), (3,)],
                         ids=["scalar", "d2", "d3"])
@pytest.mark.parametrize("topo_name", sorted(TOPOLOGIES))
def test_mirror_slots_matches_numpy_indexing(topo_name, comp, dtype,
                                             vmapped):
    t = TOPOLOGIES[topo_name]()
    if topo_name == "ba_padded":
        assert not t.mask.all()
    topo = lss.TopoArrays.from_topology(t)
    rng = np.random.default_rng(len(comp) * 10 + vmapped)
    q = 3 if vmapped else 1
    a = _values(rng, (q, t.n, t.max_deg, *comp), dtype)
    if vmapped:
        got = jax.jit(jax.vmap(lss.mirror_slots, (0, None)))(
            jnp.asarray(a), topo)
    else:
        got = jax.jit(lss.mirror_slots)(jnp.asarray(a[0]), topo)[None]
    got = np.asarray(got)
    assert got.shape == a.shape and got.dtype == a.dtype
    valid = np.asarray(t.mask)
    nbr, rev = np.asarray(t.nbr), np.asarray(t.rev)
    for i in range(q):
        want = a[i][nbr, rev]
        assert np.array_equal(got[i][valid].view(np.uint8),
                              want[valid].view(np.uint8))


def test_component_planes_are_gathered_one_at_a_time():
    """A ``(Q, n, D, d)`` array compiles to d gathers whose windows hold
    one component each: slice sizes ``(Q, 1)``, never ``(Q, d, 1)``.  The
    (Q, d) window is the form that cost the TPU ~18x per value."""
    t = topology.grid(36)
    topo = lss.TopoArrays.from_topology(t)
    q, d = 5, 2
    a = jnp.zeros((q, t.n, t.max_deg, d), jnp.float32)
    txt = jax.jit(jax.vmap(lss.mirror_slots, (0, None))).lower(
        a, topo).compile().as_text()
    sizes = re.findall(r" gather\(.*?slice_sizes=\{([\d,]+)\}", txt)
    assert len(sizes) == d, sizes
    assert all(s == f"{q},1" for s in sizes), sizes
