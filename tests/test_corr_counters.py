"""The correction do-while's counters on the served path.

Each tenant's record carries ``corr_iters`` (the passes its slot needed),
``corr_running`` (the peers still running, summed over those passes) and
``corr_passes`` (the passes the dispatch's vmapped loop ran: it runs until
every slot stops).  On a small Chord service (reference suite), a
single-tenant ``lss.cycle_impl(..., with_stats=True)`` replay of each
tenant gives its state and its counts bitwise, and the lockstep passes
are the per-cycle most over the slots.
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lss, regions, sim, topology
from repro.obs import InMemoryTracker
from repro.service import QuerySpec, Service, ServiceConfig
from repro.service import query as qmod

K, DISPATCHES = 8, 3


def _specs(n):
    out = []
    for i in range(4):
        centers, sample, _, _ = sim.make_problem(
            sim.ProblemSpec(n=n, seed=20 + i))
        x = sample(np.random.default_rng(30 + i), n)
        if i % 2:
            region = regions.HalfspaceRegions(
                w=jnp.asarray(np.float32([1.0, -0.5])), b=jnp.float32(0.1))
        else:
            region = regions.VoronoiRegions(centers)
        out.append(QuerySpec(region=region, inputs=x, seed=i,
                             beta=1e-3 if i < 2 else 2e-3, ell=1 + i % 2))
    return out


def test_counters_match_single_tenant_replay():
    topo = topology.chord(100)
    tracker = InMemoryTracker()
    svc = Service(topo, ServiceConfig(capacity=4, k_max=4, d=2,
                                      cycles_per_dispatch=K,
                                      use_kernels=False), tracker=tracker)
    specs = _specs(topo.n)
    qids = [svc.admit(s) for s in specs]
    recs = [svc.tick() for _ in range(DISPATCHES)]
    ta = lss.TopoArrays.from_topology(topo)
    n = topo.n

    @jax.jit
    def replay_cycle(st, qp):
        cfg = svc.base_cfg._replace(beta=qp.beta, ell=qp.ell, eps=qp.eps)
        return lss.cycle_impl(st, ta, cfg, qmod.decide_fn(qp.regions),
                              gate=qp.active, with_stats=True)

    iters = np.zeros((len(qids), DISPATCHES, K), np.int64)
    running = np.zeros_like(iters)
    for q, (qid, spec) in enumerate(zip(qids, specs)):
        slot = svc.registry.slot_of(qid)
        qp = jax.tree_util.tree_map(lambda a: a[slot], svc.registry.params)
        st = lss.init_state(ta, spec.input_wv(), seed=spec.seed)
        for dsp in range(DISPATCHES):
            for c in range(K):
                st, _, it, ran = replay_cycle(st, qp)
                iters[q, dsp, c], running[q, dsp, c] = int(it), int(ran)
        snap = svc.snapshot(qid)
        for f in lss.LSSState._fields:
            if f == "msgs":  # the service resets it after each window
                continue
            assert np.array_equal(np.asarray(getattr(snap, f)),
                                  np.asarray(getattr(st, f))), (qid, f)
    assert iters.sum() > 0
    for dsp, window in enumerate(recs):
        by_q = {r["query"]: r for r in window}
        passes = int(iters[:, dsp].max(axis=0).sum())
        for q, qid in enumerate(qids):
            r = by_q[qid]
            assert r["corr_iters"] == iters[q, dsp].sum()
            assert r["corr_running"] == running[q, dsp].sum()
            assert r["corr_passes"] == passes
            assert r["corr_running"] <= r["corr_iters"] * n
            assert r["corr_iters"] <= r["corr_passes"]
    reg = tracker.registry
    assert reg.counter("service_corr_passes_total").value() == sum(
        int(iters[:, d].max(axis=0).sum()) for d in range(DISPATCHES))
    for q, qid in enumerate(qids):
        assert reg.counter("service_corr_running_peers_total").value(
            query=qid) == running[q].sum()


def test_engine_counts_match_core():
    """The engine backend's records carry the core backend's counts: the
    same do-while runs over the shards' flattened rows."""
    topo = topology.chord(64)
    got = {}
    for backend in ("core", "engine"):
        svc = Service(topo, ServiceConfig(capacity=2, k_max=4, d=2,
                                          cycles_per_dispatch=4,
                                          use_kernels=False,
                                          backend=backend, engine_shards=2))
        for spec in _specs(topo.n)[:2]:
            svc.admit(spec)
        got[backend] = [
            sorted((r["slot"], r["corr_iters"], r["corr_passes"],
                    r["corr_running"]) for r in svc.tick())
            for _ in range(2)]
    assert got["engine"] == got["core"]
    assert any(r[1] > 0 for window in got["core"] for r in window)
