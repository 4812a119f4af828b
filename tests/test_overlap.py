"""Overlapped host boundary: double-buffered dispatch, staged epochs.

``ServiceConfig(overlap=True)`` pipelines the service loop: tick K+1's
host boundary (membership drain, admission, ingest) runs while dispatch
K's device work is still in flight, and dispatch K's telemetry is
finished one tick later off its :class:`~repro.service.overlap.
PendingWindow`.  The contracts under test:

* record CONTENT is bitwise identical to synchronous mode under full
  churn + ingest load, on both backends — only *emission* is deferred
  by one tick (``flush()``/``serve()`` drain the last window);
* steady-state overlap stays zero-recompile: the
  :class:`~repro.service.overlap.DoubleBuffer` canary proves every
  swapped operand keeps its traced (shape, dtype) signature, and an
  undeclared reshape raises :class:`~repro.service.overlap.
  BufferReshape` instead of silently recompiling;
* a preempted tenant's targeted ingest is parked and replayed at
  resume, not dropped;
* staged epochs (background partition builds) adopt prebuilt engines
  bitwise-equivalently to the synchronous rebuild, including journal
  catch-up for membership applied while the build was staged.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lss, regions, sim, topology
from repro.obs import InMemoryTracker, jit_cache_size
from repro.service import (ControlPlaneConfig, QuerySpec, Service,
                          ServiceConfig)
from repro.service.overlap import BufferReshape, DoubleBuffer, StagedBuild

DynTopology = topology.DynTopology


def _problem(n, seed=0):
    centers, sample, _, _ = sim.make_problem(sim.ProblemSpec(n=n, seed=seed))
    x = sample(np.random.default_rng(seed + 1), n)
    return np.asarray(centers), x


def _spec(centers, x, seed=0, priority=0):
    return QuerySpec(region=regions.VoronoiRegions(jnp.asarray(centers)),
                     inputs=x, seed=seed, priority=priority)


def _padded_spec(centers, x, n_cap, seed=0):
    """Inputs sized to capacity: zero-weight padding rows (spare slots)."""
    n = x.shape[0]
    xx = np.zeros((n_cap, x.shape[1]), np.float32)
    xx[:n] = x
    w = np.zeros((n_cap,), np.float32)
    w[:n] = 1.0
    return QuerySpec(region=regions.VoronoiRegions(jnp.asarray(centers)),
                     inputs=xx, weights=w, seed=seed)


def _strip(rec):
    """Drop the per-service-instance identifier; everything else in a
    tenant record is part of the parity contract."""
    return {k: v for k, v in rec.items() if k != "trace_id"}


def _state_fields_equal(a: lss.LSSState, b: lss.LSSState, skip=()):
    for name in lss.LSSState._fields:
        if name in skip:
            continue
        av, bv = np.asarray(getattr(a, name)), np.asarray(getattr(b, name))
        assert np.array_equal(av, bv), name


# ---------------------------------------------------------------------------
# record parity: overlap == sync, bitwise, under churn + ingest
# ---------------------------------------------------------------------------


def _run_churny(overlap, backend, ticks=6):
    """One service under the full boundary load: two tenants, streaming
    ingest, a leave and a join mid-serve.  Returns (records, snapshots).
    """
    base = topology.grid(36)
    centers, x = _problem(36, seed=11)
    dyn = DynTopology.from_topology(base, n_cap=40, deg_cap=6)
    svc = Service(dyn, ServiceConfig(
        capacity=2, k_max=3, d=2, cycles_per_dispatch=2, backend=backend,
        engine_shards=2, overlap=overlap))
    qa = svc.admit(_padded_spec(centers, x, 40, seed=0))
    qb = svc.admit(_padded_spec(centers, x, 40, seed=1))
    records = []
    for t in range(ticks):
        if t == 1:
            svc.push_updates([3, 5], [[0.9, 0.1], [0.2, 0.7]])
        if t == 2:
            svc.leave_peer(7)
        if t == 4:
            svc.join_peer(7, value=[0.4, 0.4])
            svc.link_peers(7, 8)
        records.extend(svc.tick())
    records.extend(svc.flush())
    snaps = {q: svc.snapshot(q) for q in (qa, qb)}
    svc.close()
    return records, snaps, (qa, qb)


@pytest.mark.parametrize("backend", ["core", "engine"])
def test_overlap_record_parity_under_churn_and_ingest(backend):
    """The acceptance gate: overlap mode's records are bitwise the sync
    mode's — same dispatch indices, same metrics, same message counts —
    under ingest, a leave, and a join; final slot states match too."""
    sync_recs, sync_snaps, qids = _run_churny(False, backend)
    over_recs, over_snaps, _ = _run_churny(True, backend)
    key = lambda r: (r["dispatch"], r["query"])
    assert len(sync_recs) == len(over_recs)
    for a, b in zip(sorted(sync_recs, key=key), sorted(over_recs, key=key)):
        assert _strip(a) == _strip(b)
    for q in qids:
        _state_fields_equal(sync_snaps[q], over_snaps[q])


def test_overlap_defers_emission_one_tick():
    """tick() under overlap returns the PREVIOUS window's records: the
    first tick emits nothing, each later tick emits dispatch K-1, and
    flush()/serve() drain the final in-flight window."""
    topo = topology.grid(25)
    centers, x = _problem(25, seed=3)
    svc = Service(topo, ServiceConfig(capacity=1, k_max=3, d=2,
                                      cycles_per_dispatch=2, overlap=True))
    svc.admit(_spec(centers, x))
    assert svc.tick() == []  # window 1 launched, nothing to emit yet
    (r1,) = svc.tick()
    assert r1["dispatch"] == 1  # one-tick deferral (sync numbering is 1-based)
    (r2,) = svc.flush()
    assert r2["dispatch"] == 2
    assert svc.flush() == []  # idempotent: nothing pending
    svc.close()

    # serve() self-drains: the trailing window is flushed, so the return
    # value is the FINAL dispatch's records in overlap mode too.
    svc2 = Service(topo, ServiceConfig(capacity=1, k_max=3, d=2,
                                       cycles_per_dispatch=2, overlap=True))
    svc2.admit(_spec(centers, x))
    recs = svc2.serve(4)
    assert [r["dispatch"] for r in recs] == [4]
    assert svc2._pending is None  # nothing left in flight
    svc2.close()


# ---------------------------------------------------------------------------
# zero-recompile: the DoubleBuffer canary and steady-state jit cache
# ---------------------------------------------------------------------------


def test_double_buffer_canary_catches_undeclared_reshape():
    buf = DoubleBuffer()
    a = jnp.zeros((4, 2)), jnp.zeros((4,), jnp.int32)
    buf.swap(*a)
    buf.swap(jnp.ones((4, 2)), jnp.zeros((4,), jnp.int32))  # data-only: ok
    assert buf.swaps == 2 and buf.epochs == 0
    with pytest.raises(BufferReshape):
        buf.swap(jnp.zeros((5, 2)), jnp.zeros((4,), jnp.int32))
    with pytest.raises(BufferReshape):  # dtype change is a retrace too
        buf.swap(jnp.zeros((4, 2)), jnp.zeros((4,), jnp.float32))
    buf.invalidate()  # declared epoch: the new signature is adopted
    buf.swap(jnp.zeros((5, 2)), jnp.zeros((4,), jnp.int32))
    assert buf.epochs == 1


def test_overlap_steady_state_zero_recompile_under_churn():
    """After the warm-up dispatch, membership churn within capacity must
    not grow the jit cache in overlap mode — the double-buffered swap is
    data-only — while the buffer swap counter tracks every dispatch."""
    base = topology.grid(36)
    centers, x = _problem(36, seed=5)
    dyn = DynTopology.from_topology(base, n_cap=40, deg_cap=6)
    svc = Service(dyn, ServiceConfig(capacity=2, k_max=3, d=2,
                                     cycles_per_dispatch=2,
                                     backend="engine", engine_shards=2,
                                     overlap=True))
    svc.admit(_padded_spec(centers, x, 40, seed=0))
    svc.tick()  # warm-up: compiles the step
    before = jit_cache_size(svc._step_call)
    for t in range(4):
        if t == 0:
            svc.leave_peer(11)
        if t == 2:
            svc.join_peer(11, value=[0.3, 0.3])
            svc.link_peers(11, 12)
        svc.tick()
    svc.flush()
    after = jit_cache_size(svc._step_call)
    if before is not None and after is not None:
        assert after == before  # churn stayed data-only
    assert svc._buffers.swaps == 5
    assert svc._buffers.epochs == 0
    svc.close()


# ---------------------------------------------------------------------------
# preempted-tenant ingest: parked, replayed at resume, dropped at retire
# ---------------------------------------------------------------------------


def test_preempted_ingest_parks_and_replays_on_resume():
    """Targeted updates streamed at a preempted tenant buffer in the
    ingest parking lot and replay into its slot when it resumes — the
    suspension pauses the stream instead of losing it."""
    centers, x = _problem(25, seed=5)
    topo = topology.grid(25)
    cp = ControlPlaneConfig(scheduler="priority", preempt=True)
    svc = Service(topo, ServiceConfig(capacity=1, k_max=3, d=2,
                                      cycles_per_dispatch=2, control=cp))
    a = svc.admit(_spec(centers, x, seed=0, priority=0))
    svc.tick()
    b = svc.admit(_spec(centers, x, seed=1, priority=5))
    svc.tick()  # b preempts a
    assert svc.admission_status(a) == "preempted"

    svc.push_updates([3], [[9.0, 9.0]], query_ids=[a])
    svc.tick()  # boundary: the batch targets a suspended tenant -> parked
    assert svc.ingest.num_parked(a) == 1
    svc.push_updates([4], [[7.0, 7.0]], query_ids=[a])
    svc.tick()
    assert svc.ingest.num_parked(a) == 2

    svc.retire(b)  # frees the slot: a resumes, replaying its backlog
    assert svc.admission_status(a) == "active"
    assert svc.ingest.num_parked(a) == 0
    snap = svc.snapshot(a)
    np.testing.assert_array_equal(np.asarray(snap.x_m)[3], [9.0, 9.0])
    np.testing.assert_array_equal(np.asarray(snap.x_m)[4], [7.0, 7.0])
    np.testing.assert_array_equal(np.asarray(snap.x_c)[[3, 4]], [1.0, 1.0])
    svc.close()


def test_preempted_ingest_discarded_at_retire_and_bounded():
    centers, x = _problem(16, seed=2)
    topo = topology.grid(16)
    cp = ControlPlaneConfig(scheduler="priority")
    svc = Service(topo, ServiceConfig(capacity=1, k_max=3, d=2,
                                      cycles_per_dispatch=1, control=cp))
    a = svc.admit(_spec(centers, x, 0, priority=0))
    svc.admit(_spec(centers, x, 1, priority=4))
    svc.tick()
    assert svc.admission_status(a) == "preempted"
    svc.push_updates([2], [[1.0, 1.0]], query_ids=[a])
    svc.tick()
    assert svc.ingest.num_parked(a) == 1
    svc.retire(a)  # retiring a suspended tenant drops its backlog
    assert svc.ingest.num_parked(a) == 0
    svc.close()

    # The parking lot is bounded per tenant: oldest batches are shed.
    from repro.service import StreamIngest
    ing = StreamIngest(max_parked=2)
    for i in range(4):
        ing.park("q", ing.push([0], [[float(i), 0.0]], query_ids=("q",)))
        ing.drain()
    assert ing.num_parked("q") == 2
    assert ing.parked_dropped == 2
    got = ing.take_parked("q")
    assert [float(b.values[0, 0]) for b in got] == [2.0, 3.0]  # oldest shed


# ---------------------------------------------------------------------------
# staged epochs: background builds adopt bitwise
# ---------------------------------------------------------------------------


def test_staged_rebalance_adopts_prebuilt_engine_bitwise():
    """A rebalance epoch that adopts a background-staged partition build
    emits exactly what the synchronous rebuild emits (which itself is
    observable-invisible)."""
    base = topology.grid(36)
    centers, x = _problem(40, seed=9)

    def run(staged):
        dyn = DynTopology.from_topology(base, n_cap=40, deg_cap=6)
        svc = Service(dyn, ServiceConfig(
            capacity=2, k_max=3, d=2, cycles_per_dispatch=2,
            backend="engine", engine_shards=2))
        q = svc.admit(_padded_spec(centers, x, 40, seed=0))
        out = []
        for disp in range(6):
            if disp == 2:
                svc.join_peer(36, value=[0.2, 0.2])
                svc.link_peers(36, 7)
                svc.leave_peer(12)
            if disp == 3:
                if staged:
                    svc._staged["rebalance"] = \
                        svc.backend.stage_rebalance(svc._dyn)
                ev = svc.rebalance_now()
                assert ev is not None and ev["staged"] is staged
            out.extend(svc.tick())
        snap = svc.snapshot(q)
        svc.close()
        return out, snap

    recs_sync, snap_sync = run(False)
    recs_staged, snap_staged = run(True)
    assert len(recs_sync) == len(recs_staged) == 6
    for a, b in zip(recs_sync, recs_staged):
        assert _strip(a) == _strip(b)
    _state_fields_equal(snap_sync, snap_staged)


def test_staged_regrow_adopts_with_journal_catchup():
    """A regrow epoch adopting a build staged BEFORE further membership
    churn catches the prebuilt engine up from the topology journal
    (changed_rows_since the staged version) and matches the synchronous
    rebuild bitwise."""
    base = topology.grid(25)
    centers, x = _problem(26, seed=7)
    x26 = np.zeros((26, 2), np.float32)
    x26[:25] = x[:25]

    def run(staged):
        dyn = DynTopology.from_topology(base, n_cap=26, deg_cap=5)
        svc = Service(dyn, ServiceConfig(
            capacity=2, k_max=3, d=2, cycles_per_dispatch=2,
            backend="engine", engine_shards=2))
        spec = QuerySpec(region=regions.VoronoiRegions(jnp.asarray(centers)),
                         inputs=x26,
                         weights=np.r_[np.ones(25), 0.0].astype(np.float32),
                         seed=0)
        q = svc.admit(spec)
        out = [*svc.tick()]
        if staged:
            build, ver = svc.backend.stage_regrow(svc._dyn, n_cap=30,
                                                  deg_cap=5)
            svc._staged["regrow"] = (build, ver,
                                     {"n_cap": 30, "deg_cap": 5})
        # Membership applied AFTER staging: adoption must replay it onto
        # the prebuilt tables from the journal.
        svc.unlink_peers(3, 4)
        out.extend(svc.tick())
        svc.grow_capacity(n_cap=30, deg_cap=5)
        assert svc.capman.epochs[-1]["kind"] == "regrow"
        assert svc.capman.epochs[-1]["staged"] is staged
        svc.join_peer(26, value=[0.1, 0.1])
        svc.link_peers(26, 5)
        out.extend(svc.tick())
        out.extend(svc.tick())
        snap = svc.snapshot(q)
        svc.close()
        return out, snap

    recs_sync, snap_sync = run(False)
    recs_staged, snap_staged = run(True)
    assert len(recs_sync) == len(recs_staged)
    for a, b in zip(recs_sync, recs_staged):
        assert _strip(a) == _strip(b)
    _state_fields_equal(snap_sync, snap_staged)


def test_staged_build_surfaces_build_errors_at_take():
    def boom():
        raise RuntimeError("partition build failed")

    sb = StagedBuild(boom, label="rebalance")
    with pytest.raises(RuntimeError, match="partition build failed"):
        sb.take()  # take() joins, then re-raises the build error
    assert sb.ready()

    ok = StagedBuild(lambda: "engine", label="regrow")
    assert ok.take() == "engine"


# ---------------------------------------------------------------------------
# observability staleness under overlap: window-scoped triggers and spans
# ---------------------------------------------------------------------------


def test_overlap_flight_dump_and_spans_use_window_dispatch(tmp_path):
    """Under overlap, window K's telemetry is finished while dispatch K+1
    is already live — so a flight-recorder trigger and the observe span
    must be stamped with the WINDOW's counters, not the service's.  The
    dump filename/header carry ``w.dispatch``/``w.t``, and every observe
    span's ``dispatch`` attr matches its window (one behind the tick root
    that finished it)."""
    import json
    import os

    from repro.obs import AlertRule

    topo = topology.grid(25)
    centers, x = _problem(25, seed=3)
    tr = InMemoryTracker()
    svc = Service(topo, ServiceConfig(
        capacity=1, k_max=3, d=2, cycles_per_dispatch=2, overlap=True,
        alerts=(AlertRule("always_on", "service_active_slots",
                          above=-1.0),),
        flight_dump_dir=str(tmp_path)), tracker=tr)
    svc.admit(_spec(centers, x))
    svc.tick()  # launches window 1; nothing finished yet -> no dump
    svc.tick()  # finishes window 1 while dispatch 2 is live -> alert dump
    # The trigger fired for window 1; the live counter already says 2.
    assert svc.dispatches == 2
    dumps = sorted(os.listdir(tmp_path))
    assert dumps == ["flight-d000001-alert.jsonl"]
    header = json.loads(
        open(os.path.join(tmp_path, dumps[0])).readline())
    assert header["dispatch"] == 1
    assert header["t"] == 2  # window 1 ran 2 cycles
    svc.flush()
    svc.close()

    # Span bookkeeping: each observe span is stamped with the window it
    # synced; under overlap that is one behind the tick that ran it
    # (except the flush tick, which IS its window's root).
    spans = [r for r in tr.records if r.get("kind") == "span"]
    ticks = {s["span_id"]: s for s in spans if s["name"] == "tick"}
    observes = [s for s in spans if s["name"] == "observe"]
    assert len(observes) == 2  # windows 1 and 2 both finished
    for obs_span in observes:
        parent = ticks[obs_span["parent_id"]]
        if parent["attrs"].get("flush"):
            assert obs_span["attrs"]["dispatch"] == \
                parent["attrs"]["dispatch"]
        else:
            assert obs_span["attrs"]["dispatch"] == \
                parent["attrs"]["dispatch"] - 1
    # Tick roots are labeled with the dispatch they RAN: 1, 2, then the
    # flush root re-labeled with the window it drained (2).
    assert [t["attrs"]["dispatch"] for t in
            sorted(ticks.values(), key=lambda s: s["span_id"])] == [1, 2, 2]


def test_sync_observe_span_matches_tick_dispatch():
    """In sync mode the observe span and its tick root agree on the
    dispatch index — the window is finished inside the tick that ran
    it."""
    topo = topology.grid(25)
    centers, x = _problem(25, seed=3)
    tr = InMemoryTracker()
    svc = Service(topo, ServiceConfig(capacity=1, k_max=3, d=2,
                                      cycles_per_dispatch=2), tracker=tr)
    svc.admit(_spec(centers, x))
    svc.tick()
    svc.tick()
    svc.close()
    spans = [r for r in tr.records if r.get("kind") == "span"]
    ticks = {s["span_id"]: s for s in spans if s["name"] == "tick"}
    observes = [s for s in spans if s["name"] == "observe"]
    assert len(observes) == 2
    for obs_span in observes:
        assert obs_span["attrs"]["dispatch"] == \
            ticks[obs_span["parent_id"]]["attrs"]["dispatch"]
