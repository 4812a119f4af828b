"""The service on the profiler's timeline.

* every tracker span opens a ``repro.<name>`` profiler annotation, so a
  tick under ``jax.profiler.trace`` shows the service's spans on the host
  plane, nested in its ``repro.tick``;
* the compiled ``_step`` names its device work by scope: every gather
  (``live_mask`` and the ``mirror_slots`` deliveries) under ``deliver``,
  the do-while under ``correction``, the loop-entry status under
  ``status``;
* a profiler session changes no record and no state;
* ``UpdateBatch.pushed_at`` feeds the ``service_ingest_wait_seconds``
  histogram and the ``ingest_apply`` span's ``waited`` / ``wait_s``.
"""

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import regions, sim, topology
from repro.obs import InMemoryTracker
from repro.service import (ControlPlaneConfig, QuerySpec, Service,
                           ServiceConfig)
from repro.service.ingest import StreamIngest, UpdateBatch

SCOPES = ("deliver", "status", "correction")


def _service(tracker=None, use_kernels=False, **kw):
    topo = topology.grid(36)
    centers, sample, _, _ = sim.make_problem(sim.ProblemSpec(n=36, seed=0))
    rng = np.random.default_rng(1)
    cfg = dict(capacity=2, k_max=3, d=2, cycles_per_dispatch=2,
               use_kernels=use_kernels)
    cfg.update(kw)
    svc = Service(topo, ServiceConfig(**cfg), tracker=tracker)
    for i in range(2):
        svc.admit(QuerySpec(region=regions.VoronoiRegions(
            jnp.asarray(centers)), inputs=sample(rng, topo.n), seed=i))
    return svc


def _host_events(trace_dir):
    """(name, start_ns, end_ns) of every ``repro.*`` host event."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert files, f"no profile under {trace_dir}"
    pd = jax.profiler.ProfileData.from_file(files[0])
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.")]


def _scopes(op_name: str) -> set:
    """The named scopes on an HLO ``op_name`` path; under ``vmap`` a
    scope shows as ``vmap(<scope>)``."""
    out = set()
    for part in op_name.split("/"):
        m = re.fullmatch(r"(?:vmap\()*(\w+)\)*", part)
        if m and m.group(1) in SCOPES:
            out.add(m.group(1))
    return out


def test_tick_spans_land_on_the_profiler_host_plane(tmp_path):
    svc = _service()
    svc.tick()  # compile outside the session
    svc.push_updates([1, 2], [[0.5, 0.5], [1.0, -1.0]])
    with jax.profiler.trace(str(tmp_path)):
        svc.tick()
    svc.close()
    events = _host_events(str(tmp_path))
    names = {n for n, _, _ in events}
    want = {"repro.tick", "repro.membership_drain", "repro.admission_drain",
            "repro.ingest_apply", "repro.dispatch", "repro.observe",
            "repro.observe_emit"}
    assert want <= names, want - names
    ticks = [(s, e) for n, s, e in events if n == "repro.tick"]
    assert len(ticks) == 1
    (t0, t1), = ticks
    for n, s, e in events:
        assert t0 <= s <= e <= t1, n
    order = sorted((s, n) for n, s, _ in events if n != "repro.tick")
    assert [n for _, n in order] == [
        "repro.membership_drain", "repro.admission_drain",
        "repro.ingest_apply", "repro.dispatch", "repro.observe",
        "repro.observe_emit"]


@pytest.mark.parametrize("use_kernels", [False, True],
                         ids=["reference", "fused"])
def test_step_hlo_names_its_work_by_scope(use_kernels):
    svc = _service(use_kernels=use_kernels)
    txt = svc._step.lower(svc.states, svc.registry.params,
                          svc.backend.topo_args(), k=2).compile().as_text()
    svc.close()
    ops = [(m.group(1), m.group(2), m.group(3)) for m in re.finditer(
        r"^\s*(?:ROOT )?%(\S+) = .*? (\w[\w-]*)\(.*op_name=\"([^\"]*)\"",
        txt, re.M)]
    gathers = [(name, path) for name, kind, path in ops if kind == "gather"]
    # live_mask's, and the mirror_slots deliveries: 2 + d (one per
    # component plane of out_m).
    assert len(gathers) >= 3
    for name, path in gathers:
        assert _scopes(path) == {"deliver"}, (name, path)
    loops = [path for _, kind, path in ops
             if kind == "while" and path.endswith("(correction)/while")]
    assert loops, "the correction do-while carries no correction scope"
    assert any("status" in _scopes(path) for _, _, path in ops)


def test_records_bitwise_with_a_profiler_session(tmp_path):
    def run(trace_dir):
        tr = InMemoryTracker()
        svc = _service(tracker=tr)
        svc.tick()
        svc.push_updates([3, 4], [[2.0, 0.0], [0.0, 2.0]])
        if trace_dir is None:
            recs = svc.tick() + svc.tick()
        else:
            with jax.profiler.trace(trace_dir):
                recs = svc.tick() + svc.tick()
        states = svc.states
        svc.close()
        # Control records carry host timings (``spans``); the rest of
        # every record is data.
        kept = [{k: v for k, v in r.items() if k != "spans"}
                for r in tr.records if r.get("kind") != "span"]
        return recs, kept, states

    off = run(None)
    on = run(str(tmp_path))
    assert on[0] == off[0] and on[1] == off[1]
    for a, b in zip(on[2], off[2]):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_ingest_wait_from_push_to_the_applying_boundary():
    tr = InMemoryTracker()
    svc = _service(tracker=tr)
    svc.tick()
    svc.push_updates([1], [[0.1, 0.2]])
    svc.push_updates([2], [[0.3, 0.4]])
    svc.tick()
    hist = tr.registry.get("service_ingest_wait_seconds")
    (_, (counts, total)), = hist.series()
    assert sum(counts) == 2 and total > 0
    sp, = [s for s in tr.spans_named("ingest_apply") if s.attrs]
    assert sp.attrs["waited"] == 2
    assert sp.attrs["wait_s"] == pytest.approx(total)
    svc.close()
    # A batch built by hand carries no stamp; push stamps it.
    assert UpdateBatch(np.array([0]), np.zeros((1, 2))).pushed_at is None
    assert StreamIngest().push([0], [[1.0, 1.0]]).pushed_at > 0


def test_parked_batch_counts_its_park_time():
    tr = InMemoryTracker()
    cp = ControlPlaneConfig(scheduler="priority", preempt=True)
    topo = topology.grid(25)
    centers, sample, _, _ = sim.make_problem(sim.ProblemSpec(n=25, seed=5))
    x = sample(np.random.default_rng(6), 25)
    svc = Service(topo, ServiceConfig(capacity=1, k_max=3, d=2,
                                      cycles_per_dispatch=2, control=cp),
                  tracker=tr)

    def spec(seed, priority):
        return QuerySpec(region=regions.VoronoiRegions(jnp.asarray(centers)),
                         inputs=x, seed=seed, priority=priority)

    a = svc.admit(spec(0, 0))
    svc.tick()
    b = svc.admit(spec(1, 5))
    svc.tick()  # b preempts a
    batch = svc.push_updates([3], [[9.0, 9.0]], query_ids=[a])
    svc.tick()  # parked, not applied: nothing observed yet
    assert tr.registry.get("service_ingest_wait_seconds") is None or sum(
        sum(c) for _, (c, _) in
        tr.registry.get("service_ingest_wait_seconds").series()) == 0
    svc.retire(b)  # a resumes and replays the parked batch
    (_, (counts, total)), = tr.registry.get(
        "service_ingest_wait_seconds").series()
    assert sum(counts) == 1
    # the wait runs from the push through the park to the replay
    resume, = tr.spans_named("resume")
    assert total >= resume.start - batch.pushed_at > 0
    svc.close()
