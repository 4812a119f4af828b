"""Causal tracing, flight recorder, alerts, push tracker.

The PR-7 contract: every span record carries ``span_id``/``parent_id``/
tenant ``trace`` ids and reassembles into a complete causal forest
(every dispatch reachable from the admission that minted its trace id);
the flight recorder dumps its ring exactly when an SLO violation /
eviction / epoch / alert happens; alert rules fire on
sustained predicates only; and ALL of it keeps serving bitwise
identical to an uninstrumented run.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import regions, sim, topology
from repro.obs import (AlertEngine, AlertRule, FlightRecorder, InMemoryTracker,
                       MetricsRegistry, NoopTracker, PushTracker, assemble,
                       render_histogram, trace_view, validate_record,
                       validate_stream)
from repro.service import QuerySpec, Service, ServiceConfig, SLOSpec

ALWAYS = (AlertRule(name="always", metric="service_queue_depth",
                    above=-1.0),)


def _specs(n, q, seed=3):
    centers, sample, _, _ = sim.make_problem(sim.ProblemSpec(n=n, seed=seed))
    rng = np.random.default_rng(seed + 1)
    return [QuerySpec(region=regions.VoronoiRegions(jnp.asarray(centers)),
                      inputs=sample(rng, n), seed=i) for i in range(q)]


def _serve(tracker=None, ticks=3, n_specs=3, slo=None, **cfg_kw):
    topo = topology.grid(36)
    kw = dict(capacity=3, k_max=3, d=2, cycles_per_dispatch=2)
    kw.update(cfg_kw)
    svc = Service(topo, ServiceConfig(**kw), tracker=tracker)
    for s in _specs(topo.n, n_specs):
        if slo is not None:
            s = dataclasses.replace(s, slo=slo)
        svc.admit(s)
    out = []
    for _ in range(ticks):
        out.extend(svc.tick())
    return svc, out


# ---------------------------------------------------------------------------
# trace trees
# ---------------------------------------------------------------------------


def test_trace_round_trip_every_dispatch_has_admission_ancestor():
    tr = InMemoryTracker()
    svc, _ = _serve(tracker=tr, ticks=3)
    forest = assemble(tr.records)
    assert forest.orphans == []  # stream completeness
    tids = forest.trace_ids()
    assert len(tids) == 3  # one per admitted tenant
    for tid in tids:
        tree = forest.tenant(tid)
        assert len(tree.spans_named("admission")) == 1
        assert len(tree.spans_named("dispatch")) == 3  # one per tick
        assert tree.has_ancestry("dispatch", "admission")
        assert tree.has_ancestry("observe", "admission")
        (root,) = tree.roots  # single tree, rooted at admission
        assert root.name == "admission"
    svc.close()


def test_trace_ids_deterministic_and_in_records():
    """Trace ids are minted service-side (never by the tracker), so the
    per-query record stream is identical across backends."""
    tr = InMemoryTracker()
    svc, _ = _serve(tracker=tr, ticks=1)
    per_q = [r for r in tr.records if "query" in r]
    assert all(r["trace_id"] == f"t{i + 1:05d}:{r['query']}"
               for i, r in enumerate(sorted(per_q, key=lambda r: r["slot"])))
    svc.close()


def test_preempt_resume_spans_carry_tenant_trace():
    tr = InMemoryTracker()
    topo = topology.grid(16)
    svc = Service(topo, ServiceConfig(capacity=1, k_max=3, d=2,
                                      cycles_per_dispatch=2,
                                      admission_queue=4),
                  tracker=tr)
    (a,) = _specs(topo.n, 1)
    qa = svc.admit(a)
    svc.tick()
    svc._preempt(qa)  # scheduler entry point, driven directly
    svc._resume(qa)
    svc.tick()
    forest = assemble(tr.records)
    names = {n.name for n in forest.nodes.values()}
    assert {"admission", "activate", "preempt", "resume"} <= names
    for name in ("preempt", "resume"):
        (node,) = [n for n in forest.nodes.values() if n.name == name]
        assert node.trace and node.trace[0].endswith(qa)
    # The tenant projection keeps the suspension in its causal chain.
    tree = forest.tenant(forest.trace_ids()[0])
    assert tree.has_ancestry("preempt", "admission")
    assert tree.has_ancestry("resume", "admission")
    svc.close()


def test_trace_view_renders_and_epoch_spans_fan_out():
    tr = InMemoryTracker()
    dyn = topology.DynTopology.from_topology(topology.grid(36), n_cap=38,
                                             deg_cap=6)
    svc = Service(dyn, ServiceConfig(capacity=2, k_max=3, d=2,
                                     cycles_per_dispatch=2), tracker=tr)
    for s in _specs(dyn.n, 2):
        svc.admit(s)
    svc.tick()
    svc.grow_capacity(n_cap=44)
    svc.tick()
    forest = assemble(tr.records)
    # The epoch span names every active tenant's trace id.
    (epoch,) = [n for n in forest.nodes.values() if n.name == "epoch_regrow"]
    assert set(epoch.trace) == set(forest.trace_ids())
    view = trace_view(tr.records)
    for tid in forest.trace_ids():
        assert tid in view
    assert "admission" in view and "dispatch" in view
    assert "orphan" not in view
    # Single-tenant render accepts an explicit id, and a forest directly.
    one = trace_view(forest, trace_id=forest.trace_ids()[0])
    assert forest.trace_ids()[1] not in one
    svc.close()


# ---------------------------------------------------------------------------
# engine transport spans
# ---------------------------------------------------------------------------


def test_engine_mesh_transport_spans_on_collective_path(subproc):
    """On a real 4-device mesh the dispatch spans say transport=
    all_to_all and the per-shard halo/cut counters are nonzero."""
    out = subproc("""
import jax, numpy as np, jax.numpy as jnp
from repro.core import lss, sim, topology, wvs
from repro.engine import ShardedLSS, EngineConfig
from repro.obs import InMemoryTracker, assemble

topo = topology.grid(64)
centers, sample, _, _ = sim.make_problem(sim.ProblemSpec(n=64, seed=0))
rng = np.random.default_rng(1)
inputs = wvs.from_vector(jnp.asarray(sample(rng, topo.n)),
                         jnp.ones((topo.n,), jnp.float32))
tr = InMemoryTracker()
mesh = jax.make_mesh((4,), ("shards",))
eng = ShardedLSS(topo, centers, lss.LSSConfig(),
                 EngineConfig(num_shards=4, cycles_per_dispatch=4),
                 tracker=tr).use_mesh(mesh, "shards")
est = eng.init(inputs, seed=0)
est = eng.run(est, 8)
spans = [n for n in assemble(tr.records).nodes.values()
         if n.name == "engine.dispatch"]
assert len(spans) == 2
assert all(s.attrs["transport"] == "all_to_all" for s in spans)
assert all(s.attrs["halo_bytes"] > 0 for s in spans)
assert all(s.attrs["cut_edges"] > 0 for s in spans)
halo = tr.registry.get("engine_shard_halo_bytes_total")
for s in range(4):
    assert halo.value(shard=str(s), transport="all_to_all") > 0
    assert tr.registry.gauge("engine_shard_cut_edges").value(
        shard=str(s)) > 0
print("MESH_TRANSPORT_SPANS_OK")
""", n_devices=4)
    assert "MESH_TRANSPORT_SPANS_OK" in out


# ---------------------------------------------------------------------------
# alert rules
# ---------------------------------------------------------------------------


def test_alert_rule_sustain_window_semantics():
    reg = MetricsRegistry()
    eng = AlertEngine([AlertRule(name="hot", metric="temp", above=10.0,
                                 sustain=3)], reg)
    g = reg.gauge("temp")
    g.set(50.0)
    assert eng.evaluate() == []  # streak 1
    assert eng.evaluate() == []  # streak 2
    (fired,) = eng.evaluate(dispatch=7)  # streak 3: fires once
    assert fired["state"] == "firing" and fired["value"] == 50.0
    assert fired["dispatch"] == 7 and fired["sustain"] == 3
    assert eng.evaluate() == []  # no re-fire while firing
    g.set(5.0)
    (resolved,) = eng.evaluate()
    assert resolved["state"] == "resolved"
    g.set(50.0)
    assert eng.evaluate() == []  # streak restarts after resolve
    assert validate_record(fired) == [] and validate_record(resolved) == []


def test_alert_blip_below_sustain_never_fires():
    reg = MetricsRegistry()
    eng = AlertEngine([AlertRule(name="hot", metric="temp", above=10.0,
                                 sustain=2)], reg)
    g = reg.gauge("temp")
    for v in (50.0, 5.0, 50.0, 5.0):  # alternating: streak never hits 2
        g.set(v)
        assert eng.evaluate() == []
    assert eng.firing() == []


def test_alert_label_filter_and_series_disappearance():
    reg = MetricsRegistry()
    eng = AlertEngine([AlertRule(name="q-acc", metric="tenant_accuracy",
                                 below=0.5, labels=(("query", "q1"),))], reg)
    g = reg.gauge("tenant_accuracy")
    g.set(0.1, query="q1")
    g.set(0.1, query="q2")  # filtered out
    (fired,) = eng.evaluate()
    assert fired["labels"] == {"query": "q1"}
    reg.remove_labels(query="q1")  # tenant retired: scrubbed
    assert eng.evaluate() == []  # silent resolve, no record
    assert eng.firing() == []


def test_service_alerts_emit_records_into_stream():
    tr = InMemoryTracker()
    svc, _ = _serve(tracker=tr, ticks=2, alerts=ALWAYS)
    alerts = [r for r in tr.records if r.get("kind") == "alert"]
    assert len(alerts) == 1  # fires on the first observe, then holds
    assert alerts[0]["rule"] == "always"
    assert alerts[0]["dispatch"] >= 1
    assert validate_stream(tr.records) == []
    svc.close()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flight_recorder_ring_and_manual_dump(tmp_path):
    fr = FlightRecorder(InMemoryTracker(), capacity=3)
    for i in range(5):
        fr.log_record({"kind": "control", "dispatch": i, "t": i,
                       "queue_depth": 0, "preempted_depth": 0})
    assert len(fr) == 3  # bounded ring
    assert [r["dispatch"] for r in fr.snapshot()] == [2, 3, 4]
    assert len(fr.inner.records) == 5  # inner tracker got everything
    path = str(tmp_path / "dump.jsonl")
    fr.dump(path, reason="test", dispatch=5)
    lines = [json.loads(l) for l in open(path)]
    assert lines[0]["kind"] == "flight" and lines[0]["reason"] == "test"
    assert lines[0]["records"] == 3
    assert [r["dispatch"] for r in lines[1:]] == [2, 3, 4]
    assert validate_stream(lines) == []


def test_flight_dump_triggered_by_slo_violation(tmp_path):
    """An impossible SLO (accuracy > 1 required) violates on the first
    observe; the service auto-dumps its ring into flight_dump_dir."""
    svc, _ = _serve(ticks=2, slo=SLOSpec(target_accuracy=1.5),
                    flight_dump_dir=str(tmp_path))
    dumps = sorted(os.listdir(tmp_path))
    assert dumps and all("slo_violation" in d for d in dumps)
    lines = [json.loads(l) for l in open(tmp_path / dumps[0])]
    assert lines[0]["kind"] == "flight"
    assert lines[0]["reason"] == "slo_violation"
    assert any(r.get("slo_ok") is False for r in lines[1:])
    assert any(r.get("kind") == "span" for r in lines[1:])  # spans ride along
    svc.close()


def test_flight_dump_triggered_by_alert_and_manual_api(tmp_path):
    svc, _ = _serve(ticks=1, alerts=ALWAYS, flight_dump_dir=str(tmp_path))
    dumps = os.listdir(tmp_path)
    assert len(dumps) == 1 and "alert" in dumps[0]
    path = svc.dump_flight_recorder(reason="because")
    assert os.path.basename(path).endswith("because.jsonl")
    assert json.loads(open(path).readline())["reason"] == "because"
    os.remove(path)
    svc.close()


def test_flight_dump_on_crash(tmp_path):
    svc, _ = _serve(ticks=1, flight_dump_dir=str(tmp_path))
    svc._step_call = None  # break the dispatch path
    with pytest.raises(TypeError):
        svc.tick()
    assert any("crash" in d for d in os.listdir(tmp_path))
    svc.close()


def test_no_auto_dump_without_dir(tmp_path):
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        svc, _ = _serve(ticks=1, slo=SLOSpec(target_accuracy=1.5))
        assert os.listdir(".") == []  # violation happened, no dump
        svc.close()
    finally:
        os.chdir(cwd)


# ---------------------------------------------------------------------------
# push tracker
# ---------------------------------------------------------------------------


def test_push_tracker_buffers_and_flushes():
    tr = PushTracker(flush_every=3)
    assert tr.log({"a": 1}) == 0
    assert tr.log({"a": 2}) == 1
    assert tr.pushed == []  # below flush_every
    tr.log({"a": 3})
    assert len(tr.pushed) == 1  # auto-flush at 3
    assert [p["step"] for p in tr.pushed[0]] == [0, 1, 2]
    tr.log({"a": 4}, step=10)  # explicit step jumps forward
    with pytest.raises(ValueError):
        tr.log({"a": 5}, step=3)  # monotone: can't go back
    tr.close()  # drains the remainder
    assert tr.pushed[1][0] == {"step": 10, "a": 4}
    # Tracker-protocol entry points produce payloads + registry state.
    tr2 = PushTracker(flush_every=1)
    tr2.log_metrics({"depth": 2.0}, backend="core")
    assert tr2.registry.gauge("depth").value(backend="core") == 2.0
    assert tr2.pushed[0][0]["metrics"] == {"depth": 2.0}


def test_push_tracker_service_parity():
    noop_out, noop_states = None, None
    for tracker in (NoopTracker(), PushTracker(flush_every=4)):
        svc, out = _serve(tracker=tracker, ticks=3)
        states = svc.states
        svc.close()
        if noop_out is None:
            noop_out, noop_states = out, states
        else:
            assert out == noop_out
            for a, b in zip(states, noop_states):
                assert np.array_equal(np.asarray(a), np.asarray(b))
            recs = [p["record"] for batch in tracker.pushed for p in batch
                    if "record" in p]
            assert validate_stream(recs) == []


# ---------------------------------------------------------------------------
# parity: full instrumentation on == off
# ---------------------------------------------------------------------------


def test_full_instrumentation_bitwise_parity(tmp_path):
    """Alerts + flight auto-dump + tracing all on,
    vs a bare NoopTracker run: records and states bitwise identical."""
    def run(tracker, **cfg_kw):
        svc, out = _serve(tracker=tracker, ticks=4, **cfg_kw)
        states = svc.states
        svc.close()
        return out, states

    rec_off, st_off = run(NoopTracker())
    rec_on, st_on = run(InMemoryTracker(), alerts=ALWAYS, flight_capacity=64,
                        flight_dump_dir=str(tmp_path))
    assert rec_on == rec_off  # floats exactly equal, trace ids included
    for a, b in zip(st_on, st_off):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# schema + dashboard hardening
# ---------------------------------------------------------------------------


def test_new_record_kinds_validate():
    span = {"kind": "span", "name": "dispatch", "span_id": 3, "seconds": 0.1,
            "parent_id": 1, "trace": ["t00001:q0"], "attrs": {"k": 2}}
    alert = {"kind": "alert", "rule": "r", "metric": "m", "value": 1.0,
             "state": "firing", "dispatch": 0, "t": 0, "sustain": 2,
             "labels": {}}
    flight = {"kind": "flight", "reason": "crash", "records": 9,
              "error": "boom"}
    assert validate_stream([span, alert, flight]) == []
    assert validate_record({**span, "span_id": "three"})  # wrong type
    assert validate_record({"kind": "span", "name": "x"})  # missing fields


def test_dashboard_histogram_and_empty_series():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(0.1, 1.0))
    assert "no samples" in render_histogram(h)
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v, span="tick")
    art = render_histogram(h, span="tick")
    assert "lat" in art and "█" in art
    # trace_view over a stream with no spans degrades, never raises.
    assert "no tenant spans" in trace_view([{"query": "q0"}])
