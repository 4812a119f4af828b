"""Tracker overhead: what does observability cost the dispatch loop?

The observability layer (:mod:`repro.obs`) promises that instrumentation
is host-side bookkeeping over numbers the service already synced — the
batched device round-trip is unchanged, so the tracker's cost must be a
small fraction of dispatch wall time.  This suite measures it directly:
the same Q-tenant workload is served three times, identical except for
the tracker backend —

* ``noop``   — :class:`repro.obs.NoopTracker`: spans still timed, but no
  records, no metrics, no registry writes.  The floor.
* ``jsonl``  — :class:`repro.obs.JsonlTracker` writing every per-query
  record to a real file (the production default via ``TelemetrySink``).
* ``prom``   — :class:`repro.obs.PrometheusTextTracker` plus one
  ``expose()`` scrape per dispatch (a live /metrics endpoint's steady
  load).
* ``traced`` — :class:`repro.obs.InMemoryTracker` with the full
  instrumentation switched on: causal spans (always emitted) and an
  always-firing alert rule evaluated at every observe boundary.  The
  worst-case tracing window.
* ``audited`` — :class:`repro.obs.InMemoryTracker` with the audit plane
  sampling EVERY window (``audit_every=1``): the invariant reductions
  fold into the jitted observe program and their scalars ride the same
  round-trip, so the audited dispatch must stay inside the same
  overhead budget as plain tracking.

Timed windows are interleaved round-robin across the three services so
slow host drift (thermal, noisy neighbors) lands on all backends alike.
``overhead_frac`` = (median dispatch wall - noop median) / noop median,
clamped at 0.  The committed ``BENCH_obs.json`` baseline records it and
``run.py --check`` enforces the absolute <5% budget — a tracker change
that makes observability expensive fails CI even if the baseline was
recorded on a slower host.
"""

from __future__ import annotations

import os
import tempfile
import time

import numpy as np

from repro.core import topology
from repro.obs import (AlertRule, InMemoryTracker, JsonlTracker, NoopTracker,
                       PrometheusTextTracker)
from repro.service import Service, ServiceConfig, heterogeneous_tenants

from . import common
from .common import Row

OVERHEAD_BUDGET = 0.05  # tracker overhead must stay <5% of dispatch wall


def _build(topo, specs, k, tracker, **cfg_kw):
    svc = Service(topo, ServiceConfig(
        capacity=len(specs), k_max=3, d=2, cycles_per_dispatch=k, **cfg_kw),
        tracker=tracker)
    for s in specs:
        svc.admit(s)
    svc.tick()  # startup compile + first observe: excluded from windows
    return svc


def run(full: bool = False):
    n = common.clamp_n(10_000)
    q = 8 if common.SMOKE else 64
    k = 4 if common.SMOKE else 8
    rounds = 2 if common.SMOKE else 3
    per_round = 1 if common.SMOKE else 2
    side = int(round(n ** 0.5))
    topo = topology.grid(side * side)
    specs = heterogeneous_tenants(topo.n, q)

    tmp = tempfile.NamedTemporaryFile(suffix=".jsonl", delete=False)
    tmp.close()
    prom = PrometheusTextTracker()
    traced_cfg = dict(
        alerts=(AlertRule(name="always", metric="service_queue_depth",
                          above=-1.0),))
    backends = [
        ("noop", NoopTracker(), None, {}),
        ("jsonl", JsonlTracker(tmp.name), None, {}),
        ("prom", prom, prom.expose, {}),
        ("traced", InMemoryTracker(max_records=4096), None, traced_cfg),
        ("audited", InMemoryTracker(max_records=4096), None,
         {"audit_every": 1}),
    ]
    try:
        services = [(name, _build(topo, specs, k, tr, **cfg), scrape)
                    for name, tr, scrape, cfg in backends]
        walls = {name: [] for name, _, _ in services}
        for _ in range(rounds):  # interleaved: drift hits all alike
            for name, svc, scrape in services:
                for _ in range(per_round):
                    t0 = time.perf_counter()
                    svc.tick()
                    if scrape is not None:
                        scrape()
                    walls[name].append(time.perf_counter() - t0)
        meds = {name: float(np.median(w)) for name, w in walls.items()}
        for _, svc, _ in services:
            svc.close()
    finally:
        os.unlink(tmp.name)

    rows = []
    for name, _, _ in services:
        med = meds[name]
        frac = max(0.0, (med - meds["noop"]) / meds["noop"])
        extra = {"n": topo.n, "q": q, "k": k, "tracker": name,
                 "median_dispatch_s": med, "overhead_frac": frac}
        rows.append(Row(
            f"obs/tracker/{name}/n{topo.n}/q{q}", med / (q * k) * 1e6,
            f"dispatch={med * 1e3:.1f}ms overhead={frac:.1%}", extra=extra))
    return rows


if __name__ == "__main__":
    for r in run(full="--full" in __import__("sys").argv):
        print(r.csv())
