"""Overlapped host boundary: how long does the dispatch pipeline sit idle?

``ServiceConfig(overlap=True)`` pipelines the serving loop (see
:mod:`repro.service.overlap`): tick K+1's host boundary — membership
drain, admission, ingest, and dispatch K's telemetry emission — runs
while dispatch K is still in flight, so the device-side pipeline never
drains between dispatches.  This suite measures that directly on a
churning multi-tenant workload (every tick streams a wide update batch
and flips a block of peers' membership), identical in sync and overlap
mode.

The headline metric is the **pipeline bubble**: wall time during which
NO dispatch is in flight.  A dispatch is in flight from the end of its
``dispatch`` span (enqueue done) to the end of its window's ``observe``
span (host synced the results) — both real `perf_counter` timestamps
recorded by the service's own tracker, no fenced twin, no device-time
calibration.  In sync mode every boundary, telemetry emission, and
ingest push happens inside a bubble (the device is idle while the host
works); in overlap mode the next dispatch is already enqueued, so the
same host work is covered by an in-flight window.  This holds on any
host: on a multi-core box the bubble converts 1:1 into wall savings,
on a single-core CI runner the wall clock stays flat (host and device
share the core) but the bubble — the latency the host adds before the
device can start — still collapses.

* ``host_overhead_frac`` = bubble seconds / timed wall;
* ``host_frac_ratio`` = sync frac / overlap frac (capped at 100x) — the
  committed ``BENCH_async.json`` baseline records it and ``run.py
  --check`` enforces the absolute >=2x budget: overlap must keep the
  pipeline at least twice as busy;
* ``wall_ratio`` = sync wall / overlap wall — overlap must never *cost*
  steady-state wall time (>=0.9 absolute, noise slack).  The trailing
  ``flush()`` (a one-time drain, amortized away in steady state) is
  excluded from the timed windows of both modes;
* ``recompiles`` — the churn loop must stay zero-recompile in both
  modes after warm-up (the :class:`~repro.service.overlap.DoubleBuffer`
  canary backs the same invariant in-process); ``--check`` requires 0;
* ``msgs_per_link`` — deterministic: overlap mode must emit bitwise
  the sync records, so the 1% exact gate catches semantic drift.

Timed windows are interleaved round-robin across the two services so
slow host drift (thermal, noisy neighbors) lands on both modes alike;
in-flight intervals are clipped to each service's own timed chunks.
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import topology
from repro.obs import InMemoryTracker, jit_cache_size
from repro.service import Service, ServiceConfig, heterogeneous_tenants

from . import common
from .common import Row

FRAC_RATIO_CAP = 100.0  # fully-hidden host work: report 100x, not inf


def _build(topo, specs, k, overlap):
    dyn = topology.DynTopology.from_topology(topo, n_cap=topo.n, deg_cap=6)
    svc = Service(dyn, ServiceConfig(
        capacity=len(specs), k_max=3, d=2, cycles_per_dispatch=k,
        overlap=overlap), tracker=InMemoryTracker())
    for s in specs:
        svc.admit(s)
    svc.tick()  # startup compile + first observe: excluded from windows
    svc.flush()  # overlap: drain the warm-up window too
    return svc


def _churn(svc, t: int, n: int, block: int) -> None:
    """Per-tick boundary load, identical for every service: one wide
    streaming batch plus a block of membership flips (a leave wave,
    then a rejoin+relink wave) — real host work for the drain to hide."""
    who = [(t * 97 + 13 * i + 1) % n for i in range(4 * block)]
    vals = [[(i % 7) * 0.1, (i % 5) * 0.1] for i in range(len(who))]
    svc.push_updates(who, vals, mode="set")
    lo = n // 2  # churn block: far from the ingest rows' low indices
    peers = range(lo, lo + block)
    if t % 2 == 0:
        for p in peers:
            svc.leave_peer(p)
    else:
        for p in peers:
            svc.join_peer(p, value=[0.4, 0.4])
            svc.link_peers(p, p + 2 * block)  # stable far neighbor


def _in_flight(tr: InMemoryTracker, skip: int):
    """In-flight intervals [enqueue done, observe synced] per window,
    from the service's own span timestamps (FIFO pairing; ``skip``
    drops the warm-up window)."""
    enq = [s.start + s.seconds for s in tr.spans_named("dispatch")][skip:]
    syn = [s.start + s.seconds for s in tr.spans_named("observe")][skip:]
    merged = []
    for lo, hi in sorted(zip(enq, syn)):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def _bubble_frac(chunks, intervals) -> float:
    """Fraction of the timed chunks NOT covered by an in-flight
    dispatch — the pipeline bubble the overlap mode exists to remove."""
    covered = 0.0
    for lo, hi in intervals:  # merged: no double counting
        for c0, c1 in chunks:
            covered += max(0.0, min(hi, c1) - max(lo, c0))
    total = sum(c1 - c0 for c0, c1 in chunks)
    return max(0.0, total - covered) / total


def run(full: bool = False):
    n = common.clamp_n(4_096)
    q = 8 if common.SMOKE else 64
    k = 4 if common.SMOKE else 8
    rounds = 2 if common.SMOKE else 3
    per_round = 2 if common.SMOKE else 3
    block = 4 if common.SMOKE else 8
    side = int(round(n ** 0.5))
    topo = topology.grid(side * side)
    specs = heterogeneous_tenants(topo.n, q)
    ticks = rounds * per_round

    services = [("sync", _build(topo, specs, k, False)),
                ("overlap", _build(topo, specs, k, True))]
    walls = {name: 0.0 for name, _ in services}
    chunks = {name: [] for name, _ in services}
    clock = {name: 0 for name, _ in services}
    cache0 = {name: jit_cache_size(svc._step_call)
              for name, svc in services}
    records = {name: [] for name, _ in services}
    for _ in range(rounds):  # interleaved: drift hits both modes alike
        for name, svc in services:
            t0 = time.perf_counter()
            for _ in range(per_round):
                _churn(svc, clock[name], topo.n, block)
                clock[name] += 1
                records[name].extend(svc.tick())
            t1 = time.perf_counter()
            walls[name] += t1 - t0
            chunks[name].append((t0, t1))
    frac, recompiles = {}, {}
    for name, svc in services:
        records[name].extend(svc.flush())  # trailing drain: not timed
        frac[name] = _bubble_frac(chunks[name],
                                  _in_flight(svc.tracker, skip=1))
        c0, c1 = cache0[name], jit_cache_size(svc._step_call)
        recompiles[name] = (c1 - c0
                            if c0 is not None and c1 is not None else 0)
        svc.close()

    per_tick = {name: walls[name] / ticks * 1e3 for name, _ in services}
    frac_ratio = min(FRAC_RATIO_CAP,
                     frac["sync"] / max(frac["overlap"],
                                        frac["sync"] / FRAC_RATIO_CAP,
                                        1e-9))
    wall_ratio = per_tick["sync"] / per_tick["overlap"]

    rows = []
    for name, _ in services:
        extra = {
            "n": topo.n, "q": q, "k": k, "mode": name,
            "wall_per_tick_ms": per_tick[name],
            "host_overhead_frac": frac[name],
            "recompiles": recompiles[name],
            "peers_per_s": topo.n * q * k * ticks / walls[name],
            "msgs_per_link": float(np.mean(
                [r["msgs_per_link"] for r in records[name]])),
        }
        if name == "overlap":
            extra["host_frac_ratio"] = frac_ratio
            extra["wall_ratio"] = wall_ratio
        rows.append(Row(
            f"async/{name}/n{topo.n}/q{q}",
            per_tick[name] * 1e3 / (q * k),
            f"tick={per_tick[name]:.1f}ms host_frac={frac[name]:.3f}",
            extra=extra))
    return rows


if __name__ == "__main__":
    for r in run(full="--full" in __import__("sys").argv):
        print(r.csv())
