"""Multi-tenant monitor service demo: contention, SLOs, and the control
plane.

Admits 64 tenants onto a service provisioned with fewer slots than
tenants (contended on purpose): Voronoi source-selection and halfspace
threshold queries in three priority classes, the high class carrying an
accuracy-within-T SLO.  The priority scheduler preempts and resumes
low-priority tenants to keep the high class inside its SLO; mid-run, a
burst of peer joins exhausts the membership capacity and the control
plane transparently regrows it (one recompile, logged as an epoch).
Prints per-class SLO attainment, the control-plane activity trail, and
the :mod:`repro.obs` convergence dashboard (per-tenant accuracy
sparklines, quiescence times, boundary-span costs).

    PYTHONPATH=src python examples/serve_monitor.py --n 4096 --queries 64
"""

import argparse
import dataclasses
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import topology
from repro.obs import render_controls, render_dashboard
from repro.service import (ControlPlaneConfig, SLOSpec, Service,
                           ServiceConfig, TelemetrySink,
                           heterogeneous_tenants)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--slots", type=int, default=48,
                    help="slot capacity (< queries: contended)")
    ap.add_argument("--dispatches", type=int, default=10)
    ap.add_argument("--k", type=int, default=8, help="cycles per dispatch")
    ap.add_argument("--joins", type=int, default=24,
                    help="peer joins at mid-run (forces a regrow epoch)")
    ap.add_argument("--jsonl", default=None, help="telemetry JSONL path")
    ap.add_argument("--kernels", action="store_true",
                    help="fused Pallas kernel suite for the hot loop "
                         "(interpret mode off-TPU: bit-exact but slow — "
                         "keep --n small; auto-selected on TPU)")
    args = ap.parse_args()
    enable_compile_cache()

    side = int(round(args.n ** 0.5))
    base = topology.grid(side * side)
    # Tight membership headroom: the mid-run join burst must outgrow it.
    dyn = topology.DynTopology.from_topology(
        base, n_cap=base.n + args.joins // 2, deg_cap=base.max_deg + 2)
    sink = TelemetrySink(path=args.jsonl)
    cp = ControlPlaneConfig(scheduler="priority", preempt=True, aging=0.2,
                            violation_boost=0.5, auto_regrow=True)
    svc = Service(dyn, ServiceConfig(capacity=args.slots, k_max=4, d=2,
                                     cycles_per_dispatch=args.k,
                                     admission_queue=args.queries,
                                     control=cp,
                                     use_kernels=args.kernels or None),
                  telemetry=sink)
    print(f"dispatch runs the {svc.dispatch_info()['suite']!r} kernel suite"
          f" (fused={svc.dispatch_info()['fused']})")

    # Three priority classes; the high class declares an accuracy SLO.
    slo = SLOSpec(target_accuracy=0.95, within_cycles=4 * args.k)
    classes = {0: [], 1: [], 2: []}
    t0 = time.perf_counter()
    for i, spec in enumerate(heterogeneous_tenants(dyn.n, args.queries)):
        prio = i % 3
        spec = dataclasses.replace(spec, priority=prio,
                                   slo=slo if prio == 2 else None)
        classes[prio].append(svc.admit(spec))
    print(f"admitted {args.queries} tenants into {args.slots} slots on a "
          f"{base.n}-peer grid ({time.perf_counter() - t0:.2f}s) — "
          f"{svc.registry.num_active} active, {len(svc.admission)} queued")

    rng = np.random.default_rng(7)
    t0 = time.perf_counter()
    for step in range(args.dispatches):
        # Streaming updates land between dispatches (1% of peers).
        who = rng.choice(base.n, size=max(1, base.n // 100), replace=False)
        svc.push_updates(who, rng.normal(size=(who.size, 2)), mode="set")
        if step == args.dispatches // 2:
            # A join burst past n_cap: auto-regrow fires transparently.
            before = dyn.n_cap
            for _ in range(args.joins):
                p = svc.join_peer(value=rng.normal(size=2))
                svc.link_peers(p, int(rng.integers(base.n)))
            print(f"  join burst: n_cap {before} -> {svc.topo.n_cap} "
                  f"(epochs: "
                  f"{[e['kind'] for e in svc.capman.epochs[1:]]})")
        records = svc.tick()
        done = sum(r["quiescent"] for r in records)
        acc = np.mean([r["accuracy"] for r in records])
        print(f"dispatch {step + 1}: t={svc.cycles}  mean acc={acc:.3f}  "
              f"quiescent {done}/{len(records)}  "
              f"active {svc.registry.num_active}  "
              f"queued {len(svc.admission)}  "
              f"preempted {svc.num_preempted}")
    dt = time.perf_counter() - t0
    qc = args.queries * args.dispatches * args.k
    print(f"{args.dispatches} dispatches x {args.k} cycles x "
          f"{args.queries} tenants in {dt:.2f}s "
          f"({qc / dt:,.0f} query-cycles/s)")

    print("\nper-class mean SLO attainment / final accuracy:")
    last = sink.last_by_query()
    for prio, qids in classes.items():
        att = np.mean([svc.slo.attainment(q) for q in qids])
        accs = [last[q]["accuracy"] for q in qids if q in last]
        label = {0: "low", 1: "mid", 2: "high+SLO"}[prio]
        print(f"  class {prio} [{label:>8}] attainment={att:.2f}  "
              f"acc={np.mean(accs) if accs else float('nan'):.3f}  "
              f"({len(accs)}/{len(qids)} served)")

    print("\nhigh-class tenants (first 8):")
    for qid in classes[2][:8]:
        rep = svc.slo_report().get(qid, {})
        status = svc.admission_status(qid)
        print(f"  {qid} [{status:>9}] attainment={rep.get('attainment', 1.0):.2f} "
              f"violations={rep.get('violations', 0)}")

    ctrl = sink.controls()
    n_pre = sum(len(c.get("preempted", [])) for c in ctrl)
    n_res = sum(len(c.get("resumed", [])) for c in ctrl)
    print(f"\ncontrol plane: {n_pre} preemptions, {n_res} resumes, "
          f"epochs={[e['kind'] for e in svc.capman.epochs]}")

    # Convergence dashboard straight off the telemetry the service kept.
    print()
    print(render_dashboard(sink.records, sort_by="accuracy"))
    print()
    print(render_controls(sink.records))
    svc.close()  # flushes the (borrowed) sink
    sink.close()


if __name__ == "__main__":
    main()
