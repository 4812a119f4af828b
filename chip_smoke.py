"""Chip smoke test: the multi-tenant monitor, once, on a TPU.

    python chip_smoke.py            # one chip: the served path
    python chip_smoke.py --chips 4  # four chips: the mesh engine only

One chip: a ``Service`` with the default ``ServiceConfig`` (core backend,
kernel suite picked by the platform) serves 16 mixed Voronoi/halfspace
tenants from ``heterogeneous_tenants`` in 8 slots over a 283x283 grid,
the paper's largest size (80,089 peers, Sec. VI), with streaming updates,
peer joins and links between dispatches.  A second ``Service`` built with
``use_kernels=False`` replays the same tenants, seeds, updates and joins;
both must agree on every record and on every peer's decision.

Four chips: ``ShardedLSS(num_shards=4)`` on the same grid over a 4-device
mesh (the halo ``all_to_all``) against the same engine with the
single-device gather transport, compared bitwise.

Exits non-zero, with no result line, when JAX finds no TPU or any check
fails.  The last line of a passing run is one JSON object naming the
device.  Timings printed here are smoke numbers, not a benchmark.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import lss, regions, sim, stopping, topology, wvs  # noqa: E402
from repro.engine import EngineConfig, ShardedLSS  # noqa: E402
from repro.service import (Service, ServiceConfig,  # noqa: E402
                           heterogeneous_tenants)

SIDE = 283  # 283 x 283 = 80,089 peers
TENANTS = 16
SLOTS = 8
CYCLES_PER_DISPATCH = 16
STREAM_TICKS = 4  # ticks with updates before each dispatch
SETTLE_TICKS = 16  # at most this many further ticks per wave
JOINS = 4


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@jax.jit
def _decisions(ta: lss.TopoArrays, snap: lss.LSSState,
               slot: regions.PackedSlot, eps) -> jax.Array:
    """Each peer's decision: the region of ``vec(S_i)`` (a record's
    accuracy is the share of these equal to the global one)."""
    live = lss.live_mask(ta, snap.alive)
    s = stopping.status(snap.x_m, snap.x_c, snap.out_m, snap.out_c,
                        snap.in_m, snap.in_c, live)
    return slot.decide(wvs.vec(s, eps))


def serve(side: int, use_kernels, *, step_text: bool) -> dict:
    """Drive one ``Service`` through two waves of tenants.

    Wave 1 holds the first 8 tenants; the other 8 wait in the admission
    queue and activate once wave 1 retires.  Each wave streams 1% updates
    before each of ``STREAM_TICKS`` dispatches (joins and links in the
    second), then ticks without updates until every Voronoi tenant is
    correct everywhere and quiescent, or ``SETTLE_TICKS`` pass.
    """
    base = topology.grid(side * side)
    dyn = topology.DynTopology.from_topology(
        base, n_cap=base.n + 2 * JOINS, deg_cap=base.max_deg + 2)
    specs = heterogeneous_tenants(dyn.n, TENANTS)
    svc = Service(dyn, ServiceConfig(capacity=SLOTS, k_max=4, d=2,
                                     cycles_per_dispatch=CYCLES_PER_DISPATCH,
                                     use_kernels=use_kernels))
    out = {"dispatch_info": svc.dispatch_info(), "records": [],
           "decisions": {}, "states": {}, "tick_s": [], "tenant_cycles": 0}
    t0 = time.perf_counter()
    lowered = svc._step.lower(svc.states, svc.registry.params,
                              svc.backend.topo_args(),
                              k=CYCLES_PER_DISPATCH)
    compiled = lowered.compile()
    out["compile_s"] = time.perf_counter() - t0
    label = out["dispatch_info"]["suite"]
    print(f"{label}: _step compiled in {out['compile_s']:.1f} s")
    if step_text:
        out["step_text"] = compiled.as_text()
    spec_of = {svc.admit(spec): spec for spec in specs}
    kinds = {q: type(spec.region).__name__ for q, spec in spec_of.items()}
    out["kinds"] = kinds
    check(len(svc.admission) == TENANTS - SLOTS,
          f"admission queue holds {len(svc.admission)} tenants, "
          f"expected {TENANTS - SLOTS}")
    rng = np.random.default_rng(7)

    def tick():
        t = time.perf_counter()
        recs = svc.tick()
        out["tick_s"].append(time.perf_counter() - t)
        print(f"{label}: tick {len(out['tick_s'])} took "
              f"{out['tick_s'][-1]:.3f} s, {len(recs)} tenants")
        out["tenant_cycles"] += len(recs) * CYCLES_PER_DISPATCH
        out["records"].append([(r["query"], r["accuracy"], r["quiescent"],
                                r["msgs"], r["region"]) for r in recs])
        return recs

    for wave in range(2):
        for step in range(STREAM_TICKS):
            who = rng.choice(base.n, size=base.n // 100, replace=False)
            svc.push_updates(who, rng.normal(size=(who.size, 2)),
                             mode="set")
            if wave == 0 and step == 1:
                for _ in range(JOINS):
                    p = svc.join_peer(value=rng.normal(size=2))
                    svc.link_peers(p, int(rng.integers(base.n)))
            recs = tick()
        for _ in range(SETTLE_TICKS):
            vor = [r for r in recs if kinds[r["query"]] == "VoronoiRegions"]
            if all(r["accuracy"] == 1.0 and r["quiescent"] for r in vor):
                break
            recs = tick()
        svc.flush()
        ta = svc.backend.topo_args()
        for r in recs:
            qid = r["query"]
            snap = svc.snapshot(qid)
            out["decisions"][qid] = np.asarray(_decisions(
                ta, snap, regions.as_packed_slot(spec_of[qid].region),
                svc.scfg.eps))
            out["states"][qid] = jax.tree_util.tree_map(np.asarray, snap)
            if kinds[qid] == "VoronoiRegions":
                check(r["accuracy"] == 1.0,
                      f"Voronoi tenant {qid} ends wave {wave + 1} at "
                      f"accuracy {r['accuracy']:.6f}, not 1.000")
            svc.retire(qid)
    svc.flush()
    svc.close()
    return out


def _ulps(a: np.ndarray, b: np.ndarray) -> int:
    """Largest distance in units of last place between two f32 arrays."""
    ia = a.astype(np.float32).view(np.int32).astype(np.int64)
    ib = b.astype(np.float32).view(np.int32).astype(np.int64)
    ia = np.where(ia < 0, -(ia & 0x7FFFFFFF), ia)
    ib = np.where(ib < 0, -(ib & 0x7FFFFFFF), ib)
    return int(np.max(np.abs(ia - ib), initial=0))


def serve_phase(dev) -> None:
    fused = serve(SIDE, None, step_text=True)
    info = fused["dispatch_info"]
    print(f"fused service: suite={info['suite']} fused={info['fused']}")
    check(info["fused"] is True, "the default service did not pick the "
          "fused kernel suite on the chip")
    check("tpu_custom_call" in fused["step_text"],
          "compiled _step holds no tpu_custom_call: the kernels did not "
          "go through Mosaic")
    steady = fused["tick_s"][1:]
    peak = dev.memory_stats()["peak_bytes_in_use"]
    print(f"compile seconds (fused _step): {fused['compile_s']:.3f}")
    print(f"wall per tick (smoke number, not a benchmark): "
          f"{np.mean(steady):.4f} s over {len(steady)} ticks "
          f"(first tick {fused['tick_s'][0]:.4f} s)")
    print(f"tenant-cycles per second (smoke): "
          f"{fused['tenant_cycles'] / sum(fused['tick_s']):.1f}")
    print(f"peak_bytes_in_use after the fused service: {peak} "
          f"({peak / 2**30:.3f} GiB)")
    vor = [q for q, k in fused["kinds"].items() if k == "VoronoiRegions"]
    print(f"ticks: {len(fused['tick_s'])}; Voronoi tenants at accuracy "
          f"1.000: {len(vor)}/{len(vor)}")

    ref = serve(SIDE, False, step_text=False)
    print(f"reference service: suite={ref['dispatch_info']['suite']} "
          f"compile seconds {ref['compile_s']:.3f}")
    compare(fused, ref)


def compare(fused: dict, ref: dict) -> None:
    """Fused and reference services: identical records (accuracy,
    quiescence, message counts, global decision) at every tick and
    identical per-peer decisions for every tenant."""
    check(ref["dispatch_info"]["fused"] is False,
          "use_kernels=False did not give the reference suite")
    check(len(ref["records"]) == len(fused["records"]),
          f"tick counts differ: fused {len(fused['records'])}, "
          f"reference {len(ref['records'])}")
    for t, (rf, rr) in enumerate(zip(fused["records"], ref["records"])):
        check(rf == rr, f"tick {t + 1}: records differ\n fused {rf}\n"
              f" reference {rr}")
    bitwise, worst = 0, 0
    for qid, dec in fused["decisions"].items():
        check(np.array_equal(dec, ref["decisions"][qid]),
              f"tenant {qid}: per-peer decisions differ on "
              f"{int(np.sum(dec != ref['decisions'][qid]))} peers")
        sf, sr = fused["states"][qid], ref["states"][qid]
        same = all(np.array_equal(a, b) for a, b in zip(sf, sr))
        bitwise += same
        for a, b in zip(sf, sr):
            if a.dtype == np.float32:
                worst = max(worst, _ulps(a, b))
    print(f"fused vs reference: records identical over "
          f"{len(fused['records'])} ticks, per-peer decisions identical for "
          f"{len(fused['decisions'])} tenants; state bitwise equal for "
          f"{bitwise}/{len(fused['decisions'])} tenants "
          f"(largest f32 distance {worst} ulp)")


def mesh_phase() -> None:
    """The four-chip halo ``all_to_all`` against the gather transport."""
    topo = topology.grid(SIDE * SIDE)
    centers, sample, _, _ = sim.make_problem(sim.ProblemSpec(n=topo.n,
                                                             seed=0))
    inputs = wvs.from_vector(
        jnp.asarray(sample(np.random.default_rng(1), topo.n)),
        jnp.ones((topo.n,), jnp.float32))
    ecfg = EngineConfig(num_shards=4, cycles_per_dispatch=CYCLES_PER_DISPATCH)
    cycles = 4 * CYCLES_PER_DISPATCH
    mesh = jax.make_mesh((4,), ("shards",))
    engines = {
        "all_to_all": ShardedLSS(topo, centers, lss.LSSConfig(),
                                 ecfg).use_mesh(mesh, "shards"),
        "gather": ShardedLSS(topo, centers, lss.LSSConfig(), ecfg),
    }
    final = {}
    for name, eng in engines.items():
        check(eng.dispatch_info["fused"] is True,
              f"{name} engine did not pick the fused kernel suite")
        st = eng.init(inputs, seed=0)
        t0 = time.perf_counter()
        st = eng.run(st, CYCLES_PER_DISPATCH)
        jax.block_until_ready(st)
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        st = eng.run(st, cycles - CYCLES_PER_DISPATCH)
        jax.block_until_ready(st)
        rest = time.perf_counter() - t0
        acc, quiescent, _ = eng.metrics(st)
        final[name] = eng.to_lss_state(st)
        print(f"{name}: first dispatch {first:.3f} s (compile included), "
              f"then {rest / (cycles - CYCLES_PER_DISPATCH) * 1e3:.3f} ms "
              f"per cycle (smoke); accuracy {float(acc):.6f} "
              f"quiescent {bool(quiescent)} msgs {int(final[name].msgs)}")
    a, b = final["all_to_all"], final["gather"]
    for field in a._fields:
        check(np.array_equal(np.asarray(getattr(a, field)),
                             np.asarray(getattr(b, field))),
              f"mesh engine and gather engine differ on {field!r}")
    print(f"mesh engine == gather engine bitwise on every LSSState field "
          f"after {cycles} cycles at {topo.n} peers")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)  # a cut run keeps its lines
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"no TPU: JAX runs on {devs[0].platform}", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, JAX sees "
              f"{len(devs)}", file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}")
    print(f"device: {devs[0].device_kind} x{len(devs)}, jax {jax.__version__}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            mesh_phase()
        else:
            serve_phase(devs[0])
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    print(f"smoke wall: {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
