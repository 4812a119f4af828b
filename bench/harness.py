"""One run of one cell: set-up, the measured window, the check.

Everything a cell is made of is found by name (see ``Bench``): the
configuration's file from ``BENCHMARK.json``, the mix as
``bench/traffic/<mix>.json``, a topology kind as
``bench/topologies/<kind>.py``, a per-layer metric's reader as
``bench/metrics/<metric>.py``.  Adding a configuration, a mix or a
metric adds files and entries and edits nothing here.

The system under test is the monitor's ``Service`` with the default
``ServiceConfig`` except the fields a configuration file lists: core
backend, kernel suite picked by the platform, sync mode, audit off.
Updates enter through ``Service.push_updates`` and tenants through
``Service.admit``; the window drives ``Service.tick``.
"""

from __future__ import annotations

import copy
import gc
import importlib.util
import json
import pathlib
import resource
import shutil
import tempfile
import time
from typing import Callable, Optional

import numpy as np

from bench import generator, reference, tracefile

ROOT = pathlib.Path(__file__).resolve().parents[1]
TRACE_TICKS = 3  # ticks traced in the middle of a --trace 1 window
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class Bench:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: pathlib.Path = ROOT):
        self.root = pathlib.Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def mix(self, name: str) -> dict:
        return json.loads(
            (self.root / "bench" / "traffic" / f"{name}.json").read_text())

    def peaks(self, kind: str) -> dict:
        table = json.loads((self.root / "bench" / "peaks.json").read_text())
        if kind not in table:
            raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
        return table[kind]

    def _module(self, *parts: str):
        path = self.root.joinpath("bench", *parts)
        spec = importlib.util.spec_from_file_location(
            "bench_" + "_".join(parts).replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def topology(self, spec: dict) -> dict:
        params = {k: v for k, v in spec.items() if k != "kind"}
        return self._module("topologies", f"{spec['kind']}.py").build(
            **params)

    def reader(self, metric: str):
        return self._module("metrics", f"{metric}.py")

    def metrics(self, workload: str, kind: str) -> list:
        """The cell's metrics of one kind (``end_to_end``/``per_layer``)."""
        return [m for m in self.spec[kind]
                if "workloads" not in m or workload in m["workloads"]]


def _override(cfg: dict, patch: dict) -> dict:
    out = copy.deepcopy(cfg)
    for k, v in patch.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _override(out[k], v)
        else:
            out[k] = v
    return out


class CompileWatch:
    """Counts JAX traces, lowerings, compiles and compile-cache reads
    while ``armed``: inside the window there must be none."""

    def __init__(self):
        import jax.monitoring

        self.armed = False
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if self.armed and event in COMPILE_EVENTS:
            self.events += 1

    def close(self) -> None:
        import jax.monitoring

        self.armed = False
        jax.monitoring.unregister_event_duration_listener(self._on)


class GcWatch:
    """Python garbage collections while ``armed``: how many, how long."""

    def __init__(self):
        self.armed = False
        self.count, self.seconds = 0, 0.0
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, _info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self.armed and self._t is not None:
            self.count += 1
            self.seconds += time.perf_counter() - self._t

    def close(self) -> None:
        self.armed = False
        gc.callbacks.remove(self._on)


def _main_thread_usage():
    """(CPU seconds, involuntary context switches) of the calling thread."""
    ru = resource.getrusage(resource.RUSAGE_THREAD)
    return ru.ru_utime + ru.ru_stime, ru.ru_nivcsw


def _window_diag(marks, gcw: GcWatch, usage0, usage1) -> dict:
    """Where the window's host-clock time went, for a run that reads far
    off: per tick (start, tick start, tick end, end) in ``marks``."""
    ticks = [c - b for _, b, c, _ in marks]
    outside = [(b - a) + (d - c) for a, b, c, d in marks]
    slow = max(range(len(ticks)), key=ticks.__getitem__)
    busy = max(range(len(outside)), key=outside.__getitem__)
    return {"ticks": len(marks),
            "tick_ms_median": 1e3 * float(np.median(ticks)),
            "tick_ms_max": 1e3 * ticks[slow], "tick_max_at": slow,
            "outside_ms_sum": 1e3 * sum(outside),
            "outside_ms_max": 1e3 * outside[busy], "outside_max_at": busy,
            "gc_runs": gcw.count, "gc_ms": 1e3 * gcw.seconds,
            "main_cpu_s": usage1[0] - usage0[0],
            "main_involuntary_switches": usage1[1] - usage0[1]}


def _percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least q% of
    the samples at or below it."""
    v = sorted(values)
    return float(v[max(0, int(np.ceil(q / 100 * len(v))) - 1)])


class Run:
    """Everything a run saw, for the metric readers and the check."""

    def __init__(self):
        self.records = []  # (dispatch, record dict, host time of emission)
        self.bursts = []  # (burst index, arrival time, dispatch applying it)
        self.snaps = []  # (dispatch, qid, state read back)
        self.window_ticks = []  # dispatches of the window
        self.window_recs = []  # the window's records
        self.corr_iters = None  # correction totals at open and at close
        self.trace = None  # the --trace 1 window, as tracefile.load gives
        self.tracker = None  # the service's InMemoryTracker
        self.out = None  # the run's numbers (run_cell's first result)


def run_cell(bench: Bench, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, rehearse: bool = False,
             control: bool = False,
             break_service: Optional[Callable] = None,
             drain_cap_s: Optional[float] = None) -> tuple:
    """Set up, measure ``seconds``, check.  Returns the run's numbers
    (end-to-end metrics, counts, ``checks``) and the ``Run`` that the
    per-layer readers read.  What is pushed when, and who comes and
    goes, is the mix's (``generator.Traffic``); this only times and
    checks.

    ``rehearse`` runs the configuration's tiny ``rehearsal`` sizes on any
    platform; ``control`` runs the configuration's ``control_drop_rate``
    (message loss, which breaks a stated guarantee); ``break_service``
    plants a fault in the service before set-up and ``drain_cap_s``
    shortens the mix's wait for late answers (tests only).
    """
    import jax
    import jax.numpy as jnp
    from repro.core import regions
    from repro.core import topology as ptopo
    from repro.obs import InMemoryTracker, jit_cache_size
    from repro.service import QuerySpec, Service, ServiceConfig

    phases = {"imports": time.perf_counter() - t_start}
    cell = bench.cell(workload)
    cfg = bench.config(cell["config"])
    if rehearse:
        cfg = _override(cfg, cfg["rehearsal"])
    top = bench.topology(cfg["topology"])
    n = top["n"]
    service = dict(cfg["service"])
    if control:
        service["drop_rate"] = float(cfg["control_drop_rate"])
    scfg = ServiceConfig(**service)
    q, d, k = scfg.capacity, scfg.d, scfg.cycles_per_dispatch
    phases["topology"] = time.perf_counter() - t_start
    traffic = generator.Traffic(bench.mix(cell["traffic"]), n, q, d, seed)
    mix = traffic.mix
    watch = CompileWatch()
    gcw = GcWatch()
    tracker = InMemoryTracker()
    svc = Service(ptopo.Topology(nbr=top["nbr"], mask=top["mask"],
                                 rev=top["rev"], n=n,
                                 max_deg=top["max_deg"]),
                  scfg, tracker=tracker)
    if break_service is not None:
        break_service(svc)
    tenant_of = {}  # qid -> tenant description, every admission

    def admit(t: dict) -> None:
        region = (regions.VoronoiRegions(jnp.asarray(t["centers"]))
                  if t["kind"] == "voronoi"
                  else regions.HalfspaceRegions(w=jnp.asarray(t["w"]),
                                                b=jnp.float32(t["b"])))
        qid = svc.admit(QuerySpec(region=region, inputs=t["x"],
                                  seed=t["seed"], beta=t["beta"],
                                  ell=t["ell"]))
        tenant_of[qid] = t

    for t in traffic.tenants:
        admit(t)
    run = Run()
    run.tracker = tracker
    take = jax.jit(lambda sts, i: jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), sts))
    order = np.random.default_rng([seed, 3]).permutation(q)
    ran, last = {}, {}  # per running tenant: dispatches run, latest record

    def push(bursts) -> None:
        for idx, arrival in bursts:
            who, vals = traffic.burst(idx)
            with jax.profiler.TraceAnnotation("bench.push"):
                at = time.perf_counter()
                svc.push_updates(who, vals, mode="set")
            run.bursts.append((idx, at if arrival is None else arrival,
                               svc.dispatches + 1))

    def tick() -> list:
        with jax.profiler.TraceAnnotation(tracefile.TICK):
            recs = svc.tick()
        at = time.perf_counter()
        run.records += [(r["dispatch"], r, at) for r in recs]
        for r in recs:
            ran[r["query"]] = ran.get(r["query"], 0) + 1
            last[r["query"]] = r
        return recs

    def done(recs) -> bool:
        return all(r["accuracy"] == 1.0 and r["quiescent"] for r in recs)

    def churn() -> None:
        for qid in traffic.retire(ran, last):
            svc.retire(qid)
            del last[qid]
            admit(tenant_of[qid])

    phases["admitted"] = time.perf_counter() - t_start
    # -- set-up: converge from cold, then warm every shape the window uses
    tick_s = []
    for _ in range(int(mix["settle_cap_ticks"])):
        t0 = time.perf_counter()
        recs = tick()
        tick_s.append(time.perf_counter() - t0)
        if done(recs):
            break
    phases["settled"] = time.perf_counter() - t_start
    phases["settle_ticks"] = len(tick_s)
    for _ in range(int(mix["warm_ticks"])):
        push([(i, None) for i in traffic.warm()])
        t0 = time.perf_counter()
        tick()
        tick_s.append(time.perf_counter() - t0)
        churn()
    jax.block_until_ready(take(svc.states, 0))
    traffic.prepare(seconds, min(tick_s))

    # -- the measured window -------------------------------------------
    step_cache = jit_cache_size(svc._step)
    corr0 = _corr_totals(tracker)
    watch.armed = gcw.armed = True
    usage0 = _main_thread_usage()
    marks = []  # per window tick: start, tick start, tick end, end
    t_open = time.perf_counter()
    setup_s = t_open - t_start
    traffic.open(t_open)
    trace_dir, traced = None, 0  # traced: ticks so far, -1 once stopped
    copying = None  # the last snapshot, on its way to the host
    while time.perf_counter() - t_open < seconds:
        t0 = time.perf_counter()
        if (trace and trace_dir is None
                and time.perf_counter() - t_open >= seconds / 3):
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(trace_dir)
        push(traffic.due(time.perf_counter()))
        t1 = time.perf_counter()
        recs = tick()
        t2 = time.perf_counter()
        run.window_ticks.append(svc.dispatches)
        # One tenant's state read back per tick, its slot drawn from the
        # seed in turn; it goes to the host behind the next dispatch.
        slot = int(order[len(run.window_ticks) % q])
        if copying is not None:
            run.snaps.append(copying[:2] + (jax.device_get(copying[2]),))
            copying = None
        for r in recs:
            if r["slot"] == slot:
                st = take(svc.states, slot)
                for leaf in jax.tree_util.tree_leaves(st):
                    leaf.copy_to_host_async()
                copying = (svc.dispatches, r["query"], st)
        churn()
        if trace_dir is not None and traced >= 0:
            traced += 1
            if traced == TRACE_TICKS:
                jax.profiler.stop_trace()
                traced = -1
        marks.append((t0, t1, t2, time.perf_counter()))
    t_close = max(at for _, _, at in run.records)
    usage1 = _main_thread_usage()
    gcw.armed = False
    if traced > 0:
        jax.profiler.stop_trace()
    watch.close()
    gcw.close()
    run.corr_iters = (corr0, _corr_totals(tracker))
    window_compiles = watch.events + (jit_cache_size(svc._step) - step_cache
                                      if step_cache is not None else 0)
    if copying is not None:
        run.snaps.append(copying[:2] + (jax.device_get(copying[2]),))

    # -- drain: no more bursts; every sample resolves or the cap passes --
    drain_until = time.perf_counter() + float(
        mix["drain_cap_s"] if drain_cap_s is None else drain_cap_s)

    def all_answered() -> bool:
        running = [qid for qid, _, _ in svc.registry.active_items()]
        return all(qid in last for qid in running) and done(
            last[qid] for qid in running)

    while not all_answered() and time.perf_counter() < drain_until:
        tick()

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    final = jax.device_get(svc.states)
    final_slot = {qid: slot for qid, slot, _ in svc.registry.active_items()}
    topo_np = {"nbr": top["nbr"], "mask": top["mask"], "rev": top["rev"]}
    svc.close()
    del svc

    window = set(run.window_ticks)
    window_recs = [r for dsp, r, _ in run.records if dsp in window]
    window_s = t_close - t_open
    tenant_cycles = len(window_recs) * k
    out = {
        "setup_s": setup_s,
        "window_s": window_s,
        "tenant_cycles": tenant_cycles,
        "window_ticks": len(run.window_ticks),
        "window_compiles": window_compiles,
        "setup_phases": phases,
        "window_diag": _window_diag(marks, gcw, usage0, usage1),
        "peak": peak,
        "links": int(top["mask"].sum()) // 2,
        "shapes": {"q": q, "n": n, "D": int(top["max_deg"]), "d": d, "k": k},
    }
    e2e = {"setup_s": setup_s, "tenant_cycles_per_s": tenant_cycles / window_s}
    samples, unresolved = _latencies(run) if traffic.bursty else ([], 0)
    if traffic.bursty:
        attempted, failed = len(samples) + unresolved, unresolved
        if samples:
            e2e["decision_p50_ms"] = 1e3 * _percentile(samples, 50)
            e2e["decision_p95_ms"] = 1e3 * _percentile(samples, 95)
    else:
        attempted, failed = len(window_recs), _fell_back(run, window)
    out.update(attempted=attempted, failed=failed, e2e=e2e)
    out["checks"], out["forgiven"] = _check(
        run, tenant_of, traffic, k, final, final_slot, last, topo_np,
        scfg.eps, unresolved)
    if trace and trace_dir is not None:
        run.trace = tracefile.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    run.window_recs = window_recs
    run.out = out
    return out, run


def _corr_totals(tracker):
    """(iterations, slot-dispatches) so far in the service's
    ``service_corr_iters`` histogram, over every tenant."""
    hist = tracker.registry.get("service_corr_iters")
    tot = cnt = 0
    for _labels, (counts, total) in (hist.series() if hist else ()):
        tot += total
        cnt += sum(counts)
    return float(tot), int(cnt)


def _latencies(run: Run):
    """Per (burst applied in the window, tenant running at that dispatch):
    seconds from the burst's arrival to the first record of that tenant,
    at or after the dispatch that applied it, with accuracy 1.0.  Returns
    (samples, unresolved)."""
    first_window = min(run.window_ticks)
    running, by_q = {}, {}
    for dsp, r, at in run.records:
        running.setdefault(dsp, []).append(r["query"])
        if r["accuracy"] == 1.0:
            by_q.setdefault(r["query"], []).append((dsp, at))
    samples, unresolved = [], 0
    for _, arrived, applied in run.bursts:
        if applied < first_window:
            continue  # a set-up burst
        for qid in running.get(applied, ()):
            hit = next((at for dsp, at in by_q.get(qid, ()) if dsp >= applied),
                       None)
            if hit is None:
                unresolved += 1
            else:
                samples.append(hit - arrived)
    return samples, unresolved


def _fell_back(run: Run, window: set) -> int:
    """Window records below accuracy 1.0 of tenants that had settled (an
    earlier record at accuracy 1.0 and quiescent)."""
    settled, fell = set(), 0
    for dsp, r, _ in run.records:
        if dsp in window and r["query"] in settled and r["accuracy"] != 1.0:
            fell += 1
        if r["accuracy"] == 1.0 and r["quiescent"]:
            settled.add(r["query"])
    return fell


def _state_dict(st) -> dict:
    return {f: np.asarray(getattr(st, f)) for f in st._fields}


def _check(run: Run, tenant_of: dict, traffic, k: int, final,
           final_slot: dict, last: dict, topo: dict, eps: float,
           unresolved: int) -> tuple:
    """Every number compared, each as [value, limit] (see PERF.md), and
    the peers the status error bound alone left unjudged (no limit): the
    most in any read-back state, the largest share of a state's live
    peers, and the total over the states judged."""
    truths = {}  # qid -> reference.Truth, from the tenant's first dispatch
    want = {}  # (dispatch, qid) -> reference region
    applied_at = {}
    for idx, _, applied in run.bursts:
        applied_at.setdefault(applied, []).append(idx)
    running = {}  # dispatch -> the tenants that ran in it
    for dsp, r, _ in run.records:
        running.setdefault(dsp, []).append(r["query"])
    snap_at = {}
    for dsp, qid, st in run.snaps:
        snap_at.setdefault(dsp, []).append((qid, _state_dict(st)))
    acc_of = {(dsp, r["query"]): r for dsp, r, _ in run.records}
    cycles_run = {}  # qid -> dispatches run so far
    input_bad = links_bad = acc_gap = cycle_bad = 0
    spared = []  # per judged state: (peers forgiven, live peers)

    def judge(t, st, want):
        right, wrong, spare = reference.verdict(t, st, topo, eps, want)
        spared.append((int(spare.sum()), int(st["alive"].sum())))
        return right, wrong
    for dsp in sorted(running):
        for qid in running[dsp]:
            if qid not in truths:
                truths[qid] = reference.Truth(tenant_of[qid])
            cycles_run[qid] = cycles_run.get(qid, 0) + 1
        # A burst reaches every tenant running at the dispatch applying it.
        for idx in applied_at.get(dsp, ()):
            who, vals = traffic.burst(idx)
            for qid in running[dsp]:
                truths[qid].apply(who, vals)
        for qid in running[dsp]:
            want[(dsp, qid)] = truths[qid].region()
        for qid, st in snap_at.get(dsp, ()):
            tr = truths[qid]
            input_bad += reference.input_mismatch(tr, st)
            links_bad += reference.unsettled_links(st, topo)
            cycle_bad += int(st["t"]) != k * cycles_run[qid]
            right, wrong = judge(tr.t, st, tr.region())
            alive = int(st["alive"].sum())
            claimed = round(acc_of[(dsp, qid)]["accuracy"] * alive)
            # claimed must lie between the peers right beyond doubt and
            # those not wrong beyond doubt
            acc_gap = max(acc_gap, int(right.sum()) - claimed,
                          claimed - (alive - int(wrong.sum())))
    region_bad = sum(r["region"] != want[(dsp, r["query"])]
                     for dsp, r, _ in run.records)
    # Messages sent by a tenant that was quiescent at its previous record,
    # in a dispatch that applied no burst, must be none.
    prev, quiet_msgs = {}, 0
    for dsp, r, _ in run.records:
        p = prev.get(r["query"])
        if p is not None and p["quiescent"] and dsp not in applied_at:
            quiet_msgs += r["msgs"]
        prev[r["query"]] = r
    # The final state of every running tenant: its cycle counter, inputs
    # and links, and, where its last record claims accuracy 1.0 at
    # quiescence, every peer deciding the reference's region.
    fin = _state_dict(final)
    wrong_final = unconverged = 0
    for qid, slot in final_slot.items():
        if qid not in truths:  # admitted at the window's end, never ran
            truths[qid] = reference.Truth(tenant_of[qid])
            cycles_run[qid] = 0
        tr, r = truths[qid], last.get(qid)
        st = {f: v[slot] for f, v in fin.items()}
        input_bad += reference.input_mismatch(tr, st)
        links_bad += reference.unsettled_links(st, topo)
        cycle_bad += int(st["t"]) != k * cycles_run[qid]
        if r is None or not (r["accuracy"] == 1.0 and r["quiescent"]):
            unconverged += 1
            continue
        _, wrong = judge(tr.t, st, tr.region())
        wrong_final += int(wrong.sum())
    forgiven = {"states": len(spared),
                "most": max((f for f, _ in spared), default=0),
                "most_pct": max((100.0 * f / n for f, n in spared if n),
                                default=0.0),
                "total": sum(f for f, _ in spared)}
    return {
        "region_mismatch": [int(region_bad), 0],
        "input_mismatch": [int(input_bad), 0],
        "unsettled_links": [int(links_bad), 0],
        "cycle_mismatch": [int(cycle_bad), 0],
        "accuracy_gap_peers": [int(acc_gap), 0],
        "final_wrong_peers": [int(wrong_final), 0],
        "unconverged_tenants": [int(unconverged), 0],
        "quiet_msgs": [int(quiet_msgs), 0],
        "unresolved_samples": [int(unresolved), 0],
    }, forgiven
