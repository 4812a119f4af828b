"""Profiler trace -> plain events, and the reductions every reader shares.

A TPU trace (``jax.profiler`` ``.xplane.pb``) holds, per chip, a plane
``/device:TPU:<i>`` whose line ``XLA Modules`` has one event per program
run (``jit__step_impl(...)``) and whose line ``XLA Ops`` has one event
per HLO instruction run, named by the instruction's text
(``%lss_state.6 = (...) custom-call(...)``; a ``%while`` event spans its
whole body).  The host plane ``/host:CPU`` has a line ``python`` with the
interpreter's frames; the benchmark's ``TraceAnnotation`` spans
(``bench.tick``, ``bench.push``) are taken from whichever host line holds
them.  Times are nanoseconds on one clock.

``load`` turns that into a dict of plain lists, which is also the form a
recorded trace is kept in for the tests:

    {"devices": [{"ops": [[name, start, end], ...],
                  "modules": [[name, start, end], ...]}, ...],
     "host": [[name, start, end], ...]}
"""

from __future__ import annotations

import glob
import os
import re

TICK = "bench.tick"
STEP = "jit__step_impl"  # the dispatch program, Service._step


def load(trace_dir: str) -> dict:
    import jax  # the reader needs nothing else of JAX

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    devices, host = [], []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [[e.name, e.start_ns, e.start_ns + e.duration_ns]
                                for e in line.events]
            devices.append(dev)
        else:  # host planes: the interpreter's frames, and our spans
            for line in plane.lines:
                for e in line.events:
                    if line.name == "python" or e.name.startswith("bench."):
                        host.append([e.name, e.start_ns,
                                     e.start_ns + e.duration_ns])
    host = sorted({(n, s, e) for n, s, e in host}, key=lambda ev: ev[1])
    host = [list(ev) for ev in host]
    return {"devices": devices, "host": host}


def ticks(trace: dict) -> list:
    """(start, end) of each traced tick: the ``bench.tick`` spans, or,
    where the host plane holds none, the dispatch programs' runs."""
    spans = [(s, e) for n, s, e in trace["host"] if n == TICK]
    if not spans and trace["devices"]:
        spans = [(s, e) for n, s, e in trace["devices"][0]["modules"]
                 if n.startswith(STEP)]
    return spans


def window(trace: dict):
    """(start, end) of the traced ticks: the first tick's start to the
    last one's end; None when the trace holds no tick."""
    spans = ticks(trace)
    if not spans:
        return None
    return min(s for s, _ in spans), max(e for _, e in spans)


def ticks_in(trace: dict) -> int:
    return len(ticks(trace))


def clip(events, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted((s, e) for _, s, e in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(trace: dict, dev: int = 0):
    """Nanoseconds in the traced window in which an operation ran on chip
    ``dev``, and the window's length; None without a window."""
    w = window(trace)
    if w is None or not trace["devices"]:
        return None
    ops = clip(trace["devices"][dev]["ops"], *w)
    return sum(e - s for s, e in union(ops)), w[1] - w[0]


def instruction(op_name: str) -> str:
    """``%fusion.77 = f32[...] ...`` -> ``fusion.77``."""
    return op_name.split(" = ", 1)[0].lstrip("%")


def kernel_of(op_name: str):
    """The Pallas kernel an op event runs (``lss_state``, ``correction``),
    or None for an XLA op.  A Pallas call is a ``tpu_custom_call`` named
    after its kernel, a batched one as ``vmap_jit_<name>__``."""
    if 'custom_call_target="tpu_custom_call"' not in op_name:
        return None
    stem = instruction(op_name).rsplit(".", 1)[0]
    return re.sub(r"^vmap_jit_|_+$", "", stem)


def module_ns(trace: dict, prefix: str, dev: int = 0) -> float:
    """Device nanoseconds of program runs whose name starts with
    ``prefix`` (``jit__step_impl``) inside the traced window."""
    w = window(trace)
    if w is None or not trace["devices"]:
        return 0.0
    mods = clip(trace["devices"][dev]["modules"], *w)
    return float(sum(e - s for n, s, e in mods if n.startswith(prefix)))


def kernel_ns(trace: dict, kernel: str, dev: int = 0):
    """(device nanoseconds, runs) of one Pallas kernel in the window."""
    w = window(trace)
    if w is None or not trace["devices"]:
        return 0.0, 0
    ops = [(n, s, e) for n, s, e in clip(trace["devices"][dev]["ops"], *w)
           if kernel_of(n) == kernel]
    return float(sum(e - s for _, s, e in ops)), len(ops)


def all_kernels_ns(trace: dict, dev: int = 0) -> float:
    w = window(trace)
    if w is None or not trace["devices"]:
        return 0.0
    return float(sum(e - s for n, s, e in clip(trace["devices"][dev]["ops"],
                                               *w)
                     if kernel_of(n) is not None))


def host_frame(trace: dict, t: float) -> str:
    """The innermost host frame running at time ``t``."""
    best = None
    for n, s, e in trace["host"]:
        if s <= t < e and (best is None or s >= best[1]):
            best = (n, s)
    return best[0].lstrip("$") if best else "(no host frame)"


def breakdown(trace: dict, dev: int = 0, top: int = 10) -> dict:
    """The device operations that took most time, and the idle gaps
    summed by the innermost host frame running at each gap's middle."""
    w = window(trace)
    if w is None or not trace["devices"]:
        return {"device_ops": [], "idle_gaps": []}
    ops = clip(trace["devices"][dev]["ops"], *w)
    by_op = {}
    for n, s, e in ops:
        name = instruction(n)
        if not name.startswith("while"):  # a loop's span holds its body
            by_op[name] = by_op.get(name, 0) + (e - s)
    gaps, prev = {}, w[0]
    for s, e in union(ops) + [[w[1], w[1]]]:
        if s > prev:
            label = host_frame(trace, (prev + s) / 2)
            gaps[label] = gaps.get(label, 0) + (s - prev)
        prev = max(prev, e)
    rank = lambda d: [[k, v / 1e9] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(by_op), "idle_gaps": rank(gaps)}
