"""Device idle per traced tick while the host runs the service's host
boundary: its ``membership_drain``, ``admission_drain`` and
``ingest_apply`` spans, taken from the ``InMemoryTracker`` and put on the
trace's clock.

The harness opens each traced tick's ``bench.tick`` annotation right
around ``Service.tick()``, whose ``tick`` span starts microseconds later,
so a boundary span lies at ``bench.tick``'s start plus its offset from
its tick span's start.  The traced ticks are the run of consecutive
``tick`` spans whose starts keep one offset to the ``bench.tick`` starts
(a wrong run is off by whole ticks).  A program whose spans carry no
``start`` gives nothing."""

from bench import tracefile

BOUNDARY = ("membership_drain", "admission_drain", "ingest_apply")


def _covered(busy, lo, hi) -> float:
    return float(sum(max(0, min(e, hi) - max(s, lo)) for s, e in busy))


def read(run, ctx):
    if run.trace is None or not run.trace["devices"]:
        return None
    marks = sorted(s for n, s, _ in run.trace["host"] if n == tracefile.TICK)
    ticks = sorted((sp for sp in run.tracker.spans if sp.name == "tick"),
                   key=lambda sp: getattr(sp, "start", 0.0))
    if (not marks or len(ticks) < len(marks)
            or not all(hasattr(sp, "start") for sp in ticks)):
        return None

    def spread(j):
        offs = [m - 1e9 * ticks[j + i].start for i, m in enumerate(marks)]
        return max(offs) - min(offs)

    first = min(range(len(ticks) - len(marks) + 1), key=spread)
    inside = {}
    for sp in run.tracker.spans:
        if sp.name in BOUNDARY:
            inside.setdefault(sp.parent_id, []).append(sp)
    busy = tracefile.union(run.trace["devices"][0]["ops"])
    idle = 0.0
    for mark, tick in zip(marks, ticks[first:]):
        for sp in inside.get(tick.span_id, ()):
            lo = mark + 1e9 * (sp.start - tick.start)
            hi = lo + 1e9 * sp.seconds
            idle += (hi - lo) - _covered(busy, lo, hi)
    return idle / 1e6 / len(marks)
