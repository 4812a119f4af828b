"""Observe's host work after its sync, per window tick: the service's
``observe_emit`` span (per-tenant records, gauges, audit, alerts, the
control record), summed per window tick and averaged (host clock, read
through the ``InMemoryTracker``)."""


def read(run, ctx):
    window = set(run.window_ticks)
    tick_of = {sp.span_id: sp.attrs.get("dispatch")
               for sp in run.tracker.spans if sp.name == "tick"}
    spans = [sp.seconds for sp in run.tracker.spans
             if sp.name == "observe_emit"
             and tick_of.get(sp.parent_id) in window]
    return 1e3 * sum(spans) / len(window) if spans else None
