"""Host boundary per tick: the service's ``membership_drain``,
``admission_drain`` and ``ingest_apply`` spans, summed per window tick
and averaged (host clock, read through the ``InMemoryTracker``)."""

BOUNDARY = ("membership_drain", "admission_drain", "ingest_apply")


def read(run, ctx):
    window = set(run.window_ticks)
    tick_of = {sp.span_id: sp.attrs.get("dispatch")
               for sp in run.tracker.spans if sp.name == "tick"}
    total = sum(sp.seconds for sp in run.tracker.spans
                if sp.name in BOUNDARY and tick_of.get(sp.parent_id) in window)
    return 1e3 * total / len(window) if window else None
