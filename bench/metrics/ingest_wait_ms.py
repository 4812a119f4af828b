"""Mean wait of an update batch applied in the window: from
``push_updates`` to the start of the ``ingest_apply`` span that applied
it.  Read from that span's ``waited`` and ``wait_s`` attributes (the
same waits the service's ``service_ingest_wait_seconds`` histogram
observes), over the window's ticks."""


def read(run, ctx):
    window = set(run.window_ticks)
    tick_of = {sp.span_id: sp.attrs.get("dispatch")
               for sp in run.tracker.spans if sp.name == "tick"}
    spans = [sp for sp in run.tracker.spans
             if sp.name == "ingest_apply" and "wait_s" in sp.attrs
             and tick_of.get(sp.parent_id) in window]
    batches = sum(sp.attrs["waited"] for sp in spans)
    if not batches:
        return None
    return 1e3 * sum(sp.attrs["wait_s"] for sp in spans) / batches
