"""Device time of the dispatch program ``Service._step`` per traced tick
(``jit__step_impl`` runs in the trace's ``XLA Modules`` line)."""

from bench import tracefile


def read(run, ctx):
    if run.trace is None or not tracefile.ticks_in(run.trace):
        return None
    ns = tracefile.module_ns(run.trace, tracefile.STEP)
    return ns / 1e6 / tracefile.ticks_in(run.trace) if ns else None
