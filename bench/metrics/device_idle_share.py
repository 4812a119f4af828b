"""Share of the traced window in which no operation ran on the chip:
1 - (union of the ``XLA Ops`` intervals) / (first tick's start to the
last tick's end)."""

from bench import tracefile


def read(run, ctx):
    if run.trace is None:
        return None
    busy = tracefile.busy_ns(run.trace)
    if busy is None or busy[1] <= 0:
        return None
    return 100.0 * (1.0 - busy[0] / busy[1])
