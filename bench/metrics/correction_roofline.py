"""``correction`` kernel's share of its HBM roofline: least bytes of the work
(``bytemodel.correction_bytes``) per run, times runs, over the v5e's HBM
bandwidth, over the kernel's device time in the trace."""

from bench import tracefile
from bench.metrics import bytemodel


def read(run, ctx):
    if run.trace is None:
        return None
    ns, runs = tracefile.kernel_ns(run.trace, "correction")
    if not runs or ns <= 0:
        return None
    s = ctx["shapes"]
    work = runs * bytemodel.correction_bytes(s["q"], s["n"], s["D"], s["d"])
    return 100.0 * work / ctx["peaks"]["hbm_bytes_per_s"] / (ns / 1e9)
