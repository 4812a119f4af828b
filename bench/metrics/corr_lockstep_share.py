"""Share of the correction do-while's passes that each slot needed:
100 x the passes the slots needed / the passes the device ran for them,
from the window's records.  The query ``vmap`` runs the loop until every
slot stops, so a slot that stops early rides the rest of the passes.
Nothing where the records lack the counts, or the loop never ran."""


def read(run, ctx):
    recs = [r for r in run.window_recs if "corr_passes" in r]
    ran = sum(r["corr_passes"] for r in recs)
    if not ran:
        return None
    return 100.0 * sum(r["corr_iters"] for r in recs) / ran
