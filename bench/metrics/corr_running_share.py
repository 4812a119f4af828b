"""Share of the peers in the correction do-while's needed passes that
were still running: 100 x the running peers summed over each slot's
passes / (those passes x the peers), from the window's records.  Each
pass computes every peer; only the running ones can change.  Nothing
where the records lack the counts, or the loop never ran."""


def read(run, ctx):
    recs = [r for r in run.window_recs if "corr_running" in r]
    covered = sum(r["corr_iters"] for r in recs) * ctx["shapes"]["n"]
    if not covered:
        return None
    return 100.0 * sum(r["corr_running"] for r in recs) / covered
