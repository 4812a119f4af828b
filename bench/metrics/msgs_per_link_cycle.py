"""The paper's cost: messages sent in the window / (links x
tenant-cycles), from the per-tenant records' exact send counts."""


def read(run, ctx):
    cycles = run.out["tenant_cycles"]
    if not cycles:
        return None
    sent = sum(r["msgs"] for r in run.window_recs)
    return sent / (run.out["links"] * cycles)
