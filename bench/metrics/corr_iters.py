"""Mean correction do-while iterations per slot per dispatch in the
window, from the service's ``service_corr_iters`` histogram."""


def read(run, ctx):
    (t0, c0), (t1, c1) = run.corr_iters
    return (t1 - t0) / (c1 - c0) if c1 > c0 else None
