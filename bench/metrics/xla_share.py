"""Share of ``_step``'s device time spent outside the Pallas kernels:
the core cycle's XLA work (the ``mirror_slots`` delivery gathers,
``live_mask``, layout copies, loop control)."""

from bench import tracefile


def read(run, ctx):
    if run.trace is None:
        return None
    step = tracefile.module_ns(run.trace, tracefile.STEP)
    if not step:
        return None
    return 100.0 * (step - tracefile.all_kernels_ns(run.trace)) / step
