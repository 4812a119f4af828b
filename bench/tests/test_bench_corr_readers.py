"""The readers of the correction do-while's counters, on hand-made window
records: the share of the passes the device ran that each slot needed
(``corr_lockstep_share``), and the share of the peers in those passes
still running (``corr_running_share``).  Records without the counts (a
service that does not make them) give nothing."""

import types

import pytest

from bench import harness

N = 1000


def run_of(recs):
    return types.SimpleNamespace(window_recs=recs)


def rec(iters, passes, running):
    return {"corr_iters": iters, "corr_passes": passes,
            "corr_running": running}


CTX = {"shapes": {"q": 2, "n": N, "D": 34, "d": 2, "k": 16}}
# Two dispatches of two tenants: the loop ran 10 and then 4 passes.
RECS = [rec(10, 10, 3000), rec(5, 10, 500), rec(4, 4, 40), rec(0, 4, 0)]


def test_lockstep_share():
    got = harness.Bench().reader("corr_lockstep_share").read(
        run_of(RECS), CTX)
    assert got == pytest.approx(100.0 * 19 / 28)


def test_running_share():
    got = harness.Bench().reader("corr_running_share").read(
        run_of(RECS), CTX)
    assert got == pytest.approx(100.0 * 3540 / (19 * N))


@pytest.mark.parametrize("metric",
                         ["corr_lockstep_share", "corr_running_share"])
@pytest.mark.parametrize("recs", [
    [{"msgs": 3}],  # records of a service that makes no such counts
    [rec(0, 0, 0), rec(0, 0, 0)],  # the loop never ran
])
def test_nothing_to_read(metric, recs):
    assert harness.Bench().reader(metric).read(run_of(recs), CTX) is None


def test_full_use_reads_100():
    """One tenant whose every peer ran every pass the loop ran."""
    recs = [rec(7, 7, 7 * N)]
    bench = harness.Bench()
    for metric in ("corr_lockstep_share", "corr_running_share"):
        assert bench.reader(metric).read(run_of(recs), CTX) == 100.0
