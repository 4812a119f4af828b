"""The Chord configuration (``p2p-chord-80k``) on the CPU at its rehearsal
size (256 peers, D = 15, 4 tenants): ``chord80k.stream`` comes out
correct, its message-loss control does not, and a planted fault is
caught."""

import time

from bench import harness
from bench.tests.test_bench_faults import half_burst

SEED = 2 ** 31 + 77


def rehearse(**kw):
    return harness.run_cell(harness.Bench(), "chord80k.stream", SEED, 0.5,
                            False, t_start=time.perf_counter(),
                            rehearse=True, drain_cap_s=5.0, **kw)


def test_rehearsal_is_correct():
    out, run = rehearse()
    assert out["shapes"] == {"q": 4, "n": 256, "D": 15, "d": 2, "k": 16}
    for name, (value, limit) in out["checks"].items():
        assert value == 0 == limit, (name, value)
    assert out["window_compiles"] == 0
    assert out["attempted"] > 0 and out["failed"] == 0
    assert {"decision_p50_ms", "decision_p95_ms"} <= set(out["e2e"])
    assert all(r["corr_iters"] <= 16 * 15 for r in run.window_recs)


def test_control_is_not_correct():
    out, _ = rehearse(control=True)
    assert out["checks"]["unsettled_links"][0] > 0
    assert not all(v <= lim for v, lim in out["checks"].values())


def test_dropped_half_burst_is_caught():
    out, _ = rehearse(break_service=half_burst)
    value, limit = out["checks"]["input_mismatch"]
    assert value > limit
