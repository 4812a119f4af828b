"""The trace -> metrics reduction, on a hand-made trace and on one tick of
a recorded TPU v5e trace of ``grid80k.stream`` (``data/``)."""

import gzip
import json
import pathlib
import types

import pytest

from bench import harness, tracefile
from bench.metrics import bytemodel

DATA = pathlib.Path(__file__).parent / "data"
LSS = ('%lss_state.6 = (f32[16,2,80089]) custom-call(...), '
       'custom_call_target="tpu_custom_call"')
CORR = ('%vmap_jit_correction__.13 = (f32[16,4,2,80089]) custom-call(...), '
        'custom_call_target="tpu_custom_call"')


def toy():
    return {"devices": [{
        "ops": [["%while.1 = (s32[])", 10, 60],
                [LSS, 12, 20],
                ["%fusion.7 = f32[8]", 20, 30],
                [CORR, 30, 40],
                ["%custom-call.3 = s32[16] custom-call(), "
                 "custom_call_target=\"AllocateBuffer\"", 40, 42],
                ["%fusion.7 = f32[8]", 70, 80]],
        "modules": [["jit__step_impl(1)", 10, 60],
                    ["jit__observe_impl(2)", 70, 80]]}],
        "host": [["bench.tick", 0, 100],
                 ["$service.py:1336 _apply_ingest", 0, 10],
                 ["$array.py:631 _value", 60, 70],
                 ["$service.py:1534 _finish_window", 55, 100]]}


def test_toy_reduction_by_hand():
    t = toy()
    assert tracefile.window(t) == (0, 100)
    assert tracefile.busy_ns(t) == (60, 100)  # [10, 60] and [70, 80]
    assert tracefile.module_ns(t, "jit__step_impl") == 50
    assert tracefile.kernel_ns(t, "lss_state") == (8, 1)
    assert tracefile.kernel_ns(t, "correction") == (10, 1)
    assert tracefile.all_kernels_ns(t) == 18  # AllocateBuffer is XLA's
    b = tracefile.breakdown(t)
    assert b["device_ops"][0] == ["fusion.7", 20e-9]
    # idle [0, 10] under _apply_ingest, [60, 70] under _value, [80, 100]
    # under _finish_window
    assert dict(b["idle_gaps"]) == {
        "service.py:1336 _apply_ingest": 10e-9,
        "array.py:631 _value": 10e-9,
        "service.py:1534 _finish_window": 20e-9}


def test_toy_metrics_through_readers():
    bench = harness.Bench()
    run = types.SimpleNamespace(trace=toy())
    ctx = {"peaks": {"hbm_bytes_per_s": 819e9},
           "shapes": {"q": 1, "n": 10, "D": 2, "d": 2}}
    assert bench.reader("step_ms").read(run, ctx) == 50 / 1e6
    assert bench.reader("device_idle_share").read(run, ctx) == \
        pytest.approx(40.0)
    assert bench.reader("xla_share").read(run, ctx) == pytest.approx(
        100 * (50 - 18) / 50)
    want = 100 * bytemodel.lss_state_bytes(1, 10, 2, 2) / 819e9 / 8e-9
    assert bench.reader("lss_state_roofline").read(run, ctx) == \
        pytest.approx(want)


def test_readers_find_nothing_without_a_trace():
    bench = harness.Bench()
    run = types.SimpleNamespace(trace=None)
    for name in ("step_ms", "device_idle_share", "xla_share",
                 "lss_state_roofline", "correction_roofline"):
        assert bench.reader(name).read(run, {}) is None


@pytest.fixture(scope="module")
def recorded():
    return json.loads(gzip.decompress(
        (DATA / "trace_grid_tick.json.gz").read_bytes()))


def test_recorded_tick(recorded):
    t = recorded
    assert tracefile.ticks_in(t) == 1
    lo, hi = tracefile.window(t)
    ops = tracefile.clip(t["devices"][0]["ops"], lo, hi)
    busy, span = tracefile.busy_ns(t)
    assert span == hi - lo
    assert busy <= sum(e - s for _, s, e in ops)
    assert 0.9 < busy / span <= 1.0  # one grid tick: the step fills it
    step = tracefile.module_ns(t, "jit__step_impl")
    assert step == sum(e - s for n, s, e in t["devices"][0]["modules"]
                       if n.startswith("jit__step_impl"))
    assert step <= busy
    for kernel in ("lss_state", "correction"):
        ns, runs = tracefile.kernel_ns(t, kernel)
        assert runs == sum(1 for n, _, _ in ops
                           if kernel in n.split(" = ")[0]
                           and "tpu_custom_call" in n)
        assert runs >= 16  # at least once per cycle, 16 cycles
    assert tracefile.all_kernels_ns(t) == sum(
        tracefile.kernel_ns(t, k)[0] for k in ("lss_state", "correction"))


def test_recorded_shares_stay_under_peak(recorded):
    bench = harness.Bench()
    run = types.SimpleNamespace(trace=recorded)
    ctx = {"peaks": bench.peaks("TPU v5 lite"),
           "shapes": {"q": 16, "n": 80089, "D": 4, "d": 2}}
    for name in ("lss_state_roofline", "correction_roofline", "xla_share",
                 "device_idle_share"):
        value = bench.reader(name).read(run, ctx)
        assert 0 < value < 100, (name, value)
    b = tracefile.breakdown(recorded)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0].startswith("fusion")  # the gather


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        harness.Bench().peaks("TPU v9 imaginary")


def test_ticks_fall_back_to_step_runs():
    t = toy()
    t["host"] = [ev for ev in t["host"] if ev[0] != tracefile.TICK]
    assert tracefile.ticks_in(t) == 1
    assert tracefile.window(t) == (10, 60)
    assert tracefile.busy_ns(t) == (50, 50)
