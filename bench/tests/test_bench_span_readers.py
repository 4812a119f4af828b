"""The readers of the service's own spans, on a hand-made run (a tracker
holding set-up and window ticks, and a two-tick trace) and on one tick of
``grid80k.stream`` recorded on a TPU v5 lite with the service's ``repro.*``
annotations (``data/trace_grid_tick_scoped.json.gz``; its ``scopes`` map
each ``jit__step_impl`` instruction to its HLO ``op_name``)."""

import gzip
import json
import pathlib
import types

import pytest

from bench import harness, tracefile

DATA = pathlib.Path(__file__).parent / "data"
BOUNDARY = ("membership_drain", "admission_drain", "ingest_apply")


def span(name, sid, parent, start, seconds, **attrs):
    return types.SimpleNamespace(name=name, span_id=sid, parent_id=parent,
                                 start=start, seconds=seconds, attrs=attrs)


def toy_run():
    """Ticks 4 (set-up), 5 and 6 (window, traced).  On the trace's clock a
    tick span's start lies 99.998 s before its ``bench.tick``."""
    spans = []
    for sid, (dsp, start) in enumerate([(4, 99.4), (5, 100.0), (6, 100.5)]):
        tick = 10 * (sid + 1)
        spans += [
            span("tick", tick, None, start, 0.49, dispatch=dsp),
            span("membership_drain", tick + 1, tick, start,
                 [0.003, 0.0005, 0.0005][sid]),
            span("admission_drain", tick + 2, tick, start + 0.0005, 0.0005,
                 activations=0),
            span("ingest_apply", tick + 3, tick, start + 0.001, 0.004,
                 waited=[1, 1, 2][sid], wait_s=[5.0, 2e-4, 6e-4][sid]),
            span("dispatch", tick + 4, tick, start + 0.005, 0.001),
            span("observe", tick + 5, tick, start + 0.006, 0.4),
            span("observe_emit", tick + 6, tick, start + 0.406,
                 [1.0, 0.002, 0.004][sid], dispatch=dsp),
        ]
    trace = {"devices": [{"ops": [["%fusion.1 = f32[8]", 0, 3_500_000],
                                  ["%fusion.2 = f32[8]", 6_000_000,
                                   503_000_000],
                                  ["%fusion.3 = f32[8]", 505_000_000,
                                   1_000_000_000]],
                          "modules": []}],
             "host": [["bench.tick", 2_000_000, 500_000_000],
                      ["bench.tick", 502_000_000, 1_000_000_000]]}
    return types.SimpleNamespace(
        tracker=types.SimpleNamespace(spans=spans), window_ticks=[5, 6],
        trace=trace)


# Each by hand.  boundary_idle_ms: tick 5's ingest_apply lies at [3, 7] ms
# on the trace, busy on [3, 3.5] and [6, 7]: 2.5 ms idle; tick 6's at
# [503, 507] ms, busy on [505, 507]: 2 ms; the drains lie in busy time.
# observe_emit_ms: (2 + 4) ms over 2 ticks.  ingest_wait_ms: (0.2 + 0.6)
# ms over 1 + 2 batches.  Tick 4 is set-up and counts nowhere (its longer
# membership drain would add idle if it were taken for a traced tick).
@pytest.mark.parametrize("metric, want", [
    ("boundary_idle_ms", 2.25),
    ("observe_emit_ms", 3.0),
    ("ingest_wait_ms", 0.8 / 3),
])
def test_span_readers_by_hand(metric, want):
    value = harness.Bench().reader(metric).read(toy_run(), {})
    assert value == pytest.approx(want)


@pytest.mark.parametrize("metric", ["boundary_idle_ms", "observe_emit_ms",
                                    "ingest_wait_ms"])
def test_span_readers_find_nothing_in_an_older_program(metric):
    """A program without ``observe_emit``, ingest stamps or ``Span.start``
    gives no value, and no error."""
    run = toy_run()
    for sp in run.tracker.spans:
        del sp.start
        sp.attrs.pop("waited", None)
        sp.attrs.pop("wait_s", None)
    run.tracker.spans = [sp for sp in run.tracker.spans
                         if sp.name != "observe_emit"]
    assert harness.Bench().reader(metric).read(run, {}) is None


def test_boundary_idle_on_a_recorded_scoped_tick():
    """Given the tracker's view of the recorded tick's spans, on a clock of
    its own, the reader finds the device idle that the ``repro.*``
    annotations show on the trace's clock."""
    with gzip.open(DATA / "trace_grid_tick_scoped.json.gz", "rt") as f:
        trace = json.load(f)
    host = {n: (s, e) for n, s, e in trace["host"]}
    t0 = host["repro.tick"][0]

    def at(name):
        s, e = host["repro." + name]
        return 4000.0 + (s - t0) / 1e9, (e - s) / 1e9

    spans = [span("tick", 1, None, *at("tick"), dispatch=9)] + [
        span(name, i + 2, 1, *at(name)) for i, name in enumerate(BOUNDARY)]
    run = types.SimpleNamespace(
        trace=trace, tracker=types.SimpleNamespace(spans=spans),
        window_ticks=[9])
    busy = tracefile.union(trace["devices"][0]["ops"])
    want = 0.0
    for name in BOUNDARY:
        lo, hi = host["repro." + name]
        want += (hi - lo) - sum(max(0, min(e, hi) - max(s, lo))
                                for s, e in busy)
    want /= 1e6
    assert want > 10  # a stream tick's ingest: the chip waits for the host
    got = harness.Bench().reader("boundary_idle_ms").read(run, {})
    assert got == pytest.approx(want, abs=0.1)
