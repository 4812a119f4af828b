"""The harness end to end on the CPU at the configurations' rehearsal
sizes: each cell's run comes out correct, a configuration, a mix and a
metric added as files are found by name, and the command refuses to run
without a chip."""

import json
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]


def rehearse(bench, workload, seed=2 ** 31 + 5, **kw):
    return harness.run_cell(bench, workload, seed, 0.5, False,
                            t_start=time.perf_counter(), rehearse=True, **kw)


@pytest.mark.parametrize("workload", ["grid80k.stream", "grid80k.steady"])
def test_rehearsal_is_correct(workload):
    out, run = rehearse(harness.Bench(), workload)
    for name, (value, limit) in out["checks"].items():
        assert value <= limit, (name, value)
    assert out["window_compiles"] == 0
    spared = out["forgiven"]
    assert spared["states"] > 0 and spared["most"] <= spared["total"]
    assert out["tenant_cycles"] > 0 and out["attempted"] > 0
    assert out["failed"] == 0
    if workload.endswith(".stream"):
        assert {"decision_p50_ms", "decision_p95_ms"} <= set(out["e2e"])
        assert run.corr_iters[1][1] > run.corr_iters[0][1]


def test_added_files_are_found_by_name(tmp_path):
    """A config, a mix and a metric that a later change adds as files and
    entries: the harness runs them with no edit of its own."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/wsn-grid-80k.json").read_text())
    cfg["name"] = "tmp-grid"
    cfg["rehearsal"] = {"topology": {"side": 9},
                        "service": {"capacity": 2}}
    (tmp_path / "bench/configs/tmp-grid.json").write_text(json.dumps(cfg))
    mix = json.loads((ROOT / "bench/traffic/stream.json").read_text())
    mix["burst_fraction"] = 0.05
    (tmp_path / "bench/traffic/tmp_mix.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/tmp_window_ticks.py").write_text(
        "def read(run, ctx):\n    return float(len(run.window_ticks))\n")
    spec["configs"].append({"name": "tmp-grid", "source": "test",
                            "file": "bench/configs/tmp-grid.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tmp.cell", "config": "tmp-grid",
                              "traffic": "tmp_mix", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "tmp_window_ticks", "unit": "ticks",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "tenant_cycles_per_s",
                              "workloads": ["tmp.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    bench = harness.Bench(tmp_path)
    out, run = rehearse(bench, "tmp.cell")
    assert out["shapes"] == {"q": 2, "n": 81, "D": 4, "d": 2, "k": 16}
    assert len(run.bursts) and all(
        len(generator_burst) for generator_burst in run.bursts)
    names = [m["name"] for m in bench.metrics("tmp.cell", "per_layer")]
    assert "tmp_window_ticks" in names
    assert bench.reader("tmp_window_ticks").read(run, {}) == \
        len(run.window_ticks)
    assert all(v <= lim for v, lim in out["checks"].values())


NEW_MIXES = {
    # tenants admitted and retired through the admission queue in the
    # window: twice as many tenants as slots, each retired at quiescence
    "churn": {"tenants_per_slot": 2, "retire_after_ticks": 1},
    # bursts arriving on the host clock, not per tick
    "wall": {"arrivals": "wall", "bursts_per_tick": 0, "bursts_per_s": 20},
}


@pytest.mark.parametrize("shape", sorted(NEW_MIXES))
def test_new_traffic_shapes_are_data_only(tmp_path, shape):
    """A mix of another shape than the cells' is one data file and one
    entry: the harness admits and retires tenants mid-window, or pushes
    bursts as they arrive on the clock, with no edit of its own, and the
    run comes out correct."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    mix = json.loads((ROOT / "bench/traffic/stream.json").read_text())
    mix.update(NEW_MIXES[shape])
    (tmp_path / f"bench/traffic/tmp_{shape}.json").write_text(
        json.dumps(mix))
    spec["workloads"].append({"name": "tmp.cell", "config": "wsn-grid-80k",
                              "traffic": f"tmp_{shape}", "chips": 1,
                              "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    out, run = harness.run_cell(harness.Bench(tmp_path), "tmp.cell",
                                2 ** 31 + 21, 3.0, False,
                                t_start=time.perf_counter(), rehearse=True)
    assert all(v <= lim for v, lim in out["checks"].values()), out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["window_compiles"] == 0
    first = {}
    for dsp, r, _ in run.records:
        first.setdefault(r["query"], dsp)
    if shape == "churn":
        assert sum(dsp in run.window_ticks for dsp in first.values()) > 0
    else:
        applied = [b[2] for b in run.bursts if b[2] >= min(run.window_ticks)]
        assert abs(len(applied) - 3.0 * 20) <= 2
        assert set(run.window_ticks) - set(applied)  # ticks with none


def _cli(cwd, *args):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "grid80k.stream",
         "--seed", "1", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(cwd)})


def test_no_chip_no_result():
    out = _cli(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    out = _cli(tmp_path, "--rehearse")
    assert out.returncode != 0
    assert out.stdout.strip() == ""
