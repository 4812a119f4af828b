"""The monitor's chip benchmark: one run of one cell.

    python3 bench/run.py --workload grid80k.stream --seed 7 --seconds 10 \\
        --trace 0

Runs on the chip JAX finds and nowhere else: with no TPU, or fewer chips
than the cell asks for, it exits non-zero and prints no result.  The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``:
every number the check compared, beside its limit.

``--rehearse`` runs the configuration's tiny rehearsal sizes on any
platform (the CPU, here) and prints counts and checks only: no time or
device number.  ``--control`` runs the configuration's message-loss
control, which has to come out not correct.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)

    import jax

    from bench.harness import Bench, run_cell
    from repro.compile_cache import enable_compile_cache

    bench = Bench(ROOT)
    cell = bench.cell(args.workload)
    devs = jax.devices()
    print(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)} jax={jax.__version__}", file=sys.stderr)
    if not args.rehearse:
        if devs[0].platform != "tpu":
            print(f"no TPU: JAX runs on {devs[0].platform}", file=sys.stderr)
            return 2
        if len(devs) < cell["chips"]:
            print(f"{args.workload} needs {cell['chips']} chips, JAX sees "
                  f"{len(devs)}", file=sys.stderr)
            return 2
        peaks = bench.peaks(devs[0].device_kind)
    cache = enable_compile_cache()
    # Cache every program, however quick to compile, so that a run's
    # set-up after the first compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(f"compile cache: {cache}", file=sys.stderr)

    out, run = run_cell(bench, args.workload, args.seed, args.seconds,
                        bool(args.trace), t_start=T_START,
                        rehearse=args.rehearse, control=args.control)
    checks = out["checks"]
    print("set-up (s from start): " + ", ".join(
        f"{k} {v:.3f}" if isinstance(v, float) else f"{k} {v}"
        for k, v in out["setup_phases"].items())
        + f", window opens {out['setup_s']:.3f}", file=sys.stderr)
    print("window: " + ", ".join(
        f"{k} {v:.3f}" if isinstance(v, float) else f"{k} {v}"
        for k, v in out["window_diag"].items()), file=sys.stderr)
    correct = all(v <= lim for v, lim in checks.values())
    if out["window_compiles"]:
        print(f"{out['window_compiles']} compiles inside the window",
              file=sys.stderr)
        return 3
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"]}
    if args.rehearse:
        line["rehearsal"] = {k: out[k] for k in
                             ("window_ticks", "tenant_cycles", "links",
                              "shapes")}
    else:
        ctx = {"peaks": peaks, "shapes": out["shapes"]}
        metrics = {}
        kind = "per_layer" if args.trace else "end_to_end"
        for m in bench.metrics(args.workload, kind):
            if args.trace:
                value = bench.reader(m["name"]).read(run, ctx)
            else:
                value = out["e2e"].get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        line["metrics"] = metrics
        line["device"] = {"platform": devs[0].platform,
                          "kind": devs[0].device_kind, "count": len(devs),
                          "memory_peak_bytes": out["peak"]}
        if args.trace and run.trace is not None:
            from bench import tracefile

            print(f"trace: {tracefile.ticks_in(run.trace)} ticks, "
                  f"{sum(len(d['ops']) for d in run.trace['devices'])} "
                  f"device ops", file=sys.stderr)
            busy = tracefile.busy_ns(run.trace)
            if busy is not None:
                line["device"].update(busy_s=busy[0] / 1e9,
                                      window_s=busy[1] / 1e9)
            line["breakdown"] = tracefile.breakdown(run.trace)
    line["checks"] = {name: {"value": v, "limit": lim}
                      for name, (v, lim) in checks.items()}
    print("forgiven by the status error bound (no limit): " + ", ".join(
        f"{k} {v}" for k, v in out["forgiven"].items()), file=sys.stderr)
    for name, (v, lim) in checks.items():
        print(f"check {name}: {v} (limit {lim})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
