"""Benchmark driver — one function per paper table/figure + systems suites.

Prints ``name,us_per_call,derived`` CSV.  ``--full`` runs paper-scale
(slow); default sizes fit the CI budget; ``--smoke`` clamps every suite
to toy sizes (a does-it-still-run gate for CI).  ``--only fig2`` filters.

Machine-readable perf tracking: the systems suites (``JSON_SUITES``:
service, engine, controlplane, kernels, obs, async, comm) additionally
write
``BENCH_<suite>.json`` next to the working directory (``--json-dir`` to
relocate, ``--no-json`` to skip) with per-row extras (median wall-time,
msgs/link, peers/s, tracker overhead) so the perf trajectory is diffable
across PRs.

``--check`` turns the committed baselines into a regression gate: it runs
only the JSON suites, compares the fresh summary medians against the
``BENCH_*.json`` files in ``--json-dir`` (never overwriting them), and
exits non-zero on regression.  Wall-clock medians tolerate a
``--check-tolerance`` factor (default 3x — CI hosts vary); msgs/link is
deterministic for a fixed mode and compares at 1%, so a *semantic*
regression (the algorithm sending more messages) fails even when timing
noise would hide it.  Baselines must have been recorded in the same mode
(``--smoke``/default/``--full``) as the checking run.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

JSON_SUITES = ("service", "engine", "controlplane", "kernels", "obs",
               "async", "comm")

# Tracker overhead is budgeted absolutely (fraction of dispatch wall),
# not relative to a baseline: observability must stay cheap everywhere.
OBS_OVERHEAD_BUDGET = 0.05

# Overlap-mode budgets (async suite), absolute like the obs budget:
# overlap must hide at least half the host boundary, never cost wall
# time beyond noise, and the churning steady state must not recompile.
ASYNC_FRAC_RATIO_MIN = 2.0
ASYNC_WALL_RATIO_MIN = 0.9

# Halo wire-format budgets (comm suite), absolute: compression must
# actually shrink the boundary bytes, and must not cost wall time.  The
# wall gate only applies outside --smoke (at toy sizes fixed per-dispatch
# overheads dominate and the ratio is meaningless).
COMM_COMPACT_BYTES_MIN = 1.5
COMM_INT8_BYTES_MIN = 4.0
COMM_WIRE_WALL_MAX = 1.1


def _summary(rows) -> dict:
    med = lambda k: (statistics.median(r.extra[k] for r in rows
                                       if k in r.extra)
                     if any(k in r.extra for r in rows) else None)
    return {
        "median_us_per_call": statistics.median(r.us_per_call for r in rows)
        if rows else None,
        "median_msgs_per_link": med("msgs_per_link"),
        "median_peers_per_s": med("peers_per_s"),
        "median_overhead_frac": med("overhead_frac"),
        "median_host_frac_ratio": med("host_frac_ratio"),
        "median_wall_ratio": med("wall_ratio"),
        "median_recompiles": med("recompiles"),
        "median_compact_bytes_ratio": med("compact_bytes_ratio"),
        "median_int8_bytes_ratio": med("int8_bytes_ratio"),
        "median_wire_wall_ratio": med("wire_wall_ratio"),
    }


def _check_summary(suite: str, fresh: dict, baseline: dict,
                   tol: float) -> list:
    """Compare fresh vs baseline payloads; returns regression messages."""
    if baseline["mode"] != fresh["mode"]:
        return [f"{suite}: baseline mode {baseline['mode']!r} != fresh "
                f"mode {fresh['mode']!r} — regenerate the baseline with "
                "the same flags"]
    errors = []
    bs, fs = baseline["summary"], fresh["summary"]
    checks = (
        ("median_us_per_call", "wall"),
        ("median_peers_per_s", "rate"),
        ("median_msgs_per_link", "exact"),
        ("median_overhead_frac", "budget"),
    )
    for key, kind in checks:
        b, f = bs.get(key), fs.get(key)
        if kind == "budget":
            # Absolute bound — no baseline scaling, no tolerance factor.
            if f is not None and f > OBS_OVERHEAD_BUDGET:
                errors.append(f"{suite}.{key}: {f:.3f} exceeds the absolute "
                              f"{OBS_OVERHEAD_BUDGET:.0%} tracker-overhead "
                              "budget")
            continue
        if b is None or f is None:
            continue
        if kind == "wall" and f > b * tol:
            errors.append(f"{suite}.{key}: {f:.1f} > {tol:.1f}x baseline "
                          f"{b:.1f}")
        elif kind == "rate" and f < b / tol:
            errors.append(f"{suite}.{key}: {f:.1f} < baseline {b:.1f} / "
                          f"{tol:.1f}")
        elif kind == "exact" and abs(f - b) > 0.01 * max(abs(b), 1e-12):
            errors.append(f"{suite}.{key}: {f!r} differs from baseline "
                          f"{b!r} by >1% (deterministic metric — semantic "
                          "change?)")
    # Absolute overlap budgets (async suite; keys absent elsewhere).
    fr = fs.get("median_host_frac_ratio")
    if fr is not None and fr < ASYNC_FRAC_RATIO_MIN:
        errors.append(f"{suite}.median_host_frac_ratio: {fr:.2f}x < the "
                      f"absolute {ASYNC_FRAC_RATIO_MIN:.0f}x budget — "
                      "overlap no longer hides the host boundary")
    wr = fs.get("median_wall_ratio")
    if wr is not None and wr < ASYNC_WALL_RATIO_MIN:
        errors.append(f"{suite}.median_wall_ratio: {wr:.2f} < "
                      f"{ASYNC_WALL_RATIO_MIN} — overlap mode is slower "
                      "than the synchronous loop")
    rc = fs.get("median_recompiles")
    if rc is not None and rc > 0:
        errors.append(f"{suite}.median_recompiles: {rc} — the churning "
                      "steady state must stay zero-recompile")
    # Absolute wire-format budgets (comm suite; keys absent elsewhere).
    cb = fs.get("median_compact_bytes_ratio")
    if cb is not None and cb < COMM_COMPACT_BYTES_MIN:
        errors.append(f"{suite}.median_compact_bytes_ratio: {cb:.2f}x < "
                      f"the absolute {COMM_COMPACT_BYTES_MIN}x byte-"
                      "reduction budget for the lossless compact wire")
    ib = fs.get("median_int8_bytes_ratio")
    if ib is not None and ib < COMM_INT8_BYTES_MIN:
        errors.append(f"{suite}.median_int8_bytes_ratio: {ib:.2f}x < "
                      f"the absolute {COMM_INT8_BYTES_MIN}x byte-"
                      "reduction budget for the int8 wire")
    ww = fs.get("median_wire_wall_ratio")
    if (ww is not None and fresh["mode"] != "smoke"
            and ww > COMM_WIRE_WALL_MAX):
        errors.append(f"{suite}.median_wire_wall_ratio: {ww:.2f} > "
                      f"{COMM_WIRE_WALL_MAX} — compressed wires may not "
                      "cost wall time")
    return errors


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes: every suite must merely complete")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json-dir", default=".")
    ap.add_argument("--no-json", action="store_true")
    ap.add_argument("--check", action="store_true",
                    help="regression gate: compare the JSON suites against "
                         "the committed BENCH_*.json baselines and exit "
                         "non-zero on regression (baselines not rewritten)")
    ap.add_argument("--check-tolerance", type=float, default=3.0,
                    help="wall-clock/throughput regression factor tolerated "
                         "by --check (msgs/link always compares at 1%%)")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import common

    if args.smoke:
        common.SMOKE = True

    from . import (async_overlap, controlplane, engine_scaleup,
                   fig2_scaleup, fig3_connectivity, fig4_message_loss,
                   fig5_difficulty, fig6_dynamic_data, fig7_loss_dynamic,
                   fig8_churn, figD_ineffective, halo_wire, kernel_bench,
                   kernels, membership_churn, obs_overhead,
                   service_throughput)

    suites = {
        "fig2": fig2_scaleup, "fig3": fig3_connectivity,
        "fig4": fig4_message_loss, "fig5": fig5_difficulty,
        "fig6": fig6_dynamic_data, "fig7": fig7_loss_dynamic,
        "fig8": fig8_churn, "figD": figD_ineffective,
        "kernel": kernel_bench, "engine": engine_scaleup,
        "service": service_throughput, "membership": membership_churn,
        "controlplane": controlplane, "kernels": kernels,
        "obs": obs_overhead, "async": async_overlap,
        "comm": halo_wire,
    }
    if args.check:
        suites = {k: v for k, v in suites.items() if k in JSON_SUITES}
    mode = "smoke" if args.smoke else "full" if args.full else "default"
    regressions = []
    print("name,us_per_call,derived")
    for name, mod in suites.items():
        if args.only and args.only not in name:
            continue
        try:
            rows = list(mod.run(full=args.full))
        except Exception as e:  # noqa: BLE001
            print(f"{name}/ERROR,0,{e!r}", flush=True)
            raise
        for row in rows:
            print(row.csv(), flush=True)
        if name not in JSON_SUITES:
            continue
        payload = {
            "suite": name,
            "mode": mode,
            "rows": [r.json() for r in rows],
            "summary": _summary(rows),
        }
        path = os.path.join(args.json_dir, f"BENCH_{name}.json")
        if args.check:
            if not os.path.exists(path):
                regressions.append(f"{name}: no baseline at {path}")
                continue
            with open(path) as fh:
                baseline = json.load(fh)
            regressions += _check_summary(name, payload, baseline,
                                          args.check_tolerance)
        elif not args.no_json:
            os.makedirs(args.json_dir, exist_ok=True)
            with open(path, "w") as fh:
                json.dump(payload, fh, indent=2, default=str)
            print(f"# wrote {path}", file=sys.stderr)

    if args.check:
        if regressions:
            print("BENCH CHECK FAILED:", file=sys.stderr)
            for msg in regressions:
                print(f"  - {msg}", file=sys.stderr)
            sys.exit(1)
        print("# bench check passed (tolerance "
              f"{args.check_tolerance:.1f}x)", file=sys.stderr)


if __name__ == "__main__":
    main()
