import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: the 512
placeholder host devices let ``jax.make_mesh`` build the production meshes,
``jit(step).lower(**ShapeDtypeStructs)`` + ``.compile()`` exercise the SPMD
partitioner end-to-end, and the compiled artifact yields the roofline terms
(FLOPs, bytes from ``cost_analysis``; collective bytes parsed from the
HLO text; per-device memory from ``memory_analysis``).

Usage:
  python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
Results are JSON per cell (resumable: existing files are skipped).
"""

import argparse
import json
import pathlib
import re
import sys
import time
import traceback

import jax
import numpy as np

from repro import configs
from repro.compile_cache import enable_compile_cache
from repro.launch import hlo_cost
from repro.launch.mesh import make_production_mesh
from repro.models import build
from repro.training.steps import build_for_cell

# v5e-class hardware constants for the roofline (per chip).
PEAK_FLOPS = 197e12  # bf16
HBM_BW = 819e9  # bytes/s
ICI_BW = 50e9  # bytes/s/link (per DESIGN.md; ~4 links/chip on a 2D torus)

_COLL_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[^\n=]*=\s*([a-z0-9]+)\[([0-9,]*)\]"
)
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}


def collective_bytes(hlo_text: str):
    """Sum output-operand sizes of collective ops in an HLO dump."""
    totals = {}
    for m in _COLL_RE.finditer(hlo_text):
        op, dtype, dims = m.group(1), m.group(2), m.group(3)
        nbytes = _DTYPE_BYTES.get(dtype)
        if nbytes is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        totals[op] = totals.get(op, 0) + n * nbytes
    totals["total"] = sum(v for k, v in totals.items() if k != "total")
    return totals


def model_flops(cfg, cell) -> float:
    """6*N*D for train (N = active params), 2*N*D for inference."""
    try:
        n_active = cfg.active_param_count()
    except AttributeError:
        n_active = cfg.param_count()
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * n_active * tokens
    if cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * cell.global_batch  # decode: one token per seq


def run_cell(arch_id: str, shape_name: str, multi_pod: bool):
    cell = next(s for s in configs.SHAPES if s.name == shape_name)
    skip = configs.skip_reason(arch_id, shape_name)
    if skip:
        return {"arch": arch_id, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": skip}

    cfg = configs.get(arch_id)
    model = build(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))

    # Gradient accumulation: keep the live microbatch at 2 seqs/replica so
    # activations fit HBM on the big archs (see TrainHParams.accum_steps).
    from repro.training.steps import TrainHParams
    dp = int(np.prod([mesh.shape[a] for a in ("pod", "data")
                      if a in mesh.axis_names]))
    accum = max(1, (cell.global_batch // dp) // 2) if cell.kind == "train" else 1
    hp = TrainHParams(accum_steps=accum)

    t0 = time.time()
    with mesh:
        jitted, in_sh, out_sh, input_specs = build_for_cell(model, mesh, cell,
                                                            hp)
        args = input_specs()
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t1

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()

    # XLA's cost_analysis counts while bodies ONCE (scanned layers vanish);
    # hlo_cost re-walks the module with loop-trip multipliers.
    walked = hlo_cost.analyze(hlo)
    flops = walked["flops"]
    bytes_acc = walked["hbm_bytes"]
    colls = walked["collective_bytes"]
    xla_flops = float(cost.get("flops", 0.0)) if cost else 0.0
    mflops = model_flops(cfg, cell)

    # Roofline terms (seconds) — per-device SPMD program numbers.
    t_compute = flops / PEAK_FLOPS
    t_memory = bytes_acc / HBM_BW
    t_coll = colls.get("total", 0) / ICI_BW
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dominant = max(terms, key=terms.get)

    rec = {
        "arch": arch_id,
        "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "status": "ok",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_acc,
        "xla_cost_analysis_flops": xla_flops,  # while-body-once; reference
        "collective_bytes_per_device": colls,
        "model_flops_global": mflops,
        "model_flops_per_device": mflops / n_chips,
        "useful_flops_ratio": (mflops / n_chips) / flops if flops else None,
        "roofline": terms,
        "dominant": dominant,
        "step_time_bound_s": max(terms.values()),
        "memory_analysis": {
            k: getattr(mem, k)
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
            if mem is not None and hasattr(mem, k)
        },
    }
    if rec["memory_analysis"]:
        ma = rec["memory_analysis"]
        rec["bytes_per_device"] = (ma.get("argument_size_in_bytes", 0)
                                   + ma.get("temp_size_in_bytes", 0))
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args(argv)
    # The persistent compilation cache speeds up re-lowers.
    enable_compile_cache()

    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    archs = configs.ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = ([s.name for s in configs.SHAPES]
              if (args.all or not args.shape) else [args.shape])
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'multi' if mp else 'single'}"
                path = outdir / f"{tag}.json"
                if path.exists():
                    print(f"[skip existing] {tag}", flush=True)
                    continue
                print(f"[dryrun] {tag} ...", flush=True)
                try:
                    rec = run_cell(arch, shape, mp)
                except Exception as e:  # noqa: BLE001 — report and continue
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "multi" if mp else "single",
                           "status": "error", "error": repr(e),
                           "traceback": traceback.format_exc()}
                    failures += 1
                path.write_text(json.dumps(rec, indent=2, default=str))
                status = rec["status"]
                extra = ""
                if status == "ok":
                    extra = (f" dominant={rec['dominant']}"
                             f" bound={rec['step_time_bound_s']:.4f}s"
                             f" compile={rec['compile_s']}s")
                print(f"[done] {tag}: {status}{extra}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
