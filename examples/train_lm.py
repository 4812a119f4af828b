"""End-to-end training driver: LM + full substrate + the paper as monitor.

Runs the production train step (sharded, donated, accumulated), the
deterministic data pipeline, async checkpointing with exact resume, and an
LSS mesh-monitor divergence guard — the paper's thresholding as a
first-class training service.

    PYTHONPATH=src python examples/train_lm.py --steps 200          # ~8M CI run
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300

On a real pod this script is launched per-host unchanged; the mesh comes
from repro.launch.mesh.make_production_mesh instead of the host mesh.
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as cfgs
from repro.configs import ShapeCell
from repro import checkpoint
from repro.compile_cache import enable_compile_cache
from repro.core import monitor as monitor_lib
from repro.core import wvs
from repro.data import TokenSource
from repro.models import build
from repro.models.transformer import LMConfig
from repro.optim import adamw_init
from repro.training.steps import TrainHParams, build_for_cell

PRESETS = {
    # ~8M params: CI-friendly.
    "tiny": LMConfig(name="tiny", n_layers=4, d_model=256, vocab=4096,
                     n_heads=4, n_kv=2, d_head=64, d_ff=1024, block="dense",
                     remat=False, fsdp=False, dtype=jnp.float32),
    # ~100M params: the deliverable-scale run (use on real hardware).
    "100m": LMConfig(name="lm100m", n_layers=12, d_model=768, vocab=32_768,
                     n_heads=12, n_kv=4, d_head=64, d_ff=3072, block="dense",
                     remat=True, fsdp=False, dtype=jnp.bfloat16),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny", choices=PRESETS)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default="/tmp/repro_train_lm")
    ap.add_argument("--arch", default=None,
                    help="train an assigned arch's smoke config instead")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = cfgs.get_smoke(args.arch) if args.arch else PRESETS[args.preset]
    model = build(cfg)
    n_dev = len(jax.devices())
    mesh = jax.make_mesh((n_dev, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cell = ShapeCell("train", "train", args.seq, args.batch)
    hp = TrainHParams(lr=args.lr, warmup=20, total_steps=args.steps)

    with mesh:
        step, _, _, _ = build_for_cell(model, mesh, cell, hp)
        params = model.init(jax.random.PRNGKey(0))
        opt = adamw_init(params)
        n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
        print(f"model={cfg.name} params={n_params/1e6:.1f}M "
              f"devices={n_dev} batch={args.batch}x{args.seq}")

        src = TokenSource(vocab=cfg.vocab, seq_len=args.seq,
                          global_batch=args.batch, seed=0)

        # LSS divergence guard: options {healthy, diverged} on the loss axis.
        div_thresh = float(np.log(cfg.vocab)) + 2.0
        mon = monitor_lib.MeshMonitor(
            mesh, ("data",), jnp.array([[div_thresh - 1.0], [div_thresh + 1.0]]),
            monitor_lib.MonitorConfig(rounds=1))
        mon_state = mon.init()
        mon_step = jax.jit(mon.step)

        start = checkpoint.latest_step(args.ckpt)
        if start is not None:
            params, opt = checkpoint.load(args.ckpt, start, (params, opt))
            print(f"resumed from step {start}")
        start = start or 0

        t0 = time.perf_counter()
        for s in range(start, args.steps):
            b = src.global_batch_at(s)
            params, opt, m = step(params, opt, {"tokens": b.tokens,
                                                "labels": b.labels})
            loss = float(m["loss"])
            stat = wvs.from_vector(
                jnp.full((mon.n_peers, 1), loss), jnp.ones((mon.n_peers,)))
            mon_state, decision, _ = mon_step(mon_state, stat)
            diverged = bool(jnp.any(decision == 1))
            if s % 20 == 0 or s == args.steps - 1:
                dt = (time.perf_counter() - t0) / max(s - start + 1, 1)
                tok_s = args.batch * args.seq / dt
                print(f"step {s:4d}  loss={loss:7.4f}  gnorm={float(m['gnorm']):6.2f}  "
                      f"lr={float(m['lr']):.2e}  {tok_s:9.0f} tok/s  "
                      f"monitor={'DIVERGED' if diverged else 'healthy'}")
            if s and s % 100 == 0:
                checkpoint.save_async(args.ckpt, s, (params, opt))
        checkpoint.save(args.ckpt, args.steps, (params, opt))
        checkpoint.wait_pending()
        print("done; checkpoint at", args.ckpt)


if __name__ == "__main__":
    main()
