"""Quickstart: the paper's algorithm in ~40 lines.

1000 peers on a *cyclic* grid pick, with purely local messages, the option
closest to the global average of their inputs — no coordinator, no
all-to-all, no spanning tree.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import lss, topology, wvs

enable_compile_cache()

n = 1024
topo = topology.grid(n)                      # 32x32 grid: full of cycles
ta = lss.TopoArrays.from_topology(topo)

# Three options ("sources", Sec. V); peers vote with noisy 2-D inputs whose
# true mean is nearest to option 1.
centers = jnp.array([[0.0, 0.0], [2.0, 2.0], [4.0, 0.0]])
rng = np.random.default_rng(0)
inputs = rng.normal(loc=(1.8, 1.9), scale=1.5, size=(n, 2)).astype(np.float32)

state = lss.init_state(ta, wvs.from_vector(jnp.asarray(inputs),
                                           jnp.ones((n,))))
cfg = lss.LSSConfig(beta=1e-3, ell=1)

for cycle in range(200):
    state, sent = lss.cycle(state, ta, centers, cfg)
    acc, quiescent, _ = lss.metrics(state, ta, centers)
    if cycle % 5 == 0 or quiescent:
        print(f"cycle {cycle:3d}  accuracy={float(acc):6.3f}  "
              f"msgs so far={int(state.msgs):6d}  quiescent={bool(quiescent)}")
    if quiescent:
        break

gx = inputs.mean(0)
true_choice = int(np.argmin(((gx - np.asarray(centers)) ** 2).sum(-1)))
print(f"\nglobal mean = {gx.round(3)} -> true option {true_choice}; "
      f"all {n} peers agree, using "
      f"{float(state.msgs) / topo.num_edges:.2f} messages per link.")
