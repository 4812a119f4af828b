"""The sharded engine + vmapped sweeps in ~50 lines.

Same computation as quickstart.py, but:
  * the 4096 peers are partitioned over 4 shards (BFS edge-cut), boundary
    messages travel through the halo exchange, and 10 cycles run per jit
    dispatch — the execution shape that scales to millions of peers on a
    device mesh;
  * then a 5-seed scenario sweep runs as ONE vmapped dispatch and prints
    the paper's "cycles to 95%" statistic across trials.

    PYTHONPATH=src python examples/engine_demo.py
"""

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import lss, sim, topology
from repro.engine import EngineConfig, ShardedLSS, sweep_static
from repro.engine.sweep import cycles_to_accuracy

enable_compile_cache()

n = 4096
topo = topology.grid(n)  # 64x64 grid, full of cycles
spec = sim.ProblemSpec(n=n, seed=0)

# --- sharded engine -------------------------------------------------------
res = sim.run_static(
    topo, spec, max_cycles=300,
    engine=EngineConfig(num_shards=4, cycles_per_dispatch=10),
)
print(f"engine: {res['engine_shards']} shards, "
      f"{res['cut_edges']}/{topo.num_edges} edges cut by the partition")
print(f"quiesced at cycle {res['quiesced_at']} "
      f"(accuracy {res['final_accuracy']:.3f}), "
      f"{res['msgs_per_link']:.2f} messages per link\n")

# --- vmapped scenario sweep ----------------------------------------------
seeds = [0, 1, 2, 3, 4]
sweep = sweep_static(topo, spec, seeds, cycles=120)
c95 = cycles_to_accuracy(sweep["accuracy"], 0.95)
c100 = cycles_to_accuracy(sweep["accuracy"], 1.0)
print(f"sweep over seeds {seeds} (one vmapped dispatch):")
print(f"  cycles to 95%:  {c95.tolist()}  (mean {np.mean(c95):.1f})")
print(f"  cycles to 100%: {c100.tolist()}")
print(f"  msgs/link at end: "
      f"{(sweep['msgs'][:, -1] / sweep['num_edges']).round(2).tolist()}")
