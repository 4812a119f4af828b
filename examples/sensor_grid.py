"""Wireless-sensor-network scenario (the paper's third target system).

A 48x48 grid of sensors tracks which of k "sources" is closest to the
fleet-average reading while (a) readings drift, (b) 2% of messages are
lost, and (c) sensors die.  The LSS algorithm keeps ~99% of live sensors
correct with a fraction of a message per link per cycle — the in-network
alternative to convergecast or gossip.

    PYTHONPATH=src python examples/sensor_grid.py
"""

import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import lss, sim, topology

enable_compile_cache()

n = 48 * 48
topo = topology.grid(n)
spec = sim.ProblemSpec(n=n, k=3, d=2, bias=0.2, std=2.0, seed=7)

print(f"{n} sensors, 2% message loss, data drift 1000 ppmc, churn 100 ppmc")
res = sim.run_dynamic(
    topo, spec,
    lss.LSSConfig(drop_rate=0.02),
    cycles=400,
    noise_ppmc=1000.0,
    churn_ppmc=100.0,
    warmup=100,
)
print(f"average accuracy over live sensors : {res['avg_accuracy']*100:6.2f}%")
print(f"messages per link per cycle        : "
      f"{res['msgs_per_link_per_cycle']:.3f}  (paper's normalized messaging)")
print(f"sensors still alive at the end     : {res['alive_frac']*100:6.1f}%")
