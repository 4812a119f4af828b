"""The paper's algorithm on a TPU device mesh (the hardware adaptation).

Peers = devices; edges = ICI torus links along the chosen mesh axes; message
passing = ``lax.ppermute`` inside ``shard_map``.  Each device contributes a
statistic vector (grad-norm^2, loss, step-time, ...) with weight 1; LSS
maintains the device's status S_i; the output is ``f(vec(S_i))`` — the
region of the *global average* statistic, computed with **neighbor-local
traffic only** (no all-reduce, no global barrier chain).

Topology: a ring over one axis (D = 2 slots) or a 2-D torus over two axes
(D = 4).  A torus has cycles — which is exactly why the paper's new stopping
rule (and not the older cycle-free ones) is required here.

Differences from the P2P setting, per DESIGN.md §3: rounds are bulk-
synchronous (one bidirectional ppermute per axis per round); a peer whose
stopping rule holds sends a *masked* (ignored) payload — on ICI the bytes
still move, so the monitor reports both physical and *effective* message
counts, the latter matching the paper's accounting and the achievable DCN
saving across pods.

The update math is shared verbatim with the simulator
(:mod:`repro.core.stopping` / :mod:`repro.core.correction`): peers-as-
devices is just batch = 1 per shard.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import correction, regions as regions_lib, stopping, wvs

__all__ = ["MonitorConfig", "MonitorState", "MeshMonitor"]


class MonitorConfig(NamedTuple):
    beta: float = 1e-3
    rounds: int = 1  # LSS rounds per .step() call
    eps: float = 1e-9


class MonitorState(NamedTuple):
    out_m: jax.Array  # (n_peers, D, d) — sharded so each device holds 1 row
    out_c: jax.Array  # (n_peers, D)
    in_m: jax.Array
    in_c: jax.Array
    eff_sends: jax.Array  # (n_peers,) cumulative effective (unmasked) sends
    phys_sends: jax.Array  # (n_peers,) cumulative physical sends


def _ring_perm(n: int, shift: int):
    return [(i, (i + shift) % n) for i in range(n)]


class MeshMonitor:
    """LSS threshold monitor over one or two mesh axes.

    Args:
      mesh: the device mesh.
      axis_names: 1 axis -> ring (D=2); 2 axes -> 2-D torus (D=4).
      centers: (k, d) Voronoi option points (region family of Sec. V).
      cfg: MonitorConfig.
    """

    def __init__(self, mesh: Mesh, axis_names: Sequence[str], centers,
                 cfg: MonitorConfig = MonitorConfig()):
        if len(axis_names) not in (1, 2):
            raise ValueError("monitor runs on 1 (ring) or 2 (torus) axes")
        self.mesh = mesh
        self.axes = tuple(axis_names)
        self.centers = jnp.asarray(centers)
        self.cfg = cfg
        self.sizes = tuple(mesh.shape[a] for a in self.axes)
        self.n_peers = int(np.prod(self.sizes))
        self.D = 2 * len(self.axes)
        self.d = int(self.centers.shape[1])
        # Degenerate axes (size 1) have no distinct neighbors: mask them out.
        slot_ax = []
        for ax_i, sz in enumerate(self.sizes):
            slot_ax += [(ax_i, +1), (ax_i, -1)]
        self._slots = slot_ax
        self._slot_live = np.array(
            [self.sizes[ax] > 1 for ax, _ in slot_ax], dtype=bool
        )
        self._spec = P((*self.axes,))  # peers dim sharded over both axes

    # -- state ------------------------------------------------------------
    def init(self, dtype=jnp.float32) -> MonitorState:
        n, D, d = self.n_peers, self.D, self.d
        sh = NamedSharding(self.mesh, self._spec)
        z = functools.partial(jnp.zeros, dtype=dtype)
        return MonitorState(
            out_m=jax.device_put(z((n, D, d)), sh),
            out_c=jax.device_put(z((n, D)), sh),
            in_m=jax.device_put(z((n, D, d)), sh),
            in_c=jax.device_put(z((n, D)), sh),
            eff_sends=jax.device_put(z((n,)), sh),
            phys_sends=jax.device_put(z((n,)), sh),
        )

    def init_like(self, state: MonitorState) -> MonitorState:
        """Zeroed state with the same shapes/shardings (jit-safe reset)."""
        return jax.tree.map(jnp.zeros_like, state)

    # -- one monitor step (possibly several LSS rounds) --------------------
    def step(self, state: MonitorState, stat: wvs.WV):
        """Run ``cfg.rounds`` LSS rounds with local stat (n_peers, d).

        Returns (state', decision (n_peers,) int32, s_vec (n_peers, d)).
        Call inside jit; all comms are ppermute on the monitor axes.
        """
        spec = self._spec
        f = jax.shard_map(
            self._step_local,
            mesh=self.mesh,
            in_specs=(MonitorState(spec, spec, spec, spec, spec, spec),
                      wvs.WV(spec, spec)),
            out_specs=(MonitorState(spec, spec, spec, spec, spec, spec),
                       spec, spec),
            check_vma=False,
        )
        return f(state, stat)

    # -- device-local body --------------------------------------------------
    def _exchange(self, send_m, send_c):
        """Swap per-slot messages with torus neighbors via ppermute."""
        recv_m = jnp.zeros_like(send_m)
        recv_c = jnp.zeros_like(send_c)
        for k, (ax_i, sgn) in enumerate(self._slots):
            if not self._slot_live[k]:
                continue
            ax = self.axes[ax_i]
            n = self.sizes[ax_i]
            perm = _ring_perm(n, sgn)
            # My slot k (+1 => right neighbor). The right neighbor stores me
            # in its opposite slot (k^1).
            opp = k ^ 1
            got_m = jax.lax.ppermute(send_m[:, k], ax, perm)
            got_c = jax.lax.ppermute(send_c[:, k], ax, perm)
            recv_m = recv_m.at[:, opp].set(got_m)
            recv_c = recv_c.at[:, opp].set(got_c)
        return recv_m, recv_c

    def _step_local(self, state: MonitorState, stat: wvs.WV):
        cfg = self.cfg
        decide = lambda v: regions_lib.decide_voronoi(v, self.centers)
        live = jnp.broadcast_to(
            jnp.asarray(self._slot_live)[None, :], state.out_c.shape
        )
        x_m, x_c = stat.m, stat.c  # (1, d), (1,) block per device

        out_m, out_c = state.out_m, state.out_c
        in_m, in_c = state.in_m, state.in_c
        eff, phys = state.eff_sends, state.phys_sends

        for _ in range(cfg.rounds):
            s = stopping.status(x_m, x_c, out_m, out_c, in_m, in_c, live)
            a = stopping.agreements(out_m, out_c, in_m, in_c)
            viol = stopping.violations_alg1(decide, s, a, live, cfg.eps)
            # Selective correction, do-while unrolled to D iterations
            # (degree is tiny here).
            v = viol
            for _ in range(self.D):
                nm, nc = correction.corrected_messages(
                    s, a, in_m, in_c, v, cfg.beta, cfg.eps
                )
                om2 = jnp.where(v[..., None], nm, out_m)
                oc2 = jnp.where(v, nc, out_c)
                s2 = stopping.status(x_m, x_c, om2, oc2, in_m, in_c, live)
                a2 = stopping.agreements(om2, oc2, in_m, in_c)
                w = stopping.violations_alg1(decide, s2, a2, live, cfg.eps) & ~v
                v = v | w
            send = v & jnp.any(viol, axis=1)[:, None]
            nm, nc = correction.corrected_messages(
                s, a, in_m, in_c, send, cfg.beta, cfg.eps
            )
            out_m = jnp.where(send[..., None], nm, out_m)
            out_c = jnp.where(send, nc, out_c)
            eff = eff + jnp.sum(send, axis=1).astype(eff.dtype)
            phys = phys + jnp.sum(live, axis=1).astype(phys.dtype)
            # Bulk-synchronous exchange: everyone permutes; non-senders'
            # payloads are their previous out-message (idempotent at the
            # receiver), i.e. masked traffic.
            in_m, in_c = self._exchange(out_m, out_c)

        s = stopping.status(x_m, x_c, out_m, out_c, in_m, in_c, live)
        decision = decide(wvs.vec(s, cfg.eps))
        new_state = MonitorState(out_m, out_c, in_m, in_c, eff, phys)
        return new_state, decision, wvs.vec(s, cfg.eps)
