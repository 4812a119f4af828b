"""The service driver: Q query slots, one vmapped jit dispatch per K cycles.

Execution model::

    admit (or queue) / retire --+                +--> telemetry (JSONL)
    membership joins/leaves ----+--> [boundary] -+
    stream updates -------------+        |   ^
                                         v   |
                        one jit dispatch: fori_loop of K cycles,
                        vmap over Q query slots (core backend), or
                        vmap over Q x ShardedLSS cycle (engine backend)

All Q queries advance in lockstep through ONE compiled program; the query
axis is a plain ``vmap`` over :func:`repro.core.lss.cycle_impl` (or
:meth:`repro.engine.ShardedLSS._cycle_full`) with per-query traced region
parameters, traced ``beta``/``ell``/``eps`` knobs, and the active-slot
gate.  Masked (free) slots ride along as no-ops that send zero messages.
State buffers are donated to the dispatch off-CPU, so the K-cycle block
updates in place like the engine's run loop.

The shared topology is threaded through every jitted program as a traced
*argument* (never a closed-over constant): built on a
:class:`~repro.core.topology.DynTopology`, the service applies queued
membership events (:class:`~repro.service.membership.MembershipQueue`)
at dispatch boundaries — joins/leaves/rewires within the topology's
capacity swap in same-shaped table data and therefore never recompile
the dispatch, while in-flight tenants keep converging (joining peers
start from the paper's knowledge-init state).

With ``ServiceConfig(overlap=True)`` the tick is re-cut around jax's
async dispatch (:mod:`repro.service.overlap`): the host boundary for
dispatch K+1 runs while dispatch K still occupies the device, K's
telemetry syncs one tick later as a :class:`PendingWindow`, and epoch
rebuilds stage on a background thread, swapping in at a boundary.
Record content is bitwise identical to sync mode — only emission is
deferred by one tick (:meth:`Service.flush` drains the tail).

The **control plane** (:mod:`repro.service.controlplane`) runs on top of
the same boundaries: per-tenant SLO evaluation folded into every
telemetry record, a pluggable admission/preemption scheduler when the Q
slots are contended (preempted queries are snapshotted core-layout —
partition independent — and resume bitwise where they stopped), and the
capacity epochs — auto-regrow on membership-capacity exhaustion and
drift-triggered partition rebalance.  Steady-state serving stays
zero-recompile; only the explicit epochs change traced shapes (regrow)
or rebuild engine tables (rebalance), and each recompiles at most once.
"""

from __future__ import annotations

import os
import time
from typing import Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lss, regions, topology, wvs
from repro.kernels import suite as kernel_suite
from repro.obs import AlertEngine, FlightRecorder, Tracker, jit_cache_size
from repro.obs import audit as obs_audit
from repro.obs import metrics as obs_metrics

from . import query as qmod
from .admission import AdmissionQueue
from .controlplane import (ActiveView, CapacityManager, ControlPlaneConfig,
                           SLOEvictionPolicy, SLOTracker, WaitingView,
                           make_scheduler)
from .ingest import StreamIngest, UpdateBatch
from .membership import MembershipQueue
from .overlap import CorrStats, DoubleBuffer, PendingWindow, StagedBuild
from .registry import QueryRegistry
from .telemetry import TelemetrySink

__all__ = ["ServiceConfig", "Service"]


class ServiceConfig(NamedTuple):
    """Service shape + the static (structural) simulator knobs.

    ``capacity``/``k_max``/``d`` fix every traced shape at construction;
    tenant churn then never recompiles.  ``policy``/``drop_rate``/
    ``max_corr_iters`` are structural LSS knobs shared by all slots;
    ``beta``/``ell``/``eps`` are the *defaults* for the per-query
    traceable knobs (each :class:`~repro.service.query.QuerySpec` may
    override them per tenant).

    ``admission_queue``/``admission_overflow`` bound the admission
    backpressure queue (see :class:`~repro.service.admission.
    AdmissionQueue`; ``admission_queue=0`` restores fail-fast).
    ``engine_halo_slack`` pads the engine backend's halo tables so
    membership-driven boundary growth stays recompile-free.
    ``control`` selects the control-plane policies
    (:class:`~repro.service.controlplane.ControlPlaneConfig`; the
    default is FIFO / no preemption / no auto-regrow / no rebalance —
    exactly the pre-control-plane behavior).

    ``use_kernels`` picks the :class:`~repro.kernels.suite.KernelSuite`
    for the per-cycle hot loop on BOTH backends: ``None`` = auto (fused
    Pallas on TPU, reference elsewhere), bool, or a registered suite
    name.  The fused path composes with the vmapped query axis — each
    tenant's packed region table becomes one grid step's VMEM table —
    and admit/retire stays zero-recompile (region tables are traced
    data, exactly like the topology tables).

    Observability knobs: ``alerts`` is a tuple of
    :class:`~repro.obs.AlertRule` evaluated at every observe boundary;
    ``flight_capacity`` sizes the always-on flight-recorder ring
    (:meth:`Service.dump_flight_recorder`); ``flight_dump_dir`` enables
    *automatic* dumps on SLO violation / eviction / epoch / alert /
    crash (None = manual dumps only).  None of these touch the data
    plane: results stay bitwise identical with them on or off.
    """

    capacity: int = 64  # Q query slots
    k_max: int = 4  # max Voronoi centers per query
    d: int = 2  # statistic dimensionality
    cycles_per_dispatch: int = 8  # K cycles fused per jit dispatch
    policy: str = "selective"
    drop_rate: float = 0.0
    max_corr_iters: int = 0
    beta: float = 1e-3
    ell: int = 1
    eps: float = 1e-9
    backend: str = "core"  # "core" | "engine"
    engine_shards: int = 2  # engine backend: shard count
    engine_method: str = "bfs"  # engine backend: partitioner
    engine_halo_slack: float = 1.5  # halo-width headroom for membership
    # Engine backend halo wire format (repro.engine.exchange.get_wire):
    # "exact" (bitwise default), "compact" (lossless byte reduction),
    # "int8" / "bf16" (per-link quantization with error feedback).
    engine_wire: str = "exact"
    admission_queue: int = 16  # waiting specs bound (0 = fail fast)
    admission_overflow: str = "reject"  # "reject" | "evict-oldest"
    control: ControlPlaneConfig = ControlPlaneConfig()  # control plane
    use_kernels: Union[bool, str, None] = None  # kernel suite (see above)
    alerts: Tuple = ()  # AlertRule set, evaluated per observe boundary
    flight_capacity: int = 1024  # flight-recorder ring size (records)
    flight_dump_dir: Optional[str] = None  # auto-dump dir (None = manual)
    # Overlapped host boundary (see repro.service.overlap): tick K+1's
    # host work runs while dispatch K is still on the device; dispatch
    # K's telemetry is finished one tick later (flush() at shutdown
    # drains the last window).  Record CONTENT is identical to sync
    # mode — only emission is one tick deferred.
    overlap: bool = False  # overlap host boundary with in-flight dispatch
    # Audit plane (repro.obs.audit): every Nth dispatch the observation
    # pass additionally evaluates the paper's algebraic invariants as
    # device-side reductions (conservation, edge symmetry, stopping
    # soundness) and emits schema'd kind="audit" records + the
    # audit_violations_total / audit_residual metrics.  The reductions
    # ride the SAME batched observe round-trip — zero extra host
    # transfers — and audited state is read-only, so results stay
    # bitwise identical with auditing on or off.  0 disables.
    audit_every: int = 0  # audit the observe pass every N dispatches


class _Preempted(NamedTuple):
    """A suspended tenant: its spec, its core-layout state snapshot
    (partition independent — survives rebalance/regrow epochs unchanged),
    and the bookkeeping the scheduler ages it by."""

    spec: qmod.QuerySpec
    state: lss.LSSState
    topo_version: int  # applied topology version at suspension
    enqueued_dispatch: int  # when it re-entered the waiting pool


def _grow_core_states(states: lss.LSSState, n2: int,
                      D2: int) -> lss.LSSState:
    """Pad core-layout (Q, n, ...) slot states to a grown capacity.

    New rows/slots start at init values (dead, empty, cold timer), which
    is bitwise what a fresh init over the grown topology gives them.
    """
    q, n1 = states.alive.shape
    D1 = states.out_c.shape[-1]
    if (n1, D1) == (n2, D2):
        return states
    d = states.x_m.shape[-1]
    dt = states.x_m.dtype
    return states._replace(
        out_m=jnp.zeros((q, n2, D2, d), dt).at[:, :n1, :D1]
        .set(states.out_m),
        out_c=jnp.zeros((q, n2, D2), dt).at[:, :n1, :D1].set(states.out_c),
        in_m=jnp.zeros((q, n2, D2, d), dt).at[:, :n1, :D1].set(states.in_m),
        in_c=jnp.zeros((q, n2, D2), dt).at[:, :n1, :D1].set(states.in_c),
        x_m=jnp.zeros((q, n2, d), dt).at[:, :n1].set(states.x_m),
        x_c=jnp.zeros((q, n2), dt).at[:, :n1].set(states.x_c),
        pending=jnp.zeros((q, n2, D2), bool).at[:, :n1, :D1]
        .set(states.pending),
        last_send=jnp.full((q, n2), lss.COLD_TIMER, jnp.int32).at[:, :n1]
        .set(states.last_send),
        alive=jnp.zeros((q, n2), bool).at[:, :n1].set(states.alive))


@jax.jit
def _jit_core_leave(states, who):
    return states._replace(alive=states.alive.at[:, who].set(False))


@jax.jit
def _jit_core_join(states, who, m, c):
    return states._replace(
        alive=states.alive.at[:, who].set(True),
        x_m=states.x_m.at[:, who].set(m),
        x_c=states.x_c.at[:, who].set(c),
        last_send=states.last_send.at[:, who].set(lss.COLD_TIMER))


class _CoreBackend:
    """Query axis directly over :func:`lss.cycle_impl` on one device."""

    def __init__(self, topo, scfg: ServiceConfig):
        self.topo = topo
        self.ta = lss.TopoArrays.from_topology(topo)
        self.suite = kernel_suite.resolve_suite(scfg.use_kernels)

    def dispatch_info(self) -> dict:
        """What the compiled dispatch runs (mirrors the engine's)."""
        return {"suite": self.suite.name, "fused": self.suite.fused}

    def topo_args(self):
        """The traced topology pytree each dispatch takes as an argument."""
        return self.ta

    def refresh_topology(self, dyn) -> bool:
        """Swap in the mutated topology's data (same shapes: no
        recompile).  Returns True if any traced shape changed."""
        self.ta = lss.TopoArrays.from_topology(dyn)
        return False

    def zero_inputs(self, n: int, d: int) -> wvs.WV:
        return wvs.zero(d, batch=(n,))

    def init_slot(self, inputs: wvs.WV, seed: int,
                  alive=None) -> lss.LSSState:
        return lss.init_state(self.ta, inputs, seed=seed, alive=alive)

    def cycle(self, st: lss.LSSState, cfg: lss.LSSConfig, decide, gate, topo,
              pregions=None):
        if self.suite.fused and pregions is not None:
            st, _, iters, running = lss.cycle_impl(
                st, topo, cfg, None, gate=gate, suite=self.suite,
                regions=pregions, with_stats=True)
        else:
            st, _, iters, running = lss.cycle_impl(
                st, topo, cfg, decide, gate=gate, with_stats=True)
        return st, iters, running

    def metrics(self, st: lss.LSSState, decide, eps, topo):
        return lss.metrics_impl(st, topo, decide, eps=eps)

    def audit(self, st: lss.LSSState, decide, eps, topo):
        return lss.audit_impl(st, topo, decide, eps=eps)

    def capacity_slots(self) -> int:
        """n * D message-slot capacity: the static per-cycle send bound
        the audit plane's exact counter check uses (sound under churn)."""
        return int(self.ta.nbr.shape[0] * self.ta.nbr.shape[1])

    def msgs_of(self, states) -> np.ndarray:
        return np.asarray(states.msgs)  # (Q,)

    def msgs_device(self, states):
        """Per-slot send counts as a DEVICE array — no host sync, so the
        overlapped observe path can enqueue behind the dispatch."""
        return states.msgs  # (Q,)

    def reset_msgs(self, states):
        return states._replace(msgs=jnp.zeros_like(states.msgs))

    def x_moments(self, states):
        return states.x_m, states.x_c, None  # (Q, n, d), (Q, n), identity

    def with_x(self, states, x_m, x_c):
        return states._replace(x_m=x_m, x_c=x_c)

    def apply_leaves(self, states, who):
        """Mark rows ``who`` dead in EVERY slot (one jitted program)."""
        return _jit_core_leave(states, jnp.asarray(who, jnp.int32))

    def apply_joins(self, states, who, m, c):
        """Knowledge-init rows ``who`` in EVERY slot: alive, local input
        ``<m, c>``, cold send timer — fused into one jitted program."""
        return _jit_core_join(states, jnp.asarray(who, jnp.int32),
                              jnp.asarray(m, states.x_m.dtype),
                              jnp.asarray(c, states.x_c.dtype))

    def clear_slots(self, states, rows, slots):
        return lss.clear_slots(states, rows, slots)

    def snapshot(self, states, slot: int) -> lss.LSSState:
        return jax.tree_util.tree_map(lambda a: a[slot], states)

    def restore_slot(self, states, slot: int,
                     snap: lss.LSSState) -> lss.LSSState:
        """Exact inverse of :meth:`snapshot` (``snap`` pre-padded to the
        current capacity by the service)."""
        return jax.tree_util.tree_map(
            lambda all_q, one: all_q.at[slot].set(one.astype(all_q.dtype)),
            states, snap)

    def cut_frac(self) -> Optional[float]:
        return None  # one device, no partition to drift

    def halo_bytes_per_cycle(self) -> int:
        return 0  # one device, nothing crosses a shard boundary

    def regrow(self, dyn, states, prebuilt=None, catchup_rows=None):
        """Adopt a grown topology (shape change: the service's jitted
        programs recompile once) and pad every slot's state to match.
        ``prebuilt``/``catchup_rows`` are the engine backend's staged-
        epoch protocol; the core backend has no tables to pre-build."""
        self.topo = dyn
        self.ta = lss.TopoArrays.from_topology(dyn)
        return _grow_core_states(states, dyn.n, dyn.max_deg)


class _EngineBackend:
    """Query axis composed with :class:`ShardedLSS`'s shard axis."""

    def __init__(self, topo, scfg: ServiceConfig):
        self.topo = topo
        self.scfg = scfg
        self.eng = self._build(topo)
        self._leave_jit = jax.jit(self._leave_impl)
        self._join_jit = jax.jit(self._join_impl)

    def _build(self, topo):
        from repro.engine import EngineConfig, ShardedLSS  # lazy: no cycle

        scfg = self.scfg
        base = lss.LSSConfig(beta=scfg.beta, ell=scfg.ell,
                             drop_rate=scfg.drop_rate, policy=scfg.policy,
                             max_corr_iters=scfg.max_corr_iters, eps=scfg.eps)
        # The per-query packed region slices ride the engine's kernel
        # suite (the vmapped query axis becomes a leading Pallas grid
        # dimension), so use_kernels composes with Q x S.
        return ShardedLSS(
            topo, jnp.zeros((1, scfg.d), jnp.float32), base,
            EngineConfig(num_shards=scfg.engine_shards,
                         cycles_per_dispatch=scfg.cycles_per_dispatch,
                         method=scfg.engine_method,
                         use_kernels=scfg.use_kernels,
                         halo_slack=scfg.engine_halo_slack,
                         wire=scfg.engine_wire))

    def dispatch_info(self) -> dict:
        return dict(self.eng.dispatch_info)

    def topo_args(self):
        return self.eng._tables

    def refresh_topology(self, dyn) -> bool:
        return self.eng.apply_membership(dyn)

    def zero_inputs(self, n: int, d: int) -> wvs.WV:
        return wvs.zero(d, batch=(n,))

    def init_slot(self, inputs: wvs.WV, seed: int, alive=None):
        return self.eng.init(inputs, seed=seed, alive=alive)

    def cycle(self, st, cfg: lss.LSSConfig, decide, gate, topo,
              pregions=None):
        return self.eng._cycle_full(st, topo, decide=decide, cfg=cfg,
                                    gate=gate, pregions=pregions,
                                    with_stats=True)

    def metrics(self, st, decide, eps, topo):
        return self.eng._metrics_impl(st, topo, eps=eps, decide=decide)

    def audit(self, st, decide, eps, topo):
        return self.eng._audit_impl(st, topo, eps=eps, decide=decide)

    def capacity_slots(self) -> int:
        """S * B * D capacity (padding rows included — still a sound
        upper bound on per-cycle sends)."""
        return int(self.eng.S * self.eng.B * self.eng.D)

    def msgs_of(self, states) -> np.ndarray:
        return np.asarray(states.msgs).sum(axis=-1)  # (Q, S) -> (Q,)

    def msgs_device(self, states):
        return states.msgs.sum(axis=-1)  # (Q, S) -> (Q,), still device

    def reset_msgs(self, states):
        return states._replace(msgs=jnp.zeros_like(states.msgs))

    def x_moments(self, states):
        q = states.x_m.shape[0]
        x_m = states.x_m.reshape(q, -1, states.x_m.shape[-1])
        x_c = states.x_c.reshape(q, -1)
        return x_m, x_c, self.eng._pos  # permuted rows

    def with_x(self, states, x_m, x_c):
        return states._replace(x_m=x_m.reshape(states.x_m.shape),
                               x_c=x_c.reshape(states.x_c.shape))

    def _leave_impl(self, states, pos):
        q = states.alive.shape[0]
        flat = states.alive.reshape(q, -1).at[:, pos].set(False)
        return states._replace(alive=flat.reshape(states.alive.shape))

    def _join_impl(self, states, pos, m, c):
        q = states.alive.shape[0]
        alive = states.alive.reshape(q, -1).at[:, pos].set(True)
        x_m = (states.x_m.reshape(q, -1, states.x_m.shape[-1])
               .at[:, pos].set(m))
        x_c = states.x_c.reshape(q, -1).at[:, pos].set(c)
        last = states.last_send.reshape(q, -1).at[:, pos].set(lss.COLD_TIMER)
        return states._replace(
            alive=alive.reshape(states.alive.shape),
            x_m=x_m.reshape(states.x_m.shape),
            x_c=x_c.reshape(states.x_c.shape),
            last_send=last.reshape(states.last_send.shape))

    def apply_leaves(self, states, who):
        return self._leave_jit(states, self.eng._pos[jnp.asarray(who)])

    def apply_joins(self, states, who, m, c):
        return self._join_jit(states, self.eng._pos[jnp.asarray(who)],
                              jnp.asarray(m, states.x_m.dtype),
                              jnp.asarray(c, states.x_c.dtype))

    def clear_slots(self, states, rows, slots):
        return self.eng.clear_slots(states, rows, slots)

    def snapshot(self, states, slot: int) -> lss.LSSState:
        one = jax.tree_util.tree_map(lambda a: a[slot], states)
        return self.eng.to_lss_state(one)

    def restore_slot(self, states, slot: int, snap: lss.LSSState):
        """Place a core-layout snapshot back into one slot (see
        :meth:`ShardedLSS.place_lss_state` for what is and is not carried
        row-for-row)."""
        one = self.eng.place_lss_state(snap)
        return jax.tree_util.tree_map(
            lambda all_q, o: all_q.at[slot].set(o.astype(all_q.dtype)),
            states, one)

    def cut_frac(self) -> Optional[float]:
        """Fraction of edges crossing shards — the partition-quality
        number the drift metric is built on."""
        st = self.eng.stopo
        return st.cut_edges() / max(st.num_edges, 1)

    def halo_bytes_per_cycle(self) -> int:
        """Bytes the halo transport moves per cycle per query slot under
        the ACTIVE wire format (:meth:`ShardedLSS.wire_pair_bytes`):
        dense ``(S, S, H)`` capacity rows for ``"exact"`` — the buffers
        ship whole — ragged occupied widths (+ packed flags / quantized
        payloads) for the compact family."""
        return int(self.eng.wire_pair_bytes(self.scfg.d).sum())

    def _reshard(self, dyn, states, prebuilt=None, catchup_rows=None):
        """Fresh partition of ``dyn`` + state migration across
        ``new_of_old`` — the mechanics shared by both epoch kinds.

        ``prebuilt`` is a staged background build (see
        :meth:`stage_rebalance` / :meth:`stage_regrow`): an engine built
        over an earlier snapshot, caught up here via the same incremental
        journal repair live membership uses (``catchup_rows`` overrides
        the changed-row set when ``dyn``'s own journal can't reach back
        to the snapshot — the regrow case).  Any catch-up failure falls
        back to the synchronous full rebuild."""
        if prebuilt is not None:
            try:
                if prebuilt._topo_version != getattr(dyn, "version", 0):
                    prebuilt.apply_membership(dyn, rows=catchup_rows)
            except Exception:
                prebuilt = None  # stale beyond repair: rebuild in line
        old = self.eng
        self.eng = prebuilt if prebuilt is not None else self._build(dyn)
        self.topo = dyn
        return self.eng.migrate_from(old, states)

    def regrow(self, dyn, states, prebuilt=None, catchup_rows=None):
        """Re-shard over a grown topology (shape change: one recompile)."""
        return self._reshard(dyn, states, prebuilt=prebuilt,
                             catchup_rows=catchup_rows)

    def rebalance(self, dyn, states, prebuilt=None):
        """Re-partition the CURRENT graph (fresh BFS edge cut over the
        churned adjacency).  Same capacity, so traced shapes only change
        if the fresh halo tables need a different width — within the
        halo slack the service's compiled dispatch is reused as-is."""
        return self._reshard(dyn, states, prebuilt=prebuilt)

    # -- staged epoch builds (overlap mode) --------------------------------
    def stage_rebalance(self, dyn):
        """Kick off a background partition+table build over an immutable
        snapshot of the current graph.  Returns ``(build, version)``; the
        adopter hands ``build.take()`` to :meth:`rebalance` at a later
        boundary and the catch-up repair covers whatever churned since
        ``version`` (the service defers journal compaction past it)."""
        snap = dyn.snapshot() if hasattr(dyn, "snapshot") else dyn
        ver = getattr(dyn, "version", 0)

        def build():
            eng = self._build(snap)
            eng._topo_version = ver  # snapshot carries no version
            return eng

        return StagedBuild(build, label="rebalance"), ver

    def stage_regrow(self, dyn, n_cap=None, deg_cap=None):
        """Background build over a grown COPY of ``dyn`` (the ``grow()``
        call itself runs here, on the caller's thread — cheap array
        copies — so the background work touches only the immutable
        product).  The grown copy carries ``dyn``'s version, so the
        returned version is what the adopter must supply catch-up rows
        relative to (a fresh ``grow()`` product journals nothing)."""
        grown = dyn.grow(n_cap=n_cap, deg_cap=deg_cap)
        ver = getattr(dyn, "version", 0)
        return StagedBuild(lambda: self._build(grown),
                           label="regrow"), ver


class Service:
    """Long-running multi-tenant monitor over one network graph.

    Args:
      topo: the shared :class:`~repro.core.topology.Topology` — or a
        :class:`~repro.core.topology.DynTopology` to serve a network
        whose membership changes while queries are in flight
        (:meth:`join_peer`/:meth:`leave_peer`/:meth:`link_peers`/
        :meth:`unlink_peers`).
      scfg: :class:`ServiceConfig` (slot capacity, dispatch fusion, knobs).
      telemetry: optional :class:`TelemetrySink` (legacy spelling of
        ``tracker``; a sink IS a tracker).
      tracker: optional :class:`repro.obs.Tracker` the service routes ALL
        observability through — per-query / control records
        (``log_record``), host-boundary and dispatch spans (``span``),
        and convergence / control-plane metrics (the shared registry).
        Default: an owned, ring-buffered :class:`TelemetrySink`
        (in-memory only, bounded at ``_STATUS_CAP`` records) that
        :meth:`close` disposes of.  Mutually exclusive with ``telemetry``.

    The service is a context manager: ``with Service(...) as svc: ...``
    closes the tracker it owns on exit (a caller-supplied tracker is
    borrowed and stays open).
    """

    # Bound on remembered terminal query statuses (retired ids) and, at
    # 2x, on retained per-query message totals.
    _STATUS_CAP = 1 << 16

    def __init__(self, topo,
                 scfg: ServiceConfig = ServiceConfig(),
                 telemetry: Optional[TelemetrySink] = None,
                 tracker: Optional[Tracker] = None):
        if telemetry is not None and tracker is not None:
            raise ValueError(
                "pass either telemetry= (legacy) or tracker=, not both")
        self.topo = topo
        self.scfg = scfg
        self.base_cfg = lss.LSSConfig(
            beta=scfg.beta, ell=scfg.ell, drop_rate=scfg.drop_rate,
            policy=scfg.policy, max_corr_iters=scfg.max_corr_iters,
            eps=scfg.eps)
        if scfg.backend == "core":
            self.backend = _CoreBackend(topo, scfg)
        elif scfg.backend == "engine":
            self.backend = _EngineBackend(topo, scfg)
        else:
            raise ValueError(f"unknown backend {scfg.backend!r}")
        self.registry = QueryRegistry(scfg.capacity, scfg.k_max, scfg.d,
                                      self.base_cfg)
        self.ingest = StreamIngest()
        self.admission = AdmissionQueue(scfg.admission_queue,
                                        scfg.admission_overflow,
                                        clock=lambda: self.dispatches)
        # One tracker carries every observability surface; the service
        # owns (and closes) the default it builds for itself.
        self._owns_tracker = telemetry is None and tracker is None
        if tracker is not None:
            self.tracker = tracker
        elif telemetry is not None:
            self.tracker = telemetry
        else:
            self.tracker = TelemetrySink(max_records=self._STATUS_CAP)
        # Legacy alias: callers historically read svc.telemetry.records.
        self.telemetry = self.tracker
        # ALL instrumentation routes through the flight-recorder tee:
        # the user's tracker sees exactly what it always saw (records
        # forwarded verbatim, registry shared), while the bounded ring
        # retains the last N records + spans for post-mortem dumps even
        # under the Noop baseline.
        self._obs = FlightRecorder(self.tracker,
                                   capacity=max(1, scfg.flight_capacity))
        # Per-tenant causal trace ids, minted deterministically at admit
        # (part of the record stream — MUST NOT depend on the tracker
        # backend, or tracking-on/off bitwise parity breaks).
        self._trace_seq = 0
        self._trace_ids: Dict[str, str] = {}
        self.alerts = (AlertEngine(scfg.alerts, self.tracker.registry)
                       if scfg.alerts else None)
        # Control plane: SLO books, the admission/preemption scheduler,
        # and the capacity (regrow / rebalance-epoch) policy.  The SLO
        # tracker publishes its books into the shared metrics registry;
        # the eviction policy reads them back from the same registry.
        cp = scfg.control
        self.cp = cp
        self.slo = SLOTracker(registry=self.tracker.registry)
        self.evictor = SLOEvictionPolicy(
            self.tracker.registry,
            attainment_below=cp.evict_attainment_below,
            min_windows=cp.evict_min_windows)
        self.scheduler = make_scheduler(cp)
        self.capman = CapacityManager(
            auto_regrow=cp.auto_regrow, grow_factor=cp.grow_factor,
            rebalance_drift=cp.rebalance_drift,
            rebalance_check_every=cp.rebalance_check_every)
        self._preempted: Dict[str, _Preempted] = {}
        self._enqueued_at: Dict[str, int] = {}  # qid -> dispatch queued
        self._activated_at: Dict[str, int] = {}  # qid -> dispatch activated
        self._ctrl_events: list = []  # boundary activity -> control record
        self._dyn = topo if isinstance(topo, topology.DynTopology) else None
        self.membership = (MembershipQueue(self._dyn)
                           if self._dyn is not None else None)
        self._applied_version = (self._dyn.version
                                 if self._dyn is not None else 0)
        self._present = (self._dyn.present.copy()
                         if self._dyn is not None else None)
        self.dispatches = 0
        self.cycles = 0
        self._edges = max(topo.num_edges, 1)
        # Per-boundary span timings / work counts, folded into the next
        # control record (epoch spans land here too, from grow_capacity /
        # rebalance_now calls between ticks).
        self._boundary_spans: Dict[str, float] = {}
        self._boundary_counts: Dict[str, int] = {}
        self._recompiles = 0  # cumulative _step cache growth (incl. cold)
        self._corr = None  # CorrStats of the last dispatch
        self._last_k = scfg.cycles_per_dispatch  # cycles in last window
        self._quiesced_at: Dict[str, int] = {}  # qid -> first quiescent t
        self._total_msgs = {}  # query_id -> host-side exact total
        # Ids that held a slot and released it (bounded: oldest evicted
        # past _STATUS_CAP so a long-lived service's memory tracks live
        # tenants, not total tenants ever served; an evicted id's
        # admission_status degrades to KeyError).
        self._retired: dict = {}  # insertion-ordered set

        q = scfg.capacity
        blank = self.backend.init_slot(
            self.backend.zero_inputs(topo.n, scfg.d), seed=0,
            alive=self._present)
        self.states = jax.tree_util.tree_map(
            lambda a: jnp.stack([a] * q), blank)
        # Donation reuses the Q-slot state buffers across dispatches; CPU
        # does not support it and warns, so gate on backend (as the engine
        # does for its run loop).
        donate = (0,) if jax.default_backend() != "cpu" else ()
        self._step = jax.jit(self._step_impl, static_argnames=("k",),
                             donate_argnums=donate)
        # The dispatch calls through this seam, so a caller can wrap the
        # call (fault injection) while cache probes and recompile
        # accounting keep reading self._step directly.  It returns
        # (states', CorrStats); a wrapped call may give the per-slot
        # iterations alone in place of the CorrStats.
        self._step_call = self._step
        self._observe = jax.jit(self._observe_impl)
        # The audited observe variant is a SEPARATE jitted program: the
        # audit_every cadence is decided host-side between two cached
        # executables, so sampling never retraces either one.
        self._observe_audit = jax.jit(self._observe_audit_impl)
        # Overlap machinery (used by sync mode too: the double buffer's
        # reshape canary and the staged-epoch books are mode independent;
        # _pending only ever holds a window under scfg.overlap).
        self._pending: Optional[PendingWindow] = None
        self._buffers = DoubleBuffer()
        # kind ("rebalance" | "regrow") -> (StagedBuild, version[, caps]).
        # While any build is in flight the membership journal is only
        # compacted up to the oldest staged version, so adoption-time
        # catch-up repair still finds the events it needs.
        self._staged: Dict[str, tuple] = {}
        self.capman.note_epoch("init", self.backend.cut_frac())

    @property
    def topo_version(self) -> int:
        """Version of the topology the compiled tables currently reflect."""
        return self._applied_version

    @property
    def num_preempted(self) -> int:
        """Suspended queries currently waiting to resume."""
        return len(self._preempted)

    def dispatch_info(self) -> dict:
        """Which kernel suite the compiled dispatch runs (``suite`` name +
        ``fused`` flag) — benchmark/telemetry ground truth, so an unfused
        fallback can't be mislabeled as a kernel run — plus the compile
        books: ``recompiles`` (cumulative ``_step`` cache growth observed
        across ticks, cold compile included) and ``step_cache_size`` (the
        jit cache's current variant count, None when the running jax
        doesn't expose it).  The same numbers live in the registry as
        ``service_dispatch_recompiles_total``."""
        info = dict(self.backend.dispatch_info())
        info["recompiles"] = self._recompiles
        info["step_cache_size"] = jit_cache_size(self._step)
        return info

    def close(self) -> None:
        """Deterministically dispose of observability resources: finishes
        any pending overlapped window (best effort), flushes the tracker
        and, when the service built its own (no ``tracker=``/
        ``telemetry=`` argument), closes it.  Borrowed trackers stay
        open — the caller owns their lifecycle.  Idempotent."""
        try:
            self.flush()
        except Exception:
            pass  # shutdown must not fail on a poisoned window
        if self._owns_tracker:
            self.tracker.close()
        else:
            self.tracker.flush()

    def __enter__(self) -> "Service":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    # -- the batched step --------------------------------------------------
    def _one_cycle(self, st, qp: qmod.QueryParams, topo):
        cfg = self.base_cfg._replace(beta=qp.beta, ell=qp.ell, eps=qp.eps)
        # Under the query-axis vmap each leaf of qp.regions is a per-slot
        # slice — exactly one packed region table (PackedSlot), which the
        # backend's kernel suite consumes directly.
        return self.backend.cycle(st, cfg, qmod.decide_fn(qp.regions),
                                  qp.active, topo,
                                  pregions=regions.PackedSlot(*qp.regions))

    def _step_impl(self, states, params: qmod.QueryParams, topo, k: int):
        # The carry also accumulates the correction do-while's work
        # across the K cycles (CorrStats) — convergence effort rides the
        # dispatch it already pays for.
        def body(_, carry):
            sts, c = carry
            sts, it, running = jax.vmap(
                lambda st, qp: self._one_cycle(st, qp, topo))(sts, params)
            return sts, CorrStats(iters=c.iters + it,
                                  running=c.running + running,
                                  passes=c.passes + jnp.max(it))
        zero = jnp.zeros((states.alive.shape[0],), jnp.int32)
        c0 = CorrStats(iters=zero, running=zero,
                       passes=jnp.zeros((), jnp.int32))
        return jax.lax.fori_loop(0, k, body, (states, c0))

    def _observe_impl(self, states, params: qmod.QueryParams, topo):
        def one(st, qp):
            acc, quiescent, _, want = self.backend.metrics(
                st, qmod.decide_fn(qp.regions), qp.eps, topo)
            return acc, quiescent, want
        return jax.vmap(one)(states, params)

    def _observe_audit_impl(self, states, params: qmod.QueryParams, topo):
        # Identical to _observe_impl plus the audit-plane reductions — a
        # dict of per-slot scalars that rides the same round-trip, so an
        # audited window costs zero extra host transfers.
        def one(st, qp):
            decide = qmod.decide_fn(qp.regions)
            acc, quiescent, _, want = self.backend.metrics(
                st, decide, qp.eps, topo)
            return acc, quiescent, want, self.backend.audit(
                st, decide, qp.eps, topo)
        return jax.vmap(one)(states, params)

    # -- admission (between dispatches) ------------------------------------
    def admit(self, spec: qmod.QuerySpec,
              query_id: Optional[str] = None) -> str:
        """Admit a tenant's query (no recompilation, ever).

        With a free slot the query activates immediately; otherwise it
        waits in the bounded admission queue and activates as slots free
        (at retires and dispatch boundaries).  Check
        :meth:`admission_status` to distinguish ``"active"`` from
        ``"queued"``.  Raises ``RuntimeError`` only on queue overflow
        under the ``"reject"`` policy (or with queueing disabled).
        """
        if spec.inputs.shape[0] != self.topo.n:
            raise ValueError(
                f"query inputs cover {spec.inputs.shape[0]} peers, "
                f"graph has {self.topo.n}")
        if spec.inputs.shape[-1] != self.scfg.d:
            raise ValueError(
                f"query inputs have d={spec.inputs.shape[-1]}, "
                f"service is configured for d={self.scfg.d}")
        if query_id is not None and (query_id in self.admission
                                     or query_id in self.registry._slot_of
                                     or query_id in self._preempted):
            raise ValueError(f"query id {query_id!r} already admitted")
        qid = query_id if query_id is not None else self.registry.reserve_id()
        # The admission span is the root of this tenant's causal trace:
        # every later span that does work for the tenant carries the
        # same trace id, so obs.trace.assemble() hangs dispatches,
        # preempts, resumes, and evictions under this scope.
        tid = self._mint_trace(qid)
        with self._obs.span("admission", trace=(tid,), query=qid,
                            dispatch=self.dispatches) as sp:
            if self.registry.num_free > 0:
                self.registry.admit(spec, qid)
                self.slo.submit(qid, spec.slo, self.cycles)
                self._activate(qid, spec)
                sp.set("status", "active")
                return qid
            # push may raise (overflow under "reject"): record the
            # waiting bookkeeping only once the spec actually holds a
            # queue place.
            evicted = self.admission.push(qid, spec)
            self.slo.submit(qid, spec.slo, self.cycles)
            self._enqueued_at[qid] = self.dispatches
            sp.set("status", "queued")
            if evicted is not None:
                self._enqueued_at.pop(evicted, None)
                self._note_eviction(evicted,
                                    self.admission.terminal_reason(evicted))
            return qid

    def _mint_trace(self, qid: str) -> str:
        """Deterministic per-admission trace id (tracker independent)."""
        self._trace_seq += 1
        tid = f"t{self._trace_seq:05d}:{qid}"
        self._trace_ids[qid] = tid
        return tid

    def _active_traces(self) -> tuple:
        """Trace ids of the tenants the next shared scope works for."""
        return tuple(self._trace_ids[qid]
                     for qid, _slot, _spec in self.registry.active_items()
                     if qid in self._trace_ids)

    def _note_eviction(self, qid: str, reason: Optional[str]) -> None:
        """Record one queue eviction everywhere it is observable: the
        control record, the causal trace (a per-tenant span), and the
        flight-recorder trigger set."""
        tid = self._trace_ids.get(qid)
        with self._obs.span("evict", trace=(tid,) if tid else (),
                            query=qid, reason=str(reason),
                            at=self.admission.terminal_at(qid)):
            pass
        self._ctrl_events.append(("evicted", (qid, reason)))

    def admission_status(self, query_id: str) -> str:
        """``"active"`` | ``"queued"`` | ``"preempted"`` | ``"retired"`` |
        ``"evicted"`` | ``"cancelled"`` | ``"rejected"``."""
        if query_id in self.registry._slot_of:
            return "active"
        if query_id in self.admission:
            return "queued"
        if query_id in self._preempted:
            return "preempted"
        status = self.admission.terminal_status(query_id)
        if status is not None:
            return status
        if query_id in self._retired:
            return "retired"
        raise KeyError(f"unknown query id {query_id!r}")

    def _activate(self, qid: str, spec: qmod.QuerySpec) -> None:
        """Host-side slot setup for a freshly-admitted (not resumed)
        query whose registry slot is already claimed."""
        tid = self._trace_ids.get(qid)
        with self._obs.span("activate", trace=(tid,) if tid else (),
                            query=qid, slot=self.registry.slot_of(qid)):
            self._reset_slot(self.registry.slot_of(qid), spec)
        self._total_msgs[qid] = 0
        self._activated_at[qid] = self.dispatches
        self._enqueued_at.pop(qid, None)

    def _drain_admission(self) -> int:
        """One scheduler pass: preempt (if the policy says so), then fill
        free slots from the waiting pool — queued and previously preempted
        queries together, in policy order.  Returns activations."""
        waiting = [
            WaitingView(qid, spec.priority, self.slo.violations(qid),
                        self._enqueued_at.get(qid, self.dispatches), False)
            for qid, spec in self.admission.items()
        ] + [
            WaitingView(qid, e.spec.priority, self.slo.violations(qid),
                        e.enqueued_dispatch, True)
            for qid, e in self._preempted.items()
        ]
        if not waiting:
            return 0
        active = [ActiveView(qid, spec.priority, self.slo.violations(qid),
                             self._activated_at.get(qid, 0))
                  for qid, _slot, spec in self.registry.active_items()]
        plan = self.scheduler.plan(active, waiting, self.registry.num_free,
                                   self.dispatches)
        for qid in plan.preempt:
            self._preempt(qid)
        n = 0
        for qid in plan.admit:
            if self.registry.num_free == 0:
                break
            if qid in self._preempted:
                self._resume(qid)
            else:
                spec = self.admission.take(qid)
                self.registry.admit(spec, qid)
                self._activate(qid, spec)
                self._ctrl_events.append(("activated", qid))
            n += 1
        return n

    # -- preemption / resume (between dispatches) --------------------------
    def _preempt(self, query_id: str) -> None:
        """Suspend an active query: snapshot its slot (core layout, via
        the same :meth:`snapshot` path users see), free the slot, and put
        it in the waiting pool to age back in."""
        slot = self.registry.slot_of(query_id)
        spec = self.registry._specs[slot]
        tid = self._trace_ids.get(query_id)
        with self._obs.span("preempt", trace=(tid,) if tid else (),
                            query=query_id, slot=slot):
            snap = self.backend.snapshot(self.states, slot)
            self.registry.retire(query_id)
            self._reset_slot(slot, None)
        self._preempted[query_id] = _Preempted(
            spec, snap, self._applied_version, self.dispatches)
        self._ctrl_events.append(("preempted", query_id))

    def _resume(self, query_id: str) -> None:
        """Reactivate a preempted query in a free slot, restoring its
        snapshot.  With an unchanged topology the restore is exact (the
        suspension was a pause); if membership moved on, the snapshot is
        reconciled first (see :meth:`_reconcile_snapshot`).  The tenant's
        cumulative message total carries across the suspension."""
        e = self._preempted.pop(query_id)
        self.registry.admit(e.spec, query_id)
        slot = self.registry.slot_of(query_id)
        tid = self._trace_ids.get(query_id)
        with self._obs.span("resume", trace=(tid,) if tid else (),
                            query=query_id, slot=slot,
                            reconciled=e.topo_version
                            != self._applied_version) as sp:
            snap = self._pad_snapshot(e.state)
            if e.topo_version != self._applied_version:
                snap = self._reconcile_snapshot(snap)
            self.states = self.backend.restore_slot(self.states, slot, snap)
            # Replay updates that streamed in while the tenant held no
            # slot (parked by _apply_ingest), oldest first — the resumed
            # statistic is what an unsuspended tenant would hold.
            parked = self.ingest.take_parked(query_id)
            if parked:
                self._ingest_waited(parked, time.perf_counter())
                x_m, x_c, pos = self.backend.x_moments(self.states)
                slot_arr = np.array([slot], np.int32)
                for b in parked:
                    x_m, x_c = self.ingest.apply(x_m, x_c, b, slot_arr,
                                                 pos=pos)
                self.states = self.backend.with_x(self.states, x_m, x_c)
                sp.set("replayed_batches", len(parked))
        self._activated_at[query_id] = self.dispatches
        self._ctrl_events.append(("resumed", query_id))

    def _pad_snapshot(self, snap: lss.LSSState) -> lss.LSSState:
        """Pad a snapshot taken before a regrow epoch to the current
        capacity — :func:`_grow_core_states` on a batch of one, so both
        paths share the one init-value recipe."""
        n2, D2 = self.topo.n, self.topo.max_deg
        if (snap.alive.shape[0], snap.out_c.shape[-1]) == (n2, D2):
            return snap
        one = jax.tree_util.tree_map(lambda a: a[None], snap)
        return jax.tree_util.tree_map(
            lambda a: a[0], _grow_core_states(one, n2, D2))

    def _reconcile_snapshot(self, snap: lss.LSSState) -> lss.LSSState:
        """Catch a suspended query up with membership that changed while
        it held no slot.  Its link agreements are stale (edges may have
        been rewired through reused slots), so the messaging state is
        scrubbed wholesale and knowledge restarts from the current local
        statistics — the algorithm is self-stabilizing (Alg. 1
        re-converges from ``S_i = X_ii``).  The alive mask snaps to the
        current present set; peers that joined during the suspension get
        the no-value knowledge-init (zero vector, weight 1), exactly what
        :meth:`join_peer` gives an active slot."""
        present = (jnp.asarray(self._present) if self._present is not None
                   else jnp.ones_like(snap.alive))
        newly = present & ~snap.alive
        return snap._replace(
            out_m=jnp.zeros_like(snap.out_m),
            out_c=jnp.zeros_like(snap.out_c),
            in_m=jnp.zeros_like(snap.in_m),
            in_c=jnp.zeros_like(snap.in_c),
            pending=jnp.zeros_like(snap.pending),
            last_send=jnp.full_like(snap.last_send, lss.COLD_TIMER),
            alive=present,
            x_m=jnp.where(newly[:, None], 0.0, snap.x_m),
            x_c=jnp.where(newly, 1.0, snap.x_c))

    def retire(self, query_id: str) -> None:
        """Retire a query; its slot becomes a masked no-op padding slot
        (immediately refilled from the admission queue when non-empty).
        Retiring a still-queued query cancels it; retiring a preempted
        query discards its suspended state."""
        if self.admission.cancel(query_id):
            self._enqueued_at.pop(query_id, None)
            return
        if query_id in self._preempted:
            del self._preempted[query_id]
            self.ingest.discard_parked(query_id)
            self._record_retired(query_id)
            return
        slot = self.registry.retire(query_id)
        self._record_retired(query_id)
        self._reset_slot(slot, None)
        self._drain_admission()

    def _record_retired(self, query_id: str) -> None:
        self._retired[query_id] = None
        self._activated_at.pop(query_id, None)
        self._quiesced_at.pop(query_id, None)
        # Per-tenant metric series die with the tenant (the record stream
        # keeps the history; the registry tracks the live fleet).
        self.tracker.registry.remove_labels(query=query_id)
        while len(self._retired) > self._STATUS_CAP:
            self._retired.pop(next(iter(self._retired)))
            # _total_msgs keeps pace: final totals stay queryable for as
            # long as the retired id's status does.
        for stale in list(self._total_msgs):
            if len(self._total_msgs) <= self._STATUS_CAP * 2:
                break
            if stale not in self.registry._slot_of:
                del self._total_msgs[stale]

    def replace(self, query_id: str, spec: qmod.QuerySpec) -> None:
        """Swap a tenant's predicate/inputs in place (fresh slot state)."""
        self.registry.replace(query_id, spec)
        self._reset_slot(self.registry.slot_of(query_id), spec)

    def _reset_slot(self, slot: int, spec: Optional[qmod.QuerySpec]):
        if spec is None:
            fresh = self.backend.init_slot(
                self.backend.zero_inputs(self.topo.n, self.scfg.d), seed=0,
                alive=self._present)
        else:
            iw = spec.input_wv()
            if iw.m.shape[0] < self.topo.n:
                # Spec admitted before a regrow epoch: rows beyond its
                # coverage start as zero-weight inputs (they are absent
                # peers; a later join knowledge-inits them anyway).
                pad = self.topo.n - iw.m.shape[0]
                iw = wvs.WV(
                    jnp.concatenate(
                        [iw.m, jnp.zeros((pad, iw.m.shape[-1]), iw.m.dtype)]),
                    jnp.concatenate([iw.c, jnp.zeros((pad,), iw.c.dtype)]))
            fresh = self.backend.init_slot(iw, seed=spec.seed,
                                           alive=self._present)
        self.states = jax.tree_util.tree_map(
            lambda all_q, one: all_q.at[slot].set(one.astype(all_q.dtype)),
            self.states, fresh)

    # -- membership (between dispatches) -----------------------------------
    def _require_dyn(self) -> MembershipQueue:
        if self.membership is None:
            raise RuntimeError(
                "membership events need a DynTopology-backed service "
                "(construct with topology.DynTopology.from_topology(...))")
        return self.membership

    def join_peer(self, peer: Optional[int] = None, value=None,
                  weight: float = 1.0) -> int:
        """Queue a peer join (applied at the next dispatch boundary).

        The joining peer starts from the paper's knowledge-init state in
        every query slot: local input ``<weight * value, weight>``
        (zeros if no value is given), empty message slots, send timer
        cold.  Returns the peer row the join will claim.
        """
        if value is not None:
            value = np.asarray(value, np.float32).reshape(-1)
            if value.shape[0] != self.scfg.d:
                raise ValueError(f"join value has d={value.shape[0]}, "
                                 f"service is configured for d={self.scfg.d}")
        mq = self._require_dyn()
        try:
            return mq.join(peer, value, weight)
        except topology.CapacityError:
            if not self.capman.auto_regrow:
                raise
            caps = self.capman.grown_caps(self._dyn.n_cap,
                                          self._dyn.deg_cap, "rows")
            if peer is not None:  # grow at least far enough for the row
                caps["n_cap"] = max(caps["n_cap"], int(peer) + 1)
            self.grow_capacity(**caps)
            return self.membership.join(peer, value, weight)

    def leave_peer(self, peer: int) -> None:
        """Queue a peer leave (churn: all its links fail with it)."""
        self._require_dyn().leave(peer)

    def link_peers(self, i: int, j: int) -> None:
        """Queue an edge add between two present peers.  With
        ``auto_regrow``, an endpoint at degree capacity grows ``deg_cap``
        (one epoch) instead of raising."""
        mq = self._require_dyn()
        try:
            mq.link(i, j)
        except topology.CapacityError:
            if not self.capman.auto_regrow:
                raise
            self.grow_capacity(**self.capman.grown_caps(
                self._dyn.n_cap, self._dyn.deg_cap, "slots"))
            self.membership.link(i, j)

    def unlink_peers(self, i: int, j: int) -> None:
        """Queue an edge removal (no-op if a leave already tore it down)."""
        self._require_dyn().unlink(i, j)

    # -- capacity epochs (between dispatches) ------------------------------
    def grow_capacity(self, n_cap: Optional[int] = None,
                      deg_cap: Optional[int] = None) -> None:
        """Regrow epoch: larger membership capacity, in place.

        Drives :meth:`DynTopology.grow`, re-shards the backend over the
        grown tables, and migrates every slot's state across
        ``new_of_old`` (new rows start dead at init values) — plus every
        queued membership event and preempted snapshot survives.  Traced
        shapes change, so the next dispatch recompiles ONCE; with
        ``control.auto_regrow`` this runs transparently when
        :meth:`join_peer` / :meth:`link_peers` hit the capacity wall.
        """
        dyn = self._dyn
        if dyn is None:
            raise RuntimeError(
                "grow_capacity needs a DynTopology-backed service")
        # A pre-staged background build (see _maybe_stage_growth) whose
        # capacity covers the request is adopted instead of rebuilding
        # in line; its catch-up rows come from the OLD dyn's journal —
        # computed before grow(), which resets the journal floor.
        prebuilt = catchup_rows = None
        staged = self._staged.pop("regrow", None)
        if staged is not None:
            build, ver, caps = staged
            if ((n_cap is None or caps["n_cap"] >= n_cap)
                    and (deg_cap is None or caps["deg_cap"] >= deg_cap)):
                n_cap, deg_cap = caps["n_cap"], caps["deg_cap"]
                try:
                    catchup_rows = dyn.changed_rows_since(ver)
                    prebuilt = build.take()
                except Exception:
                    prebuilt = catchup_rows = None
        new_dyn = dyn.grow(n_cap=n_cap, deg_cap=deg_cap)
        self.topo = self._dyn = new_dyn
        self.membership.rebind(new_dyn)
        with self._obs.span("epoch_regrow", trace=self._active_traces(),
                            n_cap=new_dyn.n_cap,
                            deg_cap=new_dyn.deg_cap,
                            staged=prebuilt is not None) as sp:
            self.states = self.backend.regrow(new_dyn, self.states,
                                              prebuilt=prebuilt,
                                              catchup_rows=catchup_rows)
        self._buffers.invalidate()  # shape change: expected recompile
        self._boundary_spans["epoch_regrow"] = sp.seconds
        self._boundary_counts["epochs"] = (
            self._boundary_counts.get("epochs", 0) + 1)
        self._present = new_dyn.present.copy()
        self._applied_version = new_dyn.version
        self._edges = max(new_dyn.num_edges, 1)
        ev = self.capman.note_epoch(
            "regrow", self.backend.cut_frac(),
            n_cap=new_dyn.n_cap, deg_cap=new_dyn.deg_cap,
            staged=prebuilt is not None)
        self._ctrl_events.append(("epoch", ev))

    def rebalance_now(self) -> Optional[dict]:
        """Explicit re-partition epoch (engine backend; ``None`` on the
        partitionless core backend).

        Long churn drifts shard occupancy away from the BFS edge-cut
        optimum; this rebuilds the partition over the *current* graph and
        migrates state bitwise across ``new_of_old``.  Returns the epoch
        record (drift and cut fractions).  Runs automatically when
        ``control.rebalance_drift`` > 0 and the drift metric crosses it.
        """
        before = self.backend.cut_frac()
        if before is None:
            return None
        prebuilt = None
        staged = self._staged.pop("rebalance", None)
        if staged is not None:
            try:
                prebuilt = staged[0].take()
            except Exception:
                prebuilt = None  # failed build: rebuild synchronously
        drift = self.capman.drift(before)
        with self._obs.span("epoch_rebalance", trace=self._active_traces(),
                            drift=drift, staged=prebuilt is not None) as sp:
            self.states = self.backend.rebalance(self.topo, self.states,
                                                 prebuilt=prebuilt)
        self._buffers.invalidate()  # fresh tables may change halo width
        self._boundary_spans["epoch_rebalance"] = sp.seconds
        self._boundary_counts["epochs"] = (
            self._boundary_counts.get("epochs", 0) + 1)
        ev = self.capman.note_epoch(
            "rebalance", self.backend.cut_frac(),
            cut_before=before, drift=drift, staged=prebuilt is not None)
        self._ctrl_events.append(("epoch", ev))
        return ev

    def _maybe_rebalance(self) -> None:
        # A staged rebalance build adopts as soon as it is ready (and
        # suppresses new drift checks while in flight).
        staged = self._staged.get("rebalance")
        if staged is not None:
            if staged[0].ready():
                self.rebalance_now()
            return
        # should_rebalance re-checks the cadence/threshold itself; the
        # early-outs here just avoid the O(edges) cut_frac() host scan on
        # every off-cadence dispatch.
        if self.dispatches == 0 or self.capman.rebalance_drift <= 0.0:
            return
        if self.dispatches % self.capman.rebalance_check_every:
            return
        if self.capman.should_rebalance(self.dispatches,
                                        self.backend.cut_frac()):
            if self.scfg.overlap and hasattr(self.backend,
                                             "stage_rebalance"):
                # Overlap mode: kick the partition rebuild off-thread and
                # keep dispatching; adoption happens at a later boundary.
                src = self._dyn if self._dyn is not None else self.topo
                with self._obs.span("epoch_stage", kind="rebalance"):
                    self._staged["rebalance"] = \
                        self.backend.stage_rebalance(src)
            else:
                self.rebalance_now()

    def _maybe_stage_growth(self) -> None:
        """Overlap mode: pre-stage the regrow epoch's partition + table
        build in the background when free membership rows run low, so
        the capacity-wall epoch adopts a finished build instead of
        stalling the boundary for the full rebuild."""
        if (not self.scfg.overlap or self._dyn is None
                or not self.capman.auto_regrow or self._staged
                or not hasattr(self.backend, "stage_regrow")):
            return
        free = int((~self._dyn.present).sum())
        if free >= max(1, self._dyn.n_cap // 16):
            return
        caps = self.capman.grown_caps(self._dyn.n_cap, self._dyn.deg_cap,
                                      "rows")
        with self._obs.span("epoch_stage", kind="regrow", **caps):
            build, ver = self.backend.stage_regrow(self._dyn, **caps)
        self._staged["regrow"] = (build, ver, caps)

    def drift(self) -> float:
        """Current partition drift (cut-fraction increase since the last
        epoch); 0.0 on the core backend."""
        return self.capman.drift(self.backend.cut_frac())

    def _apply_membership(self) -> int:
        """Drain queued events into the DynTopology and catch every
        execution surface up: incremental table repair (data-only within
        capacity: zero recompiles) + per-slot state edits."""
        if self._dyn is None:
            return 0
        if (not self.membership.has_pending()
                and self._dyn.version == self._applied_version):
            return 0  # quiet tick: skip the drain machinery entirely
        join_inits = self.membership.drain_into(self._dyn)
        events = self._dyn.events_since(self._applied_version)
        if not events:
            return 0
        if self.backend.refresh_topology(self._dyn):
            # Halo width regrew: traced shapes changed, the next swap's
            # reshape is a declared epoch rather than a canary trip.
            self._buffers.invalidate()

        # 1. Scrub the messaging state of every touched (peer, slot) —
        #    freed and claimed alike (idempotent; order-free).
        rows, slots = [], []
        for ev in events:
            if ev.kind in ("link", "unlink"):
                rows += [ev.a, ev.b]
                slots += [ev.slot_a, ev.slot_b]
        if rows:
            # Idempotent edits + power-of-two padding: bounded scatter
            # shapes (see lss.pad_bucket).
            self.states = self.backend.clear_slots(
                self.states, *lss.pad_bucket(np.asarray(rows, np.int32),
                                             np.asarray(slots, np.int32)))

        # 2. Alive transitions: the LAST join/leave per peer wins.
        final = {}
        for ev in events:
            if ev.kind in ("join", "leave"):
                final[ev.a] = ev.kind
        joins = np.array([p for p, k in final.items() if k == "join"],
                         np.int32)
        leaves = np.array([p for p, k in final.items() if k == "leave"],
                          np.int32)
        if leaves.size:
            self.states = self.backend.apply_leaves(
                self.states, *lss.pad_bucket(leaves))
        if joins.size:
            # Knowledge-init: X_ii = <w*v, w>, empty slots, cold timer.
            d = self.scfg.d
            vals = np.zeros((joins.size, d), np.float32)
            wts = np.ones((joins.size,), np.float32)
            for idx, p in enumerate(joins):
                v, w = join_inits.get(int(p), (None, 1.0))
                if v is not None:
                    vals[idx] = v
                wts[idx] = w
            joins_p, vals_p, wts_p = lss.pad_bucket(joins, vals, wts)
            self.states = self.backend.apply_joins(
                self.states, joins_p, vals_p * wts_p[:, None], wts_p)

        self._present = self._dyn.present.copy()
        self._edges = max(self._dyn.num_edges, 1)
        self._applied_version = self._dyn.version
        # Staged epoch builds catch up from the journal at adoption time,
        # so compaction may only advance to the oldest staged version.
        floor = self._applied_version
        for entry in self._staged.values():
            floor = min(floor, entry[1])
        self._dyn.compact(floor)
        return len(events)

    # -- streaming ingest --------------------------------------------------
    def push_updates(self, who, values, weights=None, mode: str = "set",
                     query_ids=None) -> UpdateBatch:
        """Queue a per-peer update batch (applied at the next boundary)."""
        return self.ingest.push(who, values, weights, mode, query_ids)

    def _ingest_waited(self, batches, at: float) -> Tuple[int, float]:
        """Observe each stamped batch's seconds from its push to ``at``,
        the boundary applying it (a parked batch's replay counts its park
        time); returns how many were observed and their summed wait."""
        hist = self.tracker.histogram(
            "service_ingest_wait_seconds",
            "seconds from push_updates to the boundary applying the batch")
        waits = [at - b.pushed_at for b in batches if b.pushed_at is not None]
        for w in waits:
            hist.observe(w)
        return len(waits), sum(waits)

    def _apply_ingest(self) -> Tuple[int, Tuple[int, float]]:
        """Apply every queued batch.  Returns the batches drained and
        ``_ingest_waited`` of those applied to an active slot now (a
        batch only parked is observed at its replay)."""
        at = time.perf_counter()
        batches = self.ingest.drain()
        if not batches:
            return 0, (0, 0.0)
        applied = []
        x_m, x_c, pos = self.backend.x_moments(self.states)
        active = {qid: s for qid, s, _ in self.registry.active_items()}
        for b in batches:
            if b.query_ids is None:
                slots = np.fromiter(active.values(), np.int32,
                                    count=len(active))
            else:
                # Ids retired while the batch sat in the queue are dropped
                # (their slot may already belong to a new tenant); a
                # PREEMPTED target parks the batch for replay at resume.
                for q in b.query_ids:
                    if q not in active and q in self._preempted:
                        self.ingest.park(q, b)
                slots = np.array([active[q] for q in b.query_ids
                                  if q in active], np.int32)
            if slots.size:
                applied.append(b)
            x_m, x_c = self.ingest.apply(x_m, x_c, b, slots, pos=pos)
        self.states = self.backend.with_x(self.states, x_m, x_c)
        return len(batches), self._ingest_waited(applied, at)

    # -- the serving loop --------------------------------------------------
    def tick(self, cycles: Optional[int] = None) -> list:
        """One dispatch: apply queued membership events, drain the
        admission queue, apply queued updates, run K cycles over all Q
        slots in one jit call, observe, emit per-tenant telemetry.

        The whole boundary runs inside one ``tick`` root span; every
        host boundary nests under it (``membership_drain`` /
        ``admission_drain`` / ``ingest_apply`` / ``dispatch`` /
        ``observe``, the sync, and ``observe_emit``, the records and
        gauges built after it, plus ``epoch_regrow`` /
        ``epoch_rebalance`` when an epoch fires, and the per-tenant
        ``activate`` / ``preempt`` / ``resume`` / ``evict`` scopes) —
        the stream reconstructs into a causal tree via
        :func:`repro.obs.trace.assemble`.  Timings and work counts also
        land in the registry and in the next control record's ``spans``
        / ``boundary`` maps.  An exception escaping the tick dumps the
        flight recorder (when ``flight_dump_dir`` is set) before
        propagating.

        Returns this dispatch's telemetry records (active slots only).
        Under ``scfg.overlap`` the records returned are the PREVIOUS
        dispatch's (its observation synced while this one ran); the
        first tick returns ``[]`` and :meth:`flush` drains the last
        window.  Record content is identical to sync mode either way.
        """
        try:
            # dispatches increments mid-tick (at _launch); the root span
            # is labeled with the dispatch this tick RUNS, so its attr
            # matches the window records it causally covers.
            with self._obs.span("tick", dispatch=self.dispatches + 1):
                return self._tick_inner(cycles)
        except Exception as e:
            self._auto_flight_dump("crash", error=repr(e))
            raise

    def _tick_inner(self, cycles: Optional[int]) -> list:
        k = cycles if cycles is not None else self.scfg.cycles_per_dispatch
        self._host_boundary()
        window = self._launch(k)
        if not self.scfg.overlap:
            return self._finish_window(window)
        # Overlap: window K's telemetry syncs NEXT tick, while dispatch
        # K+1 runs — this tick returns window K-1's records (empty on
        # the first tick; flush() drains the last one).
        prev, self._pending = self._pending, window
        return self._finish_window(prev) if prev is not None else []

    def _host_boundary(self) -> None:
        """Everything the host does between dispatches: membership
        drain, epoch checks/staging, SLO eviction, admission, ingest.
        In overlap mode all of it runs while the PREVIOUS dispatch is
        still on the device — nothing here blocks on device results."""
        tr = self._obs
        with tr.span("membership_drain") as sp:
            n_events = self._apply_membership()
            if n_events and self.membership is not None:
                for key, v in self.membership.last_drain_stats.items():
                    sp.set(key, v)
        self._boundary_spans["membership_drain"] = sp.seconds
        self._boundary_counts["membership_events"] = n_events
        self._maybe_rebalance()
        self._maybe_stage_growth()
        self._evict_unrecoverable()
        with tr.span("admission_drain") as sp:
            n_act = self._drain_admission()
            sp.set("activations", n_act)
        self._boundary_spans["admission_drain"] = sp.seconds
        self._boundary_counts["activations"] = n_act
        with tr.span("ingest_apply") as sp:
            n_batches, (waited, wait_s) = self._apply_ingest()
            if waited:
                sp.set("waited", waited)
                sp.set("wait_s", wait_s)
        self._boundary_spans["ingest_apply"] = sp.seconds
        self._boundary_counts["ingest_batches"] = n_batches

    def _launch(self, k: int) -> PendingWindow:
        """Stage the dispatch operands (the double-buffer swap), enqueue
        the K-cycle dispatch + the observation pass behind it, and return
        the un-synced window."""
        params = self.registry.params
        topo = self.backend.topo_args()
        # The swap enforces the zero-recompile invariant: boundary work
        # must not change traced shapes outside a declared epoch.
        self._buffers.swap(params, topo)
        info = self.backend.dispatch_info()
        tr = self._obs
        before = jit_cache_size(self._step)
        with tr.span("dispatch", trace=self._active_traces(), k=k,
                     backend=self.scfg.backend,
                     suite=info.get("suite"), fused=info.get("fused")) as sp:
            self.states, corr = self._step_call(
                self.states, params, topo, k=k)
            if not isinstance(corr, CorrStats):
                # A fault-injection wrapper that gives the per-slot
                # iterations alone (its step runs no cycle).
                corr = CorrStats(corr, jnp.zeros_like(corr), jnp.max(corr))
            self._corr = corr
            after = jit_cache_size(self._step)
            if before is not None and after is not None and after > before:
                sp.set("recompiled", after - before)
                self._recompiles += after - before
                tr.counter(
                    "service_dispatch_recompiles_total",
                    "jit cache growth across service dispatches "
                    "(includes the cold compile)").inc(after - before)
        self._boundary_spans["dispatch"] = sp.seconds
        self.dispatches += 1
        self.cycles += k
        self._last_k = k
        return self._begin_observe(params, topo, k)

    def _begin_observe(self, params: qmod.QueryParams, topo,
                       k: int) -> PendingWindow:
        """Enqueue the observation pass right behind the dispatch and
        capture the host bookkeeping its records will be built from.
        The returned arrays are futures — nothing here syncs."""
        ae = self.scfg.audit_every
        if ae and (self.dispatches - 1) % ae == 0:
            # Audited window: the audit reductions fold into the same
            # observe program (dispatches was just incremented, so the
            # first window is always audited).
            acc, quiescent, want, audit = self._observe_audit(
                self.states, params, topo)
        else:
            acc, quiescent, want = self._observe(self.states, params, topo)
            audit = None
        msgs = self.backend.msgs_device(self.states)
        self.states = self.backend.reset_msgs(self.states)
        events, self._ctrl_events = self._ctrl_events, []
        spans, self._boundary_spans = self._boundary_spans, {}
        counts, self._boundary_counts = self._boundary_counts, {}
        return PendingWindow(
            dispatch=self.dispatches, t=self.cycles, k=k,
            acc=acc, quiescent=quiescent, want=want, msgs=msgs,
            corr=self._corr,
            active=tuple((qid, slot) for qid, slot, _spec
                         in self.registry.active_items()),
            queued=tuple(self.admission.queued_ids()),
            preempted=tuple(self._preempted),
            topo_version=self._applied_version,
            edges=self._edges,
            events=events, spans=spans, counts=counts, audit=audit)

    def flush(self) -> list:
        """Finish the pending overlapped window without launching a new
        dispatch: syncs its observation and emits its telemetry.  No-op
        (empty list) in sync mode or when nothing is pending.  serve()
        and close() call this; call it directly after a manual tick()
        loop when record delivery must be caught up."""
        w, self._pending = self._pending, None
        if w is None:
            return []
        try:
            with self._obs.span("tick", dispatch=w.dispatch, flush=True):
                return self._finish_window(w)
        except Exception as e:
            self._auto_flight_dump("crash", error=repr(e))
            raise

    def _evict_unrecoverable(self) -> None:
        """SLO-driven eviction: drop *waiting* tenants whose published
        attainment says their SLO is already lost (policy reads the
        shared metrics registry — see :class:`~repro.service.controlplane.
        eviction.SLOEvictionPolicy`)."""
        if not self.evictor.enabled:
            return
        for qid, reason in self.evictor.victims(self.admission.queued_ids()):
            if self.admission.evict(qid, reason):
                self._enqueued_at.pop(qid, None)
                self._note_eviction(qid, reason)

    def serve(self, dispatches: int) -> list:
        """Run ``dispatches`` ticks; returns the final tick's records
        (overlap mode flushes the trailing window first, so the return
        value is the final dispatch's records in both modes)."""
        records = []
        for _ in range(dispatches):
            records = self.tick()
        if self._pending is not None:
            records = self.flush()
        return records

    # -- observation -------------------------------------------------------
    def _finish_window(self, w: PendingWindow) -> list:
        """Sync a launched window's observation futures and emit its
        telemetry.  Sync mode calls this immediately after the launch
        (bitwise the old single-pass tick); overlap mode calls it one
        tick later, while the next dispatch occupies the device."""
        with self._obs.span(
                "observe", dispatch=w.dispatch,
                trace=tuple(self._trace_ids[qid] for qid, _slot in w.active
                            if qid in self._trace_ids)) as sp:
            # ONE host sync for the whole fleet: metrics, message counts,
            # the correction do-while's counts and (on sampled windows)
            # the audit reductions come back in one batched transfer
            # (device_get starts every copy before it waits on any).
            acc, quiescent, want, msgs, corr, audit_raw = jax.device_get(
                (w.acc, w.quiescent, w.want, w.msgs, w.corr, w.audit))
        # The window's own observe cost belongs to ITS control record.
        w.spans["observe"] = sp.seconds
        with self._obs.span("observe_emit", dispatch=w.dispatch):
            return self._emit_window(w, acc, quiescent, want, msgs,
                                     corr, audit_raw)

    def _emit_window(self, w: PendingWindow, acc, quiescent, want, msgs,
                     corr, audit_raw) -> list:
        """Everything observe does after its sync, on the host: the
        per-tenant records, gauges, audit records, alerts, the control
        record and the flight-recorder trigger."""
        reg = self.tracker.registry
        corr_hist = self.tracker.histogram(
            "service_corr_iters",
            "correction do-while iterations per slot per dispatch window",
            buckets=obs_metrics.DEFAULT_COUNT_BUCKETS)
        passes = int(corr.passes)
        reg.counter(
            "service_corr_passes_total",
            "correction do-while passes the vmapped dispatch ran "
            "(per cycle the most over the slots)").inc(passes)
        records = []
        for qid, slot in w.active:
            sent = int(msgs[slot])
            self._total_msgs[qid] = self._total_msgs.get(qid, 0) + sent
            iters = int(corr.iters[slot])
            running = int(corr.running[slot])
            rec = {
                "dispatch": w.dispatch,
                "t": w.t,
                "query": qid,
                "slot": slot,
                "accuracy": float(acc[slot]),
                "quiescent": bool(quiescent[slot]),
                "region": int(want[slot]),
                "msgs": sent,
                "msgs_per_link": sent / w.edges,
                "topo_version": w.topo_version,
                "trace_id": self._trace_ids.get(qid, ""),
                "corr_iters": iters,
                "corr_passes": passes,
                "corr_running": running,
            }
            slo_fields = self.slo.observe(qid, rec)
            if slo_fields is not None:
                rec.update(slo_fields)
            # Convergence metrics, per tenant, into the shared registry.
            reg.gauge("tenant_accuracy",
                      "fraction of live peers deciding correctly").set(
                          rec["accuracy"], query=qid)
            reg.gauge("tenant_msgs_per_link",
                      "sends per link in the last dispatch window").set(
                          rec["msgs_per_link"], query=qid)
            reg.counter("tenant_msgs_total",
                        "cumulative sends, per query").inc(sent, query=qid)
            if rec["quiescent"]:
                if qid not in self._quiesced_at:
                    self._quiesced_at[qid] = w.t
                    reg.gauge(
                        "tenant_quiesced_at_cycles",
                        "cycle count at which the tenant first "
                        "quiesced and stayed quiescent").set(
                            w.t, query=qid)
            else:
                if self._quiesced_at.pop(qid, None) is not None:
                    reg.gauge("tenant_quiesced_at_cycles").remove(query=qid)
            corr_hist.observe(iters, query=qid)
            reg.counter(
                "service_corr_running_peers_total",
                "peers still running, summed over the correction "
                "do-while's passes, per query").inc(running, query=qid)
            self._obs.log_record(rec)
            records.append(rec)
        # Audit plane: on sampled windows, evaluate the invariant
        # reductions per active slot and emit kind="audit" records.
        audit_bad = False
        if audit_raw is not None:
            max_sent = w.k * self.backend.capacity_slots()
            for qid, slot in w.active:
                raw = {key: v[slot] for key, v in audit_raw.items()}
                rep = obs_audit.evaluate(
                    raw, claimed_quiescent=bool(quiescent[slot]),
                    max_sent=max_sent)
                arec = obs_audit.record(
                    rep, dispatch=w.dispatch, t=w.t, query=qid, slot=slot,
                    trace_id=self._trace_ids.get(qid, ""))
                self._obs.log_record(arec)
                reg.gauge("audit_residual",
                          "conservation residual of the last audited "
                          "window (absolute, tolerance-gated)").set(
                              arec["residual"], query=qid)
                if not rep.ok:
                    audit_bad = True
                    for m, held in rep.monitors.items():
                        if not held:
                            reg.counter(
                                "audit_violations_total",
                                "audit-plane invariant violations, per "
                                "query and monitor").inc(
                                    1, query=qid, monitor=m)
        halo_bytes = self.backend.halo_bytes_per_cycle()
        if halo_bytes and records:
            reg.counter(
                "engine_halo_bytes_total",
                "halo exchange buffer bytes moved (dense transport "
                "footprint), summed over cycles and active slots").inc(
                    halo_bytes * w.k * len(records))
        reg.gauge("service_queue_depth",
                  "admission queue occupancy").set(len(self.admission))
        reg.gauge("service_preempted_depth",
                  "suspended queries waiting to resume").set(
                      len(self._preempted))
        reg.gauge("service_active_slots",
                  "occupied query slots").set(len(records))
        # Tenants holding no slot still burn their SLO deadline —
        # evaluated against the window's waiting pools and clock, so
        # deferral does not double- or under-count waiting windows.
        for qid in w.queued:
            self.slo.observe_waiting(qid, w.t)
        for qid in w.preempted:
            self.slo.observe_waiting(qid, w.t)
        # Alert rules: the registry's second policy consumer.  Evaluated
        # after every gauge above is current; transitions become
        # kind="alert" records and arm the flight-recorder trigger.
        fired = []
        if self.alerts is not None:
            for a in self.alerts.evaluate(dispatch=w.dispatch, t=w.t):
                if a["state"] == "firing":
                    fired.append(a)
                self._obs.log_record(a)
        # Flight-recorder trigger set for this window.  An invariant
        # violation outranks the service-level triggers: it means the
        # algorithm itself broke, not just its operating envelope.
        trigger = None
        if audit_bad:
            trigger = "audit_violation"
        elif any(r.get("slo_ok") is False for r in records):
            trigger = "slo_violation"
        elif any(kind == "evicted" for kind, _ in w.events):
            trigger = "eviction"
        elif any(kind == "epoch" for kind, _ in w.events):
            trigger = "epoch"
        elif fired:
            trigger = "alert"
        self._emit_control_record(w)
        if trigger is not None:
            # Stamp the dump with the WINDOW's counters: under overlap
            # the live ones already advanced past the window that
            # tripped the trigger.
            self._auto_flight_dump(trigger, dispatch=w.dispatch, t=w.t)
        return records

    # -- flight recorder ---------------------------------------------------
    def dump_flight_recorder(self, path: Optional[str] = None,
                             reason: str = "manual",
                             dispatch: Optional[int] = None,
                             t: Optional[int] = None) -> str:
        """Write the flight-recorder ring (last ``flight_capacity``
        records + spans) as JSONL and return the path.  Default path:
        ``flight-d<dispatch>-<reason>.jsonl`` under ``flight_dump_dir``
        (or the CWD when unset).  ``dispatch`` / ``t`` override the
        header's counters (triggered dumps pass the offending WINDOW's
        values, which under overlap lag the live ones)."""
        dispatch = self.dispatches if dispatch is None else dispatch
        t = self.cycles if t is None else t
        if path is None:
            base = self.scfg.flight_dump_dir or "."
            os.makedirs(base, exist_ok=True)
            path = os.path.join(
                base, f"flight-d{dispatch:06d}-{reason}.jsonl")
        return self._obs.dump(path, reason=reason, dispatch=dispatch, t=t)

    def _auto_flight_dump(self, reason: str, dispatch: Optional[int] = None,
                          t: Optional[int] = None,
                          **context) -> Optional[str]:
        """Automatic dump on audit / SLO violation / eviction / epoch /
        alert / crash — only when the service was configured with a dump
        dir (manual :meth:`dump_flight_recorder` works regardless).
        ``dispatch`` / ``t`` default to the live counters; window-scoped
        triggers pass the window's own."""
        base = self.scfg.flight_dump_dir
        if base is None:
            return None
        dispatch = self.dispatches if dispatch is None else dispatch
        t = self.cycles if t is None else t
        os.makedirs(base, exist_ok=True)
        path = os.path.join(
            base, f"flight-d{dispatch:06d}-{reason}.jsonl")
        return self._obs.dump(path, reason=reason, dispatch=dispatch, t=t,
                              **context)

    def _emit_control_record(self, w: PendingWindow) -> None:
        """One record per dispatch with the control plane's activity —
        only when there is any (idle services emit nothing extra).

        "Activity" covers scheduler/capacity events, non-empty waiting
        pools, and boundary work (membership events drained, ingest
        batches applied) — the record then carries the boundary ``spans``
        (seconds) and ``boundary`` (work counts) maps, which is how the
        host-boundary costs reach the JSONL trail.  Everything comes from
        the WINDOW (captured right after its boundary ran), so sync and
        overlap modes emit identical records."""
        events, spans, counts = w.events, w.spans, w.counts
        boundary_work = (counts.get("membership_events", 0)
                         or counts.get("ingest_batches", 0)
                         or counts.get("epochs", 0))
        if (not events and not w.queued and not w.preempted
                and not boundary_work):
            return
        agg: dict = {"activated": [], "resumed": [], "preempted": [],
                     "evicted": [], "epochs": []}
        for kind, payload in events:
            if kind == "epoch":
                agg["epochs"].append(payload)
            elif kind == "evicted":
                agg["evicted"].append(
                    {"query": payload[0], "reason": payload[1]})
            else:
                agg[kind].append(payload)
        self._obs.log_record({
            "kind": "control",
            "dispatch": w.dispatch,
            "t": w.t,
            "queue_depth": len(w.queued),
            "preempted_depth": len(w.preempted),
            **{k: v for k, v in agg.items() if v},
            **({"spans": spans} if spans else {}),
            **({"boundary": {k: v for k, v in counts.items() if v}}
               if any(counts.values()) else {}),
        })

    def total_msgs(self, query_id: str) -> int:
        """Exact cumulative sends by this query (host-side accumulation;
        carries across preemption)."""
        return self._total_msgs[query_id]

    def snapshot(self, query_id: str) -> lss.LSSState:
        """This query's full simulator state (original peer order) — the
        parity-test / debugging view.  For a preempted query, the state
        it was suspended with (shapes reflect the capacity at suspension
        time)."""
        if query_id in self._preempted:
            return self._preempted[query_id].state
        return self.backend.snapshot(self.states,
                                     self.registry.slot_of(query_id))

    def slo_report(self) -> Dict[str, dict]:
        """Per-tenant SLO summary: violations, evaluated windows,
        attainment — every tenant that declared an SLO (including retired
        ones, up to the bookkeeping bound)."""
        return self.slo.report()
