"""ShardedLSS — the exact :mod:`repro.core.lss` semantics on a device mesh.

The peer population is partitioned into ``S`` blocks (:mod:`.partition`);
every state array carries a leading shard axis ``(S, B, ...)``.  One engine
cycle is::

    1. deliver   — pending out-messages land in in-slots: shard-local edges
                   by the same reverse-slot scatter the core simulator uses,
                   cross-shard edges through the halo exchange
                   (:mod:`.exchange`);
    2. update    — status / violation / selective-correction math, reused
                   VERBATIM from the core (``stopping``, ``correction``,
                   ``lss.correction_loop``), or routed through a
                   :class:`~repro.kernels.suite.KernelSuite` — e.g. the
                   fused Pallas kernels over the packed region
                   representation — per shard (``EngineConfig.
                   use_kernels``).

Because step 2 is peer-local and step 1 reproduces exactly the core's
"message (i, k) lands at (nbr[i,k], rev[i,k])" delivery, the engine is
cycle-for-cycle equivalent to :func:`repro.core.lss.cycle` (bitwise, up to
the RNG stream when ``drop_rate > 0`` — the engine draws per-shard drop
keys where the core draws one global key).

Host-sync amortization: :meth:`ShardedLSS.run` dispatches
``cycles_per_dispatch`` cycles per jit call through a ``lax.fori_loop``
with donated state buffers, so a million-peer run costs one dispatch +
one device-sync per K cycles instead of per cycle.

Transports: on a single device the halo exchange is a transpose (gather
fallback); given a mesh axis of size ``S`` the same per-shard code runs
under ``shard_map`` with ``lax.all_to_all`` (:meth:`use_mesh`).

Async execution mode (``EngineConfig.async_mode`` / :meth:`init_async`):
the cross-shard exchange drops the per-cycle barrier semantics.  Every
shard keeps its own clock, publishes its boundary sends into a
bounded-staleness ring (:func:`repro.engine.exchange.ring_publish`), and
reads every peer shard at a receiver-chosen delay of up to
``EngineConfig.staleness`` cycles.  Out-of-order and superseded
deliveries are guarded by per-message sequence numbers — exactly the
``seq_i``/``last_j`` counters Alg. 1 carries for general (non-FIFO)
networks, promoted from the event-driven :mod:`repro.core.async_sim`
reference.  At ``staleness=0`` the ring read degenerates to the
synchronous transpose and the mode is **bitwise identical** to the sync
engine (drop-RNG stream included); with ``staleness>0`` stale reads are
bounded, dropped messages age out of the ring, and the realized delay /
stale-drop counts surface as gauges.

Dynamic membership: the topology tables (:class:`DeviceTopo`) are traced
*arguments* of the jitted step, and the partition spans the topology's
full capacity, so a :class:`~repro.core.topology.DynTopology` mutation
only needs :meth:`ShardedLSS.apply_membership` — an incremental
host-side halo repair plus a data-only table swap.  Within the padded
capacities (peer rows, degree slots, ``halo_slack`` width) nothing
recompiles.
"""

from __future__ import annotations

import warnings
from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh

from repro.core import lss, regions, stopping, topology, wvs
from repro.kernels import suite as kernel_suite

from . import exchange, partition

__all__ = ["DeviceTopo", "EngineConfig", "ShardedState", "AsyncShardedState",
           "ShardedLSS"]


def _take_slots(flat: jax.Array, idx: jax.Array) -> jax.Array:
    """``flat[idx]`` for a per-slot index array ``idx`` (..., D), gathered
    slot-major (see :func:`repro.core.lss.mirror_slots` for why)."""
    return jnp.moveaxis(flat[jnp.moveaxis(idx, -1, 0)], 0, -1)


def _intra_deliver(in_buf, out_buf, deliv, tgt_row, rev, intra):
    """One shard's receive-side gather over its intra-shard edges: for an
    intra slot the ``(tgt_row, rev)`` map is an involution, so in-slot
    (j, r) reads its unique source slot (tgt_row[j,r], rev[j,r])."""
    t = lss.TopoArrays(nbr=tgt_row, mask=intra, rev=rev)
    got = lss.mirror_slots(deliv, t) & intra
    cond = got[..., None] if out_buf.ndim > got.ndim else got
    return jnp.where(cond, lss.mirror_slots(out_buf, t), in_buf)


class _LocalTables(NamedTuple):
    """One shard's view of the topology tables inside shard_map."""

    mask: jax.Array  # (B, D)
    rev: jax.Array  # (B, D)
    tgt_row: jax.Array  # (B, D)
    tgt_pos: jax.Array  # (B, D) flattened global target positions
    intra: jax.Array  # (B, D)
    halo: partition.HaloTables  # (S, H) local rows


class DeviceTopo(NamedTuple):
    """Device-side topology tables, threaded through the jitted step.

    These are *arguments* of every compiled program, never closed-over
    constants: a dynamic-membership edit swaps in new table data of the
    same shape and the existing executable keeps running (zero
    recompiles).  Baking them in as jit constants would silently pin the
    first topology forever.
    """

    mask: jax.Array  # bool  (S, B, D)
    rev: jax.Array  # int32 (S, B, D)
    tgt_row: jax.Array  # int32 (S, B, D)
    tgt_pos: jax.Array  # int32 (S, B, D)
    intra: jax.Array  # bool  (S, B, D)
    halo: partition.HaloTables  # (S, S, H) jnp tables

    @classmethod
    def from_sharded(cls, st: partition.ShardedTopo) -> "DeviceTopo":
        j = jnp.asarray
        return cls(mask=j(st.mask), rev=j(st.rev), tgt_row=j(st.tgt_row),
                   tgt_pos=j(st.tgt_pos), intra=j(st.intra),
                   halo=partition.HaloTables(*(j(a) for a in st.halo)))


class EngineConfig(NamedTuple):
    num_shards: int = 2
    cycles_per_dispatch: int = 8  # K cycles fused per jit dispatch
    method: str = "bfs"  # partitioner: "bfs" | "stride"
    # Kernel suite for the per-peer hot loop: None = auto (fused Pallas on
    # TPU, reference elsewhere), bool, or a registered suite name
    # (repro.kernels.suite).  Works for ANY packed region family
    # (Voronoi + halfspace) and composes with the service query axis.
    use_kernels: Union[bool, str, None] = None
    halo_slack: float = 1.0  # >1 pads halo width for membership headroom
    # Asynchronous gossip execution mode: per-shard clocks, cross-shard
    # messages published into a bounded-staleness ring and read at a
    # receiver-chosen delay in [0, staleness] cycles, per-message seq
    # guards (Alg. 1's seq/last counters) against reordering.  At
    # staleness=0 the mode is bitwise identical to the sync engine.
    async_mode: bool = False
    staleness: int = 0  # halo reads may lag the sender by <= this many cycles
    # Halo wire format (repro.engine.exchange.get_wire): "exact" (f32,
    # bitwise — the default), "compact" (lossless: bit-packed flags +
    # occupied-width transport), "int8" / "bf16" (per-link quantization
    # with error feedback; convergence-preserving, not bitwise).
    wire: str = "exact"
    # Cost-model autotuning (repro.engine.autotune): enumerate candidate
    # (shards, halo_slack, K, wire) plans at construction, score each
    # from the compiled dispatch HLO (launch.hlo_cost) + the wire byte
    # model, time the shortlist, and adopt the winner's config.
    auto_plan: bool = False


class ShardedState(NamedTuple):
    """:class:`repro.core.lss.LSSState`, blocked ``(S, B, ...)`` per shard.

    The two trailing ``wire_err_*`` fields exist only under a stateful
    (quantized) wire format: per-out-slot error-feedback buffers in
    membership-stable ``(S, B, D, ...)`` coordinates (independent of the
    halo width, so table repairs and wire-width bumps never reshape
    them).  ``None`` — an empty pytree node — everywhere else, keeping
    the exact/compact state trees structurally identical to before.
    """

    out_m: jax.Array  # (S, B, D, d)
    out_c: jax.Array  # (S, B, D)
    in_m: jax.Array  # (S, B, D, d)
    in_c: jax.Array  # (S, B, D)
    x_m: jax.Array  # (S, B, d)
    x_c: jax.Array  # (S, B)
    pending: jax.Array  # (S, B, D) bool
    last_send: jax.Array  # (S, B) int32
    alive: jax.Array  # (S, B) bool — padding rows stay False
    t: jax.Array  # ()  current cycle, replicated
    msgs: jax.Array  # (S,) per-shard cumulative sends (exact int)
    rng: jax.Array  # (S, 2) per-shard PRNG keys
    wire_err_m: Optional[jax.Array] = None  # (S, B, D, d) quant error
    wire_err_c: Optional[jax.Array] = None  # (S, B, D)


class AsyncShardedState(NamedTuple):
    """Async-mode engine state: the sync per-shard state plus the
    bounded-staleness transport books.

    ``clock`` is per shard.  In this single-dispatcher engine all shards
    step together so the clocks stay equal, but every timer / ring /
    sequence computation is written against the per-shard value — the
    layout a multi-host dispatcher with genuinely divergent shard clocks
    needs.  ``R = staleness + 1`` ring slots guarantee a publication
    survives exactly the read window that may still target it.
    """

    sync: ShardedState  # the paper state, (S, B, ...) as ever
    clock: jax.Array  # (S,) int32 per-shard local clocks
    out_seq: jax.Array  # (S, B, D) int32 — seq of the newest posted message
    last_seq: jax.Array  # (S, B, D) int32 — newest seq applied per in-slot
    ring_m: jax.Array  # (R, S, S, H, d) published halo payloads
    ring_c: jax.Array  # (R, S, S, H)
    ring_flag: jax.Array  # (R, S, S, H) bool
    ring_seq: jax.Array  # (R, S, S, H) int32
    stale_drops: jax.Array  # (S,) seq-guarded (reordered/superseded) drops
    applied: jax.Array  # (S,) cross-shard messages applied
    delay_sum: jax.Array  # (S,) total realized delay of applied messages


class ShardedLSS:
    """Partitioned multi-shard LSS engine with halo exchange.

    Args:
      topo: host-side :class:`~repro.core.topology.Topology`.
      centers: (k, d) Voronoi option points.
      cfg: the simulator :class:`~repro.core.lss.LSSConfig` (semantics).
      ecfg: :class:`EngineConfig` (execution: shards, dispatch fusion).
      decide: optional OPAQUE region decision fn (reference formulas only
        — the packed kernels cannot represent it; prefer ``region=``).
      region: optional region family (``VoronoiRegions`` /
        ``HalfspaceRegions`` / :class:`~repro.core.regions.PackedSlot`)
        replacing the default Voronoi-on-``centers``; packed, so it rides
        the fused kernel path.
      tracker: optional :class:`repro.obs.Tracker`; :meth:`run` wraps
        every jit dispatch in an ``engine.dispatch`` span (wall time, k,
        recompile delta) recorded into the tracker's registry.  Default
        is a :class:`~repro.obs.NoopTracker` (timing only, nothing kept).
    """

    def __init__(self, topo: topology.Topology, centers,
                 cfg: lss.LSSConfig = lss.LSSConfig(),
                 ecfg: EngineConfig = EngineConfig(), decide=None,
                 region=None, tracker=None):
        from repro.obs import NoopTracker  # local: keep engine import light

        if ecfg.auto_plan:
            # Cost-model autotuning: enumerate (S, slack, K, wire)
            # candidates around this config, score their compiled HLO +
            # wire byte model, time the shortlist, adopt the winner.
            # The probes themselves build with auto_plan=False.
            from . import autotune  # lazy: autotune constructs engines

            ecfg = autotune.plan(topo, centers, cfg=cfg, base=ecfg).config
        self.cfg = cfg
        self.ecfg = ecfg
        self.tracker = tracker if tracker is not None else NoopTracker()
        self.centers = jnp.asarray(centers)
        if region is not None:
            self.region_slot = regions.as_packed_slot(region)
            self.decide = decide or self.region_slot.decide
        elif decide is None:
            self.region_slot = regions.PackedSlot.voronoi(self.centers)
            self.decide = lambda v: regions.decide_voronoi(v, self.centers)
        else:
            self.region_slot = None  # opaque decide: not packable
            self.decide = decide
        part = partition.make_partition(topo, ecfg.num_shards, ecfg.method)
        # halo_slack > 1 pads the halo width for membership headroom: edge
        # churn that grows a boundary stays a data-only update until the
        # slack is exhausted.
        st = partition.shard_topology(topo, part,
                                      halo_slack=ecfg.halo_slack)
        self.stopo = st
        self.part = part
        self.S, self.B, self.D = part.num_shards, part.block, st.D
        self.n, self.num_edges = st.n, st.num_edges
        # Halo wire format: what the cross-shard transport actually ships
        # (and how the byte accounting models it).  Width-trimming
        # formats slice the device-side halo tables to the occupied
        # width (_wire_tables), so the trim is a traced-shape property —
        # a later width bump recompiles through exactly the machinery a
        # halo regrow already uses.
        self._wire = exchange.get_wire(ecfg.wire)
        self._wire_w = self._wire_width()
        self._tables = self._wire_tables(DeviceTopo.from_sharded(st))
        # Version of the (Dyn)topology the tables reflect; apply_membership
        # catches up incrementally from here.
        self._topo_version = getattr(topo, "version", 0)
        self._pos = jnp.asarray(part.new_of_old)  # (n,) orig -> flattened
        if self.region_slot is None:
            # An opaque decide callable cannot feed the packed kernels:
            # auto falls back to the reference suite, an explicitly
            # requested FUSED suite is an error (a non-fused suite name
            # honors the opaque decide and is fine).
            requested = (kernel_suite.get_suite("reference")
                         if ecfg.use_kernels in (None, False)
                         else kernel_suite.resolve_suite(ecfg.use_kernels))
            if requested.fused:
                raise ValueError(
                    "use_kernels routes decisions through the packed "
                    "Pallas kernels and cannot honor an opaque `decide` "
                    "callable — pass `region=` (a region family) instead")
            self.suite = requested
        else:
            self.suite = kernel_suite.resolve_suite(ecfg.use_kernels)
        self.use_kernels = self.suite.fused
        # Host-visible record of what the most recently TRACED dispatch
        # runs (benchmarks read this so unfused fallbacks can't mislabel
        # runs; _peer_update keeps "fused" current per compilation).
        self.dispatch_info = {"suite": self.suite.name,
                              "fused": self.suite.fused}
        self._warned_unfused = False
        self._mesh = None
        self._axis = None
        # Donation lets XLA reuse the K-cycle block's state buffers in
        # place; CPU does not support it and warns, so gate on backend.
        self._donate = (0,) if jax.default_backend() != "cpu" else ()
        self._run_jit = jax.jit(self._run_block, static_argnames=("k",),
                                donate_argnums=self._donate)
        self._run_async_jit = jax.jit(self._run_async_block,
                                      static_argnames=("k",),
                                      donate_argnums=self._donate)
        self._metrics_jit = jax.jit(self._metrics_impl,
                                    static_argnames=("eps",))
        self._audit_jit = jax.jit(self._audit_impl,
                                  static_argnames=("eps",))
        self._audit_async_jit = jax.jit(self._audit_async_impl)
        self._clear_jit = jax.jit(self._clear_slots_impl)

    # -- mesh attachment ---------------------------------------------------
    def use_mesh(self, mesh, axis_name: str) -> "ShardedLSS":
        """Route the halo exchange through shard_map + all_to_all.

        The mesh axis size must equal ``num_shards``; state arrays should be
        device_put with the shard axis over ``axis_name``.  The engine
        keeps its own copy of ``mesh`` with ``Auto`` axes: the host-side
        gathers and scatters (:meth:`to_lss_state`, :meth:`set_inputs`,
        ...) index sharded state eagerly, which the ``Explicit`` axes that
        ``jax.make_mesh`` builds by default refuse.
        """
        if mesh.shape[axis_name] != self.S:
            raise ValueError(
                f"mesh axis {axis_name!r} has size {mesh.shape[axis_name]}, "
                f"engine has {self.S} shards")
        self._mesh = Mesh(mesh.devices, mesh.axis_names,
                          axis_types=(AxisType.Auto,) * len(mesh.axis_names))
        self._axis = axis_name
        self._run_jit = jax.jit(self._run_block_collective,
                                static_argnames=("k",),
                                donate_argnums=self._donate)
        return self

    # -- state -------------------------------------------------------------
    def init(self, inputs: wvs.WV, seed: int = 0, alive=None):
        """Build sharded state from inputs in ORIGINAL peer order.

        ``alive`` (optional bool (n,), original order) seeds the churn
        mask — a capacity-padded :class:`~repro.core.topology.DynTopology`
        passes its ``present`` mask so spare rows start dead.

        With ``EngineConfig.async_mode`` the return value is an
        :class:`AsyncShardedState` (use :meth:`init_sync` for the bare
        sync state).
        """
        if self.ecfg.async_mode:
            return self.init_async(inputs, seed=seed, alive=alive)
        return self.init_sync(inputs, seed=seed, alive=alive)

    def init_sync(self, inputs: wvs.WV, seed: int = 0,
                  alive=None) -> ShardedState:
        """:meth:`init`'s sync-state half, mode flag ignored."""
        S, B, D = self.S, self.B, self.D
        d = inputs.m.shape[-1]
        dt = inputs.m.dtype
        x_m = jnp.zeros((S * B, d), dt).at[self._pos].set(inputs.m)
        x_c = jnp.zeros((S * B,), dt).at[self._pos].set(inputs.c)
        alive0 = (jnp.ones((self.n,), bool) if alive is None
                  else jnp.array(alive, bool))  # copy: caller may mutate
        alive = jnp.zeros((S * B,), bool).at[self._pos].set(alive0)
        state = ShardedState(
            out_m=jnp.zeros((S, B, D, d), dt),
            out_c=jnp.zeros((S, B, D), dt),
            in_m=jnp.zeros((S, B, D, d), dt),
            in_c=jnp.zeros((S, B, D), dt),
            x_m=x_m.reshape(S, B, d),
            x_c=x_c.reshape(S, B),
            pending=jnp.zeros((S, B, D), bool),
            last_send=jnp.full((S, B), lss.COLD_TIMER, jnp.int32),
            alive=alive.reshape(S, B),
            t=jnp.zeros((), jnp.int32),
            msgs=jnp.zeros((S,), lss.counter_dtype()),
            rng=jax.random.split(jax.random.PRNGKey(seed), S),
        )
        if self._wire.stateful:
            # Quantization error feedback, per out-slot (membership-stable
            # coordinates: halo repairs never reshape these).
            state = state._replace(
                wire_err_m=jnp.zeros((S, B, D, d), jnp.float32),
                wire_err_c=jnp.zeros((S, B, D), jnp.float32))
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            shard = NamedSharding(self._mesh, P(self._axis))
            repl = NamedSharding(self._mesh, P())
            state = ShardedState(*(
                None if a is None else
                jax.device_put(a, repl if a.ndim == 0 else shard)
                for a in state))
        return state

    def init_async(self, inputs: wvs.WV, seed: int = 0,
                   alive=None) -> AsyncShardedState:
        """Async-mode init: the sync state wrapped with cold transport
        books (empty ring, zero clocks/sequence counters)."""
        return self.wrap_async(self.init_sync(inputs, seed=seed, alive=alive))

    def wrap_async(self, base: ShardedState) -> AsyncShardedState:
        """Wrap an existing sync state for async execution.  The ring
        starts empty: the first async cycle behaves exactly like a sync
        cycle would from the same state."""
        S, B, D = self.S, self.B, self.D
        # Ring slots match the WIRE width (trimmed tables), not the padded
        # host halo capacity — the ring holds what the transport ships.
        H = int(self._tables.halo.send_ok.shape[-1])
        R = max(1, int(self.ecfg.staleness) + 1)
        d = base.x_m.shape[-1]
        dt = base.x_m.dtype
        cnt = lss.counter_dtype()
        return AsyncShardedState(
            sync=base,
            clock=jnp.full((S,), base.t, jnp.int32),
            out_seq=jnp.zeros((S, B, D), jnp.int32),
            last_seq=jnp.zeros((S, B, D), jnp.int32),
            ring_m=jnp.zeros((R, S, S, H, d), dt),
            ring_c=jnp.zeros((R, S, S, H), dt),
            ring_flag=jnp.zeros((R, S, S, H), bool),
            ring_seq=jnp.zeros((R, S, S, H), jnp.int32),
            stale_drops=jnp.zeros((S,), cnt),
            applied=jnp.zeros((S,), cnt),
            delay_sum=jnp.zeros((S,), cnt))

    # -- dynamic-data hooks (original peer ids) ------------------------------
    def set_inputs(self, state: ShardedState, who, new_x) -> ShardedState:
        """Resample inputs: ``x_m[who] = new_x`` (moment form, weight kept)."""
        pos = self._pos[jnp.asarray(who)]
        flat = state.x_m.reshape(self.S * self.B, -1)
        flat = flat.at[pos].set(jnp.asarray(new_x, flat.dtype))
        return state._replace(x_m=flat.reshape(state.x_m.shape))

    def kill_peers(self, state: ShardedState, who) -> ShardedState:
        """Churn: permanently mark original ids ``who`` dead."""
        return self.set_alive(state, who, False)

    def set_alive(self, state: ShardedState, who, value: bool
                  ) -> ShardedState:
        """Set the churn mask of original ids ``who`` (True = join)."""
        pos = self._pos[jnp.asarray(who)]
        flat = state.alive.reshape(self.S * self.B)
        flat = flat.at[pos].set(bool(value))
        return state._replace(alive=flat.reshape(state.alive.shape))

    def clear_slots(self, state: ShardedState, rows, slots) -> ShardedState:
        """Scrub the messaging state of ``(peer, slot)`` coordinates in
        ORIGINAL ids — the engine-layout counterpart of
        :func:`repro.core.lss.clear_slots` (see there for why membership
        edits must do this, and why it runs as one jitted program).
        Broadcasts over leading (query) axes."""
        return self._clear_jit(state, jnp.asarray(rows, jnp.int32),
                               jnp.asarray(slots, jnp.int32))

    def _clear_slots_impl(self, state: ShardedState, rows, slots):
        pos = self._pos[rows]
        s_idx, b_idx = pos // self.B, pos % self.B
        upd = dict(
            out_m=state.out_m.at[..., s_idx, b_idx, slots, :].set(0.0),
            out_c=state.out_c.at[..., s_idx, b_idx, slots].set(0.0),
            in_m=state.in_m.at[..., s_idx, b_idx, slots, :].set(0.0),
            in_c=state.in_c.at[..., s_idx, b_idx, slots].set(0.0),
            pending=state.pending.at[..., s_idx, b_idx, slots].set(False),
        )
        if state.wire_err_m is not None:
            # A scrubbed slot's quantization debt dies with its message.
            upd["wire_err_m"] = (state.wire_err_m
                                 .at[..., s_idx, b_idx, slots, :].set(0.0))
            upd["wire_err_c"] = (state.wire_err_c
                                 .at[..., s_idx, b_idx, slots].set(0.0))
        return state._replace(**upd)

    # -- dynamic membership ------------------------------------------------
    def apply_membership(self, dyn, rows=None) -> bool:
        """Catch the halo/local tables up to a mutated
        :class:`~repro.core.topology.DynTopology`.

        The partition (row placement) is fixed at construction over the
        topology's full capacity, so membership edits never move peers —
        only the adjacency tables of the touched rows and the halo rows of
        their shard pairs are repaired (:func:`repro.engine.partition.
        repair_sharded_topo`).  Returns True when the halo width regrew —
        a shape change, i.e. the next dispatch recompiles (async-mode
        ring buffers are keyed by halo width too: re-wrap via
        :meth:`wrap_async` after a regrow); within the halo headroom the
        swap is data-only and the compiled step is reused.

        ``rows`` overrides the changed-row set when the caller knows it
        from a different journal than ``dyn``'s own — the staged-epoch
        adoption path hands a background-built engine the rows that
        churned between its snapshot and now, even though ``dyn`` itself
        (a fresh ``grow()`` product) no longer journals back that far.
        """
        if rows is None:
            rows = dyn.changed_rows_since(self._topo_version)
        self._topo_version = dyn.version
        if rows.size == 0:
            return False
        old_width = self.stopo.halo_width
        old_wire_w = self._wire_w
        self.stopo = partition.repair_sharded_topo(
            self.stopo, dyn, rows,
            halo_slack=max(self.ecfg.halo_slack, 1.25))
        self.num_edges = self.stopo.num_edges
        # The wire width only ever grows within an engine's lifetime: a
        # shrink after unlinks would recompile for no correctness reason.
        self._wire_w = max(old_wire_w, self._wire_width())
        self._tables = self._wire_tables(DeviceTopo.from_sharded(self.stopo))
        return (self.stopo.halo_width != old_width
                or self._wire_w != old_wire_w)

    # -- wire format -------------------------------------------------------
    def _wire_width(self) -> int:
        """Static halo width the wire transport ships.

        The full padded ``H`` for non-trimming formats; otherwise the
        last occupied table position (+1) rounded up to a byte boundary
        (flags bit-pack evenly), so ``halo_slack`` headroom stays
        host-side capacity instead of riding the transport.  Computed
        from occupied *positions*, not counts, so it stays correct even
        if a repair leaves a pair's entries non-contiguous.
        """
        H = self.stopo.halo_width
        if not self._wire.trims:
            return H
        ok = np.asarray(self.stopo.halo.send_ok)
        occupied = ok * (np.arange(H, dtype=np.int64) + 1)[None, None, :]
        needed = int(occupied.max()) if occupied.size else 0
        return max(1, min(H, -(-needed // 8) * 8))

    def _wire_tables(self, tables: DeviceTopo) -> DeviceTopo:
        """Slice the device halo tables to the wire width.

        Entries at or beyond the wire width are all ``send_ok``-False
        padding, so the slice is bitwise-invisible to the exchange; the
        narrower traced table shapes are what make a wire-width bump a
        *declared* recompile (same jit-cache mechanics as a halo regrow)
        on every consumer, the service's compiled step included.
        """
        W = self._wire_w
        halo = tables.halo
        if W >= halo.send_ok.shape[-1]:
            return tables
        return tables._replace(halo=partition.HaloTables(
            *(a[:, :, :W] for a in halo)))

    def wire_pair_bytes(self, d: int) -> "np.ndarray":
        """Modeled wire bytes per cycle per ordered shard pair ``(S, S)``
        for ``d``-dimensional statistics: the active format's
        serialization of each pair's halo row (dense rows for ``exact``,
        ragged occupied widths + bit-packed flags for the compact family
        — see the wire-format table in :mod:`repro.engine.exchange`).
        Recomputed from the host tables, so membership repairs are
        reflected immediately."""
        counts = np.asarray(self.stopo.halo.send_ok).sum(axis=-1)
        return self._wire.pair_bytes(counts, self._wire_w, int(d))

    # -- per-peer update (flattened), shared with the collective path ------
    def _peer_update(self, out_m, out_c, in_m, in_c, x_m, x_c, live,
                     last_send, alive, t, decide=None, cfg=None, gate=None,
                     pregions=None):
        """Violation test + selective correction on flattened (N, ...) rows.

        This is exactly the post-delivery half of :func:`repro.core.lss.
        cycle`; ``lss.correction_loop`` is the same do-while object.

        ``decide``/``cfg``/``gate``/``pregions`` override the engine's own
        (used by the service layer, which vmaps a query axis of per-query
        region families, traceable knobs and an active-slot gate over this
        body).  A packed ``pregions`` slot — or a family given at
        construction — rides the fused kernel suite, per-query knobs
        included; only an OPAQUE ``decide`` override forces the reference
        formulas (noted once via warning + ``dispatch_info["fused"]``).
        """
        cfg = cfg if cfg is not None else self.cfg
        slot = pregions if pregions is not None else self.region_slot
        fused = self.suite.fused and (decide is None or pregions is not None)
        # Trace-time record of what THIS compilation runs (not latched:
        # a later fused trace flips it back to True).
        self.dispatch_info["fused"] = fused
        if self.suite.fused and not fused:
            self._note_unfused()
        decide = decide if decide is not None else self.decide

        flat_state = lss.LSSState(
            out_m=out_m, out_c=out_c, in_m=in_m, in_c=in_c,
            x_m=x_m, x_c=x_c, pending=live, last_send=last_send,
            alive=alive, t=t, msgs=t, rng=t)
        flat_topo = lss.TopoArrays(nbr=jnp.zeros(live.shape, jnp.int32),
                                   mask=live, rev=jnp.zeros_like(live, jnp.int32))
        status_viol = corrected = None
        if fused:
            # Same do-while, fused Pallas paths for the per-peer math.
            status_viol, corrected, entry = lss.suite_hooks(
                self.suite, flat_state, live, slot, cfg)
        else:
            s = stopping.status(x_m, x_c, out_m, out_c, in_m, in_c, live)
            a = stopping.agreements(out_m, out_c, in_m, in_c)
            viol = stopping.violations_alg1(decide, s, a, live, cfg.eps)
            entry = (s, a, viol)
        s, _a0, viol = entry
        timer_ok = (t - last_send) >= cfg.ell
        active = alive & timer_ok & jnp.any(viol, axis=1)
        if gate is not None:
            active = active & gate

        out_m2, out_c2, v, did_send, corr_iters, corr_running = (
            lss.correction_loop(decide, flat_state, flat_topo, live, active,
                                cfg, status_viol=status_viol,
                                corrected=corrected, entry=entry))
        pending = v & did_send[:, None]
        new_last = jnp.where(did_send, t, last_send)
        return out_m2, out_c2, pending, new_last, (corr_iters, corr_running)

    def _note_unfused(self) -> None:
        """An opaque per-call decide bypassed the fused path: the caller
        already recorded ``fused=False`` in the dispatch telemetry (so
        benchmarks can't mislabel runs); warn once.  Runs at trace time —
        once per compilation."""
        if not self._warned_unfused:
            self._warned_unfused = True
            warnings.warn(
                "ShardedLSS: a per-call `decide` override without packed "
                "region parameters bypasses the fused kernel path; this "
                "dispatch runs the reference formulas (recorded as "
                "fused=False in dispatch_info). Pass packed regions (a "
                "PackedSlot / QueryParams.regions slice) to keep the "
                "fused path.", RuntimeWarning, stacklevel=3)

    # -- one cycle, gather-fallback (full arrays, one device) --------------
    def _cycle_full(self, state: ShardedState, tables: DeviceTopo,
                    decide=None, cfg=None, gate=None,
                    pregions=None, with_stats=False):
        """One engine cycle on full ``(S, B, ...)`` arrays.

        ``tables`` is the traced :class:`DeviceTopo` (membership edits swap
        its data between dispatches).  ``decide``/``cfg``/``gate``/
        ``pregions`` are per-call overrides (see :meth:`_peer_update`); the
        service layer vmaps this body over a query axis, composing Q
        concurrent monitoring queries with the shard axis in a single
        dispatch — with packed per-query ``pregions`` the whole Q x S
        batch rides the fused kernels.

        ``with_stats=True`` (Python static, selects the return arity)
        returns ``(state', corr_iters, corr_running)`` — the correction
        do-while's iteration count and running-peer sum, mirroring
        ``lss.cycle_impl(with_stats=True)``.
        """
        cfg = cfg if cfg is not None else self.cfg
        S, B, D = self.S, self.B, self.D
        keys = jax.vmap(jax.random.split)(state.rng)  # (S, 2, 2)
        rng, kdrop = keys[:, 0], keys[:, 1]

        nbr_alive = _take_slots(state.alive.reshape(S * B), tables.tgt_pos)
        live = tables.mask & state.alive[..., None] & nbr_alive
        send = state.pending & live
        if cfg.drop_rate > 0.0:
            keep = jax.vmap(
                lambda k: jax.random.uniform(k, (B, D)))(kdrop)
            delivered = send & (keep >= cfg.drop_rate)
        else:
            delivered = send
        sent = jnp.sum(send, axis=(1, 2))

        # Shard-local edges: the core's receive-side gather.
        deliver = jax.vmap(_intra_deliver)
        in_m = deliver(state.in_m, state.out_m, delivered, tables.tgt_row,
                       tables.rev, tables.intra)
        in_c = deliver(state.in_c, state.out_c, delivered, tables.tgt_row,
                       tables.rev, tables.intra)

        # Cross-shard edges: halo gather -> wire encode -> transpose ->
        # wire decode -> scatter.  The exact wire's encode/decode are the
        # identity on the same (buf_m, buf_c, flag) triple, so this IS the
        # pre-wire program bitwise (and compile-cache-identical).
        buf_m, buf_c, flag = exchange.gather_halo(
            state.out_m, state.out_c, delivered, tables.halo)
        wire = self._wire
        if wire.stateful:
            g_em, g_ec = exchange.gather_err(
                state.wire_err_m, state.wire_err_c, tables.halo)
            payload, n_em, n_ec = wire.encode(buf_m, buf_c, flag, g_em, g_ec)
            err_m, err_c = exchange.scatter_err(
                state.wire_err_m, state.wire_err_c, n_em, n_ec, tables.halo)
        else:
            payload, _, _ = wire.encode(buf_m, buf_c, flag)
            err_m, err_c = state.wire_err_m, state.wire_err_c
        payload = tuple(exchange.transpose_all_to_all(p) for p in payload)
        buf_m, buf_c, flag = wire.decode(payload)
        in_m, in_c = exchange.scatter_halo(in_m, in_c, buf_m, buf_c, flag,
                                           tables.halo)

        # Peer-local update on flattened rows.
        fl = lambda a: a.reshape(S * B, *a.shape[2:])
        out_m, out_c, pending, last_send, corr = self._peer_update(
            fl(state.out_m), fl(state.out_c), fl(in_m), fl(in_c),
            fl(state.x_m), fl(state.x_c), fl(live), fl(state.last_send),
            fl(state.alive), state.t, decide=decide, cfg=cfg, gate=gate,
            pregions=pregions)
        sh = lambda a: a.reshape(S, B, *a.shape[1:])
        state = state._replace(
            out_m=sh(out_m), out_c=sh(out_c), in_m=in_m, in_c=in_c,
            pending=sh(pending), last_send=sh(last_send),
            t=state.t + 1, msgs=state.msgs + sent.astype(state.msgs.dtype),
            rng=rng, wire_err_m=err_m, wire_err_c=err_c)
        if with_stats:
            return (state,) + corr
        return state

    def _run_block(self, state: ShardedState, tables: DeviceTopo,
                   k: int) -> ShardedState:
        return jax.lax.fori_loop(
            0, k, lambda _, st: self._cycle_full(st, tables), state)

    # -- one cycle, asynchronous gossip mode -------------------------------
    def _cycle_async(self, astate: AsyncShardedState,
                     tables: DeviceTopo) -> AsyncShardedState:
        """One async-mode cycle: sync-identical intra-shard delivery and
        per-peer update, but cross-shard messages go through the
        bounded-staleness ring with per-message sequence guards.

        The structure mirrors :meth:`_cycle_full` operation-for-operation
        where the semantics coincide, because at ``staleness=0`` the two
        must be bitwise identical — same RNG splits (the extra delay draw
        happens only when ``staleness > 0``), same gathers, same scatter;
        the ring write+read collapses to the transpose and the seq guard
        passes every flagged message (sequence numbers are monotone per
        out-slot, so a fresh delivery can never be stale).
        """
        cfg = self.cfg
        state = astate.sync
        S, B, D = self.S, self.B, self.D
        staleness = int(self.ecfg.staleness)
        R = max(1, staleness + 1)
        keys = jax.vmap(jax.random.split)(state.rng)  # (S, 2, 2)
        rng, kdrop = keys[:, 0], keys[:, 1]
        if staleness > 0:
            # Extra per-shard split for the delay draw — deliberately
            # OUTSIDE the staleness=0 path so the drop stream stays
            # bitwise on the sync engine's sequence there.
            keys2 = jax.vmap(jax.random.split)(rng)
            rng, kdelay = keys2[:, 0], keys2[:, 1]

        nbr_alive = _take_slots(state.alive.reshape(S * B), tables.tgt_pos)
        live = tables.mask & state.alive[..., None] & nbr_alive
        send = state.pending & live
        if cfg.drop_rate > 0.0:
            keep = jax.vmap(
                lambda kk: jax.random.uniform(kk, (B, D)))(kdrop)
            delivered = send & (keep >= cfg.drop_rate)
        else:
            delivered = send
        sent = jnp.sum(send, axis=(1, 2))

        # Shard-local edges: identical to the sync engine (same shard,
        # same clock — nothing to be stale against).
        deliver = jax.vmap(_intra_deliver)
        in_m = deliver(state.in_m, state.out_m, delivered, tables.tgt_row,
                       tables.rev, tables.intra)
        in_c = deliver(state.in_c, state.out_c, delivered, tables.tgt_row,
                       tables.rev, tables.intra)

        # Cross-shard: publish this cycle's boundary sends (+ their seq
        # stamps) into each shard's ring slot at its own clock...
        buf_m, buf_c, flag = exchange.gather_halo(
            state.out_m, state.out_c, delivered, tables.halo)
        wire = self._wire
        if wire.lossy:
            # Quantize at the SENDER boundary (encode -> decode before the
            # ring), so what the ring holds — and any bounded-stale read
            # later delivers — is exactly what a quantized transport ships.
            # The error feedback updates on publish, the only sender-side
            # event; staleness only affects which publication is read.
            g_em, g_ec = exchange.gather_err(
                state.wire_err_m, state.wire_err_c, tables.halo)
            payload, n_em, n_ec = wire.encode(buf_m, buf_c, flag, g_em, g_ec)
            buf_m, buf_c, flag = wire.decode(payload)
            err_m, err_c = exchange.scatter_err(
                state.wire_err_m, state.wire_err_c, n_em, n_ec, tables.halo)
            state = state._replace(wire_err_m=err_m, wire_err_c=err_c)
        buf_seq = jax.vmap(lambda sq, r, sl: sq[r, sl])(
            astate.out_seq, tables.halo.send_row, tables.halo.send_slot)
        wslot = astate.clock % R
        ring_m, ring_c, ring_flag, ring_seq = exchange.ring_publish(
            astate.ring_m, astate.ring_c, astate.ring_flag, astate.ring_seq,
            wslot, buf_m, buf_c, flag, buf_seq)

        # ...and read every (dst, src) pair at a bounded-stale sender
        # clock.  delay[t, s] in [0, staleness], capped by the sender's
        # clock so early cycles never reach before time 0 (untouched ring
        # rows carry False flags anyway).
        if staleness > 0:
            delay = jax.vmap(lambda kk: jax.random.randint(
                kk, (S,), 0, staleness + 1))(kdelay)  # (S_dst, S_src)
            delay = jnp.minimum(delay, astate.clock[None, :])
        else:
            delay = jnp.zeros((S, S), jnp.int32)
        rslot = (astate.clock[None, :] - delay) % R
        got_m, got_c, got_flag, got_seq = exchange.ring_read(
            ring_m, ring_c, ring_flag, ring_seq, rslot)

        # Alg. 1's per-message guard: a delivery whose seq lags what its
        # in-slot already applied is a reordered stale message — drop it
        # (equal seq re-applies the identical payload: idempotent).
        dst = jnp.arange(S)[:, None, None]
        cur = astate.last_seq[dst, tables.halo.recv_row,
                              tables.halo.recv_slot]
        ok = got_flag & (got_seq >= cur)
        in_m, in_c = exchange.scatter_halo(in_m, in_c, got_m, got_c, ok,
                                           tables.halo)
        last_seq = exchange.scatter_seq(astate.last_seq, got_seq, ok,
                                        tables.halo.recv_row,
                                        tables.halo.recv_slot)
        cnt = astate.applied.dtype
        stale = jnp.sum(got_flag & ~ok, axis=(1, 2)).astype(cnt)
        applied = jnp.sum(ok, axis=(1, 2)).astype(cnt)
        lag = jnp.sum(jnp.where(ok, delay[:, :, None], 0),
                      axis=(1, 2)).astype(cnt)

        # Peer-local update against the PER-SHARD clock (broadcast to
        # rows); scalar-vs-row t is value-identical while clocks agree.
        t_rows = jnp.repeat(astate.clock, B)
        fl = lambda a: a.reshape(S * B, *a.shape[2:])
        out_m, out_c, pending, last_send, _ = self._peer_update(
            fl(state.out_m), fl(state.out_c), fl(in_m), fl(in_c),
            fl(state.x_m), fl(state.x_c), fl(live), fl(state.last_send),
            fl(state.alive), t_rows, cfg=cfg)
        sh = lambda a: a.reshape(S, B, *a.shape[1:])
        pending = sh(pending)
        # Fresh postings advance their out-slot's sequence number.
        out_seq = jnp.where(pending, astate.out_seq + 1, astate.out_seq)
        state = state._replace(
            out_m=sh(out_m), out_c=sh(out_c), in_m=in_m, in_c=in_c,
            pending=pending, last_send=sh(last_send),
            t=state.t + 1, msgs=state.msgs + sent.astype(state.msgs.dtype),
            rng=rng)
        return astate._replace(
            sync=state, clock=astate.clock + 1, out_seq=out_seq,
            last_seq=last_seq, ring_m=ring_m, ring_c=ring_c,
            ring_flag=ring_flag, ring_seq=ring_seq,
            stale_drops=astate.stale_drops + stale,
            applied=astate.applied + applied,
            delay_sum=astate.delay_sum + lag)

    def _run_async_block(self, astate: AsyncShardedState, tables: DeviceTopo,
                         k: int) -> AsyncShardedState:
        return jax.lax.fori_loop(
            0, k, lambda _, st: self._cycle_async(st, tables), astate)

    def async_in_flight(self, astate: AsyncShardedState) -> jax.Array:
        """Conservative device-side bool: could any ring publication
        still be delivered by a future bounded-stale read?

        A slot published at sender time c is readable until c+staleness;
        of the R live slots only the oldest (about to be overwritten,
        index ``(clock+1) % R``) has aged past every admissible delay.
        At staleness=0 nothing lingers.  "Conservative" because a
        flagged entry may already be superseded (its seq below the
        receiver's last) — quiescence checks treat it as in flight
        anyway and converge once the ring ages it out.
        """
        R = astate.ring_flag.shape[0]
        if R == 1:
            return jnp.zeros((), bool)
        oldest = (astate.clock + 1) % R  # (S,) per src shard
        live = (jnp.arange(R)[:, None] != oldest[None, :])  # (R, S_src)
        return jnp.any(astate.ring_flag & live[:, :, None, None])

    def async_lag_stats(self, astate: AsyncShardedState) -> dict:
        """Host-side staleness summary (one device sync): applied
        cross-shard messages, their mean realized delay in cycles, and
        the cumulative seq-guarded stale-drop count."""
        applied = int(jnp.sum(astate.applied))
        return {
            "applied": applied,
            "stale_drops": int(jnp.sum(astate.stale_drops)),
            "mean_delay": (float(jnp.sum(astate.delay_sum)) / applied
                           if applied else 0.0),
        }

    # -- one cycle, collective (per-shard block inside shard_map) ----------
    def _cycle_block(self, state: ShardedState,
                     tables: "_LocalTables") -> ShardedState:
        """Body on LOCAL (1, B, ...) blocks; comms via all_gather/all_to_all."""
        cfg, axis = self.cfg, self._axis
        B, D = self.B, self.D
        mask, rev, tgt_row, tgt_pos, intra, halo = tables
        sq = lambda a: a[0]  # local blocks carry a leading (1, ...) axis

        key2 = jax.random.split(state.rng[0])
        rng, kdrop = key2[0][None], key2[1]
        alive = sq(state.alive)
        alive_all = jax.lax.all_gather(alive, axis, tiled=True)  # (S*B,)
        nbr_alive = _take_slots(alive_all, tgt_pos)
        live = mask & alive[:, None] & nbr_alive
        send = sq(state.pending) & live
        if cfg.drop_rate > 0.0:
            keep = jax.random.uniform(kdrop, (B, D))
            delivered = send & (keep >= cfg.drop_rate)
        else:
            delivered = send
        sent = jnp.sum(send)

        out_m, out_c = sq(state.out_m), sq(state.out_c)
        # Intra edges as the receive-side gather (see _cycle_full).
        in_m = _intra_deliver(sq(state.in_m), out_m, delivered, tgt_row,
                              rev, intra)
        in_c = _intra_deliver(sq(state.in_c), out_c, delivered, tgt_row,
                              rev, intra)

        buf_m, buf_c, flag = exchange.gather_block(
            out_m, out_c, delivered, halo.send_row, halo.send_slot,
            halo.send_ok)
        wire = self._wire
        if wire.stateful:
            em, ec = sq(state.wire_err_m), sq(state.wire_err_c)
            g_em, g_ec = em[halo.send_row, halo.send_slot], \
                ec[halo.send_row, halo.send_slot]
            payload, n_em, n_ec = wire.encode(buf_m, buf_c, flag, g_em, g_ec)
            em, ec = exchange.scatter_err_block(
                em, ec, n_em, n_ec, halo.send_row, halo.send_slot,
                halo.send_ok)
            err_m, err_c = em[None], ec[None]
        else:
            payload, _, _ = wire.encode(buf_m, buf_c, flag)
            err_m, err_c = state.wire_err_m, state.wire_err_c
        payload = tuple(exchange.collective_all_to_all(p, axis)
                        for p in payload)
        buf_m, buf_c, flag = wire.decode(payload)
        in_m, in_c = exchange.scatter_block(in_m, in_c, buf_m, buf_c, flag,
                                            halo.recv_row, halo.recv_slot)

        out_m2, out_c2, pending, last_send, _ = self._peer_update(
            out_m, out_c, in_m, in_c, sq(state.x_m), sq(state.x_c), live,
            sq(state.last_send), alive, state.t)
        ex = lambda a: a[None]
        return state._replace(
            out_m=ex(out_m2), out_c=ex(out_c2), in_m=ex(in_m), in_c=ex(in_c),
            pending=ex(pending), last_send=ex(last_send),
            t=state.t + 1,
            msgs=state.msgs + sent.astype(state.msgs.dtype)[None],
            rng=rng, wire_err_m=err_m, wire_err_c=err_c)

    def _run_block_collective(self, state: ShardedState, tables: DeviceTopo,
                              k: int):
        from jax.sharding import PartitionSpec as P
        sh, repl = P(self._axis), P()
        err_sp = sh if state.wire_err_m is not None else None
        spec = ShardedState(sh, sh, sh, sh, sh, sh, sh, sh, sh, repl, sh, sh,
                            err_sp, err_sp)

        def local(state, mask, rev, tgt_row, tgt_pos, intra, *halo):
            local_t = _LocalTables(mask[0], rev[0], tgt_row[0], tgt_pos[0],
                                   intra[0],
                                   partition.HaloTables(*(a[0] for a in halo)))
            return jax.lax.fori_loop(
                0, k, lambda _, st: self._cycle_block(st, local_t), state)

        f = jax.shard_map(
            local, mesh=self._mesh,
            in_specs=(spec,) + (sh,) * 10,
            out_specs=spec, check_vma=False)
        return f(state, tables.mask, tables.rev, tables.tgt_row,
                 tables.tgt_pos, tables.intra, *tables.halo)

    # -- driver ------------------------------------------------------------
    def run(self, state, cycles: int):
        """Advance ``cycles`` cycles, ``cycles_per_dispatch`` per jit call.

        Accepts a :class:`ShardedState` (synchronous cycles) or an
        :class:`AsyncShardedState` (bounded-staleness gossip cycles) and
        returns the same kind.  Async runs additionally publish
        ``engine_async_*`` staleness gauges when the tracker is not the
        Noop — reading the device counters costs one host sync per
        ``run`` call, which the Noop path (and therefore the overlap
        benchmarks) never pays.

        Each jit call is an ``engine.dispatch`` span in the tracker: wall
        time, ``k``, suite/fused attributes, the halo ``transport``
        ("all_to_all" under a mesh, "gather" fallback), the per-dispatch
        cross-shard traffic (``halo_bytes`` / ``cut_edges`` attrs, plus
        per-shard ``engine_shard_halo_bytes_total`` counters and
        ``engine_shard_cut_edges`` gauges for non-noop trackers), and the
        compiled-variant delta (``recompiled``) accumulated into the
        registry's ``engine_dispatch_recompiles_total`` counter.
        """
        from repro.obs import NoopTracker, jit_cache_size

        is_async = isinstance(state, AsyncShardedState)
        run_jit = self._run_async_jit if is_async else self._run_jit
        k = max(1, self.ecfg.cycles_per_dispatch)
        transport = "all_to_all" if self._mesh is not None else "gather"
        # Host-side traffic model of the halo exchange: what the ACTIVE
        # wire format serializes per ordered shard pair per cycle
        # (wire_pair_bytes) — dense rows under "exact", ragged occupied
        # widths under the compact family, so compact/quantized modes are
        # not charged for padding or halo_slack headroom.  Recomputed per
        # run() — the tables are tiny and apply_membership may have
        # rewritten them.
        st = self.stopo
        counts = np.asarray(st.halo.send_ok).sum(axis=-1)  # (S, S) slots
        cuts = (st.mask & ~st.intra).reshape(self.S, -1).sum(axis=1)
        d_dim = (state.sync if is_async else state).x_m.shape[-1]
        pair = self.wire_pair_bytes(d_dim)  # (S, S) bytes per cycle
        shard_bytes = pair.sum(axis=1)  # per src shard
        total_bytes = int(pair.sum())
        wire_w = int(self._tables.halo.send_ok.shape[-1])
        publish = not isinstance(self.tracker, NoopTracker)
        done = 0
        while done < cycles:
            step = min(k, cycles - done)
            before = jit_cache_size(run_jit)
            with self.tracker.span("engine.dispatch", k=step,
                                   suite=self.suite.name,
                                   mode="async" if is_async else "sync",
                                   transport=transport) as sp:
                state = run_jit(state, self._tables, k=step)
                after = jit_cache_size(run_jit)
                if (before is not None and after is not None
                        and after > before):
                    sp.set("recompiled", after - before)
                    self.tracker.counter(
                        "engine_dispatch_recompiles_total",
                        "jit cache growth across engine run dispatches").inc(
                            after - before)
                sp.set("fused", self.dispatch_info["fused"])
                sp.set("wire", self._wire.name)
                sp.set("halo_bytes", total_bytes * step)
                sp.set("cut_edges", int(cuts.sum()) // 2)
                if publish:
                    halo_c = self.tracker.counter(
                        "engine_shard_halo_bytes_total",
                        "cross-shard halo traffic per shard in "
                        "wire-format bytes (active EngineConfig.wire "
                        "serialization of the send tables)")
                    cut_g = self.tracker.gauge(
                        "engine_shard_cut_edges",
                        "directed cross-shard edge slots per shard")
                    pad_g = self.tracker.gauge(
                        "engine_halo_padding_frac",
                        "fraction of the shipped halo width that is "
                        "send_ok-masked padding, per ordered shard pair "
                        "(waste the compact wire family removes)")
                    for s in range(self.S):
                        halo_c.inc(int(shard_bytes[s]) * step,
                                   shard=str(s), transport=transport)
                        cut_g.set(int(cuts[s]), shard=str(s))
                        for tdst in range(self.S):
                            if tdst != s and pair[s, tdst] > 0:
                                pad_g.set(
                                    1.0 - counts[s, tdst] / wire_w,
                                    src=str(s), dst=str(tdst))
            done += step
        if is_async and publish:
            # Staleness surfaced as gauges (cumulative totals live in
            # the state itself, so a fresh tracker still sees them).
            lag = self.async_lag_stats(state)
            self.tracker.gauge(
                "engine_async_staleness_mean",
                "mean realized halo delay (cycles) of applied "
                "cross-shard messages, cumulative").set(lag["mean_delay"])
            self.tracker.gauge(
                "engine_async_stale_drops_total",
                "cross-shard deliveries dropped by the per-message "
                "seq guard (reordered/superseded), cumulative").set(
                    lag["stale_drops"])
            self.tracker.gauge(
                "engine_async_applied_total",
                "cross-shard messages applied, cumulative").set(
                    lag["applied"])
        return state

    @staticmethod
    def _base(state) -> ShardedState:
        """The sync :class:`ShardedState` under either state kind."""
        return state.sync if isinstance(state, AsyncShardedState) else state

    def drain_msgs(self, state):
        """Read-and-reset the device send counter: (state', exact int).

        The per-shard counter is int32 without x64; draining at every
        metrics check keeps the device-side count within one check
        interval (bounded by n*D*interval) while the host total stays
        exact at any run length.
        """
        base = self._base(state)
        total = int(jnp.sum(base.msgs))
        base = base._replace(msgs=jnp.zeros_like(base.msgs))
        if isinstance(state, AsyncShardedState):
            return state._replace(sync=base), total
        return base, total

    # -- observers ---------------------------------------------------------
    def _metrics_impl(self, state: ShardedState, tables: DeviceTopo,
                      eps=1e-9, decide=None):
        """Unjitted metrics body; ``decide``/``eps`` may be per-query
        (traced) overrides when the service vmaps this over its query axis.
        Returns ``(acc, quiescent, correct-in-original-order, want)``."""
        decide = decide if decide is not None else self.decide
        S, B = self.S, self.B
        fl = lambda a: a.reshape(S * B, *a.shape[2:])
        nbr_alive = _take_slots(state.alive.reshape(S * B), tables.tgt_pos)
        live = fl(tables.mask & state.alive[..., None] & nbr_alive)
        x_m, x_c = fl(state.x_m), fl(state.x_c)
        alive = fl(state.alive)
        s = stopping.status(x_m, x_c, fl(state.out_m), fl(state.out_c),
                            fl(state.in_m), fl(state.in_c), live)
        gx = wvs.WV(jnp.sum(jnp.where(alive[:, None], x_m, 0.0), axis=0),
                    jnp.sum(jnp.where(alive, x_c, 0.0), axis=0))
        want = decide(wvs.vec(gx, eps)[None])[0]
        got = decide(wvs.vec(s, eps))
        correct = (got == want) & alive
        acc = jnp.sum(correct) / jnp.maximum(jnp.sum(alive), 1)
        a = stopping.agreements(fl(state.out_m), fl(state.out_c),
                                fl(state.in_m), fl(state.in_c))
        viol = stopping.violations_alg1(decide, s, a, live, eps)
        quiescent = ~jnp.any(fl(state.pending) & live) & ~jnp.any(viol)
        return acc, quiescent, correct[self._pos], want  # original order

    def metrics(self, state, eps: float = 1e-9):
        """(accuracy, quiescent, correct-mask in original order) — the same
        numbers :func:`repro.core.lss.metrics` reports.  For an async
        state the quiescence bit additionally requires an empty ring
        (:meth:`async_in_flight`): a message still deliverable at a
        bounded-stale read could wake a peer back up."""
        if isinstance(state, AsyncShardedState):
            acc, quiescent, correct = self._metrics_jit(
                state.sync, self._tables, eps=eps)[:3]
            return acc, quiescent & ~self.async_in_flight(state), correct
        return self._metrics_jit(state, self._tables, eps=eps)[:3]

    def total_msgs(self, state):
        return jnp.sum(self._base(state).msgs)

    def _audit_impl(self, state: ShardedState, tables: DeviceTopo, eps=1e-9,
                    decide=None, sample_mod=1, sample_phase=0):
        """Unjitted audit body: flatten the shard layout into the core
        layout and delegate to :func:`repro.core.lss.audit_impl`.

        ``tgt_pos`` IS the flat-neighbor table (``alive.reshape(S*B)
        [tgt_pos]`` is how :meth:`_metrics_impl` reads neighbor liveness),
        and ``rev`` holds the reverse slot at the target row, so the flat
        ``(nbr, mask, rev)`` triple satisfies the slot involution the core
        reductions are built on — including across shard boundaries.  In
        async mode the halo slots' in/out pairing is relaxed by the
        bounded-staleness ring, so they move to the in-flight side of the
        conservation ledger and out of the bitwise edge check
        (``settled_ok=intra``); :meth:`_audit_async_impl` covers the
        transport books instead.  ``decide``/``eps`` may be per-query
        (traced) overrides when the service vmaps this.
        """
        decide = decide if decide is not None else self.decide
        S, B = self.S, self.B
        fl = lambda a: a.reshape(S * B, *a.shape[2:])
        flat_topo = lss.TopoArrays(nbr=fl(tables.tgt_pos),
                                   mask=fl(tables.mask), rev=fl(tables.rev))
        flat_state = lss.LSSState(
            out_m=fl(state.out_m), out_c=fl(state.out_c),
            in_m=fl(state.in_m), in_c=fl(state.in_c),
            x_m=fl(state.x_m), x_c=fl(state.x_c),
            pending=fl(state.pending), last_send=fl(state.last_send),
            alive=fl(state.alive), t=state.t, msgs=jnp.sum(state.msgs),
            rng=state.rng[0])
        # A lossy wire relaxes the halo slots the same way async mode
        # does: delivered values differ from the sender's copy (by the
        # quantization bound), so cross-shard slots move to the measured
        # in-flight side and out of the bitwise edge check, and the
        # conservation rounding model widens by the wire's documented
        # per-component error bound (quant_eps).
        relaxed = self.ecfg.async_mode or self._wire.lossy
        settled_ok = fl(tables.intra) if relaxed else None
        return lss.audit_impl(flat_state, flat_topo, decide, eps=eps,
                              sample_mod=sample_mod,
                              sample_phase=sample_phase,
                              settled_ok=settled_ok,
                              tol_rel_extra=self._wire.quant_eps)

    def _audit_async_impl(self, astate: AsyncShardedState,
                          tables: DeviceTopo):
        """Async-monotonicity reductions over the transport books.

        ``snd[src, dst, h]`` is the sender-side out-slot counter — the
        supremum of every seq that slot has ever stamped into flight.  Two
        invariants follow: the receiver's last *applied* seq never exceeds
        it (``seq_bad``), and no live ring publication carries a stamp
        beyond it (``ring_bad``).  Either count going positive means a
        per-link sequence number regressed — the exact fault Alg. 1's
        monotone guard assumes away.
        """
        S = self.S
        h = tables.halo
        snd = jax.vmap(lambda sq, r, sl: sq[r, sl])(
            astate.out_seq, h.send_row, h.send_slot)  # (S_src, S_dst, H)
        cur = astate.last_seq[jnp.arange(S)[:, None, None],
                              h.recv_row, h.recv_slot]  # (S_dst, S_src, H)
        ok = jnp.swapaxes(h.send_ok, 0, 1)
        seq_bad = jnp.sum(ok & (cur > jnp.swapaxes(snd, 0, 1)))
        ring_bad = jnp.sum(astate.ring_flag & h.send_ok[None]
                           & (astate.ring_seq > snd[None]))
        return dict(seq_bad=seq_bad, ring_bad=ring_bad,
                    stale_drops=jnp.sum(astate.stale_drops),
                    in_flight=self.async_in_flight(astate))

    def audit(self, state, eps: float = 1e-9, sample_mod: int = 1,
              sample_phase: int = 0) -> dict:
        """Host-side audit read: raw invariant reductions as a dict of
        Python scalars.  Accepts either state kind; an async state adds
        the seq-monotonicity counters and the cumulative stale-drop total
        (reconciled against ``engine_async_stale_drops_total`` by
        :mod:`repro.obs.audit`).  One jit dispatch (+1 for async books);
        the sampling knobs are traced, so changing them never recompiles.
        """
        raw = dict(self._audit_jit(
            self._base(state), self._tables, eps=eps,
            sample_mod=jnp.asarray(sample_mod, jnp.int32),
            sample_phase=jnp.asarray(sample_phase, jnp.int32)))
        if isinstance(state, AsyncShardedState):
            raw.update(self._audit_async_jit(state, self._tables))
        return {k: v.item() for k, v in raw.items()}

    def to_lss_state(self, state) -> lss.LSSState:
        """Unpermute into a core :class:`LSSState` (parity tests, debug).
        Accepts either state kind (async transport books are dropped)."""
        state = self._base(state)
        S, B = self.S, self.B
        take = lambda a: a.reshape(S * B, *a.shape[2:])[self._pos]
        return lss.LSSState(
            out_m=take(state.out_m), out_c=take(state.out_c),
            in_m=take(state.in_m), in_c=take(state.in_c),
            x_m=take(state.x_m), x_c=take(state.x_c),
            pending=take(state.pending), last_send=take(state.last_send),
            alive=take(state.alive), t=state.t,
            msgs=jnp.sum(state.msgs), rng=state.rng[0])

    def place_lss_state(self, snap: lss.LSSState) -> ShardedState:
        """Inverse of :meth:`to_lss_state`: place a core-layout state into
        this engine's shard layout.

        The placement recipe is exactly :meth:`init`'s (init values
        everywhere, then scatter the logical rows through ``new_of_old``),
        so the result is bitwise what a fresh ``shard_topology`` + re-init
        of the same logical state produces.  ``snap`` may cover fewer
        rows / degree slots than this engine's capacity (a snapshot taken
        before a regrow): missing rows and slots stay at init values.

        Not carried row-for-row: the aggregate send counter lands on
        shard 0 (totals — the only thing consumers read — are preserved)
        and the per-shard drop-RNG keys are re-derived by splitting
        ``snap.rng`` (delivery semantics are unaffected at
        ``drop_rate=0``; a lossy run resumes on a fresh drop stream —
        :meth:`migrate_from` between equal shard counts carries the
        per-shard keys verbatim instead, keeping epochs bitwise
        invisible to the drop sequence).
        """
        S, B, D = self.S, self.B, self.D
        n1 = snap.alive.shape[0]
        if n1 > self.n:
            raise ValueError(f"snapshot covers {n1} rows > capacity {self.n}")
        D1 = snap.out_c.shape[-1]
        if D1 > D:
            raise ValueError(f"snapshot has {D1} degree slots > {D}")
        pos = self._pos[:n1]
        d = snap.x_m.shape[-1]
        dt = snap.x_m.dtype
        return ShardedState(
            out_m=jnp.zeros((S * B, D, d), dt).at[pos, :D1]
            .set(snap.out_m).reshape(S, B, D, d),
            out_c=jnp.zeros((S * B, D), dt).at[pos, :D1]
            .set(snap.out_c).reshape(S, B, D),
            in_m=jnp.zeros((S * B, D, d), dt).at[pos, :D1]
            .set(snap.in_m).reshape(S, B, D, d),
            in_c=jnp.zeros((S * B, D), dt).at[pos, :D1]
            .set(snap.in_c).reshape(S, B, D),
            x_m=jnp.zeros((S * B, d), dt).at[pos].set(snap.x_m)
            .reshape(S, B, d),
            x_c=jnp.zeros((S * B,), dt).at[pos].set(snap.x_c).reshape(S, B),
            pending=jnp.zeros((S * B, D), bool).at[pos, :D1]
            .set(snap.pending).reshape(S, B, D),
            last_send=jnp.full((S * B,), lss.COLD_TIMER, jnp.int32).at[pos]
            .set(snap.last_send.astype(jnp.int32)).reshape(S, B),
            alive=jnp.zeros((S * B,), bool).at[pos].set(snap.alive)
            .reshape(S, B),
            t=jnp.asarray(snap.t, jnp.int32),
            msgs=jnp.zeros((S,), lss.counter_dtype()).at[0]
            .set(jnp.asarray(snap.msgs, lss.counter_dtype())),
            rng=jax.random.split(snap.rng, S),
            wire_err_m=(jnp.zeros((S, B, D, d), jnp.float32)
                        if self._wire.stateful else None),
            wire_err_c=(jnp.zeros((S, B, D), jnp.float32)
                        if self._wire.stateful else None),
        )

    def migrate_from(self, old: "ShardedLSS",
                     state: ShardedState) -> ShardedState:
        """Move ``old``'s state into THIS engine's layout (one epoch).

        Gather/scatter across :func:`repro.engine.partition.migrate_rows`
        — equivalent to ``place_lss_state(old.to_lss_state(state))`` but
        named for what re-partition epochs (regrow, edge-cut rebalance)
        actually do.  Broadcasts over leading (query) axes, which the
        core-layout detour cannot (``to_lss_state`` is single-state).
        """
        # src gathers each logical row out of the old layout; the dst
        # half of the map (this engine's new_of_old) is applied by
        # place_lss_state's scatter below.
        src, _ = partition.migrate_rows(old.part, self.part)
        src = jnp.asarray(src)
        batch = state.x_c.shape[:-2]

        def move(a):
            flat = a.reshape(*batch, old.S * old.B, *a.shape[len(batch) + 2:])
            return jnp.take(flat, src, axis=len(batch))

        snap = lss.LSSState(
            out_m=move(state.out_m), out_c=move(state.out_c),
            in_m=move(state.in_m), in_c=move(state.in_c),
            x_m=move(state.x_m), x_c=move(state.x_c),
            pending=move(state.pending), last_send=move(state.last_send),
            alive=move(state.alive), t=state.t,
            msgs=jnp.sum(state.msgs, axis=-1), rng=state.rng[..., 0, :])
        place = self.place_lss_state
        for _ in batch:
            place = jax.vmap(place)
        placed = place(snap)
        if self._wire.stateful and state.wire_err_m is not None:
            # Error feedback rides the migration row-for-row: a peer's
            # unshipped quantization debt must survive the epoch or the
            # convergence guarantee of error feedback breaks at every
            # regrow/rebalance.  Slots are copied as-is (out-slot
            # coordinates are partition-independent per logical row).
            em, ec = move(state.wire_err_m), move(state.wire_err_c)
            S, B, D = self.S, self.B, self.D
            n1, D1 = em.shape[len(batch)], em.shape[len(batch) + 1]
            pos = self._pos[:n1]
            d = em.shape[-1]

            def _place_err(em1, ec1):
                zm = jnp.zeros((S * B, D, d), em1.dtype)
                zc = jnp.zeros((S * B, D), ec1.dtype)
                return (zm.at[pos, :D1].set(em1).reshape(S, B, D, d),
                        zc.at[pos, :D1].set(ec1).reshape(S, B, D))

            pe = _place_err
            for _ in batch:
                pe = jax.vmap(pe)
            pm, pc = pe(em, ec)
            placed = placed._replace(wire_err_m=pm, wire_err_c=pc)
        if old.S == self.S:
            # Drop-RNG continuity: with an equal shard count the (S, 2)
            # per-shard key array transfers verbatim, so a regrow /
            # rebalance epoch is bitwise INVISIBLE to the message-drop
            # sequence (shard s keeps drawing the stream it was on).  A
            # shard-count change has no faithful key mapping — only then
            # does place_lss_state's re-split apply.
            placed = placed._replace(rng=state.rng)
        return placed
