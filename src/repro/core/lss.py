"""LSS — Local Source Selection in general network graphs (Alg. 1).

The peersim-style synchronous simulation of the paper's algorithm,
vectorized over all peers as JAX arrays and fully ``jit``-compiled,
including the selective-correction do-while (a ``lax.while_loop``).

State layout (n peers, D = max degree slots, d dims; moment form):

    out_m/out_c   (n,D,d)/(n,D)  X_ij — latest message content per out-slot
    in_m/in_c     (n,D,d)/(n,D)  X_ji — latest message received per slot
    x_m/x_c       (n,d)/(n,)     X_ii — local input
    pending       (n,D) bool     out-slots changed and not yet delivered
    last_send     (n,) int32     cycle of the peer's last send (the ell timer)
    alive         (n,) bool      churn mask

One :func:`cycle` =
  1. deliver pending messages through the reverse-slot gather, dropping each
     independently with probability ``drop_rate`` (dropped messages are
     *lost*, never retried — the paper's loss model);
  2. recompute S_i / A_ij, evaluate Alg. 1's violation sets;
  3. peers with violations (and a cold ``ell`` timer) run the selective
     correction do-while (Sec. IV-C2, Eq. 10) — or the uniform policy
     (Eq. 5) if configured — and post new messages on the violating slots.

Messages are counted per send (paper's "normalized messages" = sends per
link per cycle).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import correction, stopping, topology, wvs

__all__ = [
    "LSSConfig", "TopoArrays", "LSSState", "init_state", "cycle",
    "cycle_impl", "clear_slots", "pad_bucket", "metrics", "metrics_impl",
    "audit_impl", "counter_dtype", "suite_hooks", "live_mask",
    "mirror_slots", "COLD_TIMER",
]

# Send-timer value of a peer that has never sent: far enough in the past
# that the ell-cycle resend timer fires on the first eligible cycle.
# Every layer that (re)initializes ``last_send`` — init, joins, regrow
# padding, snapshot reconcile — uses this one value, so "cold" is a
# single bitwise-comparable constant across core, engine and service.
COLD_TIMER = -(10 ** 6)


def pad_bucket(*arrays):
    """Pad same-length index arrays to the next power-of-two length by
    repeating their last entry.

    Membership boundary edits (:func:`clear_slots`, alive/x scatters) are
    idempotent, so the repeats are harmless — and bucketing the lengths
    means XLA compiles each scatter a bounded number of times instead of
    once per distinct event-batch size, which otherwise dominates the
    boundary cost under sustained churn.
    """
    arrays = tuple(np.asarray(a) for a in arrays)
    m = max(1, int(arrays[0].shape[0]))
    size = 1 << (m - 1).bit_length()
    pad = lambda a: np.concatenate(
        [a, np.repeat(a[-1:], size - a.shape[0], axis=0)], axis=0)
    return tuple(pad(a) for a in arrays)


def counter_dtype():
    """Exact dtype for cumulative message counters.

    float32 loses integer exactness past 2^24 sends — a threshold million-
    peer runs cross within a handful of cycles.  int64 is exact to 2^63 when
    x64 is enabled; otherwise jax lowers it to int32 (exact to 2^31).  The
    sim/engine drivers drain the device counter into a host Python int at
    every metrics check, so the device-side count only ever spans one check
    interval (bounded by n*D*check_every << 2^31) and the reported totals
    are exact at any run length.
    """
    return jnp.int64 if jax.config.jax_enable_x64 else jnp.int32


class LSSConfig(NamedTuple):
    """Simulator knobs.

    ``beta``/``ell``/``eps`` are *traceable*: they only enter arithmetic,
    so :func:`cycle_impl` accepts them as jax scalars — this is what lets
    the service layer vmap a query axis with per-query knobs.  ``policy``,
    ``drop_rate`` and ``max_corr_iters`` are structural (they change the
    traced program: branch choice, drop branch, loop bound) and must stay
    Python values.
    """

    beta: float = 1e-3  # minimum-weight floor on |S_i| (Sec. IV-C)
    ell: int = 1  # min cycles between a peer's sends (Alg. 1)
    drop_rate: float = 0.0  # i.i.d. message-loss probability
    policy: str = "selective"  # "selective" (Eq. 10) | "uniform" (Eq. 5)
    max_corr_iters: int = 0  # 0 = use max degree D
    eps: float = 1e-9


class TopoArrays(NamedTuple):
    nbr: jax.Array  # int32 (n, D)
    mask: jax.Array  # bool  (n, D) — static link validity
    rev: jax.Array  # int32 (n, D)

    @classmethod
    def from_topology(cls, t: topology.Topology) -> "TopoArrays":
        # jnp.array (forced copy), NOT jnp.asarray: a DynTopology mutates
        # its numpy buffers in place, and CPU jax may zero-copy-alias
        # numpy memory — an aliased table would let an asynchronously
        # executing dispatch read post-mutation data.  (Immutable
        # Topologies pay one extra host copy; correctness wins.)
        return cls(jnp.array(t.nbr), jnp.array(t.mask), jnp.array(t.rev))


class LSSState(NamedTuple):
    out_m: jax.Array
    out_c: jax.Array
    in_m: jax.Array
    in_c: jax.Array
    x_m: jax.Array
    x_c: jax.Array
    pending: jax.Array
    last_send: jax.Array
    alive: jax.Array
    t: jax.Array  # current cycle (int32)
    msgs: jax.Array  # cumulative messages sent (exact int, see counter_dtype)
    rng: jax.Array


def init_state(topo: TopoArrays, inputs: wvs.WV, seed: int = 0,
               alive=None) -> LSSState:
    """Fresh all-quiescent state (S_i = X_ii, empty message slots).

    ``alive`` (optional bool (n,)) seeds the churn mask — a capacity-padded
    :class:`~repro.core.topology.DynTopology` passes its ``present`` mask
    so spare rows start dead; default: every peer alive.
    """
    n, D = topo.nbr.shape
    d = inputs.m.shape[-1]
    dt = inputs.m.dtype
    alive = (jnp.ones((n,), bool) if alive is None
             else jnp.array(alive, bool))  # copy: caller may mutate theirs
    return LSSState(
        out_m=jnp.zeros((n, D, d), dt),
        out_c=jnp.zeros((n, D), dt),
        in_m=jnp.zeros((n, D, d), dt),
        in_c=jnp.zeros((n, D), dt),
        x_m=inputs.m,
        x_c=inputs.c,
        pending=jnp.zeros((n, D), bool),
        last_send=jnp.full((n,), COLD_TIMER, jnp.int32),
        alive=alive,
        t=jnp.zeros((), jnp.int32),
        msgs=jnp.zeros((), counter_dtype()),
        rng=jax.random.PRNGKey(seed),
    )


@jax.jit
def _clear_slots_impl(state: LSSState, rows, slots) -> LSSState:
    return state._replace(
        out_m=state.out_m.at[..., rows, slots, :].set(0.0),
        out_c=state.out_c.at[..., rows, slots].set(0.0),
        in_m=state.in_m.at[..., rows, slots, :].set(0.0),
        in_c=state.in_c.at[..., rows, slots].set(0.0),
        pending=state.pending.at[..., rows, slots].set(False),
    )


def clear_slots(state: LSSState, rows, slots) -> LSSState:
    """Scrub the messaging state of the given ``(peer, slot)`` coordinates.

    Dynamic membership reuses degree slots: when an edge is removed (and
    later a new one claims the freed slot) the out/in message moments,
    pending flag — everything the old link left behind — must go back to
    the empty-slot state, or the new link would start from a stale
    agreement.  Works on a single state or a query-batched one (leading
    axes broadcast).  The five scatters run as ONE jitted program — under
    sustained churn the per-edit eager dispatches were the dominant
    boundary cost.
    """
    return _clear_slots_impl(state, jnp.asarray(rows, jnp.int32),
                             jnp.asarray(slots, jnp.int32))


def live_mask(topo: TopoArrays, alive: jax.Array) -> jax.Array:
    """Valid slots between two live peers (churn = failure of all links)."""
    # Gathered slot-major, as in mirror_slots.
    return topo.mask & alive[:, None] & alive[topo.nbr.T].T


def mirror_slots(a: jax.Array, topo: TopoArrays) -> jax.Array:
    """``a[nbr[i, k], rev[i, k]]`` for every slot ``(i, k)`` of a per-slot
    array ``a`` (n, D, ...): the value at each slot's mirror across its
    edge, i.e. where a message into that slot comes from.

    The gather runs slot-major, peers along the minor axis.  TPU compilers
    take about a second for that form at 10^5 peers, and minutes for the
    peer-major one (a gather whose output's minor axis is the D slots).

    Trailing component axes are gathered one (n, D) plane at a time and
    stacked back.  Gathered whole, each index's window spans the
    components too: on a TPU v5e the d=2 axis then sets a 2-row tile,
    and the call cost 18x the single-plane gather's at half the values.
    """
    n, D = topo.nbr.shape
    if a.ndim > 2:
        planes = a.reshape(n, D, -1)
        return jnp.stack([mirror_slots(planes[..., j], topo)
                          for j in range(planes.shape[-1])],
                         axis=-1).reshape(a.shape)
    src = (topo.rev * n + topo.nbr).T  # (D, n) flat slot-major sources
    lanes = jnp.moveaxis(a, (0, 1), (-1, -2))  # (..., D, n)
    flat = lanes.reshape(*lanes.shape[:-2], D * n)
    return jnp.moveaxis(flat[..., src], (-1, -2), (0, 1))


def _deliver(state: LSSState, topo: TopoArrays, drop_rate: float, key):
    """Move pending out-messages into the recipients' in-slots.

    Message (i,k) lands at (nbr[i,k], rev[i,k]).  Because ``rev`` makes
    the slot map an involution (``nbr[nbr[i,k], rev[i,k]] == i``), the
    same delivery reads as: in-slot (j,r) *receives from* its unique
    source slot (nbr[j,r], rev[j,r]).  The receive formulation is a
    gather, which XLA vectorizes where the equivalent scatter serializes
    — same values in the same slots, bitwise.
    """
    with jax.named_scope("deliver"):
        live = live_mask(topo, state.alive)
        send = state.pending & live
        if drop_rate > 0.0:
            keep = jax.random.uniform(key, send.shape) >= drop_rate
            delivered = send & keep
        else:
            delivered = send
        # Did my source post a message that survived?  (Padding slots
        # alias arbitrary sources — mask them out on the receiver side.)
        got = mirror_slots(delivered, topo) & topo.mask
        in_m = jnp.where(got[..., None], mirror_slots(state.out_m, topo),
                         state.in_m)
        in_c = jnp.where(got, mirror_slots(state.out_c, topo), state.in_c)
        sent = jnp.sum(send)
        return state._replace(
            in_m=in_m,
            in_c=in_c,
            pending=jnp.zeros_like(state.pending),
            msgs=state.msgs + sent.astype(state.msgs.dtype),
        ), sent


def _violations(decide, s, a, live, eps):
    return stopping.violations_alg1(decide, s, a, live, eps)


def _correction_loop(decide, state, topo, live, active, cfg: LSSConfig,
                     status_viol=None, corrected=None, entry=None):
    """Alg. 1's do-while, vectorized across peers.

    The corrected messages for a violating set V_i are a pure function of
    the *loop-entry* state (oldS_i, the entry agreements A0, the received
    X_ji) — Eq. 10 distributes ``(|oldS| - beta)/2`` over V_i exactly once,
    keeping ``|S'_i| = (|oldS_i| + beta)/2 >= beta``.  The do-while is a
    fixed-point iteration that only *grows* V_i: recompute the would-be
    correction from scratch with the larger V_i until no new slot violates.
    (Re-incrementing already-corrected weights each iteration would leak
    another ``(|oldS|-beta)/2`` of weight per iteration and can drive
    ``|S_i|`` negative — a subtle mis-reading of Alg. 1 that destabilizes
    the computation on high-degree graphs.)

    ``status_viol(out_m, out_c) -> (S: WV, viol)`` and
    ``corrected(old_s, a0, in_m, in_c, v) -> (new_m, new_c)`` are pluggable
    so the sharded engine can route the same loop through the fused Pallas
    kernels; the defaults are the reference :mod:`stopping` /
    :mod:`correction` formulas.  ``entry=(old_s, a0, viol0)`` hands in the
    loop-entry status/agreements/violations when the caller has already
    computed them (every caller has — it needed ``viol0`` for the
    ``active`` test), saving one full status/violation evaluation per
    cycle.

    Returns ``(out_m, out_c, v, did_send, iters, ran)`` — ``iters`` is
    the do-while's fixed-point iteration count (scalar int32), the
    convergence-effort number telemetry aggregates into histograms, and
    ``ran`` the peers still running summed over those passes (scalar
    int32): of the ``iters * n`` peer-passes the loop computes, the ones
    whose result can change.
    """
    n, D = topo.nbr.shape
    if status_viol is None:
        def status_viol(out_m, out_c):
            s = stopping.status(state.x_m, state.x_c, out_m, out_c,
                                state.in_m, state.in_c, live)
            a = stopping.agreements(out_m, out_c, state.in_m, state.in_c)
            return s, _violations(decide, s, a, live, cfg.eps)
    if corrected is None:
        def corrected(old_s, a0, in_m, in_c, v):
            return correction.corrected_messages(
                old_s, a0, in_m, in_c, v, cfg.beta, cfg.eps)

    if entry is not None:
        old_s, a0, viol0 = entry
    else:
        old_s, viol0 = status_viol(state.out_m, state.out_c)
        a0 = stopping.agreements(state.out_m, state.out_c,
                                 state.in_m, state.in_c)
    v0 = viol0 & active[:, None]
    if cfg.policy == "uniform":
        # Eq. 5: a violating peer corrects *every* neighbor, not just V_i.
        any_viol = jnp.any(v0, axis=1)
        v0 = live & (active & any_viol)[:, None]
    running0 = active & jnp.any(v0, axis=1)
    max_iters = cfg.max_corr_iters or D

    def apply_v(v):
        """Corrected out-messages from the entry state, for slots in v."""
        new_m, new_c = corrected(old_s, a0, state.in_m, state.in_c, v)
        out_m = jnp.where(v[..., None], new_m, state.out_m)
        out_c = jnp.where(v, new_c, state.out_c)
        return out_m, out_c

    def body(carry):
        v, running, it, ran = carry
        out_m, out_c = apply_v(v)
        _, viol2 = status_viol(out_m, out_c)
        w = viol2 & running[:, None] & ~v
        grew = jnp.any(w, axis=1)
        return (v | w, running & grew, it + 1,
                ran + jnp.sum(running, dtype=jnp.int32))

    def cond(carry):
        _, running, it, _ = carry
        return jnp.any(running) & (it < max_iters)

    with jax.named_scope("correction"):
        zero = jnp.zeros((), jnp.int32)
        v, _, iters, ran = jax.lax.while_loop(
            cond, body, (v0, running0, zero, zero))
        out_m, out_c = apply_v(v)
        did_send = active & jnp.any(v, axis=1)
    return out_m, out_c, v, did_send, iters, ran


# Public alias: the engine re-runs the same do-while per shard block.
correction_loop = _correction_loop


def suite_hooks(suite, state: LSSState, live, regions, cfg: LSSConfig):
    """Bind a :class:`repro.kernels.suite.KernelSuite` to one state.

    Returns ``(status_viol, corrected, entry)`` in the shape
    :func:`correction_loop` consumes — the one adapter every layer (core
    cycle, engine ``_peer_update``, service vmapped dispatch) shares.
    ``regions`` is the packed :class:`~repro.core.regions.PackedSlot`
    whose table the suite's decide runs against; ``cfg.beta``/``cfg.eps``
    may be traced per-query scalars (they reach the kernels as data).
    """
    def status_viol(out_m, out_c):
        return suite.status_viol(state.x_m, state.x_c, out_m, out_c,
                                 state.in_m, state.in_c, live, regions,
                                 cfg.eps)

    def corrected(old_s, a0, in_m, in_c, v):
        return suite.corrected(old_s, a0, in_m, in_c, v, cfg.beta, cfg.eps)

    with jax.named_scope("status"):
        s, viol = status_viol(state.out_m, state.out_c)
        a0 = stopping.agreements(state.out_m, state.out_c,
                                 state.in_m, state.in_c)
    return status_viol, corrected, (s, a0, viol)


def cycle_impl(state: LSSState, topo: TopoArrays, cfg: LSSConfig, decide,
               gate=None, suite=None, regions=None, with_stats=False):
    """Untraced body of :func:`cycle` — the query-batchable form.

    Unlike :func:`cycle` this takes ``decide`` explicitly and is not jitted,
    so it composes with ``vmap``/``scan``: the service layer maps it over a
    *query axis* where ``cfg.beta``/``cfg.ell``/``cfg.eps`` are traced
    per-query scalars and ``decide`` closes over per-query (traced) region
    parameters.  ``cfg.policy``/``cfg.drop_rate``/``cfg.max_corr_iters``
    must remain Python values (they select the traced program).

    ``gate`` (optional bool, broadcastable to (n,)) implements masked-slot
    semantics: where False the peer may not *initiate* sends this cycle —
    a padding query slot whose state starts quiescent therefore never
    posts a message and its ``msgs`` counter stays exactly zero, while the
    cycle/RNG bookkeeping still advances in lockstep with the live slots.

    ``suite`` + ``regions`` (a :class:`repro.kernels.suite.KernelSuite`
    and a packed :class:`~repro.core.regions.PackedSlot`) route the hot
    loop — status/violations and the Eq.-10 correction — through that
    suite (e.g. the fused Pallas kernels) instead of ``decide``-based
    formulas; ``decide`` may then be None.  Because the packed table and
    the knobs are traced data, a vmapped query axis batches the kernels
    into a leading grid dimension and slot updates never recompile.

    ``with_stats=True`` (a Python static: it selects the return arity)
    additionally returns the correction loop's iteration count and its
    running-peer sum — ``(state', sent_now, corr_iters, corr_running)``
    — so instrumented callers get the convergence-effort numbers from
    the same compiled program; the default 2-tuple contract is unchanged.
    """
    rng, kdrop = jax.random.split(state.rng)
    state = state._replace(rng=rng)
    state, _ = _deliver(state, topo, cfg.drop_rate, kdrop)

    with jax.named_scope("deliver"):
        live = live_mask(topo, state.alive)
    status_viol = corrected = None
    if suite is not None:
        if regions is None:
            raise ValueError("cycle_impl(suite=...) needs packed `regions`")
        status_viol, corrected, entry = suite_hooks(
            suite, state, live, regions, cfg)
        s, _a0, viol = entry
        # decide (possibly None) is unused downstream: correction_loop
        # only consults it through the default hooks, which are supplied.
    else:
        with jax.named_scope("status"):
            s = stopping.status(
                state.x_m, state.x_c, state.out_m, state.out_c, state.in_m,
                state.in_c, live
            )
            a = stopping.agreements(state.out_m, state.out_c, state.in_m,
                                    state.in_c)
            viol = _violations(decide, s, a, live, cfg.eps)
        entry = (s, a, viol)
    timer_ok = (state.t - state.last_send) >= cfg.ell
    active = state.alive & timer_ok & jnp.any(viol, axis=1)
    if gate is not None:
        active = active & gate

    out_m, out_c, v, did_send, corr_iters, corr_running = _correction_loop(
        decide, state, topo, live, active, cfg, status_viol=status_viol,
        corrected=corrected, entry=entry)
    pending = state.pending | (v & did_send[:, None])
    last_send = jnp.where(did_send, state.t, state.last_send)
    sent_now = jnp.sum(v & did_send[:, None])

    state = state._replace(
        out_m=out_m, out_c=out_c, pending=pending, last_send=last_send,
        t=state.t + 1,
    )
    if with_stats:
        return state, sent_now, corr_iters, corr_running
    return state, sent_now


@functools.partial(jax.jit, static_argnames=("cfg", "decide", "suite"))
def cycle(state: LSSState, topo: TopoArrays, centers: jax.Array, cfg: LSSConfig,
          decide=None, suite=None):
    """One synchronous simulator cycle.  Returns (state', sent_this_cycle).

    ``suite`` (a registered :class:`~repro.kernels.suite.KernelSuite`,
    static) routes the hot loop through that suite's fused path with
    ``centers`` packed as a Voronoi slot; ``decide`` remains the general
    escape hatch for opaque decision functions (reference formulas only).
    """
    from . import regions as _regions

    if suite is not None:
        if decide is not None:
            # Mirror the engine's contract: never drop a requested
            # kernel path silently.
            raise ValueError(
                "cycle() cannot honor both `decide` and `suite` — an "
                "opaque decide cannot feed the packed kernels; drop one "
                "(or pack the family and use cycle_impl(suite=, "
                "regions=))")
        return cycle_impl(state, topo, cfg, None, suite=suite,
                          regions=_regions.PackedSlot.voronoi(centers))
    if decide is None:
        decide = lambda v: _regions.decide_voronoi(v, centers)
    return cycle_impl(state, topo, cfg, decide)


def metrics_impl(state: LSSState, topo: TopoArrays, decide, eps=1e-9):
    """Unjitted, decide-pluggable body of :func:`metrics`.

    Like :func:`cycle_impl` this is the query-batchable form: ``decide``
    may close over traced per-query region parameters and ``eps`` may be a
    traced scalar, so the service layer vmaps it over its query axis.
    Returns ``(accuracy, quiescent, correct_mask, want)`` — ``want`` is
    the ground-truth region id ``f(vec((+)X))``, which per-tenant
    telemetry reports alongside accuracy.
    """
    live = live_mask(topo, state.alive)
    s = stopping.status(
        state.x_m, state.x_c, state.out_m, state.out_c, state.in_m, state.in_c, live
    )
    gx = wvs.WV(
        jnp.sum(jnp.where(state.alive[:, None], state.x_m, 0.0), axis=0),
        jnp.sum(jnp.where(state.alive, state.x_c, 0.0), axis=0),
    )
    want = decide(wvs.vec(gx, eps)[None])[0]
    got = decide(wvs.vec(s, eps))
    correct = (got == want) & state.alive
    acc = jnp.sum(correct) / jnp.maximum(jnp.sum(state.alive), 1)

    a = stopping.agreements(state.out_m, state.out_c, state.in_m, state.in_c)
    viol = stopping.violations_alg1(decide, s, a, live, eps)
    quiescent = ~jnp.any(state.pending & live) & ~jnp.any(viol)
    return acc, quiescent, correct, want


def metrics(state: LSSState, topo: TopoArrays, centers: jax.Array,
            eps: float = 1e-9):
    """(accuracy, quiescent, correct_mask): fraction of live peers whose
    f(vec(S_i)) equals f(vec((+)X over live peers)), and quiescence."""
    from . import regions as _regions

    decide = lambda v: _regions.decide_voronoi(v, centers)
    acc, quiescent, correct, _ = metrics_impl(state, topo, decide, eps)
    return acc, quiescent, correct


def audit_impl(state: LSSState, topo: TopoArrays, decide, eps=1e-9,
               sample_mod=1, sample_phase=0, settled_ok=None,
               tol_rel_extra=0.0):
    """Device-side invariant reductions for the audit plane.

    Evaluates the paper's algebraic invariants as pure reductions over the
    state — everything returned is a scalar, so the service layer folds the
    whole dict into its existing batched observe round-trip (vmapped over
    the query axis) at zero extra host transfers.

    **Conservation.**  By the slot involution, summing the status identity
    ``S_i = X_ii (+) (+)_k (X_ki (-) X_ik)`` over alive peers telescopes:
    every *settled* slot's in-message is bitwise the reverse slot's
    out-message (the correction loop only mutates ``out`` where it sets
    ``pending``, and delivery copies verbatim), so those terms cancel
    exactly and only in-flight slots (``pending`` on the reverse side, or
    excluded from ``settled_ok``) contribute.  The residual

        ``(+)_alive S_i  (-)  (+)_alive X_ii  (-)  (+)_infl (in (-) out_rev)``

    is therefore pure rounding noise, bounded by the classic summation
    bound ``u * N_terms * L1-mass`` — any physical conservation break (a
    corrupted knowledge vector, a halo repair applied twice) shows up far
    above ``tol``.

    **Edge symmetry.**  On settled slots ``A_ij = X_ij (+) X_ji`` and
    ``A_ji = X_ji (+) X_ij`` are the same two IEEE additions in either
    order — commutativity makes them *bitwise* equal, so the monitor
    counts exact mismatches (no tolerance).  ``sample_mod``/``sample_phase``
    rotate a ``1/sample_mod`` slot sample for scale (traced ints — changing
    them never recompiles); the default checks every slot.

    **Stopping soundness.**  Recomputes quiescence from the reference
    formulas and counts alive peers whose Def.-4 balance condition fails
    (``stop_bad``).  The count is returned *ungated*: because Alg. 1's
    violating set is strictly stronger than Def. 4, a state this very
    function calls quiescent always has ``stop_bad == 0`` — the host pairs
    ``stop_bad`` with the quiescence bit the *serving path* claimed, so a
    fused-kernel or stale metrics path reporting quiescence on a state
    whose balance conditions fail is caught.

    ``settled_ok`` (bool (n, D) or None) restricts "settled" further — the
    bounded-staleness engine passes its intra-shard mask so halo slots,
    whose in/out pairing is legitimately relaxed by the seq-number
    protocol, move to the in-flight side of the ledger instead of being
    asserted bitwise.  A quantized halo wire passes the same mask for the
    same reason: a delivered in-message legitimately differs from the
    reverse out-slot by the (error-feedback-bounded) quantization error.

    ``tol_rel_extra`` widens the conservation rounding model for lossy
    transports: the engine passes its wire format's documented
    per-component relative error bound (``Wire.quant_eps`` — ``1/254``
    for int8, ``2^-8`` for bf16), which joins the ``u``-scaled term so
    the same ``N_terms * L1-mass`` envelope covers quantization residue
    still in flight through the error-feedback state.  Zero (the
    default, and every exact/compact path) leaves the tolerance bitwise
    unchanged.

    Returns a dict of scalars: ``resid``/``tol``/``mag`` (conservation),
    ``edge_bad``/``edge_checked``, ``stop_bad``/``quiescent``, and
    ``live_slots``/``msgs``/``t`` passthroughs for the exact counter check
    host-side.
    """
    n, D = topo.nbr.shape
    live = live_mask(topo, state.alive)
    out_rev_m = mirror_slots(state.out_m, topo)
    out_rev_c = mirror_slots(state.out_c, topo)
    pend_rev = mirror_slots(state.pending, topo)

    s = stopping.status(
        state.x_m, state.x_c, state.out_m, state.out_c,
        state.in_m, state.in_c, live,
    )
    gx_m = jnp.sum(jnp.where(state.alive[:, None], state.x_m, 0.0), axis=0)
    gx_c = jnp.sum(jnp.where(state.alive, state.x_c, 0.0))

    infl = live & pend_rev
    if settled_ok is not None:
        infl = live & (pend_rev | ~settled_ok)
    sum_s_m = jnp.sum(jnp.where(state.alive[:, None], s.m, 0.0), axis=0)
    sum_s_c = jnp.sum(jnp.where(state.alive, s.c, 0.0))
    infl_k = infl[..., None]
    flight_m = jnp.sum(jnp.where(infl_k, state.in_m - out_rev_m, 0.0),
                       axis=(0, 1))
    flight_c = jnp.sum(jnp.where(infl, state.in_c - out_rev_c, 0.0))
    resid = jnp.maximum(
        jnp.max(jnp.abs(sum_s_m - gx_m - flight_m)),
        jnp.abs(sum_s_c - gx_c - flight_c),
    )
    mag = (
        jnp.sum(jnp.where(state.alive[:, None], jnp.abs(state.x_m), 0.0))
        + jnp.sum(jnp.where(state.alive, jnp.abs(state.x_c), 0.0))
        + jnp.sum(jnp.where(live[..., None],
                            jnp.abs(state.in_m) + jnp.abs(out_rev_m), 0.0))
        + jnp.sum(jnp.where(live,
                            jnp.abs(state.in_c) + jnp.abs(out_rev_c), 0.0))
    )
    u = jnp.finfo(state.x_m.dtype).eps
    tol = 1e-6 + (4.0 * u + tol_rel_extra) * (n * (D + 1)) * mag

    # Edge-agreement symmetry on settled slots (bitwise; rotating sample).
    settled = live & ~state.pending & ~pend_rev
    if settled_ok is not None:
        settled = settled & settled_ok
    mod = jnp.maximum(jnp.asarray(sample_mod, jnp.int32), 1)
    sm = ((jnp.arange(n * D, dtype=jnp.int32).reshape(n, D)
           + jnp.asarray(sample_phase, jnp.int32)) % mod) == 0
    check = settled & sm
    a_m = state.out_m + state.in_m
    a_c = state.out_c + state.in_c
    mismatch = ((jnp.any(a_m != mirror_slots(a_m, topo), axis=-1))
                | (a_c != mirror_slots(a_c, topo)))
    edge_bad = jnp.sum(check & mismatch)
    edge_checked = jnp.sum(check)

    a = stopping.agreements(state.out_m, state.out_c,
                            state.in_m, state.in_c)
    ok4 = stopping.def4_satisfied(decide, s, a, live, eps)
    stop_bad = jnp.sum(state.alive & ~ok4)
    viol = stopping.violations_alg1(decide, s, a, live, eps)
    quiescent = ~jnp.any(state.pending & live) & ~jnp.any(viol)

    return dict(
        resid=resid, tol=tol, mag=mag,
        edge_bad=edge_bad, edge_checked=edge_checked,
        stop_bad=stop_bad, quiescent=quiescent,
        live_slots=jnp.sum(live), msgs=state.msgs, t=state.t,
    )
