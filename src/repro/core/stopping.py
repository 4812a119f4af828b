"""The paper's local stopping rule (Def. 4) and quiescence predicates.

Def. 4: peer ``p_i`` can stop sending messages in the context of a convex
region ``R`` iff for every neighbor ``p_j``:

  * ``|A_ij| = 0``        or  ``vec(A_ij) in R``, and
  * ``|S_i - A_ij| = 0``  or  ``vec(S_i - A_ij) in R``,

with ``A_ij = X_ij (+) X_ji`` and
``S_i = X_ii (+) (+)_j (X_ji (-) X_ij)``.

Theorems 5+6 prove that in any network-wide stopping state (no messages in
flight), all ``vec(S_i)`` share one region ``R`` and ``vec((+)X) in R`` —
with **no cycle-freedom assumption**.  These predicates are used by the
algorithm (via the Alg.-1 violation set, see :mod:`repro.core.lss`), by the
tests (to assert final states are genuine stopping states), and by the mesh
monitor.

All functions are batched over peers and slots and work in moment form.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import wvs

__all__ = [
    "agreements",
    "status",
    "def4_satisfied",
    "violations_alg1",
]


def agreements(out_m, out_c, in_m, in_c) -> wvs.WV:
    """A_ij = X_ij (+) X_ji for every slot: (n, D, d) moments."""
    return wvs.WV(out_m + in_m, out_c + in_c)


def status(x_m, x_c, out_m, out_c, in_m, in_c, mask) -> wvs.WV:
    """S_i = X_ii (+) (+)_j (X_ji (-) X_ij), masked over valid slots."""
    mk = mask[..., None]
    s_m = x_m + wvs.slot_sum(jnp.where(mk, in_m - out_m, 0.0))
    s_c = x_c + wvs.slot_sum(jnp.where(mask, in_c - out_c, 0.0))
    return wvs.WV(s_m, s_c)


def def4_satisfied(decide, s: wvs.WV, a: wvs.WV, mask, eps: float = 1e-9):
    """Def. 4 per peer: True where the peer may stop sending.

    ``decide`` maps vectors (..., d) -> region ids; the rule is evaluated in
    the context of R = region of vec(S_i) (as Alg. 1 prescribes).
    Returns bool (n,).
    """
    region = decide(wvs.vec(s, eps))  # (n,)
    sa = wvs.WV(s.m[:, None, :] - a.m, s.c[:, None] - a.c)  # S_i (-) A_ij

    a_zero = jnp.abs(a.c) <= eps
    sa_zero = jnp.abs(sa.c) <= eps
    a_ok = a_zero | (decide(wvs.vec(a, eps)) == region[:, None])
    sa_ok = sa_zero | (decide(wvs.vec(sa, eps)) == region[:, None])
    slot_ok = (~mask) | (a_ok & sa_ok)
    return jnp.all(slot_ok, axis=1)


def violations_alg1(decide, s: wvs.WV, a: wvs.WV, mask, eps: float = 1e-9):
    """Alg. 1's violating set V_i, per slot (bool (n, D)).

    A slot violates iff ``f(vec(A_ij)) != f(vec(S_i))`` or
    ``f(vec(S_i - A_ij)) != f(vec(S_i))`` (weight-guarded), **or** the
    agreement still has zero weight.  The last clause is what bootstraps
    communication from the all-zero initial state (the earlier cycle-free
    algorithms do the same by sending X_ii to every neighbor at init):
    without it, Def. 4 is vacuously satisfied at initialization and no peer
    would ever send.  It also strengthens quiescent states so that Thm. 5's
    consensus argument applies to every link (each A_ij has weight and pins
    both endpoints to one region).
    """
    region = decide(wvs.vec(s, eps))  # (n,)
    sa = wvs.WV(s.m[:, None, :] - a.m, s.c[:, None] - a.c)
    a_zero = jnp.abs(a.c) <= eps
    sa_zero = jnp.abs(sa.c) <= eps
    a_bad = ~a_zero & (decide(wvs.vec(a, eps)) != region[:, None])
    sa_bad = ~sa_zero & (decide(wvs.vec(sa, eps)) != region[:, None])
    return (a_zero | a_bad | sa_bad) & mask
