"""Balance correction (Sec. IV): Thm. 8 and the weight-distribution schemes.

When the stopping rule fails at ``p_i``, the peer computes new outgoing
messages ``X'_ij`` so that afterwards all agreements equal its new status
(Eq. 1: ``vec(A'_ij) = vec(S'_i)``).  Thm. 8 shows the solution family:

    A'_ij = (|A'_ij| / |T_i|) (.) T_i,
    T_i   = X_ii (+) (+)_k 2 (.) X_ki                      (full, Eq. 3)
    T_i   = S_i (+) (+)_{k in V_i} A_ik                    (selective, Eq. 8)

and the *uniform weight distribution* (Eq. 5 / Eq. 10) picks

    |A'_ij| = |A_ij| + (|S_i| - beta) / (2 |V_i|),

which halves ``|S_i|`` (down to the ``beta`` floor) per correction.  The
message realizing a chosen agreement is ``X'_ij = A'_ij (-) X_ji``.

These are pure formula functions in moment form, shared by the simulator
(:mod:`repro.core.lss`), the Pallas kernel oracle
(:mod:`repro.kernels.ref`), and the tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import wvs

__all__ = [
    "selective_target",
    "new_agreement_weights",
    "corrected_messages",
]


def _safe(c, eps):
    return jnp.where(jnp.abs(c) > eps, c, 1.0)


def selective_target(s: wvs.WV, a: wvs.WV, v_set, eps: float = 1e-9) -> wvs.WV:
    """T_i = S_i (+) (+)_{k in V_i} A_ik  (Eq. 8's normalization target).

    ``s``: (n, d)-moment WV;  ``a``: (n, D, d)-moment WV;  ``v_set``: bool
    (n, D).  With ``v_set = mask`` (all neighbors) this equals the full
    Thm.-8 target ``X_ii (+) (+)_k 2 (.) X_ki`` because
    S_i (+) (+)_k A_ik = X_ii (+) (+)_k (X_ki - X_ik) (+) (+)_k (X_ik + X_ki).
    """
    t_m = s.m + wvs.slot_sum(jnp.where(v_set[..., None], a.m, 0.0))
    t_c = s.c + wvs.slot_sum(jnp.where(v_set, a.c, 0.0))
    return wvs.WV(t_m, t_c)


def new_agreement_weights(s_c, a_c, v_set, beta: float):
    """|A'_ij| = |A_ij| + (|S_i| - beta) / (2 |V_i|) on the violating set."""
    nv = jnp.maximum(jnp.sum(v_set, axis=1), 1)  # |V_i|, guard empty
    inc = (s_c - beta) / (2.0 * nv.astype(s_c.dtype))
    return a_c + inc[:, None]


def corrected_messages(
    s: wvs.WV,
    a: wvs.WV,
    in_m,
    in_c,
    v_set,
    beta: float,
    eps: float = 1e-9,
):
    """One Alg.-1 correction: new out-messages on ``v_set`` slots.

    Returns ``(out_m', out_c')`` *only for the v_set slots* (callers blend
    with the previous messages via ``jnp.where``).  Implements

        X'_ij = ( ((|S|-beta)/(2|V|) + |A_ij|) / |T| ) (.) T  (-)  X_ji.
    """
    t = selective_target(s, a, v_set, eps)
    w_new = new_agreement_weights(s.c, a.c, v_set, beta)  # (n, D)
    scale = w_new / _safe(t.c, eps)[:, None]
    new_a_m = scale[..., None] * t.m[:, None, :]
    new_a_c = scale * t.c[:, None]
    return new_a_m - in_m, new_a_c - in_c
