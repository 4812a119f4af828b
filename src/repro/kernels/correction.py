"""Pallas TPU kernel: Eq.-10 balance-correction message computation.

For a block of peers with violating sets V_i, computes in one VMEM pass:

    T_i      = S_i (+) (+)_{k in V} A_ik           (selective target, Eq. 8)
    |A'_ik|  = |A_ik| + (|S_i| - beta) / (2 |V_i|)  (uniform distribution)
    X'_ik    = (|A'_ik| / |T_i|) (.) T_i  (-)  X_ki  (Eq. 10)

Everything is elementwise + a D-slot sum per peer, in slot order as the
reference (:func:`repro.core.correction.selective_target`) sums: VPU
work over lane-dense ``(D, d, n)`` message blocks, streamed through VMEM
once.  ``beta`` and ``eps`` arrive in the traced ``meta`` row ``[kind,
b, eps, beta]`` (see :mod:`.ops`), so per-query knob overrides never
recompile and the service query axis batches straight into a leading
grid dimension under ``vmap``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["correction_kernel", "correction_call"]

BLOCK_N = 512


def correction_kernel(s_m_ref, s_c_ref, a_m_ref, a_c_ref, in_m_ref, in_c_ref,
                      v_ref, meta_ref, o_m_ref, o_c_ref):
    s_m = s_m_ref[...]  # (d, BN)
    s_c = s_c_ref[...]  # (1, BN)
    a_m = a_m_ref[...]  # (D, d, BN)
    a_c = a_c_ref[...]  # (D, BN)
    i_m = in_m_ref[...]
    vf = v_ref[...].astype(jnp.float32)  # (D, BN)
    v = vf != 0
    eps, beta = meta_ref[0, 2], meta_ref[0, 3]
    D = a_c.shape[0]

    acc_m = jnp.zeros(s_m.shape, jnp.float32)
    acc_c = jnp.zeros(s_c.shape, jnp.float32)
    nv = jnp.zeros(s_c.shape, jnp.float32)
    for k in range(D):
        acc_m = acc_m + jnp.where(v[k:k + 1], a_m[k], 0.0)
        acc_c = acc_c + jnp.where(v[k:k + 1], a_c[k:k + 1], 0.0)
        nv = nv + vf[k:k + 1]
    t_m = s_m + acc_m
    t_c = s_c + acc_c
    nv = jnp.maximum(nv, 1.0)
    w_new = a_c + (s_c - beta) / (2.0 * nv)  # (D, BN)
    t_safe = jnp.where(jnp.abs(t_c) > eps, t_c, 1.0)
    scale = w_new / t_safe
    for k in range(D):
        o_m_ref[k] = scale[k:k + 1] * t_m - i_m[k]
    o_c_ref[...] = scale * t_c - in_c_ref[...]


def correction_call(s_m, s_c, a_m, a_c, in_m, in_c, v_set, meta,
                    *, interpret: bool):
    """Lane-dense inputs: s_m (d, n), s_c (1, n), a_m/in_m (D, d, n),
    a_c/in_c/v_set (D, n).  Returns (o_m (D, d, n), o_c (D, n))."""
    D, d, n = a_m.shape
    row = lambda r: pl.BlockSpec((r, BLOCK_N), lambda i: (0, i))
    msg = pl.BlockSpec((D, d, BLOCK_N), lambda i: (0, 0, i))
    return pl.pallas_call(
        correction_kernel,
        grid=(pl.cdiv(n, BLOCK_N),),
        in_specs=[row(d), row(1), msg, row(D), msg, row(D), row(D),
                  pl.BlockSpec((1, 4), lambda i: (0, 0))],
        out_specs=[msg, row(D)],
        out_shape=[
            jax.ShapeDtypeStruct((D, d, n), jnp.float32),
            jax.ShapeDtypeStruct((D, n), jnp.float32),
        ],
        interpret=interpret,
    )(s_m, s_c, a_m, a_c, in_m, in_c, v_set, meta)
