"""Pallas TPU kernel: fused LSS per-peer state update (the simulator hot loop).

One pass over a block of peers computes, entirely in VMEM:

    S_i  = X_ii (+) sum_k mask * (X_ki (-) X_ik)        (status, moment form)
    A_ik = X_ik (+) X_ki                                 (agreements)
    f(vec(S)), f(vec(A)), f(vec(S (-) A))                (region decisions)
    viol = a_zero | f(A) != f(S) | f(S-A) != f(S)        (Alg.-1 V_i)

``f`` is the packed family decision (:func:`repro.kernels.region_decide.
packed_decide`); the ``meta`` row ``[kind, b, eps, beta]`` selects the
kind per call — traced data, so per-query families/knobs are
zero-recompile and ``jax.vmap`` turns the service's query axis into a
leading grid dimension with each slot's table resident in VMEM.

Unfused, this is 6+ HBM round-trips over the (n, D, d) message arrays per
cycle; fused it is one read + one small write.

Layout: peers run along the lanes — messages as ``(D, d, n)``, weights
and masks as ``(D, n)`` — in blocks of ``BLOCK_N`` peers, and the slot
sums run in slot order (as :func:`repro.core.wvs.slot_sum` does), so the
kernel computes the reference's float operations in the reference's
order.  VMEM per step ~ 2 * (4 D*8 + 4 D) * BN * 4 bytes (d pads to 8
sublanes): ~2 MiB at D=6, BN=512.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .region_decide import packed_decide

__all__ = ["lss_state_kernel", "lss_state_call"]

BLOCK_N = 512


def lss_state_kernel(x_m_ref, x_c_ref, out_m_ref, out_c_ref, in_m_ref,
                     in_c_ref, mask_ref, tab_ref, cn_ref, meta_ref,
                     s_m_ref, s_c_ref, viol_ref, dec_ref):
    o_m = out_m_ref[...]  # (D, d, BN)
    o_c = out_c_ref[...]  # (D, BN)
    i_m = in_m_ref[...]
    i_c = in_c_ref[...]
    msk = mask_ref[...].astype(jnp.float32) != 0  # (D, BN)
    tab, cn, meta = tab_ref[...], cn_ref[...], meta_ref[...]
    eps = meta[0, 2]
    D = o_c.shape[0]

    # --- status (slot order) and agreements (moment form) --------------
    acc_m = jnp.zeros(x_m_ref.shape, jnp.float32)
    acc_c = jnp.zeros(x_c_ref.shape, jnp.float32)
    for k in range(D):
        live = msk[k:k + 1]
        acc_m = acc_m + jnp.where(live, i_m[k] - o_m[k], 0.0)
        acc_c = acc_c + jnp.where(live, i_c[k:k + 1] - o_c[k:k + 1], 0.0)
    s_m = x_m_ref[...] + acc_m  # (d, BN)
    s_c = x_c_ref[...] + acc_c  # (1, BN)
    a_m = o_m + i_m  # (D, d, BN)
    a_c = o_c + i_c  # (D, BN)
    sa_m = s_m[None] - a_m
    sa_c = s_c - a_c

    # --- decisions --------------------------------------------------------
    dec_s = packed_decide(s_m[None], s_c, tab, cn, meta)  # (1, BN)
    dec_a = packed_decide(a_m, a_c, tab, cn, meta)  # (D, BN)
    dec_sa = packed_decide(sa_m, sa_c, tab, cn, meta)

    a_zero = jnp.abs(a_c) <= eps
    sa_zero = jnp.abs(sa_c) <= eps
    a_bad = ~a_zero & (dec_a != dec_s)
    sa_bad = ~sa_zero & (dec_sa != dec_s)
    viol = (a_zero | a_bad | sa_bad) & msk

    s_m_ref[...] = s_m
    s_c_ref[...] = s_c
    viol_ref[...] = viol.astype(jnp.int32)
    dec_ref[...] = dec_s


def lss_state_call(x_m, x_c, out_m, out_c, in_m, in_c, mask, tab, cn, meta,
                   *, interpret: bool):
    """Lane-dense inputs: x_m (d, n), x_c (1, n), out_m/in_m (D, d, n),
    out_c/in_c/mask (D, n).  Returns (s_m (d, n), s_c (1, n), viol int32
    (D, n), dec int32 (1, n))."""
    D, d, n = out_m.shape
    k1 = tab.shape[1]
    row = lambda r: pl.BlockSpec((r, BLOCK_N), lambda i: (0, i))
    msg = pl.BlockSpec((D, d, BLOCK_N), lambda i: (0, 0, i))
    whole = lambda *s: pl.BlockSpec(s, lambda i: (0,) * len(s))
    return pl.pallas_call(
        lss_state_kernel,
        grid=(pl.cdiv(n, BLOCK_N),),
        in_specs=[row(d), row(1), msg, row(D), msg, row(D), row(D),
                  whole(d, k1), whole(1, k1 - 1), whole(1, 4)],
        out_specs=[row(d), row(1), row(D), row(1)],
        out_shape=[
            jax.ShapeDtypeStruct((d, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((D, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
        ],
        interpret=interpret,
    )(x_m, x_c, out_m, out_c, in_m, in_c, mask, tab, cn, meta)
