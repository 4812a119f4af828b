"""Pallas TPU kernel: the packed region decision f on lane-dense peers.

``argmin_k ||v - c_k||^2  ==  argmin_k (-2 v . c_k + ||c_k||^2)`` — the
per-peer Voronoi decision is k dot products plus a running argmin, and
the halfspace decision of the packed ``(kind, centers, cmask, w, b)``
representation (:mod:`repro.core.regions`) is one more dot product,
against the normal ``w``, compared with ``b``.  Masked (padding) center
slots carry ``+inf`` in the precomputed norm row and never win, exactly
as in :func:`repro.core.regions.decide_packed`.  A per-call ``meta`` row
``[kind, b, eps, beta]`` (see :mod:`.ops`) selects the family kind —
traced data, so per-query families and knobs never recompile, and
``jax.vmap`` batches a service query axis into a leading grid dimension.

Layout: peers run along the 128 lanes (``(d, n)`` blocks of ``BLOCK_N``
peers), so a statistic of d=2 coordinates costs two sublanes, not a
128-lane row.  The dot products run on the VPU in coordinate order — the
same float operations, in the same order, as the reference formulas —
so fused and reference decisions agree bit for bit on every backend.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["packed_decide", "region_decide_kernel", "region_decide_call"]

BLOCK_N = 1024


def packed_decide(m, c, tab, cn, meta):
    """Packed-family ids of a block of weighted vectors.

    ``m``: (R, d, BN) moments and ``c``: (R, BN) weights (the vector is
    ``m / c``, zero where ``|c| <= eps``); ``tab``: (d, k+1) =
    ``[centers^T | w]``; ``cn``: (1, k) center norms with +inf on masked
    slots; ``meta``: (1, 4) ``[kind, b, eps, beta]``.  Returns int32
    (R, BN).
    """
    d, k1 = tab.shape
    eps = meta[0, 2]
    keep = jnp.abs(c) > eps
    safe = jnp.where(keep, c, 1.0)
    v = [jnp.where(keep, m[:, j, :] / safe, 0.0) for j in range(d)]

    def dot(col):
        acc = v[0] * tab[0, col]
        for j in range(1, d):
            acc = acc + v[j] * tab[j, col]
        return acc

    best = -2.0 * dot(0) + cn[0, 0]
    vor = jnp.zeros(best.shape, jnp.int32)
    for col in range(1, k1 - 1):
        score = -2.0 * dot(col) + cn[0, col]
        better = score < best
        vor = jnp.where(better, col, vor)
        best = jnp.where(better, score, best)
    half = (dot(k1 - 1) >= meta[0, 1]).astype(jnp.int32)
    return jnp.where(meta[0, 0] == 0.0, vor, half)


def region_decide_kernel(v_ref, tab_ref, cn_ref, meta_ref, out_ref):
    v = v_ref[...][None]  # (1, d, BN)
    ones = jnp.ones((1, v.shape[-1]), jnp.float32)
    out_ref[...] = packed_decide(v, ones, tab_ref[...], cn_ref[...],
                                 meta_ref[...])


def region_decide_call(v, tab, cn, meta, *, interpret: bool):
    """v: (d, n); tab: (d, k+1); cn: (1, k); meta: (1, 4)
    -> (1, n) int32."""
    d, n = v.shape
    k1 = tab.shape[1]
    return pl.pallas_call(
        region_decide_kernel,
        grid=(pl.cdiv(n, BLOCK_N),),
        in_specs=[
            pl.BlockSpec((d, BLOCK_N), lambda i: (0, i)),
            pl.BlockSpec((d, k1), lambda i: (0, 0)),
            pl.BlockSpec((1, k1 - 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 4), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_N), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=interpret,
    )(v, tab, cn, meta)
