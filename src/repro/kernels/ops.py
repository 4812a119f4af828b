"""Jit'd public wrappers for the Pallas kernels.

Handle the lane-dense layout (callers pass peer-major ``(n, D, d)``
arrays; the kernels take ``(D, d, n)``, peers along the lanes, so a d=2
statistic is not padded to a 128-lane row in HBM), dtype normalization,
the packed-region table layout, and the execution mode: Mosaic on TPU,
``interpret=True`` on any other backend (the kernel bodies run as XLA
ops, the correctness path the CPU tests validate).  The kernels' grids
cover a trailing partial block, so no peer padding is copied either.

Region families arrive as a :class:`repro.core.regions.PackedSlot` (or
anything :func:`repro.core.regions.as_packed_slot` coerces: bare Voronoi
``(k, d)`` centers, ``VoronoiRegions``, ``HalfspaceRegions``).  The slot
is prepared into the kernel table layout:

* ``cthw`` (d, k+1): ``[centers^T | w]`` — the Voronoi dot products and
  the halfspace projection read one table;
* ``cn`` (1, k): center norms, ``+inf`` on masked padding slots (so a
  padded family decides bitwise like the unpadded one);
* ``meta`` (1, 4): ``[kind, b, eps, beta]`` — the family kind plus the
  traceable knobs.  Everything is traced DATA: swapping families or knobs
  between dispatches never recompiles, and ``jax.vmap`` batches a service
  query axis into a leading Pallas grid dimension.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import regions as _regions

from . import correction as _corr
from . import lss_state as _state
from . import region_decide as _dec

__all__ = ["region_decide", "lss_state", "correction", "prep_slot"]


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def prep_slot(region, eps=1e-9, beta=0.0):
    """Kernel table layout of one packed family: ``(cthw, cn, meta)``.

    ``eps``/``beta`` may be traced scalars; they ride in the meta row so
    per-query knob overrides stay zero-recompile.
    """
    slot = _regions.as_packed_slot(region)
    f32 = jnp.float32
    centers = slot.centers.astype(f32)
    cthw = jnp.concatenate([centers.T, slot.w.astype(f32)[:, None]],
                           axis=1)  # (d, k+1)
    cn = jnp.where(slot.cmask, _regions.coord_dot(centers, centers),
                   jnp.inf)[None, :]  # (1, k)
    meta = jnp.stack([
        slot.kind.astype(f32),
        slot.b.astype(f32),
        jnp.asarray(eps, f32),
        jnp.asarray(beta, f32),
    ]).reshape(1, 4)
    return cthw, cn, meta


@jax.jit
def region_decide(v, region):
    """Packed-family region ids, kernel-accelerated: (n, d) -> (n,) int32."""
    cthw, cn, meta = prep_slot(region)
    out = _dec.region_decide_call(v.astype(jnp.float32).T, cthw, cn, meta,
                                  interpret=_interpret())
    return out[0]


def _lanes(a):
    """Peer-major ``(n, ...)`` -> lane-dense ``(..., n)``."""
    return jnp.moveaxis(a.astype(jnp.float32), 0, -1)


@jax.jit
def lss_state(x_m, x_c, out_m, out_c, in_m, in_c, mask, region, eps=1e-9):
    """Fused S/A/violations/decision.  Peer-major moment-form inputs.

    Returns (s_m (n,d), s_c (n,), viol bool (n,D), decision (n,) int32).
    """
    cthw, cn, meta = prep_slot(region, eps=eps)
    s_m, s_c, viol, dec = _state.lss_state_call(
        _lanes(x_m), _lanes(x_c[:, None]), _lanes(out_m), _lanes(out_c),
        _lanes(in_m), _lanes(in_c), mask.T.astype(jnp.int8), cthw, cn, meta,
        interpret=_interpret())
    return s_m.T, s_c[0], viol.T.astype(bool), dec[0]


@jax.jit
def correction(s_m, s_c, a_m, a_c, in_m, in_c, v_set, beta=1e-3, eps=1e-9):
    """Eq.-10 corrected messages: returns (out_m' (n,D,d), out_c' (n,D)).

    ``beta``/``eps`` may be traced per-query scalars (they ride the meta
    row, not the compiled program).
    """
    f32 = jnp.float32
    meta = jnp.stack([jnp.zeros((), f32), jnp.zeros((), f32),
                      jnp.asarray(eps, f32),
                      jnp.asarray(beta, f32)]).reshape(1, 4)
    o_m, o_c = _corr.correction_call(
        _lanes(s_m), _lanes(s_c[:, None]), _lanes(a_m), _lanes(a_c),
        _lanes(in_m), _lanes(in_c), v_set.T.astype(jnp.int8), meta,
        interpret=_interpret())
    return jnp.moveaxis(o_m, -1, 0), o_c.T
