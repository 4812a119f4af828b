"""Weighted vector space (Def. 1 of the paper), in moment form.

The paper works with pairs ``<v, c>`` (vector, weight) under

    c1 (.) <v, c2>          = <v, c1*c2>                    (scalar mult)
    <v1,c1> (+) <v2,c2>     = <(c1 v1 + c2 v2)/(c1+c2), c1+c2>

We store the *moment* ``m = c * v`` instead of ``v``.  Under this change of
coordinates the weighted vector space is plain linear algebra:

    (+)  ->  elementwise +        (-)  ->  elementwise -
    c (.) <m, c2>  ->  <c*m, c*c2>

and the "vector part" is ``m / c`` (defined only when ``c != 0``), exactly
matching footnote 1 of the paper (``X (-) Y`` undefined at ``|X|=|Y|``).

Every theorem in the paper becomes a linear identity in moment form; mass
conservation (Thm. 3) is exact up to float summation error.

A ``WV`` pytree holds arbitrarily-batched weighted vectors: ``m`` has shape
``(*batch, d)`` and ``c`` has shape ``(*batch,)``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = [
    "WV",
    "wv",
    "zero",
    "add",
    "sub",
    "smul",
    "vec",
    "weight",
    "wsum",
    "slot_sum",
    "from_vector",
    "allclose",
]


class WV(NamedTuple):
    """A (batch of) weighted vector(s) in moment form."""

    m: jax.Array  # (*batch, d) moment = weight * vector
    c: jax.Array  # (*batch,)   weight

    @property
    def d(self) -> int:
        return self.m.shape[-1]

    def __add__(self, other: "WV") -> "WV":  # X (+) Y
        return add(self, other)

    def __sub__(self, other: "WV") -> "WV":  # X (-) Y
        return sub(self, other)

    def __rmul__(self, s) -> "WV":  # s (.) X
        return smul(s, self)


def wv(m, c) -> WV:
    """Build a WV from a moment array and a weight array."""
    m = jnp.asarray(m)
    c = jnp.asarray(c)
    return WV(m, c)


def from_vector(v, c) -> WV:
    """Build ``<v, c>`` from the paper's (vector, weight) coordinates."""
    v = jnp.asarray(v)
    c = jnp.asarray(c)
    return WV(v * c[..., None], c)


def zero(d: int, batch=(), dtype=jnp.float32) -> WV:
    """An identity element: any X0 with |X0| = 0 (here the canonical one)."""
    return WV(jnp.zeros((*batch, d), dtype), jnp.zeros(batch, dtype))


def add(x: WV, y: WV) -> WV:
    """The paper's (+): weighted average.  Moment form: elementwise sum."""
    return WV(x.m + y.m, x.c + y.c)


def sub(x: WV, y: WV) -> WV:
    """The paper's (-): X (-) Y = Z iff X = Y (+) Z."""
    return WV(x.m - y.m, x.c - y.c)


def smul(s, x: WV) -> WV:
    """The paper's (.): scales the weight, keeps the vector part.

    In moment form both components scale: c (.) <m, w> = <c m, c w>.
    """
    s = jnp.asarray(s)
    return WV(s[..., None] * x.m, s * x.c)


def vec(x: WV, eps: float = 0.0) -> jax.Array:
    """Vector part ``m / c``.  Where ``|c| <= eps`` returns 0 (guarded)."""
    safe = jnp.where(jnp.abs(x.c) > eps, x.c, 1.0)
    v = x.m / safe[..., None]
    return jnp.where((jnp.abs(x.c) > eps)[..., None], v, jnp.zeros_like(v))


def weight(x: WV) -> jax.Array:
    return x.c


def wsum(x: WV, axis=0) -> WV:
    """(+)-fold over an axis of a batched WV: the paper's big-oplus."""
    return WV(jnp.sum(x.m, axis=axis), jnp.sum(x.c, axis=axis))


def slot_sum(a: jax.Array) -> jax.Array:
    """Sum over the neighbor-slot axis (axis 1) in slot order.

    Every per-peer slot fold (status, correction target) goes through this
    one fixed order of float additions, so the reference formulas and the
    Pallas kernels give the same bits on every backend; ``jnp.sum`` leaves
    the order to the compiler, which picks a different one per backend and
    per fusion.
    """
    acc = jnp.zeros_like(a[:, 0])
    for j in range(a.shape[1]):
        acc = acc + a[:, j]
    return acc


def allclose(x: WV, y: WV, rtol=1e-5, atol=1e-6) -> jax.Array:
    return jnp.logical_and(
        jnp.allclose(x.m, y.m, rtol=rtol, atol=atol),
        jnp.allclose(x.c, y.c, rtol=rtol, atol=atol),
    )
