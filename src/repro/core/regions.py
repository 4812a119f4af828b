"""Convex region families for the thresholding problem (Problem 2).

A region family maps a vector in R^d to the index of the (convex,
non-overlapping) region containing it.  Two families cover the paper and the
training-monitor use cases:

* ``VoronoiRegions`` — the source-selection problem (Sec. V): regions are
  Voronoi cells of k option points; ``f(v) = argmin_c ||c - v||``.  Reduces
  to majority voting for C = {0, 1}.
* ``HalfspaceRegions`` — one hyperplane ``w . v >= b`` (two convex regions);
  the classic threshold-monitoring predicate (e.g. ``||g||^2 < tau`` on a
  statistics vector that carries the squared norm as a coordinate).

Decision functions are pure and vectorized: input (..., d) -> int32 (...).
``decide_voronoi`` uses the expansion ||v - c||^2 = ||v||^2 - 2 v.c + ||c||^2
with the dot products in coordinate order (:func:`coord_dot`), the same
float operations the Pallas kernel in ``repro.kernels.region_decide``
runs.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

__all__ = [
    "VoronoiRegions",
    "HalfspaceRegions",
    "PackedRegions",
    "PackedSlot",
    "coord_dot",
    "decide_voronoi",
    "decide_packed",
    "as_packed_slot",
    "KIND_VORONOI",
    "KIND_HALFSPACE",
]

KIND_VORONOI = 0
KIND_HALFSPACE = 1


def coord_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """``sum_j a[..., j] * b[..., j]``, one coordinate at a time.

    Every decision contraction goes through this fixed order of f32
    multiplies and adds, as the Pallas kernels' VPU loop does, so the
    reference and fused decisions agree bit for bit on every backend (a
    matmul would leave the passes and their order to the compiler).
    """
    acc = a[..., 0] * b[..., 0]
    for j in range(1, a.shape[-1]):
        acc = acc + a[..., j] * b[..., j]
    return acc


def decide_voronoi(v: jax.Array, centers: jax.Array) -> jax.Array:
    """argmin_k ||v - centers[k]||^2 for batched v: (..., d) -> int32 (...)."""
    # ||v||^2 is constant across candidates: argmin needs only the last terms.
    scores = -2.0 * coord_dot(v[..., None, :], centers) + coord_dot(
        centers, centers)
    return jnp.argmin(scores, axis=-1).astype(jnp.int32)


class VoronoiRegions(NamedTuple):
    """Voronoi cells of k centers — the source-selection region family."""

    centers: jax.Array  # (k, d)

    @property
    def k(self) -> int:
        return self.centers.shape[0]

    @property
    def d(self) -> int:
        return self.centers.shape[1]

    def decide(self, v: jax.Array) -> jax.Array:
        return decide_voronoi(v, self.centers)


class HalfspaceRegions(NamedTuple):
    """Two regions split by ``w . v >= b`` (region 1 = above threshold)."""

    w: jax.Array  # (d,)
    b: jax.Array  # ()

    @property
    def k(self) -> int:
        return 2

    @property
    def d(self) -> int:
        return self.w.shape[0]

    def decide(self, v: jax.Array) -> jax.Array:
        return (coord_dot(v, self.w) >= self.b).astype(jnp.int32)


RegionFamily = Callable[[jax.Array], jax.Array]


def decide_packed(v: jax.Array, kind, centers, cmask, w, b) -> jax.Array:
    """Decision function of ONE packed family on batched ``v`` (..., d).

    All parameters may be traced (this is the form the service vmaps over
    its query axis): ``kind`` scalar int32, ``centers`` (Kmax, d) with
    validity ``cmask`` (Kmax,), ``w`` (d,) / ``b`` () for the halfspace.
    Padding center slots are excluded by an +inf score, so a k-center
    Voronoi family padded to Kmax decides bitwise-identically to
    :func:`decide_voronoi` on the unpadded centers.
    """
    scores = -2.0 * coord_dot(v[..., None, :], centers) + coord_dot(
        centers, centers)
    scores = jnp.where(cmask, scores, jnp.inf)
    vor = jnp.argmin(scores, axis=-1).astype(jnp.int32)
    half = (coord_dot(v, w) >= b).astype(jnp.int32)
    return jnp.where(kind == KIND_VORONOI, vor, half)


class PackedSlot(NamedTuple):
    """ONE family in the packed ``(kind, centers, cmask, w, b)`` form.

    This is the currency every execution layer passes around: it is what
    :class:`PackedRegions` holds per query slot, what the fused Pallas
    kernels (:mod:`repro.kernels`) take as their region table, and what
    the engine/core fused paths build from a concrete family.  All fields
    may be traced — under the service's query-axis ``vmap`` each leaf is
    a per-slot slice of the (Q, ...) batch.  Field order matches
    :class:`PackedRegions` so ``PackedSlot(*packed_slice)`` works.
    """

    kind: jax.Array  # int32 ()  KIND_VORONOI | KIND_HALFSPACE
    centers: jax.Array  # (Kmax, d)
    cmask: jax.Array  # bool (Kmax,)
    w: jax.Array  # (d,)
    b: jax.Array  # ()

    @property
    def k_max(self) -> int:
        return self.centers.shape[0]

    @property
    def d(self) -> int:
        return self.centers.shape[1]

    @classmethod
    def voronoi(cls, centers) -> "PackedSlot":
        """Pack unpadded Voronoi centers (all-valid ``cmask``)."""
        centers = jnp.asarray(centers)
        k, d = centers.shape
        return cls(
            kind=jnp.asarray(KIND_VORONOI, jnp.int32),
            centers=centers,
            cmask=jnp.ones((k,), bool),
            w=jnp.zeros((d,), centers.dtype),
            b=jnp.zeros((), centers.dtype),
        )

    @classmethod
    def halfspace(cls, w, b, k_max: int = 1) -> "PackedSlot":
        w = jnp.asarray(w)
        return cls(
            kind=jnp.asarray(KIND_HALFSPACE, jnp.int32),
            centers=jnp.zeros((k_max, w.shape[0]), w.dtype),
            cmask=jnp.zeros((k_max,), bool),
            w=w,
            b=jnp.asarray(b, w.dtype),
        )

    def decide(self, v: jax.Array) -> jax.Array:
        return decide_packed(v, *self)


def as_packed_slot(region) -> PackedSlot:
    """Coerce a region family (or bare Voronoi centers) to a PackedSlot."""
    if isinstance(region, PackedSlot):
        return region
    if isinstance(region, VoronoiRegions):
        return PackedSlot.voronoi(region.centers)
    if isinstance(region, HalfspaceRegions):
        return PackedSlot.halfspace(region.w, region.b)
    arr = jnp.asarray(region)
    if arr.ndim == 2:  # bare (k, d) Voronoi centers
        return PackedSlot.voronoi(arr)
    raise TypeError(f"cannot pack region family {type(region)!r}")


class PackedRegions(NamedTuple):
    """A stackable, padded batch of Q region families (one per query slot).

    Fixed shapes — (Q, Kmax, d) centers etc. — make the batch a plain
    pytree: families can be written into / cleared from individual slots
    between dispatches without changing any traced shape, which is what
    lets the service admit/retire queries without recompiling.  Unused
    parameter blocks (e.g. ``w``/``b`` of a Voronoi slot) are zeros.
    """

    kind: jax.Array  # int32 (Q,)  KIND_VORONOI | KIND_HALFSPACE
    centers: jax.Array  # (Q, Kmax, d)
    cmask: jax.Array  # bool (Q, Kmax)
    w: jax.Array  # (Q, d)
    b: jax.Array  # (Q,)

    @property
    def q(self) -> int:
        return self.kind.shape[0]

    @property
    def k_max(self) -> int:
        return self.centers.shape[1]

    @property
    def d(self) -> int:
        return self.centers.shape[2]

    @classmethod
    def empty(cls, q: int, k_max: int, d: int,
              dtype=jnp.float32) -> "PackedRegions":
        """Q all-padding slots (every slot decides region 0 everywhere)."""
        return cls(
            kind=jnp.zeros((q,), jnp.int32),
            centers=jnp.zeros((q, k_max, d), dtype),
            cmask=jnp.zeros((q, k_max), bool),
            w=jnp.zeros((q, d), dtype),
            b=jnp.zeros((q,), dtype),
        )

    @classmethod
    def pack(cls, families, k_max: int | None = None) -> "PackedRegions":
        """Stack concrete families (Voronoi/Halfspace) into padded slots."""
        if not families:
            raise ValueError("pack() needs at least one family")
        d = families[0].d
        if k_max is None:
            k_max = max([f.k for f in families
                         if isinstance(f, VoronoiRegions)] or [1])
        out = cls.empty(len(families), k_max, d)
        for i, fam in enumerate(families):
            out = out.set(i, fam)
        return out

    def set(self, slot: int, family) -> "PackedRegions":
        """Write one family into ``slot`` (host-side, between dispatches)."""
        if isinstance(family, VoronoiRegions):
            k = family.k
            if k > self.k_max:
                raise ValueError(
                    f"family has {k} centers, slot capacity is {self.k_max}")
            if family.d != self.d:
                raise ValueError(f"family d={family.d} != packed d={self.d}")
            cent = jnp.zeros((self.k_max, self.d), self.centers.dtype
                             ).at[:k].set(family.centers)
            return self._replace(
                kind=self.kind.at[slot].set(KIND_VORONOI),
                centers=self.centers.at[slot].set(cent),
                cmask=self.cmask.at[slot].set(jnp.arange(self.k_max) < k),
                w=self.w.at[slot].set(0.0),
                b=self.b.at[slot].set(0.0),
            )
        if isinstance(family, HalfspaceRegions):
            if family.d != self.d:
                raise ValueError(f"family d={family.d} != packed d={self.d}")
            return self._replace(
                kind=self.kind.at[slot].set(KIND_HALFSPACE),
                centers=self.centers.at[slot].set(0.0),
                cmask=self.cmask.at[slot].set(False),
                w=self.w.at[slot].set(family.w),
                b=self.b.at[slot].set(family.b),
            )
        raise TypeError(f"unsupported region family: {type(family)!r}")

    def clear(self, slot: int) -> "PackedRegions":
        """Reset ``slot`` to padding."""
        return PackedRegions(
            kind=self.kind.at[slot].set(KIND_VORONOI),
            centers=self.centers.at[slot].set(0.0),
            cmask=self.cmask.at[slot].set(False),
            w=self.w.at[slot].set(0.0),
            b=self.b.at[slot].set(0.0),
        )

    def slot(self, i: int) -> PackedSlot:
        """One slot's packed parameters (indexable under tracing)."""
        return PackedSlot(self.kind[i], self.centers[i], self.cmask[i],
                          self.w[i], self.b[i])

    def decide_slot(self, slot: int) -> RegionFamily:
        """The decision function of one slot (host-side convenience)."""
        return self.slot(slot).decide
