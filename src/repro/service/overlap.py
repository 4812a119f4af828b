"""Overlapped host-boundary primitives for :class:`~repro.service.Service`.

jax dispatches asynchronously: a jitted call returns as soon as the
computation is *enqueued*, and the returned arrays are futures.  The
synchronous service tick throws that window away — it fences the
dispatch (telemetry ``np.asarray`` round-trips) before starting the next
boundary, so host work and device compute serialize:

    sync     |--boundary K--|--device K--|--boundary K+1--|--device K+1--|
    overlap  |--boundary K--|--device K----------|
                            |--boundary K+1------|--device K+1----------|

Overlap mode restructures the tick around three primitives:

* :class:`PendingWindow` — everything dispatch K's telemetry needs,
  captured at launch time: the observation futures (un-synced device
  arrays) plus an immutable host-side snapshot of the bookkeeping the
  records are built from (active slots, dispatch/cycle counters, control
  events).  The window is finished — synced and emitted — one tick
  later, while dispatch K+1 runs.  Functional state updates make this
  safe: the pytrees the window holds are never mutated in place, and
  device ops execute in enqueue order, so the window's reads always see
  dispatch K's outputs.
* :class:`DoubleBuffer` — the zero-recompile invariant made explicit.
  Each launch stages fresh ``QueryParams``/``DeviceTopo`` buffers while
  the previous pair is still referenced by the in-flight dispatch
  (immutability IS the double buffer); ``swap`` checks that the traced
  shapes/dtypes are unchanged, so a boundary edit that would silently
  recompile the hot dispatch raises instead.  Epochs legitimately
  reshape and declare it via :meth:`DoubleBuffer.invalidate`.
* :class:`StagedBuild` — an epoch's heavy host work (BFS re-partition +
  halo table construction) run on a background thread against an
  immutable topology snapshot.  The boundary polls :meth:`ready` and
  adopts the finished build at a later tick — catch-up is the same
  incremental journal repair live membership uses — instead of stalling
  the dispatch pipeline for the full rebuild.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax

__all__ = ["CorrStats", "PendingWindow", "DoubleBuffer", "StagedBuild",
           "BufferReshape"]


class CorrStats(NamedTuple):
    """The correction do-while's work in one dispatch (device arrays)."""

    iters: Any  # (Q,) passes each slot needed, summed over the K cycles
    running: Any  # (Q,) peers still running in those passes, summed
    # () passes the vmapped loop ran: it runs until every slot stops, so
    # per cycle the most over the Q slots, summed over the K cycles
    passes: Any


class PendingWindow(NamedTuple):
    """Dispatch K's un-finished telemetry: device futures + the host
    bookkeeping snapshot the records will be built from."""

    dispatch: int  # 1-based dispatch index (post-increment)
    t: int  # service cycle counter after this window's K cycles
    k: int  # cycles this dispatch ran
    acc: Any  # (Q,) device — per-slot accuracy
    quiescent: Any  # (Q,) device — per-slot quiescence
    want: Any  # (Q,) device — global correct region
    msgs: Any  # (Q,) device — per-slot sends this window
    corr: Any  # CorrStats of the dispatch — correction do-while work
    active: Tuple[Tuple[str, int], ...]  # (query_id, slot) at launch
    queued: Tuple[str, ...]  # waiting query ids at launch
    preempted: Tuple[str, ...]  # suspended query ids at launch
    topo_version: int  # applied topology version at launch
    edges: int  # live edge count at launch (msgs_per_link denominator)
    events: list  # control events swapped out at launch
    spans: dict  # boundary span seconds swapped out at launch
    counts: dict  # boundary work counts swapped out at launch
    audit: Any = None  # dict of (Q,) device audit reductions, or None
    # when this window was not sampled (scfg.audit_every)


class BufferReshape(RuntimeError):
    """A boundary changed a traced buffer shape without declaring an
    epoch — the next dispatch would silently recompile."""


def _signature(tree) -> tuple:
    """Traced (shape, dtype) signature of a pytree; non-array leaves
    (static ints etc.) contribute their value."""
    return tuple(
        (tuple(leaf.shape), str(leaf.dtype))
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype") else leaf
        for leaf in jax.tree_util.tree_leaves(tree))


class DoubleBuffer:
    """Front/back staging of the dispatch operands (params + topo).

    ``swap`` stages the buffers for the next launch while the previous
    pair stays alive inside the in-flight dispatch, and enforces the
    zero-recompile invariant: staged buffers must keep the traced
    signature of the pair they replace.  An epoch (regrow / rebalance /
    halo-width growth) calls :meth:`invalidate` first — the one place a
    reshape, and therefore a recompile, is expected.
    """

    __slots__ = ("front", "swaps", "epochs", "_sig")

    def __init__(self):
        self.front: Optional[tuple] = None  # buffers of the in-flight dispatch
        self.swaps = 0  # shape-stable swaps performed
        self.epochs = 0  # declared invalidations (expected reshapes)
        self._sig: Optional[tuple] = None

    def invalidate(self) -> None:
        """Declare an epoch: the next swap may (and probably will)
        reshape, and the one recompile it costs is intentional."""
        self.epochs += 1
        self._sig = None
        self.front = None

    def swap(self, *bufs) -> None:
        """Stage ``bufs`` as the next dispatch's operands.

        Raises :class:`BufferReshape` if their traced signature differs
        from the in-flight pair's without an :meth:`invalidate` between —
        the canary for accidental recompiles on the steady-state path.
        """
        sig = _signature(bufs)
        if self._sig is not None and sig != self._sig:
            raise BufferReshape(
                "dispatch buffer shapes changed outside an epoch "
                "(undeclared recompile hazard); call invalidate() from "
                "the epoch path if this reshape is intentional")
        self._sig = sig
        self.front = bufs
        self.swaps += 1


class StagedBuild:
    """One background build of an epoch's host-side product.

    Runs ``fn`` (pure host work over an immutable snapshot — typically
    partition + halo-table construction producing a fresh engine) on a
    daemon thread started immediately.  The boundary polls
    :meth:`ready` and calls :meth:`take` to adopt; ``take`` joins, so
    calling it early degrades to the synchronous wait rather than
    racing.  Exceptions are captured and re-raised at ``take`` time —
    the adopter's fallback path (synchronous rebuild) handles them.
    """

    __slots__ = ("label", "_fn", "_result", "_error", "_thread")

    def __init__(self, fn: Callable[[], Any], label: str = ""):
        self.label = label
        self._fn = fn
        self._result: Any = None
        self._error: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._run, name=f"staged-build-{label or 'epoch'}",
            daemon=True)
        self._thread.start()

    def _run(self) -> None:
        try:
            self._result = self._fn()
        except BaseException as e:  # surfaced at take()
            self._error = e

    def ready(self) -> bool:
        """True once the build finished (successfully or not)."""
        return not self._thread.is_alive()

    def take(self) -> Any:
        """Join and return the build product (re-raising its error)."""
        self._thread.join()
        if self._error is not None:
            raise self._error
        return self._result
