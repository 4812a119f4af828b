"""The one traffic generator: tenants, arrivals and churn from a mix file.

A mix (``bench/traffic/<name>.json``) is data only.  From it and the
run's seed this module draws, with numpy alone:

* the tenants, alternating two families, as the monitor's
  ``service.workload.heterogeneous_tenants`` does: even ones a Voronoi
  source-selection problem (k centres, the data mean ``voronoi_bias`` of
  the way from the desired centre to its nearest rival, the geometry of
  ``sim.make_problem``, Sec. VI-A of arXiv 1212.5880), odd ones a
  halfspace threshold; each with its own ``beta`` and ``ell``;
* a stream of bursts, each setting ``burst_fraction`` of the peers to
  fresh draws for every tenant that is running;
* when each burst arrives, and which tenants leave and come back.

Every tenant's data, and every burst, is N(0, I_d): the Voronoi centres
are given in units of the problem's own spread, centred on the data mean,
so a burst draws from the same law as the tenant's data and the global
mean does not drift as the stream replaces it.  The halfspace offset sits
``halfspace_offset`` off the mean, where the monitor's workload puts it
on the mean and leaves the answer on a region boundary.  So each
tenant's global vector keeps at least ``margin`` from every region
boundary for the whole stream (``test_bench_traffic.py`` checks this at
the cells' own sizes), and every decision has one right answer.

What a mix may set (``DEFAULTS`` gives the rest):

``tenants_per_slot``
    tenants in all, per query slot of the configuration; those beyond
    the slots wait in the service's admission queue.
``retire_after_ticks``
    ``null``: tenants stay.  A number: in the window, a tenant that has
    run that many dispatches and whose last record reads accuracy 1.0 at
    quiescence is retired and admitted again, fresh, at the back of the
    queue, so the admission path and cold convergence keep running.
``arrivals``
    ``"tick"``: ``bursts_per_tick`` bursts pushed before every tick, a
    closed loop tied to cycles.  ``"wall"``: ``bursts_per_s`` bursts a
    second, evenly spaced on the host clock from the window's open; each
    is pushed at the first tick boundary after it arrives and its
    latency counts from its arrival.
``settle_cap_ticks``, ``warm_ticks``, ``drain_cap_s``
    set-up converges every running tenant from cold (at most the cap),
    then ticks ``warm_ticks`` times with bursts; after the window the run
    ticks without bursts until every answer is in or the cap passes.
"""

from __future__ import annotations

import math
from typing import Optional

import numpy as np

DEFAULTS = {
    "tenants_per_slot": 1,
    "retire_after_ticks": None,
    "arrivals": "tick",
    "bursts_per_tick": 0,
    "bursts_per_s": 0.0,
}


def tenant_rng(seed: int, i: int) -> np.random.Generator:
    return np.random.default_rng([seed, 1, i])


def make_tenants(n: int, count: int, d: int, mix: dict, seed: int) -> list:
    """``count`` tenant descriptions (plain numpy), in admission order."""
    out = []
    for i in range(count):
        rng = tenant_rng(seed, i)
        x = rng.standard_normal((n, d)).astype(np.float32)
        t = {"x": x, "seed": i, "beta": 1e-3 * (1.0 + i / (2.0 * count)),
             "ell": 1 + i % 2}
        if i % 2 == 0:
            k = int(mix["voronoi_k"])
            centers = rng.standard_normal((k, d))
            desired = int(rng.integers(k))
            dist = np.linalg.norm(centers - centers[desired], axis=1)
            dist[desired] = np.inf
            rival = int(np.argmin(dist))
            gap = float(dist[rival])
            bias = float(mix["voronoi_bias"])
            mean = (1 - bias) * centers[desired] + bias * centers[rival]
            t.update(kind="voronoi",
                     centers=((centers - mean) / gap).astype(np.float32))
        else:
            w = rng.standard_normal(d)
            w = (w / np.linalg.norm(w)).astype(np.float32)
            side = 1.0 if rng.integers(2) else -1.0
            t.update(kind="halfspace", w=w,
                     b=np.float32(-side * float(mix["halfspace_offset"])))
        out.append(t)
    return out


def burst_size(n: int, mix: dict) -> int:
    return max(1, int(n * float(mix["burst_fraction"])))


def burst(n: int, d: int, mix: dict, seed: int, index: int):
    """Burst ``index`` of the stream: (peer ids int32, values f32 (m, d))."""
    rng = np.random.default_rng([seed, 2, index])
    who = rng.choice(n, size=burst_size(n, mix), replace=False)
    vals = rng.standard_normal((who.size, d)).astype(np.float32)
    return who.astype(np.int32), vals


class Traffic:
    """One run's traffic: its tenants, its bursts and when each arrives,
    and which tenants retire.  The harness asks it before every tick."""

    def __init__(self, mix: dict, n: int, slots: int, d: int, seed: int):
        self.mix = {**DEFAULTS, **mix}
        self.n, self.d, self.seed = n, d, seed
        self.tenants = make_tenants(
            n, slots * int(self.mix["tenants_per_slot"]), d, self.mix, seed)
        self._bursts = {}
        self._next = 0  # index of the next burst to hand out
        self._open = None  # window open (host clock), for wall arrivals
        self._released = 0  # wall arrivals handed out in the window
        if self.mix["arrivals"] not in ("tick", "wall"):
            raise ValueError(f"unknown arrivals {self.mix['arrivals']!r}")

    @property
    def bursty(self) -> bool:
        if self.mix["arrivals"] == "wall":
            return float(self.mix["bursts_per_s"]) > 0
        return int(self.mix["bursts_per_tick"]) > 0

    def burst(self, index: int):
        if index not in self._bursts:
            self._bursts[index] = burst(self.n, self.d, self.mix, self.seed,
                                        index)
        return self._bursts[index]

    def _take(self, count: int) -> list:
        out = list(range(self._next, self._next + count))
        self._next += count
        return out

    def warm(self) -> list:
        """Bursts for one set-up tick after settling: one per tick's worth,
        so every shape the window pushes is compiled."""
        if not self.bursty:
            return []
        return self._take(max(1, int(self.mix["bursts_per_tick"])))

    def prepare(self, seconds: float, tick_s: float) -> None:
        """Draw every burst the window will push before it opens (the
        window times the system, not the generator): for tick arrivals,
        as many as ticks of ``tick_s`` fit, with room to spare."""
        if self.mix["arrivals"] == "wall":
            count = math.ceil(seconds * float(self.mix["bursts_per_s"])) + 1
        else:
            ticks = math.ceil(seconds / max(tick_s, 1e-3) * 1.5) + 2
            count = ticks * int(self.mix["bursts_per_tick"])
        for i in range(self._next, self._next + count):
            self.burst(i)

    def open(self, t_open: float) -> None:
        """The window opens: wall arrivals count from ``t_open``."""
        self._open = t_open

    def due(self, now: float) -> list:
        """The window's bursts to push at a tick boundary at ``now``, as
        (index, arrival); an arrival of None is the push itself."""
        if self.mix["arrivals"] == "tick":
            return [(i, None)
                    for i in self._take(int(self.mix["bursts_per_tick"]))]
        rate = float(self.mix["bursts_per_s"])
        if rate <= 0:
            return []
        upto = math.floor((now - self._open) * rate) + 1
        out = []
        while self._released < upto:
            out.append((self._take(1)[0],
                        self._open + self._released / rate))
            self._released += 1
        return out

    def retire(self, ran: dict, last: dict) -> list:
        """Tenants to retire after a window tick: ``ran`` counts each
        running tenant's dispatches, ``last`` holds its latest record."""
        after: Optional[int] = self.mix["retire_after_ticks"]
        if after is None:
            return []
        return [qid for qid, r in last.items()
                if ran.get(qid, 0) >= int(after)
                and r["accuracy"] == 1.0 and r["quiescent"]]
