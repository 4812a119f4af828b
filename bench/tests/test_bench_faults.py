"""``correct`` comes out false when the timed path is broken underneath:
the harness runs at the rehearsal size on the CPU (no look for a chip)
with a fault planted in the service, and with the control (message loss,
which breaks the configuration's stated no-loss guarantee)."""

import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness


def stale_step(svc):
    """The step returns the state it was given."""
    svc._step_call = lambda states, params, topo, k: (
        states, jnp.zeros((states.alive.shape[0],), jnp.int32))


def half_slots(svc):
    """Only the first half of the tenants' slots advance."""
    step = svc._step_call

    def call(states, params, topo, k):
        new, iters = step(states, params, topo, k=k)
        keep = jnp.arange(states.alive.shape[0]) < states.alive.shape[0] // 2
        pick = lambda a, b: jnp.where(
            keep.reshape((-1,) + (1,) * (a.ndim - 1)), a, b)
        return jax.tree_util.tree_map(pick, new, states), iters
    svc._step_call = call


def half_burst(svc):
    """Ingest applies half of each burst."""
    apply = svc.ingest.apply

    def half(x_m, x_c, batch, slots, pos=None):
        h = batch.who.shape[0] // 2
        return apply(x_m, x_c, batch._replace(who=batch.who[:h],
                                              values=batch.values[:h]),
                     slots, pos=pos)
    svc.ingest.apply = half


def wrong_region(svc):
    """Each record's global answer is altered where it is produced."""
    finish = svc._finish_window

    def altered(w):
        recs = finish(w)
        for r in recs:
            r["region"] = r["region"] + 1
        return recs
    svc._finish_window = altered


def miscounted_accuracy(svc):
    """Each record's accuracy is altered where it is produced."""
    finish = svc._finish_window

    def altered(w):
        recs = finish(w)
        for r in recs:
            r["accuracy"] = r["accuracy"] * 0.5
        return recs
    svc._finish_window = altered


def run(workload, **kw):
    out, _ = harness.run_cell(harness.Bench(), workload, 2 ** 31 + 99, 0.5,
                              False, t_start=time.perf_counter(),
                              rehearse=True, drain_cap_s=2.0, **kw)
    return {k: v for k, (v, lim) in out["checks"].items() if v > lim}


@pytest.mark.parametrize("workload", ["grid80k.stream", "grid80k.steady"])
def test_control_is_not_correct(workload):
    assert "unsettled_links" in run(workload, control=True)


STREAM_FAULTS = [
    (stale_step, "unconverged_tenants"),
    (half_slots, "unconverged_tenants"),
    (half_burst, "input_mismatch"),
    (wrong_region, "region_mismatch"),
    (miscounted_accuracy, "accuracy_gap_peers"),
]
# With no bursts a settled tenant's state is a fixed point of the step: a
# step that returns its state, on all slots or half, gives the right
# answers there, and only the cycle counter shows that no cycle ran.
STEADY_FAULTS = [
    (stale_step, "cycle_mismatch"),
    (half_slots, "cycle_mismatch"),
    (wrong_region, "region_mismatch"),
    (miscounted_accuracy, "accuracy_gap_peers"),
]


@pytest.mark.parametrize("workload,fault,caught", [
    ("grid80k.stream",) + f for f in STREAM_FAULTS] + [
    ("grid80k.steady",) + f for f in STEADY_FAULTS])
def test_fault_is_not_correct(workload, fault, caught):
    assert caught in run(workload, break_service=fault)
