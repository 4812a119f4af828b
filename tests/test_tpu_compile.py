"""The Pallas kernels compile with Mosaic for a described TPU v5e.

Interpret mode (every other test here) never runs the Mosaic compiler,
which refuses what the interpreter accepts: unsupported slices, shape
casts, blocks that do not tile, kernels that overflow VMEM.  These tests
compile each kernel, and the query-axis ``vmap`` form the service
dispatches, for one chip of a v5e described but not attached, at the
80,089-peer grid padded to 80,128 rows: D=4 (grid) and D=34 (Chord at
80k peers).  Nothing runs; ``tpu_custom_call`` in the compiled text
proves the kernel went through Mosaic and not the interpreter.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import regions
from repro.kernels import ops

ROWS = 80_128
K_MAX = 4
D_STAT = 2
Q = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Compile the kernels with Mosaic although JAX runs on the CPU, and
    keep the described chip's programs out of any persistent cache (they
    cannot be read back without the chip)."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _shapes(one_chip, D, batch=()):
    f32 = jnp.float32
    s = lambda *shape, dt=f32: jax.ShapeDtypeStruct(  # noqa: E731
        batch + shape, dt, sharding=one_chip)
    slot = regions.PackedSlot(s(dt=jnp.int32), s(K_MAX, D_STAT),
                              s(K_MAX, dt=bool), s(D_STAT), s())
    peers = dict(
        x_m=s(ROWS, D_STAT), x_c=s(ROWS), out_m=s(ROWS, D, D_STAT),
        out_c=s(ROWS, D), in_m=s(ROWS, D, D_STAT), in_c=s(ROWS, D),
        mask=s(ROWS, D, dt=bool))
    return slot, peers


def _lower(kernel, slot, p):
    if kernel == "region_decide":
        return ops.region_decide.lower(p["x_m"], slot)
    if kernel == "lss_state":
        return ops.lss_state.lower(p["x_m"], p["x_c"], p["out_m"],
                                   p["out_c"], p["in_m"], p["in_c"],
                                   p["mask"], slot)
    return ops.correction.lower(p["x_m"], p["x_c"], p["out_m"], p["out_c"],
                                p["in_m"], p["in_c"], p["mask"])


@pytest.mark.parametrize("kernel,D", [
    ("region_decide", 4),  # reads no per-slot array: D does not enter
    ("lss_state", 4), ("lss_state", 34),
    ("correction", 4), ("correction", 34)])
def test_kernel_compiles_for_v5e(kernel, D, one_chip, mosaic):
    slot, peers = _shapes(one_chip, D)
    compiled = _lower(kernel, slot, peers).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["region_decide", "lss_state",
                                    "correction"])
def test_query_axis_vmap_compiles_for_v5e(kernel, one_chip, mosaic):
    """The service's form: the kernels vmapped over Q=8 tenant slots,
    which Pallas turns into a leading grid dimension."""
    slot, peers = _shapes(one_chip, 4, batch=(Q,))
    if kernel == "region_decide":
        f = jax.vmap(lambda sl, p: ops.region_decide(p["x_m"], sl))
    elif kernel == "lss_state":
        f = jax.vmap(lambda sl, p: ops.lss_state(
            p["x_m"], p["x_c"], p["out_m"], p["out_c"], p["in_m"],
            p["in_c"], p["mask"], sl))
    else:
        f = jax.vmap(lambda sl, p: ops.correction(
            p["x_m"], p["x_c"], p["out_m"], p["out_c"], p["in_m"],
            p["in_c"], p["mask"]))
    compiled = jax.jit(f).lower(slot, peers).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # The lane-dense layout keeps the step's temporaries small: the
    # message arrays are not padded to 128 lanes on their way in.
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2**30, f"{temp / 2**30:.2f} GiB of temporaries"
