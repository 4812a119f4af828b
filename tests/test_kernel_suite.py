"""KernelSuite: one fused packed decide/correction path across every layer.

The contract (interpret mode — the CI path; Mosaic on TPU compiles the
same calls): the fused Pallas kernels are **bitwise-equal** to the
reference semantics — ``lss.correction_loop`` + ``regions.decide_packed``
— for every packed family kind (Voronoi AND halfspace), with masked
padding center slots, at peer counts that are not multiples of the kernel
blocks, on the core loop, the sharded engine, and under the service's
vmapped query axis with mixed-kind tenants.

The bitwise anchor is always the CORE reference program (that IS
``lss.correction_loop``/``decide_packed``): the engine's *reference* path
has always been a last-ulp off the core one (XLA fuses the open formulas
differently inside the engine graph — see ``_assert_state_close`` in
test_engine.py), whereas the fused kernels compile to the same program in
every context, so engine-fused == core-reference exactly.

Also covered: the engine's unfused-override telemetry (an opaque per-call
``decide`` must not silently drop the kernel path), zero-recompile
admit/retire with kernels enabled, and the property test that packed
fused decisions equal each family's own unpadded decide.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # real hypothesis when installed (CI); seeded fallback shim otherwise
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core import lss, regions, topology, wvs
from repro.engine import EngineConfig, ShardedLSS
from repro.kernels import get_suite, resolve_suite
from repro.kernels import ops as kernel_ops
from repro.obs import jit_cache_size
from repro.service import Service, ServiceConfig
from repro.service.query import QuerySpec

FUSED = get_suite("fused")


def _inputs(n, d=2, seed=0):
    rng = np.random.default_rng(seed)
    v = rng.standard_normal((n, d)).astype(np.float32)
    return wvs.from_vector(jnp.asarray(v), jnp.ones((n,), jnp.float32))


def _families(d=2, seed=0):
    """One of each kind, the Voronoi one padded (masked center slots)."""
    rng = np.random.default_rng(seed)
    vor = regions.VoronoiRegions(
        jnp.asarray(rng.standard_normal((3, d)).astype(np.float32)))
    half = regions.HalfspaceRegions(
        w=jnp.asarray(rng.standard_normal((d,)).astype(np.float32)),
        b=jnp.asarray(np.float32(0.1)))
    padded = regions.PackedRegions.pack([vor], k_max=6).slot(0)
    return {"voronoi": vor, "halfspace": half, "padded-voronoi": padded}


def _assert_state_bitwise(got: lss.LSSState, want: lss.LSSState, msg=""):
    for g, w, name in zip(got, want, got._fields):
        assert np.array_equal(np.asarray(g), np.asarray(w)), (
            f"{msg}: field {name!r} not bitwise-equal")


# ---------------------------------------------------------------------------
# core loop: fused suite vs correction_loop + decide_packed, bitwise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fam_name", ["voronoi", "halfspace",
                                      "padded-voronoi"])
def test_core_cycle_fused_bitwise(fam_name):
    """Both kinds + masked padding slots, n = 90 (not a block multiple):
    every state array identical after every cycle."""
    topo = topology.barabasi_albert(90, m=2, seed=1)
    ta = lss.TopoArrays.from_topology(topo)
    fam = _families()[fam_name]
    slot = regions.as_packed_slot(fam)
    cfg = lss.LSSConfig()
    inputs = _inputs(topo.n, seed=2)
    ref = lss.init_state(ta, inputs, seed=0)
    fus = lss.init_state(ta, inputs, seed=0)
    decide = lambda v: regions.decide_packed(v, *slot)  # noqa: E731

    ref_cycle = jax.jit(
        lambda s: lss.cycle_impl(s, ta, cfg, decide))
    fus_cycle = jax.jit(
        lambda s: lss.cycle_impl(s, ta, cfg, None, suite=FUSED,
                                 regions=slot))
    for c in range(8):
        ref, sent_r = ref_cycle(ref)
        fus, sent_f = fus_cycle(fus)
        assert int(sent_r) == int(sent_f)
        _assert_state_bitwise(fus, ref, f"cycle {c}")


def test_core_cycle_jitted_wrapper_suite():
    """lss.cycle(suite=...) — the static-suite entry point — matches the
    decide path bitwise (suites are hashable singletons)."""
    topo = topology.grid(36)
    ta = lss.TopoArrays.from_topology(topo)
    centers = jnp.asarray(
        np.random.default_rng(3).standard_normal((4, 2)).astype(np.float32))
    cfg = lss.LSSConfig()
    ref = fus = lss.init_state(ta, _inputs(topo.n, seed=3), seed=0)
    for _ in range(6):
        ref, _ = lss.cycle(ref, ta, centers, cfg)
        fus, _ = lss.cycle(fus, ta, centers, cfg, suite=FUSED)
    _assert_state_bitwise(fus, ref, "cycle(suite=fused)")


# ---------------------------------------------------------------------------
# engine: fused path vs the core reference, bitwise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fam_name", ["voronoi", "halfspace"])
def test_engine_fused_bitwise_vs_core_reference(fam_name):
    topo = topology.grid(36)
    ta = lss.TopoArrays.from_topology(topo)
    fam = _families(seed=4)[fam_name]
    slot = regions.as_packed_slot(fam)
    cfg = lss.LSSConfig()
    inputs = _inputs(topo.n, seed=5)
    core = lss.init_state(ta, inputs, seed=0)
    eng = ShardedLSS(topo, jnp.zeros((1, 2), jnp.float32), cfg,
                     EngineConfig(num_shards=2, cycles_per_dispatch=1,
                                  use_kernels=True),
                     region=fam)
    assert eng.dispatch_info == {"suite": "fused", "fused": True}
    est = eng.init(inputs, seed=0)
    decide = lambda v: regions.decide_packed(v, *slot)  # noqa: E731
    ref_cycle = jax.jit(lambda s: lss.cycle_impl(s, ta, cfg, decide))
    for c in range(8):
        core, _ = ref_cycle(core)
        est = eng.run(est, 1)
        _assert_state_bitwise(
            eng.to_lss_state(est)._replace(rng=core.rng, msgs=core.msgs),
            core, f"cycle {c}")
        assert int(jnp.sum(est.msgs)) == int(core.msgs)


# ---------------------------------------------------------------------------
# service: vmapped query axis, mixed-kind tenants, both backends
# ---------------------------------------------------------------------------


def _mixed_specs(n, d=2, seed=6):
    rng = np.random.default_rng(seed)
    fams = _families(d=d, seed=seed)
    mk = lambda fam, s, **kw: QuerySpec(
        region=fam, inputs=rng.standard_normal((n, d)).astype(np.float32),
        seed=s, **kw)
    return [mk(fams["voronoi"], 1),
            mk(fams["halfspace"], 2),
            mk(fams["voronoi"], 3, beta=1e-2, ell=2),
            mk(regions.VoronoiRegions(fams["voronoi"].centers[:2]), 4)]


@pytest.mark.parametrize("backend,graph", [
    pytest.param("core", "grid", id="core"),
    pytest.param("engine", "grid", id="engine"),
    pytest.param("core", "chord", id="core-chord"),
])
def test_service_query_axis_fused_bitwise(backend, graph):
    """Mixed Voronoi+halfspace tenants (ragged k -> masked padding slots,
    per-query knobs): the fused vmapped dispatch is bitwise-equal to the
    core-reference service, per-tenant telemetry included.  The Chord
    case takes rows of D = 13 through the kernels' slot loops."""
    topo = topology.grid(36) if graph == "grid" else topology.chord(128)
    specs = _mixed_specs(topo.n)
    scfg = dict(capacity=6, k_max=6, d=2, cycles_per_dispatch=2)

    def run(backend, uk):
        svc = Service(topo, ServiceConfig(backend=backend, use_kernels=uk,
                                          **scfg))
        qids = [svc.admit(s) for s in specs]
        recs = []
        for _ in range(4):
            recs.append(svc.tick())
        return svc, qids, recs

    svc_ref, qids_ref, recs_ref = run("core", False)
    svc_fus, qids_fus, recs_fus = run(backend, True)
    fus_info = svc_fus.dispatch_info()
    assert fus_info["suite"] == "fused" and fus_info["fused"] is True
    for ra, rb in zip(recs_ref, recs_fus):
        for a, b in zip(ra, rb):
            assert a["accuracy"] == b["accuracy"]
            assert a["msgs"] == b["msgs"]
            assert a["quiescent"] == b["quiescent"]
            assert a["region"] == b["region"]
            assert a["corr_iters"] == b["corr_iters"]
            assert a["corr_running"] == b["corr_running"]
    for qa, qb in zip(qids_ref, qids_fus):
        sa, sb = svc_ref.snapshot(qa), svc_fus.snapshot(qb)
        _assert_state_bitwise(sb._replace(rng=sa.rng, msgs=sa.msgs), sa,
                              f"query {qa} ({backend})")


def test_service_kernels_zero_recompile_admit_retire():
    """Steady-state serving with kernels enabled: admit/retire (region
    table swaps) are data-only — the jitted dispatch never recompiles."""
    topo = topology.grid(25)
    svc = Service(topo, ServiceConfig(capacity=4, k_max=4, d=2,
                                      cycles_per_dispatch=2,
                                      use_kernels=True))
    specs = _mixed_specs(topo.n, seed=7)
    q0 = svc.admit(specs[0])
    svc.serve(2)  # warm the compile caches
    warm = jit_cache_size(svc._step)
    if warm is None:
        pytest.skip("jit cache stats unavailable on this jax")
    q1 = svc.admit(specs[1])  # halfspace joins a Voronoi tenant
    svc.serve(2)
    svc.retire(q0)
    q2 = svc.admit(specs[2])  # per-query knob overrides
    svc.serve(2)
    svc.retire(q1)
    svc.retire(q2)
    svc.serve(1)
    assert jit_cache_size(svc._step) == warm


# ---------------------------------------------------------------------------
# engine unfused-override telemetry (the silent-drop fix)
# ---------------------------------------------------------------------------


def test_engine_opaque_decide_override_warns_and_records():
    """A per-call opaque `decide` on a fused engine must not silently run
    unfused: one warning, and dispatch_info records fused=False."""
    topo = topology.grid(16)
    centers = jnp.asarray(
        np.random.default_rng(8).standard_normal((3, 2)).astype(np.float32))
    eng = ShardedLSS(topo, centers, lss.LSSConfig(),
                     EngineConfig(num_shards=2, use_kernels=True))
    est = eng.init(_inputs(topo.n, seed=8), seed=0)
    assert eng.dispatch_info["fused"] is True
    custom = lambda v: (v[..., 0] > 0).astype(jnp.int32)  # noqa: E731
    with pytest.warns(RuntimeWarning, match="bypasses the fused kernel"):
        eng._cycle_full(est, eng._tables, decide=custom)
    assert eng.dispatch_info["fused"] is False
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # second bypass: no re-warn
        eng._cycle_full(est, eng._tables, decide=custom)
    # The flag is per-trace, not latched: a normal fused dispatch
    # flips it back.
    eng.run(est, 1)
    assert eng.dispatch_info["fused"] is True


def test_engine_use_kernels_rejects_opaque_decide_at_init():
    topo = topology.grid(16)
    centers = jnp.zeros((2, 2), jnp.float32)
    custom = lambda v: (v[..., 0] > 0).astype(jnp.int32)  # noqa: E731
    with pytest.raises(ValueError, match="opaque"):
        ShardedLSS(topo, centers, lss.LSSConfig(),
                   EngineConfig(num_shards=2, use_kernels=True),
                   decide=custom)
    # But a packed region family composes with the kernels.
    eng = ShardedLSS(topo, centers, lss.LSSConfig(),
                     EngineConfig(num_shards=2, use_kernels=True),
                     region=_families(seed=9)["halfspace"])
    assert eng.use_kernels
    # An explicitly NON-fused suite honors an opaque decide just fine.
    eng2 = ShardedLSS(topo, centers, lss.LSSConfig(),
                      EngineConfig(num_shards=2, use_kernels="reference"),
                      decide=custom)
    assert not eng2.use_kernels


def test_core_cycle_rejects_decide_plus_suite():
    """cycle() mirrors the engine: a requested kernel suite is never
    silently dropped in favor of an opaque decide."""
    topo = topology.grid(16)
    ta = lss.TopoArrays.from_topology(topo)
    st = lss.init_state(ta, _inputs(topo.n, seed=12), seed=0)
    centers = jnp.zeros((2, 2), jnp.float32)
    custom = lambda v: (v[..., 0] > 0).astype(jnp.int32)  # noqa: E731
    with pytest.raises(ValueError, match="decide"):
        lss.cycle(st, ta, centers, lss.LSSConfig(), decide=custom,
                  suite=FUSED)


# ---------------------------------------------------------------------------
# property: packed fused decide == per-family unpadded decide
# ---------------------------------------------------------------------------


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=1, max_value=200),
       st.lists(st.integers(min_value=1, max_value=7), min_size=1,
                max_size=5),
       st.integers(min_value=0, max_value=2**31 - 1))
def test_fused_decide_matches_unpadded_families(n, ks, seed):
    """Random PackedRegions.pack families (mixed kinds, ragged k): the
    fused decision of every slot equals that family's own unpadded decide
    for all peers — flat (engine-style) and vmapped (service-style)."""
    rng = np.random.default_rng(seed)
    d = int(rng.integers(1, 5))
    fams = []
    for k in ks:
        if rng.random() < 0.4:
            fams.append(regions.HalfspaceRegions(
                w=jnp.asarray(rng.standard_normal((d,)).astype(np.float32)),
                b=jnp.asarray(np.float32(rng.standard_normal()))))
        else:
            fams.append(regions.VoronoiRegions(jnp.asarray(
                rng.standard_normal((k, d)).astype(np.float32))))
    pr = regions.PackedRegions.pack(fams)
    v = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))

    # Engine-style: one slot at a time through the fused kernel.
    for i, fam in enumerate(fams):
        got = FUSED.decide(v, pr.slot(i))
        want = fam.decide(v)
        assert np.array_equal(np.asarray(got), np.asarray(want)), (
            f"slot {i} ({type(fam).__name__}, n={n}, d={d})")

    # Service-style: all slots at once under vmap (leading grid dim).
    got_all = jax.vmap(lambda s: FUSED.decide(v, regions.PackedSlot(*s))
                       )(pr)
    want_all = jnp.stack([f.decide(v) for f in fams])
    assert np.array_equal(np.asarray(got_all), np.asarray(want_all))


def test_resolve_suite_knob():
    assert resolve_suite(True).name == "fused"
    assert resolve_suite(False).name == "reference"
    assert resolve_suite("fused").fused
    auto = resolve_suite(None)
    assert auto.fused == (jax.default_backend() == "tpu")
    with pytest.raises(KeyError):
        resolve_suite("no-such-suite")


def test_ops_traced_knobs_do_not_recompile():
    """beta/eps ride the kernels' meta row as data: sweeping them hits
    one compiled executable."""
    rng = np.random.default_rng(10)
    n, D, d = 64, 3, 2
    f = lambda *s: jnp.asarray(rng.standard_normal(s).astype(np.float32))
    a_m, a_c = f(n, D, d), jnp.abs(f(n, D)) + 0.1
    in_m, in_c = f(n, D, d), jnp.abs(f(n, D))
    s_m, s_c = f(n, d), jnp.abs(f(n,)) + 0.5
    v = jnp.asarray(rng.random((n, D)) < 0.3)
    kernel_ops.correction(s_m, s_c, a_m, a_c, in_m, in_c, v,
                          beta=jnp.float32(1e-3), eps=jnp.float32(1e-9))
    warm = jit_cache_size(kernel_ops.correction)
    if warm is None:
        pytest.skip("jit cache stats unavailable on this jax")
    for beta in (1e-2, 0.3):
        kernel_ops.correction(s_m, s_c, a_m, a_c, in_m, in_c, v,
                              beta=jnp.float32(beta),
                              eps=jnp.float32(1e-8))
    assert jit_cache_size(kernel_ops.correction) == warm
