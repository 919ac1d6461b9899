"""Parity suite for the jax solver backend (ISSUE 8).

The contract: `repro.core.estimator_jax` is a jit-compiled twin of the
NumPy water-filling solver, equal at 1e-9 (rtol AND atol — slowdowns of
excluded-neighbor scenarios legitimately reach ~1e9, where 1e-9 absolute
on a ~1e-16 relative error is unattainable in float64) on every branch
of the model: slot-fraction exclusion, smem equal-throttle, the cache
thrash cliff exactly at the boundary, ragged widths, empty batches.

The random-scenario distributions come from benchmarks/bench_planner.py
(the same generators the oracle tests and the CI bench fuzz), steered
into specific estimator branches via its flags.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks"))

jax = pytest.importorskip("jax")

from bench_planner import random_profile, random_scenarios  # noqa: E402
from repro.core import (TPU_V5E, DENSE_SEARCH, FractionSearchConfig,  # noqa: E402
                        KernelProfile, Scenario, get_solver_backend,
                        set_solver_backend, solver_backend)
from repro.core import estimator_jax  # noqa: E402
from repro.core.estimator import solve_batch, solve_scenarios  # noqa: E402
from repro.core.profile import ProfileMatrix  # noqa: E402

DEV = TPU_V5E
RTOL = ATOL = 1e-9


def both_backends(fn):
    """Run `fn` under numpy then jax and return both results."""
    r_np = fn()
    with solver_backend("jax"):
        r_jx = fn()
    return r_np, r_jx


def assert_results_equal(r_np, r_jx):
    assert r_np.mask.shape == r_jx.mask.shape
    np.testing.assert_array_equal(r_np.mask, r_jx.mask)
    np.testing.assert_array_equal(r_np.bottleneck, r_jx.bottleneck)
    np.testing.assert_array_equal(r_np.feasible_slots, r_jx.feasible_slots)
    for field in ("speeds", "slowdowns", "axis_load"):
        a, b = getattr(r_np, field), getattr(r_jx, field)
        fin = np.isfinite(a)
        np.testing.assert_array_equal(fin, np.isfinite(b),
                                      err_msg=f"{field}: finiteness differs")
        np.testing.assert_allclose(b[fin], a[fin], rtol=RTOL, atol=ATOL,
                                   err_msg=field)


def pool(rng, n=48):
    """Mixed kernel pool hitting every solver branch: zeroed axes,
    smem-saturating, cache-heavy."""
    return [random_profile(rng, f"k{i}", DEV,
                           zero_axes=(i % 3 == 0),
                           smem_heavy=(i % 5 == 0),
                           cache_heavy=(i % 4 == 0)) for i in range(n)]


@pytest.mark.parametrize("k", [1, 2, 3, 4, 5, 6])
def test_parity_random_widths(k):
    rng = np.random.default_rng(100 + k)
    pm = ProfileMatrix.from_profiles(pool(rng))
    idx = rng.integers(0, len(pm.names), (128, k))
    r_np, r_jx = both_backends(lambda: solve_batch(pm, idx, DEV))
    assert_results_equal(r_np, r_jx)


@pytest.mark.parametrize("k", [2, 3, 4, 6])
def test_parity_slot_fractions_and_exclusion(k):
    """Random simplex fractions, some pushed to (and below) the
    FRACTION_FLOOR exclusion — excluded members must come back speed 0 /
    slowdown +inf on both backends."""
    rng = np.random.default_rng(200 + k)
    pm = ProfileMatrix.from_profiles(pool(rng))
    S = 128
    idx = rng.integers(0, len(pm.names), (S, k))
    frac = rng.random((S, k)) * 0.9 + 0.05
    frac /= frac.sum(1, keepdims=True)
    excl = rng.random((S, k)) < 0.1
    frac = np.where(excl, 1e-7, frac)
    r_np, r_jx = both_backends(lambda: solve_batch(pm, idx, DEV, frac))
    assert np.isinf(r_np.slowdowns[excl]).all()
    assert np.isinf(r_jx.slowdowns[excl]).all()
    assert_results_equal(r_np, r_jx)


def test_parity_smem_worst_axis():
    """Batches built to freeze on the smem equal-throttle branch."""
    rng = np.random.default_rng(7)
    profs = [random_profile(rng, f"s{i}", DEV, smem_heavy=True)
             for i in range(16)]
    pm = ProfileMatrix.from_profiles(profs)
    idx = rng.integers(0, 16, (64, 3))
    r_np, r_jx = both_backends(lambda: solve_batch(pm, idx, DEV))
    # the branch actually fired: some member froze on the smem axis
    from repro.core.estimator import _SMEM
    assert (r_np.bottleneck == _SMEM).any()
    assert_results_equal(r_np, r_jx)


def test_parity_cache_cliff_boundary():
    """total_ws == cache_cap sits exactly ON the thrash cliff (share
    collapses only strictly ABOVE capacity) — the discrete comparison
    must agree between backends at the boundary and on either side."""
    cap = DEV.cache_capacity
    mk = lambda name, ws: KernelProfile(
        name, demand={"hbm": 0.8 * DEV.capacity("hbm")},
        cache_working_set=ws, cache_hit_fraction=0.9)
    bg = KernelProfile("bg", demand={"hbm": 0.4 * DEV.capacity("hbm")})
    scens = [Scenario((mk(f"a{ws}", ws), bg))
             for ws in (0.5 * cap, cap, np.nextafter(cap, np.inf),
                        2.0 * cap)]
    r_np, r_jx = both_backends(lambda: solve_scenarios(scens, DEV))
    assert_results_equal(r_np, r_jx)
    # AT capacity the hits survive (cliff is strictly above); one ulp
    # over, they collapse and the pair saturates hbm
    assert (r_np.slowdowns[1] < r_np.slowdowns[2]).all()
    np.testing.assert_allclose(r_np.slowdowns[0], r_np.slowdowns[1])


def test_parity_empty_and_zero_width():
    r_np, r_jx = both_backends(lambda: solve_scenarios([], DEV))
    assert len(r_np) == len(r_jx) == 0
    empty = [Scenario(()), Scenario(())]
    r_np, r_jx = both_backends(lambda: solve_scenarios(empty, DEV))
    assert r_np.speeds.shape == r_jx.speeds.shape
    assert r_np.feasible_slots.all() and r_jx.feasible_slots.all()


def test_ragged_batch_equals_per_row_solves():
    """Satellite regression: compile_scenarios pads ragged widths to one
    dense (S, K_max) masked batch — results must equal solving each
    scenario on its own, on BOTH backends."""
    rng = np.random.default_rng(11)
    scen_kernels = random_scenarios(rng, 40, DEV)   # widths 2..4, ragged
    scens = [Scenario(tuple(sc)) for sc in scen_kernels]
    widths = {len(sc.members) for sc in scens}
    assert len(widths) > 1, "distribution must actually be ragged"
    for backend in ("numpy", "jax"):
        with solver_backend(backend):
            batched = solve_scenarios(scens, DEV)
            for s, sc in enumerate(scens):
                solo = solve_scenarios([sc], DEV)
                k = len(sc.members)
                np.testing.assert_allclose(
                    batched.slowdowns[s, :k], solo.slowdowns[0],
                    rtol=RTOL, atol=ATOL, err_msg=f"{backend} row {s}")
                assert (batched.bottleneck[s, :k]
                        == solo.bottleneck[0]).all()
                assert batched.feasible_slots[s] == solo.feasible_slots[0]


def test_compiled_ragged_is_dense_with_mask():
    from repro.core import compile_scenarios
    rng = np.random.default_rng(3)
    ps = pool(rng, 8)
    scens = [Scenario(tuple(ps[:2])), Scenario(tuple(ps[:4])),
             Scenario((ps[5],))]
    comp = compile_scenarios(scens)
    assert isinstance(comp.members, np.ndarray)
    assert comp.members.shape == (3, 4)
    assert comp.mask is not None
    assert comp.mask.sum(1).tolist() == [2, 4, 1]


def test_jit_cache_two_shapes_two_traces():
    """Shape discipline: batches land in power-of-two size buckets, so
    two DIFFERENT batch sizes in the same bucket share one trace and a
    second bucket adds exactly one more."""
    rng = np.random.default_rng(5)
    pm = ProfileMatrix.from_profiles(pool(rng, 8))
    # K=7 is unique to this test: the jit cache is process-global, so any
    # (bucket, K) shape another test already solved would be warm here
    with solver_backend("jax"):
        idx = rng.integers(0, 8, (33, 7))
        solve_batch(pm, idx, DEV)                     # bucket 64
        t0 = estimator_jax.trace_count()
        solve_batch(pm, idx[:40], DEV)                # still bucket 64
        solve_batch(pm, idx[:64], DEV)                # still bucket 64
        assert estimator_jax.trace_count() == t0
        solve_batch(pm, np.vstack([idx, idx]), DEV)   # bucket 128: 1 trace
        assert estimator_jax.trace_count() == t0 + 1
        solve_batch(pm, np.vstack([idx, idx]), DEV)   # warm: no new trace
        assert estimator_jax.trace_count() == t0 + 1


def test_x64_stays_scoped_to_the_solver():
    """Importing the jax solver and solving with it leave the process-wide
    x64 flag alone, so f32/bf16 model and kernel code in the same process
    keeps its dtypes (a global flip broke every Pallas kernel on a TPU)."""
    import importlib

    import jax.numpy as jnp
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        importlib.reload(estimator_jax)
        assert jax.config.jax_enable_x64 is False
        rng = np.random.default_rng(31)
        pm = ProfileMatrix.from_profiles(pool(rng, 8))
        idx = rng.integers(0, 8, (16, 3))
        r_np, r_jx = both_backends(lambda: solve_batch(pm, idx, DEV))
        assert_results_equal(r_np, r_jx)
        assert r_jx.slowdowns.dtype == np.float64
        assert jax.config.jax_enable_x64 is False
        assert jnp.zeros(2).dtype == jnp.float32
    finally:
        jax.config.update("jax_enable_x64", prev)


def test_backend_switch_and_env():
    assert get_solver_backend() in ("numpy", "jax")
    prev = set_solver_backend("jax")
    try:
        assert get_solver_backend() == "jax"
        with solver_backend("numpy"):
            assert get_solver_backend() == "numpy"
        assert get_solver_backend() == "jax"
        with pytest.raises(ValueError):
            set_solver_backend("tpu")
    finally:
        set_solver_backend(prev)


def test_default_search_config_follows_backend():
    with solver_backend("numpy"):
        assert FractionSearchConfig.default() == FractionSearchConfig()
    with solver_backend("jax"):
        assert FractionSearchConfig.default() == DENSE_SEARCH
    # the dense grid embeds the standard one: every 8-step coarse point
    # (and its level-1 refinement points, which land on 16ths) is a
    # 16-step point, so the dense search can never select a worse gain
    from repro.core import simplex_candidates
    coarse8 = set(simplex_candidates(2, 8))
    coarse16 = set(simplex_candidates(2, 16))
    assert coarse8 <= coarse16


def test_warmup_compiles_each_shape_once_shared_across_models():
    """warmup() AOT-compiles each requested (bucket, K) shape exactly
    once; re-warming is free, a different device model hits the same
    traces (capacities are traced operands), and a real solve of a
    warmed shape adds no trace."""
    from repro.core import TPU_V5P
    # K=11 is unique to this test (the jit cache is process-global)
    with solver_backend("jax"):
        assert estimator_jax.warmup(DEV, ks=(11,)) == 1
        assert estimator_jax.warmup(DEV, ks=(11,)) == 0
        assert estimator_jax.warmup(TPU_V5P, ks=(11,)) == 0
        rng = np.random.default_rng(21)
        pm = ProfileMatrix.from_profiles(pool(rng, 12))
        t0 = estimator_jax.trace_count()
        solve_batch(pm, rng.integers(0, 12, (5, 11)), DEV)  # bucket 8
        assert estimator_jax.trace_count() == t0


def test_scheduler_warmup_flag_precompiles_group_widths():
    """ColocationScheduler(warmup=True) warms every group width up to
    max_group_size at construction, so the first plan's solves of any
    warmed shape compile nothing."""
    from repro.core import ColocationScheduler
    # max_group_size=12 -> K=12 is unique to this test
    with solver_backend("jax"):
        ColocationScheduler(DEV, max_group_size=12, warmup=True)
        rng = np.random.default_rng(23)
        ps = pool(rng, 12)
        t0 = estimator_jax.trace_count()
        solve_scenarios([Scenario(tuple(ps))], DEV)   # width 12, bucket 8
        assert estimator_jax.trace_count() == t0


def test_warmup_solver_is_noop_on_numpy_backend():
    """The backend-level switch: warmup_solver never imports or traces
    anything when the numpy solver is active."""
    from repro.core import warmup_solver
    with solver_backend("numpy"):
        assert warmup_solver(DEV, ks=(2, 3)) == 0
