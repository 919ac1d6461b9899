"""JAX-jitted port of the batched water-filling interference solver.

This is the accelerator-resident twin of the NumPy solver in
`repro.core.estimator` (ROADMAP item 2): the effective-demand /
cache-share precompute, the freeze-round water-filling fixed point
(``lax.while_loop`` over the fixed ``K + N_AXES`` bound with the per-
scenario ``done`` mask, including the smem equal-throttle branch and the
sorted-cumsum theta computation), and the queueing-inflation epilogue —
written as pure padded-array functions over ONE scenario and ``vmap``ped
over the batch, so XLA fuses the whole pricing pipeline into a handful
of kernels on whatever backend jax runs on (CPU today, TPU/GPU when
present).

Numerical contract: float64 everywhere — x64 is enabled only around
tracing and calling the solver (``jax.enable_x64`` in `solve_gathered`
and `warmup`), never process-wide, so bf16/f32 model and kernel code in
the same process keeps its dtypes; every floor/tolerance constant is
imported from `repro.core.estimator` (never re-typed here), and results
equal the NumPy oracle at 1e-9 — enforced by
``tests/test_estimator_jax.py`` and the ``bench_planner`` solver gate in
CI.  Selection happens in `repro.core.backend`; this module is only
imported when the jax backend is requested.

Shape discipline: one trace per padded (S, K) shape.  Batch sizes are
bucketed up to powers of two (scenario padding rows are fully masked and
solve to no-ops), so a scheduler churning through thousands of distinct
batch sizes compiles O(log S_max x distinct K) programs, not O(events).

The same XLA program runs on every platform: the cache-share stage is a
row sum and an elementwise select over (S, K) that XLA fuses into the
solve (on a TPU the f64 arithmetic is emulated by XLA).
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro import obs
from repro.core.estimator import (CAP_REMAIN_FLOOR, DEMAND_EPS,
                                  FRACTION_FLOOR, OVERSUB_RTOL, RATIO_FLOOR,
                                  SPEED_FLOOR, TIME_EPS, _INFLATION,
                                  _INFLATION_MAJORITY, _INFLATION_MIN_UTIL,
                                  _N_AXES, _SMEM, PER_SLOT_AXES)
from repro.core.resources import AXIS_INDEX, RESOURCE_AXES, DeviceModel

_HBM = AXIS_INDEX["hbm"]
_L2 = AXIS_INDEX["l2"]
_PER_SLOT_MASK = np.array([r in PER_SLOT_AXES for r in RESOURCE_AXES])

# batch-size bucket floor: tiny scheduler batches all share one trace
_MIN_BUCKET = 8

# incremented inside the traced function — counts actual XLA traces, so
# tests can pin the jit cache behavior (same bucket twice -> one trace)
_trace_count = 0


def trace_count() -> int:
    return _trace_count


def _bucket(s: int) -> int:
    """Next power of two >= s (floored at _MIN_BUCKET): the padded batch
    size a solve of s scenarios compiles for."""
    b = _MIN_BUCKET
    while b < s:
        b <<= 1
    return b


# --------------------------------------------------------------------- #
#  Per-scenario solve (vmapped over the batch)                           #
# --------------------------------------------------------------------- #
def _effective_demand(demand, ws, hit, cache_cap, share):
    """jnp twin of profile.effective_demand_arrays (cache hits discount
    HBM traffic; the absorbed stream reappears as L2 demand)."""
    cached = (ws > 0) & (hit > 0)
    resident = jnp.minimum(1.0, (cache_cap * share) / jnp.maximum(ws, 1.0))
    hit_f = hit * resident
    d_hbm = jnp.where(cached, demand[..., _HBM] * (1.0 - hit_f),
                      demand[..., _HBM])
    d_l2 = jnp.where(cached,
                     jnp.maximum(demand[..., _L2], demand[..., _HBM]),
                     demand[..., _L2])
    d = demand.at[..., _HBM].set(d_hbm)
    return d.at[..., _L2].set(d_l2)


def _cache_share(ws, present, cache_cap):
    """The cache-share / thrash-cliff stage: isolated residency is
    proportional (min(1, C/ws)); colocated streaming residency
    collapses once the combined working set exceeds capacity
    (paper Fig. 3's thrash cliff).  ws must already be exclusion-zeroed;
    shapes (S, K) / scalar -> (S, K)."""
    total_ws = ws.sum(-1, keepdims=True)
    resident_col = jnp.where(total_ws > cache_cap, 0.0, 1.0)
    nk = present.sum(-1, keepdims=True)
    has_ws = ws > 0
    return jnp.where(
        has_ws & (nk > 1), resident_col,
        jnp.where(has_ws, jnp.minimum(1.0, cache_cap / jnp.maximum(ws, 1.0)),
                  1.0))


def _solve_one(demand, duration, ws, hit, slots, frac, present, excluded,
               share, cap_vec, cache_cap, n_slots):
    """Water-fill ONE padded scenario: demand (K, A), the rest (K,).
    Inputs are already exclusion-zeroed; `share` is the precomputed
    cache share (the one batch-level stage, see _solve_padded)."""
    K = duration.shape[0]

    eff_col = _effective_demand(demand, ws, hit, cache_cap, share)
    t_col = jnp.maximum((eff_col / cap_vec).max(-1), duration)
    eff_iso = _effective_demand(demand, ws, hit, cache_cap,
                                jnp.ones_like(share))
    t_iso = jnp.maximum((eff_iso / cap_vec).max(-1), duration)
    u = jnp.where(t_col[:, None] > 0,
                  (eff_col / t_col[:, None]) / cap_vec, 0.0)
    slot_scale = jnp.where(frac < 1.0, jnp.maximum(frac, FRACTION_FLOOR),
                           1.0)
    u = jnp.where(_PER_SLOT_MASK[None, :], u / slot_scale[:, None], u)
    axis_load = u.sum(0)

    # freeze-round fixed point: while any axis is oversubscribed, freeze
    # its over-fair-share users (equal throttle on smem, max-min theta
    # elsewhere).  The K + N_AXES bound and the `done` mask mirror the
    # NumPy loop exactly; under vmap, finished scenarios' carries are
    # masked while stragglers keep iterating.
    def cond(carry):
        i, _, _, _, _, done = carry
        return (~done) & (i < K + _N_AXES)

    def body(carry):
        i, speeds, active, frozen, used, done = carry
        dem = (u * (speeds * active)[:, None]).sum(0)
        cap_rem = jnp.maximum(1.0 - used, CAP_REMAIN_FLOOR)
        ratio = dem / cap_rem
        worst = jnp.argmax(ratio)
        worst_ratio = ratio[worst]
        done = done | (worst_ratio <= 1.0 + OVERSUB_RTOL)
        live = ~done
        d = speeds * u[:, worst]

        # smem: bank-conflict serialization throttles EVERY user equally
        is_smem = live & (worst == _SMEM)
        users = active & (d > DEMAND_EPS) & is_smem
        s_eq = 1.0 / jnp.maximum(worst_ratio, RATIO_FLOOR)
        speeds = jnp.where(users, speeds * s_eq, speeds)
        used = used + (u * (speeds * users)[:, None]).sum(0)
        frozen = jnp.where(users, _SMEM, frozen)
        active = active & ~users

        # max-min rate cap theta on worst: sum min(d_n, theta) = cap.
        is_mm = live & (worst != _SMEM)
        elig = active & (d > DEMAND_EPS) & is_mm
        cap_w = cap_rem[worst]
        ds = jnp.where(elig, d, jnp.inf)
        order = jnp.sort(ds)
        finite = jnp.isfinite(order)
        vals = jnp.where(finite, order, 0.0)
        csum = jnp.cumsum(vals)
        m = elig.sum()
        pos = jnp.arange(K)
        even = (cap_w - (csum - vals)) / jnp.maximum(m - pos, 1)
        breach = finite & (order > even) & (pos < m)
        has_theta = breach.any() & is_mm
        theta = even[jnp.argmax(breach)]
        # no breach -> every user fits under the fair share: done
        done = done | (is_mm & ~has_theta)
        throttled = elig & has_theta & (d > theta)
        speeds = jnp.where(throttled,
                           speeds * (theta / jnp.where(d > 0, d, 1.0)),
                           speeds)
        used = used + (u * (speeds * throttled)[:, None]).sum(0)
        frozen = jnp.where(throttled, worst, frozen)
        active = active & ~throttled
        return (i + 1, speeds, active, frozen, used, done)

    init = (jnp.int64(0), jnp.ones(K), present,
            jnp.full(K, -1, jnp.int64), jnp.zeros(_N_AXES),
            jnp.asarray(False))
    _, speeds, _, frozen, _, _ = lax.while_loop(cond, body, init)

    # queueing inflation on near-saturated latency-sensitive axes
    base = (t_col / jnp.maximum(t_iso, TIME_EPS)) / jnp.maximum(speeds,
                                                                SPEED_FLOOR)
    infl = jnp.ones(K)
    for axis, (gamma, p) in _INFLATION.items():
        ai = AXIS_INDEX[axis]
        u_ax = u[:, ai]
        rho = jnp.minimum(1.0, (speeds * u_ax).sum())
        skip = ((frozen == ai) | (u_ax <= _INFLATION_MIN_UTIL)
                | (u_ax >= _INFLATION_MAJORITY
                   * jnp.maximum(rho, SPEED_FLOOR)))
        infl = infl + jnp.where(~skip & present, gamma * rho ** p, 0.0)
    slowdowns = base * infl
    speeds = jnp.where(excluded, 0.0, speeds)
    slowdowns = jnp.where(excluded, jnp.inf, slowdowns)

    tot_slots = (slots * jnp.minimum(frac, 1.0)).sum()
    feasible = (tot_slots <= n_slots) | (tot_slots == 0)
    return speeds, slowdowns, frozen, axis_load, feasible


@jax.jit
def _solve_padded(demand, duration, ws, hit, slots, frac, mask, cap_vec,
                  cache_cap, n_slots):
    """The whole batch solve as one XLA program: exclusion zeroing, the
    cache-share stage, then the vmapped per-scenario water-fill.  One
    trace per padded (S, K); call it under ``jax.enable_x64(True)``."""
    global _trace_count
    _trace_count += 1
    excluded = mask & (frac <= FRACTION_FLOOR)
    present = mask & ~excluded
    demand = jnp.where(present[:, :, None], demand, 0.0)
    duration = jnp.where(present, duration, 0.0)
    ws = jnp.where(present, ws, 0.0)
    hit = jnp.where(present, hit, 0.0)
    slots = jnp.where(present, slots, 0.0)
    share = _cache_share(ws, present, cache_cap)
    return jax.vmap(
        _solve_one,
        in_axes=(0, 0, 0, 0, 0, 0, 0, 0, 0, None, None, None))(
        demand, duration, ws, hit, slots, frac, present, excluded, share,
        cap_vec, cache_cap, n_slots)


def warmup(dev: DeviceModel, ks=(2, 3),
           buckets=(_MIN_BUCKET,)) -> int:
    """Ahead-of-time compile the (bucket, K) shapes a scheduler's group
    pricing will hit, with all-masked zero batches (they solve to
    no-ops).  The dummy operands match the real call signature exactly —
    float64 numpy arrays, python-float scalars — so the warmed traces
    ARE the cache entries later solves hit; device capacities are traced
    operands, so the traces are shared across device models.  Returns
    the number of new traces compiled (0 when every shape was warm)."""
    before = _trace_count
    with jax.enable_x64(True):
        for K in ks:
            for S in buckets:
                shape = (int(S), int(K))
                _solve_padded(
                    np.zeros(shape + (_N_AXES,)), np.zeros(shape),
                    np.zeros(shape), np.zeros(shape), np.zeros(shape),
                    np.ones(shape), np.zeros(shape, bool),
                    dev.capacity_vector(), dev.cache_capacity,
                    float(dev.n_slots))
    return _trace_count - before


def solve_gathered(mask, frac, demand, duration, ws, hit, slots,
                   dev: DeviceModel) -> Tuple[np.ndarray, ...]:
    """Entry point for `estimator.solve_batch`'s jax dispatch: takes the
    NumPy-gathered padded arrays, pads the batch up to its size bucket
    (masked rows solve to no-ops), runs the jitted program, and returns
    NumPy (speeds, slowdowns, bottleneck, axis_load, feasible_slots)."""
    S, K = mask.shape
    with obs.span("price.solve"):
        pad = _bucket(S) - S
        if pad:
            z = ((0, pad), (0, 0))
            mask = np.pad(mask, z)
            frac = np.pad(frac, z, constant_values=1.0)
            demand = np.pad(demand, z + ((0, 0),))
            duration = np.pad(duration, z)
            ws = np.pad(ws, z)
            hit = np.pad(hit, z)
            slots = np.pad(slots, z)
        with jax.enable_x64(True):
            out = _solve_padded(demand, duration, ws, hit, slots, frac, mask,
                                dev.capacity_vector(), dev.cache_capacity,
                                float(dev.n_slots))
            jax.block_until_ready(out)
            with obs.span("price.fetch"):
                return tuple(np.asarray(o)[:S] for o in out)
