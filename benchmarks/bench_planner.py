"""Estimator + scheduler throughput: batched/incremental vs the seed.

Measures
  1. estimator solves/sec: seed pure-Python `estimate`, the current scalar
     wrapper looped, and `estimate_batch` in one vectorized pass over the
     same scenarios (target: batch >= 10x looped on 1k scenarios);
  2. cold `ColocationScheduler.plan()` wall-time at n in {16, 64, 256,
     1024} workloads (target: >= 20x vs the seed O(n^3) planner at n=256);
  3. online churn: with n resident workloads, arrive/leave events must
     replan with O(n) estimator scenarios each (the cached price matrix
     makes re-planning a row update, not an O(n^2) re-price);
  4. the partition-search gate: on the SLO-tight decode-heavy mix the
     k-way slot-fraction search must strictly beat the legacy fixed-grid
     pair planner in total gain via partitioned groups of size > 2;
  5. the jax solver-backend gate: numpy/jax parity at 1e-9 on a 10k
     mixed-width scenario sweep, a batch-size throughput sweep (jax must
     reach >= 10x the deployed numpy estimate_batch baseline at batch
     >= 4096), and the denser jax-default fraction search matching the
     partition gate's gain.

`--quick` (the CI smoke) also writes BENCH_planner.json — plan latency,
scenarios/arrival, and the partition-search gate in machine-readable
form, uploaded as a CI artifact.

Outputs are cross-checked against the seed at <= 1e-9 (slowdowns,
speeds, plus placement-for-placement Plan equality) wherever the seed is
actually run; beyond --seed-cap workloads the seed planner would take
hours, so its time is extrapolated from its measured per-pair cost and
marked "est".

  PYTHONPATH=src python benchmarks/bench_planner.py          # full sweep
  PYTHONPATH=src python benchmarks/bench_planner.py --quick  # CI smoke
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np

import _seed_reference as seed
from repro.core import (LEGACY_SEARCH, TPU_V5E, ColocationScheduler,
                        KernelProfile, WorkloadProfile, estimate,
                        estimate_batch)
from repro.core.resources import RESOURCE_AXES
from repro.launch.cache import enable_compile_cache

TOL = 1e-9


def cold_plan(works, dev, max_group_size=2, search=None):
    """One-shot plan through the online API (what `plan_colocation`
    forwards to, minus the DeprecationWarning).  `search=LEGACY_SEARCH`
    reproduces the seed's fixed-grid pair behavior bit-for-bit; the
    default is the full k-way fraction search."""
    sched = ColocationScheduler(dev, max_group_size=max_group_size,
                                fraction_search=search)
    for w in works:
        sched.submit(w)
    return sched.plan()


# ------------------------------------------------------------------ #
#  Random workload generation (continuous draws: no branch ties).     #
#  tests/test_batch_estimator.py imports these so the oracle tests    #
#  and the benchmark fuzz the same input distribution; the optional   #
#  flags steer draws into specific estimator branches and leave the   #
#  default draw sequence untouched.                                   #
# ------------------------------------------------------------------ #
def random_profile(rng, name, dev, zero_axes=False, smem_heavy=False,
                   cache_heavy=False):
    d = {r: float(rng.uniform(0.02, 1.1)) * dev.capacity(r)
         for r in RESOURCE_AXES}
    if zero_axes and rng.random() < 0.3:
        for r in rng.choice(RESOURCE_AXES, size=3, replace=False):
            d[r] = 0.0
    if smem_heavy:
        d["smem"] = float(rng.uniform(0.8, 1.6)) * dev.capacity("smem")
    ws, hit = 0.0, 0.0
    if cache_heavy or rng.random() < 0.3:
        ws = float(rng.uniform(0.1, 1.5)) * dev.cache_capacity
        hit = float(rng.uniform(0.1, 1.0))
    return KernelProfile(
        name, demand=d,
        duration=float(rng.uniform(0.5, 2.0)) if rng.random() < 0.5 else None,
        cache_working_set=ws, cache_hit_fraction=hit)


def random_scenarios(rng, n, dev):
    return [[random_profile(rng, f"s{s}k{i}", dev)
             for i in range(int(rng.integers(2, 5)))] for s in range(n)]


def random_workloads(rng, n, dev):
    return [WorkloadProfile(
        f"w{i}",
        tuple(random_profile(rng, f"w{i}p{j}", dev)
              for j in range(int(rng.integers(1, 3)))),
        slo_slowdown=float(rng.uniform(1.1, 1.6)))
        for i in range(n)]


def decode_heavy_mix(dev, n_decode=4, n_aux=2):
    """The SLO-tight decode-heavy mix of the partition-search gate
    (tests/test_fracsearch.py imports it — single source of truth).

    Decode instances are bandwidth-bound (hbm/l2 0.6) with light compute
    and a tight 1.15x SLO: two of them over-commit the device-wide
    bandwidth axes at full share, but slot-partitioning (0.5, 0.5)
    throttles each other's representative to its slice and rescues the
    pair.  The aux jobs are short best-effort VPU bursts (distillation /
    eval-style) whose partitioned representative freezes on an axis the
    decodes never contend on, so a k-way fraction search can pack
    decode+decode+aux per device — the fixed-grid pair planner cannot."""
    def prof(name, slo, dur, **u):
        d = {r: u.get(r, 0.0) * dev.capacity(r) for r in RESOURCE_AXES}
        return WorkloadProfile(
            name, (KernelProfile(f"{name}#step", demand=d, duration=dur),),
            slo_slowdown=slo)

    decodes = [prof(f"decode{i}", 1.15, 1.0, mxu=0.4, vpu=0.1, issue=0.1,
                    smem=0.05, hbm=0.6, l2=0.6) for i in range(n_decode)]
    aux = [prof(f"aux{i}", 12.0, 0.08, vpu=0.072, issue=0.004, mxu=0.004,
                hbm=0.0016, l2=0.0016) for i in range(n_aux)]
    return decodes + aux


# ------------------------------------------------------------------ #
#  Checks                                                             #
# ------------------------------------------------------------------ #
def max_result_diff(a, b) -> float:
    return max(
        max(abs(a.slowdowns[k] - b.slowdowns[k]) for k in b.slowdowns),
        max(abs(a.speeds[k] - b.speeds[k]) for k in b.speeds))


def assert_plans_equal(got, want):
    assert [p.workloads for p in got.placements] == \
        [p.workloads for p in want.placements], "placement order differs"
    assert got.solo == want.solo, "solo set differs"
    for g, w in zip(got.placements, want.placements):
        assert g.slot_fraction == w.slot_fraction
        assert g.meets_slo == w.meets_slo
        assert abs(g.throughput_gain - w.throughput_gain) <= TOL
        for k in w.predicted_slowdown:
            assert abs(g.predicted_slowdown[k]
                       - w.predicted_slowdown[k]) <= TOL


# ------------------------------------------------------------------ #
#  Benches                                                            #
# ------------------------------------------------------------------ #
def _best_of(fn, reps: int = 3):
    """Min wall-time over reps (standard noise suppression) + last result."""
    best, result = float("inf"), None
    for _ in range(reps):
        t0 = time.perf_counter()
        result = fn()
        best = min(best, time.perf_counter() - t0)
    return best, result


def bench_estimator(n_scenarios: int, dev) -> float:
    rng = np.random.default_rng(0)
    scenarios = random_scenarios(rng, n_scenarios, dev)

    t_seed, seed_results = _best_of(
        lambda: [seed.estimate(sc, dev) for sc in scenarios])
    t_loop, loop_results = _best_of(
        lambda: [estimate(sc, dev) for sc in scenarios])
    t_batch, batch_results = _best_of(
        lambda: estimate_batch(scenarios, dev))

    err_loop = max(max_result_diff(g, w)
                   for g, w in zip(batch_results, loop_results))
    err_seed = max(max_result_diff(g, w)
                   for g, w in zip(batch_results, seed_results))
    assert err_loop <= TOL, f"batch vs looped estimate: {err_loop:.2e}"
    assert err_seed <= TOL, f"batch vs seed estimate: {err_seed:.2e}"

    print(f"\n== estimator: {n_scenarios} scenarios (2-4 kernels each) on "
          f"{dev.name} ==")
    print(f"  seed scalar loop   {t_seed:8.3f}s  "
          f"({n_scenarios / t_seed:9.0f} solves/s)")
    print(f"  wrapper loop       {t_loop:8.3f}s  "
          f"({n_scenarios / t_loop:9.0f} solves/s)")
    print(f"  estimate_batch     {t_batch:8.3f}s  "
          f"({n_scenarios / t_batch:9.0f} solves/s)")
    print(f"  batch vs looped    {t_loop / t_batch:8.1f}x   "
          f"(max |diff| {max(err_loop, err_seed):.1e})")
    print(f"  batch vs seed      {t_seed / t_batch:8.1f}x")
    return t_loop / t_batch


def bench_planner(ns, seed_cap: int, dev) -> dict:
    print(f"\n== planner: greedy SLO-feasible pairing on {dev.name} ==")
    print(f"  {'n':>5} {'pairs':>8} {'new (s)':>9} {'seed (s)':>10} "
          f"{'speedup':>9}  plan")
    speedups = {}
    latency = {}
    per_pair_cost = None
    for n in ns:
        rng = np.random.default_rng(42)
        works = random_workloads(rng, n, dev)
        pairs = n * (n - 1) // 2

        # headline timing: the DEFAULT config (full fraction search)
        t0 = time.perf_counter()
        plan = cold_plan(works, dev)
        t_new = time.perf_counter() - t0
        latency[n] = t_new
        rounds = len(plan.placements) + 1

        if n <= seed_cap:
            t0 = time.perf_counter()
            seed_plan = seed.plan_colocation(works, dev)
            t_seed = time.perf_counter() - t0
            # equivalence oracle: the LEGACY fixed-grid config must
            # reproduce the seed planner placement-for-placement
            assert_plans_equal(cold_plan(works, dev, search=LEGACY_SEARCH),
                               seed_plan)
            # greedy rounds each rescan ~all pairs: amortized per-pair cost
            per_pair_cost = t_seed / (rounds * pairs)
            tag = ""
        elif per_pair_cost is not None:
            t_seed = per_pair_cost * rounds * pairs
            tag = " est"
        else:
            t_seed, tag = float("nan"), " n/a"
        speedups[n] = t_seed / t_new
        print(f"  {n:>5} {pairs:>8} {t_new:>9.3f} {t_seed:>10.2f}{tag:<4}"
              f"{t_seed / t_new:>8.0f}x  "
              f"{len(plan.placements)} pairs, {len(plan.solo)} solo, "
              f"gain {plan.total_gain:.2f}")
    return {"speedups": speedups, "latency_s": latency}


def bench_churn(n: int, events: int, dev, max_group_size: int = 2) -> dict:
    """Online arrive/leave trace: per-event estimator work must stay O(n).

    Starts from a cold pool of n workloads, then alternates departures
    (random resident) and arrivals (fresh workload), replanning after
    every event. Reports wall-time and estimator-scenario counts per
    event, cross-checked for placement equality against a cold plan on
    the surviving set after the last event."""
    rng = np.random.default_rng(7)
    pool = random_workloads(rng, n + (events + 1) // 2, dev)
    sched = ColocationScheduler(dev, max_group_size=max_group_size)
    for w in pool[:n]:
        sched.submit(w)
    t0 = time.perf_counter()
    sched.plan()
    t_cold = time.perf_counter() - t0
    cold_scen = sched.stats["scenarios_solved"]

    resident = list(pool[:n])
    fresh = list(pool[n:])
    arr_t, dep_t, arr_scen, dep_scen = [], [], [], []
    for e in range(events):
        s0 = sched.stats["scenarios_solved"]
        t0 = time.perf_counter()
        if e % 2 == 0:                      # departure
            p0 = sched.stats["pairs_priced"]
            victim = resident.pop(int(rng.integers(len(resident))))
            sched.remove(victim.name)
            sched.plan()
            dep_t.append(time.perf_counter() - t0)
            assert sched.stats["pairs_priced"] == p0, \
                "departure must not re-price any pair"
            if max_group_size == 2:
                # k>2 replans may legitimately price never-seen GROUP
                # combinations; the pairwise matrix is always untouched
                assert sched.stats["scenarios_solved"] == s0, \
                    "departure must not trigger estimator work at k=2"
            dep_scen.append(sched.stats["scenarios_solved"] - s0)
        else:                               # arrival
            w = fresh.pop()
            resident.append(w)
            sched.submit(w)
            sched.plan()
            arr_t.append(time.perf_counter() - t0)
            arr_scen.append(sched.stats["scenarios_solved"] - s0)

    final = sched.plan()
    assert_plans_equal(final, cold_plan(resident, dev, max_group_size))

    m = len(resident)
    scen_per_arrival = float(np.mean(arr_scen))
    # a full re-price would re-solve every pair's kernel probes (the cold
    # count); an arrival's new row is ~cold/n of that
    ratio = cold_scen / max(scen_per_arrival, 1e-9)
    print(f"\n== online churn: n={n} resident, {events} events "
          f"(k<={max_group_size}) on {dev.name} ==")
    print(f"  cold plan          {t_cold:8.3f}s  "
          f"({cold_scen} estimator scenarios)")
    print(f"  arrival event      {np.mean(arr_t):8.3f}s  "
          f"({scen_per_arrival:.0f} scenarios — {ratio:.0f}x fewer "
          f"than a cold re-price)")
    print(f"  departure event    {np.mean(dep_t):8.3f}s  "
          f"({np.mean(dep_scen):.0f} scenarios)")
    # O(n) scenarios with a constant covering the fraction search's
    # coarse grid + refinement on every SLO-failing pair of the new row
    # (the constant follows the active config — the jax backend's denser
    # default grid prices more candidates per pair)
    per_pair = 5 * (sched.search.steps_for(2) - 1
                    + sched.search.refine_levels)
    o_n = scen_per_arrival <= per_pair * (m + 1)
    print(f"  arrival estimator work O(n): "
          f"{'PASS' if o_n else 'FAIL'} "
          f"({scen_per_arrival:.0f} scenarios vs n={m})")
    return {"o_n": o_n, "scen_per_arrival": scen_per_arrival,
            "cold_scen": cold_scen}


def bench_partition_search(dev) -> dict:
    """The k-way slot-fraction search gate: on the SLO-tight decode-heavy
    mix, the k=3 scheduler with the default search must strictly beat the
    legacy fixed-grid pair planner in total gain, via partitioned groups
    of size > 2 (every member within SLO)."""
    mix = decode_heavy_mix(dev)

    t0 = time.perf_counter()
    baseline = cold_plan(mix, dev, max_group_size=2, search=LEGACY_SEARCH)
    t_base = time.perf_counter() - t0
    t0 = time.perf_counter()
    kway = cold_plan(mix, dev, max_group_size=3)
    t_kway = time.perf_counter() - t0

    grown = [p for p in kway.placements
             if len(p.workloads) > 2 and p.slot_fraction]
    ok = (kway.total_gain > baseline.total_gain + 1e-6 and bool(grown)
          and all(p.meets_slo for p in kway.placements))
    print(f"\n== partition search: SLO-tight decode-heavy mix "
          f"({len(mix)} workloads) on {dev.name} ==")
    print(f"  fixed-grid pairs   gain {baseline.total_gain:8.3f}  "
          f"({len(baseline.placements)} placements, "
          f"{len(baseline.solo)} solo, {t_base:.3f}s)")
    print(f"  k-way + search     gain {kway.total_gain:8.3f}  "
          f"({len(kway.placements)} placements, "
          f"{len(kway.solo)} solo, {t_kway:.3f}s)")
    for p in kway.placements:
        fr = {n: round(f, 4) for n, f in p.slot_fraction.items()}
        print(f"    {'+'.join(p.workloads):32s} fractions {fr or 'full'}")
    print(f"  partitioned k-way groups beat fixed-grid pairs: "
          f"{'PASS' if ok else 'FAIL'}")
    return {
        "baseline_gain": baseline.total_gain,
        "kway_gain": kway.total_gain,
        "kway_groups": [
            {"workloads": p.workloads, "fractions": p.slot_fraction,
             "gain": p.throughput_gain} for p in kway.placements],
        "pass": ok,
    }


def bench_solver(dev, partition_gain: float, n_parity: int = 10_000) -> dict:
    """The jax solver-backend gate (ISSUE 8): numpy/jax parity at 1e-9
    on a mixed-width scenario sweep, a batch-size throughput sweep, and
    the denser jax-default fraction search matching the partition gate.

    The speedup gate compares the warmed jax path against the DEPLOYED
    numpy baseline — `estimate_batch` end-to-end on mixed scenarios, the
    ~28k solves/s this repo's schedulers actually paid before ISSUE 8
    (the raw dense solve_batch-vs-solve_batch ratio is recorded too)."""
    try:
        from repro.core import set_solver_backend, solver_backend  # noqa
        from repro.core import estimator_jax  # noqa: F401
    except (ImportError, RuntimeError) as e:
        print(f"\n== solver backend: jax unavailable ({e}) ==")
        return {"available": False, "pass": False}
    from repro.core.estimator import solve_batch, solve_scenarios
    from repro.core.profile import ProfileMatrix
    from repro.core.scenario import Scenario

    rng = np.random.default_rng(0)

    # -- parity: mixed-width (ragged) scenarios through the padded path --
    kernels = random_scenarios(rng, n_parity, dev)
    scens = [Scenario(tuple(sc)) for sc in kernels]
    r_np = solve_scenarios(scens, dev)
    with solver_backend("jax"):
        r_jx = solve_scenarios(scens, dev)
    parity = 0.0
    parity_ok = True
    for field in ("speeds", "slowdowns", "axis_load"):
        a, b = getattr(r_np, field), getattr(r_jx, field)
        fin = np.isfinite(a)
        parity_ok &= bool((np.isfinite(b) == fin).all())
        err = (float((np.abs(a[fin] - b[fin])
                      / (1.0 + np.abs(a[fin]))).max()) if fin.any() else 0.0)
        parity = max(parity, err)
        parity_ok &= bool(np.allclose(b[fin], a[fin], rtol=TOL, atol=TOL))
    parity_ok &= bool((r_np.bottleneck == r_jx.bottleneck).all())
    parity_ok &= bool((r_np.feasible_slots == r_jx.feasible_slots).all())

    # -- deployed numpy baseline: what schedulers paid pre-ISSUE 8 --
    base_n = min(1000, n_parity)
    t_dep, _ = _best_of(lambda: estimate_batch(kernels[:base_n], dev))
    deployed = base_n / t_dep

    # -- batch-size sweep: raw dense solve_batch, numpy vs warmed jax --
    profs = [random_profile(rng, f"sv{i}", dev) for i in range(64)]
    pm = ProfileMatrix.from_profiles(profs)
    sweep = {}
    print(f"\n== solver backend: numpy vs jax on {dev.name} "
          f"(deployed numpy baseline {deployed:,.0f} solves/s) ==")
    print(f"  parity sweep       {n_parity} mixed-width scenarios, "
          f"max rel err {parity:.1e}: {'PASS' if parity_ok else 'FAIL'}")
    for S in (256, 1024, 4096, 16384):
        idx = rng.integers(0, len(profs), (S, 4))
        t_np, _ = _best_of(lambda: solve_batch(pm, idx, dev))
        with solver_backend("jax"):
            solve_batch(pm, idx, dev)            # warm the trace
            t_jx, _ = _best_of(lambda: solve_batch(pm, idx, dev))
        sweep[S] = {"numpy_solves_per_s": S / t_np,
                    "jax_solves_per_s": S / t_jx,
                    "raw_speedup": t_np / t_jx,
                    "speedup_vs_deployed": (S / t_jx) / deployed}
        print(f"  batch {S:>6}       numpy {S / t_np:>9,.0f}/s   "
              f"jax {S / t_jx:>9,.0f}/s   raw {t_np / t_jx:4.1f}x   "
              f"vs deployed {sweep[S]['speedup_vs_deployed']:5.1f}x")
    speedup = max(v["speedup_vs_deployed"] for s, v in sweep.items()
                  if s >= 4096)

    # -- denser jax-default fraction search: gain >= the partition gate --
    mix = decode_heavy_mix(dev)
    with solver_backend("jax"):
        t0 = time.perf_counter()
        kway = cold_plan(mix, dev, max_group_size=3)
        t_dense = time.perf_counter() - t0
    dense_gain = kway.total_gain
    dense_ok = dense_gain >= partition_gain - 1e-9
    print(f"  dense search gain  {dense_gain:.3f} vs partition gate "
          f"{partition_gain:.3f} ({t_dense:.2f}s incl. jit warmup): "
          f"{'PASS' if dense_ok else 'FAIL'}")
    ok = parity_ok and speedup >= 10 and dense_ok
    print(f"  jax >= 10x deployed numpy at batch >= 4096: "
          f"{'PASS' if speedup >= 10 else 'FAIL'} ({speedup:.1f}x)")
    return {
        "available": True,
        "parity_scenarios": n_parity,
        "parity_max_rel_err": parity,
        "parity_pass": bool(parity_ok),
        "numpy_deployed_solves_per_s": deployed,
        "batch_sweep": {str(s): v for s, v in sweep.items()},
        "speedup_vs_deployed": speedup,
        "dense_search_gain": dense_gain,
        "dense_search_wall_s": t_dense,
        "pass": bool(ok),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: small n, fewer scenarios; writes "
                         "BENCH_planner.json unless --json overrides it")
    ap.add_argument("--json", type=str, default=None,
                    help="write a machine-readable result summary to this "
                         "path (plan latency, scenarios/arrival, partition-"
                         "search gate; implied as BENCH_planner.json by "
                         "--quick)")
    ap.add_argument("--n", type=int, nargs="*", default=None,
                    help="workload counts to plan (default 16 64 256 1024)")
    ap.add_argument("--scenarios", type=int, default=None,
                    help="estimator batch size (default 1000)")
    ap.add_argument("--seed-cap", type=int, default=None,
                    help="largest n at which the seed planner actually runs "
                         "(beyond: extrapolated; default 256, quick 64)")
    ap.add_argument("--churn-n", type=int, default=256,
                    help="resident workloads in the online-churn bench")
    ap.add_argument("--churn-events", type=int, default=64,
                    help="arrive/leave events in the online-churn bench")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.quick:
        ns = args.n or [16, 64]
        n_scen = args.scenarios or 250
        seed_cap = args.seed_cap if args.seed_cap is not None else 64
    else:
        ns = args.n or [16, 64, 256, 1024]
        n_scen = args.scenarios or 1000
        seed_cap = args.seed_cap if args.seed_cap is not None else 256

    batch_speedup = bench_estimator(n_scen, TPU_V5E)
    planner = bench_planner(ns, seed_cap, TPU_V5E)
    plan_speedups = planner["speedups"]
    churn = bench_churn(args.churn_n, args.churn_events, TPU_V5E)
    partition = bench_partition_search(TPU_V5E)
    solver = bench_solver(TPU_V5E, partition["kway_gain"])

    print("\n== acceptance ==")
    ok_batch = batch_speedup >= 10
    print(f"  estimate_batch >= 10x looped estimate: "
          f"{'PASS' if ok_batch else 'FAIL'} ({batch_speedup:.1f}x)")
    target_n = 256
    if target_n in plan_speedups:
        ok_plan = plan_speedups[target_n] >= 20
        print(f"  cold plan >= 20x seed @ n={target_n}: "
              f"{'PASS' if ok_plan else 'FAIL'} "
              f"({plan_speedups[target_n]:.0f}x)")
    else:
        ok_plan = all(s >= 20 for k, s in plan_speedups.items()
                      if k >= 64 and np.isfinite(s))
        print(f"  cold plan >= 20x seed (n<=cap measured): "
              f"{'PASS' if ok_plan else 'FAIL'} "
              f"({ {k: round(v, 1) for k, v in plan_speedups.items()} })")
    ok_churn = churn["o_n"]
    print(f"  arrival replans with O(n) estimator scenarios: "
          f"{'PASS' if ok_churn else 'FAIL'} "
          f"({churn['scen_per_arrival']:.0f} per arrival vs "
          f"{churn['cold_scen']} cold)")
    ok_part = partition["pass"]
    print(f"  partitioned k-way groups > fixed-grid pairs: "
          f"{'PASS' if ok_part else 'FAIL'} "
          f"({partition['kway_gain']:.3f} vs "
          f"{partition['baseline_gain']:.3f})")
    ok_solver = solver["pass"]
    print(f"  jax solver backend (parity + >= 10x deployed + dense "
          f"search): {'PASS' if ok_solver else 'FAIL'}")

    ok = ok_batch and ok_plan and ok_churn and ok_part and ok_solver
    json_path = args.json or ("BENCH_planner.json" if args.quick else None)
    if json_path:
        payload = {
            "estimator_batch_speedup": batch_speedup,
            "plan_latency_s": {str(n): t
                               for n, t in planner["latency_s"].items()},
            "plan_speedup_vs_seed": {str(n): (None if not np.isfinite(s)
                                              else s)
                                     for n, s in plan_speedups.items()},
            "churn": {"scenarios_per_arrival": churn["scen_per_arrival"],
                      "cold_scenarios": churn["cold_scen"],
                      "o_n_pass": bool(churn["o_n"])},
            "partition_search": partition,
            "solver": solver,
            "acceptance": {"batch": ok_batch, "plan": ok_plan,
                           "churn": ok_churn, "partition": ok_part,
                           "solver": ok_solver, "all": ok},
        }
        Path(json_path).write_text(json.dumps(payload, indent=2) + "\n")
        print(f"\n  wrote {json_path}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
