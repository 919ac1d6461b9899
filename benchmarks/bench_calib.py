"""Calibration gate: the measure -> fit -> validate -> drift loop holds.

Two CI contracts over `repro.calib`:

1. **Synthetic round-trip** — hide perturbed ground-truth KernelProfiles
   behind the deterministic synthetic backend, run the §4 stressor×victim
   sweep, fit profiles from the observed slowdowns alone, then score the
   fit on HELD-OUT k-way mixes (victim+cohort colocations and off-grid
   stressor intensities the fitter never saw).  Gate: max relative
   slowdown-prediction error <= 5%.  The whole pipeline is seeded, so
   the calibration report must also be bit-identical across two runs.

2. **Drift monitor** — replay a fixed sim trace with a mid-trace
   profile shift injected into one colocated SLO tenant (its TRUE
   demand inflates past its roofline while the fleet keeps believing
   the original).  Gate: exactly that tenant is flagged and re-fit, the
   clean same-seed twin trace produces zero flags, calib counters
   surface in fleet stats and the sim report, and the shifted run's
   report is bit-identical across two runs.

  PYTHONPATH=src python benchmarks/bench_calib.py          # full
  PYTHONPATH=src python benchmarks/bench_calib.py --quick  # CI gate
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np

from repro.calib import (FitConfig, SyntheticBackend, fit_profiles,
                         fit_report, holdout_mixes, perturb_profile,
                         validate)
from repro.core.fleet import SLO
from repro.launch.cache import enable_compile_cache
from repro.core.profile import KernelProfile
from repro.core.resources import TPU_V5E, TPU_V5P
from repro.sim import Simulator, TraceConfig, generate_trace

MAX_REL_ERROR = 0.05         # held-out mix prediction error ceiling
SEED = 2026
SHIFT_T = 30.0               # virtual seconds into the drift trace
DRIFT_TRACE = dict(seed=11, duration=90.0, n_tenants=14, n_bursts=1,
                   churn_fraction=0.0)
DRIFT_DEVICES = 6


# ------------------------------------------------------------------ #
#  Round-trip: hidden truth -> sweep -> fit -> held-out validation     #
# ------------------------------------------------------------------ #
def base_kernels(dev) -> dict:
    """A diverse victim set: bandwidth-bound decode, matmul-bound gemm,
    vector scan, a cache-resident attention-like kernel, and a
    scratch/interconnect-leaning collective — one per paper workload
    archetype, all duration-bound like the registry profiles."""
    C = dev.capacity
    return {
        "decode": KernelProfile("decode", demand={
            "hbm": 0.70 * C("hbm"), "mxu": 0.25 * C("mxu"),
            "issue": 0.30 * C("issue")}, duration=1.0),
        "gemm": KernelProfile("gemm", demand={
            "mxu": 0.85 * C("mxu"), "hbm": 0.20 * C("hbm")}, duration=1.0),
        "scan": KernelProfile("scan", demand={
            "vpu": 0.75 * C("vpu"), "issue": 0.45 * C("issue"),
            "smem": 0.30 * C("smem"), "hbm": 0.25 * C("hbm")},
            duration=1.0),
        "attn": KernelProfile("attn", demand={
            "hbm": 0.60 * C("hbm"), "vpu": 0.30 * C("vpu")}, duration=1.0,
            cache_working_set=0.5 * dev.cache_capacity,
            cache_hit_fraction=0.6),
        "allreduce": KernelProfile("allreduce", demand={
            "ici": 0.65 * C("ici"), "hbm": 0.35 * C("hbm"),
            "issue": 0.20 * C("issue")}, duration=1.0),
    }


def run_roundtrip(seed: int = SEED, dev=TPU_V5E, noise: float = 0.0) -> dict:
    rng = np.random.default_rng(seed)
    truth = {n: perturb_profile(k, rng, scale=0.25, dev=dev)
             for n, k in base_kernels(dev).items()}
    backend = SyntheticBackend(truth, dev, noise=noise, seed=seed + 1)
    t0 = time.perf_counter()
    sweep = backend.run_sweep(sorted(truth))
    fitted = fit_profiles(sweep, FitConfig())
    fit_s = time.perf_counter() - t0
    mixes = holdout_mixes(sorted(truth), np.random.default_rng(seed + 2))
    report = validate(fitted, backend, mixes)
    return {
        "device": dev.name,
        "noise": noise,
        "n_observations": len(sweep),
        "fit_seconds": fit_s,
        "fit": fit_report(sweep, fitted).to_json(),
        "validation": report.to_json(),
    }


# ------------------------------------------------------------------ #
#  Drift: injected profile shift on a fixed sim trace                  #
# ------------------------------------------------------------------ #
def drift_devices() -> dict:
    return {f"dev{i}": (TPU_V5E if i % 2 else TPU_V5P)
            for i in range(DRIFT_DEVICES)}


def pick_shift_target() -> tuple:
    """Deterministic discovery: run the clean trace once and pick the
    first (sorted device order) long-lived SLO tenant placed in a >=2
    group, with a demand scale that pushes its roofline 1.4x past its
    duration — the regime where a pure demand shift is observable (see
    repro.calib.drift)."""
    trace = generate_trace(TraceConfig(**DRIFT_TRACE))
    sim = Simulator(trace, drift_devices())
    sim.run()
    plan = sim.fleet.plan()
    for did in sorted(plan.placements):
        p = plan.placements[did]
        if len(p.workloads) < 2:
            continue
        for name in p.workloads:
            spec = trace.tenants.get(name)
            if spec is None or spec.priority != SLO \
                    or spec.depart is not None:
                continue
            model = sim.fleet.devices[did].model
            umax = max(spec.profile.mixed_utilization(model).values())
            return name, 1.4 / max(umax, 1e-9)
    raise RuntimeError("drift trace has no colocated SLO tenant to shift")


def run_drift(tenant: str, scale: float) -> dict:
    cfg = TraceConfig(**DRIFT_TRACE,
                      profile_shifts=((SHIFT_T, tenant, scale),))
    sim = Simulator(generate_trace(cfg), drift_devices())
    return sim.run()


def run_clean() -> dict:
    sim = Simulator(generate_trace(TraceConfig(**DRIFT_TRACE)),
                    drift_devices())
    return sim.run()


# ------------------------------------------------------------------ #
#  Gates                                                               #
# ------------------------------------------------------------------ #
def _no_timing(report: dict) -> dict:
    return {k: v for k, v in report.items() if k != "fit_seconds"}


def gate(roundtrip: dict, roundtrip_twin: dict, shifted: dict,
         shifted_twin: dict, clean: dict, tenant: str) -> dict:
    val = roundtrip["validation"]
    calib = shifted["calib"]
    checks = {
        "roundtrip_max_rel_error": val["max_rel_error"] <= MAX_REL_ERROR,
        "roundtrip_deterministic": (_no_timing(roundtrip)
                                    == _no_timing(roundtrip_twin)),
        "drift_flagged": (calib["flags"] >= 1
                          and calib["flagged_tenants"] == [tenant]),
        "drift_refit": calib["refits"] >= 1,
        "drift_no_errors": shifted["fleet"]["event_loop_errors"] == 0,
        "clean_zero_flags": (clean["calib"]["flags"] == 0
                             and clean["calib"]["refits"] == 0
                             and clean["calib"]["flagged_tenants"] == []),
        "clean_observed": clean["calib"]["observations"] > 0,
        "drift_deterministic": shifted == shifted_twin,
    }
    checks["all"] = all(checks.values())
    return checks


def describe(roundtrip: dict, shifted: dict, clean: dict,
             tenant: str, scale: float) -> None:
    val = roundtrip["validation"]
    print("== synthetic round-trip ==")
    print(f"  {roundtrip['n_observations']} sweep observations on "
          f"{roundtrip['device']}, fit in "
          f"{roundtrip['fit_seconds']:.1f}s")
    print(f"  held-out mixes: {val['n_mixes']}, max rel error "
          f"{val['max_rel_error']:.4f} (mean {val['mean_rel_error']:.4f},"
          f" ceiling {MAX_REL_ERROR})")
    worst_axis = max(val["per_axis"], key=lambda a: val["per_axis"][a])
    print(f"  worst axis {worst_axis} "
          f"({val['per_axis'][worst_axis]:.4f}), worst mix "
          f"{val['worst_mix']}")
    print("== drift monitor ==")
    c, cc = shifted["calib"], clean["calib"]
    print(f"  shifted {tenant} x{scale:.1f} at t={SHIFT_T:.0f}s: "
          f"{c['flags']} flags {c['refits']} refits "
          f"(flagged: {', '.join(c['flagged_tenants']) or '-'}), "
          f"{c['observations']} observations")
    print(f"  clean twin: {cc['flags']} flags {cc['refits']} refits, "
          f"{cc['observations']} observations")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI gate; writes BENCH_calib.json unless "
                         "--json overrides it")
    ap.add_argument("--json", type=str, default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    roundtrip = run_roundtrip()
    roundtrip_twin = run_roundtrip()
    tenant, scale = pick_shift_target()
    shifted = run_drift(tenant, scale)
    shifted_twin = run_drift(tenant, scale)
    clean = run_clean()
    describe(roundtrip, shifted, clean, tenant, scale)

    extras = {}
    if not args.quick:
        noisy = run_roundtrip(noise=0.01)
        v5p = run_roundtrip(dev=TPU_V5P)
        print("== variants ==")
        print(f"  1% lognormal noise: max rel error "
              f"{noisy['validation']['max_rel_error']:.4f}")
        print(f"  v5p round-trip: max rel error "
              f"{v5p['validation']['max_rel_error']:.4f}")
        extras = {"noisy": noisy, "v5p": v5p}

    checks = gate(roundtrip, roundtrip_twin, shifted, shifted_twin,
                  clean, tenant)
    print("\n== acceptance ==")
    for name, ok in checks.items():
        if name != "all":
            print(f"  {name}: {'PASS' if ok else 'FAIL'}")

    json_path = args.json or ("BENCH_calib.json" if args.quick else None)
    if json_path:
        payload = {
            "roundtrip": roundtrip,
            "drift": {"tenant": tenant, "scale": scale,
                      "shifted": shifted["calib"],
                      "shifted_fleet": shifted["fleet"],
                      "clean": clean["calib"]},
            "acceptance": checks,
            **extras,
        }
        Path(json_path).write_text(json.dumps(payload, indent=2,
                                              sort_keys=True) + "\n")
        print(f"\n  wrote {json_path}")
    return 0 if checks["all"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
