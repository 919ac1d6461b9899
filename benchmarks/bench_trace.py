"""Trace-driven serving gate: SLO attainment under sustained load.

Replays a fixed seeded diurnal+burst trace (>=1k requests, >=32 tenants,
a mid-trace device kill) through the `repro.sim` closed loop — fleet
event loop + interference-inflated request serving on one virtual
clock — and gates the paper's operational claim: the
estimator/scheduler/fleet stack keeps *per-request* SLOs predictable
under multi-tenant colocation, arrival storms, churn, and faults.

Gates (the CI contract):
  1. SLO-class attainment >= 0.95 on the fixed trace, with the kill's
     outage as the only tolerated misses;
  2. zero event-loop errors (the fleet's no-crash contract holds under
     ~3k scripted events);
  3. determinism — the whole generate->simulate->report pipeline is run
     TWICE from the same seed and the reports must match bit-for-bit;
  4. trace floor — the gate is meaningless on a toy tape, so the trace
     itself must carry >=1000 requests, >=32 tenants, >=1 device death.

`--quick` (the CI smoke) runs the same fixed trace — it is already
sized to the floor — and writes BENCH_trace.json as a CI artifact next
to the planner/fleet benches.  The full run adds a calm (fault-free)
and a storm-heavy variant for context; only the fixed trace gates.

  PYTHONPATH=src python benchmarks/bench_trace.py          # full
  PYTHONPATH=src python benchmarks/bench_trace.py --quick  # CI gate
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.core import TPU_V5E, TPU_V5P
from repro.sim import Simulator, TraceConfig, generate_trace
from repro.launch.cache import enable_compile_cache

# the fixed gate trace: 36 tenants (half SLO) on 12 devices (36 slots at
# k=3), 240 virtual seconds of diurnal+burst traffic (~2.5k requests),
# dev3 killed mid-trace while a burst window is possible.  The fleet is
# HETEROGENEOUS — alternating v5e/v5p — so every pricing decision, the
# kill recovery, and the determinism twin exercise two device models.
GATE_TRACE = TraceConfig(seed=2026, duration=240.0, n_tenants=36,
                         kills=((120.0, "dev3"),))
GATE_DEVICES = 12
ATTAINMENT_TARGET = 0.95


def hetero_models(n_devices: int) -> dict:
    """Alternating two-model mix: even devices v5e, odd devices v5p."""
    return {f"dev{i}": (TPU_V5E if i % 2 == 0 else TPU_V5P)
            for i in range(n_devices)}


def run_once(cfg: TraceConfig, n_devices: int = GATE_DEVICES) -> dict:
    """One full generate -> simulate -> report pass (fresh RNG, fresh
    clock, fresh fleet — everything derives from cfg.seed)."""
    trace = generate_trace(cfg)
    sim = Simulator(trace, hetero_models(n_devices))
    return sim.run()


def gate(report: dict, twin: dict) -> dict:
    """Evaluate the acceptance gates against the fixed-trace report and
    its same-seed twin."""
    slo_cls = report["slo"]["per_class"].get("slo", {"attainment": 0.0})
    checks = {
        "slo_attainment": slo_cls["attainment"] >= ATTAINMENT_TARGET,
        "no_event_loop_errors": report["fleet"]["event_loop_errors"] == 0,
        "deterministic": report == twin,
        "trace_floor": (report["requests"]["total"] >= 1000
                        and report["trace"]["tenants"] >= 32
                        and report["fleet"]["device_deaths"] >= 1),
        # two genuinely different device models in the gate fleet
        "heterogeneous_fleet": len({m.name for m in
                                    hetero_models(GATE_DEVICES).values()
                                    }) == 2,
    }
    checks["all"] = all(checks.values())
    return checks


def describe(tag: str, report: dict) -> None:
    req, slo, tbt = report["requests"], report["slo"], report["tbt"]
    fleet, good = report["fleet"], report["goodput"]
    print(f"== {tag} ==")
    print(f"  trace: {report['trace']['tenants']} tenants "
          f"({report['trace']['slo_tenants']} SLO-class), "
          f"{req['total']} requests "
          f"({req['completed']} completed, {req['canceled']} canceled, "
          f"{req['unfinished']} unfinished)")
    for cls in sorted(slo["per_class"]):
        a = slo["per_class"][cls]
        t = tbt[cls]
        print(f"  {cls:>11}: attainment {a['attainment']:.3f} "
              f"({a['met']}/{a['resolved']} resolved), "
              f"TBT p50/p99 {t['observed_p50_ms']:.1f}/"
              f"{t['observed_p99_ms']:.1f} ms observed, "
              f"{t['service_p50_ms']:.1f}/{t['service_p99_ms']:.1f} ms "
              f"service")
    print(f"  goodput: {good['slo_met_tokens_per_s']:.0f} SLO-met tok/s "
          f"of {good['tokens_per_s']:.0f} tok/s "
          f"({good['requests_per_s']:.2f} req/s)")
    util = report["devices"]["utilization"]
    print(f"  fleet: {fleet['replans']} replans, "
          f"{fleet['migrations']} migrations, "
          f"{fleet['evictions']} evictions, "
          f"{fleet['device_deaths']} device deaths, "
          f"{fleet['event_loop_errors']} errors; "
          f"mean gain {report['devices']['mean_gain']:.2f}x, "
          f"mean util {sum(util.values()) / max(len(util), 1):.2f}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI gate: fixed trace only; writes "
                         "BENCH_trace.json unless --json overrides it")
    ap.add_argument("--json", type=str, default=None,
                    help="write a machine-readable result summary to this "
                         "path (implied as BENCH_trace.json by --quick)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    report = run_once(GATE_TRACE)
    twin = run_once(GATE_TRACE)      # same seed, fresh everything
    describe("gate trace (diurnal + bursts + kill)", report)
    checks = gate(report, twin)

    variants = {}
    if not args.quick:
        calm = run_once(TraceConfig(seed=7, duration=240.0, n_tenants=36))
        stormy = run_once(TraceConfig(seed=11, duration=240.0, n_tenants=36,
                                      burst_factor=6.0, n_bursts=5,
                                      kills=((100.0, "dev1"),
                                             (160.0, "dev7"))))
        describe("calm variant (no faults)", calm)
        describe("stormy variant (2 kills, 6x bursts)", stormy)
        variants = {"calm": calm, "stormy": stormy}

    print("\n== acceptance ==")
    slo_att = report["slo"]["per_class"].get("slo", {}).get("attainment", 0.0)
    print(f"  SLO-class attainment {slo_att:.3f} >= {ATTAINMENT_TARGET}: "
          f"{'PASS' if checks['slo_attainment'] else 'FAIL'}")
    print(f"  0 event-loop errors: "
          f"{'PASS' if checks['no_event_loop_errors'] else 'FAIL'}")
    print(f"  same seed -> identical report: "
          f"{'PASS' if checks['deterministic'] else 'FAIL'}")
    print(f"  trace floor (>=1k requests, >=32 tenants, >=1 kill): "
          f"{'PASS' if checks['trace_floor'] else 'FAIL'}")

    json_path = args.json or ("BENCH_trace.json" if args.quick else None)
    if json_path:
        payload = {"gate": report, "acceptance": checks, **variants}
        Path(json_path).write_text(json.dumps(payload, indent=2,
                                              sort_keys=True) + "\n")
        print(f"\n  wrote {json_path}")
    return 0 if checks["all"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
