"""Fleet recovery gate: deterministic fault injection over FleetScheduler.

Replays fixed traces (virtual clock, no sleeps — bit-identical every
run) against a ``FleetScheduler`` and gates the robustness claims:

  1. recovery: after an injected device kill, 100% of SLO workloads are
     re-placed on the survivors; every displaced best-effort workload
     has an explicit "evicted" decision; the fleet never raises out of
     the event loop (stats["errors"] == 0); and the post-recovery online
     fleet plan equals a cold ``FleetScheduler`` plan over the surviving
     devices/workloads at 1e-9 (placements, slowdowns, fractions, gain);
  2. admission: an arrival storm against a bounded queue rejects the
     overflow with explicit decision records and the tracked pool stays
     bounded — no silent unbounded growth;
  3. straggler: a slow device degrades via the EWMA monitor; SLO work
     migrates off it while best-effort may remain.
  4. scale (scoped repair): a 256-device heterogeneous fleet (alternating
     v5e/v5p) under ~64 churn mutations — arrivals, departures, planned
     drains, revives — must repair INCREMENTALLY: p95 devices touched
     per scoped repair <= 16, mean replan latency >= 10x faster than a
     forced full-replay twin, total packed gain within the configured
     divergence epsilon of a cold replay, the placed-SLO set identical
     to the cold replay, and zero event-loop errors.

`--quick` (the CI smoke) runs the same traces — they are already small,
and the scale gate is sized to stay inside the CI budget — and writes
BENCH_fleet.json (recovery latency, evictions, SLO re-placement rate,
online==cold, the scale gate) as a CI artifact next to
BENCH_planner.json.

  PYTHONPATH=src python benchmarks/bench_fleet.py          # full gates
  PYTHONPATH=src python benchmarks/bench_fleet.py --quick  # CI smoke
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np

from bench_planner import decode_heavy_mix
from repro.core import (TPU_V5E, TPU_V5P, BEST_EFFORT, SLO, FleetConfig,
                        FleetScheduler, KernelProfile, WorkloadProfile)
from repro.core.resources import RESOURCE_AXES
from repro.ft.inject import FakeClock, FaultInjector, arrive, kill, slow, storm
from repro.launch.cache import enable_compile_cache

TOL = 1e-9


def fleet_plans_equal(got, want, tol=TOL):
    """FleetPlan equality at tol: same placements (members in order),
    slot fractions, predicted slowdowns, and gains; same UNPLACED set
    (queued + degraded pooled — the queued/degraded split is retry
    history, which a cold fleet by definition does not have)."""
    if set(got.placements) != set(want.placements):
        return False
    for did, a in got.placements.items():
        b = want.placements[did]
        if a.workloads != b.workloads or set(a.slot_fraction) != set(b.slot_fraction):
            return False
        if any(abs(a.slot_fraction[n] - b.slot_fraction[n]) > tol
               for n in a.slot_fraction):
            return False
        if any(abs(a.predicted_slowdown[n] - b.predicted_slowdown[n]) > tol
               for n in a.workloads):
            return False
        if abs(a.throughput_gain - b.throughput_gain) > tol:
            return False
    return (sorted(got.queued + got.degraded)
            == sorted(want.queued + want.degraded))


def cold_fleet(online, dev_models, config):
    """Cold FleetScheduler over the given devices, fed the online
    fleet's tracked pool in arrival order (the recovery-gate contract)."""
    fleet = FleetScheduler(dev_models, config)
    for prof, prio in online.workloads:
        fleet.submit(prof, priority=prio)
    return fleet


# ------------------------------------------------------------------ #
def bench_recovery(dev):
    """The fixed device-kill trace: 4 devices, 4 SLO decodes + 6
    best-effort auxes (10 workloads, 12 slots), kill dev1 at t=8 —
    9 surviving slots force best-effort evictions while every SLO
    workload must re-place."""
    cfg = FleetConfig(max_group_size=3, heartbeat_timeout=3.0,
                      backoff_base=1.0, max_retries=3)
    works = decode_heavy_mix(dev, n_decode=4, n_aux=6)
    decodes, auxes = works[:4], works[4:]
    clock = FakeClock()
    models = {f"dev{i}": dev for i in range(4)}
    fleet = FleetScheduler(models, cfg, clock=clock)
    kill_t = 8.0
    trace = ([arrive(float(i), d, priority=SLO)
              for i, d in enumerate(decodes)]
             + storm(4.0, auxes, priority=BEST_EFFORT)
             + [kill(kill_t, "dev1")])
    FaultInjector(fleet, clock).run(trace, until=30.0)

    plan = fleet.plan()
    slo_names = [w.name for w in decodes]
    slo_rate = plan.placement_rate(slo_names)
    pre_kill_placed = {d.workload for d in fleet.decisions
                      if d.time <= kill_t and d.action == "placed"}
    evicted = [d for d in fleet.decisions if d.action == "evicted"]
    placed_now = plan.placed
    # every best-effort workload that lost its pre-kill placement for
    # good must have an explicit eviction record
    displaced_be = [w.name for w in auxes
                    if w.name in pre_kill_placed and w.name not in placed_now]
    evicted_names = {d.workload for d in evicted}
    evictions_recorded = all(n in evicted_names for n in displaced_be)

    dead_t = next(d.time for d in fleet.decisions
                  if d.action == "device-dead")
    slo_recovered_t = max(
        (d.time for d in fleet.decisions
         if d.time >= dead_t and d.workload in slo_names
         and d.action in ("placed", "migrated")), default=dead_t)
    recovery_latency = slo_recovered_t - kill_t

    survivors = {did: m for did, m in models.items() if did != "dev1"}
    cold = cold_fleet(fleet, survivors, cfg)
    online_eq_cold = fleet_plans_equal(plan, cold.plan())

    res = {
        "slo_replacement_rate": slo_rate,
        "evictions": len(evicted),
        "evictions_recorded": bool(evictions_recorded),
        "recovery_latency_s": recovery_latency,
        "event_loop_errors": fleet.stats["errors"],
        "online_equals_cold": bool(online_eq_cold),
        "migrations": fleet.stats["migrated"],
        "replans": fleet.stats["replans"],
        "scenarios_solved": fleet.stats["scenarios_solved"],
        "decisions": len(fleet.decisions),
    }
    res["pass"] = bool(slo_rate == 1.0 and evictions_recorded
                       and len(evicted) >= 1
                       and fleet.stats["errors"] == 0 and online_eq_cold)
    return res


def bench_admission(dev):
    """Arrival storm vs a bounded queue: one device, queue_limit=2, a
    storm of 8 best-effort workloads on one tick — the overflow must be
    rejected with decision records and the tracked pool stays bounded.
    Also gates storm *batching*: the whole same-tick storm must be
    admitted through ONE deduplicated replay (replans-per-storm == 1,
    not one per arrival)."""
    cfg = FleetConfig(max_group_size=2, queue_limit=2,
                      heartbeat_timeout=3.0)
    works = decode_heavy_mix(dev, n_decode=2, n_aux=8)
    decodes, auxes = works[:2], works[2:]
    clock = FakeClock()
    fleet = FleetScheduler({"dev0": dev}, cfg, clock=clock)
    trace = ([arrive(0.0, d, priority=SLO) for d in decodes]
             + storm(1.0, auxes, priority=BEST_EFFORT))
    replans_at = {}
    def snap(f, now):
        replans_at[now] = f.stats["replans"]
    FaultInjector(fleet, clock, on_tick=snap).run(trace, until=5.0)
    storm_replans = replans_at[1.0] - replans_at[0.0]
    rejected = [d for d in fleet.decisions if d.action == "rejected"]
    tracked = len(fleet)
    bound = 2 * cfg.max_group_size + 2 * cfg.queue_limit  # placed + queues
    res = {
        "storm_size": len(auxes),
        "rejected": len(rejected),
        "tracked_after_storm": tracked,
        "tracked_bound": bound,
        "storm_replans": storm_replans,
        "event_loop_errors": fleet.stats["errors"],
    }
    res["pass"] = bool(len(rejected) >= 1 and tracked <= bound
                       and storm_replans == 1
                       and fleet.stats["errors"] == 0)
    return res


def bench_straggler(dev):
    """A slow device degrades via the EWMA monitor: SLO work must leave
    it; best-effort may stay (degraded devices still take best-effort)."""
    cfg = FleetConfig(max_group_size=3, heartbeat_timeout=3.0)
    works = decode_heavy_mix(dev, n_decode=2, n_aux=2)
    decodes, auxes = works[:2], works[2:]
    clock = FakeClock()
    fleet = FleetScheduler({"dev0": dev, "dev1": dev}, cfg, clock=clock)
    trace = ([arrive(float(i), d, priority=SLO)
              for i, d in enumerate(decodes)]
             + [arrive(2.0, a, priority=BEST_EFFORT) for a in auxes]
             + [slow(4.0, "dev1")])
    FaultInjector(fleet, clock).run(trace, until=10.0)
    plan = fleet.plan()
    slo_on_degraded = [n for n in (w.name for w in decodes)
                       if plan.placed.get(n) == "dev1"]
    res = {
        "device_states": plan.device_states,
        "slo_replacement_rate": plan.placement_rate(
            [w.name for w in decodes]),
        "slo_on_degraded_device": slo_on_degraded,
        "event_loop_errors": fleet.stats["errors"],
    }
    res["pass"] = bool(plan.device_states["dev1"] == "degraded"
                       and not slo_on_degraded
                       and res["slo_replacement_rate"] == 1.0
                       and fleet.stats["errors"] == 0)
    return res


# ------------------------------------------------------------------ #
#  Scale gate: scoped repair on a 256-device heterogeneous fleet      #
# ------------------------------------------------------------------ #
SCALE_DEVICES = 256
SCALE_INIT = 192        # initial tenants (submitted in waves)
SCALE_WAVE = 16
SCALE_CHURN = 64        # churn mutations after the initial load
SCALE_TOUCHED_P95 = 16.0
SCALE_SPEEDUP = 10.0
SCALE_FULL_MUTATIONS = 3   # mutations timed on the forced-full twin


def loose_mix(n, prefix="s"):
    """n loose-SLO (1.5x) workloads, alternating compute- and
    bandwidth-leaning so triples contend mildly on one axis but always
    meet their SLO at full share — the scale gate measures repair
    *width*, not partition-search depth.  Demands are absolute (sized
    off v5e capacities), so the same workload leaves genuinely more
    headroom on a v5p — the heterogeneous greedy sees different prices
    per model."""
    out = []
    for i in range(n):
        if i % 2 == 0:
            u = {"mxu": 0.40, "vpu": 0.05, "issue": 0.06,
                 "hbm": 0.18, "l2": 0.18}
        else:
            u = {"mxu": 0.12, "vpu": 0.04, "issue": 0.05,
                 "hbm": 0.38, "l2": 0.38}
        d = {r: u.get(r, 0.0) * TPU_V5E.capacity(r) for r in RESOURCE_AXES}
        name = f"{prefix}{i}"
        out.append(WorkloadProfile(
            name, (KernelProfile(f"{name}#step", demand=d, duration=1.0),),
            slo_slowdown=1.5))
    return out


def scale_models(n=SCALE_DEVICES):
    """The heterogeneous mix: even devices v5e, odd devices v5p."""
    return {f"dev{i:03d}": (TPU_V5E if i % 2 == 0 else TPU_V5P)
            for i in range(n)}


def _scale_churn(fleet, clock, init, churn):
    """Apply the fixed churn script: per 8-mutation block, 3 arrivals,
    3 departures, one planned drain (decommission) and one revive of
    the oldest drained device — every kind routes its own RepairScope."""
    prios = [SLO, BEST_EFFORT]
    drained = []
    ci = si = 0
    for m in range(SCALE_CHURN):
        step = m % 8
        if step in (0, 2, 4):
            fleet.submit(churn[ci], priority=prios[ci % 2])
            ci += 1
        elif step in (1, 3, 5):
            name = init[si].name
            si += 1
            if name in fleet:
                fleet.remove(name)
        elif step == 6:
            fleet.decommission(f"dev{(m * 5) % SCALE_DEVICES:03d}")
            drained.append(f"dev{(m * 5) % SCALE_DEVICES:03d}")
        else:
            fleet.heartbeat(drained.pop(0))
        clock.advance(1.0)


def bench_scale():
    """256-device churn under scoped repair, gated four ways: repair
    locality (touched p95), replan speedup vs a forced-full twin, the
    bounded-divergence contract vs a cold replay, and exact SLO-set
    agreement with that cold replay."""
    cfg = FleetConfig(max_group_size=3, queue_limit=64,
                      heartbeat_timeout=1e9)
    models = scale_models()
    clock = FakeClock()
    fleet = FleetScheduler(models, cfg, clock=clock)
    init = loose_mix(SCALE_INIT, prefix="s")
    prios = [SLO if i % 2 == 0 else BEST_EFFORT for i in range(SCALE_INIT)]
    for w0 in range(0, SCALE_INIT, SCALE_WAVE):
        fleet.submit_many(list(zip(init[w0:w0 + SCALE_WAVE],
                                   prios[w0:w0 + SCALE_WAVE])))
        clock.advance(1.0)
    n_init = len(fleet.repairs)

    churn = loose_mix(SCALE_CHURN, prefix="c")
    _scale_churn(fleet, clock, init, churn)

    churn_recs = fleet.repairs[n_init:]
    scoped = [r for r in churn_recs if not r.full]
    touched_p95 = float(np.percentile(
        [r.devices_touched for r in scoped], 95)) if scoped else float("inf")
    scoped_lat = float(np.mean([r.latency_s for r in churn_recs]))

    plan = fleet.plan()
    slo_names = [p.name for p, prio in fleet.workloads if prio == SLO]
    slo_rate = plan.placement_rate(slo_names)

    # bounded-divergence contract vs a cold full replay over the same
    # pool and surviving devices (one batched storm = ONE cold replay)
    full_cfg = FleetConfig(max_group_size=3, queue_limit=64,
                           heartbeat_timeout=1e9, repair_mode="full")
    survivors = {did: d.model for did, d in fleet.devices.items()
                 if d.state != "dead"}
    cold = FleetScheduler(survivors, full_cfg)
    cold.submit_many([(p, prio) for p, prio in fleet.workloads])
    cold_plan = cold.plan()
    gain_ratio = (plan.total_gain / cold_plan.total_gain
                  if cold_plan.total_gain > 0 else 1.0)
    slo_sets_match = ({n for n in slo_names if n in plan.placed}
                      == {n for n in slo_names if n in cold_plan.placed})

    # forced-full twin: same fleet and load, repair_mode="full" — time a
    # handful of the same mutation kinds through the cold-replay path
    twin = FleetScheduler(scale_models(), full_cfg, clock=FakeClock())
    twin.submit_many(list(zip(init, prios)))
    n_twin = len(twin.repairs)
    twin.submit(loose_mix(1, prefix="t")[0], priority=BEST_EFFORT)
    twin.remove(init[0].name)
    twin.decommission("dev030")
    full_lat = float(np.mean(
        [r.latency_s for r in twin.repairs[n_twin:][:SCALE_FULL_MUTATIONS]]))
    speedup = full_lat / max(scoped_lat, 1e-12)

    res = {
        "devices": SCALE_DEVICES,
        "device_models": sorted({m.name for m in models.values()}),
        "workloads_final": len(fleet),
        "churn_mutations": SCALE_CHURN,
        "churn_repairs": len(churn_recs),
        "scoped_repairs": fleet.stats["scoped_repairs"],
        "full_replays": fleet.stats["full_replays"],
        "repair_fallbacks": fleet.stats["repair_fallbacks"],
        "touched_p95": touched_p95,
        "scoped_mean_latency_s": scoped_lat,
        "full_mean_latency_s": full_lat,
        "replan_speedup": speedup,
        "gain_ratio_vs_cold": gain_ratio,
        "divergence_epsilon": cfg.divergence_epsilon,
        "slo_replacement_rate": slo_rate,
        "slo_sets_match": bool(slo_sets_match),
        "event_loop_errors": fleet.stats["errors"],
    }
    res["pass"] = bool(
        touched_p95 <= SCALE_TOUCHED_P95
        and speedup >= SCALE_SPEEDUP
        and gain_ratio >= 1.0 - cfg.divergence_epsilon
        and slo_rate == 1.0 and slo_sets_match
        and fleet.stats["errors"] == 0)
    return res


# ------------------------------------------------------------------ #
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: same deterministic traces; writes "
                         "BENCH_fleet.json unless --json overrides it")
    ap.add_argument("--json", type=str, default=None,
                    help="write a machine-readable result summary to this "
                         "path (implied as BENCH_fleet.json by --quick)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    dev = TPU_V5E

    print("== recovery (device kill) ==")
    recovery = bench_recovery(dev)
    print(f"  SLO re-placement rate: {recovery['slo_replacement_rate']:.0%}")
    print(f"  evictions: {recovery['evictions']} "
          f"(all recorded: {recovery['evictions_recorded']})")
    print(f"  recovery latency: {recovery['recovery_latency_s']:.1f}s "
          f"virtual (kill -> all SLO re-placed)")
    print(f"  online == cold over survivors @1e-9: "
          f"{recovery['online_equals_cold']}")
    print(f"  event-loop errors: {recovery['event_loop_errors']}")

    print("== admission (arrival storm) ==")
    admission = bench_admission(dev)
    print(f"  storm of {admission['storm_size']}: "
          f"{admission['rejected']} rejected with records, "
          f"{admission['tracked_after_storm']} tracked "
          f"(bound {admission['tracked_bound']})")
    print(f"  replans for the storm: {admission['storm_replans']} "
          f"(batched admission; was one per arrival)")

    print("== straggler (slow device) ==")
    straggler = bench_straggler(dev)
    print(f"  device states: {straggler['device_states']}")
    print(f"  SLO on degraded device: "
          f"{straggler['slo_on_degraded_device'] or 'none'}")

    print("== scale (scoped repair, 256 heterogeneous devices) ==")
    scale = bench_scale()
    print(f"  fleet: {scale['devices']} devices "
          f"({'/'.join(scale['device_models'])}), "
          f"{scale['workloads_final']} tenants after "
          f"{scale['churn_mutations']} churn mutations")
    print(f"  repairs: {scale['scoped_repairs']} scoped, "
          f"{scale['full_replays']} full "
          f"({scale['repair_fallbacks']} fallbacks); "
          f"touched p95 {scale['touched_p95']:.0f} devices "
          f"(gate <= {SCALE_TOUCHED_P95:.0f})")
    print(f"  replan latency: {scale['scoped_mean_latency_s'] * 1e3:.1f} ms "
          f"scoped vs {scale['full_mean_latency_s'] * 1e3:.1f} ms full "
          f"-> {scale['replan_speedup']:.0f}x "
          f"(gate >= {SCALE_SPEEDUP:.0f}x)")
    print(f"  divergence: gain ratio vs cold "
          f"{scale['gain_ratio_vs_cold']:.4f} "
          f"(gate >= {1.0 - scale['divergence_epsilon']:.2f}); "
          f"SLO sets match: {scale['slo_sets_match']}")
    print(f"  SLO placement rate: {scale['slo_replacement_rate']:.0%}; "
          f"event-loop errors: {scale['event_loop_errors']}")

    print("\n== acceptance ==")
    for name, r in (("recovery", recovery), ("admission", admission),
                    ("straggler", straggler), ("scale", scale)):
        print(f"  {name}: {'PASS' if r['pass'] else 'FAIL'}")
    ok = (recovery["pass"] and admission["pass"] and straggler["pass"]
          and scale["pass"])

    json_path = args.json or ("BENCH_fleet.json" if args.quick else None)
    if json_path:
        payload = {
            "recovery": recovery,
            "admission": admission,
            "straggler": straggler,
            "scale": scale,
            "acceptance": {"recovery": recovery["pass"],
                           "admission": admission["pass"],
                           "straggler": straggler["pass"],
                           "scale": scale["pass"],
                           "all": ok},
        }
        Path(json_path).write_text(json.dumps(payload, indent=2) + "\n")
        print(f"\n  wrote {json_path}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
