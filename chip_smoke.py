#!/usr/bin/env python3
"""Drive the main path once on one TPU chip and check what comes out.

  python chip_smoke.py [--seed N]                 # on a host with a TPU
  JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal

One process, one chip, no child process.  The phases run in this order,
and each prints one line with its result and its compile and run seconds:

  device       the attached chip, mapped to its DeviceModel (unknown
               kinds are an error); no TPU is an error
  pricing      4096 seeded scenarios of 2..4 tenant profiles solved by
               the jax solver on the chip, against the NumPy oracle on
               the host: identical bottlenecks and slot feasibility,
               slowdowns within PARITY_BOUND
  fleet        the device-kill replay of ``bench_fleet`` on the jax
               solver: no ``error`` decision, every SLO tenant re-placed
  serving      qwen3-1.7b at published width (random weights from the
               seed) through ``serve.Engine`` in interference-aware
               mode: 8 requests x 32 tokens, two of them checked against
               a teacher-forced ``model.forward``
  calibration  the four Pallas stressors compiled at the sweep's sizes
               against their jnp oracles, then one measured colocation
               of a victim beside the HBM stressor (run after pricing, so
               an x64 leak from the solver would fail it)

Any failure exits non-zero.  The last line of a chip run is
``{"ok": true, "device": {...}}``.  ``--cpu-rehearsal`` runs the same
phases at tiny sizes with interpret-mode kernels and never prints a
result.  The compile cache is ``repro.launch.cache.enable_compile_cache``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "benchmarks"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.calib.measure import (Colocation, PallasBackend,  # noqa: E402
                                 StressorSpec, stressor_kernel,
                                 stressor_operands)
from repro.configs.registry import (ASSIGNED, PAPER_WORKLOADS,  # noqa: E402
                                    get_config, tiny_config)
from repro.core import (TPU_V5E, Scenario, device_model,  # noqa: E402
                        solve_scenarios, solver_backend)
from repro.core.fleet import BEST_EFFORT, SLO  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.serve import Engine, EngineConfig  # noqa: E402
from repro.sim.traces import tenant_profile  # noqa: E402

# the jax solver's contract with the NumPy oracle (tests/test_estimator_jax.py)
PARITY_BOUND = 1e-9
# the bound that holds instead if XLA's emulated f64 on the TPU misses
# PARITY_BOUND; discrete outputs must still be identical
TPU_F64_FALLBACK_BOUND = 1e-6
# a greedy token may differ from the reference only on a bf16 near-tie
NEAR_TIE_GAP = 0.05
# a compiled stressor against its jnp oracle (on a v5e they agree exactly)
STRESSOR_RTOL = 1e-5


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ------------------------------------------------------------------ #
#  Compile seconds, from JAX's own compile events                      #
# ------------------------------------------------------------------ #
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_s = [0.0]


def _on_event(name, secs, **_):
    if name in _COMPILE_EVENTS:
        _compile_s[0] += secs


def run_phase(name: str, fn):
    """Run one phase, print its line, and exit 1 on any failure."""
    c0, t0 = _compile_s[0], time.perf_counter()
    try:
        detail, out = fn()
    except Exception as e:
        print(f"{name}: FAIL {type(e).__name__}: {e}", flush=True)
        traceback.print_exc()
        raise SystemExit(1)
    wall = time.perf_counter() - t0
    comp = _compile_s[0] - c0
    print(f"{name}: ok {detail} | compile_s={comp:.3f} "
          f"run_s={wall - comp:.3f}", flush=True)
    return out


# ------------------------------------------------------------------ #
#  Phases                                                              #
# ------------------------------------------------------------------ #
def phase_device(rehearsal: bool):
    d0 = jax.devices()[0]
    desc = {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(jax.devices())}
    if rehearsal:
        return (f"{desc} rehearsal, pricing for {TPU_V5E.name}",
                (TPU_V5E, desc))
    check(d0.platform == "tpu", f"no TPU: jax runs on {d0.platform}")
    dev = device_model(d0.device_kind)
    return f"{desc} -> {dev.name} (jax {jax.__version__})", (dev, desc)


def pricing_scenarios(rng, dev, n: int, pool_size: int = 64):
    archs = list(ASSIGNED) + list(PAPER_WORKLOADS)
    pool = [tenant_profile(rng, f"t{i}", archs[int(rng.integers(len(archs)))],
                           dev, SLO if rng.random() < 0.5 else BEST_EFFORT)
            .kernels[0] for i in range(pool_size)]
    scens = []
    for s in range(n):
        k = int(rng.integers(2, 5))
        members = tuple(pool[j] for j in rng.choice(pool_size, k,
                                                    replace=False))
        frac = None
        if s % 4 == 0:                   # a quarter share the slots
            f = rng.dirichlet(np.ones(k))
            frac = {m.name: float(x) for m, x in zip(members, f)}
        scens.append(Scenario(members, slot_fraction=frac))
    return scens


def phase_pricing(dev, seed: int, n: int):
    scens = pricing_scenarios(np.random.default_rng(seed), dev, n)
    with solver_backend("numpy"):
        want = solve_scenarios(scens, dev)
    with solver_backend("jax"):
        solve_scenarios(scens, dev)                  # compiles
        t0 = time.perf_counter()
        got = solve_scenarios(scens, dev)
        solve_s = time.perf_counter() - t0
    check(np.array_equal(got.mask, want.mask), "masks differ")
    check(np.array_equal(got.bottleneck, want.bottleneck),
          "bottleneck axes differ")
    check(np.array_equal(got.feasible_slots, want.feasible_slots),
          "slot feasibility differs")
    fin = np.isfinite(want.slowdowns)
    check(np.array_equal(fin, np.isfinite(got.slowdowns)),
          "slowdown finiteness differs")
    fin &= want.mask
    rel = np.abs(got.slowdowns[fin] - want.slowdowns[fin]) / np.abs(
        want.slowdowns[fin])
    err = float(rel.max()) if rel.size else 0.0
    bound = PARITY_BOUND if err <= PARITY_BOUND else TPU_F64_FALLBACK_BOUND
    check(err <= bound, f"max relative slowdown error {err!r} > {bound:g}")
    which = ("PARITY_BOUND" if bound == PARITY_BOUND
             else "TPU_F64_FALLBACK_BOUND (missed PARITY_BOUND 1e-09)")
    return (f"S={n} K=2..4 bottleneck+feasibility identical, "
            f"max_rel_slowdown_err={err!r} <= {bound:g} [{which}], "
            f"warm jax solve {solve_s!r}s"), err


def phase_fleet(dev):
    from bench_fleet import bench_recovery
    with solver_backend("jax"):
        res = bench_recovery(dev)
    check(res["event_loop_errors"] == 0,
          f"{res['event_loop_errors']} fleet error decisions")
    check(res["slo_replacement_rate"] == 1.0,
          f"SLO re-placement {res['slo_replacement_rate']!r}")
    return (f"device-kill replay: errors=0, SLO re-placed "
            f"{res['slo_replacement_rate']:.0%}, evictions "
            f"{res['evictions']}, online==cold {res['online_equals_cold']}, "
            f"decisions {res['decisions']}"), res


def phase_serving(dev, seed: int, rehearsal: bool):
    cfg = get_config("qwen3-1.7b")
    if rehearsal:
        cfg = tiny_config(cfg)
        slots, max_len, lo, hi, max_new = 4, 256, 16, 96, 8
    else:
        slots, max_len, lo, hi, max_new = 8, 2048, 128, 1024, 32
    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, hi + 1, size=8)
    lens[1] = lens[0]                   # the two reference requests
    prompts = [rng.integers(1, cfg.vocab_size, size=int(n)).tolist()
               for n in lens]
    with solver_backend("jax"):
        eng = Engine(cfg, ecfg=EngineConfig(max_slots=slots, max_len=max_len,
                                            mode="interference_aware",
                                            seed=seed), dev=dev)
        decode, step_s = eng._decode, []

        def timed_decode(*args):
            t0 = time.perf_counter()
            out = jax.block_until_ready(decode(*args))
            step_s.append(time.perf_counter() - t0)
            return out

        eng._decode = timed_decode
        ids = [eng.submit(p, max_new=max_new) for p in prompts]
        metrics = eng.run_until_done()
    check(sorted(metrics) == sorted(ids),
          f"{len(metrics)}/{len(ids)} requests finished")
    for i in ids:
        check(metrics[i]["new_tokens"] == max_new,
              f"request {i}: {metrics[i]['new_tokens']} tokens")

    # reference: one teacher-forced forward over prompt + 3 generated
    # tokens; its last 4 argmaxes are the engine's first 4 greedy tokens
    fwd = jax.jit(lambda p, t: eng.model.forward(p, {"tokens": t})[0][0, -4:]
                  .astype(jnp.float32))
    ties = []
    for i in ids[:2]:
        out = metrics[i]["output"]
        toks = jnp.asarray([prompts[i] + out[:3]], jnp.int32)
        logits = np.asarray(fwd(eng.params, toks))
        for j in range(4):
            if int(np.argmax(logits[j])) != out[j]:
                top2 = np.sort(logits[j])[-2:]
                gap = float(top2[1] - top2[0])
                ties.append((i, j, gap))
                check(gap <= NEAR_TIE_GAP,
                      f"request {i} token {j}: engine {out[j]} != reference "
                      f"{int(np.argmax(logits[j]))}, top-2 gap {gap!r}")
    chunks = [e.detail["chunk"] for e in eng.events
              if e.kind == "prefill_chunk"]
    ttft = [round(metrics[i]["ttft_s"], 6) for i in ids]
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", "not reported")
    del eng
    return (f"{cfg.name} L={cfg.n_layers} d={cfg.d_model} "
            f"{len(ids)}x{max_new} tokens, reference ok on 2 requests "
            f"(near-ties {ties}), chunks={chunks}, ttft_s={ttft}, "
            f"median_decode_step_s={float(np.median(step_s))!r} "
            f"over {len(step_s)} steps, peak_bytes_in_use={peak}"), None


def phase_calibration(dev, rehearsal: bool):
    check(not jax.config.jax_enable_x64, "x64 leaked into the process")
    errs = {}
    for axis in ("mxu", "vpu", "hbm", "smem"):
        kernel, ref, operands = stressor_kernel(StressorSpec(axis, 1.0),
                                                interpret=rehearsal)
        args = stressor_operands(operands)
        got = np.asarray(jax.jit(kernel)(*args), np.float32)
        want = np.asarray(jax.jit(ref)(*args), np.float32)
        err = float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1)))
        check(err <= STRESSOR_RTOL,
              f"stress_{axis} off its oracle by {err!r}")
        errs[axis] = err
    shape = (256, 128) if rehearsal else (8192, 4096)
    x = jax.random.normal(jax.random.PRNGKey(3), shape, jnp.float32)
    stream = jax.jit(lambda a: a * 1.0001 + 1.0)   # x passed, not baked in
    be = PallasBackend({"stream": lambda: stream(x)}, dev, repeats=5,
                       interpret=rehearsal)
    slow = float(be.measure([Colocation("stream",
                                        (StressorSpec("hbm", 1.0),))])[0])
    return (f"stressors vs oracle max_rel_err={errs}, "
            f"victim stream{shape} + hbm stressor: slowdown={slow!r} "
            f"(t_iso={be.isolated_time('stream')!r}s)"), slow


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="same phases at tiny sizes with interpret-mode "
                         "kernels; never reports a result")
    args = ap.parse_args(argv)
    rehearsal = args.cpu_rehearsal
    cache_dir = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    print(f"compile cache: {cache_dir}", flush=True)
    t0 = time.perf_counter()

    dev, desc = run_phase("device", lambda: phase_device(rehearsal))
    run_phase("pricing", lambda: phase_pricing(
        dev, args.seed, 256 if rehearsal else 4096))
    run_phase("fleet", lambda: phase_fleet(dev))
    run_phase("serving", lambda: phase_serving(dev, args.seed, rehearsal))
    run_phase("calibration", lambda: phase_calibration(dev, rehearsal))

    total = time.perf_counter() - t0
    print(f"total: compile_s={_compile_s[0]:.3f} "
          f"run_s={total - _compile_s[0]:.3f}", flush=True)
    if rehearsal:
        print("cpu rehearsal: every phase passed (not a chip run)")
        return 0
    print(json.dumps({"ok": True, "device": desc}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
