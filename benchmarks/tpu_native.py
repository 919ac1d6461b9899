"""TPU-native benchmarks: the paper's methodology applied to this
framework's own workloads (dry-run-derived profiles on the v5e model),
the Pallas stressor suite, and the serving engine's interference-aware
scheduling.
"""
from __future__ import annotations

import json
import time
from pathlib import Path
from typing import List, Tuple

import numpy as np

from repro.core import (TPU_V5E, ColocationScheduler, WorkloadProfile,
                        sensitivity_batch)
from repro.core.profile import from_dryrun_json

Row = Tuple[str, float, str]
RESULTS = Path(__file__).resolve().parents[1] / "results" / "dryrun"


def stressor_suite(repeats: int = 5) -> List[Row]:
    """Wall-time of the compiled Pallas stressors at full intensity
    (``repro.calib.measure.stressor_kernel``) on the attached TPU, each
    timed ``repeats`` times through the shared ``median_iqr_time`` timer
    (median + IQR).  Without a TPU every row reads "not measured": the
    Pallas interpreter's time says nothing about the kernel."""
    import jax
    from repro.calib.measure import (StressorSpec, median_iqr_time,
                                     stressor_kernel, stressor_operands)

    dev = jax.devices()[0]
    rows = []
    for axis in ("mxu", "vpu", "hbm", "smem"):
        name = f"stress_{axis}"
        if dev.platform != "tpu":
            rows.append((name, float("nan"),
                         f"not measured|no TPU ({dev.platform})"))
            continue
        kernel, _, operands = stressor_kernel(StressorSpec(axis, 1.0))
        fn = jax.jit(kernel)
        args = stressor_operands(operands)
        med_s, iqr_s = median_iqr_time(lambda: fn(*args), repeats=repeats,
                                       warmup=1)
        rows.append((name, med_s * 1e6,
                     f"{dev.device_kind}|median_of={repeats}"
                     f"|iqr_us={iqr_s * 1e6:.1f}"))
    return rows


def phase_sensitivity() -> List[Row]:
    """Sensitivity fingerprint of each arch x shape phase (dry-run) — all
    phases fingerprinted in ONE batched estimator solve."""
    recs, profs = [], []
    for f in sorted(RESULTS.glob("*__pod1.json")):
        rec = json.loads(f.read_text())
        if rec.get("skipped"):
            continue
        recs.append(rec)
        profs.append(from_dryrun_json(rec))
    if not profs:
        return []
    t0 = time.perf_counter()
    reps = sensitivity_batch(profs, TPU_V5E)
    us_each = (time.perf_counter() - t0) * 1e6 / len(profs)
    rows = []
    for rec, rep in zip(recs, reps):
        top = rep.ranked()[:2]
        rows.append((f"sensitivity_{rec['arch']}_{rec['shape']}", us_each,
                     f"dominant={top[0]}:{rep.scores[top[0]]:.2f}x"
                     f"|second={top[1]}:{rep.scores[top[1]]:.2f}x"))
    return rows


def colocation_plan() -> List[Row]:
    """Paper §5.1: plan pairings across this framework's phases."""
    works = []
    for f in sorted(RESULTS.glob("*__pod1.json")):
        rec = json.loads(f.read_text())
        if rec.get("skipped") or rec["shape"] not in ("prefill_32k",
                                                      "decode_32k"):
            continue
        p = from_dryrun_json(rec)
        works.append(WorkloadProfile(p.name, (p,), slo_slowdown=1.3))
    if not works:
        return [("colocation_plan", 0.0, "no-dryrun-artifacts")]
    t0 = time.perf_counter()
    sched = ColocationScheduler(TPU_V5E)
    for w in works[:12]:
        sched.submit(w)
    plan = sched.plan()
    us = (time.perf_counter() - t0) * 1e6
    pairs = "; ".join("+".join(p.workloads) for p in plan.placements[:4])
    return [("colocation_plan_12phases", us,
             f"pairs={len(plan.placements)}|solo={len(plan.solo)}|{pairs}")]


def serve_chunked_vs_serial() -> List[Row]:
    """Engine HOL mitigation (paper §4.2 takeaway): TBT gap of the decode
    batch while a long prompt prefills, serial vs interference-aware."""
    from repro.configs.registry import get_config, tiny_config
    from repro.serve import Engine, EngineConfig

    cfg = tiny_config(get_config("qwen3-1.7b"))
    out = []
    for mode in ("serial", "interference_aware"):
        eng = Engine(cfg, ecfg=EngineConfig(max_slots=4, max_len=640,
                                            prefill_chunk=64, mode=mode),
                     dev=TPU_V5E)
        eng.submit(list(range(1, 17)), max_new=24)       # short: decodes
        eng.run_until_done(max_steps=6)                  # warm decode
        eng.submit(list(range(1, 513)), max_new=4)       # long prompt
        t0 = time.perf_counter()
        eng.run_until_done()
        us = (time.perf_counter() - t0) * 1e6
        decode_ts = [e.t for e in eng.events if e.kind == "decode"]
        gaps = np.diff(decode_ts) * 1e3
        worst = float(np.max(gaps)) if len(gaps) else 0.0
        chunks = [e.detail["chunk"] for e in eng.events
                  if e.kind == "prefill_chunk"]
        out.append((f"serve_hol_{mode}", us,
                    f"worst_decode_gap={worst:.1f}ms|chunks={chunks[:8]}"))
    return out


ALL = [stressor_suite, phase_sensitivity, colocation_plan,
       serve_chunked_vs_serial]
