#!/usr/bin/env python3
"""Record the small trace that ``test_traces.py`` reads, on a chip.

  python chipbench/tests/record_trace.py [--out chipbench/tests/data]

Runs the ``qwen3-1.7b`` configuration at its rehearsal sizes through
``repro.serve.Engine`` with the harness's spans for a few dozen steps,
traces them, copies the ``.xplane.pb`` file to ``--out`` and prints what
``traces.reduce_trace`` reads from it, with a summary of its planes.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from collections import defaultdict
from pathlib import Path
from types import SimpleNamespace

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(HERE / "tests" / "data"))
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import ProfileData
    from repro.core import TPU_V5E, solver_backend
    from repro.serve import Engine, EngineConfig
    from traces import find, reduce_trace

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    cfg = json.loads((HERE / "configs" / "qwen3-1.7b.json").read_text())
    cfg = {**cfg, **cfg["rehearsal"]["hf"]}
    ref = run.load_module(HERE / "configs" / cfg["reference"])
    prog = run.load_module(HERE / "configs" / cfg["program"])
    w = jax.jit(lambda k: ref.make_weights(cfg, k))(
        jnp.asarray([0, 1], jnp.uint32))
    rec = SimpleNamespace(spans=defaultdict(list), calls=[])
    with solver_backend("jax"):
        eng = Engine(prog.model_config(cfg), params=prog.program_params(w),
                     ecfg=EngineConfig(max_slots=4, max_len=128),
                     dev=TPU_V5E)
        run.instrument(eng, jax, rec)
        rng = np.random.default_rng(0)
        for n in (40, 24, 33):
            eng.submit(rng.integers(1, cfg["vocab_size"], n).tolist(), 12)
        for _ in range(8):                    # compile outside the trace
            eng.step()
        tdir = run.STATE / "record_trace"
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(str(tdir),
                                 profiler_options=run.profile_options(jax))
        eng.submit(rng.integers(1, cfg["vocab_size"], 37).tolist(), 6)
        for _ in range(12):
            with jax.profiler.TraceAnnotation(run.SPAN + "step"):
                eng.step()
            time.sleep(0.002)
        jax.profiler.stop_trace()
    path = find(str(tdir))
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    dst = out / "tiny_engine.xplane.pb"
    shutil.copy(path, dst)
    pd = ProfileData.from_file(str(dst))
    for pl in pd.planes:
        lines = {ln.name: len(list(ln.events)) for ln in pl.lines}
        print("plane", pl.name, lines)
        for ln in pl.lines:
            if ln.name in ("XLA Modules", "Steps"):
                print("   ", ln.name, sorted({e.name for e in ln.events})[:8])
    print(json.dumps(reduce_trace(str(dst), run.SPAN)))
    print("bytes", dst.stat().st_size)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
