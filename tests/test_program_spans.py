"""The program's spans as the benchmark reads them from a trace, the split
of the device's idle time by host span (``chipbench/program_spans.py``),
and ``traces.reduce_trace`` pinned on
the small trace recorded on a TPU v5 lite (``chipbench/tests/data``)."""
import importlib.util
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from repro import obs

BENCH = Path(__file__).resolve().parents[1] / "chipbench"
sys.path.insert(0, str(BENCH))

import program_spans  # noqa: E402
import traces  # noqa: E402

DATA = str(BENCH / "tests" / "data" / "tiny_engine.xplane.pb")


def test_gap_is_split_among_the_spans_covering_it():
    host = [(0, 200, "step"), (10, 60, "serve.decode.fetch"),
            (40, 100, "serve.sample")]
    got = program_spans.split_gaps([(0, 100), (150, 160), (250, 300)], host)
    assert got == pytest.approx({
        "step": (10 + 10) / 1e9,                 # [0, 10) and [150, 160)
        "serve.decode.fetch": 50 / 1e9,          # innermost over [10, 60)
        "serve.sample": 40 / 1e9,                # [60, 100)
        traces.NO_SPAN: 50 / 1e9})


def test_split_sums_to_window_less_busy():
    lo, hi = 0, 1000
    busy = traces.union([(100, 180), (170, 300), (640, 700), (900, 950)])
    host = [(0, 1000, "step"), (120, 420, "serve.decode.wait"),
            (420, 500, "serve.decode.fetch"), (500, 650, "serve.sample"),
            (510, 520, "sample"), (700, 800, "serve.admit")]
    got = program_spans.split_gaps(traces.gaps(busy, lo, hi), host)
    idle = (hi - lo) - sum(e - s for s, e in busy)
    assert sum(got.values()) == pytest.approx(idle / 1e9, abs=1e-15)
    assert got["sample"] == pytest.approx(10 / 1e9)
    assert got["serve.sample"] == pytest.approx((640 - 500 - 10) / 1e9)
    assert got["serve.decode.wait"] == pytest.approx((420 - 300) / 1e9)


def test_empty_gaps_and_spans():
    assert program_spans.split_gaps([], [(0, 5, "step")]) == {}
    assert program_spans.split_gaps([(0, 5)], []) == {traces.NO_SPAN: 5e-9}


def test_recorded_trace_reduction_is_pinned():
    r = traces.reduce_trace(DATA, "chipbench.")
    assert r["busy_s"] == pytest.approx(0.00038434, abs=1e-12)
    assert r["window_s"] == pytest.approx(0.582065935, abs=1e-12)
    assert r["programs"] == {"_solve_padded": (4.8677e-05, 1),
                             "extend": (1.4987e-05, 1),
                             "decode": (0.000325642, 6)}


def test_recorded_trace_split_keeps_window_and_busy():
    r = traces.reduce_trace(DATA, "chipbench.")
    (got,) = program_spans.idle_by_span(DATA)
    assert got["window_s"] == r["window_s"]
    assert got["busy_s"] == pytest.approx(r["busy_s"], abs=1e-12)
    assert got["idle_s"] == pytest.approx(r["window_s"] - r["busy_s"],
                                          abs=1e-9)
    names = {n for n, _ in got["idle_by_span"]}
    assert names <= {"step", "pick_chunk", "extend", "decode", "sample",
                     "wait", traces.NO_SPAN}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


READERS = ("logits_copy_ms", "sample_ms_per_step", "step_self_ms",
           "pricing_solve_ms")


def test_readers_find_nothing_without_spans(monkeypatch):
    monkeypatch.setattr(program_spans, "_kept", [])
    run = SimpleNamespace(t0=0.0, t1=1.0)
    for name in READERS:
        assert reader(name)(run) is None, name


def test_readers_on_spans_in_the_window(monkeypatch):
    S = program_spans.Span
    kept = [
        S("serve.step", 0.0, 0.1, None),
        S("serve.price", 0.01, 0.03, "serve.step"),
        S("price.solve", 0.015, 0.025, "serve.price"),
        S("price.fetch", 0.02, 0.025, "price.solve"),
        S("serve.decode.fetch", 0.05, 0.06, "serve.step"),
        S("serve.sample", 0.06, 0.09, "serve.step"),
        S("serve.step", 0.2, 0.3, None),
        S("serve.decode.fetch", 0.21, 0.24, "serve.step"),
        S("serve.sample", 0.24, 0.25, "serve.step"),
        S("price.solve", 0.5, 0.6, None),     # outside any serve.price
    ]
    monkeypatch.setattr(program_spans, "_kept", kept)
    run = SimpleNamespace(t0=0.0, t1=1.0)
    got = {n: reader(n)(run) for n in READERS}
    assert got == pytest.approx({
        "logits_copy_ms": 20.0, "sample_ms_per_step": 20.0,
        # (100 - 20 - 10 - 30) and (100 - 30 - 10) ms
        "step_self_ms": 50.0, "pricing_solve_ms": 10.0})


def test_trace_spans_carry_the_kept_names_and_parents(tmp_path):
    """The spans the readers take from a profiler trace of a tiny engine
    are the ones the recorder keeps: the same names, parents and
    lengths."""
    import jax
    from jax.profiler import ProfileData

    from repro.configs.registry import get_config, tiny_config
    from repro.core import TPU_V5E, solver_backend
    from repro.serve import Engine, EngineConfig
    cfg = tiny_config(get_config("qwen3-1.7b")).with_overrides(
        attn_impl="reference")

    def serve(steps):
        eng = Engine(cfg, ecfg=EngineConfig(max_slots=2, max_len=96,
                                            prefill_chunk=16), dev=TPU_V5E)
        rng = np.random.default_rng(5)
        with solver_backend("jax"):
            for i in range(3):
                eng.submit(rng.integers(1, 50, size=20 + 7 * i).tolist(),
                           max_new=4)
            for _ in range(steps):
                eng.step()

    serve(6)                                     # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    obs.drain()
    obs.enable()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        serve(6)
    finally:
        jax.profiler.stop_trace()
        obs.disable()
    order = lambda s: (s.t0, -s.t1)
    lifecycle = ("serve.queue", "serve.prefill_wait", "serve.prefill")
    kept = sorted((s for s in obs.drain() if s.name not in lifecycle),
                  key=order)
    path = str(next(tmp_path.rglob("*.xplane.pb")))
    got = sorted(program_spans.program_spans(
        ProfileData.from_file(path).planes), key=order)
    assert {"serve.step", "serve.price", "price.solve",
            "serve.decode.fetch"} <= {s.name for s in got}
    assert [(s.name, s.parent) for s in got] == [(s.name, s.parent)
                                                 for s in kept]
    for a, b in zip(got, kept):
        assert abs((a.t1 - a.t0) - (b.t1 - b.t0)) < 50e-6, a.name
