"""The host span recorder (``repro.obs``) and the spans the serving engine
and the pricing solve open: nothing when off, the right nesting and
lifecycle when on, and one clock with the profiler's trace."""
import jax
import numpy as np
import pytest

from repro import obs
from repro.configs.registry import get_config, tiny_config
from repro.core import TPU_V5E, solver_backend
from repro.serve import Engine, EngineConfig

CFG = tiny_config(get_config("qwen3-1.7b")).with_overrides(
    attn_impl="reference")
STEP_CHILDREN = {"serve.admit", "serve.price", "serve.extend",
                 "serve.first_token", "serve.decode.inputs", "serve.decode",
                 "serve.decode.wait", "serve.decode.fetch", "serve.sample"}
LIFECYCLE = ("serve.queue", "serve.prefill_wait", "serve.prefill")


@pytest.fixture(autouse=True)
def recorder_off():
    obs.disable()
    obs.drain()
    yield
    obs.disable()
    obs.drain()


def serve(n=3, steps=None, backend="numpy"):
    """A tiny engine over ``n`` requests; (engine, submitted ids)."""
    eng = Engine(CFG, ecfg=EngineConfig(max_slots=2, max_len=96,
                                        prefill_chunk=16), dev=TPU_V5E)
    rng = np.random.default_rng(3)
    with solver_backend(backend):
        ids = [eng.submit(rng.integers(1, 50, size=20 + 7 * i).tolist(),
                          max_new=4) for i in range(n)]
        if steps is None:
            eng.run_until_done()
        else:
            for _ in range(steps):
                eng.step()
    return eng, ids


def test_off_records_nothing_and_shares_one_noop():
    a, b = obs.span("serve.step"), obs.span("price.solve", rows=3)
    assert a is b
    with a:
        pass
    obs.record("serve.queue", 0.0, 1.0, seq=0)
    serve(n=1)
    assert obs.drain() == []


def test_on_spans_nest_inside_the_step():
    obs.enable()
    eng, _ = serve()
    got = obs.drain()
    steps = [s for s in got if s.name == "serve.step"]
    assert steps and all(s.parent is None for s in steps)
    kids = [s for s in got if s.parent == "serve.step"]
    assert {s.name for s in kids} == STEP_CHILDREN
    for st in steps:
        inner = sorted((s for s in kids if st.t0 <= s.t0 < st.t1),
                       key=lambda s: s.t0)
        assert all(s.t1 <= st.t1 for s in inner)
        assert all(a.t1 <= b.t0 for a, b in zip(inner, inner[1:]))
        own = (st.t1 - st.t0) - sum(s.t1 - s.t0 for s in inner)
        assert own >= 0
        assert own + sum(s.t1 - s.t0 for s in inner) == pytest.approx(
            st.t1 - st.t0, abs=1e-12)
    decodes = sum(e.kind == "decode" for e in eng.events)
    for name in ("serve.decode.wait", "serve.decode.fetch", "serve.sample"):
        assert sum(s.name == name for s in got) == decodes, name
    chunks = sum(e.kind == "prefill_chunk" for e in eng.events)
    assert sum(s.name == "serve.price" for s in got) == chunks


def test_recording_changes_no_token_or_chunk():
    def outputs():
        eng, _ = serve()
        chunks = [e.detail["chunk"] for e in eng.events
                  if e.kind == "prefill_chunk"]
        return {k: v["output"] for k, v in eng.metrics.items()}, chunks

    off = outputs()
    obs.enable()
    assert outputs() == off


def test_lifecycle_spans_are_ordered_per_request():
    obs.enable()
    eng, ids = serve()
    got = [s for s in obs.drain() if s.name in LIFECYCLE]
    for sid in ids:
        mine = {s.name: s for s in got if s.attrs["seq"] == sid}
        assert set(mine) == set(LIFECYCLE)
        q, w, p = (mine[n] for n in LIFECYCLE)
        assert q.t0 <= q.t1 == w.t0 <= w.t1 == p.t0 <= p.t1
        assert p.t1 - q.t0 == pytest.approx(eng.metrics[sid]["ttft_s"])
    # the requests queued behind the two slots waited in the queue
    assert max(s.t1 - s.t0 for s in got if s.name == "serve.queue") > 0


def test_price_solve_nests_under_serve_price():
    obs.enable()
    serve(steps=6, backend="jax")
    got = obs.drain()
    solves = [s for s in got if s.name == "price.solve"]
    assert solves and all(s.parent == "serve.price" for s in solves)
    prices = [s for s in got if s.name == "serve.price"]
    assert all(any(p.t0 <= s.t0 and s.t1 <= p.t1 for p in prices)
               for s in solves)
    fetches = [s for s in got if s.name == "price.fetch"]
    assert len(fetches) == len(solves)
    assert all(f.parent == "price.solve" for f in fetches)


def traced(tmp_path, steps):
    """Serve ``steps`` engine steps under a profiler trace that opens with
    the ``serve.clock`` marker; the trace's file."""
    serve(steps=4)                               # compile outside the trace
    obs.drain()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with obs.span("serve.clock"):
            pass
        serve(steps=steps)
    finally:
        jax.profiler.stop_trace()
    return str(next(tmp_path.rglob("*.xplane.pb")))


def trace_events(path):
    from jax.profiler import ProfileData
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(("serve.", "price.")):
                        events.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    return events


def test_recorder_follows_the_profiler_onto_its_clock(tmp_path):
    """Each kept span lies on the trace's host plane under its name: one
    offset, from the ``serve.clock`` marker, maps every kept span to its
    trace event within 50 us."""
    obs.enable()
    events = trace_events(traced(tmp_path, steps=8))
    kept = [s for s in obs.drain() if s.name not in LIFECYCLE]
    assert {"serve.step", "serve.decode.fetch"} <= {s.name for s in kept}
    clock = next(s for s in kept if s.name == "serve.clock")
    offset = events["serve.clock"][0][0] / 1e9 - clock.t0
    for name in {s.name for s in kept}:
        mine = sorted((s.t0, s.t1) for s in kept if s.name == name)
        theirs = sorted(events[name])
        assert len(mine) == len(theirs), name
        for (t0, t1), (s_ns, e_ns) in zip(mine, theirs):
            assert abs(s_ns / 1e9 - offset - t0) < 50e-6, name
            assert abs(e_ns / 1e9 - offset - t1) < 50e-6, name


def test_profiler_alone_traces_spans_and_keeps_none(tmp_path):
    """Without ``enable`` a trace still carries the spans, but nothing is
    kept in memory, and once the trace stops ``span`` is the no-op."""
    events = trace_events(traced(tmp_path, steps=8))
    assert {"serve.clock", "serve.step", "serve.decode.fetch"} <= set(events)
    assert obs.drain() == []
    assert obs.span("serve.step") is obs.span("serve.admit")


@pytest.mark.parametrize("temperature", [0.0, 0.9],
                         ids=["device_pick", "host_pick"])
def test_fetch_and_sample_spans_at_either_pick(temperature):
    """Each decode step opens one ``serve.decode.fetch`` (the pick and
    what it copies to the host) and one ``serve.sample`` (the host loop
    over the decoding slots), whichever side picks."""
    obs.enable()
    eng = Engine(CFG, ecfg=EngineConfig(max_slots=2, max_len=96,
                                        prefill_chunk=16,
                                        temperature=temperature),
                 dev=TPU_V5E)
    rng = np.random.default_rng(4)
    for n in (9, 21):
        eng.submit(rng.integers(1, 50, size=n).tolist(), max_new=5)
    eng.run_until_done()
    got = obs.drain()
    decodes = sum(e.kind == "decode" for e in eng.events)
    assert decodes
    for name in ("serve.decode.fetch", "serve.sample"):
        mine = [s for s in got if s.name == name]
        assert len(mine) == decodes, name
        assert all(s.parent == "serve.step" for s in mine), name
