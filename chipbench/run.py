#!/usr/bin/env python3
"""Run one benchmark cell once on the chip it is started on.

  python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
  python chipbench/run.py --workload <cell> --rehearsal     # CPU, tiny sizes

A cell of ``BENCHMARK.json`` names a configuration (``configs/<name>.json``
with its plain reference and the program's adapter beside it) and a
traffic mix (``mixes/<traffic>.json``).  Every metric is a reader of its
own, ``metrics/<metric>.py``, found by name.  The run:

1. makes the weights on the device from the seed in one jitted program
   and builds ``repro.serve.Engine`` on them, pricing on the chip;
2. compiles (or loads from the compile cache inside the checkout) every
   shape the cell's traffic can make the engine use;
3. replays the lead-in of the schedule (``schedule.py``), then opens the
   window: for ``--seconds`` the requests are submitted when due and the
   engine is stepped; afterwards it drains until every request due in the
   window has its first token;
4. compares a sample of the finished requests, drawn from the seed, with
   the plain reference (the gaps by which the served tokens' logits lie
   below the reference's best; the configuration names the statistic
   compared and its limit);
5. prints the set-up breakdown, the generator's lateness and the
   window's population, the numbers compared beside their limits on
   standard error, and one JSON line.

``--control`` puts the control (the reference in float8) in the
program's place at step 4: its first-ranked tokens are judged as the
served tokens are, and the run must read ``correct`` false.

With ``--trace 1`` the last ``TRACE_S`` seconds of the window are traced
and the line carries the per-layer metrics; with ``--trace 0`` the
end-to-end ones.  With no TPU, or fewer chips than the cell asks for, it
exits 2 and prints no result.  ``--rehearsal`` runs the same path on the
CPU at the configuration's tiny sizes and never reports ``correct``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
STATE = ROOT / ".chipbench"            # compile cache and traces, ignored by git
TRACE_S = 6.0                          # traced part of the window, seconds
SPAN = "chipbench."                    # prefix of the harness's host spans
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(HERE))


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem.replace("-", "_")
                                                  .replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, rehearsal: bool):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    centry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    cfg = json.loads((ROOT / centry["file"]).read_text())
    mix = json.loads((HERE / "mixes" / f"{wl['traffic']}.json").read_text())
    if rehearsal:
        r = cfg["rehearsal"]
        cfg = {**cfg, **r["hf"], "engine": {**cfg["engine"], **r["engine"]},
               "capacity_factor": r.get("capacity_factor",
                                        cfg.get("capacity_factor"))}
        # lengths shrink by length_scale, and so do the requests' lives
        mix = {**mix, "lead_in_s": mix["lead_in_s"] * r["length_scale"]}
    return bench, wl, cfg, mix


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# --------------------------------------------------------------------- #
#  Compile events                                                        #
# --------------------------------------------------------------------- #
class CompileLog:
    """Times of JAX's lowering events (one per new program in this
    process, whether it then compiles or loads from the cache)."""
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.lowered, self.compile_s, self.cache_hits = [], 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **_):
        if name == self.LOWER:
            self.lowered.append(time.perf_counter())
        elif name == self.COMPILE:
            self.compile_s += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def between(self, t0, t1):
        return sum(t0 <= t < t1 for t in self.lowered)


# --------------------------------------------------------------------- #
#  Spans around the engine's calls into each layer                       #
# --------------------------------------------------------------------- #
def instrument(eng, jax, rec):
    """Wrap the engine's per-layer calls on this instance: host spans
    (also written into the profiler's trace) and the shapes of each
    device call, for the counts."""
    def wrap(attr, name, note=None):
        fn = getattr(eng, attr)

        def wrapped(*a, **k):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(SPAN + name):
                out = fn(*a, **k)
            t1 = time.perf_counter()
            rec.spans[name].append((t0, t1))
            if note:
                rec.calls.append((name, t0, note(*a)))
            return out
        setattr(eng, attr, wrapped)

    wrap("_pick_chunk", "pick_chunk")
    wrap("_extend", "extend", lambda p, tok, c, slot, pos0: (tok.shape[1],
                                                            int(pos0)))
    wrap("_decode", "decode", lambda p, tok, c, pos: pos)  # read after
    wrap("_sample", "sample")


# --------------------------------------------------------------------- #
#  Set-up                                                                #
# --------------------------------------------------------------------- #
def warm_up(eng, jax, jnp, np, max_prompt, workers, setup):
    """Compile every shape the cell's traffic can make the engine use.
    The engine runs one ``extend`` program per chunk length and one
    ``decode`` program; the longest chunk is what ``_pick_chunk`` takes
    for the longest prompt, and any length up to it can occur (a final
    remainder, or a chunk cut when the last decode finishes)."""
    from repro.serve.kvcache import Sequence
    t = time.perf_counter()
    slots = eng.ecfg.max_slots
    # the longest chunk, over every number of running decodes
    longest = max(min(eng._pick_chunk(Sequence(-1, max_prompt, 1), n),
                      max_prompt) for n in range(slots))
    for r in (1, 17, 33, 65, 129):     # each count of priced candidates
        eng._pick_chunk(Sequence(-1, min(r, max_prompt), 1), 1)
    setup["warm_pricing_s"] = time.perf_counter() - t

    t = time.perf_counter()
    p, cache = eng.params, eng.cache

    def compile_extend(c):
        eng._extend.lower(p, jax.ShapeDtypeStruct((1, c), jnp.int32), cache,
                          0, 0).compile()

    def compile_decode():
        eng._decode.lower(p, jnp.asarray(np.zeros((slots, 1), np.int32)),
                          cache, jnp.asarray(np.zeros((slots,), np.int32))
                          ).compile()

    with ThreadPoolExecutor(max_workers=workers) as ex:
        futs = [ex.submit(compile_decode)]
        futs += [ex.submit(compile_extend, c) for c in range(1, longest + 1)]
        for f in futs:
            f.result()
    setup["warm_compile_s"] = time.perf_counter() - t
    setup["extend_shapes"] = longest

    # one real call of each program on the engine's own cache
    t = time.perf_counter()
    trash = np.full((slots,), eng.ecfg.max_len, np.int32)
    logits, eng.cache = eng._extend(p, jnp.zeros((1, 16 if longest >= 16
                                                  else longest), jnp.int32),
                                    eng.cache, 0, 0)
    np.asarray(logits)
    logits, eng.cache = eng._decode(p, jnp.asarray(np.zeros((slots, 1),
                                                            np.int32)),
                                    eng.cache, jnp.asarray(trash))
    np.asarray(logits)
    setup["warm_run_s"] = time.perf_counter() - t


# --------------------------------------------------------------------- #
#  The open loop                                                         #
# --------------------------------------------------------------------- #
def profile_options(jax):
    """Device and host activity with the harness's spans; no tracing of
    every Python call, which would slow the host loop it measures."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def drive(eng, jax, reqs, base, t_win1, drain_limit, rec, trace_dir):
    """Submit each request when due (``base + due``), step the engine,
    stamp every token when the step that made it returns.  Stops at
    ``t_win1`` once every request due in the window has its first token,
    or at ``t_win1 + drain_limit``.  With ``trace_dir``, the last
    ``rec.trace_s`` seconds of the window are traced."""
    now = time.perf_counter
    live = {}                       # seq_id -> (Sequence, request record)
    nxt, n = 0, len(reqs)
    tracing = None
    loop_start = now()
    while True:
        t = now()
        while nxt < n and base + reqs[nxt].due <= t:
            q = reqs[nxt]
            sid = eng.submit(q.prompt.tolist(), max_new=q.max_new)
            r = rec.requests[q.idx]
            r.update(seq=sid, submit=now())
            live[sid] = (eng.waiting[-1], r)
            nxt += 1
        if trace_dir and tracing is None and t >= t_win1 - rec.trace_s:
            jax.profiler.start_trace(str(trace_dir),
                                     profiler_options=profile_options(jax))
            tracing = now()
            rec.trace_host = [tracing, None]
        if tracing and rec.trace_host[1] is None and t >= t_win1:
            jax.profiler.stop_trace()       # slow: it writes the trace out
            rec.trace_host[1] = now()
        if t >= t_win1:
            pending = [r for r in rec.requests
                       if r["in_window"] and r["first"] is None]
            if not pending or t >= t_win1 + drain_limit:
                break
        s0 = now()
        with jax.profiler.TraceAnnotation(SPAN + "step"):
            busy = eng.step()
        s1 = now()
        if not busy:                # nothing admitted: wait for the next due
            wake = base + reqs[nxt].due if nxt < n else t_win1
            with jax.profiler.TraceAnnotation(SPAN + "wait"):
                time.sleep(max(0.0, min(wake - now(), 0.01)))
            continue
        rec.steps.append((s0, s1))
        for sid in list(live):
            seq, r = live[sid]
            got = len(seq.tokens) - seq.prompt_len
            while len(r["stamps"]) < got:
                r["stamps"].append(s1)
            if r["first"] is None and got:
                r["first"] = s1
            if seq.done:
                r["output"] = seq.tokens[seq.prompt_len:]
                del live[sid]
    if tracing and rec.trace_host[1] is None:
        jax.profiler.stop_trace()
        rec.trace_host[1] = now()
    rec.loop_s = now() - loop_start


# --------------------------------------------------------------------- #
#  Correctness: the served tokens against the plain reference            #
# --------------------------------------------------------------------- #
def compare(ref, cfg, w, rec, seed, jax, jnp, np, control=None):
    """Gaps by which each served token's reference logit lies below the
    reference's best, over a sample drawn from the seed of the finished
    requests, the longest among them.  With ``control``, the token that
    the control ranks first at each of those positions takes the served
    token's place, and the same statistics are taken of it."""
    done = [r for r in rec.requests if r.get("output") is not None]
    if not done:
        return None
    want = cfg["check"]["requests"]
    longest = max(done, key=lambda r: (len(r["output"]), -r["idx"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed % 2**63, 7])
    pick = [longest] + [rest[i] for i in rng.permutation(len(rest))[:want - 1]]
    rows = 256 if cfg["engine"]["max_len"] >= 256 else 16
    T = -(-cfg["engine"]["max_len"] // rows) * rows
    fn = jax.jit(lambda w, t: ref.served_gaps(cfg, w, t, rows=rows,
                                              control=control))
    gaps = []
    for r in pick:
        P, out = len(r["prompt"]), r["output"]
        toks = np.zeros(T, np.int32)
        toks[:P] = r["prompt"]
        toks[P:P + len(out)] = out
        gaps.append(np.asarray(fn(w, jnp.asarray(toks)))[P - 1:P - 1 + len(out)])
    return gap_stats(np, gaps)


def gap_stats(np, gaps):
    g = np.concatenate(gaps)
    return {"max_logit_gap": float(g.max()), "mean_logit_gap": float(g.mean()),
            "argmax_mismatch": float((g > 0).mean()), "tokens": int(g.size)}


def new_records(reqs):
    return [dict(idx=q.idx, prompt=q.prompt, max_new=q.max_new, due=q.due,
                 in_window=q.in_window, seq=None, submit=None, first=None,
                 stamps=[], output=None) for q in reqs]


def population(requests, t):
    """(requests submitted by ``t`` without a first token, requests
    decoding at ``t``)."""
    decoding = sum(r["first"] is not None and r["first"] <= t
                   and (len(r["stamps"]) < r["max_new"] or r["stamps"][-1] > t)
                   for r in requests)
    return backlog(requests, t), decoding


def backlog(requests, t):
    """Requests submitted by ``t`` that had no first token yet."""
    return sum(r["submit"] is not None and r["submit"] <= t
               and (r["first"] is None or r["first"] > t) for r in requests)


def sweep(eng, jax, np, args, mix, seconds, cfg, scale, rec):
    """One window at each offered rate on the same engine; between rates
    the engine runs until it is idle."""
    import schedule
    for rate in (float(x) for x in args.sweep.split(",")):
        m = {**mix, "cycle": {"requests": max(1, round(rate * seconds)),
                              "period_s": seconds}}
        reqs = schedule.build(m, seconds, args.seed, cfg["vocab_size"], scale)
        rec.requests, rec.steps = new_records(reqs), []
        base = time.perf_counter() - min(q.due for q in reqs)
        drive(eng, jax, reqs, base, base + seconds, m["drain_limit_s"], rec,
              None)
        t0, t1 = base, base + seconds
        win = [r for r in rec.requests if r["in_window"]]
        run = SimpleNamespace(t0=t0, t1=t1, seconds=seconds, window=win,
                              requests=rec.requests, np=np)
        ttft = [r["first"] - (t0 + r["due"]) for r in win if r["first"]]
        half = len(ttft) // 2
        line = {"sweep_rate": rate, "requests": len(win),
                "backlog_open": backlog(rec.requests, t0),
                "backlog_close": backlog(rec.requests, t1),
                "ttft_first_half": float(np.median(ttft[:half] or [0])),
                "ttft_second_half": float(np.median(ttft[half:] or [0]))}
        for name in ("ttft_p50_s", "tbt_p95_ms", "out_tok_s"):
            line[name] = load_module(HERE / "metrics" / f"{name}.py").read(run)
        print(json.dumps(line), flush=True)
        t = time.perf_counter()
        while (eng.step() or eng.waiting) and time.perf_counter() - t < 30:
            pass
    return 0


# --------------------------------------------------------------------- #
def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU; never reports correct")
    ap.add_argument("--sweep", default=None,
                    help="comma-separated offered rates (requests/s): run "
                         "the window once at each, print one line per rate "
                         "and no result (the sweep that finds the knee)")
    ap.add_argument("--control", action="store_true",
                    help="judge the fp8 control in the program's place: the "
                         "run must read correct false")
    return ap.parse_args(argv)


def main(argv=None, engine_hook=None) -> int:
    args = parse(argv)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    bench, wl, cfg, mix = load_cell(args.workload, args.rehearsal)
    seconds = args.seconds if args.seconds is not None else bench["run_seconds"]
    setup = {}

    import jax
    import jax.numpy as jnp
    import numpy as np
    cache_dir = STATE / ("jax_cache_cpu" if args.rehearsal else "jax_cache")
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no size limit, hence no eviction and no lock file: compiles from many
    # threads at once otherwise time out on the lock and miss the cache
    jax.config.update("jax_compilation_cache_max_size", -1)
    clog = CompileLog(jax)
    setup["import_s"] = time.perf_counter() - T_START

    devs = jax.devices()
    dev0 = devs[0]
    if not args.rehearsal and (dev0.platform != "tpu"
                               or len(devs) < wl["chips"]):
        log(f"no result: the cell needs {wl['chips']} TPU chip(s); JAX "
            f"found {len(devs)} {dev0.platform} device(s)")
        return 2
    from repro.core import TPU_V5E, device_model, solver_backend
    from repro.serve import Engine, EngineConfig
    import counts
    import schedule
    from peaks import peaks
    peak = None if args.rehearsal else peaks(dev0.device_kind)
    dev_model = TPU_V5E if args.rehearsal else device_model(dev0.device_kind)

    ref = load_module(HERE / "configs" / cfg["reference"])
    prog = load_module(HERE / "configs" / cfg["program"])
    scale = cfg["rehearsal"]["length_scale"] if args.rehearsal else 1.0
    reqs = schedule.build(mix, seconds, args.seed, cfg["vocab_size"], scale)
    max_len = cfg["engine"]["max_len"]
    too_long = [q.idx for q in reqs if len(q.prompt) + q.max_new > max_len]
    if too_long:
        raise SystemExit(f"requests {too_long} do not fit {max_len} positions")
    setup["device_s"] = time.perf_counter() - T_START - setup["import_s"]

    # weights: one jitted program from the seed, on the device
    t = time.perf_counter()
    words = np.random.SeedSequence(args.seed).generate_state(2, np.uint32)
    w = jax.jit(lambda k: ref.make_weights(cfg, k))(jnp.asarray(words))
    jax.block_until_ready(w)
    setup["weights_s"] = time.perf_counter() - t

    rec = SimpleNamespace(spans=defaultdict(list), calls=[], steps=[],
                          requests=[], trace_host=None,
                          trace_s=min(seconds, TRACE_S), loop_s=0.0)
    with solver_backend("jax"):
        t = time.perf_counter()
        ecfg = EngineConfig(**cfg["engine"])
        eng = Engine(prog.model_config(cfg), params=prog.program_params(w),
                     ecfg=ecfg, dev=dev_model)
        jax.block_until_ready(eng.cache)
        setup["engine_s"] = time.perf_counter() - t
        warm_up(eng, jax, jnp, np, max(len(q.prompt) for q in reqs),
                os.cpu_count() or 4, setup)
        instrument(eng, jax, rec)
        if engine_hook:
            engine_hook(eng)

        if args.sweep:
            return sweep(eng, jax, np, args, mix, seconds, cfg, scale, rec)
        trace_dir = None
        if args.trace:           # the profiler's first start is slow: do it now
            trace_dir = STATE / "trace"
            t = time.perf_counter()
            jax.profiler.start_trace(str(trace_dir),
                                     profiler_options=profile_options(jax))
            jax.profiler.stop_trace()
            shutil.rmtree(trace_dir, ignore_errors=True)
            setup["profiler_s"] = time.perf_counter() - t
        rec.requests = new_records(reqs)
        # set-up's objects (the traced and lowered programs among them)
        # leave the collector's view, so that no full collection over
        # them pauses the loop
        gc.collect()
        gc.freeze()
        lead = -min((q.due for q in reqs), default=0.0)
        base = time.perf_counter() + lead
        drive(eng, jax, reqs, base, base + seconds, mix["drain_limit_s"],
              rec, trace_dir)
    setup["lead_in_s"] = lead
    setup_s = base - T_START
    t0, t1 = base, base + seconds

    stats = dev0.memory_stats() or {}
    mem_peak = stats.get("peak_bytes_in_use")
    seq_of = {r["seq"]: r for r in rec.requests if r["seq"] is not None}
    chunks = defaultdict(int)
    for e in eng.events:
        if e.kind == "prefill_chunk" and e.detail["seq"] in seq_of:
            chunks[e.detail["seq"]] += 1
    for sid, r in seq_of.items():
        r["chunks"] = chunks[sid]
    decode_pos = [(t, np.asarray(note)) for name, t, note in rec.calls
                  if name == "decode"]
    eng.cache = None
    del eng
    gc.unfreeze()
    gc.collect()

    # the comparison with the plain reference, once the window has closed
    t = time.perf_counter()
    stats = compare(ref, cfg, w, rec, args.seed, jax, jnp, np,
                    control="fp8" if args.control else None)
    check_s = time.perf_counter() - t

    window = [r for r in rec.requests if r["in_window"]]
    no_first = sum(r["first"] is None for r in window)
    wrong_len = sum(len(r["output"]) != r["max_new"] for r in rec.requests
                    if r["output"] is not None)
    number, limit = cfg["check"]["number"], cfg["check"]["limit"]
    value = stats[number] if stats else None
    checks = {
        number: {"value": value, "limit": limit,
                 "tokens": stats["tokens"] if stats else 0},
        "wrong_length": {"value": wrong_len, "limit": 0},
        "no_first_token": {"value": no_first, "limit": 0},
    }
    ok = (value is not None and limit is not None and value <= limit
          and wrong_len == 0 and no_first == 0)

    trace = None
    if args.trace and rec.trace_host:
        from traces import find, reduce_trace
        path = find(str(STATE / "trace"))
        trace = reduce_trace(path, SPAN) if path else None

    run = SimpleNamespace(
        t0=t0, t1=t1, seconds=seconds, setup_s=setup_s, setup=setup,
        requests=rec.requests, window=window, spans=rec.spans,
        calls=rec.calls, decode_pos=decode_pos, steps=rec.steps,
        compiles_in_window=clog.between(t0, t1), trace=trace,
        trace_host=rec.trace_host, dims=counts.Dims.from_hf(cfg),
        counts=counts, peak=peak, np=np, max_len=max_len)
    names = [m for m in bench["per_layer" if args.trace else "end_to_end"]
             if wl["name"] in m.get("workloads", [wl["name"]])]
    metrics = {}
    for m in names:
        v = load_module(HERE / "metrics" / f"{m['name']}.py").read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    late = np.asarray([r["submit"] - (base + r["due"]) for r in window
                       if r["submit"] is not None] or [0.0])
    log("setup: " + " ".join(f"{k}={v!r}" for k, v in setup.items())
        + f" setup_s={setup_s!r} compile_s={clog.compile_s!r} "
        f"cache_hits={clog.cache_hits} lowered={len(clog.lowered)}")
    log(f"generator lateness (window): p50={float(np.median(late))!r} s "
        f"max={float(late.max())!r} s over {len(window)} requests")
    log(f"window: {len(window)} requests due, loop {rec.loop_s!r} s, "
        f"{len(rec.steps)} engine steps, reference check {check_s!r} s")
    step_gap = max((b[0] - a[1] for a, b in zip(rec.steps, rec.steps[1:])
                    if t0 <= a[1] < t1), default=0.0)
    log(f"population (waiting for a first token / decoding): open "
        f"{population(rec.requests, t0)}, middle "
        f"{population(rec.requests, (t0 + t1) / 2)}, close "
        f"{population(rec.requests, t1)}; longest gap between busy steps "
        f"{step_gap!r} s")
    log("ttft_s in due order: " + " ".join(
        f"{r['first'] - (t0 + r['due']):.3f}" if r["first"] else "-"
        for r in window))
    log(("the fp8 control's first-ranked tokens, in the served tokens' "
         "place," if args.control else "served tokens")
        + f" against the reference: {stats}")
    for k, v in checks.items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")

    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devs),
              "memory_peak_bytes": mem_peak}
    line = {"correct": ok, "attempted": len(window), "failed": no_first,
            "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    line["gaps"] = stats
    line["checks"] = checks
    if args.rehearsal:
        line = {"rehearsal": True, "would_be_correct": ok,
                **{k: v for k, v in line.items() if k != "correct"}}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
