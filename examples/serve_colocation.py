"""Serving with interference-aware chunked prefill (paper §4.2/§5.1).

Runs the same request mix through the engine in `serial` mode (monolithic
prefills -> head-of-line blocking of the decode batch) and in
`interference_aware` mode (prefill chunks sized per-step by pricing
decode-vs-chunk `Scenario`s so the decode batch's TBT stays within SLO),
and compares decode-gap statistics.

Run:  PYTHONPATH=src python examples/serve_colocation.py
"""
import numpy as np

from repro.configs.registry import get_config, tiny_config
from repro.core import TPU_V5E, Scenario, solve_scenarios
from repro.serve import Engine, EngineConfig


def run(mode: str):
    cfg = tiny_config(get_config("qwen3-1.7b"))
    eng = Engine(cfg, ecfg=EngineConfig(max_slots=4, max_len=768,
                                        prefill_chunk=64, mode=mode,
                                        tbt_slo_ms=1e-6), dev=TPU_V5E)
    # a decode-heavy workload...
    for _ in range(3):
        eng.submit(list(np.random.default_rng(0).integers(1, 99, 12)),
                   max_new=30)
    for _ in range(5):
        eng.step()
    # ...interrupted by a LONG prompt (the paper's sleep-kernel analogue)
    long_id = eng.submit(list(np.random.default_rng(1).integers(1, 99, 512)),
                         max_new=4)
    eng.run_until_done()

    # structural HOL metric: how many decode steps ran BETWEEN the long
    # prompt's first and last prefill chunk (serial: 0 — the decode batch
    # stalls for the whole monolithic prefill). Wall-clock on this CPU
    # container is dominated by XLA compiles, so the schedule itself is
    # the meaningful observable.
    kinds = [e.kind for e in eng.events]
    long_chunks = [i for i, e in enumerate(eng.events)
                   if e.kind == "prefill_chunk" and e.detail["seq"] == long_id]
    interleaved = (kinds[long_chunks[0]:long_chunks[-1]].count("decode")
                   if len(long_chunks) > 1 else 0)
    print(f"mode={mode:20s} long prompt split into "
          f"{len(long_chunks)} chunk(s); decode steps interleaved during "
          f"its prefill: {interleaved}")
    return interleaved


def show_chunk_pricing():
    """The engine's per-step decision, spelled out: one Scenario per
    chunk candidate (victim = decode batch, background = the chunk)."""
    cfg = tiny_config(get_config("qwen3-1.7b"))
    eng = Engine(cfg, ecfg=EngineConfig(), dev=TPU_V5E)
    decode = eng._phase_profile("decode", 3)
    cands = [256, 128, 64, 32]
    chunks = [eng._phase_profile(f"prefill{c}", c) for c in cands]
    br = solve_scenarios([Scenario((decode,), (ch,)) for ch in chunks],
                         eng.dev)
    print("\nchunk-size pricing (decode batch of 3):")
    for c, s in zip(cands, br.slowdowns[:, 0]):
        print(f"  chunk {c:4d} -> predicted decode slowdown {s:.2f}x")


def main():
    i_serial = run("serial")
    i_aware = run("interference_aware")
    print(f"\nHOL mitigation: serial interleaves {i_serial} decode steps "
          f"during the long prefill; interference-aware interleaves "
          f"{i_aware} (decode batch keeps flowing)")
    assert i_aware > i_serial
    show_chunk_pricing()


if __name__ == "__main__":
    main()
