"""A run whose timed path is broken underneath reads ``correct`` false.

Each case drives the whole of ``run.main`` at the configuration's
rehearsal sizes on the CPU (the look for a chip is the rehearsal's to
skip) with one fault planted in the engine, and sees the number that the
configuration compares pass its limit, so that the verdict is false; the
clean run of the same seed stays within it.  Run by hand (the cells compile
their shapes on the CPU, ~1 minute a case):

  JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_faults.py
"""
import contextlib
import io
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

CELLS = [w["name"] for w in
         json.loads((HERE.parent / "BENCHMARK.json").read_text())["workloads"]]
SEED = 2**31 + 77


def state_unchanged(eng):
    """The decode step hands back the cache it was given."""
    import jax
    import jax.numpy as jnp
    dec = eng._decode

    def bad(p, tok, cache, pos):
        keep = jax.tree.map(jnp.copy, cache)
        logits, _ = dec(p, tok, cache, pos)
        return logits, keep
    eng._decode = bad


def half_batch(eng):
    """The decode step computes the first half of the slots only."""
    dec = eng._decode

    def bad(p, tok, cache, pos):
        half = tok.shape[0] // 2
        return dec(p, tok.at[half:].set(0), cache,
                   pos.at[half:].set(eng.ecfg.max_len))
    eng._decode = bad


def token_altered(eng):
    """Every third sampled token is replaced where it is produced."""
    sample, n = eng._sample, [0]

    def bad(logits):
        n[0] += 1
        tok = sample(logits)
        return (tok + 1) % logits.shape[-1] if n[0] % 3 == 0 else tok
    eng._sample = bad


def _run(cell, hook):
    import run
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.main(["--workload", cell, "--rehearsal", "--seconds", "12",
                         "--seed", str(SEED)], engine_hook=hook) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [None, state_unchanged, half_batch,
                                   token_altered],
                         ids=["clean", "state_unchanged", "half_batch",
                              "token_altered"])
def test_fault_reads_incorrect(cell, fault):
    line = _run(cell, fault)
    assert "correct" not in line            # a rehearsal never reports it
    gap = next(iter(line["checks"].values()))   # the number compared
    assert gap["limit"] is not None
    if fault is None:
        assert line["would_be_correct"], line["checks"]
    else:
        assert gap["value"] > gap["limit"], gap
        assert line["would_be_correct"] is False
