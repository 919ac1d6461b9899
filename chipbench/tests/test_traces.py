"""The trace reduction on interval arithmetic and on a small trace that
``record_trace.py`` recorded on a TPU v5 lite (``data/``)."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import traces  # noqa: E402

DATA = HERE / "tests" / "data" / "tiny_engine.xplane.pb"
SPANS = {"step", "pick_chunk", "extend", "decode", "sample", "wait",
         traces.NO_SPAN}


def test_union_and_gaps():
    busy = traces.union([(5, 7), (0, 2), (1, 3), (6, 9), (9, 10)])
    assert busy == [(0, 3), (5, 10)]
    assert traces.gaps(busy, -1, 12) == [(-1, 0), (3, 5), (10, 12)]
    assert traces.clip(busy, 2, 6) == [(2, 3), (5, 6)]


def test_program_name():
    assert traces.program_name("jit_decode(1234)") == "decode"
    assert traces.program_name("jit_extend") == "extend"


@pytest.fixture(scope="module")
def reduced():
    return traces.reduce_trace(str(DATA), "chipbench.")


def test_recorded_trace_busy_within_window(reduced):
    assert reduced["n_devices"] == 1
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(v for _, v in reduced["idle_gaps"]) == pytest.approx(
        idle, rel=1e-6)


def test_recorded_trace_programs(reduced):
    progs = reduced["programs"]
    for name in ("decode", "extend"):
        total, count = progs[name]
        assert count > 0 and 0 < total < reduced["window_s"]


def test_recorded_trace_names_layers(reduced):
    names = {n for n, _ in reduced["idle_gaps"]}
    assert names <= SPANS
    assert names & {"pick_chunk", "sample", "step", "decode", "extend"}
    assert len(reduced["device_ops"]) == 10
    assert all(v > 0 for _, v in reduced["device_ops"])
