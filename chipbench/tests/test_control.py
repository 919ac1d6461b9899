"""The control: the plain reference computed with float8 (e4m3) weights,
the precision below the configuration's bfloat16.  ``run.py --control``
puts its first-ranked tokens in the place of the served tokens and
judges them with the same checks and limits, so the run must read
``correct`` false, while the program's run of the same seed reads within
the limit.  On the chip this is read at each cell's own size (readings
in PERF.md); here at the rehearsal sizes on the CPU, where the control
must also read at least three times what the served tokens read.

  JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_control.py
"""
import contextlib
import io
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _line(cell, *extra):
    import run
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.main(["--workload", cell, "--rehearsal", "--seconds", "20",
                         "--seed", "19", *extra]) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_incorrect(cell):
    program, control = _line(cell), _line(cell, "--control")
    assert "correct" not in control         # a rehearsal never reports it
    assert program["would_be_correct"], program["checks"]
    assert control["would_be_correct"] is False
    number = next(iter(control["checks"]))
    chk = control["checks"][number]
    assert chk["value"] > chk["limit"]
    assert chk["tokens"] == program["checks"][number]["tokens"] > 50
    assert chk["value"] >= 3 * program["checks"][number]["value"]
