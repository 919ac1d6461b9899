"""Every cell, configuration, mix and metric of ``BENCHMARK.json`` is a
file of its own that the harness finds by name."""
import json
import re
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_part_is_a_file_found_by_name():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for wl in BENCH["workloads"]:
        cfg = json.loads((ROOT / configs[wl["config"]]["file"]).read_text())
        assert cfg["name"] == wl["config"]
        assert (HERE / "configs" / cfg["reference"]).is_file()
        assert (HERE / "configs" / cfg["program"]).is_file()
        assert (HERE / "mixes" / f"{wl['traffic']}.json").is_file()
        assert wl["name"] == f"{wl['config']}.{wl['traffic']}"
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        mod = run.load_module(HERE / "metrics" / f"{m['name']}.py")
        assert callable(mod.read)


def test_names_and_keys():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert c["source"] == cfg["source"]
    moves = {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["moves"] in moves for m in BENCH["per_layer"])
    assert all(1 <= len(m["layer"]) <= 200 for m in BENCH["per_layer"])
