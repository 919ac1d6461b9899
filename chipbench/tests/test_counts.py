"""``counts.py`` against a hand count of qwen3-1.7b (28 layers, d 2048,
16 heads and 8 KV heads of 128, SwiGLU 6144, vocabulary 151936, tied)."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import counts  # noqa: E402

# per layer: q and o 2048 x 2048 each, k and v 2048 x 1024 each
ATTN = 2048 * 2048 * 2 + 2048 * 1024 * 2          # 12,582,912
MLP = 3 * 2048 * 6144                              # 37,748,736
LAYERS = 28 * (ATTN + MLP)                         # 1,409,286,144
UNEMBED = 151936 * 2048                            # 311,164,928
KV_POS = 28 * 2 * 8 * 128 * 2                      # 114,688 bytes
ATTN_FLOPS_PER_KEY = 28 * 4 * 16 * 128             # 229,376


@pytest.fixture(scope="module")
def dims():
    cfg = json.loads((HERE / "configs" / "qwen3-1.7b.json").read_text())
    return counts.Dims.from_hf(cfg)


def test_decode_step(dims):
    flops, nbytes = counts.decode(dims, [100, 200])
    assert flops == 2 * 2 * LAYERS + ATTN_FLOPS_PER_KEY * 300 + 2 * 2 * UNEMBED
    assert flops == 6_950_617_088
    assert nbytes == 2 * LAYERS + 2 * UNEMBED + 2 * 2048 * 2 + 300 * KV_POS
    assert nbytes == 3_475_316_736


def test_extend_step(dims):
    flops, nbytes = counts.extend(dims, 16, 32)
    # 16 queries at positions 32..47 attend over 33..48 keys: 648 in all
    assert 16 * 32 + 16 * 17 // 2 == 648
    assert flops == (16 * 2 * LAYERS + ATTN_FLOPS_PER_KEY * 648
                     + 2 * UNEMBED)
    assert flops == 45_868_122_112
    assert nbytes == 2 * LAYERS + 2 * UNEMBED + 16 * 2048 * 2 + 48 * KV_POS
    assert nbytes == 3_446_472_704


def test_moe_counts_routed_experts_only():
    cfg = json.loads((HERE / "configs" / "qwen3-30b-a3b-6l.json").read_text())
    m = counts.Dims.from_hf(cfg)
    one = counts.expected_experts(128, 8, 1)
    assert one == pytest.approx(8.0)
    f1, b1 = counts.decode(m, [10])
    f2, b2 = counts.decode(m, [10, 10])
    expert = 3 * 2048 * 768
    # a second token adds its own 8 experts' FLOPs, and fewer than 8
    # experts' bytes (some it shares with the first)
    assert f2 - f1 == pytest.approx(
        6 * (2 * (2048 * (2 * 4096 + 2 * 512)) + 2 * 8 * expert
             + 2 * 2048 * 128) + 6 * 4 * 32 * 128 * 10 + 2 * 2048 * 151936)
    extra = (counts.expected_experts(128, 8, 2) - 8) * expert * 2 * 6
    assert b2 - b1 == pytest.approx(extra + 2048 * 2 + 10 * m.kv_bytes_per_position)


def test_step_mfu_over_device_time(dims):
    """The window's FLOPs, counted call by call, over the device's busy
    time times the peak; ``serve_mfu`` takes the same FLOPs over the
    whole traced window."""
    from types import SimpleNamespace

    import numpy as np

    import peaks
    from metrics import serve_mfu, step_mfu
    peak = peaks.peaks("TPU v5 lite")
    run = SimpleNamespace(
        trace={"busy_s": 0.5, "window_s": 2.0}, trace_host=(10.0, 12.0),
        peak=peak, counts=counts, dims=dims, max_len=2048,
        calls=[("extend", 10.5, (16, 32)), ("extend", 13.0, (16, 48)),
               ("pick_chunk", 10.6, None)],
        decode_pos=[(11.0, np.asarray([99, 199, 2048])),
                    (9.0, np.asarray([98, 198, 2048]))])
    flops = 45_868_122_112 + 6_950_617_088       # one extend, one decode
    assert serve_mfu.flops(run) == flops
    assert step_mfu.read(run) == pytest.approx(
        flops / (0.5 * peak["bf16_flops"]) * 100)
    assert serve_mfu.read(run) == pytest.approx(step_mfu.read(run) / 4)
    run.trace = None
    assert step_mfu.read(run) is None
