"""``counts.py`` against a hand count of qwen3-1.7b (28 layers, d 2048,
16 heads and 8 KV heads of 128, SwiGLU 6144, vocabulary 151936, tied),
and of Moonlight-16B-A3B (``deepseek_v3``: latent attention, one leading
dense layer, 64 routed and 2 shared experts)."""
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


# Moonlight-16B-A3B, the language model's keys of its published config.json
# (https://huggingface.co/moonshotai/Moonlight-16B-A3B)
MOONLIGHT = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
    "kv_lora_rank": 512, "max_position_embeddings": 8192,
    "model_type": "deepseek_v3", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts_per_tok": 6, "num_hidden_layers": 27,
    "num_key_value_heads": 16, "num_nextn_predict_layers": 0,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_theta": 50000,
    "routed_scaling_factor": 2.446, "scoring_func": "sigmoid",
    "seq_aux": True, "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840}

# per layer: q 2048 x 16*(128+64), kv_a 2048 x (512+64), kv_b 512 x 16*(128+128),
# o 16*128 x 2048
M_ATTN = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
M_DENSE = 3 * 2048 * 11264                         # layer 0
M_EXPERT = 3 * 2048 * 1408
M_SHARED = 2 * M_EXPERT
M_ROUTER = 2048 * 64                               # float32, 64 biases beside
M_UNEMBED = 163840 * 2048
M_LATENT = (512 + 64) * 2                          # bytes a position and layer
ABSORBED = 16 * (2 * (512 + 64) + 2 * 512)         # FLOPs a (query, key) pair
EXPANDED = 16 * (2 * (128 + 64) + 2 * 128)
REEXPAND = 2 * 512 * 16 * (128 + 128)              # a cached position, expanded


def _moe_token_flops(layers):
    """Matmul FLOPs of one token through a cut with one dense layer."""
    return (layers * 2 * M_ATTN + 2 * M_DENSE
            + (layers - 1) * (2 * 6 * M_EXPERT + 2 * M_SHARED + 2 * M_ROUTER))


def test_moonlight_counts_by_layer_kind():
    m = counts.Dims.from_hf(MOONLIGHT)
    assert (m.layers, m.dense_layers, m.moe_layers) == (27, 1, 26)
    assert m.attn_params == M_ATTN == 13_762_560
    assert m.dense_params == M_DENSE == 69_206_016
    assert m.expert_params == M_EXPERT == 8_650_752
    assert m.shared_params == M_SHARED == 17_301_504
    assert m.router_bytes == (M_ROUTER + 64) * 4
    assert m.params == (27 * M_ATTN + M_DENSE
                        + 26 * (64 * M_EXPERT + M_SHARED + M_ROUTER)
                        + 2 * M_UNEMBED) == 15_959_982_080
    assert m.kv_bytes_per_position == 27 * M_LATENT == 31_104


def test_moonlight_cut_decode_step():
    m = counts.Dims.from_hf({**MOONLIGHT, "num_hidden_layers": 7})
    flops, nbytes = counts.decode(m, [100, 200])
    # 300 (query, key) pairs over 298 cached positions: absorbed is cheaper
    assert 300 * ABSORBED < 300 * EXPANDED + 298 * REEXPAND
    assert flops == (2 * _moe_token_flops(7) + 7 * 300 * ABSORBED
                     + 2 * 2 * M_UNEMBED)
    assert flops == 3_741_556_736
    routed = counts.expected_experts(64, 6, 2)
    assert routed == 64 * (1 - (58 / 64) ** 2) == 11.4375
    weights = ((7 * M_ATTN + M_DENSE + 6 * (routed * M_EXPERT + M_SHARED)) * 2
               + 6 * (M_ROUTER + 64) * 4 + M_UNEMBED * 2 + 2 * 2048 * 2)
    assert nbytes == weights + 300 * 7 * M_LATENT == 2_402_684_928


def test_moonlight_cut_extend_step():
    m = counts.Dims.from_hf({**MOONLIGHT, "num_hidden_layers": 7})
    flops, nbytes = counts.extend(m, 16, 32)
    # 648 pairs over 32 cached positions: absorbed is cheaper
    assert 648 * ABSORBED < 648 * EXPANDED + 32 * REEXPAND
    assert flops == 16 * _moe_token_flops(7) + 7 * 648 * ABSORBED + 2 * M_UNEMBED
    routed = counts.expected_experts(64, 6, 16)
    weights = ((7 * M_ATTN + M_DENSE + 6 * (routed * M_EXPERT + M_SHARED)) * 2
               + 6 * (M_ROUTER + 64) * 4 + M_UNEMBED * 2 + 16 * 2048 * 2)
    assert nbytes == pytest.approx(weights + 48 * 7 * M_LATENT, rel=1e-14)


@pytest.mark.parametrize("chunk, pos0, form", [(512, 0, "expanded"),
                                               (16, 4000, "absorbed")])
def test_mla_counts_the_cheaper_form(chunk, pos0, form):
    m = counts.Dims.from_hf(MOONLIGHT)
    pairs = chunk * pos0 + chunk * (chunk + 1) // 2
    forms = {"absorbed": 27 * pairs * ABSORBED,
             "expanded": 27 * (pairs * EXPANDED + pos0 * REEXPAND)}
    flops, nbytes = counts.attention(m, chunk, [pos0])
    assert flops == forms[form] == min(forms.values())
    assert nbytes == (pos0 + chunk) * 27 * M_LATENT
    assert counts.extend(m, chunk, pos0)[0] == (
        chunk * _moe_token_flops(27) + flops + 2 * M_UNEMBED)


def test_mla_with_a_query_rank():
    m = counts.Dims.from_hf({**MOONLIGHT, "q_lora_rank": 1536})
    # q_a 2048 x 1536 and q_b 1536 x 16*(128+64) in place of q
    attn = M_ATTN - 2048 * 16 * 192 + 2048 * 1536 + 1536 * 16 * 192
    assert m.attn_params == attn == 15_335_424
    full = counts.Dims.from_hf(MOONLIGHT)
    assert m.kv_bytes_per_position == full.kv_bytes_per_position
    assert (counts.decode(m, [10])[0] - counts.decode(full, [10])[0]
            == 27 * 2 * (attn - M_ATTN))


def test_gqa_attention_is_what_decode_and_extend_count(dims):
    assert counts.attention(dims, 1, [99, 199]) == (ATTN_FLOPS_PER_KEY * 300,
                                                    300 * KV_POS)
    assert counts.attention(dims, 16, [32]) == (ATTN_FLOPS_PER_KEY * 648,
                                                48 * KV_POS)


@pytest.mark.parametrize("config, call, expected", [
    ("qwen3-30b-a3b-6l", ("decode", [2, 640, 1281]),
     (4103897088.0, 2154545152.0)),
    ("qwen3-30b-a3b-6l", ("extend", 1, 0), (1305051136.0, 1308114944.0)),
    ("qwen3-30b-a3b-6l", ("extend", 256, 1000),
     (203773444096.0, 8119352859.994383)),
    ("qwen3-30b-a3b-6l", ("extend", 7, 1273), (6279430144.0, 3505425227.0)),
    ("qwen3-1.7b", ("decode", [2, 640, 1281]), (10763796480.0, 3661459456.0)),
    ("qwen3-1.7b", ("extend", 256, 1000), (788442644480.0, 3585998848.0)),
])
def test_qwen3_counts_keep_their_floats(config, call, expected):
    """Floats of the Qwen3 counts from before the per-architecture rules,
    to the last bit."""
    cfg = json.loads((HERE / "configs" / f"{config}.json").read_text())
    m = counts.Dims.from_hf(cfg)
    assert getattr(counts, call[0])(m, *call[1:]) == expected


def _qwen3_moe():
    return json.loads((HERE / "configs" / "qwen3-30b-a3b-6l.json").read_text())


@pytest.mark.parametrize("hf, says", [
    ({**MOONLIGHT, "model_type": "llama"}, "'llama'"),
    ({k: v for k, v in MOONLIGHT.items() if k != "model_type"}, "None"),
    ({**MOONLIGHT, "moe_layer_freq": 2}, "moe_layer_freq 2"),
    ({**_qwen3_moe(), "decoder_sparse_step": 2}, "dense layers"),
    ({**_qwen3_moe(), "mlp_only_layers": [0]}, "dense layers"),
], ids=["unknown_type", "no_type", "moe_layer_freq", "sparse_step",
        "mlp_only_layers"])
def test_unknown_architecture_raises(hf, says):
    with pytest.raises(ValueError, match=says):
        counts.Dims.from_hf(hf)
