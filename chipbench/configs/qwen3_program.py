"""The system under test for a Qwen3 configuration: the program's
``ModelConfig`` and parameter tree, built from the configuration file
and the benchmark's weights (``qwen3_reference.make_weights``).  The tree
holds the same arrays; nothing is copied or converted."""
from __future__ import annotations

from repro.configs.base import AttentionConfig, ModelConfig, MoEConfig


def model_config(cfg: dict) -> ModelConfig:
    moe = bool(cfg.get("num_experts"))
    if moe and not cfg["norm_topk_prob"]:
        raise ValueError("the program always renormalises the top-k gates")
    if cfg["attention_bias"] or cfg["hidden_act"] != "silu":
        raise ValueError("the program has no attention bias and only SiLU")
    return ModelConfig(
        name=cfg["name"], family="moe" if moe else "dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        attn=AttentionConfig(n_heads=cfg["num_attention_heads"],
                             n_kv_heads=cfg["num_key_value_heads"],
                             head_dim=cfg["head_dim"], qk_norm=True,
                             rope_theta=float(cfg["rope_theta"])),
        moe=MoEConfig(n_experts=cfg["num_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_ff_expert=cfg["moe_intermediate_size"],
                      capacity_factor=cfg["capacity_factor"])
        if moe else MoEConfig(),
        act="silu", norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], source=cfg["source"])


def program_params(w: dict) -> dict:
    lw = w["layers"]
    ffn = ({"moe": {"router": lw["router"], "w_gate": lw["w_gate"],
                    "w_up": lw["w_up"], "w_down": lw["w_down"]}}
           if "router" in lw else
           {"mlp": {"w_gate": lw["w_gate"], "w_up": lw["w_up"],
                    "w_down": lw["w_down"]}})
    embed = {"embedding": w["embed"]}
    if "unembed" in w:
        embed["unembed"] = w["unembed"]
    return {"embed": embed, "final_ln": {"scale": w["final_norm"]},
            "stack": {"ln1": {"scale": lw["attn_norm"]},
                      "ln2": {"scale": lw["mlp_norm"]},
                      "attn": {"wq": lw["wq"], "wk": lw["wk"], "wv": lw["wv"],
                               "wo": lw["wo"], "q_norm": lw["q_norm"],
                               "k_norm": lw["k_norm"]},
                      **ffn}}
