"""Operations and bytes that one engine call needs, from its shapes.

The work the algorithm needs, not what an implementation happens to do:
every weight read once, the cache positions that are live in the active
slots read once, one position written per new token.  The padded
``slots x (max_len + 1)`` cache and any copy of it are not counted.
Weights are bfloat16, routers float32; norm scales and other vectors of
the width are left out.

Each architecture is read from its configuration's own published keys,
chosen by ``model_type``; any other ``model_type`` raises ``ValueError``:

- ``qwen3``: grouped-query attention (``num_attention_heads``,
  ``num_key_value_heads``, ``head_dim``) and a dense SwiGLU of
  ``intermediate_size`` in every layer.
- ``qwen3_moe``: the same attention; every layer routes each token to
  ``num_experts_per_tok`` of ``num_experts`` SwiGLU experts of
  ``moe_intermediate_size``.  A layout with dense layers among them
  (``decoder_sparse_step`` other than 1, ``mlp_only_layers``) raises.
- ``deepseek_v3``: latent attention (MLA: ``kv_lora_rank``,
  ``q_lora_rank`` null or a number, ``qk_nope_head_dim``,
  ``qk_rope_head_dim``, ``v_head_dim``).  The first
  ``first_k_dense_replace`` layers carry a dense SwiGLU of
  ``intermediate_size``; the others ``n_routed_experts`` routed experts
  and ``n_shared_experts`` shared ones, all of ``moe_intermediate_size``,
  and a router with a correction bias where ``topk_method`` is
  ``noaux_tc``.  ``moe_layer_freq`` other than 1 raises.

A dense SwiGLU and the shared experts are read whole by every call.  Of
the routed experts only those that the call's tokens route to are read;
the router is not observed, so that number is its expectation under
uniform routing, ``E * (1 - (1 - k/E) ** tokens)``.  FLOPs per token are
twice the matrix parameters a token passes through.

Attention over the context (``attention``) is counted per (query, key)
pair.  Grouped-query attention: ``4 * heads * head_dim``, and keys and
values of every KV head cached per position.  MLA caches one latent of
``kv_lora_rank + qk_rope_head_dim`` per position and has two forms.
Absorbed, ``kv_b`` serves as W_UK and W_UV and each pair costs
``heads * (2 * (lora + rope) + 2 * lora)``.  Expanded, ``kv_b``
up-projects the cache and each pair costs
``heads * (2 * (nope + rope) + 2 * v)``, plus
``2 * lora * heads * (nope + v)`` for each position already cached.
Each call counts the cheaper of the two for its own shapes (decode is
always absorbed, a long first chunk expanded), whatever form the program
runs, so the program's choice of form cannot move the count by itself.
"""
from __future__ import annotations

from collections.abc import Sequence
from dataclasses import dataclass

BF16 = 2
F32 = 4


@dataclass(frozen=True)
class Gqa:
    """Grouped-query attention."""
    heads: int
    kv_heads: int
    head_dim: int

    def params(self, d: int) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return d * (2 * q + 2 * kv)

    @property
    def cache_bytes(self) -> int:
        """Keys and values of one position in one layer."""
        return 2 * self.kv_heads * self.head_dim * BF16

    def context_flops(self, pairs: int, cached: int) -> int:
        """Scores and weighted values of ``pairs`` (query, key) pairs in
        one layer; ``cached`` is unused here."""
        return 4 * self.heads * self.head_dim * pairs


@dataclass(frozen=True)
class Mla:
    """Multi-head latent attention; ``q_lora`` 0 for a full-rank query."""
    heads: int
    kv_lora: int
    q_lora: int
    nope: int
    rope: int
    v: int

    def params(self, d: int) -> int:
        qk = self.heads * (self.nope + self.rope)
        q = d * self.q_lora + self.q_lora * qk if self.q_lora else d * qk
        return (q + d * (self.kv_lora + self.rope)               # kv_a
                + self.kv_lora * self.heads * (self.nope + self.v)  # kv_b
                + self.heads * self.v * d)                       # o

    @property
    def cache_bytes(self) -> int:
        """The latent and the rotary key of one position in one layer."""
        return (self.kv_lora + self.rope) * BF16

    def context_flops(self, pairs: int, cached: int) -> int:
        """``pairs`` (query, key) pairs in one layer, with ``cached``
        positions that the expanded form would up-project: the cheaper
        form."""
        absorbed = pairs * self.heads * (2 * (self.kv_lora + self.rope)
                                         + 2 * self.kv_lora)
        expanded = (pairs * self.heads * (2 * (self.nope + self.rope)
                                          + 2 * self.v)
                    + cached * 2 * self.kv_lora * self.heads
                    * (self.nope + self.v))
        return min(absorbed, expanded)


@dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    vocab: int
    attn: Gqa | Mla
    tied: bool = False     # unembedding shares the embedding's matrix
    dense_layers: int = 0  # leading layers with the dense SwiGLU
    d_ff: int = 0          # dense SwiGLU width
    experts: int = 0       # routed experts of each later layer
    top_k: int = 0
    d_expert: int = 0
    shared: int = 0        # shared experts of each later layer
    router_bias: bool = False

    @classmethod
    def from_hf(cls, hf: dict) -> "Dims":
        kind = hf.get("model_type")
        if kind in ("qwen3", "qwen3_moe"):
            return cls._qwen3(hf)
        if kind == "deepseek_v3":
            return cls._deepseek_v3(hf)
        raise ValueError(f"counts.py has no rule for model_type {kind!r}")

    @classmethod
    def _qwen3(cls, hf: dict) -> "Dims":
        if (hf.get("decoder_sparse_step", 1) != 1
                or hf.get("mlp_only_layers")):
            raise ValueError("qwen3_moe with dense layers among the MoE "
                             "layers is not counted")
        layers, experts = hf["num_hidden_layers"], hf.get("num_experts", 0)
        return cls(layers=layers, d=hf["hidden_size"], vocab=hf["vocab_size"],
                   attn=Gqa(heads=hf["num_attention_heads"],
                            kv_heads=hf["num_key_value_heads"],
                            head_dim=hf["head_dim"]),
                   tied=hf.get("tie_word_embeddings", False),
                   dense_layers=0 if experts else layers,
                   d_ff=0 if experts else hf["intermediate_size"],
                   experts=experts,
                   top_k=hf.get("num_experts_per_tok", 0),
                   d_expert=hf.get("moe_intermediate_size", 0))

    @classmethod
    def _deepseek_v3(cls, hf: dict) -> "Dims":
        if hf.get("moe_layer_freq", 1) != 1:
            raise ValueError(f"deepseek_v3 with moe_layer_freq "
                             f"{hf['moe_layer_freq']!r} is not counted")
        layers = hf["num_hidden_layers"]
        return cls(layers=layers, d=hf["hidden_size"], vocab=hf["vocab_size"],
                   attn=Mla(heads=hf["num_attention_heads"],
                            kv_lora=hf["kv_lora_rank"],
                            q_lora=hf.get("q_lora_rank") or 0,
                            nope=hf["qk_nope_head_dim"],
                            rope=hf["qk_rope_head_dim"], v=hf["v_head_dim"]),
                   tied=hf.get("tie_word_embeddings", False),
                   dense_layers=min(hf["first_k_dense_replace"], layers),
                   d_ff=hf["intermediate_size"],
                   experts=hf["n_routed_experts"],
                   top_k=hf["num_experts_per_tok"],
                   d_expert=hf["moe_intermediate_size"],
                   shared=hf.get("n_shared_experts") or 0,
                   router_bias=hf.get("topk_method") == "noaux_tc")

    # ---- parameters ------------------------------------------------ #
    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def attn_params(self) -> int:
        return self.attn.params(self.d)

    @property
    def dense_params(self) -> int:
        return 3 * self.d * self.d_ff

    @property
    def expert_params(self) -> int:
        return 3 * self.d * self.d_expert

    @property
    def shared_params(self) -> int:
        return self.shared * self.expert_params

    @property
    def router_bytes(self) -> int:
        bias = self.experts * F32 if self.router_bias else 0
        return self.d * self.experts * F32 + bias

    @property
    def params(self) -> int:
        """Matrix parameters of the whole model (vectors left out)."""
        moe = (self.experts * self.expert_params + self.shared_params
               + self.d * self.experts)
        return (self.layers * self.attn_params
                + self.dense_layers * self.dense_params
                + self.moe_layers * moe
                + self.vocab * self.d * (1 if self.tied else 2))

    @property
    def kv_bytes_per_position(self) -> int:
        """The cache of one position in every layer."""
        return self.layers * self.attn.cache_bytes


def expected_experts(experts: int, top_k: int, tokens: int) -> float:
    if tokens <= 0:
        return 0.0
    return experts * (1.0 - (1.0 - top_k / experts) ** tokens)


def _weight_bytes(m: Dims, tokens: int) -> float:
    attn = m.attn_params * BF16
    total = m.dense_layers * (attn + m.dense_params * BF16)
    if m.moe_layers:
        routed = expected_experts(m.experts, m.top_k, tokens) * m.expert_params
        total += m.moe_layers * (attn + routed * BF16
                                 + m.shared_params * BF16 + m.router_bytes)
    return (total
            + m.vocab * m.d * BF16                  # unembedding
            + tokens * m.d * BF16)                  # embedding rows


def _token_flops(m: Dims) -> int:
    """Matmul FLOPs of one token through the layers, without attention
    over the context and without the unembedding."""
    attn = 2 * m.attn_params
    moe = (2 * m.top_k * m.expert_params + 2 * m.shared_params
           + 2 * m.d * m.experts)                   # router
    return (m.dense_layers * (attn + 2 * m.dense_params)
            + m.moe_layers * (attn + moe))


def attention(m: Dims, chunk: int, cached: Sequence[int]) -> tuple[int, int]:
    """(FLOPs, bytes) of attention over the context in one call: each
    sequence has ``cached[i]`` positions in its cache and adds ``chunk``
    new ones, each attending over the positions before it and itself.
    Bytes are the cache positions read and written."""
    pairs = sum(chunk * c + chunk * (chunk + 1) // 2 for c in cached)
    positions = sum(c + chunk for c in cached)
    flops = m.layers * m.attn.context_flops(pairs, sum(cached))
    return flops, positions * m.kv_bytes_per_position


def decode(m: Dims, contexts) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step; ``contexts`` lists, for each
    sequence in the batch, the positions it attends over (its own
    included)."""
    b = len(contexts)
    if b == 0:
        return 0.0, 0.0
    attn_flops, attn_bytes = attention(m, 1, [c - 1 for c in contexts])
    flops = b * _token_flops(m) + attn_flops + b * 2 * m.d * m.vocab
    # each sequence reads the positions before its own and writes its own
    nbytes = _weight_bytes(m, b) + attn_bytes
    return float(flops), float(nbytes)


def extend(m: Dims, chunk: int, pos0: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one prefill chunk of ``chunk`` tokens written at
    positions ``pos0 .. pos0 + chunk - 1``; logits of its last token only."""
    attn_flops, attn_bytes = attention(m, chunk, [pos0])
    flops = chunk * _token_flops(m) + attn_flops + 2 * m.d * m.vocab
    nbytes = _weight_bytes(m, chunk) + attn_bytes
    return float(flops), float(nbytes)
