"""Operations and bytes that one engine call needs, from its shapes.

The work the algorithm needs, not what an implementation happens to do:
every weight read once, the KV positions that are live in the active
slots read once, one position written per new token.  The padded
``slots x (max_len + 1)`` cache and any copy of it are not counted.
For a mixture of experts only the experts that the call's tokens route to
are counted; the router is not observed, so that number is its
expectation under uniform routing, ``E * (1 - (1 - k/E) ** tokens)``.
"""
from __future__ import annotations

from dataclasses import dataclass

BF16 = 2
F32 = 4


@dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    d_ff: int = 0          # dense SwiGLU width
    experts: int = 0
    top_k: int = 0
    d_expert: int = 0

    @classmethod
    def from_hf(cls, hf: dict) -> "Dims":
        return cls(layers=hf["num_hidden_layers"], d=hf["hidden_size"],
                   heads=hf["num_attention_heads"],
                   kv_heads=hf["num_key_value_heads"],
                   head_dim=hf["head_dim"], vocab=hf["vocab_size"],
                   d_ff=0 if hf.get("num_experts") else hf["intermediate_size"],
                   experts=hf.get("num_experts", 0),
                   top_k=hf.get("num_experts_per_tok", 0),
                   d_expert=hf.get("moe_intermediate_size", 0))

    # ---- parameters ------------------------------------------------ #
    @property
    def attn_params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return self.d * (2 * q + 2 * kv)

    @property
    def expert_params(self) -> int:
        return 3 * self.d * self.d_expert

    def ffn_params_used(self, tokens: int) -> float:
        """FFN weights one layer must read for ``tokens`` tokens."""
        if not self.experts:
            return 3 * self.d * self.d_ff
        return expected_experts(self.experts, self.top_k, tokens) * self.expert_params

    @property
    def ffn_params_per_token(self) -> int:
        if not self.experts:
            return 3 * self.d * self.d_ff
        return self.top_k * self.expert_params

    @property
    def kv_bytes_per_position(self) -> int:
        """Keys and values of one position in every layer."""
        return self.layers * 2 * self.kv_heads * self.head_dim * BF16


def expected_experts(experts: int, top_k: int, tokens: int) -> float:
    if tokens <= 0:
        return 0.0
    return experts * (1.0 - (1.0 - top_k / experts) ** tokens)


def _weight_bytes(m: Dims, tokens: int) -> float:
    per_layer = m.attn_params * BF16 + m.ffn_params_used(tokens) * BF16
    if m.experts:
        per_layer += m.d * m.experts * F32          # router
    return (m.layers * per_layer
            + m.vocab * m.d * BF16                  # unembedding
            + tokens * m.d * BF16)                  # embedding rows


def _token_flops(m: Dims) -> int:
    """Matmul FLOPs of one token through the layers, without attention
    over the context and without the unembedding."""
    if m.experts:
        router = 2 * m.d * m.experts
    else:
        router = 0
    return m.layers * (2 * m.attn_params + 2 * m.ffn_params_per_token + router)


def _attn_flops(m: Dims, context: int) -> int:
    """Scores and weighted values of one query over ``context`` keys."""
    return m.layers * 4 * m.heads * m.head_dim * context


def decode(m: Dims, contexts) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step; ``contexts`` lists, for each
    sequence in the batch, the positions it attends over (its own
    included)."""
    b = len(contexts)
    if b == 0:
        return 0.0, 0.0
    live = sum(contexts)
    flops = (b * _token_flops(m) + sum(_attn_flops(m, c) for c in contexts)
             + b * 2 * m.d * m.vocab)
    # each sequence reads the positions before its own and writes its own
    nbytes = _weight_bytes(m, b) + live * m.kv_bytes_per_position
    return float(flops), float(nbytes)


def extend(m: Dims, chunk: int, pos0: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one prefill chunk of ``chunk`` tokens written at
    positions ``pos0 .. pos0 + chunk - 1``; logits of its last token only."""
    attn = m.layers * 4 * m.heads * m.head_dim * (
        chunk * pos0 + chunk * (chunk + 1) // 2)
    flops = chunk * _token_flops(m) + attn + 2 * m.d * m.vocab
    nbytes = (_weight_bytes(m, chunk) + pos0 * m.kv_bytes_per_position
              + chunk * m.kv_bytes_per_position)
    return float(flops), float(nbytes)
