"""Plain reference of the Qwen3 family (dense and mixture of experts), and
the benchmark's weights for it.

Follows the Hugging Face ``Qwen3ForCausalLM`` / ``Qwen3MoeForCausalLM``
modelling code: pre-norm decoder layers with RMSNorm; per-head RMSNorm
of queries and keys (QK-norm) before rotary embeddings in the
rotate-half convention; grouped-query causal attention; a SwiGLU MLP, or
a softmax router whose top-k probabilities are renormalised to gate
SwiGLU experts; a final RMSNorm and a tied or separate unembedding.

Everything is float32 at the highest matmul precision, one layer at a
time, every expert over every token (the gate is zero where a token is
not routed), so nothing is dropped or approximated.  It imports nothing
of the system under test.  ``control="fp8"`` computes the same with each
weight matrix rounded to float8 e4m3 with one scale per output channel:
the precision below the bfloat16 that the configuration states.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


# --------------------------------------------------------------------- #
#  Weights                                                               #
# --------------------------------------------------------------------- #
def weight_shapes(hf: dict) -> dict:
    d, L = hf["hidden_size"], hf["num_hidden_layers"]
    H, KV, D = (hf["num_attention_heads"], hf["num_key_value_heads"],
                hf["head_dim"])
    layer = {"attn_norm": (d,), "mlp_norm": (d,), "q_norm": (D,),
             "k_norm": (D,), "wq": (d, H * D), "wk": (d, KV * D),
             "wv": (d, KV * D), "wo": (H * D, d)}
    if hf.get("num_experts"):
        E, f = hf["num_experts"], hf["moe_intermediate_size"]
        layer.update(router=(d, E), w_gate=(E, d, f), w_up=(E, d, f),
                     w_down=(E, f, d))
    else:
        f = hf["intermediate_size"]
        layer.update(w_gate=(d, f), w_up=(d, f), w_down=(f, d))
    out = {"embed": (hf["vocab_size"], d), "final_norm": (d,),
           "layers": {k: (L,) + s for k, s in layer.items()}}
    if not hf["tie_word_embeddings"]:
        out["unembed"] = (d, hf["vocab_size"])
    return out


def make_weights(hf: dict, key):
    """Every weight from one key: bfloat16 matrices with entries
    ``normal / sqrt(fan_in)`` (the embedding's fan-in is the hidden size),
    a float32 router, float32 norm scales ``1 + 0.1 * normal``.  Jit it:
    layers are drawn one at a time."""
    shapes = weight_shapes(hf)
    d, L = hf["hidden_size"], hf["num_hidden_layers"]

    def draw(k, name, shape, fan_in):
        if name.endswith("norm"):
            return 1.0 + 0.1 * jax.random.normal(k, shape, F32)
        dtype = F32 if name == "router" else jnp.bfloat16
        return (jax.random.normal(k, shape, dtype)
                * jnp.asarray(1.0 / math.sqrt(fan_in), dtype))

    lshapes = {n: s[1:] for n, s in shapes["layers"].items()}
    names = sorted(lshapes)

    def one_layer(k):
        ks = jax.random.split(k, len(names))
        return {n: draw(kk, n, lshapes[n], lshapes[n][0] if len(lshapes[n])
                        < 3 else lshapes[n][1])
                for n, kk in zip(names, ks)}

    k_emb, k_un, k_fin, k_layers = jax.random.split(key, 4)
    w = {"embed": draw(k_emb, "embed", shapes["embed"], d),
         "final_norm": draw(k_fin, "final_norm", shapes["final_norm"], 1),
         "layers": jax.lax.map(one_layer, jax.random.split(k_layers, L))}
    if "unembed" in shapes:
        w["unembed"] = draw(k_un, "unembed", shapes["unembed"], d)
    return w


# --------------------------------------------------------------------- #
#  Forward                                                               #
# --------------------------------------------------------------------- #
def _fp8(w):
    """Round a weight to float8 e4m3 with one scale per output channel."""
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 448.0
    q = (w / scale).astype(jnp.float8_e4m3fn).astype(F32)
    return q * scale


def _prep(w, control):
    w = w.astype(F32)
    return _fp8(w) if control == "fp8" and w.ndim >= 2 else w


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, pos, theta):
    """x (T, heads, D); rotate-half convention."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = pos[:, None].astype(F32) * inv                 # (T, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(h, g, u, dn):
    a = jnp.dot(h, g, precision=HI)
    b = jnp.dot(h, u, precision=HI)
    return jnp.dot(jax.nn.silu(a) * b, dn, precision=HI)


def hidden_states(hf: dict, w: dict, tokens, control: str | None = None):
    """Final normalised hidden state of every position, (T, d) float32.
    ``tokens`` (T,) int32; positions past the prompt do not affect the
    ones before them (causal)."""
    T = tokens.shape[0]
    H, KV, D = (hf["num_attention_heads"], hf["num_key_value_heads"],
                hf["head_dim"])
    eps, theta = hf["rms_norm_eps"], hf["rope_theta"]
    pos = jnp.arange(T)
    causal = pos[None, :] <= pos[:, None]
    x = w["embed"][tokens].astype(F32)

    def layer(x, lw):
        experts = "router" in lw           # expert weights: one at a time
        p = {n: a if experts and n.startswith("w_") else _prep(a, control)
             for n, a in lw.items()}
        h = _rms(x, p["attn_norm"], eps)
        q = jnp.dot(h, p["wq"], precision=HI).reshape(T, H, D)
        k = jnp.dot(h, p["wk"], precision=HI).reshape(T, KV, D)
        v = jnp.dot(h, p["wv"], precision=HI).reshape(T, KV, D)
        q = _rope(_rms(q, p["q_norm"], eps), pos, theta)
        k = _rope(_rms(k, p["k_norm"], eps), pos, theta)
        k = jnp.repeat(k, H // KV, axis=1)               # head h -> h // G
        v = jnp.repeat(v, H // KV, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k, precision=HI) / math.sqrt(D)
        s = jnp.where(causal[None], s, -jnp.inf)
        a = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v,
                       precision=HI).reshape(T, H * D)
        x = x + jnp.dot(a, p["wo"], precision=HI)
        h = _rms(x, p["mlp_norm"], eps)
        if "router" in p:
            x = x + _moe(hf, p, h, control)
        else:
            x = x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
        return x, None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    return _rms(x, _prep(w["final_norm"], control), eps)


def _moe(hf, p, h, control):
    """Softmax router, top-k renormalised gates, every expert over every
    token weighted by its gate (zero where the token is not routed)."""
    k = hf["num_experts_per_tok"]
    probs = jax.nn.softmax(jnp.dot(h, p["router"], precision=HI), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    if hf["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    T, E = probs.shape
    gates = jnp.zeros((T, E), F32).at[jnp.arange(T)[:, None], idx].set(top)

    def expert(acc, ew):
        g, u, dn, gate = ew
        y = _swiglu(h, _prep(g, control), _prep(u, control),
                    _prep(dn, control))
        return acc + gate[:, None] * y, None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          (p["w_gate"], p["w_up"], p["w_down"], gates.T))
    return out


def logits_rows(hf: dict, w: dict, hidden, control: str | None = None):
    """Logits of a block of hidden states, (R, vocab) float32."""
    if "unembed" in w:
        return jnp.dot(hidden, _prep(w["unembed"], control), precision=HI)
    return jnp.dot(hidden, _prep(w["embed"], control).T, precision=HI)


def served_gaps(hf: dict, w: dict, tokens, rows: int = 256,
                control: str | None = None):
    """For each position t: how far the logit of ``tokens[t + 1]`` lies
    below the reference's best logit at t.  With ``control``, the token
    that the control ranks first at t takes the place of ``tokens[t + 1]``:
    the control is judged as the served tokens are.  Returns a (T,) array;
    the last position, which has no next token, reads 0."""
    T = tokens.shape[0]
    h = hidden_states(hf, w, tokens)
    hc = hidden_states(hf, w, tokens, control) if control else None
    nxt = jnp.concatenate([tokens[1:], tokens[-1:]])
    nb = T // rows

    def blk(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * rows, rows)
        lg = logits_rows(hf, w, sl(h))
        if control:
            pick = jnp.argmax(logits_rows(hf, w, sl(hc), control), axis=-1)
        else:
            pick = sl(nxt)
        got = jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
        return jnp.max(lg, axis=-1) - got

    return jax.lax.map(blk, jnp.arange(nb)).reshape(T).at[T - 1].set(0.0)
