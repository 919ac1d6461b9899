"""Pallas TPU flash-decode kernel: one query token vs. a long KV cache.

Layout: q (B, KVH, G, D) — all query heads of one kv group together so the
(G, bk) score tile feeds the MXU; k/v (B*KVH, T, D). The KV-length grid
axis is sequential with m/l/acc scratch carry (flash-decode partials).
The per-sequence valid lengths reach the kernel through scalar prefetch
(the whole (BKV,) int32 vector lives in SMEM).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, block_k: int, n_kv_blocks: int):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                       # (G, D)
    k = k_ref[0]                                       # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kv_len = len_ref[pl.program_id(0)]
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(k_pos < kv_len, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32)

    @pl.when(ik == n_kv_blocks - 1)
    def _fin():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_decode_bkgd(q, k, v, kv_len, *, block_k: int = 512,
                      interpret: bool = False) -> jnp.ndarray:
    """q: (BKV, G, D) one token per sequence; k/v: (BKV, T, D);
    kv_len: (BKV,) int32 valid lengths. Returns (BKV, G, D)."""
    BKV, G, D = q.shape
    T = k.shape[1]
    block_k = min(block_k, T)
    nk = -(-T // block_k)
    pad = nk * block_k - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    kernel = functools.partial(_kernel, scale=1.0 / math.sqrt(D),
                               block_k=block_k, n_kv_blocks=nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BKV, nk),
        in_specs=[
            pl.BlockSpec((1, G, D), lambda b, ik, lens: (b, 0, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, ik, lens: (b, ik, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, ik, lens: (b, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, D), lambda b, ik, lens: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BKV, G, D), q.dtype),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), q, k, v)
