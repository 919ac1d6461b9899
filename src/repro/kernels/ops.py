"""Jit'd public wrappers around the Pallas kernels.

Every wrapper compiles its kernel to Mosaic unless the caller passes
``interpret=True`` (the CPU tests do: the kernel body then executes in
Python with identical semantics). Nothing here picks interpret mode from
the platform. ``repro.models.attention`` dispatches here when
``attn_impl == "pallas"``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import rmsnorm as _rms
from repro.kernels import ssm_scan as _ssm
from repro.kernels import stressors as _st


@partial(jax.jit, static_argnames=("kind", "window", "softcap", "block_q",
                                   "block_k", "interpret"))
def flash_attention(q, k, v, *, kind: str = "causal", window: int = 0,
                    softcap: float = 0.0, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """Model-layout wrapper: q (B,S,H,D); k/v (B,T,KVH,D) -> (B,S,H,D).
    (softcap unsupported in the kernel; asserted off.)"""
    assert not softcap, "softcap not implemented in the Pallas kernel"
    B, S, H, D = q.shape
    T, KVH = k.shape[1], k.shape[2]
    kf = k.transpose(0, 2, 1, 3).reshape(B * KVH, T, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KVH, T, D)
    # group query heads of one kv head adjacently: (B, KVH, G, S, D)
    qf = q.reshape(B, S, KVH, H // KVH, D).transpose(0, 2, 3, 1, 4)
    qf = qf.reshape(B * H, S, D)
    o = _fa.flash_attention_bhsd(qf, kf, vf, kind=kind, window=window,
                                 block_q=block_q, block_k=block_k,
                                 interpret=interpret)
    o = o.reshape(B, KVH, H // KVH, S, D).transpose(0, 3, 1, 2, 4)
    return o.reshape(B, S, H, D)


@partial(jax.jit, static_argnames=("block_k", "interpret"))
def flash_decode(q, k, v, kv_len, *, block_k: int = 512,
                 interpret: bool = False):
    """q (B,1,H,D); k/v (B,T,KVH,D); kv_len () or (B,) -> (B,1,H,D)."""
    B, _, H, D = q.shape
    T, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    qf = q.reshape(B, KVH, G, D).reshape(B * KVH, G, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KVH, T, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KVH, T, D)
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (B,))
    lens = jnp.repeat(kv_len, KVH)
    o = _dec.flash_decode_bkgd(qf, kf, vf, lens, block_k=block_k,
                               interpret=interpret)
    return o.reshape(B, KVH, G, D).reshape(B, 1, H, D)


@partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, scale, eps: float = 1e-6, block_rows: int = 256,
            interpret: bool = False):
    shape = x.shape
    out = _rms.rmsnorm_pallas(x.reshape(-1, shape[-1]), scale, eps=eps,
                              block_rows=block_rows, interpret=interpret)
    return out.reshape(shape)


@partial(jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def ssm_scan(x, dt, A, B, C, *, chunk: int = 64, block_d: int = 512,
             interpret: bool = False):
    return _ssm.ssm_scan_pallas(x, dt, A, B, C, chunk=chunk,
                                block_d=block_d, interpret=interpret)
