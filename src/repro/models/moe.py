"""Mixture-of-Experts FFN with sort-based grouped dispatch.

Design (TPU/pjit-native — see DESIGN.md §5):
  * tokens stay sharded over the data axes; experts are sharded over the
    `model` axis (EP).  Each device keeps its local tokens, selects the
    subset routed to its *local* experts (sort + capacity buffer), runs the
    grouped expert matmuls, and the per-token combine is a single
    activation-sized ``psum`` over the model axis — no token all-to-all.
  * one-hot (T,E,C) GShard dispatch is O(T·E·C) memory and infeasible at
    top-6/64-expert scale; the sort-based path is O(T·k·d).

Two entry points share the same math:
  ``moe_ffn_local``  — single-device / oracle path (E_local = E).
  ``moe_ffn``        — shard_map path over (data…, model) for EP.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import Params, dense_init


class ParallelContext(NamedTuple):
    """How model-internal collectives see the mesh. mesh=None => local."""
    mesh: Optional[object] = None
    data_axes: Tuple[str, ...] = ()
    model_axis: Optional[str] = None
    # tp2d decode: weights are (d@data, ff@model); activations hop between
    # batch-sharded (attention/cache) and feature-sharded (MLP) layouts —
    # decode-sized reshards instead of weight-sized all-gathers (§Perf C2)
    feature_shard_decode: bool = False

    @property
    def n_model_shards(self) -> int:
        if self.mesh is None or self.model_axis is None:
            return 1
        return self.mesh.shape[self.model_axis]

    @property
    def n_data_shards(self) -> int:
        if self.mesh is None:
            return 1
        return math.prod(self.mesh.shape[a] for a in self.data_axes) or 1


LOCAL_CTX = ParallelContext()


def moe_init(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    m = cfg.moe
    d, f, E = cfg.d_model, m.d_ff_expert, m.n_experts
    ks = jax.random.split(key, 5)
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": (jax.random.normal(ks[0], (d, E), jnp.float32) * scale),
        "w_gate": (jax.random.normal(ks[1], (E, d, f), jnp.float32) * scale).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (E, d, f), jnp.float32) * scale).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (E, f, d), jnp.float32)
                   * (1.0 / math.sqrt(f))).astype(dtype),
    }
    if m.n_shared_experts:
        fs = m.n_shared_experts * f
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": dense_init(k1, d, fs, dtype),
            "w_up": dense_init(k2, d, fs, dtype),
            "w_down": dense_init(k3, fs, d, dtype),
        }
    return p


def capacity(n_tokens_local: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    c = int(math.ceil(n_tokens_local * m.top_k * m.capacity_factor / m.n_experts))
    return max(8, -(-c // 8) * 8)      # round up to a multiple of 8


# --------------------------------------------------------------------- #
#  Grouped dispatch for one shard                                        #
# --------------------------------------------------------------------- #
def _dispatch_compute_combine(x_flat, gates, ids, wg, wu, wd,
                              expert_lo: int, n_local: int, cap: int,
                              act: str = "silu"):
    """x_flat (T,d); gates/ids (T,k); expert weights are the LOCAL slice
    (n_local, d, f). Returns partial output (T, d) covering local experts."""
    T, d = x_flat.shape
    k = ids.shape[1]
    flat_ids = ids.reshape(-1)                           # (T*k,)
    flat_gate = gates.reshape(-1)
    local_ids = flat_ids - expert_lo
    is_local = (local_ids >= 0) & (local_ids < n_local)
    sort_key = jnp.where(is_local, local_ids, n_local)   # drop bucket last
    order = jnp.argsort(sort_key)                        # stable
    sorted_ids = sort_key[order]
    # position within each expert group
    starts = jnp.searchsorted(sorted_ids, jnp.arange(n_local + 1))
    pos = jnp.arange(T * k) - starts[jnp.clip(sorted_ids, 0, n_local)]
    keep = (sorted_ids < n_local) & (pos < cap)
    slot = jnp.where(keep, sorted_ids * cap + pos, n_local * cap)
    tok = order // k                                     # source token index
    buf = jnp.zeros((n_local * cap + 1, d), x_flat.dtype)
    buf = buf.at[slot].set(jnp.where(keep[:, None], x_flat[tok], 0))
    h_in = buf[:-1].reshape(n_local, cap, d)
    g = jnp.einsum("ecd,edf->ecf", h_in, wg)
    u = jnp.einsum("ecd,edf->ecf", h_in, wu)
    h = (jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g, approximate=True)) * u
    out_e = jnp.einsum("ecf,efd->ecd", h, wd).reshape(n_local * cap, d)
    contrib = out_e[jnp.where(keep, slot, n_local * cap - 1)]
    contrib = jnp.where(keep[:, None], contrib * flat_gate[order][:, None].astype(contrib.dtype), 0)
    out = jnp.zeros((T, d), x_flat.dtype).at[tok].add(contrib)
    return out


def _route(router, x_flat, cfg: ModelConfig):
    m = cfg.moe
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, m.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    # load-balance aux loss (Switch-style): E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)
    # pin one_hot to the routing dtype: its float_ default is f64 under
    # x64 (the solver backend enables it), which would leak into the
    # f32 aux-loss scan carry
    ce = jnp.mean(jax.nn.one_hot(ids, m.n_experts,
                                 dtype=probs.dtype).sum(axis=1), axis=0)
    aux = m.n_experts * jnp.sum(me * ce)
    return gates, ids, aux


def moe_ffn_local(p: Params, cfg: ModelConfig, x: jnp.ndarray):
    """Oracle / single-device path."""
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    gates, ids, aux = _route(p["router"], xf, cfg)
    cap = capacity(xf.shape[0], cfg)
    out = _dispatch_compute_combine(xf, gates, ids, p["w_gate"], p["w_up"],
                                    p["w_down"], 0, cfg.moe.n_experts, cap,
                                    cfg.act if cfg.act != "geglu" else "gelu")
    out = out + _shared_ffn(p, cfg, xf)
    return out.reshape(B, S, d), aux


def _shared_ffn(p: Params, cfg: ModelConfig, xf: jnp.ndarray) -> jnp.ndarray:
    if not cfg.moe.n_shared_experts:
        return jnp.zeros_like(xf)
    sp = p["shared"]
    g = jnp.einsum("td,df->tf", xf, sp["w_gate"])
    u = jnp.einsum("td,df->tf", xf, sp["w_up"])
    return jnp.einsum("tf,fd->td", jax.nn.silu(g) * u, sp["w_down"])


def moe_ffn(p: Params, cfg: ModelConfig, x: jnp.ndarray,
            ctx: ParallelContext):
    """EP path: experts sharded over ctx.model_axis via shard_map."""
    if ctx.mesh is None or ctx.n_model_shards == 1:
        return moe_ffn_local(p, cfg, x)
    B, S, d = x.shape
    E = cfg.moe.n_experts
    n_model = ctx.n_model_shards
    assert E % n_model == 0, f"experts {E} not divisible by model axis {n_model}"
    n_local = E // n_model
    t_local = (B * S) // ctx.n_data_shards
    cap = capacity(t_local, cfg)
    act = cfg.act if cfg.act != "geglu" else "gelu"
    batch_spec = P(ctx.data_axes if ctx.data_axes else None)
    ax = ctx.model_axis

    def shard_fn(xs, router, wg, wu, wd):
        Bl, Sl, _ = xs.shape
        xf = xs.reshape(-1, d)
        gates, ids, aux = _route(router, xf, cfg)
        idx = jax.lax.axis_index(ax)
        out = _dispatch_compute_combine(xf, gates, ids, wg, wu, wd,
                                        idx * n_local, n_local, cap, act)
        out = jax.lax.psum(out, ax)
        aux = jax.lax.pmean(aux, ax)
        for a in ctx.data_axes:
            aux = jax.lax.pmean(aux, a)
        return out.reshape(Bl, Sl, d), aux

    out, aux = jax.shard_map(
        shard_fn, mesh=ctx.mesh,
        in_specs=(batch_spec, P(), P(ax), P(ax), P(ax)),
        out_specs=(batch_spec, P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    out = out + _shared_ffn(p, cfg, x.reshape(-1, d)).reshape(B, S, d)
    return out, aux
