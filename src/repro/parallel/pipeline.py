"""Pipeline parallelism: GPipe schedule over a mesh axis via shard_map +
ppermute (stage-to-stage sends are point-to-point ICI transfers).

Stages hold disjoint layer blocks (stage_params leading dim sharded over
the pipeline axis). Microbatches stream through; JAX AD differentiates
through the ppermute ring (its transpose is the reverse permute), so the
same function trains. Combine with DP/TP on the remaining mesh axes:
e.g. mesh (pod=2, data=16, model=16) -> 2 pipeline stages x 16-way fsdp
x 16-way TP.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def pipeline_apply(mesh: Mesh, axis: str, stage_fn: Callable,
                   stage_params, microbatches: jnp.ndarray) -> jnp.ndarray:
    """Run `microbatches` (n_micro, mb, ...) through `n_stages` pipeline
    stages. stage_params: pytree with leading dim n_stages (one slice per
    stage). stage_fn(params_slice, x) -> y must preserve x's shape.

    Returns outputs (n_micro, mb, ...) — activations after the last stage.
    """
    n_stages = mesh.shape[axis]
    n_micro = microbatches.shape[0]
    T = n_micro + n_stages - 1
    ring = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def inner(params, mbs):
        params = jax.tree.map(lambda p: p[0], params)   # local stage slice
        idx = jax.lax.axis_index(axis)
        buf = jnp.zeros_like(mbs[0])
        outs = jnp.zeros_like(mbs)

        def tick(t, carry):
            buf, outs = carry
            inject = mbs[jnp.clip(t, 0, n_micro - 1)]
            x = jnp.where(idx == 0, inject, buf)
            y = stage_fn(params, x)
            # the LAST stage's result at tick t is microbatch t-(S-1)
            out_idx = t - (n_stages - 1)
            upd = jax.lax.dynamic_update_slice_in_dim(
                outs, y[None].astype(outs.dtype), jnp.clip(out_idx, 0, n_micro - 1), 0)
            outs = jnp.where((idx == n_stages - 1) & (out_idx >= 0), upd, outs)
            buf = jax.lax.ppermute(y, axis, ring)
            return buf, outs

        _, outs = jax.lax.fori_loop(0, T, tick, (buf, outs))
        return outs[None]   # (1, n_micro, ...) per stage

    spec_p = jax.tree.map(lambda _: P(axis), stage_params)
    out = jax.shard_map(inner, mesh=mesh, in_specs=(spec_p, P()),
                        out_specs=P(axis), check_vma=False)(
        stage_params, microbatches)
    # the last stage's buffer holds the real outputs; the stage axis is
    # sharded, so replicate it before indexing (explicit-sharding meshes
    # refuse a slice of a sharded dimension)
    return jax.sharding.reshard(out, NamedSharding(mesh, P()))[-1]


def split_stages(stacked_params, n_stages: int):
    """(L, ...) stacked layer params -> (n_stages, L/n_stages, ...)."""
    def re(p):
        L = p.shape[0]
        assert L % n_stages == 0, f"{L} layers not divisible by {n_stages} stages"
        return p.reshape(n_stages, L // n_stages, *p.shape[1:])

    return jax.tree.map(re, stacked_params)
