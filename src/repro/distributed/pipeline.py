"""GPipe pipeline parallelism via shard_map + collective_permute.

The production dry-run mesh has no "pipe" axis (DP×TP covers 512 chips for
every assigned arch), but beyond 2 pods the documented scaling path splits
the layer stack across pods: stage s holds layers [s·L/S, (s+1)·L/S) and
microbatches rotate stage-to-stage with ppermute. This module implements
that schedule in a mesh-shape-agnostic way; tests run it on an 8-device
host-platform mesh and check exactness against the unsharded stack.

Schedule (GPipe, no interleaving): T = n_micro + n_stages − 1 ticks. At
tick t, stage s computes microbatch (t − s) if 0 ≤ t − s < n_micro; the
boundary activations move s → s+1 between ticks. Bubble fraction =
(S − 1)/T, amortized by n_micro ≫ S; with the default schedule the
ppermute overlaps the next microbatch's compute (XLA async collective).
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P


def pipeline_apply(block_fn: Callable, stacked_params, x, *, mesh,
                   axis: str = "pipe", n_micro: int | None = None):
    """Run ``x`` through L stacked layers split over mesh axis ``axis``.

    block_fn(params_slice, x_micro) -> x_micro — one layer.
    stacked_params: leaves with leading dim L (L % n_stages == 0).
    x: (B, ...) global batch; B % n_micro == 0.
    """
    n_stages = mesh.shape[axis]
    n_micro = n_micro or n_stages
    b = x.shape[0]
    assert b % n_micro == 0, (b, n_micro)
    mb = b // n_micro

    def stage_fn(params_local, x_all):
        # params_local: (L/S, ...) this stage's layers; x_all: full batch
        # (replicated input; only stage 0's reads matter).
        sid = jax.lax.axis_index(axis)
        micro = x_all.reshape(n_micro, mb, *x_all.shape[1:])

        def run_stage(xm):
            def body(carry, p):
                return block_fn(p, carry), None
            out, _ = jax.lax.scan(body, xm, params_local)
            return out

        t_total = n_micro + n_stages - 1
        buf = jnp.zeros((mb, *x_all.shape[1:]), x_all.dtype)
        outs = jnp.zeros_like(micro)

        def tick(t, state):
            buf, outs = state
            mid = t - sid                     # microbatch index at this stage
            active = (mid >= 0) & (mid < n_micro)
            src = jax.lax.dynamic_index_in_dim(
                micro, jnp.clip(t, 0, n_micro - 1), keepdims=False)
            x_in = jnp.where(sid == 0, src, buf)
            y = run_stage(x_in)
            y = jnp.where(active, y, buf)
            # stage S-1's finished microbatch lands in outs[mid]
            out_mid = jnp.clip(mid, 0, n_micro - 1)
            is_last = sid == n_stages - 1
            upd = jnp.where(active & is_last, y,
                            jax.lax.dynamic_index_in_dim(outs, out_mid,
                                                         keepdims=False))
            outs = jax.lax.dynamic_update_index_in_dim(outs, upd, out_mid, 0)
            # rotate boundary activations to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, outs)

        buf, outs = jax.lax.fori_loop(0, t_total, tick, (buf, outs))
        # only the last stage holds real outputs; broadcast to all stages
        outs = jax.lax.psum(
            jnp.where(sid == n_stages - 1, outs, jnp.zeros_like(outs)), axis)
        return outs.reshape(b, *x_all.shape[1:])

    pp = P(axis, *([None] * (jax.tree.leaves(stacked_params)[0].ndim - 1)))
    pspecs = jax.tree.map(lambda a: P(axis, *([None] * (a.ndim - 1))),
                          stacked_params)
    del pp
    return jax.shard_map(
        stage_fn, mesh=mesh, in_specs=(pspecs, P()), out_specs=P(),
        check_vma=False)(stacked_params, x)
