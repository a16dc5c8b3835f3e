"""Training driver: sharded train step + fault-tolerant loop.

``make_train_step`` builds the jit'd (params, opt, batch, step) → (params,
opt, metrics) update with in/out shardings from distributed.sharding and
donated state buffers. ``train`` is the loop: auto-resume from the newest
checkpoint, periodic atomic saves, deterministic host-sharded data, and a
straggler hook (see data.ShardedLoader.reassign).
"""
from __future__ import annotations

import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs.base import ModelConfig
from repro.data import ShardedLoader
from repro.distributed.api import mesh_axes
from repro.distributed.sharding import batch_specs, param_specs, zero1_specs
from repro.launch.specs import input_specs, param_shapes
from repro.models import init_params, loss_fn
from repro.optim import adamw_init, adamw_update, get_schedule


def make_train_step(cfg: ModelConfig, *, schedule: Callable,
                    zero1: bool = True, remat: bool = True,
                    weight_decay: float = 0.1, donate: bool = True):
    """jit'd sharded train step. Call under `jax.set_mesh(mesh)`."""
    def step_fn(params, opt, batch, step):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, batch, remat=remat),
            has_aux=True)(params)
        params, opt, om = adamw_update(
            grads, opt, params, lr=schedule(step), zero1=zero1,
            weight_decay=weight_decay)
        metrics = dict(metrics, **om, lr=schedule(step))
        return params, opt, metrics

    meshed = bool(mesh_axes())
    shapes = param_shapes(cfg)
    pspecs = param_specs(shapes) if meshed else None
    if meshed:
        zspecs = (zero1_specs(shapes, pspecs) if zero1 else pspecs)
        ospecs = {"mu": zspecs, "nu": zspecs, "count": P()}
    else:
        ospecs = None

    def shardings_for(batch_shapes):
        if not meshed:
            return jax.jit(step_fn,  # nbl: disable=jit-discipline -- step_fn closes over this run's schedule/loss config; one wrapper per make_train_step
                           donate_argnums=(0, 1) if donate else ())
        bspecs = batch_specs(batch_shapes)
        return jax.jit(  # nbl: disable=jit-discipline -- sharded: shardings captured from the ambient mesh, per-run by design
            step_fn,
            in_shardings=(pspecs, ospecs, bspecs, P()),
            out_shardings=(pspecs, ospecs, None),
            donate_argnums=(0, 1) if donate else ())
    return step_fn, shardings_for, pspecs, ospecs


def init_state(cfg: ModelConfig, seed: int = 0, *, zero1: bool = True,
               use_specs: bool = True):
    """Sharded init (params materialize directly into their shards)."""
    meshed = bool(mesh_axes())
    shapes = param_shapes(cfg)
    pspecs = param_specs(shapes) if (use_specs and meshed) else None
    zspecs = None
    if pspecs is not None:
        zspecs = zero1_specs(shapes, pspecs) if zero1 else pspecs

    @jax.jit  # nbl: disable=jit-discipline -- init runs once per state; closes over this call's sharding specs
    def _init(key):
        p = init_params(key, cfg)
        opt = adamw_init(p)
        if pspecs is not None:
            p = jax.tree.map(jax.lax.with_sharding_constraint, p, pspecs)
            opt = {"mu": jax.tree.map(jax.lax.with_sharding_constraint,
                                      opt["mu"], zspecs),
                   "nu": jax.tree.map(jax.lax.with_sharding_constraint,
                                      opt["nu"], zspecs),
                   "count": opt["count"]}
        return p, opt

    return _init(jax.random.PRNGKey(seed))


def train(cfg: ModelConfig, *, steps: int, global_batch: int, seq: int,
          peak_lr: float = 3e-3, warmup: int = 20,
          schedule_name: str = "cosine", ckpt_dir: Optional[str] = None,
          ckpt_every: int = 50, seed: int = 0, log_every: int = 10,
          loader: Optional[ShardedLoader] = None,
          log_fn: Callable[[str], None] = print) -> dict:
    """End-to-end loop (works un-meshed on CPU and under a production mesh)."""
    sched = get_schedule(schedule_name, peak_lr, warmup, steps)
    _, shardings_for, pspecs, ospecs = make_train_step(
        cfg, schedule=sched)
    params, opt = init_state(cfg, seed)
    loader = loader or ShardedLoader(cfg.vocab_size, global_batch, seq,
                                     seed=seed)

    start = 0
    mgr = None
    if ckpt_dir:
        mgr = CheckpointManager(ckpt_dir)
        latest = mgr.latest_step()
        if latest is not None:
            state = mgr.restore(latest, {"params": params, "opt": opt})
            params, opt = state["params"], state["opt"]
            start = latest
            log_fn(f"[train] resumed from step {start}")

    step_jit = None
    hist = []
    t0 = time.time()
    for i in range(start, steps):
        batch = loader.batch(i)
        if step_jit is None:
            step_jit = shardings_for(jax.eval_shape(lambda: jax.tree.map(
                lambda a: jnp.asarray(a), batch)))
        params, opt, m = step_jit(params, opt, batch, i)
        if i % log_every == 0 or i == steps - 1:
            loss = float(m["loss"])
            hist.append((i, loss))
            log_fn(f"[train] step {i:5d} loss {loss:.4f} "
                   f"lr {float(m['lr']):.2e} gn {float(m['grad_norm']):.2f}")
        if mgr and (i + 1) % ckpt_every == 0:
            mgr.save(i + 1, {"params": params, "opt": opt})
    if mgr:
        mgr.save(steps, {"params": params, "opt": opt})
    return {"params": params, "opt": opt, "history": hist,
            "wall_s": time.time() - t0}
