"""Multi-host bootstrap for real pods (the non-dry-run path).

On a TPU pod slice each host runs this module; JAX's distributed runtime
wires the hosts into one device fabric and the SAME pjit/shard_map code
from the dry-run executes unchanged (the dry-run's 512 host-platform
devices stand in for exactly this topology).

    # per host (or via the scheduler's env):
    COORDINATOR=10.0.0.1:8476 NPROC=64 PID=$SLURM_PROCID \
        python -m repro.launch.bootstrap --arch gemma2-2b --steps 1000

Fault tolerance at this layer:
  - checkpoint auto-resume (launch.train) makes SIGTERM/preemption safe,
  - a restarted job with a different host count re-partitions the data
    stream deterministically (data.ShardedLoader) and re-shards the
    checkpoint onto the new mesh (checkpoint.manager restore shardings),
  - straggler mitigation: the scheduler can re-assign a dead host's data
    shard via ShardedLoader.reassign before restart.
"""
from __future__ import annotations

import argparse
import os


def initialize_from_env() -> tuple[int, int]:
    """jax.distributed.initialize from COORDINATOR/NPROC/PID env vars.
    No-op for single-process runs. Returns (process_id, n_processes)."""
    import jax
    coord = os.environ.get("COORDINATOR")
    nproc = int(os.environ.get("NPROC", "1"))
    pid = int(os.environ.get("PID", "0"))
    if coord and nproc > 1:
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=nproc, process_id=pid)
    return pid, nproc


def main() -> None:
    pid, nproc = initialize_from_env()

    import jax
    from repro.configs import get_config
    from repro.data import ShardedLoader
    from repro.launch.mesh import make_production_mesh
    from repro.launch.train import train

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=1000)
    ap.add_argument("--global-batch", type=int, default=256)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default="cosine")
    ap.add_argument("--ckpt", default="ckpts")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    loader = ShardedLoader(cfg.vocab_size, args.global_batch, args.seq,
                           host_index=pid, n_hosts=nproc)
    if pid == 0:
        print(f"[bootstrap] {args.arch} on {mesh.shape} "
              f"({len(jax.devices())} devices, {nproc} hosts)")
    with jax.set_mesh(mesh):
        train(cfg, steps=args.steps, global_batch=args.global_batch,
              seq=args.seq, peak_lr=args.lr, schedule_name=args.schedule,
              ckpt_dir=args.ckpt, loader=loader,
              log_fn=(print if pid == 0 else lambda s: None))


if __name__ == "__main__":
    main()
