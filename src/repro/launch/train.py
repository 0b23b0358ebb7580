"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --reduced --steps 50 --batch 8 --seq 64 [--resume] [--occl-sync]

Runs the fault-tolerant train loop (fabric/ft.py) on the host mesh with
the synthetic pipeline; full configs train the same way on a real fleet
(the dry-run proves the production-mesh lowering).  ``--occl-sync``
routes DP gradient buckets through the OCCL runtime (paper integration)
with simulated DP ranks.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-period", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--occl-sync", action="store_true")
    ap.add_argument("--dp", type=int, default=2,
                    help="simulated DP ranks for --occl-sync")
    args = ap.parse_args()

    from .compile_cache import enable_compile_cache
    enable_compile_cache()

    from ..configs import get_config
    from ..configs.base import ShapeCell
    from ..data.pipeline import SyntheticPipeline
    from ..fabric.ft import FTConfig, TrainController
    from ..checkpoint.ckpt import latest_step, restore
    from ..train.state import init_state
    from ..train.step import make_train_step

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cell = ShapeCell("cli", args.seq, args.batch, "train")

    state = init_state(cfg)
    n = sum(int(np.prod(p.shape))
            for p in jax.tree_util.tree_leaves(state.params))
    print(f"arch={cfg.name} params={n:,}")

    if args.occl_sync:
        run_occl_dp(cfg, cell, args.steps, dp=args.dp)
        return

    pipe = SyntheticPipeline(cfg, cell).start()
    step_fn = jax.jit(make_train_step(cfg))
    ft = FTConfig(ckpt_dir=args.ckpt_dir, ckpt_period=args.ckpt_period)
    ctrl = TrainController(ft, step_fn, state, pipe)
    if args.resume:
        last = latest_step(args.ckpt_dir)
        if last is not None:
            ctrl.state, extras = restore(args.ckpt_dir, last, state)
            pipe.load_state_dict(extras["pipeline"])
            print(f"resumed from step {last}")
    logs = ctrl.run(args.steps)
    pipe.stop()
    for m in logs[-5:]:
        print(f"step {m['step']:4d} loss {m['loss']:.4f} "
              f"{m['step_time_s']*1e3:7.1f} ms")


def run_occl_dp(cfg, cell, steps: int, dp: int = 2,
                slice_elems: int = 256, burst_slices: int = 1,
                on_step=None) -> dict:
    """Simulated DP training with OCCL gradient sync (paper Sec. 5.3).

    ``dp`` ranks share the one device; each draws its shard of ``cell``'s
    global batch and their gradients are averaged through
    :class:`~repro.train.occl_sync.OcclGradSync` with the given slice
    size and burst width.  ``on_step(step, per_rank, synced)`` sees each
    step's per-rank and synced gradient pytrees before the optimizer
    applies them.  Returns ``{"losses", "supersteps", "sync"}``: the
    rank-mean loss and the supersteps the sync took, per step."""
    from ..data.pipeline import SyntheticPipeline
    from ..train.occl_sync import OcclGradSync
    from ..train.state import init_state
    from ..train.step import make_apply_step, make_grads_step

    assert cell.global_batch % dp == 0
    states = [init_state(cfg) for _ in range(dp)]   # identical seeds
    pipes = [SyntheticPipeline(cfg, cell, shard_id=r, n_shards=dp)
             for r in range(dp)]
    grads_fn = jax.jit(make_grads_step(cfg))
    apply_fn = jax.jit(make_apply_step(cfg))
    gtmpl = jax.eval_shape(lambda: states[0].params)
    sync = OcclGradSync(gtmpl, dp, slice_elems=slice_elems,
                        burst_slices=burst_slices)

    def clock() -> int:
        return int(np.asarray(sync.occl.state.supersteps).max())

    losses, supersteps = [], []
    for step in range(steps):
        t0 = time.time()
        per_rank = []
        step_losses = []
        for r in range(dp):
            loss, g = grads_fn(states[r], next(pipes[r]))
            per_rank.append(g)
            step_losses.append(float(loss))
        before = clock()
        synced = sync.all_reduce(per_rank)
        supersteps.append(clock() - before)
        if on_step is not None:
            on_step(step, per_rank, synced)
        states = [apply_fn(states[r], synced[r]) for r in range(dp)]
        losses.append(float(np.mean(step_losses)))
        print(f"step {step:3d} loss {losses[-1]:.4f} "
              f"{(time.time()-t0)*1e3:7.1f} ms "
              f"(occl supersteps={supersteps[-1]} "
              f"launches={sync.occl.launches})")
    st = sync.stats()
    print("occl grad-sync: supersteps", int(st["supersteps"].max()),
          "preempts", int(st["preempts"].sum()))
    return {"losses": losses, "supersteps": supersteps, "sync": sync}


if __name__ == "__main__":
    main()
