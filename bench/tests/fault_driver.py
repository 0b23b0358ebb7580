"""Drive a cell's run at rehearsal sizes on the CPU with one fault planted
under its timed path, and print whether ``correct`` came out.

    python bench/tests/fault_driver.py <workload> <fault>

Faults: ``state_unchanged`` (a step returns its state as it came),
``half_batch`` (half of the rows left out), ``exchange_left_out`` (the
daemon's exchange between ranks returns each rank's own outbox),
``answer_altered`` (one element of the first output read back is
changed), ``sum_not_mean`` (the gradient sync returns the ranks' sum
instead of their mean).  Prints one JSON line ``{"workload", "fault",
"correct", "error"}``; a run that raises counts as not correct.
"""
from __future__ import annotations

import contextlib
import json
import os
import pathlib
import sys
import traceback
from unittest import mock

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _half_grads(orig):
    def make(cfg):
        fn = orig(cfg)

        def grads_step(state, batch):
            half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
            return fn(state, half)
        return grads_step
    return make


def _altered(orig):
    def read(self, state, keys):
        out = orig(self, state, keys)
        k = next(iter(out))
        out[k] = out[k].copy()
        out[k].flat[0] += 1.0
        return out
    return read


def _summed(orig):
    def all_reduce(self, grads):
        import jax

        return [jax.tree_util.tree_map(lambda x: x * self.n_ranks, g)
                for g in orig(self, grads)]
    return all_reduce


def patches(fault: str) -> list:
    from repro.core import staging
    from repro.train import occl_sync, step

    if fault == "state_unchanged":
        return [mock.patch.object(step, "make_apply_step",
                                  lambda cfg, opt=None: lambda s, g: s)]
    if fault == "half_batch":
        return [mock.patch.object(step, "make_grads_step",
                                  _half_grads(step.make_grads_step))]
    if fault == "exchange_left_out":
        return [mock.patch("repro.core.daemon._sim_exchange",
                           lambda fwd, rev, outbox: outbox)]
    if fault == "answer_altered":
        return [mock.patch.object(staging.StagingEngine, "read",
                                  _altered(staging.StagingEngine.read))]
    if fault == "sum_not_mean":
        return [mock.patch.object(occl_sync.OcclGradSync, "all_reduce",
                                  _summed(occl_sync.OcclGradSync.all_reduce))]
    raise KeyError(fault)


def main(workload: str, fault: str) -> int:
    from bench import run_cell

    args = run_cell.parse(["--workload", workload, "--rehearse",
                           "--seconds", "1"])
    _, cell, _, _ = run_cell.prepare(args)
    # The virtual devices are fixed before JAX is first imported.
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               f"{cell['chips']}")
    correct, error = None, None
    try:
        with contextlib.ExitStack() as stack:
            for p in patches(fault):
                stack.enter_context(p)
            correct = run_cell.run(args) == 0
    except Exception as e:  # a fault that crashes the run is caught too
        traceback.print_exc()
        correct, error = False, repr(e)[:300]
    print(json.dumps({"workload": workload, "fault": fault,
                      "correct": correct, "error": error}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
