"""``launch.train.run_occl_dp``: the DP grad-sync training loop returns
what its caller checks, and its sync heap is sized from the registrations."""
import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import ShapeCell
from repro.launch.train import run_occl_dp
from repro.train.occl_sync import static_all_reduce


def test_run_occl_dp_returns_losses_supersteps_and_synced_grads():
    cfg = get_config("qwen3-0.6b").reduced()
    cell = ShapeCell("t", 8, 2, "train")
    seen = []

    def on_step(step, per_rank, synced):
        want = static_all_reduce(per_rank)
        for r in range(2):
            for a, b in zip(jax.tree_util.tree_leaves(synced[r]),
                            jax.tree_util.tree_leaves(want[r])):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-6)
        seen.append(step)

    out = run_occl_dp(cfg, cell, 2, dp=2, slice_elems=64, burst_slices=4,
                      on_step=on_step)
    assert seen == [0, 1]
    assert len(out["losses"]) == 2 and np.isfinite(out["losses"]).all()
    # The scheduler is deterministic: every step's sync takes the same
    # number of supersteps.
    assert out["supersteps"][0] > 0
    assert out["supersteps"][0] == out["supersteps"][1]
    occl = out["sync"].occl
    assert occl.cfg.heap_elems == max(occl._in_ptr, occl._out_ptr)
