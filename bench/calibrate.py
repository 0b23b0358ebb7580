"""Readings that the limits of ``correct`` are set from, for one cell.

    python bench/calibrate.py --workload <name> --seeds 1,2,... \
        --control-seeds 1,2,3 [--rehearse]

For each seed it builds the cell as a run does and reads the numbers
compared after the program's first steps: the lower readings.  On the
control seeds it also puts the reference computed one precision lower in
the program's place (fp8 products for bf16 training), and the faults
planted in the reference (half the batch left out, which on two ranks is
also each rank keeping its own gradient; one token altered where the
rows are made; the ranks' gradients summed, not averaged; the state left
unchanged): the upper readings.  One JSON line per seed on stdout.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness as H  # noqa: E402
from bench import run_cell  # noqa: E402


def values(checks) -> dict:
    return {c["name"]: c["value"] for c in checks}


def train_seed(gen, job, control: bool) -> dict:
    import numpy as np

    lim = job.tr["limits"]
    gen.release(job)
    ref = gen.reference(job)
    out = {"program": values(gen.compare(gen.readings(job), ref, lim)),
           "reference_first_grad_norm": ref["first_gnorm"]}
    if control:
        def as_prog(r):
            return {"losses": r["losses"], "first_grad": [r["first_grad"]],
                    "change": [r["change"]]}
        out["control_fp8"] = values(gen.compare(
            as_prog(gen.reference(job, fp8=True)), ref, lim))
        half = [(t[:t.shape[0] // 2], g[:g.shape[0] // 2])
                for t, g in job.batches]
        out["fault_half_batch"] = values(gen.compare(
            as_prog(gen.reference(job, batches=half)), ref, lim))
        altered = [(t.copy(), g) for t, g in job.batches]
        for t, _ in altered:
            t[0, 0] = (t[0, 0] + 1) % job.dims["vocab_size"]
        out["fault_token"] = values(gen.compare(
            as_prog(gen.reference(job, batches=altered)), ref, lim))
        # A sync that sums the ranks' gradients instead of averaging
        # them: the optimizer gets dp times the gradient; its clipping
        # and Adam's normalisation leave the losses and the change.
        out["fault_sum_not_mean"] = values(gen.compare(
            {"losses": ref["losses"],
             "first_grad": [job.tr["dp"] * ref["first_grad"]],
             "change": [ref["change"]]}, ref, lim))
        out["fault_state_unchanged"] = values(gen.compare(
            {"losses": np.repeat(ref["losses"][:1], len(ref["losses"])),
             "first_grad": [ref["first_grad"]],
             "change": [np.zeros_like(ref["change"])]}, ref, lim))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    rargs = run_cell.parse(["--workload", args.workload] +
                           (["--rehearse"] if args.rehearse else []))
    manifest, cell, config, traffic = run_cell.prepare(rargs)
    if not args.rehearse:
        H.enable_compile_cache()
    import jax

    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    dev = H.device_info()
    if not args.rehearse and (dev["platform"] != "tpu"
                              or dev["count"] < cell["chips"]):
        H.log(f"calibrate: needs {cell['chips']} TPU chip(s), found {dev}")
        return 2
    gen = H.generator(traffic["kind"])
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        ctx = {"config": config, "traffic": traffic, "seed": seed,
               "seed_words": H.seeds(seed), "rehearse": args.rehearse,
               "spans": H.Spans(), "chips": cell["chips"]}
        job = gen.setup(ctx)
        out = train_seed(gen, job, seed in controls)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "device": dev, **out}), flush=True)
        del job
    return 0


if __name__ == "__main__":
    sys.exit(main())
