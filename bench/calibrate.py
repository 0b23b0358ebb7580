"""Readings that the limits of ``correct`` are set from, for one cell.

    python bench/calibrate.py --workload <name> --seeds 1,2,... \
        --control-seeds 1,2,3 [--rehearse]

For each seed it builds the cell as a run does (the generator's
``setup``) and prints the generator's ``calibrate(job, control)``: the
numbers compared after the program's first steps under ``program`` (the
lower readings) and, on the control seeds, the generator's controls and
planted faults under ``control_<name>`` and ``fault_<name>`` (the upper
readings).  One JSON line per seed on stdout.  ``--rehearse`` runs the
cell's tiny sizes on as many CPU devices as the cell asks for chips.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness as H  # noqa: E402
from bench import run_cell  # noqa: E402


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
    if args.rehearse and cell["chips"] > 1:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count"
                                   f"={cell['chips']}")
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
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "device": dev,
                          **gen.calibrate(job, seed in controls)}),
              flush=True)
        del job
    return 0


if __name__ == "__main__":
    sys.exit(main())
