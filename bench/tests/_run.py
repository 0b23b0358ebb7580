"""Subprocess helpers of the benchmark's CPU tests: each run gets a
process of its own (a JAX backend, a device count, the program's
module-level caches)."""
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]


def run(args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable] + [str(a) for a in args],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=timeout)


def all_cells() -> list:
    """(cell, traffic) of every cell of the benchmark."""
    import json

    return [(w["name"], w["traffic"]) for w in
            json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
