"""Generator of the ``probe`` kind, a fixture of ``test_bench_new_kind.py``.

One unit of work is one f32 ``psum`` of ``elements`` values over every
device of the cell.  ``check`` compares the last sum of the window with
numpy's float64 sum of the same inputs; ``calibrate``'s control is the
same ``psum`` in bf16, its fault each device keeping its own shard.
"""
from __future__ import annotations

import dataclasses
import sys
import time

import numpy as np


@dataclasses.dataclass
class Job:
    ctx: dict
    x: np.ndarray
    sum_fn: object
    out: np.ndarray | None = None


def setup(ctx: dict) -> Job:
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    chips = ctx["chips"]
    devices = jax.devices()[:chips]
    if len(devices) != chips:
        raise RuntimeError(f"probe: needs {chips} devices, sees "
                           f"{len(jax.devices())}")
    rng = np.random.default_rng([int(w) for w in ctx["seed_words"]])
    x = rng.standard_normal((chips, ctx["config"]["elements"]),
                            np.float32)
    mesh = Mesh(np.array(devices), ("d",))
    job = Job(ctx=ctx, x=x, sum_fn=jax.jit(jax.shard_map(
        lambda a: jax.lax.psum(a, "d"), mesh=mesh, in_specs=P("d"),
        out_specs=P())))
    job.out = np.asarray(job.sum_fn(x))           # the warm-up compiles
    return job


def window(job: Job, seconds: float | None = None,
           units: int | None = None) -> dict:
    """Whole sums until ``seconds`` have passed (or ``units`` sums)."""
    spans = job.ctx["spans"]
    t0 = time.perf_counter()
    unit_s, failed = [], 0
    while True:
        t = time.perf_counter()
        try:
            with spans("psum"):
                job.out = np.asarray(job.sum_fn(job.x))
        except Exception as e:             # a failed sum ends the window
            failed = 1
            print(f"sum failed: {e!r}", file=sys.stderr)
            break
        unit_s.append(time.perf_counter() - t)
        if (units is not None and len(unit_s) >= units) or (
                units is None and time.perf_counter() - t0 >= seconds):
            break
    t1 = time.perf_counter()
    return {"units": len(unit_s), "failed": failed, "t0": t0, "t1": t1,
            "window_s": t1 - t0, "unit_s": unit_s}


def counters(job: Job, win: dict) -> dict:
    return {}


def work(job: Job) -> dict:
    return {}


def _sum_err(out, x) -> float:
    ref = x.astype(np.float64).sum(axis=0)
    return float(np.max(np.abs(np.asarray(out, np.float64).reshape(-1)
                               - ref)) / np.max(np.abs(ref)))


def _checks(errs: dict, limits: dict) -> list:
    return [{"name": k, "value": v, "limit": limits[k],
             "ok": bool(v <= limits[k])} for k, v in errs.items()]


def check(job: Job) -> tuple:
    return _checks({"sum_err": _sum_err(job.out, job.x)},
                   job.ctx["traffic"]["limits"]), None


def calibrate(job: Job, control: bool) -> dict:
    import jax.numpy as jnp

    out = {"program": {"sum_err": _sum_err(job.sum_fn(job.x), job.x)}}
    if control:
        low = job.sum_fn(jnp.asarray(job.x, jnp.bfloat16))
        out["control_bf16"] = {"sum_err": _sum_err(
            np.asarray(low, np.float32), job.x)}
        out["fault_own_shard"] = {"sum_err": _sum_err(job.x[0], job.x)}
    return out
