"""Shared plumbing of the chip benchmark: the manifest, the compile cache,
the device check, host spans, metric readers and the result line.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration (``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<traffic>.json``, whose ``kind`` names the generator
``bench/gens/<kind>.py``) and its metrics (``bench/metrics/<metric>.py``,
each with ``read(ctx) -> float | None``).  A later cell, mix or metric is
new files plus new entries; no file here changes.

A generator of a new kind supplies ``setup(ctx) -> job``,
``window(job, seconds=, units=)``, ``counters(job, win)``, ``work(job)``,
``check(job) -> (checks, info)`` and ``calibrate(job, control) -> dict``:
the numbers compared under ``program`` and, with ``control``, each of its
controls under ``control_<name>`` and each planted fault under
``fault_<name>``, every entry a dict of the numbers that ``check``
compares.  ``bench/calibrate.py`` prints what it returns, and the control
test asks that the program stays inside every limit while a ``control_*``
entry exceeds one.  A cell on four chips rehearses and calibrates on four
CPU devices.  A cell is appended to the ``workloads`` list of each
end-to-end metric it reports that lists its cells (today
``train_tokens_per_s``).
"""
from __future__ import annotations

import collections
import contextlib
import importlib.util
import json
import os
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
MANIFEST = ROOT / "BENCHMARK.json"
# The persistent compile cache lives at a fixed path in the checkout unless
# JAX_COMPILATION_CACHE_DIR names another: the path is part of the key.
CHECKOUT_CACHE = ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ----------------------------------------------------------------------
# manifest and data files
# ----------------------------------------------------------------------
def load_manifest(path: pathlib.Path = MANIFEST) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_entry(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in {MANIFEST.name}; have "
                   f"{[w['name'] for w in manifest['workloads']]}")


def config_entry(manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config {name!r} in {MANIFEST.name}")


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def traffic_path(name: str) -> pathlib.Path:
    return BENCH / "traffic" / f"{name}.json"


def load_module(path: pathlib.Path, label: str):
    """Import a file by path (metric files carry dots in their names)."""
    spec = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[label] = mod
    spec.loader.exec_module(mod)
    return mod


def generator(kind: str):
    return load_module(BENCH / "gens" / f"{kind}.py", f"bench_gen_{kind}")


def metric_reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_"))


def cell_metrics(manifest: dict, cell: str, group: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in manifest[group]
            if cell in m.get("workloads", [cell])]


def seeds(seed: int, n: int = 4):
    """``n`` 32-bit words from an arbitrary whole-number seed."""
    import numpy as np
    return np.random.SeedSequence(int(seed)).generate_state(n, np.uint32)


# ----------------------------------------------------------------------
# compile cache, compile clock, device
# ----------------------------------------------------------------------
def enable_compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileClock:
    """Backend-compile seconds and persistent-cache hits and misses, from
    JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.secs = 0.0
        self.events = collections.Counter()
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.secs += secs

    def _event(self, name, **_):
        self.events[name] += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.secs,
                "hits": self.events["/jax/compilation_cache/cache_hits"],
                "misses": self.events["/jax/compilation_cache/cache_misses"]}


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest device (0 where unreported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


# ----------------------------------------------------------------------
# host spans
# ----------------------------------------------------------------------
class Spans:
    """Host-clock spans around the calls into each layer.  Each span also
    enters the profiler's trace as ``bench.<name>`` so idle gaps on the
    device can be attributed to what the host was doing."""

    def __init__(self):
        self.records: list = []         # (name, t0_s, t1_s)

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield
        self.records.append((name, t0, time.perf_counter()))

    def total(self, name: str, since: float = 0.0) -> float:
        return sum(t1 - t0 for n, t0, t1 in self.records
                   if n == name and t0 >= since)


# ----------------------------------------------------------------------
# the result line
# ----------------------------------------------------------------------
def metric_values(entries: list, ctx: dict) -> dict:
    """Run each metric's reader; a reader that finds nothing to read
    returns None and the metric is left out of the line."""
    out = {}
    for m in entries:
        v = metric_reader(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def print_checks(checks: list) -> None:
    """Each number compared, beside its limit: the last lines on stderr."""
    for c in checks:
        log(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['ok'] else 'FAILED'}")


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: list,
                breakdown: dict | None = None) -> str:
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in checks}
    return json.dumps(line)
