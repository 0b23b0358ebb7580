"""Readers of the program's own host spans, ``occl.<phase>``
(``src/repro/core/trace.py``), in the profiler trace of a ``--trace 1``
run.  The trace is loaded from ``.bench_trace`` once per process and
reduced over the traced window (``bench.window``):

* seconds in each phase span (``pack``, ``submit``, ``flush``,
  ``launch``, ``read``, ``unpack``), clipped to the window;
* ``occl.plan_build`` events that start inside the window;
* the share of the first device's idle time whose gap midpoint lies
  inside a phase span (the attribution rule of ``bench/trace.py``).

Every reader returns None where the run was not traced or its trace holds
no ``occl.*`` span (a program without them)."""
from __future__ import annotations

import bisect
import functools
import pathlib

from bench import trace as T

TRACE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".bench_trace"
PHASES = ("pack", "submit", "flush", "launch", "read", "unpack")


def _load() -> list:
    return T.load(TRACE_DIR)


@functools.lru_cache(maxsize=1)
def reduced() -> dict | None:
    return reduce(_load())


def reduce(events) -> dict | None:
    host, ops = [], {}
    for p, ln, n, s, d in events:
        if p.startswith("/device:"):
            if ln == "XLA Ops":
                ops.setdefault(p, []).append((s, s + d, n))
        elif n.startswith("occl.") or n == T.WINDOW_SPAN:
            host.append((s, s + d, n))
    wins = [(s, e) for s, e, n in host if n == T.WINDOW_SPAN]
    occl = [h for h in host if h[2] != T.WINDOW_SPAN]
    if not wins or not occl:
        return None
    lo, hi = min(s for s, _ in wins), max(e for _, e in wins)
    inside = T.clip_named(occl, lo, hi)
    out = {p: 1e-9 * sum(e - s for s, e, n in inside if n == f"occl.{p}")
           for p in PHASES}
    out["plan_builds"] = sum(1 for s, _, n in occl
                             if n == "occl.plan_build" and lo <= s < hi)
    out["idle_spanned_share"] = None
    if ops:
        phases = sorted((s, e) for s, e, n in inside
                        if n.removeprefix("occl.") in PHASES)
        starts = [s for s, _ in phases]
        busy = T.union((s, e) for s, e, _ in
                       T.clip_named(ops[sorted(ops)[0]], lo, hi))
        idle = [(s, e) for s, e in T.gaps(busy, lo, hi) if e > s]
        total = sum(e - s for s, e in idle)
        if total > 0:
            spanned = 0.0
            for s, e in idle:
                i = bisect.bisect_right(starts, (s + e) / 2) - 1
                if i >= 0 and (s + e) / 2 < phases[i][1]:
                    spanned += e - s
            out["idle_spanned_share"] = 100.0 * spanned / total
    return out


def value(ctx, key: str):
    """``key`` of the reduction, or None (untraced run, no spans)."""
    if ctx["trace"] is None:
        return None
    r = reduced()
    return None if r is None else r[key]


def per_step(ctx, key: str):
    v = value(ctx, key)
    return None if v is None else v / ctx["win"]["units"]
