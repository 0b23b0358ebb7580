"""Reduction of a profiler trace to the numbers the metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into plain
events ``(plane, line, name, start_ns, dur_ns)``; ``summarize`` reduces
them over the traced window (the host span ``bench.window``):

* busy: the union of the intervals in which an operation ran on a device
  (line ``XLA Ops``), clipped to the window; idle share = 1 - busy/window;
* device time per XLA module (line ``XLA Modules``, name without the
  ``jit_`` prefix and the ``(id)`` suffix) and per op within each module;
* idle gaps, each attributed to the innermost ``bench.<span>`` the host
  was in at the gap's midpoint.

Device numbers are means over the device planes (``/device:...``).
"""
from __future__ import annotations

import bisect
import pathlib
import re

WINDOW_SPAN = "bench.window"


def load(trace_dir) -> list:
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    return [(plane.name, line.name, ev.name, float(ev.start_ns),
             float(ev.duration_ns))
            for plane in pd.planes for line in plane.lines
            for ev in line.events]


def module_name(name: str) -> str:
    name = re.sub(r"\(.*\)$", "", name.strip())
    return name[4:] if name.startswith("jit_") else name


def op_name(name: str) -> str:
    """The op's HLO name: a TPU trace names each op by its whole HLO
    instruction (``fusion.3 = f32[...] fusion(...)``)."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def union(intervals) -> list:
    """Merged, sorted ``[start, end]`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(busy, lo, hi) -> list:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _innermost(spans, t) -> str:
    best = None
    for name, s, e in spans:
        if s <= t < e and name != WINDOW_SPAN and (
                best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0].removeprefix("bench.") if best else "none"


def summarize(events, top: int = 10) -> dict:
    host, by_dev = [], {}
    for p, ln, n, s, d in events:
        if p.startswith("/device:"):
            by_dev.setdefault(p, {}).setdefault(ln, []).append((s, s + d, n))
        elif n.startswith("bench."):
            host.append((n, s, s + d))
    wins = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    lo, hi = min(s for s, _ in wins), max(e for _, e in wins)
    devices = sorted(p for p, lines in by_dev.items() if "XLA Ops" in lines)
    nd = max(len(devices), 1)
    busy_ns, module_ns, module_op_ns, op_ns = 0.0, {}, {}, {}
    idle = []
    for dev in devices:
        ops = clip_named([(s, e, op_name(n))
                          for s, e, n in by_dev[dev]["XLA Ops"]], lo, hi)
        mods = sorted(clip_named([(s, e, module_name(n)) for s, e, n
                                  in by_dev[dev].get("XLA Modules", [])],
                                 lo, hi))
        busy = union([(s, e) for s, e, _ in ops])
        busy_ns += sum(e - s for s, e in busy)
        for s, e, m in mods:
            module_ns[m] = module_ns.get(m, 0.0) + (e - s)
        starts = [s for s, _, _ in mods]
        for s, e, n in ops:
            i = bisect.bisect_right(starts, s) - 1
            m = mods[i][2] if i >= 0 and s < mods[i][1] else "none"
            per = module_op_ns.setdefault(m, {})
            per[n] = per.get(n, 0.0) + (e - s)
            op_ns[f"{m}/{n}"] = op_ns.get(f"{m}/{n}", 0.0) + (e - s)
        if dev == devices[0]:
            longest = sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])
            idle = [[_innermost(host, (s + e) / 2), (e - s) * 1e-9]
                    for s, e in longest[:top]]
    sec = 1e-9 / nd
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * sec,
        "devices": len(devices),
        "module_s": {m: v * sec for m, v in module_ns.items()},
        "module_op_s": {m: {n: v * sec for n, v in per.items()}
                        for m, per in module_op_ns.items()},
        "top_ops": [[n, v * sec] for n, v in top_ops],
        "idle_gaps": idle,
    }


def clip_named(items, lo, hi) -> list:
    return [(max(s, lo), min(e, hi), n) for s, e, n in items
            if e > lo and s < hi]
