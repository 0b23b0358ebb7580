"""Shared arithmetic of the metric readers.  Each reader takes the run's
context (``ctx``: the window, the program's counters, the work computed
from shapes, the trace summary, the peaks) and returns a number, or None
where it finds nothing to read."""
from __future__ import annotations

# The staging engine's jitted plans: the write plan and the read plan are
# both ``fn``, the mesh backend's sharded write ``sharded_fn``.
STAGING_MODULES = ("fn", "sharded_fn")
DAEMON_MODULE = "daemon"


def module_s(ctx, names) -> float | None:
    tr = ctx["trace"]
    if tr is None:
        return None
    found = [tr["module_s"][m] for m in names if m in tr["module_s"]]
    return sum(found) if found else None


def per_unit(ctx, value):
    return None if value is None else value / ctx["win"]["units"]


def staging_s_per_unit(ctx):
    return per_unit(ctx, module_s(ctx, STAGING_MODULES))


def daemon_s_per_unit(ctx):
    return per_unit(ctx, module_s(ctx, (DAEMON_MODULE,)))


def idle_share(ctx):
    tr = ctx["trace"]
    if tr is None or tr["devices"] == 0 or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def unit_s(ctx):
    return ctx["win"]["window_s"] / ctx["win"]["units"]


def staging_roofline(ctx):
    """Least time for the staging layer's logical bytes at the HBM peak,
    over its measured device time, in percent."""
    t = staging_s_per_unit(ctx)
    if not t or ctx["peaks"] is None:
        return None
    least = ctx["work"]["staging_bytes_per_unit"] / \
        ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / t
