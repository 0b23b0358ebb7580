"""Whole step's share of the chip's bf16 peak: the operations forward and
backward require (bench/work.py) per step, over step time and peak."""
from bench.metrics._lib import unit_s


def read(ctx):
    if ctx["peaks"] is None or "flops_per_unit" not in ctx["work"]:
        return None
    return 100.0 * ctx["work"]["flops_per_unit"] / (
        unit_s(ctx) * ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"])
