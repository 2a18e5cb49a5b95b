"""Gain scoring's share of its roofline, in %: the least time the chip
needs for the window's scoring work (operations and bytes counted from
shapes and the solver's move counts by `bench/work.py`, bound by the
larger of compute and HBM time at the peaks of `bench/peaks.json`) over
the solver program's device time. The solver program does the scoring and
its bookkeeping, so the share is of the program that holds the kernel,
whichever implementation scores."""
from bench import work
from bench.trace_reduce import module_ns


def read(ctx):
    ns = module_ns(ctx["trace"], "grin_block_core")
    w = ctx["work"]
    if not ns or not w or not w["ops"]:
        return None
    t, _ = work.least_seconds(w["ops"], w["bytes"], ctx["peaks"])
    return 100.0 * t / (ns * 1e-9)
