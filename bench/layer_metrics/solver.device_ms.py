"""Device time of the target solver's program (the jitted block-move GrIn
loop, module `_grin_block_core`) per re-solve request, in ms."""
from bench.trace_reduce import module_ns


def read(ctx):
    ns = module_ns(ctx["trace"], "grin_block_core")
    n = sum(1 for name, _, _ in ctx["trace"]["spans"]
            if name == "bench.request")
    return ns / n * 1e-6 if ns is not None and n else None
