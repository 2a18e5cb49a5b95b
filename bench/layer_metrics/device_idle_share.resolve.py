"""Share of the traced re-solve window in which no operation ran on the
device, in %."""
from bench.trace_reduce import idle_share_pct


def read(ctx):
    return idle_share_pct(ctx["trace"])
