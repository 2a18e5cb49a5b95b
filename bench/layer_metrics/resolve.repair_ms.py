"""Host time of a re-solve request's placement repair (`repro.grid.repair`:
rounding with exact row sums), not covered by device activity, median over
the traced window's requests, in ms."""
from bench.program_spans import host_ms


def read(ctx):
    return host_ms(ctx, ("repro.grid.repair",))
