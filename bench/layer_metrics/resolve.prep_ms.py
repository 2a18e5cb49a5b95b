"""Host time of a re-solve request's preparation: guard matrices and mixes
(`repro.price.guard`), the repeat and tile to the (G*M, k, l) batch
(`repro.grid.batch`), and the solver's casts, transfers, power matrix and
static arguments (`repro.grin.prep`); the spans' time not covered by device
activity, median over the traced window's requests, in ms."""
from bench.program_spans import host_ms


def read(ctx):
    return host_ms(ctx, ("repro.price.guard", "repro.grid.batch",
                         "repro.grin.prep"))
