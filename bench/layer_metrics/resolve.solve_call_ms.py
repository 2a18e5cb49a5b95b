"""Host time of a re-solve request's solver call: the dispatch of the
jitted solver (`repro.grin.dispatch`) and the fetch of its results, the
wait and the copies back (`repro.grid.fetch`); the spans' time not covered
by device activity, median over the traced window's requests, in ms."""
from bench.program_spans import host_ms


def read(ctx):
    return host_ms(ctx, ("repro.grin.dispatch", "repro.grid.fetch"))
