"""Host time of a re-solve request's energy pricing (`repro.price.energy`:
guard correction, DVFS scaling, the eager energy call and its fetch), not
covered by device activity, median over the traced window's requests, in
ms."""
from bench.program_spans import host_ms


def read(ctx):
    return host_ms(ctx, ("repro.price.energy",))
