"""Rows a re-solve request re-rounds by largest remainder (the `rows`
counter of `repro.grid.repair`), median over the traced window's
requests."""
from bench.program_spans import counter_median


def read(ctx):
    return counter_median(ctx, "repro.grid.repair", lambda cs: (
        sum(c["rows"] for c in cs) if all("rows" in c for c in cs)
        else None))
