"""Share of the solver loop's lane steps that move, in %: the mean over the
batch's lanes of the block moves made over the most any lane made (the
`lanes`, `moves_mean` and `moves_max` counters of `repro.grid.fetch`), median
over the traced window's requests. The loop runs as deep as its slowest
lane, so the rest of the lanes idle."""
from bench.program_spans import counter_median


def _share(cs):
    if not cs or not all({"lanes", "moves_mean", "moves_max"} <= set(c)
                         for c in cs):
        return None
    most = sum(c["lanes"] * c["moves_max"] for c in cs)
    return 100.0 * sum(c["lanes"] * c["moves_mean"] for c in cs) / most \
        if most else None


def read(ctx):
    return counter_median(ctx, "repro.grid.fetch", _share)
