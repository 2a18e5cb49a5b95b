"""Device time of the target solver's initial placement (Algorithm 1's
argsort; the ops under the `grin.init` named scope of `_grin_block_core`)
per re-solve request, median over the traced window's requests, in ms."""
from bench.program_spans import scoped_ms


def read(ctx):
    return scoped_ms(ctx, "grin.init")
