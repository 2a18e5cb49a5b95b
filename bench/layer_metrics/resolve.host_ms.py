"""Host share of a re-solve request: the request's wall time not covered by
device activity (guard construction, transfers, placement repair, energy
pricing on the host), median over the traced window's requests, in ms."""
from bench.trace_reduce import host_ms_per_request


def read(ctx):
    return host_ms_per_request(ctx["trace"])
