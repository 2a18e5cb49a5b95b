"""Reduce the program's own spans and named scopes from a traced run's xplane.

The program marks its host phases with `repro.obs.profile.span`, named
`repro.<layer>.<phase>`, with counters as the span's arguments; they land in
the same xplane as the benchmark's `bench.*` annotations and the device ops,
on one clock. `bench/trace_reduce.py` reads only the `bench.*` spans; this
module reads the rest, from the trace that `bench/run.py` leaves in
`bench/traces/<cell>/`:

  * `window`: the `bench.window` span as (start, end), None without one;
  * `spans`: every `repro.*` host span as (name, start, end, counters),
    sorted by start;
  * `scoped`: per named scope of the solver (`grin.init`, `grin.loop`,
    `grin.final`), the union of its op intervals on the first device (as
    `trace_reduce` takes it). A TPU trace keeps each op's `op_name`
    metadata as the `tf_op` stat of the op's event metadata, which
    `ProfileData` does not show, so `op_scopes` reads it from the
    serialized trace itself. A CPU trace names no scope.

The per-request readers take the window's `bench.request` spans and the
device-covered time from `trace_reduce`'s result (`ctx["trace"]`). A program
without such spans or scopes (an older commit) gives empty results, and the
readers then give None.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

import numpy as np

from bench.trace_reduce import _covered, _stats, _union

BENCH = os.path.dirname(os.path.abspath(__file__))
TRACE_ROOT = os.path.join(BENCH, "traces")
_SCOPE = re.compile(r"(?:^|/)(grin\.(?:init|loop|final))(?:/|$)")
_EMPTY = {"window": None, "spans": [], "scoped": {}}


def of(ctx: dict) -> dict:
    """The reduced program spans of the run `ctx` describes: read once from
    `bench/traces/<cell>/` and kept in `ctx` for the next metric. A trace
    whose `bench.window` is not the one `ctx["trace"]` was reduced from
    (left by another run) counts as empty."""
    if "program_spans" not in ctx:
        red = reduce_dir(os.path.join(TRACE_ROOT, ctx["cell"]["name"]))
        win = [(s, e) for name, s, e in ctx["trace"]["spans"]
               if name == "bench.window"]
        ctx["program_spans"] = red if red["window"] in win else _EMPTY
    return ctx["program_spans"]


def reduce_dir(trace_dir: str) -> dict:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return _EMPTY
    with open(paths[-1], "rb") as f:
        data = f.read()
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_serialized_xspace(data),
                          op_scopes(data))


def _varint(buf, i: int) -> tuple[int, int]:
    val = shift = 0
    while True:
        byte = buf[i]
        i += 1
        val |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return val, i


def _fields(buf):
    """(field number, value) of each field of a serialized protobuf
    message: an int for a varint, a memoryview for anything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, val


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def op_scopes(data: bytes) -> dict:
    """{op event name: solver scope} over the device planes of a serialized
    XSpace, from each op's `tf_op` stat. Fields read (tsl `xplane.proto`):
    XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
    .stat_metadata = 5; XEventMetadata.name = 2, .stats = 5;
    XStatMetadata.name = 2; XStat.metadata_id = 1, .str_value = 5,
    .ref_value = 7 (a string kept as a stat metadata's name)."""
    out = {}
    for num, plane in _fields(memoryview(data)):
        if num != 1:
            continue
        fields = list(_fields(plane))
        if not any(k == 2 and _text(v).startswith("/device:")
                   for k, v in fields):
            continue
        stat_names = {}
        for k, v in fields:
            if k == 5:                        # map<int64, XStatMetadata>
                entry = dict(_fields(v))
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[entry.get(1, 0)] = _text(meta.get(2, b""))
        for k, v in fields:
            if k != 4:                        # map<int64, XEventMetadata>
                continue
            meta = list(_fields(dict(_fields(v)).get(2, b"")))
            for stat in (m for f, m in meta if f == 5):
                st = dict(_fields(stat))
                if stat_names.get(st.get(1, 0)) != "tf_op":
                    continue
                op_name = (_text(st[5]) if 5 in st
                           else stat_names.get(st.get(7), ""))
                hit = _SCOPE.search(op_name)
                if hit:
                    name = next((_text(m) for f, m in meta if f == 2), "")
                    out[name] = hit.group(1)
    return out


def reduce_profile(pd, scopes: dict) -> dict:
    """The program's spans and the solver's scoped op intervals from a
    `ProfileData`; `scopes` maps op event names to scopes (`op_scopes`)."""
    spans, scoped, window = [], {}, None
    planes = list(pd.planes)
    devices = sorted(p.name for p in planes if p.name.startswith("/device:")
                     and any(ln.name == "XLA Ops" for ln in p.lines))
    for plane in planes:
        if plane.name.startswith("/device:"):
            if plane.name != devices[0]:
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    scope = scopes.get(ev.name)
                    if scope:
                        s = float(ev.start_ns)
                        scoped.setdefault(scope, []).append(
                            (s, s + float(ev.duration_ns)))
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name == "bench.window":
                    window = (float(ev.start_ns),
                              float(ev.start_ns) + float(ev.duration_ns))
                elif name.startswith("repro."):
                    s = float(ev.start_ns)
                    spans.append((name, s, s + float(ev.duration_ns),
                                  _stats(ev)))
    spans.sort(key=lambda sp: sp[1])
    return {"window": window, "spans": spans,
            "scoped": {k: _union(v) for k, v in scoped.items()}}


def requests(ctx: dict) -> list:
    """The window's `bench.request` spans as (start, end)."""
    return [(s, e) for name, s, e in ctx["trace"]["spans"]
            if name == "bench.request"]


def per_request(ctx: dict, names: tuple, value) -> list:
    """`value(spans)` for each request that holds spans named in `names`
    (`spans` as `reduce_profile` gives them); requests without are left
    out, and so is a request whose value is None."""
    spans = [sp for sp in of(ctx)["spans"] if sp[0] in names]
    starts = [sp[1] for sp in spans]
    out = []
    for s, e in requests(ctx):
        inside = [sp for sp in spans[bisect.bisect_left(starts, s):
                                     bisect.bisect_right(starts, e)]
                  if sp[2] <= e]
        v = value(inside) if inside else None
        if v is not None:
            out.append(v)
    return out


def host_ms(ctx: dict, names: tuple) -> float | None:
    """Median over requests of the time inside the named spans that device
    activity does not cover, in ms; None when no request holds such a
    span."""
    covered = ctx["trace"]["covered"]
    vals = per_request(ctx, names, lambda sps: sum(
        (e - s) - covered(s, e) for _, s, e, _ in sps))
    return float(np.median(vals)) * 1e-6 if vals else None


def scoped_ms(ctx: dict, scope: str) -> float | None:
    """Median over requests of the device time of the ops in the named
    scope that ran inside the request, in ms; None when the trace holds no
    such op."""
    union = of(ctx)["scoped"].get(scope)
    if union is None:
        return None
    vals = [_covered(union, s, e) for s, e in requests(ctx)]
    return float(np.median(vals)) * 1e-6 if vals else None


def counter_median(ctx: dict, name: str, value) -> float | None:
    """Median over requests of `value(counters)`, `counters` the list of
    the request's `name` spans' counter dicts; None when no request gives
    a value."""
    vals = per_request(ctx, (name,), lambda sps: value([c for *_, c in sps]))
    return float(np.median(vals)) if vals else None
