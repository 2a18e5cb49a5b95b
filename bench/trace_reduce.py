"""Reduce a JAX profiler trace (xplane) of one benchmark window to numbers.

What it reads:

  * host spans: the benchmark's own `jax.profiler.TraceAnnotation`s, all
    named `bench.<what>` (`bench.window` bounds the measured window;
    `bench.request`, `bench.sample`, `bench.dispatch`, ... the steps inside
    it), from any host plane;
  * device activity: on a TPU, the `XLA Ops` and `XLA Modules` lines of each
    `/device:...` plane; on the CPU backend (the tests), the op events that
    carry an `hlo_op` stat on the host's XLA client threads.

What it gives (times in ns, clipped to the window):

  * `window_ns`, and `busy_ns`: the union of device op intervals, averaged
    over the devices that ran anything;
  * `modules`: device time per XLA module name (the `XLA Modules` events
    where the trace has them, else the union of that module's op events),
    with the trailing `(<id>)` of TPU module names stripped;
  * `top_ops`: the ten device ops (module-qualified) that took most time;
  * `idle_gaps`: device idle time grouped by the innermost `bench.*` host
    span open at the middle of each gap, the ten largest;
  * `spans`: every `bench.*` host span as (name, start, end), and
    `covered(start, end)`, the device busy time inside an interval.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import shutil

import numpy as np

_MODULE_ID = re.compile(r"\(\d+\)$")


@contextlib.contextmanager
def traced(trace_dir: str):
    """Profile the enclosed block into a fresh `trace_dir`."""
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # host spans only: no per-call tracing
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def reduce_dir(trace_dir: str) -> dict:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(paths[-1]))


def _union(iv: list) -> np.ndarray:
    """Merge (start, end) intervals into sorted disjoint ones: (n, 2)."""
    if not iv:
        return np.zeros((0, 2))
    iv = np.asarray(sorted(iv), dtype=np.float64)
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def _covered(union: np.ndarray, s: float, e: float) -> float:
    if union.size == 0 or e <= s:
        return 0.0
    return float(np.clip(np.minimum(union[:, 1], e)
                         - np.maximum(union[:, 0], s), 0.0, None).sum())


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def reduce_profile(pd) -> dict:
    spans, ops, mod_events = [], {}, {}
    planes = list(pd.planes)
    # with an accelerator in the trace, host threads hold no device ops:
    # skip reading their stats (there are hundreds of thousands of events)
    on_host = not any(p.name.startswith(("/device:TPU", "/device:GPU"))
                      for p in planes)
    for plane in planes:
        is_dev = plane.name.startswith("/device:")
        for line in plane.lines:
            lname = line.name
            for ev in line.events:
                s = float(ev.start_ns)
                e = s + float(ev.duration_ns)
                name = ev.name
                if not is_dev:
                    if name.startswith("bench."):
                        spans.append((name, s, e))
                        continue
                    if not on_host:
                        continue
                    st = _stats(ev)
                    if "hlo_op" in st:
                        mod = _MODULE_ID.sub("", str(st.get("hlo_module", "")))
                        ops.setdefault(plane.name, []).append((mod, name, s, e))
                    continue
                if lname == "XLA Modules":
                    mod_events.setdefault(plane.name, []).append(
                        (_MODULE_ID.sub("", name), s, e))
                elif lname == "XLA Ops":
                    # TPU op events are named by the whole HLO instruction:
                    # keep its name, the text before " = "
                    ops.setdefault(plane.name, []).append(
                        ("", name.split(" = ", 1)[0].lstrip("%"), s, e))
    win = [sp for sp in spans if sp[0] == "bench.window"]
    if not win:
        raise ValueError("trace holds no bench.window span")
    _, w0, w1 = win[0]

    def clip(s, e):
        return max(s, w0), min(e, w1)

    # TPU ops carry no module name: take the module event that holds them
    for dev, mevs in mod_events.items():
        mevs.sort(key=lambda m: m[1])
        starts = [m[1] for m in mevs]
        named = []
        for mod, name, s, e in ops.get(dev, []):
            i = bisect.bisect_right(starts, s) - 1
            if not mod and i >= 0 and s < mevs[i][2]:
                mod = mevs[i][0]
            named.append((mod, name, s, e))
        ops[dev] = named

    unions, busy, modules, op_time = {}, [], {}, {}
    for dev, evs in ops.items():
        iv = [clip(s, e) for _, _, s, e in evs]
        unions[dev] = _union([(s, e) for s, e in iv if e > s])
        busy.append(float((unions[dev][:, 1] - unions[dev][:, 0]).sum()))
        for mod, name, s, e in evs:
            s, e = clip(s, e)
            if e > s:
                key = f"{mod}/{name}" if mod else name
                op_time[key] = op_time.get(key, 0.0) + (e - s)
        if dev not in mod_events:
            by_mod = {}
            for mod, _, s, e in evs:
                by_mod.setdefault(mod, []).append(clip(s, e))
            for mod, iv_m in by_mod.items():
                u = _union([(s, e) for s, e in iv_m if e > s])
                if u.size:
                    modules[mod] = modules.get(mod, 0.0) + float(
                        (u[:, 1] - u[:, 0]).sum())
    for dev, evs in mod_events.items():
        for mod, s, e in evs:
            s, e = clip(s, e)
            if e > s:
                modules[mod] = modules.get(mod, 0.0) + (e - s)
    n_dev = max(len(busy), 1)
    # idle gaps on the first device, labelled by the innermost open span
    first = unions[sorted(unions)[0]] if unions else np.zeros((0, 2))
    edges = np.concatenate([[w0], first.ravel(), [w1]]).reshape(-1, 2)
    inner = sorted((sp for sp in spans if sp[0] != "bench.window"),
                   key=lambda sp: sp[2] - sp[1])
    gaps = {}
    for s, e in edges:
        if e <= s:
            continue
        mid = 0.5 * (s + e)
        label = next((n for n, a, b in inner if a <= mid <= b), "bench.window")
        gaps[label] = gaps.get(label, 0.0) + float(e - s)
    spans_in = [sp for sp in spans if sp[1] >= w0 and sp[2] <= w1]
    return {
        "window_ns": w1 - w0,
        "busy_ns": sum(busy) / n_dev,
        "modules": modules,
        "top_ops": [[k, v * 1e-9] for k, v in
                    sorted(op_time.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[k, v * 1e-9] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:10]],
        "spans": spans_in,
        "covered": lambda s, e: _covered(first, s, e),
    }


def module_ns(reduced: dict, fragment: str) -> float | None:
    """Device time of every module whose name holds `fragment`, or None when
    no such module ran in the window."""
    hits = [v for k, v in reduced["modules"].items() if fragment in k]
    return float(sum(hits)) if hits else None


def host_ms_per_request(reduced: dict) -> float | None:
    """Median over the window's `bench.request` spans of the span's wall time
    not covered by device activity, in ms; None without requests."""
    host = [(e - s) - reduced["covered"](s, e)
            for name, s, e in reduced["spans"] if name == "bench.request"]
    return float(np.median(host)) * 1e-6 if host else None


def idle_share_pct(reduced: dict) -> float | None:
    """Share of the window in which no operation ran on the device, in %;
    None when nothing ran (a share of nothing is not 100%)."""
    if not reduced["window_ns"] or not reduced["busy_ns"]:
        return None
    return 100.0 * (1.0 - reduced["busy_ns"] / reduced["window_ns"])
