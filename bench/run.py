#!/usr/bin/env python3
"""Chip benchmark of the heterogeneous-fleet scheduler: one cell per run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell's entry in `BENCHMARK.json` names its
configuration and traffic mix, `bench/workloads/<cell>.json` names the driver
(`bench/drivers/<driver>.py`), the configuration is
`bench/configs/<config>.json`, the traffic mix `bench/traffic/<mix>.json`
(read by the one generator, `bench/gen.py`), and each per-layer metric is
read by `bench/layer_metrics/<metric>.py`. Adding a cell, a configuration, a
mix or a metric therefore adds files and edits none.

A run refuses to start without a TPU (or with fewer chips than the cell
asks for), warms every shape the cell uses (set-up), measures for
`--seconds`, checks what the timed path produced against the plain
reference once the window has closed, and prints one JSON line last on
standard output. `--trace 1` runs a traced window of its own and reports the
cell's per-layer metrics instead of the end-to-end ones.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# The persistent compilation cache lives inside the checkout at a fixed
# path, whatever the environment says, so two checkouts never share one.
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
TRACE_ROOT = os.path.join(BENCH, "traces")      # profiler output (ignored)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(*parts):
    path = os.path.join(BENCH, *parts)
    name = "bench_" + os.path.splitext(os.path.relpath(path, BENCH))[0] \
        .replace(os.sep, "__").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(name: str) -> dict:
    """The cell's entry in BENCHMARK.json joined with its files: config,
    traffic mix, workload (driver + parameters) and the metrics it reports."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}: {sorted(cells)}")
    cell = dict(cells[name])
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    cell.update(config_spec=load_json(BENCH, "configs",
                                      cell["config"] + ".json"),
                traffic_spec=load_json(BENCH, "traffic",
                                       cell["traffic"] + ".json"),
                workload_spec=load_json(BENCH, "workloads", name + ".json"),
                end_to_end=e2e, per_layer=per_layer)
    return cell


def require_chips(n: int):
    """The cell's devices, or exit non-zero: no CPU fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < n:
        raise SystemExit(f"needs {n} chips; JAX found {len(devs)}")
    return devs[:n]


def device_block(devs) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max((p for p in peaks if p is not None),
                                     default=None)}


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             require=require_chips) -> dict:
    """One run of one cell; returns the result object (printed by main).
    `require` finds the cell's devices; the tests replace it to drive a run
    on the CPU."""
    import jax
    phases = {"import": time.perf_counter() - T_START}
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    # cache every program, also those that compile in under a second, and
    # evict none, so a second run of the cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)

    devs = require(cell["chips"])
    phases["devices"] = time.perf_counter() - T_START
    wl = cell["workload_spec"]
    driver = load_module("drivers", wl["driver"] + ".py")
    state = driver.setup(cell, seed)
    setup_s = time.perf_counter() - T_START
    # where set-up went: seconds from process start at the end of each phase
    print("setup phases: " + " ".join(f"{k} {v:.3f}" for k, v in
                                      dict(phases, setup=setup_s).items()),
          file=sys.stderr, flush=True)

    compiles = []

    def count_compile(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)
    jax.monitoring.register_event_duration_secs_listener(count_compile)
    if trace:
        from bench import trace_reduce
        trace_dir = os.path.join(TRACE_ROOT, cell["name"])
        seconds = min(seconds, wl.get("trace_seconds", seconds))
        with trace_reduce.traced(trace_dir):
            window = driver.window(state, seconds)
        n_compiles = len(compiles)
        device = device_block(devs)
        reduced = trace_reduce.reduce_dir(trace_dir)
    else:
        window = driver.window(state, seconds)
        n_compiles = len(compiles)
        device = device_block(devs)
    jax.monitoring.unregister_event_duration_listener(count_compile)
    work = driver.work(state, window) if trace else None
    driver.release(state)
    checks = driver.check(state, window, seed)

    if trace:
        ctx = {"cell": cell, "window": window, "trace": reduced,
               "work": work, "peaks": peak_for(devs[0].device_kind)}
        metrics = {}
        for m in cell["per_layer"]:
            val = load_module("layer_metrics", m["name"] + ".py").read(ctx)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        device.update(busy_s=reduced["busy_ns"] * 1e-9,
                      window_s=reduced["window_ns"] * 1e-9)
    else:
        vals = dict(window["metrics"], setup_s=setup_s)
        metrics = {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}

    correct = all(c["value"] <= c["limit"] for c in checks)
    out = {"correct": correct, "attempted": window["attempted"],
           "failed": window["failed"], "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": reduced["top_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    # what compiled between set-up and the end of the window (should be 0)
    out["compiles_in_window"] = n_compiles
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    return out


def peak_for(kind: str) -> dict:
    peaks = load_json(BENCH, "peaks.json")["devices"]
    if kind not in peaks:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return peaks[kind]


def print_result(out: dict) -> None:
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


if __name__ == "__main__":
    a = parse_args()
    print_result(run_cell(cell_spec(a.workload), a.seed, a.seconds,
                          bool(a.trace)))
