#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5

For each seed, in one process: the cell's set-up and a short window at its
own load, then the cell's numbers twice — once for what the program
produced (the lower readings) and once with the plain reference computed a
precision below the one the configuration states (`precision`) put in the
program's place, the control (the upper readings). Prints one JSON line
per seed and a summary line last: per number, the largest program reading
and the smallest control reading. The benchmark's own runs never run
this.
"""
import argparse
import json
import sys

import numpy as np

import run as harness


def control_dtype(precision: str):
    """The nearest precision below the configuration's."""
    import ml_dtypes
    below = {"float64": np.float32, "float32": ml_dtypes.bfloat16}
    if precision not in below:
        raise SystemExit(f"no control precision below {precision!r}")
    return below[precision]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    a = ap.parse_args(argv)
    cell = harness.cell_spec(a.workload)
    harness.require_chips(cell["chips"])
    readings(cell, [int(s) for s in a.seeds.split(",")], a.seconds,
             control_dtype(cell["config_spec"]["precision"]))


def readings(cell, seeds, seconds, dtype, *, out=sys.stdout) -> dict:
    driver = harness.load_module("drivers",
                                 cell["workload_spec"]["driver"] + ".py")
    lower, upper = {}, {}
    for seed in seeds:
        st = driver.setup(cell, seed)
        win = driver.window(st, seconds)
        driver.release(st)
        prog = {c["name"]: c["value"] for c in driver.check(st, win, seed)}
        ctrl = {c["name"]: c["value"]
                for c in driver.control(st, win, seed, dtype)}
        print(json.dumps({"seed": seed, "attempted": win["attempted"],
                          "program": prog, "control": ctrl}), file=out,
              flush=True)
        for k, v in prog.items():
            lower[k] = max(lower.get(k, v), v)
        for k, v in ctrl.items():
            upper[k] = min(upper.get(k, v), v)
    summary = {"lower": lower, "upper": upper}
    print(json.dumps(summary), file=out, flush=True)
    return summary


if __name__ == "__main__":
    main()
