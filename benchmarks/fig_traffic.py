"""Open-network traffic: tail latency vs load to the saturation knee, and
SLO admission control under overload (arXiv:1712.03246 systems, open mode).

Workload: a two-class open system on a diagonal-dominant 2x2 affinity —
class 0 a light latency-critical stream (25% of arrivals), class 1 the
dominant batch stream (75%) — with per-class Poisson arrivals swept from
half load to 1.2x the saturation knee. Every (util, seed) point rides one
batched `simulate_open_batch` device call per policy: arrivals inject on a
pre-sampled schedule, completions depart, finite queues drop, and per-class
p50/p99/p999 come off the device log-histogram accumulator.

Claims measured:
  * saturation knee — the batch class's p99 and drop fraction both blow up
    past u = 1 for every policy (the open-mode analogue of the closed
    saturation plots).
  * structural isolation — GrIn-P's deficit placement keeps the latency
    class's p99 flat through overload while class-blind JSQ lets batch
    spillover flood the latency pool; GrIn-P also sustains higher goodput.
  * admission control — capping the batch class's in-system population
    (static shed limits, the device-engine admission rule) restores the
    latency class's p99 and deadline attainment under 1.2x overload on the
    class-blind baseline: best-effort sheds, protected stops dropping.
  * device histogram vs host oracle — the host open loop (exact sorted
    quantiles) agrees with the device run at matched config.
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

from benchmarks.common import Timer, emit, save_json
from repro.sched import get_policy
from repro.sim import make_distribution
from repro.sim.engine_jax import MODE_DEFICIT, _BASELINE_MODES
from repro.sim.simulator import ClosedNetworkSimulator
from repro.traffic import LogHistogram, PoissonArrivals, TrafficSpec
from repro.traffic.config import derive_target_mix, open_sim_config
from repro.traffic.engine import simulate_open_batch

MU = np.array([[8.0, 2.0],      # class 0: latency-critical, pool 0 native
               [2.0, 6.0]])     # class 1: batch, pool 1 native
SHARES = np.array([0.25, 0.75])
CLS = [0, 1]
QCAP = 8
N_SLOTS = MU.shape[1] * QCAP
DEADLINES = np.array([1.25, 10.0])
WEIGHTS = [2.0, 1.0]            # latency class weighted, affinity-preserving
POLICIES = ("grin-p", "cab-p", "lb", "jsq")


UTILS, SEEDS = (0.5, 0.7, 0.85, 0.95, 1.05, 1.2), (0, 1, 2)
# offered rate at which the busiest class fills its best pool
X_KNEE = 1.0 / max(SHARES[c] / MU[c].max() for c in range(len(SHARES)))


def _target_for(pname, mix):
    if pname in ("lb", "jsq"):
        return _BASELINE_MODES[pname], np.zeros(MU.shape, np.int64)
    pol = get_policy(pname, weights=WEIGHTS)
    return MODE_DEFICIT, np.asarray(pol.solve_target(MU, mix))


def sample_arrivals(utils, seeds, n_arrivals):
    """Per-load Poisson TrafficSpecs and the shared arrival realization
    (times, types) of every (util, seed) point, in utils x seeds order."""
    specs = {u: TrafficSpec(tuple(PoissonArrivals(u * X_KNEE * s)
                                  for s in SHARES), np.eye(len(SHARES)))
             for u in utils}
    arr = {(u, s): specs[u].sample(s, n_arrivals)
           for u in utils for s in seeds}
    return specs, arr


def simulate(pname, admit, arr, mix, warmup_arrivals, hist=None):
    """Every (util, seed) point of `arr` under one policy and admission
    cap, in ONE `simulate_open_batch` device call."""
    points = list(arr)
    B = len(points)
    mode, target = _target_for(pname, mix)
    return simulate_open_batch(
        np.broadcast_to(MU, (B,) + MU.shape),
        np.broadcast_to(target, (B,) + target.shape),
        np.stack([arr[p][0] for p in points]),
        np.stack([arr[p][1] for p in points]),
        [p[1] for p in points], distribution=make_distribution("exponential"),
        queue_capacity=QCAP, order="PS", warmup_arrivals=warmup_arrivals,
        class_of_type=CLS, modes=np.full(B, mode, np.int32),
        admit_limits=np.broadcast_to(np.asarray(admit, np.int64),
                                     (B, len(SHARES))),
        hist=hist or LogHistogram(), deadlines=DEADLINES)


def host_oracle(spec, mix, n_arrivals, warmup_arrivals, seed):
    """GrIn-P on the host event loop at one load: the oracle the device
    sweep is held to (same arrival realization, own size stream)."""
    cfg = open_sim_config(
        MU, spec, n_arrivals=n_arrivals, warmup_arrivals=warmup_arrivals,
        queue_capacity=QCAP, deadlines=DEADLINES, class_of_type=CLS,
        target_mix=mix, distribution=make_distribution("exponential"),
        order="PS", seed=seed)
    return ClosedNetworkSimulator(cfg).run(
        get_policy("grin-p", weights=WEIGHTS))


def run(n_arrivals: int = 20000, warmup_arrivals: int = 2000,
        utils=UTILS, seeds=SEEDS, smoke: bool = False):
    if smoke:
        n_arrivals, warmup_arrivals = 2500, 250
        utils, seeds = (0.5, 0.95, 1.2), (0,)
    hist = LogHistogram()
    u_hi = max(utils)
    payload = {"smoke": smoke, "n_arrivals": n_arrivals,
               "warmup_arrivals": warmup_arrivals, "utils": list(utils),
               "seeds": list(seeds), "mu": MU.tolist(),
               "shares": SHARES.tolist(), "x_knee": float(X_KNEE),
               "queue_capacity": QCAP, "deadlines": DEADLINES.tolist(),
               "hist_rel_error_bound": float(hist.rel_error_bound)}

    # shared arrival realizations + per-class offered counts in-window
    specs, arr = sample_arrivals(utils, seeds, n_arrivals)
    offered_c = {p: np.bincount(tys[warmup_arrivals:], minlength=len(SHARES))
                 for p, (_, tys) in arr.items()}
    mix = derive_target_mix(specs[u_hi], MU.shape[1], QCAP)
    points = list(arr)
    B = len(points)

    variants = [(p, [N_SLOTS, N_SLOTS]) for p in POLICIES]
    variants += [("jsq+adm", [N_SLOTS, QCAP // 2]),
                 ("grin-p+adm", [N_SLOTS, QCAP // 2])]
    results, curves = {}, {}
    for disp, admit in variants:
        pname = disp.split("+")[0]
        with Timer() as t:
            out = simulate(pname, admit, arr, mix, warmup_arrivals, hist)
        emit(f"fig_traffic_{disp}", t.us / B, f"points={B};wall={t.dt:.2f}s")
        results[disp] = out
        rows = {}
        for i, (u, s) in enumerate(points):
            off = offered_c[(u, s)]
            r = rows.setdefault(u, {"goodput": [], "p50": [], "p99": [],
                                    "p999": [], "drop_frac": [],
                                    "deadline_met": []})
            q = out["class_quantiles"][i]
            r["goodput"].append(float(out["throughput"][i]))
            r["p50"].append(q[:, 0]); r["p99"].append(q[:, 1])
            r["p999"].append(q[:, 2])
            r["drop_frac"].append(out["class_dropped"][i]
                                  / np.maximum(off, 1))
            r["deadline_met"].append(out["class_deadline_met"][i])
        curves[disp] = {
            f"u={u:g}": {key: np.mean(vals, axis=0).tolist()
                         for key, vals in r.items()}
            for u, r in rows.items()}
    payload["curves"] = curves

    def stat(disp, u, key, c=None):
        v = np.asarray(curves[disp][f"u={u:g}"][key])
        return float(v if v.ndim == 0 else (np.mean(v) if c is None
                                            else v[c]))

    # 1. saturation knee: the batch class's tail and drop rate blow up past
    # the knee for every policy
    for disp in POLICIES:
        assert stat(disp, u_hi, "p99", 1) > 1.5 * stat(disp, 0.5, "p99", 1), \
            (disp, curves[disp])
        assert stat(disp, u_hi, "drop_frac", 1) > 0.05 > \
            stat(disp, 0.5, "drop_frac", 1), (disp, curves[disp])

    # 2. structural isolation at overload: GrIn-P holds the latency class's
    # p99 where JSQ floods it, at higher goodput
    iso = stat("jsq", u_hi, "p99", 0) / stat("grin-p", u_hi, "p99", 0)
    gp = stat("grin-p", u_hi, "goodput") / stat("jsq", u_hi, "goodput")
    payload["jsq_over_grin_p_latency_p99_at_overload"] = iso
    payload["grin_p_over_jsq_goodput_at_overload"] = gp
    assert iso > 2.0 and gp > 1.05, (iso, gp)

    # 3. admission control under >= 1.2x overload: the protected class stops
    # dropping and recovers its tail; best-effort sheds instead
    adm = {
        "protected_drop_frac": stat("jsq+adm", u_hi, "drop_frac", 0),
        "best_effort_shed_frac": stat("jsq+adm", u_hi, "drop_frac", 1),
        "protected_p99_without": stat("jsq", u_hi, "p99", 0),
        "protected_p99_with": stat("jsq+adm", u_hi, "p99", 0),
        "protected_deadline_met_without": stat("jsq", u_hi,
                                               "deadline_met", 0),
        "protected_deadline_met_with": stat("jsq+adm", u_hi,
                                            "deadline_met", 0)}
    payload["admission_at_overload"] = adm
    assert adm["protected_drop_frac"] < 0.01, adm
    assert adm["best_effort_shed_frac"] > 0.10, adm
    assert adm["protected_p99_with"] < adm["protected_p99_without"], adm
    assert adm["protected_deadline_met_with"] > \
        adm["protected_deadline_met_without"], adm

    # 4. host oracle vs device engine at one matched point (same arrival
    # realization; size streams differ, so tolerances are statistical)
    u_ref = 0.95 if smoke else 0.85
    with Timer() as t:
        host = host_oracle(specs[u_ref], mix, n_arrivals, warmup_arrivals,
                           seeds[0])
    emit("fig_traffic_host_oracle", t.us, f"wall={t.dt:.2f}s")
    i_ref = points.index((u_ref, seeds[0]))
    dev = results["grin-p"]
    x_rel = abs(host.throughput - float(dev["throughput"][i_ref])) \
        / host.throughput
    p99_rel = float(np.max(np.abs(
        np.asarray(host.class_quantiles)[:, 1]
        - dev["class_quantiles"][i_ref][:, 1])
        / np.asarray(host.class_quantiles)[:, 1]))
    payload["host_vs_device"] = {"u": u_ref, "x_rel": x_rel,
                                 "p99_max_rel": p99_rel}
    assert x_rel < 0.05 and p99_rel < 0.30, payload["host_vs_device"]

    emit("fig_traffic_summary", 0.0,
         f"knee at u~1: batch p99 x{stat('grin-p', u_hi, 'p99', 1) / stat('grin-p', 0.5, 'p99', 1):.1f};"
         f"iso {iso:.1f}x;goodput {gp:.2f}x;"
         f"adm p99 {adm['protected_p99_without']:.1f}->"
         f"{adm['protected_p99_with']:.1f}")

    save_json("fig_traffic", payload)
    if not smoke:
        with open(os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "BENCH_pr6.json"), "w") as f:
            json.dump(payload, f, indent=1)
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized invocation (no BENCH_pr6.json rewrite)")
    args = ap.parse_args()
    run(smoke=args.smoke)
