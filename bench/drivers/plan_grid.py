"""Planner re-solve: DVFS and parking candidates priced against observed mixes.

Closed loop, one client. Each request is one
`repro.sched.autoscale.price_frequency_grid` call: the governor's 3l + 1
candidate frequency vectors (hold, and per pool one DVFS step up, one
down, or park) against a fresh batch of type mixes, all solved in one
batched device call (block-move GrIn with the gain-scoring kernel), then
repaired and priced for energy on the host. A request runs from the call to
the placements and prices on the host.

`correct`: every placement of every request conserves its mix exactly, and
a sample of (request, candidate, mix) points drawn from the seed is priced
again by the plain reference (`bench/reference/grin_pricing.py`, float64):
the largest relative gap in throughput and in energy per task is compared
with the cell's limits.
"""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench import gen
from bench.reference import grin_pricing as ref

WARM_STREAM = 1 << 32          # request numbers of the warm-up draws


def governor_from_config(cfg: dict):
    """The scheduler's governor for a fleet configuration."""
    from repro.core.affinity import PowerModel
    from repro.core.energy import DVFSModel
    from repro.sched.autoscale import AutoscaleGovernor, GovernorConfig
    dv = cfg["dvfs"]
    return AutoscaleGovernor(
        np.asarray(cfg["mu"], np.float64),
        dvfs=DVFSModel(alpha=dv["alpha"], levels=tuple(dv["levels"]),
                       idle_frac=dv["idle_frac"]),
        power=PowerModel(alpha=cfg["power"]["alpha"],
                         coeff=cfg["power"]["coeff"]),
        config=GovernorConfig(n_ref_tasks=cfg["n_ref_tasks"]))


def setup(cell: dict, seed: int) -> dict:
    from repro.sched import autoscale
    cfg, tr, wl = (cell["config_spec"], cell["traffic_spec"],
                   cell["workload_spec"])
    gov = governor_from_config(cfg)
    freqs = gov.candidate_freqs()
    mixes_of = gen.for_mix(tr)

    def price(mixes):
        return autoscale.price_frequency_grid(gov.nominal_mu, gov.P_nominal,
                                              freqs, mixes, gov.dvfs)

    st = {"cell": cell, "gov": gov, "freqs": freqs, "price": price,
          "seed": seed, "k": gov.k, "mixes_of": mixes_of}
    for w in range(wl["warmup_requests"]):
        price(mixes_of(gov.k, seed, WARM_STREAM + w))
    return st


def window(st: dict, seconds: float) -> dict:
    lat, outs, mixes_all = [], [], []
    with TraceAnnotation("bench.window"):
        t_end = time.perf_counter() + seconds
        i = 0
        while time.perf_counter() < t_end:
            with TraceAnnotation("bench.sample"):
                mixes = st["mixes_of"](st["k"], st["seed"], i)
            with TraceAnnotation("bench.request"):
                t0 = time.perf_counter()
                out = st["price"](mixes)
                t1 = time.perf_counter()
            lat.append(t1 - t0)
            with TraceAnnotation("bench.host_post"):
                outs.append({"targets": out["targets"].astype(np.int16),
                             "x": out["x"], "energy": out["energy"]})
                mixes_all.append(mixes)
            i += 1
    return {"metrics": {"resolve_p95_ms":
                        float(np.percentile(lat, 95)) * 1e3},
            "attempted": i, "failed": 0, "latencies": lat, "outs": outs,
            "mixes": mixes_all}


def release(st: dict) -> None:
    st.pop("price", None)


def sample_points(st: dict, win: dict, seed: int, n: int) -> list:
    C, M = win["outs"][0]["x"].shape
    rng = gen.rng_for(seed, 9)
    flat = rng.choice(len(win["outs"]) * C * M,
                      size=min(n, len(win["outs"]) * C * M), replace=False)
    return [(int(f // (C * M)), int(f // M % C), int(f % M)) for f in flat]


def compare(points, answers, st, win, *, rowsum_all: int | None = None):
    """The cell's numbers: `answers[i] = (N (k, l), x, energy)` given for
    `points[i] = (request, candidate, mix)`, held to the float64 reference."""
    mu = st["gov"].nominal_mu
    alpha = st["gov"].dvfs.alpha
    tol = st["cell"]["config_spec"]["solver_tolerance"]
    x_rel = e_rel = 0.0
    rowsum = 0 if rowsum_all is None else rowsum_all
    for (r, c, m), (N, x, e) in zip(points, answers):
        mix = win["mixes"][r][m]
        _, x_ref, e_ref = ref.price(mu, st["freqs"][c], mix, alpha=alpha,
                                  tol=tol)
        x_rel = max(x_rel, abs(x - x_ref) / abs(x_ref))
        e_rel = max(e_rel, abs(e - e_ref) / abs(e_ref)
                    if np.isfinite(e) else np.inf)
        rowsum = max(rowsum, int(np.abs(np.asarray(N, np.float64).sum(axis=1)
                                        - mix).max()))
    return [{"name": "rowsum", "value": rowsum},
            {"name": "x_rel", "value": float(x_rel)},
            {"name": "energy_rel", "value": float(e_rel)}]


def _with_limits(st, nums):
    lim = st["cell"]["workload_spec"]["limits"]
    return [dict(c, limit=lim[c["name"]]) for c in nums]


def check(st: dict, win: dict, seed: int) -> list:
    rowsum = max(int(np.abs(o["targets"].sum(axis=3, dtype=np.int64)
                            - mixes[None]).max())
                 for o, mixes in zip(win["outs"], win["mixes"]))
    pts = sample_points(st, win, seed, st["cell"]["workload_spec"]
                        ["check_points"])
    ans = [(win["outs"][r]["targets"][c, m], float(win["outs"][r]["x"][c, m]),
            float(win["outs"][r]["energy"][c, m])) for r, c, m in pts]
    return _with_limits(st, compare(pts, ans, st, win, rowsum_all=rowsum))


def control(st: dict, win: dict, seed: int, dtype) -> list:
    """The same numbers with the reference computed in `dtype` put in the
    program's place."""
    pts = sample_points(st, win, seed, st["cell"]["workload_spec"]
                        ["control_points"])
    mu, alpha = st["gov"].nominal_mu, st["gov"].dvfs.alpha
    tol = st["cell"]["config_spec"]["solver_tolerance"]
    ans = [ref.price(mu, st["freqs"][c], win["mixes"][r][m], alpha=alpha,
                     tol=tol, dtype=dtype) for r, c, m in pts]
    return _with_limits(st, compare(pts, ans, st, win))


def work(st: dict, win: dict) -> dict:
    """Scoring work of the window's requests, from the solver's own move
    counts on the same inputs (re-solved after the window)."""
    from bench import work as w
    from repro.core.grin import grin_solve_batch_jax
    from repro.sched.autoscale import guarded_candidate_mus, guarded_mixes
    gov = st["gov"]
    mus = guarded_candidate_mus(gov.nominal_mu, st["freqs"], gov.dvfs)
    ops = nbytes = 0.0
    for mixes in win["mixes"]:
        gm = guarded_mixes(mixes, gov.l)
        C, M = len(mus), len(gm)
        mu_b = np.repeat(mus, M, axis=0)
        mix_b = np.tile(gm, (C, 1))
        moves = np.asarray(grin_solve_batch_jax(mu_b, mix_b)[3])
        _, k, l = mu_b.shape
        o, b = w.gain_scoring(C * M, k, l, len(ref.ladder(int(gm.sum(1)
                                                              .max()))),
                              int(moves.max()) + 1)
        ops += o
        nbytes += b
    return {"requests": len(win["mixes"]), "ops": ops, "bytes": nbytes}
