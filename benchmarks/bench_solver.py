"""Target-solver benchmarks on the CPU.

Measurements on a (mu x mix) grid (4 affinity matrices x 64 mixes = 256
points, k=4 types, l=6 pools, N=6000 tasks per mix; smoke mode shrinks all
of it), emitted as CSV rows and saved under reports/benchmarks/:

  * host solves/sec — `grin_solve` (Algorithm 2 sweeps) and the host
    block-move mirror, looped in Python over a grid subset;
  * block-move grid solves/sec — `solve_targets_grid_jax`, plus the same
    batch driven through the Pallas gain kernel (interpret mode off-TPU:
    correctness path, not a speed path — recorded separately);
  * acceptance checks: the device solver converged on every grid point and
    its X_sys gap to the host solver (float64, from the returned integer
    placements), and the Pallas kernel's scores bit-matching the jnp
    reference;
  * wall time of an end-to-end `sweep_jax` affinity-grid sweep (targets
    grid-solved on device, then one batched simulation call).

Usage: PYTHONPATH=src python -m benchmarks.bench_solver [--smoke]
"""
from __future__ import annotations

import argparse
import os
from unittest import mock

import jax
import numpy as np

from benchmarks.common import Timer, emit, save_json
from repro.core import (grin_block_solve, grin_solve, grin_solve_batch_jax,
                        random_affinity_matrix, system_throughput)
from repro.kernels.grin_moves import block_move_gains_pallas, block_move_scores
from repro.sched import solve_targets_grid_jax
from repro.sim import SimConfig, make_distribution, sweep_jax

_REPEATS = 3        # best-of-N: the container CPU is noisy/shared


def _best_rate(fn, units: float) -> float:
    """Max units/sec over _REPEATS timed calls (first call warms caches)."""
    fn()
    best = 0.0
    for _ in range(_REPEATS):
        with Timer() as t:
            fn()
        best = max(best, units / t.dt)
    return best


def _workload(smoke: bool):
    """(mu x mix) grid with SKEWED mixes (Dirichlet alpha=0.3): balanced
    mixes land near the Alg-1 init and need almost no moves, skewed ones
    force long single-task drains — exactly what block moves collapse."""
    k, l = 4, 6
    G, M, N = (2, 8, 600) if smoke else (4, 64, 6000)
    rng = np.random.default_rng(1)
    mus = np.stack([random_affinity_matrix(rng, k, l) for _ in range(G)])
    mixes = np.array([rng.multinomial(N, p)
                      for p in rng.dirichlet([0.3] * k, size=M)])
    return mus, mixes


def run(smoke: bool = False) -> dict:
    mus, mixes = _workload(smoke)
    G, M = len(mus), len(mixes)
    n_points = G * M
    payload: dict = {"smoke": smoke, "grid_points": n_points,
                     "k": int(mus.shape[1]), "l": int(mus.shape[2]),
                     "tasks_per_mix": int(mixes[0].sum())}

    # ---- 1. host solvers (Python loop over a grid subset) -----------------
    host_pts = min(n_points, 8 if smoke else 32)
    sub = [(mus[i % G], mixes[i % M]) for i in range(host_pts)]
    host_rate = _best_rate(
        lambda: [grin_solve(m, mix) for m, mix in sub], host_pts)
    host_block_rate = _best_rate(
        lambda: [grin_block_solve(m, mix) for m, mix in sub], host_pts)
    payload["host_solves_per_sec"] = host_rate
    payload["host_block_solves_per_sec"] = host_block_rate
    emit("solver_host", 1e6 / host_rate,
         f"solves/s={host_rate:,.1f};block={host_block_rate:,.1f}")

    # ---- 2. block-move device grids ---------------------------------------
    block_rate = _best_rate(
        lambda: solve_targets_grid_jax(mus, mixes), n_points)
    payload["block_move_solves_per_sec"] = block_rate
    payload["block_vs_host_speedup"] = block_rate / host_rate
    emit("solver_grid", 1e6 / block_rate,
         f"points={n_points};block/s={block_rate:,.0f}")

    # ---- 3. acceptance: converged everywhere, gap to the host solver ------
    # Measured in float64 from the returned integer placements.
    tb, _, conv = solve_targets_grid_jax(mus, mixes)
    payload["block_converged_everywhere"] = bool(conv.all())
    host_gap = np.array([
        1.0 - system_throughput(tb[i % G, i % M], mus[i % G])
        / grin_solve(mus[i % G], mixes[i % M]).x_sys for i in range(host_pts)])
    payload["block_vs_host_mean_rel_gap"] = float(host_gap.mean())
    emit("solver_quality", 0.0,
         f"converged={payload['block_converged_everywhere']};"
         f"host_gap={host_gap.mean():.2e}")

    # ---- 4. Pallas gain-kernel path ---------------------------------------
    b, k, l = min(16, n_points), mus.shape[1], mus.shape[2]
    kN = np.random.default_rng(0).integers(0, 40, size=(b, k, l)).astype(np.float32)
    kmu = np.repeat(mus[:1], b, axis=0).astype(np.float32)
    sizes = (2.0 ** np.arange(10, -1, -1)).astype(np.float32)
    ref_g, ref_bi, _, _ = block_move_scores(kN, kmu, sizes, use_kernel=False)
    pal_g, pal_bi, _, _ = block_move_gains_pallas(kN, kmu, sizes,
                                                  interpret=True)
    payload["pallas_bit_matches_ref"] = bool(
        np.array_equal(np.asarray(ref_g), np.asarray(pal_g))
        and np.array_equal(np.asarray(ref_bi), np.asarray(pal_bi)))
    pal_pts = min(n_points, 4 if smoke else 16)
    mu_b = np.repeat(mus[:1], pal_pts, axis=0)

    def _pallas_solve():
        grin_solve_batch_jax(mu_b, mixes[:pal_pts], use_kernel=True)

    # off-TPU the kernel runs only in interpret mode, which must be asked for
    interp = ({} if jax.default_backend() == "tpu"
              else {"REPRO_PALLAS_INTERPRET": "1"})
    with mock.patch.dict(os.environ, interp):
        _pallas_solve()     # compile/interpret warm-up
        with Timer() as t:
            _pallas_solve()
    pallas_rate = pal_pts / t.dt
    payload["pallas_path_solves_per_sec"] = pallas_rate
    payload["pallas_path_note"] = (
        "interpret mode off-TPU: parity/correctness path; compiled Pallas "
        "is the TPU production path")
    emit("solver_pallas", 1e6 / pallas_rate,
         f"bit_match={payload['pallas_bit_matches_ref']};"
         f"points={pal_pts};interp/s={pallas_rate:,.2f}")

    # ---- 5. end-to-end affinity-grid sweep (targets + simulation) ---------
    # Simulation cost scales with the population, so the sweep leg runs its
    # own smaller closed network (N=120) — the point here is the wall time
    # of "grid-solve targets on device + one batched simulate call".
    sw_mus = mus[:2]
    rng = np.random.default_rng(2)
    sw_mixes = rng.multinomial(120, [1.0 / mus.shape[1]] * mus.shape[1],
                               size=4 if smoke else 16)
    cfg = SimConfig(mu=sw_mus[0], n_programs_per_type=sw_mixes[0],
                    distribution=make_distribution("exponential"), order="PS",
                    n_completions=800 if smoke else 3000,
                    warmup_completions=160 if smoke else 600, seed=0)
    sweep_jax(cfg, "grin", mixes=sw_mixes, mus=sw_mus)   # warm (jit)
    with Timer() as t:
        grid, res = sweep_jax(cfg, "grin", mixes=sw_mixes, mus=sw_mus)
    payload["sweep_grid_points"] = len(grid)
    payload["sweep_grid_wall_s"] = t.dt
    payload["sweep_mean_throughput"] = float(res["throughput"].mean())
    emit("solver_sweep_grid", t.dt * 1e6 / len(grid),
         f"points={len(grid)};wall={t.dt:.2f}s")

    save_json("bench_solver", payload)
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized invocation")
    args = ap.parse_args()
    run(smoke=args.smoke)
