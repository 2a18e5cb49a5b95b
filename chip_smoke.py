#!/usr/bin/env python3
"""Smoke run of the scheduler's main path on one TPU chip.

    python chip_smoke.py

One process, one chip, four phases in order; any failed check raises and the
script exits non-zero without printing a result:

  * preflight  — compile cache placed; refuses to run unless JAX's first
                 device is a TPU (there is no CPU fallback);
  * solver     — the 256-point (mu x mix) target grid through
                 `solve_targets_grid_jax` with the compiled Pallas gain
                 kernel, held to the XLA (jnp) path on the same chip and to
                 the host f64 block solver; one GrIn-E solve; one
                 `AutoscaleGovernor` decision epoch on a live SchedulerCore;
  * what-if    — the full `benchmarks/fig_traffic.py` open-network sweep
                 (20,000 arrivals, 6 loads x 3 seeds, one
                 `simulate_open_batch` per policy), held to the host oracle;
  * serving    — `repro.launch.serve --heterogeneous` at qwen2.5-3b's
                 published width (random weights from a seed), plus each
                 compiled Pallas kernel of the served path against its jnp
                 reference on the served shapes.

Per-phase wall times are split into the first call (which compiles) and a
second call. The last line of standard output is one JSON object naming the
device.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# per-point gates of the open-arrival cell in tests/test_conformance.py
O_X_TOL, O_P99_TOL, O_DROP_ABS = 0.15, 0.45, 0.06
BF16_TOL = 2e-2                       # tests/test_kernels.py bf16 tolerance
HBM_BYTES = 16e9                      # one TPU v5e


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def timed(fn):
    """(result, seconds) with the device work finished inside the timing."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def preflight():
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    log(f"device_kind={dev.device_kind} count={jax.device_count()} "
        f"jax={jax.__version__} compile_cache={cache}")
    return dev


def solver_phase():
    from benchmarks.bench_solver import _workload
    from repro.core import (grin_block_solve, grin_solve_batch_jax,
                            system_throughput)
    from repro.core.grin import _TOL32_BLOCK, _grin_block_core, _ladder
    from repro.kernels.grin_moves import OBJ_X
    from repro.obs.meta import kernel_mode
    from repro.sched import SchedulerCore, solve_targets_grid_jax
    from repro.sched.api import round_placements_jax, solve_targets_jax
    from repro.sched.autoscale import AutoscaleGovernor

    check(kernel_mode() == "pallas-compiled", f"kernel_mode={kernel_mode()}")
    mus, mixes = _workload(smoke=False)
    G, M = len(mus), len(mixes)
    (targets, xs, conv), t1 = timed(lambda: solve_targets_grid_jax(mus, mixes))
    _, t2 = timed(lambda: solve_targets_grid_jax(mus, mixes))
    log(f"solver grid {G}x{M}={G * M} points k={mus.shape[1]} "
        f"l={mus.shape[2]} N={int(mixes[0].sum())}: first {t1:.3f}s "
        f"second {t2:.3f}s converged {int(conv.sum())}/{G * M}")
    check(conv.all(), "solver grid: not every point converged")
    check((targets.sum(axis=3) == mixes[None]).all(), "row sums broken")

    mu_b = np.repeat(mus, M, axis=0).astype(np.float32)
    mix_b = np.tile(mixes, (G, 1)).astype(np.float32)
    n_sizes = len(_ladder(int(mixes.sum(axis=1).max())))
    hlo = _grin_block_core.lower(mu_b, mix_b, mu_b, n_sizes=n_sizes,
                                 max_moves=None, kernel=kernel_mode(),
                                 objective=OBJ_X).compile().as_text()
    log(f"solver program contains tpu_custom_call: "
        f"{'tpu_custom_call' in hlo}")
    check("tpu_custom_call" in hlo, "solver program holds no Pallas kernel")

    (n_x, x_x, conv_x, _), t1 = timed(
        lambda: grin_solve_batch_jax(mu_b, mix_b, use_kernel=False))
    x_k = xs.reshape(-1)
    x_x = np.asarray(x_x)
    rel = np.abs(x_k - x_x) / np.abs(x_x)
    n_diff = int((np.asarray(round_placements_jax(n_x, mix_b)[0])
                  != targets.reshape(n_x.shape)).any(axis=(1, 2)).sum())
    log(f"kernel vs XLA path: placements differ on {n_diff}/{G * M} points, "
        f"max rel X_sys diff {rel.max():.3e} (tol {_TOL32_BLOCK:g}); "
        f"XLA path first call {t1:.3f}s")
    check(bool(np.asarray(conv_x).all()), "XLA path: not every point "
          "converged")
    check((rel <= _TOL32_BLOCK).all(), "kernel and XLA X_sys disagree")

    for i in range(8):
        g, m = i % G, (i * M) // 8
        host = grin_block_solve(mus[g], mixes[m])
        xb = system_throughput(targets[g, m], mus[g])
        check(xb >= 0.95 * host.x_sys, f"point {g},{m}: {xb} vs host "
              f"{host.x_sys}")
        check(abs(xb - xs[g, m]) <= 1e-3 * xb, f"point {g},{m}: f32 X_sys "
              f"{xs[g, m]} vs f64 {xb}")
    log("8 host f64 grin_block_solve checks passed")

    (t_e, x_e), t1 = timed(lambda: solve_targets_jax(
        mus[0], mixes, objective="max-x-e"))
    _, t2 = timed(lambda: solve_targets_jax(mus[0], mixes,
                                            objective="max-x-e"))
    check(np.isfinite(x_e).all() and (t_e.sum(axis=2) == mixes).all(),
          "grin-e solve")
    log(f"grin-e solve {M} mixes: first {t1:.3f}s second {t2:.3f}s")

    core = SchedulerCore("grin", mus[0])
    gov = AutoscaleGovernor(mus[0], core=core)
    gov.observe(mixes[0] / mixes[0].sum() * 40.0, 1.0)
    dec, t1 = timed(gov.decide)
    _, t2 = timed(gov.decide)
    live = gov.apply_to_core(core, dec, list(range(mus.shape[2])))
    core.reset(n_tasks=mixes[0])
    check(gov.solve_calls == 2 and core.route(0) in range(core.l),
          "governor epoch")
    log(f"governor epoch ({3 * mus.shape[2] + 1} candidates): first "
        f"{t1:.3f}s second {t2:.3f}s action={dec.action} live={live}")


def whatif_phase():
    from benchmarks import fig_traffic as ft
    from repro.traffic.config import derive_target_mix

    n_arr, warm = 20000, 2000
    specs, arr = ft.sample_arrivals(ft.UTILS, ft.SEEDS, n_arr)
    mix = derive_target_mix(specs[max(ft.UTILS)], ft.MU.shape[1], ft.QCAP)
    results = {}
    for i, pname in enumerate(ft.POLICIES):
        out, dt = timed(lambda: ft.simulate(pname, [ft.N_SLOTS] * 2, arr,
                                            mix, warm))
        results[pname] = out
        log(f"what-if {pname}: {len(arr)} points x {n_arr} arrivals "
            f"{'first' if i == 0 else 'later'} call {dt:.3f}s "
            f"goodput mean {float(np.mean(out['throughput'])):.4f}")
        check(np.isfinite(np.asarray(out["throughput"])).all(), pname)
    _, dt = timed(lambda: ft.simulate(ft.POLICIES[0], [ft.N_SLOTS] * 2, arr,
                                      mix, warm))
    log(f"what-if {ft.POLICIES[0]} second call {dt:.3f}s")

    u_ref, seed = 0.85, ft.SEEDS[0]
    host = ft.host_oracle(specs[u_ref], mix, n_arr, warm, seed)
    dev = results["grin-p"]
    i = list(arr).index((u_ref, seed))
    x_rel = abs(float(dev["throughput"][i]) - host.throughput) \
        / host.throughput
    hp99 = np.asarray(host.class_quantiles)[:, 1]
    p99_rel = np.abs(np.asarray(dev["class_quantiles"][i])[:, 1] - hp99) \
        / hp99
    drop_abs = abs(host.dropped - float(dev["dropped"][i])) / host.offered
    log(f"host oracle u={u_ref}: X rel {x_rel:.4f} (tol {O_X_TOL}), "
        f"per-class p99 rel {np.round(p99_rel, 4).tolist()} "
        f"(tol {O_P99_TOL}), drop frac abs {drop_abs:.4f} "
        f"(tol {O_DROP_ABS})")
    check(x_rel < O_X_TOL and (p99_rel < O_P99_TOL).all()
          and drop_abs < O_DROP_ABS, "device sweep disagrees with the host")


def serving_phase(dev):
    from repro.kernels import ops
    from repro.kernels.ref import rmsnorm_ref
    from repro.launch import serve
    from repro.models.attention import chunked_attention

    argv = ["--arch", "qwen2.5-3b", "--heterogeneous", "--batch", "2",
            "--prompt-len", "16", "--steps", "8"]
    res, t1 = timed(lambda: serve.run(argv))
    cfg, eng, batch, toks = (res["cfg"], res["engine"], res["batch"],
                             res["tokens"])
    again, t2 = timed(lambda: eng.generate(batch, steps=8))
    log(f"serve {cfg.name} L={cfg.n_layers} d={cfg.d_model} "
        f"V={cfg.vocab_size}: run (init + generate + scheduling) "
        f"{t1:.3f}s, second generate {t2:.3f}s")
    toks = np.asarray(toks)
    check(toks.shape == (2, 8) and (toks >= 0).all()
          and (toks < cfg.vocab_size).all(), f"tokens {toks}")
    check(np.array_equal(toks, np.asarray(again)), "greedy decode differs "
          "between two runs")
    mu = np.asarray(res["mu"])
    check(mu.shape == (2, 2) and np.isfinite(mu).all() and (mu > 0).all(),
          f"mu {mu}")
    x = res["throughput"]
    log(f"serve measured mu {np.round(mu, 3).tolist()}; closed-loop "
        f"CAB {x['cab']:.3f} req/s, LB {x['lb']:.3f} req/s")
    check(x["cab"] > 0 and x["lb"] > 0, "closed-loop throughput")

    k = jax.random.split(jax.random.PRNGKey(2), 4)
    b, s = 2, 16
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = jax.random.normal(k[0], (b, s, h, dh), jnp.bfloat16)
    kk = jax.random.normal(k[1], (b, s, kv, dh), jnp.bfloat16)
    vv = jax.random.normal(k[2], (b, s, kv, dh), jnp.bfloat16)
    blocks = dict(block_q=cfg.attn_chunk_q, block_k=cfg.attn_chunk_k)
    fa = jax.jit(lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                                     **blocks))
    check("tpu_custom_call" in fa.lower(q, kk, vv).compile().as_text(),
          "flash attention is not the Pallas kernel")
    out = fa(q, kk, vv)
    ref = chunked_attention(q, kk, vv, causal=True, chunk_q=cfg.attn_chunk_q,
                            chunk_k=cfg.attn_chunk_k)
    err_fa = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                   - ref.astype(jnp.float32))))
    xr = jax.random.normal(k[3], (b * s, cfg.d_model), jnp.bfloat16)
    w = jax.random.normal(k[0], (cfg.d_model,)) * 0.1
    rn = jax.jit(ops.rmsnorm)
    check("tpu_custom_call" in rn.lower(xr, w).compile().as_text(),
          "rmsnorm is not the Pallas kernel")
    err_rn = float(jnp.max(jnp.abs(rn(xr, w).astype(jnp.float32)
                                   - rmsnorm_ref(xr, w).astype(jnp.float32))))
    log(f"served-shape kernels vs jnp: flash_attention max abs err "
        f"{err_fa:.3e}, rmsnorm max abs err {err_rn:.3e} (bf16 tol "
        f"{BF16_TOL})")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=BF16_TOL,
                               rtol=BF16_TOL)
    np.testing.assert_allclose(np.asarray(rn(xr, w), np.float32),
                               np.asarray(rmsnorm_ref(xr, w), np.float32),
                               atol=BF16_TOL, rtol=BF16_TOL)

    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"peak_bytes_in_use={peak}")
    check(peak is not None and peak < HBM_BYTES, f"peak bytes {peak}")


def main() -> None:
    dev = preflight()
    for name, phase in (("solver", solver_phase), ("what-if", whatif_phase),
                        ("serving", lambda: serving_phase(dev))):
        t0 = time.perf_counter()
        phase()
        log(f"phase {name} done in {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))


if __name__ == "__main__":
    main()
