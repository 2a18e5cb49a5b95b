"""trace_reduce on a hand-built TPU-shaped trace, and on CPU profiler traces
of the target solver on both gain-scoring paths."""
import types

import numpy as np
import pytest

import run as harness
from bench import trace_reduce, work
from bench.reference.grin_pricing import ladder

PEAKS = {"flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}


def _ev(name, start, dur, stats=()):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats))


def _line(name, evs):
    return types.SimpleNamespace(name=name, events=evs)


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=lines)


def tpu_shaped_profile():
    dev = _plane("/device:TPU:0", [
        _line("XLA Modules", [_ev("jit__grin_block_core(123)", 100, 200),
                              _ev("jit_other(7)", 400, 50)]),
        _line("XLA Ops", [_ev("%fusion.1 = f32[8] fusion(...)", 100, 100),
                          _ev("%grin_block_move_gains.6 = (s32[1,128]) "
                              "custom-call(...)", 210, 90),
                          _ev("%copy.3 = f32[4] copy(...)", 400, 50)]),
    ])
    host = _plane("/host:CPU", [_line("python", [
        _ev("bench.window", 0, 1000),
        _ev("bench.request", 40, 330),
        _ev("bench.sample", 380, 15),
        _ev("$ignored python frame", 0, 5),
    ])])
    return types.SimpleNamespace(planes=[host, dev])


def test_reduce_tpu_shaped_trace():
    r = trace_reduce.reduce_profile(tpu_shaped_profile())
    assert r["window_ns"] == 1000
    assert r["busy_ns"] == 100 + 90 + 50
    assert r["modules"] == {"jit__grin_block_core": 200.0, "jit_other": 50.0}
    assert trace_reduce.module_ns(r, "grin_block_core") == 200.0
    assert trace_reduce.module_ns(r, "absent") is None
    ops = dict(r["top_ops"])
    assert ops["jit__grin_block_core/fusion.1"] == pytest.approx(100e-9)
    assert ops["jit__grin_block_core/grin_block_move_gains.6"] == \
        pytest.approx(90e-9)
    assert ops["jit_other/copy.3"] == pytest.approx(50e-9)
    gaps = dict(r["idle_gaps"])
    # [0,100) and [200,210) and [300,400) sit in the request (innermost
    # at their middles: 50, 205, 350); [450,1000) in the window alone
    assert gaps["bench.request"] == pytest.approx((100 + 10 + 100) * 1e-9)
    assert gaps["bench.window"] == pytest.approx(550e-9)
    assert r["covered"](40, 370) == 190
    assert trace_reduce.host_ms_per_request(r) == pytest.approx(140e-6)
    assert trace_reduce.idle_share_pct(r) == pytest.approx(76.0)
    assert [s[0] for s in r["spans"]] == ["bench.window", "bench.request",
                                          "bench.sample"]


def _solver_trace(tmp_path, use_kernel):
    import jax
    from jax.profiler import TraceAnnotation
    from repro.core.grin import grin_solve_batch_jax
    rng = np.random.default_rng(3)
    mus = rng.uniform(1.0, 30.0, size=(8, 4, 6))
    mixes = rng.multinomial(600, [0.4, 0.3, 0.2, 0.1], size=8)
    jax.block_until_ready(grin_solve_batch_jax(mus, mixes,
                                               use_kernel=use_kernel))
    d = str(tmp_path / ("kernel" if use_kernel else "xla"))
    with trace_reduce.traced(d):
        with TraceAnnotation("bench.window"):
            for _ in range(2):
                with TraceAnnotation("bench.request"):
                    out = jax.block_until_ready(grin_solve_batch_jax(
                        mus, mixes, use_kernel=use_kernel))
    steps = int(np.asarray(out[3]).max()) + 1
    ops, nbytes = work.gain_scoring(8, 4, 6, len(ladder(600)), steps)
    return trace_reduce.reduce_dir(d), {"requests": 2, "ops": 2 * ops,
                                        "bytes": 2 * nbytes}


@pytest.mark.parametrize("use_kernel", [True, False],
                         ids=["pallas-interpret", "xla"])
def test_solver_metrics_from_cpu_trace(tmp_path, monkeypatch, use_kernel):
    if use_kernel:
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    reduced, w = _solver_trace(tmp_path, use_kernel)
    assert reduced["busy_ns"] > 0
    ctx = {"trace": reduced, "work": w, "peaks": PEAKS}
    dev_ms = harness.load_module("layer_metrics",
                                 "solver.device_ms.py").read(ctx)
    share = harness.load_module("layer_metrics",
                                "gain_roofline.py").read(ctx)
    assert dev_ms is not None and dev_ms > 0
    assert share is not None and 0 < share < 100
