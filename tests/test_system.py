"""End-to-end behaviour: the paper's pipeline (measure -> solve -> dispatch ->
verify optimal throughput) and the framework pipeline (serve -> measure ->
schedule) composed together."""
import jax
import numpy as np
import pytest

from repro.configs import ARCHS, smoke_config
from repro.core import cab_solve, classify_2x2
from repro.models.model import build_model
from repro.sched import BaselineClusterScheduler, ClusterScheduler
from repro.sched.virtual import VirtualTimeCluster
from repro.serve.engine import ServeEngine, init_serving_params


def test_end_to_end_train_then_serve_then_schedule():
    # 1. a tiny model with weights straight from the serving init
    sc = smoke_config(ARCHS["qwen2.5-3b"])
    m = build_model(sc)
    params = init_serving_params(m, jax.random.PRNGKey(0))

    # 2. serve it: prefill + greedy decode
    eng = ServeEngine(m, params, max_len=64)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, sc.vocab_size)
    gen = eng.generate({"tokens": toks}, steps=4)
    assert gen.shape == (2, 4)
    assert bool((gen >= 0).all()) and bool((gen < sc.vocab_size).all())

    # 3. schedule real serving steps across two pools with the paper policy
    def prefill_task(size):
        logits, _ = eng.prefill({"tokens": toks})
        jax.block_until_ready(logits)

    def decode_task(size):
        _, cache = eng.prefill({"tokens": toks[:, :4]})
        out, _ = eng.decode_run(toks[:, :1], cache, 4, 2)
        jax.block_until_ready(out)

    def slow(fn, n):
        def g(size):
            for _ in range(n):
                fn(size)
        return g

    fns = [{0: prefill_task, 1: slow(decode_task, 3)},
           {0: slow(prefill_task, 3), 1: decode_task}]
    vc = VirtualTimeCluster(fns)
    mu = vc.measure_rates(2, reps=3)
    types = [0] * 4 + [1] * 4
    x_cab = VirtualTimeCluster(fns).run_closed(
        ClusterScheduler(mu, policy="cab"), types,
        n_completions=60, warmup=10).throughput
    x_rd = VirtualTimeCluster(fns).run_closed(
        BaselineClusterScheduler(mu, "RD"), types,
        n_completions=60, warmup=10).throughput
    assert x_cab > 0 and x_rd > 0
    assert x_cab >= 0.9 * x_rd   # CAB never materially worse


def test_virtual_platform_matches_theory_deterministic():
    """With constant service times, CAB throughput == the closed form."""
    mu = np.array([[20.0, 15.0], [3.0, 8.0]])
    fns = [{i: (lambda s, t=1 / mu[i, j]: t) for i in range(2)}
           for j in range(2)]
    vc = VirtualTimeCluster(fns, measure_real=False)
    sol = cab_solve(mu, 10, 10)
    m = vc.run_closed(ClusterScheduler(mu, policy="cab"),
                      [0] * 10 + [1] * 10, n_completions=1500, warmup=300)
    assert m.throughput == pytest.approx(sol.x_max, rel=0.05)
