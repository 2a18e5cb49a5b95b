"""Priority-class subsystem: the flattening identity, exact class-axis
deltas, C=1 bit-identical reduction to the single-class solver/policies/
engine, weighted-objective gains with C>=2, weight-aware target caching,
and the strict-priority (PRIO) service order on both engines.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (PROPORTIONAL_POWER, PowerModel, cab_target_state,
                        exhaustive_solve, grin_solve, grin_solve_batch_jax,
                        system_throughput)
from repro.core.priority import (cab_priority_solve, class_energy_per_task,
                                 class_throughputs,
                                 class_throughputs_batch_jax,
                                 delta_w_add_block_priority,
                                 delta_w_remove_block_priority,
                                 delta_xw_add_block_priority,
                                 delta_xw_remove_block_priority,
                                 flatten_state, grin_priority_solve,
                                 grin_solve_priority_batch_jax, priority_mu,
                                 unflatten_state, weighted_system_throughput)
from repro.kernels.grin_moves import (block_move_gains_pallas,
                                      block_move_scores)
from repro.sched import SchedulerCore, get_policy
from repro.sched.priority import flat_mu, flatten_mixes
from repro.sim import (ClosedNetworkSimulator, SimConfig, make_distribution,
                       priority_sim_config, simulate_policy_jax)

DIST = make_distribution("exponential")


def _rand_state(rng, C, k, l, n_max=12):
    return rng.integers(0, n_max, size=(C, k, l))


# --------------------------------------------------- flattening identity

@pytest.mark.parametrize("seed", range(5))
def test_weighted_x_equals_flat_x_under_weighted_mu(seed):
    """The subsystem's load-bearing identity: sum_c w_c X_c of a (C, k, l)
    state == single-class X_sys of the class-major flattening under
    w_c * mu — exactly (float64 host forms)."""
    rng = np.random.default_rng(seed)
    C, k, l = rng.integers(1, 4), rng.integers(1, 4), rng.integers(2, 5)
    N = _rand_state(rng, C, k, l)
    mu = rng.uniform(1, 30, (k, l))
    w = rng.uniform(0.1, 8.0, C)
    assert weighted_system_throughput(N, mu, w) == pytest.approx(
        system_throughput(flatten_state(N), priority_mu(mu, w)), rel=1e-12)
    # unit weights: weighted == plain sum of class throughputs == flat X_sys
    assert class_throughputs(N, mu).sum() == pytest.approx(
        system_throughput(flatten_state(N), flat_mu(mu, C)), rel=1e-12)
    # batched jax form agrees with host per-class X
    xc = np.asarray(class_throughputs_batch_jax(
        jnp.asarray(N[None]), jnp.asarray(mu)))[0]
    np.testing.assert_allclose(xc, class_throughputs(N, mu), rtol=1e-5)


@pytest.mark.parametrize("seed", range(3))
def test_class_axis_block_deltas_exact(seed):
    """delta_x/delta_w with a class axis are EXACT: applying the block move
    reproduces the predicted weighted-X / power-rate change."""
    rng = np.random.default_rng(100 + seed)
    C, k, l = 2, 2, 3
    N = _rand_state(rng, C, k, l) + 1
    mu = rng.uniform(1, 30, (k, l))
    w = rng.uniform(0.5, 5.0, C)
    power = PowerModel(alpha=0.5)
    Pf = np.tile(power.power_matrix(mu), (C, 1))
    for c in range(C):
        for p in range(k):
            for m in (1, 2, 4):
                dplus = delta_xw_add_block_priority(N, mu, w, c, p, m)
                dminus = delta_xw_remove_block_priority(N, mu, w, c, p, m)
                wplus = delta_w_add_block_priority(N, mu, w, power, c, p, m)
                wminus = delta_w_remove_block_priority(N, mu, w, power, c, p,
                                                      m)
                x0 = weighted_system_throughput(N, mu, w)
                flat = flatten_state(N)
                w0 = system_throughput(flat, Pf)     # total power rate
                for j in range(l):
                    Na = N.copy()
                    Na[c, p, j] += m
                    assert dplus[j] == pytest.approx(
                        weighted_system_throughput(Na, mu, w) - x0, abs=1e-9)
                    assert wplus[j] == pytest.approx(
                        system_throughput(flatten_state(Na), Pf) - w0,
                        abs=1e-9)
                    if N[c, p, j] >= m:
                        Nr = N.copy()
                        Nr[c, p, j] -= m
                        assert dminus[j] == pytest.approx(
                            weighted_system_throughput(Nr, mu, w) - x0,
                            abs=1e-9)
                        assert wminus[j] == pytest.approx(
                            system_throughput(flatten_state(Nr), Pf) - w0,
                            abs=1e-9)
                    else:
                        assert dminus[j] == np.inf and wminus[j] == np.inf


def test_kernel_scores_priority_batch_bit_identically():
    """The Pallas gain kernel is class-aware through the flattened row axis:
    on a (B, C*k, l) priority batch its scores/selections are bit-identical
    to the jnp reference (interpret mode off-TPU)."""
    rng = np.random.default_rng(7)
    C, k, l = 2, 2, 3
    w = np.array([4.0, 1.0])
    mu_w = priority_mu(rng.uniform(1, 30, (k, l)), w)
    N = np.stack([flatten_state(_rand_state(rng, C, k, l) + 1)
                  for _ in range(5)]).astype(np.float32)
    mus = np.broadcast_to(mu_w.astype(np.float32), N.shape)
    sizes = np.array([4.0, 2.0, 1.0], np.float32)
    g_ref, bi_ref, bg_ref, base_ref = block_move_scores(
        N, mus, sizes, use_kernel=False)
    g_k, bi_k, bg_k, base_k = block_move_gains_pallas(
        N, mus, sizes, interpret=True)
    np.testing.assert_array_equal(np.asarray(g_ref), np.asarray(g_k))
    np.testing.assert_array_equal(np.asarray(bi_ref), np.asarray(bi_k))
    np.testing.assert_array_equal(np.asarray(bg_ref), np.asarray(bg_k))
    np.testing.assert_array_equal(np.asarray(base_ref), np.asarray(base_k))


# --------------------------------------------------- C=1 reduction

def test_c1_unit_weight_solvers_bit_identical():
    rng = np.random.default_rng(11)
    mu = rng.uniform(1, 30, (3, 3))
    mix = np.array([[10, 8, 12]])
    rp = grin_priority_solve(mu, mix, [1.0])
    r0 = grin_solve(mu, mix[0])
    np.testing.assert_array_equal(rp.N[0], r0.N)
    assert rp.weighted_x == r0.x_sys
    # batched device solver: identical placements AND identical x floats
    Np, xp, cp, mp = grin_solve_priority_batch_jax(mu, mix[:, None, :], [1.0])
    N0, x0, c0, m0 = grin_solve_batch_jax(mu, mix)
    np.testing.assert_array_equal(np.asarray(Np)[:, 0], np.asarray(N0))
    np.testing.assert_array_equal(np.asarray(xp), np.asarray(x0))
    np.testing.assert_array_equal(np.asarray(mp), np.asarray(m0))
    # CAB-P == CAB
    mu2 = np.array([[20.0, 5.0], [4.0, 18.0]])
    np.testing.assert_array_equal(
        cab_priority_solve(mu2, np.array([[6, 7]]), [1.0])[0],
        cab_target_state(mu2, np.array([6, 7])))


def test_c1_unit_weight_policy_routing_identical():
    rng = np.random.default_rng(12)
    mu = rng.uniform(1, 30, (3, 4))
    mix = np.array([8, 9, 7])
    a = SchedulerCore("grin", mu).reset(mu, mix)
    b = SchedulerCore(get_policy("grin-p"), mu).reset(mu, mix)
    types = rng.integers(0, 3, 300)
    assert [a.route(int(t)) for t in types] == \
        [b.route(int(t)) for t in types]
    np.testing.assert_array_equal(a.counts, b.counts)
    # route_many too (same jitted kernel, same target)
    a2 = SchedulerCore("grin", mu).reset(mu, mix)
    b2 = SchedulerCore(get_policy("grin-p"), mu).reset(mu, mix)
    np.testing.assert_array_equal(a2.route_many(types), b2.route_many(types))


def test_c1_engine_metrics_identical_with_and_without_classes():
    """A single-class config with an explicit all-zeros class map must
    produce bit-identical engine metrics to the same config without one,
    on BOTH engines (the per-class machinery adds no stream consumption)."""
    rng = np.random.default_rng(13)
    mu = rng.uniform(1, 30, (3, 3))
    base = dict(mu=mu, n_programs_per_type=np.array([10, 10, 10]),
                distribution=DIST, order="PS", n_completions=2000,
                warmup_completions=400, seed=3)
    plain = SimConfig(**base)
    tagged = SimConfig(class_of_type=np.zeros(3, np.int64), **base)
    h0 = ClosedNetworkSimulator(plain).run("grin")
    h1 = ClosedNetworkSimulator(tagged).run(get_policy("grin-p"))
    assert h0.throughput == h1.throughput
    assert h0.mean_energy == h1.mean_energy
    assert h0.mean_response_time == h1.mean_response_time
    d0 = simulate_policy_jax(plain, SchedulerCore("grin", mu))
    d1 = simulate_policy_jax(tagged, SchedulerCore(get_policy("grin-p"), mu))
    assert d0.throughput == d1.throughput
    assert d0.mean_energy == d1.mean_energy
    assert np.allclose(d1.class_throughput.sum(), d1.throughput, rtol=1e-6)


# --------------------------------------------------- C>=2 weighted gains

def test_weighted_solver_beats_class_blind_on_skewed_weights():
    rng = np.random.default_rng(14)
    mu = rng.uniform(1, 30, (3, 3))
    mixes = np.array([[4, 3, 2], [6, 5, 10]])
    w = np.array([4.0, 1.0])
    rp = grin_priority_solve(mu, mixes, w)
    rb = grin_priority_solve(mu, mixes, np.ones(2))    # class-blind
    assert rp.weighted_x >= weighted_system_throughput(rb.N, mu, w) - 1e-9
    assert rp.weighted_x > weighted_system_throughput(rb.N, mu, w) * 1.05
    # per-class energy closed form is finite where the class completes work
    e = class_energy_per_task(rp.N, mu, PROPORTIONAL_POWER)
    assert np.isfinite(e[rp.class_x > 0]).all()


def test_cab_p_matches_exhaustive_on_flat_weighted_problem():
    """Two classes of one type on two pools: CAB-P == the exhaustive optimum
    of the flattened weighted problem."""
    rng = np.random.default_rng(15)
    for _ in range(4):
        mu = rng.uniform(1, 30, (1, 2))
        mixes = rng.integers(1, 8, size=(2, 1))
        w = rng.uniform(0.5, 6.0, 2)
        target = cab_priority_solve(mu, mixes, w)
        mu_w = priority_mu(mu, w)
        _, x_opt = exhaustive_solve(mu_w, flatten_mixes(mixes))
        assert system_throughput(flatten_state(target), mu_w) == \
            pytest.approx(x_opt, rel=1e-9)
    with pytest.raises(ValueError, match="grin-p"):
        cab_priority_solve(np.ones((2, 2)), np.ones((2, 2), np.int64),
                           [1.0, 1.0])


# --------------------------------------------------- weight-aware caching

def test_target_cache_keys_include_class_weights():
    """Regression: a class-weight update must never be served a stale
    target out of the warm cache (keys include the weight vector)."""
    rng = np.random.default_rng(16)
    mu = rng.uniform(1, 30, (2, 3))
    mixes = np.array([[5, 3], [7, 9]])
    pol = get_policy("grin-p", weights=[4.0, 1.0])
    core = SchedulerCore(pol, flat_mu(mu, 2))
    flat = flatten_mixes(mixes)
    core.reset(n_tasks=flat)
    t_skew = core._target_for(flat).copy()
    assert core.resolves == 1
    core._target_for(flat)
    assert core.resolves == 1                 # warm hit under same weights
    core.set_class_weights([1.0, 1.0])
    t_unit = core._target_for(flat)
    assert core.resolves == 2                 # NOT served the stale target
    assert not np.array_equal(t_skew, t_unit)
    core.set_class_weights([4.0, 1.0])
    np.testing.assert_array_equal(core._target_for(flat), t_skew)
    assert core.resolves == 2                 # old entry still keyed + valid
    # warm_targets keys include weights too
    assert core.warm_targets(flat[None]) == 0
    core.set_class_weights([2.0, 1.0])
    assert core.warm_targets(flat[None]) == 1
    with pytest.raises(ValueError, match="class_weights"):
        SchedulerCore("grin", mu).set_class_weights([1.0])
    # validation: negative weights and length changes are rejected up front
    with pytest.raises(ValueError, match="nonneg"):
        core.set_class_weights([1.0, -5.0])
    with pytest.raises(ValueError, match="nonneg"):
        core.set_class_weights([1.0, 2.0, 3.0])


def test_elastic_what_if_weighted_x_physical_energy():
    """Priority what-ifs: the X grids are the policy's weighted objective,
    while energy and EDP stay physical (weights never scale watts or the
    EDP delay term)."""
    from repro.core.energy import edp as edp_closed
    from repro.core.energy import expected_energy_per_task
    rng = np.random.default_rng(20)
    mu = rng.uniform(1, 30, (2, 3))
    mixes = np.array([[2, 2], [6, 6]])
    w = np.array([4.0, 1.0])
    pol = get_policy("grin-p", weights=w)
    core = SchedulerCore(pol, flat_mu(mu, 2))
    flat = flatten_mixes(mixes)
    out = core.elastic_what_if(mixes=flat[None])
    target = unflatten_state(core._target_for(flat), 2)
    assert out["base"][0] == pytest.approx(
        weighted_system_throughput(target, mu, w), rel=1e-4)
    mu_f = flat_mu(mu, 2)
    assert out["base_energy"][0] == pytest.approx(
        expected_energy_per_task(flatten_state(target), mu_f,
                                 PROPORTIONAL_POWER), rel=1e-4)
    assert out["base_edp"][0] == pytest.approx(
        edp_closed(flatten_state(target), mu_f, PROPORTIONAL_POWER),
        rel=1e-4)


# --------------------------------------------------- PRIO service order

def test_prio_single_class_is_fcfs_exactly():
    rng = np.random.default_rng(17)
    mu = rng.uniform(1, 30, (2, 3))
    base = dict(mu=mu, n_programs_per_type=np.array([8, 9]),
                distribution=DIST, n_completions=3000,
                warmup_completions=600, seed=0)
    for policy in ("grin", "lb"):          # fast path + compat path
        a = ClosedNetworkSimulator(SimConfig(order="FCFS", **base)).run(policy)
        b = ClosedNetworkSimulator(SimConfig(order="PRIO", **base)).run(policy)
        assert a.throughput == b.throughput, policy
        assert a.mean_response_time == b.mean_response_time, policy
        assert a.mean_power == b.mean_power, policy


def test_prio_cuts_high_class_latency_on_both_engines():
    """The point of the subsystem: under PRIO, class-0 tasks stop queueing
    behind batch work — class-0 E[T] drops vs FCFS while the placement and
    population stay fixed. Host and device agree."""
    rng = np.random.default_rng(18)
    mu = rng.uniform(1, 30, (2, 3))
    mixes = np.array([[2, 1], [7, 10]])    # small latency class, big batch
    pol = get_policy("grin-p", weights=[8.0, 1.0])
    mets = {}
    for order in ("FCFS", "PRIO"):
        cfg = priority_sim_config(mu, mixes, distribution=DIST, order=order,
                                  n_completions=6000,
                                  warmup_completions=1200, seed=2)
        mets[order] = (ClosedNetworkSimulator(cfg).run(pol),
                       simulate_policy_jax(cfg, SchedulerCore(pol, cfg.mu)))
    for host, dev in mets.values():
        assert dev.class_response_time[0] == pytest.approx(
            host.class_response_time[0], rel=0.15)
    assert mets["PRIO"][0].class_response_time[0] < \
        mets["FCFS"][0].class_response_time[0]
    assert mets["PRIO"][1].class_response_time[0] < \
        mets["FCFS"][1].class_response_time[0]


def test_per_class_distributions_and_config_validation():
    rng = np.random.default_rng(19)
    mu = rng.uniform(1, 30, (2, 2))
    mixes = np.array([[3, 2], [4, 5]])
    cfg = priority_sim_config(
        mu, mixes, class_distributions=(make_distribution("constant"), DIST),
        order="PS", n_completions=2000, warmup_completions=400, seed=0)
    host = ClosedNetworkSimulator(cfg).run(get_policy("grin-p",
                                                      weights=[2.0, 1.0]))
    dev = simulate_policy_jax(cfg, SchedulerCore(
        get_policy("grin-p", weights=[2.0, 1.0]), cfg.mu))
    assert dev.throughput == pytest.approx(host.throughput, rel=0.1)
    assert host.class_throughput.shape == (2,)
    with pytest.raises(ValueError, match="class_distributions"):
        priority_sim_config(mu, mixes, class_distributions=(DIST,),
                            n_completions=100, warmup_completions=10)
    with pytest.raises(ValueError, match="distribution"):
        priority_sim_config(mu, mixes, n_completions=100,
                            warmup_completions=10)
    with pytest.raises(ValueError, match="order"):
        ClosedNetworkSimulator(SimConfig(
            mu=mu, n_programs_per_type=np.array([5, 5]), distribution=DIST,
            order="LIFO", n_completions=100, warmup_completions=10))
