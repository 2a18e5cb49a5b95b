"""Energy-aware scheduling (PR 4 tentpole): batched JAX E/EDP forms vs the
host float64 model, closed-form per-move energy deltas, the objective switch
through the block-move solver and its Pallas kernel, the GrIn-E / GrIn-EDP /
CAB-E policies, and elastic what-if energy pricing."""
import numpy as np
import pytest
from _prop import given, st

from repro.core import (CONSTANT_POWER, PROPORTIONAL_POWER,
                        delta_edp_move_block, delta_energy_move_block,
                        delta_w_add_block, delta_w_remove_block, edp,
                        edp_batch_jax, expected_delay,
                        expected_delay_batch_jax, expected_energy_batch_jax,
                        expected_energy_per_task, grin_energy_solve,
                        grin_solve, grin_solve_batch_jax, power_matrix_jax,
                        power_rate_columns, random_affinity_matrix,
                        system_throughput)
from repro.core.affinity import PowerModel
from repro.kernels.grin_moves import (OBJ_E, OBJ_E_GUARD, OBJ_EDP, OBJ_XE,
                                      block_move_scores)
from repro.sched import SchedulerCore, get_policy, solve_targets_jax
from repro.sim import ClosedNetworkSimulator, SimConfig, make_distribution

POWER_HALF = PowerModel(alpha=0.5)


def _random_states(rng, b, k, l, hi=12):
    N = rng.integers(0, hi, size=(b, k, l))
    N[:, :, 0] += (N.sum(axis=2) == 0)      # no empty rows
    return N


# ------------------------------------------------ batched JAX forms (eq. 19-21)

def test_batched_energy_delay_edp_match_host_model():
    rng = np.random.default_rng(0)
    mu = random_affinity_matrix(rng, 3, 4)
    Ns = _random_states(rng, 16, 3, 4)
    for power in (CONSTANT_POWER, PROPORTIONAL_POWER, POWER_HALF):
        P = power.power_matrix(mu)
        e = np.asarray(expected_energy_batch_jax(Ns, mu, P))
        t = np.asarray(expected_delay_batch_jax(Ns, mu))
        d = np.asarray(edp_batch_jax(Ns, mu, P))
        for i, N in enumerate(Ns):
            assert e[i] == pytest.approx(
                expected_energy_per_task(N, mu, power), rel=1e-5)
            assert t[i] == pytest.approx(expected_delay(N, mu), rel=1e-5)
            assert d[i] == pytest.approx(edp(N, mu, power), rel=1e-4)
    # power matrix device form matches the host model
    np.testing.assert_allclose(
        np.asarray(power_matrix_jax(mu, POWER_HALF)),
        POWER_HALF.power_matrix(mu), rtol=1e-6)


# ------------------------------------------------------ per-move energy deltas

@given(st.integers(0, 10_000))
def test_energy_move_deltas_exact(seed):
    """Closed-form dW / dE / dEDP equal the full recompute for random block
    moves (the surface the device objectives score)."""
    rng = np.random.default_rng(seed)
    k, l = rng.integers(2, 5, size=2)
    mu = random_affinity_matrix(rng, k, l)
    power = PowerModel(alpha=float(rng.uniform(0.0, 1.0)))
    P = power.power_matrix(mu)
    N = rng.integers(0, 9, size=(k, l))
    p = rng.integers(k)
    if N[p].sum() == 0:
        N[p, 0] = 4
    src = rng.choice(np.flatnonzero(N[p] > 0))
    m = int(rng.integers(1, N[p, src] + 1))
    dst = int((src + 1) % l)
    N2 = N.copy()
    N2[p, src] -= m
    N2[p, dst] += m
    dw = (delta_w_remove_block(N, P, p, m)[src]
          + delta_w_add_block(N, P, p, m)[dst])
    assert power_rate_columns(N2, P).sum() - power_rate_columns(N, P).sum() \
        == pytest.approx(dw, abs=1e-9)
    x2 = system_throughput(N2, mu)
    de = delta_energy_move_block(N, mu, P, p, src, dst, m)
    dedp = delta_edp_move_block(N, mu, P, p, src, dst, m)
    if x2 <= 0:
        assert not np.isfinite(de) and not np.isfinite(dedp)
    else:
        assert expected_energy_per_task(N2, mu, power) \
            - expected_energy_per_task(N, mu, power) \
            == pytest.approx(de, abs=1e-9)
        assert edp(N2, mu, power) - edp(N, mu, power) \
            == pytest.approx(dedp, abs=1e-8)


# ----------------------------------------------------------- host energy GrIn

@given(st.integers(0, 5_000))
def test_grin_e_keeps_throughput_and_never_raises_energy(seed):
    """max-x-e: same throughput class as GrIn (the plateau polish only takes
    moves with dX >= -tol) and E[E] never above plain GrIn's."""
    rng = np.random.default_rng(seed)
    k, l = rng.integers(2, 5, size=2)
    mu = random_affinity_matrix(rng, k, l)
    nt = rng.integers(1, 8, size=k)
    power = PowerModel(alpha=float(rng.uniform(0.0, 1.0)))
    g = grin_solve(mu, nt)
    ge = grin_energy_solve(mu, nt, power, "max-x-e")
    assert ge.converged
    assert np.all(ge.N.sum(axis=1) == nt) and np.all(ge.N >= 0)
    assert ge.x_sys >= g.x_sys - 1e-6 * (1 + g.x_sys)
    assert ge.energy <= expected_energy_per_task(g.N, mu, power) + 1e-9


@given(st.integers(0, 5_000))
def test_min_e_and_min_edp_reach_local_minima(seed):
    """min-e / min-edp fixed points admit no improving single move (checked
    against the exact closed-form deltas)."""
    rng = np.random.default_rng(seed)
    k, l = rng.integers(2, 4, size=2)
    mu = random_affinity_matrix(rng, k, l)
    nt = rng.integers(1, 6, size=k)
    power = PowerModel(alpha=float(rng.uniform(0.0, 1.0)))
    P = power.power_matrix(mu)
    for obj, delta in (("min-e", delta_energy_move_block),
                       ("min-edp", delta_edp_move_block)):
        r = grin_energy_solve(mu, nt, power, obj)
        assert r.converged
        assert np.all(r.N.sum(axis=1) == nt) and np.all(r.N >= 0)
        for p in range(k):
            for s in range(l):
                if r.N[p, s] == 0:
                    continue
                for d in range(l):
                    if s != d:
                        dv = delta(r.N, mu, P, p, s, d, 1)
                        assert not np.isfinite(dv) or dv >= -1e-9
    with pytest.raises(ValueError, match="unknown objective"):
        grin_energy_solve(mu, nt, power, "warp")


# ------------------------------------------------------ device objective switch

def test_batched_objectives_converge_and_order_sensibly():
    rng = np.random.default_rng(7)
    mus = np.stack([random_affinity_matrix(rng, 4, 5) for _ in range(6)])
    mixes = rng.multinomial(200, [0.25] * 4, size=6)
    results = {}
    for obj in ("max-x", "max-x-e", "min-e", "min-edp"):
        N, xs, conv, _ = grin_solve_batch_jax(mus, mixes, objective=obj,
                                              power=CONSTANT_POWER)
        assert np.asarray(conv).all(), obj
        N = np.asarray(N)
        np.testing.assert_array_equal(N.sum(axis=2), mixes)
        results[obj] = (N, np.asarray(xs))
    for i, mu in enumerate(mus):
        e = {obj: expected_energy_per_task(results[obj][0][i], mu,
                                           CONSTANT_POWER)
             for obj in results}
        x = {obj: system_throughput(results[obj][0][i], mu)
             for obj in results}
        # the tie-broken solver keeps max-x's throughput (within f32 noise)
        # and never pays energy for it
        assert x["max-x-e"] >= x["max-x"] - 1e-4 * (1 + x["max-x"])
        assert e["max-x-e"] <= e["max-x"] + 1e-6
        # the direct energy descent is the cheapest of the four
        assert e["min-e"] <= min(e.values()) + 1e-9
    with pytest.raises(ValueError, match="unknown objective"):
        grin_solve_batch_jax(mus, mixes, objective="warp")


def test_energy_objective_kernel_bit_matches_reference(monkeypatch):
    """The Pallas kernel (interpret mode) and the jnp reference agree BIT
    for every energy objective — gains, selection, and convergence signal."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(1)
    for b, k, l, m in [(5, 3, 3, 6), (9, 4, 6, 8)]:
        N = rng.integers(0, 20, size=(b, k, l)).astype(np.float32)
        mu = rng.uniform(1, 30, size=(b, k, l)).astype(np.float32)
        P = (mu ** 0.5).astype(np.float32)
        sizes = (2.0 ** np.arange(m - 1, -1, -1)).astype(np.float32)
        for obj in (OBJ_XE, OBJ_E, OBJ_EDP, OBJ_E_GUARD):
            ref = block_move_scores(N, mu, sizes, use_kernel=False, P=P,
                                    objective=obj)
            pal = block_move_scores(N, mu, sizes, use_kernel=True, P=P,
                                    objective=obj)
            for r, p_ in zip(ref, pal):
                np.testing.assert_array_equal(np.asarray(r), np.asarray(p_))
        with pytest.raises(ValueError, match="power matrix"):
            block_move_scores(N, mu, sizes, use_kernel=False, objective=OBJ_E)


def test_batched_solver_matches_host_energy_solver_quality():
    """Device GrIn-E placements reach the host solver's (X, E) quality class
    and their f32 energies match the host f64 closed form."""
    rng = np.random.default_rng(3)
    mu = random_affinity_matrix(rng, 3, 3)
    mixes = rng.multinomial(30, [1 / 3] * 3, size=8)
    targets, _ = solve_targets_jax(mu, mixes, objective="max-x-e",
                                   power=POWER_HALF)
    for mix, N in zip(mixes, targets):
        h = grin_energy_solve(mu, mix, POWER_HALF, "max-x-e")
        assert system_throughput(N, mu) >= 0.95 * h.x_sys
        e_dev = float(expected_energy_batch_jax(
            N[None], mu, POWER_HALF.power_matrix(mu))[0])
        assert e_dev == pytest.approx(
            expected_energy_per_task(N, mu, POWER_HALF), rel=1e-5)


# ------------------------------------------------------------------- policies

def test_energy_policy_registry_and_flags():
    for key, name in (("grin-e", "GrIn-E"), ("grin-edp", "GrIn-EDP"),
                      ("cab-e", "CAB-E")):
        pol = get_policy(key, power=CONSTANT_POWER)
        assert pol.name == name and pol.power is CONSTANT_POWER
    assert get_policy("grin-e").jax_objective == "max-x-e"
    assert get_policy("grin-edp").jax_objective == "min-edp"
    assert get_policy("cab-e").pool_limit == 2
    with pytest.raises(ValueError, match="two-pool"):
        get_policy("cab-e").solve_target(np.ones((2, 3)), np.array([2, 2]))


def test_cab_e_matches_cab_throughput_and_minimizes_energy():
    """CAB-E keeps the Table-1 maximum and, over the whole (N11, N22) map,
    no equal-throughput state has lower energy."""
    from repro.core import throughput_map_2x2
    from repro.core.throughput import state_from_pair
    for mu in (np.array([[20.0, 15.0], [3.0, 8.0]]),
               np.array([[9.0, 4.0], [9.0, 4.0]]),      # big.LITTLE family
               np.full((2, 2), 7.0)):                   # homogeneous family
        n1 = n2 = 8
        Ne = get_policy("cab-e", power=POWER_HALF).solve_target(
            mu, np.array([n1, n2]))
        xmap = throughput_map_2x2(n1, n2, mu)
        xe = system_throughput(Ne, mu)
        assert xe == pytest.approx(float(xmap.max()), rel=1e-5)
        ee = expected_energy_per_task(Ne, mu, POWER_HALF)
        for i in range(n1 + 1):
            for j in range(n2 + 1):
                if xmap[i, j] >= xmap.max() * (1 - 1e-6):
                    s = state_from_pair(i, j, n1, n2)
                    assert ee <= expected_energy_per_task(
                        s, mu, POWER_HALF) + 1e-6


def test_grin_e_routes_through_simulator():
    mu = np.random.default_rng(4).uniform(1, 30, (3, 3))
    cfg = SimConfig(mu=mu, n_programs_per_type=np.array([6, 6, 6]),
                    distribution=make_distribution("exponential"),
                    order="PS", power=POWER_HALF, n_completions=1500,
                    warmup_completions=300, seed=0)
    m = ClosedNetworkSimulator(cfg).run(
        get_policy("grin-e", power=POWER_HALF))
    assert m.throughput > 0
    assert m.little_product == pytest.approx(18.0, rel=0.05)
    assert m.mean_power / m.throughput == pytest.approx(m.mean_energy,
                                                        rel=0.03)


# --------------------------------------------------------- elastic pricing

def test_elastic_what_if_prices_energy():
    mu = np.random.default_rng(4).uniform(1, 30, (3, 3))
    mixes = np.array([[6, 7, 5], [3, 3, 3]])
    core = SchedulerCore("grin-e", mu)
    out = core.elastic_what_if(mixes,
                               added_columns=np.array([[40.0, 40.0, 40.0]]))
    assert out["base_energy"].shape == (2,)
    assert out["pool_lost_energy"].shape == (3, 2)
    assert out["pool_added_energy"].shape == (1, 2)
    assert out["base_edp"].shape == (2,)
    # proportional power (the policy default): E[E] == 1 everywhere (eq. 23)
    np.testing.assert_allclose(out["base_energy"], 1.0, rtol=1e-5)
    np.testing.assert_allclose(out["pool_lost_energy"], 1.0, rtol=1e-5)
    # EDP = ntot / X under proportional power
    np.testing.assert_allclose(
        out["base_edp"], mixes.sum(axis=1) / out["base"], rtol=1e-5)
    # constant power: E = l_busy / X, so pricing under a different model
    # changes the surface
    out_c = core.elastic_what_if(mixes, power=CONSTANT_POWER)
    assert (out_c["base_energy"] < 1.0).all()
    # losing a pool can never improve EDP
    assert (out_c["pool_lost_edp"] >= out_c["base_edp"][None, :] - 1e-6).all()
