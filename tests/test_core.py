"""Paper-core invariants: CAB optimality (Table 1), GrIn monotonicity
(Lemma 8), closed forms (eq. 16-18), energy identities (eq. 22-23)."""
import numpy as np
import pytest
from _prop import given, st

from repro.core import (CONSTANT_POWER, PROPORTIONAL_POWER, AffinityCase,
                        cab_closed_form_x, cab_solve, classify_2x2,
                        delta_x_add, delta_x_remove, exhaustive_solve,
                        expected_energy_per_task, grin_init, grin_solve,
                        grin_solve_batch_jax, random_affinity_matrix,
                        system_throughput, throughput_map_2x2)
from repro.core.energy import edp, expected_delay, scenario_identities


# ---------------------------------------------------------------- classify

def test_classify_paper_cases():
    assert classify_2x2([[20, 15], [3, 8]]) is AffinityCase.P1_BIASED
    assert classify_2x2([[20, 5], [3, 8]]) is AffinityCase.GENERAL_SYMMETRIC
    assert classify_2x2([[5, 3], [9, 40]]) is AffinityCase.P2_BIASED
    assert classify_2x2([[7, 7], [7, 7]]) is AffinityCase.HOMOGENEOUS
    assert classify_2x2([[9, 4], [9, 4]]) is AffinityCase.BIG_LITTLE
    assert classify_2x2([[9, 4], [4, 9]]) is AffinityCase.SYMMETRIC


rates = st.floats(min_value=0.5, max_value=50.0, allow_nan=False)


@given(st.tuples(rates, rates, rates, rates),
       st.integers(1, 12), st.integers(1, 12))
def test_cab_matches_exhaustive_argmax(vals, n1, n2):
    """Property: CAB's Table-1 state achieves the exact maximum of the
    (N11, N22) throughput map for every valid affinity matrix."""
    a, b, c, d = vals
    mu = np.array([[max(a, b), min(a, b)], [min(c, d), max(c, d)]])
    if classify_2x2(mu) is AffinityCase.INVALID:
        return
    sol = cab_solve(mu, n1, n2)
    xmap = throughput_map_2x2(n1, n2, mu)
    assert sol.x_max == pytest.approx(float(xmap.max()), rel=1e-5)


def test_cab_closed_forms_match_state_throughput():
    for mu, n1, n2 in [(np.array([[20.0, 15.0], [3.0, 8.0]]), 7, 13),
                       (np.array([[20.0, 5.0], [3.0, 8.0]]), 9, 11),
                       (np.array([[5.0, 3.0], [4.0, 40.0]]), 10, 10)]:
        sol = cab_solve(mu, n1, n2)
        assert sol.x_max == pytest.approx(
            cab_closed_form_x(sol.case, n1, n2, mu), rel=1e-9)


def test_af_counterintuitive_structure():
    """P1-biased: exactly ONE task alone on P1 (the paper's discovery)."""
    sol = cab_solve(np.array([[20.0, 15.0], [3.0, 8.0]]), 10, 10)
    assert sol.policy == "AF"
    assert sol.state[0, 0] == 1 and sol.state[1, 0] == 0


# ---------------------------------------------------------------- GrIn

@given(st.integers(0, 10_000))
def test_grin_move_deltas_exact(seed):
    """dX formulas (eq. 33-36): moving one task changes X_sys by exactly
    dminus[src] + dplus[dst]."""
    rng = np.random.default_rng(seed)
    k, l = rng.integers(2, 5, size=2)
    mu = random_affinity_matrix(rng, k, l)
    N = rng.integers(0, 6, size=(k, l))
    p = rng.integers(k)
    if N[p].sum() == 0:
        N[p, 0] = 2
    src = rng.choice(np.flatnonzero(N[p] > 0))
    dst = (src + 1) % l
    dplus = delta_x_add(N, mu, p)
    dminus = delta_x_remove(N, mu, p)
    x0 = system_throughput(N, mu)
    N2 = N.copy()
    N2[p, src] -= 1
    N2[p, dst] += 1
    x1 = system_throughput(N2, mu)
    assert x1 - x0 == pytest.approx(dminus[src] + dplus[dst], abs=1e-9)


@given(st.integers(0, 10_000))
def test_grin_monotone_and_local_max(seed):
    """Lemma 8: GrIn never decreases X; result is a single-move local max."""
    rng = np.random.default_rng(seed)
    k, l = rng.integers(2, 5, size=2)
    mu = random_affinity_matrix(rng, k, l)
    nt = rng.integers(1, 8, size=k)
    init_x = system_throughput(grin_init(mu, nt), mu)
    res = grin_solve(mu, nt)
    assert res.x_sys >= init_x - 1e-9
    assert np.all(res.N.sum(axis=1) == nt)
    assert np.all(res.N >= 0)
    # no improving single move exists
    for p in range(k):
        dplus = delta_x_add(res.N, mu, p)
        dminus = delta_x_remove(res.N, mu, p)
        for s in range(l):
            if res.N[p, s] == 0:
                continue
            for d in range(l):
                if s != d:
                    assert dminus[s] + dplus[d] <= 1e-9


def test_grin_near_optimal_on_paper_scale():
    rng = np.random.default_rng(42)
    gaps = []
    for _ in range(100):
        mu = random_affinity_matrix(rng, 3, 3)
        nt = rng.integers(2, 10, size=3)
        g = grin_solve(mu, nt)
        _, xopt = exhaustive_solve(mu, nt)
        gaps.append((xopt - g.x_sys) / xopt)
    assert np.mean(gaps) < 0.03          # paper: 1.6% average


def test_grin_jax_matches_numpy_quality():
    """The device block solver against host Algorithm 2."""
    rng = np.random.default_rng(3)
    mus, nts = [], []
    for _ in range(10):
        mus.append(random_affinity_matrix(rng, 4, 3))
        nts.append(rng.integers(1, 10, size=4))
    N, _, _, _ = grin_solve_batch_jax(np.stack(mus), np.stack(nts))
    for mu, nt, Nj in zip(mus, nts, np.asarray(N)):
        xj = system_throughput(Nj, mu)
        xn = grin_solve(mu, nt).x_sys
        assert xj >= 0.95 * xn
        assert np.allclose(Nj.sum(1), nt)


# ---------------------------------------------------------------- energy

def test_energy_identities():
    """eq. 22-23 with both processors busy."""
    mu = np.array([[20.0, 15.0], [3.0, 8.0]])
    N = np.array([[1, 9], [0, 10]])
    x = system_throughput(N, mu)
    ids = scenario_identities(N, mu)
    assert expected_energy_per_task(N, mu, PROPORTIONAL_POWER) == \
        pytest.approx(ids["prop_power_energy"], rel=1e-9)
    assert expected_energy_per_task(N, mu, CONSTANT_POWER) == \
        pytest.approx(ids["const_power_energy"], rel=1e-9)
    assert edp(N, mu, PROPORTIONAL_POWER) == pytest.approx(20 / x, rel=1e-9)
    assert expected_delay(N, mu) == pytest.approx(20 / x, rel=1e-9)


def test_max_throughput_minimizes_energy_and_edp():
    """Lemma 6: under scenarios 1-2, argmax X == argmin E == argmin EDP."""
    mu = np.array([[20.0, 15.0], [3.0, 8.0]])
    n1 = n2 = 10
    xmap = throughput_map_2x2(n1, n2, mu)
    states = [(i, j) for i in range(n1 + 1) for j in range(n2 + 1)]
    # restrict to states with both processors busy (no idle columns)
    busy = [(i, j) for (i, j) in states
            if (i + (n2 - j)) > 0 and (j + (n1 - i)) > 0]
    from repro.core.throughput import state_from_pair
    best_x = max(busy, key=lambda s: xmap[s])
    # constant power: argmin E == argmax X (E = l*k/X, eq. 22)
    best_e = min(busy, key=lambda s: expected_energy_per_task(
        state_from_pair(*s, n1, n2), mu, CONSTANT_POWER))
    assert xmap[best_x] == pytest.approx(xmap[best_e], rel=1e-6)
    # proportional power: E == k for every state (eq. 23); argmin EDP == argmax X
    for s in busy[:20]:
        assert expected_energy_per_task(
            state_from_pair(*s, n1, n2), mu, PROPORTIONAL_POWER) == \
            pytest.approx(1.0, rel=1e-9)
    best_edp = min(busy, key=lambda s: edp(
        state_from_pair(*s, n1, n2), mu, PROPORTIONAL_POWER))
    assert xmap[best_x] == pytest.approx(xmap[best_edp], rel=1e-6)


def _busy_states(n_tasks, l):
    """All placements with every column non-empty (small instances only)."""
    import itertools
    from repro.core.exhaustive import compositions
    rows = [list(compositions(int(n), l)) for n in n_tasks]
    for combo in itertools.product(*rows):
        N = np.asarray(combo, dtype=np.int64)
        if (N.sum(axis=0) > 0).all():
            yield N


@given(st.integers(0, 2_000))
def test_max_throughput_minimizes_energy_and_edp_general(seed):
    """Lemma 6 generalized to random k x l: over every all-columns-busy
    placement, argmax X == argmin E (constant power, E = l/X), E is the
    constant k_coeff under proportional power (eq. 23), and argmin EDP ==
    argmax X under both scenarios."""
    rng = np.random.default_rng(seed)
    k, l = rng.integers(2, 4, size=2)
    mu = random_affinity_matrix(rng, k, l)
    nt = rng.integers(1, 5, size=k)
    if nt.sum() < l:                       # not enough tasks to fill columns
        nt[0] += l - nt.sum()
    states = list(_busy_states(nt, l))
    if not states:
        return
    xs = np.array([system_throughput(N, mu) for N in states])
    e_const = np.array([expected_energy_per_task(N, mu, CONSTANT_POWER)
                        for N in states])
    x_best = xs.max()
    assert xs[np.argmin(e_const)] == pytest.approx(x_best, rel=1e-9)
    np.testing.assert_allclose(e_const, l / xs, rtol=1e-9)   # eq. 22
    for N in states[:20]:
        assert expected_energy_per_task(N, mu, PROPORTIONAL_POWER) == \
            pytest.approx(1.0, rel=1e-9)                     # eq. 23
    for power in (CONSTANT_POWER, PROPORTIONAL_POWER):
        edps = np.array([edp(N, mu, power) for N in states])
        assert xs[np.argmin(edps)] == pytest.approx(x_best, rel=1e-9)


@given(st.integers(0, 10_000))
def test_scenario_identities_random_busy_states(seed):
    """eq. 22/23 closed forms hold for random (N, mu) with all columns busy
    under CONSTANT and PROPORTIONAL power."""
    rng = np.random.default_rng(seed)
    k, l = rng.integers(2, 5, size=2)
    mu = random_affinity_matrix(rng, k, l)
    N = rng.integers(0, 7, size=(k, l))
    N[rng.integers(k), N.sum(axis=0) == 0] = 1     # fill empty columns
    ids = scenario_identities(N, mu)
    assert expected_energy_per_task(N, mu, CONSTANT_POWER) == \
        pytest.approx(ids["const_power_energy"], rel=1e-9)
    assert expected_energy_per_task(N, mu, PROPORTIONAL_POWER) == \
        pytest.approx(ids["prop_power_energy"], rel=1e-9)
    assert edp(N, mu, CONSTANT_POWER) == \
        pytest.approx(ids["const_power_edp"], rel=1e-9)
    assert edp(N, mu, PROPORTIONAL_POWER) == \
        pytest.approx(ids["prop_power_edp"], rel=1e-9)


# ---------------------------------------------------------------- GrIn++

@given(st.integers(0, 2_000))
def test_grin_plus_dominates_grin(seed):
    """Beyond-paper: GrIn++ (swaps + basin hops + AF-seeded multistart) never
    does worse than GrIn and respects the constraints."""
    from repro.core import grin_multistart_solve
    rng = np.random.default_rng(seed)
    k, l = rng.integers(2, 4, size=2)
    mu = random_affinity_matrix(rng, k, l)
    nt = rng.integers(1, 7, size=k)
    g = grin_solve(mu, nt)
    gm = grin_multistart_solve(mu, nt)
    assert gm.x_sys >= g.x_sys - 1e-9
    assert np.all(gm.N.sum(axis=1) == nt) and np.all(gm.N >= 0)


def test_grin_plus_improves_af_worst_case():
    """The AF-structured instance where GrIn lands ~22% off the optimum:
    GrIn++'s AF-seeded multistart recovers most (not all) of the gap —
    the optimum additionally SPLITS a row across two columns, which no
    seeded descent reaches (honest limitation, see grin_plus.py)."""
    from repro.core import grin_multistart_solve
    mu = np.array([[4.7, 3.1, 3.0], [26.2, 19.4, 15.4], [5.7, 20.5, 10.2]])
    nt = np.array([8, 1, 6])
    _, xopt = exhaustive_solve(mu, nt)
    g = grin_solve(mu, nt)
    gm = grin_multistart_solve(mu, nt)
    assert (xopt - g.x_sys) / xopt > 0.1          # GrIn is stuck
    assert gm.x_sys > g.x_sys * 1.1               # GrIn++ recovers half+
    assert (xopt - gm.x_sys) / xopt < 0.15
