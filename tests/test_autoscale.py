"""Autoscaler + DVFS governor: property suite, guard-encoding validation,
call-count trace, and the target-cache regression under mu-rescale."""
import numpy as np
import pytest

from _prop import given, settings, st

from repro.core import (PROPORTIONAL_POWER, DVFSModel, grin_block_solve,
                        grin_init, random_affinity_matrix, system_throughput)
from repro.core.affinity import PowerModel
from repro.faults import FaultScenario, PoolEvent, compose_event_streams
from repro.sched import SchedulerCore
from repro.sched.autoscale import (AutoscaleGovernor, BudgetSpec,
                                   GovernorConfig, StaticScaler,
                                   UtilizationScaler, decisions_to_events,
                                   guarded_candidate_mus,
                                   price_frequency_grid, run_autoscaled)

DVFS = DVFSModel(alpha=3.0, levels=(0.5, 0.75, 1.0, 1.25))


def _energy_per_task(N, mu, P):
    """eq. 19 with an explicit power matrix (f64)."""
    N = np.asarray(N, dtype=np.float64)
    X = system_throughput(N, mu)
    col = N.sum(axis=0)
    W = np.where(col > 0, (N * P).sum(axis=0) / np.maximum(col, 1e-300), 0.0)
    return float(W.sum() / X) if X > 0 else np.inf


# ------------------------------------------------------------- properties

@given(st.integers(0, 10_000))
def test_x_sys_monotone_in_single_frequency_step(seed):
    """A single-pool frequency increase never lowers X_sys at a fixed
    placement (column scaling). The re-solve at the stepped rates is a
    heuristic's local optimum, which need not be monotone in one pool's
    frequency; what the solver does guarantee there is checked instead: it
    conserves the mix, converges to a placement no single move improves,
    and ends at least as high as Algorithm 1's start (Lemma 8)."""
    rng = np.random.default_rng(seed)
    k, l = rng.integers(2, 5, size=2)
    mu = random_affinity_matrix(rng, k, l)
    nt = rng.integers(1, 8, size=k)
    levels = np.asarray(DVFS.levels)
    f = levels[rng.integers(0, len(levels) - 1, size=l)]
    j = rng.integers(l)
    i = int(np.searchsorted(levels, f[j]))
    f_up = f.copy()
    f_up[j] = levels[i + 1]
    mu_up = DVFS.scale_mu(mu, f_up)
    lo = grin_block_solve(DVFS.scale_mu(mu, f), nt)
    hi = grin_block_solve(mu_up, nt)
    # fixed placement: X is linear in each pool's frequency with
    # nonnegative coefficient, so the step helps pointwise
    x_fixed = system_throughput(lo.N, mu_up)
    assert x_fixed >= lo.x_sys - 1e-12
    # the re-solve: exact mix, a single-move local maximum, above its start
    assert hi.converged
    np.testing.assert_array_equal(hi.N.sum(axis=1), nt)
    slack = 1e-9 * (1 + hi.x_sys)
    for p, src, dst in np.ndindex(k, l, l):
        if src != dst and hi.N[p, src] > 0:
            moved = hi.N.copy()
            moved[p, src] -= 1
            moved[p, dst] += 1
            assert system_throughput(moved, mu_up) <= hi.x_sys + slack
    assert hi.x_sys >= system_throughput(grin_init(mu_up, nt), mu_up) - slack


@given(st.integers(0, 10_000))
def test_energy_per_task_alpha_power_convex_in_uniform_frequency(seed):
    """At a uniform scale f, E(f) = f**(alpha-1) * E(1) exactly (mu and P
    column-scale together), hence convex in f for alpha >= 2: midpoint
    inequality on the DVFS ladder for random k x l busy states."""
    rng = np.random.default_rng(seed)
    k, l = rng.integers(2, 5, size=2)
    mu = random_affinity_matrix(rng, k, l)
    N = rng.integers(0, 7, size=(k, l))
    N[rng.integers(k), N.sum(axis=0) == 0] = 1      # all columns busy
    alpha = float(rng.uniform(2.0, 3.0))
    dvfs = DVFSModel(alpha=alpha)
    P = PROPORTIONAL_POWER.power_matrix(mu)
    e1 = _energy_per_task(N, mu, P)

    def e_at(f):
        return _energy_per_task(N, dvfs.scale_mu(mu, f),
                                dvfs.scale_power(P, f))

    fs = np.asarray(dvfs.levels)
    es = np.asarray([e_at(f) for f in fs])
    np.testing.assert_allclose(es, fs ** (alpha - 1.0) * e1, rtol=1e-9)
    np.testing.assert_allclose([dvfs.energy_scale(f) for f in fs],
                               fs ** (alpha - 1.0), rtol=1e-15)
    f_mid = 0.5 * (fs[0] + fs[-1])
    assert e_at(f_mid) <= 0.5 * (es[0] + es[-1]) + 1e-12


def test_f1_bit_identical_to_unscaled_solver():
    """f=1 scaling is the identity: bit-identical rates, bit-identical host
    solve; the device grid at f=1 tracks the host f64 optimum within the
    documented f32 tolerance (5e-3 rel — one float32 ratio-of-sums pass)."""
    rng = np.random.default_rng(29)
    mu = rng.uniform(2.0, 30.0, size=(3, 4))
    mix = np.array([9, 7, 5])
    ones = np.ones(4)
    assert np.array_equal(DVFS.scale_mu(mu, ones), mu)          # bitwise
    a = grin_block_solve(mu, mix)
    b = grin_block_solve(DVFS.scale_mu(mu, ones), mix)
    np.testing.assert_array_equal(a.N, b.N)
    assert a.x_sys == b.x_sys
    P = PROPORTIONAL_POWER.power_matrix(mu)
    priced = price_frequency_grid(mu, P, ones[None, :], mix[None, :], DVFS)
    assert priced["conv"].all()
    assert abs(priced["x"][0, 0] - a.x_sys) < 5e-3 * a.x_sys


# ------------------------------------------- big-M phantom guard encoding

def test_guard_encoding_matches_host_submatrix_solves():
    """Candidates with parked pools price EXACTLY like host solves of the
    live submatrix: no stray tasks on parked columns, X within f32
    tolerance — including a dump-site-bait slow type (the case a zeroed
    column gets wrong; see the autoscale module docstring)."""
    rng = np.random.default_rng(7)
    mu = rng.uniform(2.0, 30.0, size=(3, 4))
    mu[2] = [1.0, 1.2, 0.9, 1.1]                     # slow everywhere
    k, l = mu.shape
    mix = np.array([12, 9, 7])
    parked_sets = [[], [2], [1, 3], [0, 2, 3]]
    grid = np.ones((len(parked_sets), l))
    for c, parked in enumerate(parked_sets):
        grid[c, parked] = 0.0
    P = PROPORTIONAL_POWER.power_matrix(mu)
    priced = price_frequency_grid(mu, P, grid, mix[None, :], DVFS)
    assert priced["conv"].all()
    for c, parked in enumerate(parked_sets):
        tg = priced["targets"][c, 0]
        assert tg[:, parked].sum() == 0, (c, parked)
        assert np.array_equal(tg.sum(axis=1), mix)
        keep = [j for j in range(l) if j not in parked]
        ref = grin_block_solve(mu[:, keep], mix)
        assert abs(priced["x"][c, 0] - ref.x_sys) < 5e-3 * ref.x_sys
        e_ref = _energy_per_task(ref.N, mu[:, keep], P[:, keep])
        assert abs(priced["energy"][c, 0] - e_ref) < 2e-2 * e_ref


def _parked_grid(parked_sets, l, levels):
    """One candidate per parked set: pool j of candidate c runs at
    levels[(c + j) % len(levels)] unless parked (f = 0)."""
    C = len(parked_sets)
    idx = (np.arange(C)[:, None] + np.arange(l)) % len(levels)
    grid = np.asarray(levels, dtype=np.float64)[idx]
    for c, parked in enumerate(parked_sets):
        grid[c, parked] = 0.0
    return grid


def test_compiled_energy_matches_eager_and_host_pricing():
    """The grid's one compiled energy call prices every (candidate, mix)
    point as the eager `expected_energy_batch_jax` does on the same
    placements with the per-candidate rates repeated over the mixes, and as
    the float64 host formula does; parked columns add nothing and an empty
    mix (zero throughput) prices at inf."""
    from repro.core.energy import expected_energy_batch_jax
    rng = np.random.default_rng(7)
    mu = rng.uniform(2.0, 30.0, size=(3, 4))
    mu[2] = [1.0, 1.2, 0.9, 1.1]
    k, l = mu.shape
    P = PROPORTIONAL_POWER.power_matrix(mu)
    grid = _parked_grid([[], [2], [1, 3], [0, 2, 3]], l,
                        levels=(0.5, 1.25, 0.75, 1.0))
    mixes = np.array([[12, 9, 7], [1, 0, 5], [30, 2, 2], [0, 0, 0]])
    C, M = len(grid), len(mixes)
    priced = price_frequency_grid(mu, P, grid, mixes, DVFS)
    energy = priced["energy"]
    assert energy.shape == (C, M) and energy.dtype == np.float64
    mu_s = DVFS.scale_mu(mu[None], grid[:, None, :])
    P_s = DVFS.scale_power(P[None], grid[:, None, :])
    eager = np.asarray(expected_energy_batch_jax(
        priced["targets"].reshape(C * M, k, l), np.repeat(mu_s, M, axis=0),
        np.repeat(P_s, M, axis=0))).reshape(C, M)
    assert np.isinf(energy[:, -1]).all() and np.isinf(eager[:, -1]).all()
    np.testing.assert_allclose(energy[:, :-1], eager[:, :-1], rtol=1e-6)
    for c in range(C):
        for m in range(M - 1):
            tg = priced["targets"][c, m]
            assert tg[:, grid[c] == 0].sum() == 0
            e_host = _energy_per_task(tg, mu_s[c], P_s[c])
            assert abs(energy[c, m] - e_host) <= 1e-5 * e_host, (c, m)


def test_energy_pricing_compiles_once_per_grid_shape():
    """After one warm call, fresh mixes of the same (C, M, k, l) reuse the
    pricing program: no compile lands in a served window."""
    from repro.sched.autoscale import _price_grid_jax
    rng = np.random.default_rng(3)
    mu = rng.uniform(2.0, 30.0, size=(3, 4))
    P = PROPORTIONAL_POWER.power_matrix(mu)
    grid = _parked_grid([[], [1], [0, 3]], 4, levels=(0.75, 1.25))
    _price_grid_jax.clear_cache()
    price_frequency_grid(mu, P, grid, rng.integers(1, 20, size=(5, 3)), DVFS)
    assert _price_grid_jax._cache_size() == 1
    for _ in range(3):
        priced = price_frequency_grid(mu, P, grid,
                                      rng.integers(1, 20, size=(5, 3)), DVFS)
        assert np.isfinite(priced["energy"]).all()
    assert _price_grid_jax._cache_size() == 1


def _priced_as_before(mu, P, grid, mixes):
    """The pricing composed as it was before it became one round trip: the
    (C*M) batch repeated on the host, the solve and its results fetched,
    rounding with largest-remainder repair on the host in float64, and eager
    eq. 19 on the per-candidate rates repeated over the mixes."""
    from repro.core import grin_solve_batch_jax
    from repro.core.energy import expected_energy_batch_jax
    from repro.core.slsqp import round_largest_remainder
    from repro.sched.autoscale import GUARD_DUMMY, GUARD_W, guarded_mixes
    k, l = mu.shape
    mus = guarded_candidate_mus(mu, grid, DVFS)
    g_mixes = guarded_mixes(mixes, l)
    C, M = len(mus), len(g_mixes)
    mix_b = np.tile(g_mixes, (C, 1))
    raw, xs, conv, _ = grin_solve_batch_jax(np.repeat(mus, M, axis=0), mix_b)
    raw = np.asarray(raw, dtype=np.float64)
    out = raw.round().astype(np.int64)
    for b in np.flatnonzero((out.sum(axis=-1) != mix_b).any(axis=-1)):
        out[b] = round_largest_remainder(raw[b], mix_b[b])
    real = np.maximum(out, 0)[:, :k, :l]
    mu_s = DVFS.scale_mu(mu[None], grid[:, None, :])
    P_s = DVFS.scale_power(P[None], grid[:, None, :])
    energy = np.asarray(expected_energy_batch_jax(
        real, np.repeat(mu_s, M, axis=0), np.repeat(P_s, M, axis=0)),
        dtype=np.float64)
    x = (np.asarray(xs).reshape(C, M)
         - GUARD_W * ((grid == 0).sum(axis=1) + GUARD_DUMMY)[:, None])
    return {"targets": real.reshape(C, M, k, l), "x": np.maximum(x, 0.0),
            "energy": energy.reshape(C, M),
            "conv": np.asarray(conv).reshape(C, M)}


def test_one_round_trip_pricing_matches_the_composition_it_replaced():
    """Built, rounded and priced on the device with one fetch, the grid
    gives what the host-side composition gave: identical placements, x and
    energy within 1e-6, the same convergence; parked candidates at mixed
    DVFS levels included."""
    rng = np.random.default_rng(17)
    mu = rng.uniform(2.0, 30.0, size=(3, 4))
    mu[2] = [1.0, 1.2, 0.9, 1.1]
    P = PROPORTIONAL_POWER.power_matrix(mu)
    grid = _parked_grid([[], [2], [1, 3], [0, 2, 3], [], [1]], 4,
                        levels=(0.5, 1.25, 0.75, 1.0))
    mixes = np.concatenate([rng.multinomial(600, [0.5, 0.3, 0.2], size=6),
                            [[12, 9, 7], [1, 0, 5]]])
    new = price_frequency_grid(mu, P, grid, mixes, DVFS)
    old = _priced_as_before(mu, P, grid, mixes)
    np.testing.assert_array_equal(new["targets"], old["targets"])
    np.testing.assert_allclose(new["x"], old["x"], rtol=1e-6)
    np.testing.assert_allclose(new["energy"], old["energy"], rtol=1e-6)
    np.testing.assert_array_equal(new["conv"], old["conv"])


def test_device_rounding_flags_exactly_the_drifted_rows():
    """Rounded on the device half to even as np.round does, rows whose sums
    miss their mix are flagged (before the clamp at 0), and the host
    fallback re-rounds those alone to exact row sums."""
    from repro.sched.api import _repair_targets, round_placements_jax
    mixes = np.array([[7, 5], [4, 4]])
    raw = np.array([
        [[3.5, 3.5], [2.5, 2.5]],      # half to even: sums (8, 4) miss
        [[2.0, 2.0], [1.0, 3.0]],      # clean
        [[4.0, 3.0], [2.0, 3.0]],      # clean
        [[2.5, 1.5], [-0.4, 4.4]],     # (4, 4): clean, -0.4 clamps to 0
        [[-0.6, 7.6], [2.0, 3.0]],     # (-1 + 8): sums 7 but clamps to 8
        [[4.0, 0.0], [0.6, 3.6]],      # (4, 5): misses
    ], dtype=np.float32)               # instance b holds mix b % 2
    counts, drifted = round_placements_jax(raw, mixes.astype(np.float32))
    np.testing.assert_array_equal(np.asarray(drifted),
                                  [True, False, False, False, False, True])
    counts = np.asarray(counts)
    np.testing.assert_array_equal(counts, np.maximum(np.round(raw), 0))
    assert counts.dtype == np.int32
    targets = counts.astype(np.int64)
    repaired = _repair_targets(targets, np.asarray(drifted), raw, mixes)
    np.testing.assert_array_equal(repaired, [0, 5])
    np.testing.assert_array_equal(targets[0].sum(axis=1), mixes[0])
    np.testing.assert_array_equal(targets[5].sum(axis=1), mixes[1])
    np.testing.assert_array_equal(targets[1:5], counts[1:5])


def _drift(instances):
    """A solver that returns its placements with two columns of the first
    row of each listed instance moved up by 0.6: they round one up each,
    so the rounded row misses its mix; largest remainder restores it."""
    import jax.numpy as jnp
    import repro.sched.api as api
    real = api.grin_solve_batch_jax

    def drifting(mu, mixes, **kw):
        N, x, conv, moves = real(mu, mixes, **kw)
        bump = np.zeros(N.shape, np.float32)
        bump[list(instances), 0, :2] = 0.6
        return N + jnp.asarray(bump), x, conv, moves
    return drifting


def test_host_fallback_repairs_and_reprices_drifted_rows(monkeypatch):
    """Forced drift: the flagged placements come back with exact row sums,
    equal to the clean solve's, their energy priced on the host in float64,
    and the rest untouched."""
    import repro.sched.api as api
    rng = np.random.default_rng(23)
    mu = rng.uniform(2.0, 30.0, size=(3, 4))
    P = PROPORTIONAL_POWER.power_matrix(mu)
    grid = _parked_grid([[], [1], [0, 3]], 4, levels=(0.75, 1.25))
    mixes = rng.multinomial(300, [0.5, 0.3, 0.2], size=4)
    clean = price_frequency_grid(mu, P, grid, mixes, DVFS)
    M = len(mixes)
    flagged = [1, 2 * M + 3]          # (candidate, mix) (0, 1) and (2, 3)
    monkeypatch.setattr(api, "grin_solve_batch_jax", _drift(flagged))
    drifted = price_frequency_grid(mu, P, grid, mixes, DVFS)
    np.testing.assert_array_equal(drifted["targets"], clean["targets"])
    mu_s = DVFS.scale_mu(mu[None], grid[:, None, :])
    P_s = DVFS.scale_power(P[None], grid[:, None, :])
    for b in flagged:
        c, m = divmod(b, M)
        N = drifted["targets"][c, m]
        X = system_throughput(N, mu_s[c])
        col = N.sum(axis=0)
        W = np.where(col > 0, (N * P_s[c]).sum(axis=0) / np.maximum(col, 1),
                     0.0)
        assert drifted["energy"][c, m] == pytest.approx(W.sum() / X,
                                                        rel=1e-12)
    np.testing.assert_allclose(drifted["energy"], clean["energy"], rtol=1e-5)
    keep = np.ones(clean["energy"].shape, bool)
    keep.flat[flagged] = False
    np.testing.assert_array_equal(drifted["energy"][keep],
                                  clean["energy"][keep])


def _count_fetches(monkeypatch):
    """Record the shape of each device-to-host read of an array's values:
    `jax.device_get`, `float()` and the like read `_value`; `np.asarray`
    reads the buffer protocol where the array lives on the CPU."""
    from jax._src.array import ArrayImpl
    fetches = []
    value, buffer = ArrayImpl._value, ArrayImpl.__buffer__

    def counted_value(self):
        fetches.append(self.shape)
        return value.fget(self)

    def counted_buffer(self, flags):
        fetches.append(self.shape)
        return buffer(self, flags)
    monkeypatch.setattr(ArrayImpl, "_value", property(counted_value))
    monkeypatch.setattr(ArrayImpl, "__buffer__", counted_buffer)
    return fetches


def test_price_request_fetches_from_the_device_once(monkeypatch):
    """A request with no flagged row waits on the device once: one fetch of
    one packed array; a request with flagged rows adds one, of their raw
    placements."""
    import repro.sched.api as api
    rng = np.random.default_rng(31)
    mu = rng.uniform(2.0, 30.0, size=(3, 4))
    P = PROPORTIONAL_POWER.power_matrix(mu)
    grid = _parked_grid([[], [2], [1, 3]], 4, levels=(0.5, 1.0))
    mixes = rng.multinomial(40, [0.5, 0.3, 0.2], size=5)
    price_frequency_grid(mu, P, grid, mixes, DVFS)         # compile outside
    fetches = _count_fetches(monkeypatch)
    priced = price_frequency_grid(mu, P, grid, mixes, DVFS)
    assert fetches == [(len(grid) * len(mixes), 4 + 1 + 3 * 4)]
    assert np.array_equal(priced["targets"].sum(axis=3),
                          np.broadcast_to(mixes[None], (3, 5, 3)))
    monkeypatch.setattr(api, "grin_solve_batch_jax", _drift([4]))
    fetches.clear()
    price_frequency_grid(mu, P, grid, mixes, DVFS)
    assert len(fetches) == 2 and fetches[1] == (1, 3 + 4, 4 + 1)


def test_guarded_candidate_mus_shapes_and_guards():
    mu = np.ones((2, 3))
    grid = np.array([[1.0, 0.0, 0.5]])
    mus = guarded_candidate_mus(mu, grid, DVFS)
    assert mus.shape == (1, 2 + 3, 3 + 1)
    assert (mus[0, :2, 1] == 0).all()                # parked real rates off
    assert mus[0, 2 + 1, 1] > mus[0, 2 + 1, 3] > 0   # guard prefers its pool
    assert mus[0, 2 + 0, 0] == 0 and mus[0, 2 + 2, 2] == 0


# ---------------------------------------------- one batched call per epoch

def test_one_batched_device_call_per_decision_epoch(monkeypatch):
    """The acceptance trace: per governor decide(), exactly ONE grid solve
    (`start_targets_grid`) carrying the whole fixed-width candidate grid,
    backed by exactly ONE grin_solve_batch_jax device solve."""
    import repro.sched.api as api
    import repro.sched.autoscale as asc
    grid_calls, dev_calls = [], []
    real_grid, real_dev = asc.start_targets_grid, api.grin_solve_batch_jax

    def count_grid(mus, mixes, *a, **k):
        grid_calls.append(np.asarray(mus).shape)
        return real_grid(mus, mixes, *a, **k)

    def count_dev(*a, **k):
        dev_calls.append(1)
        return real_dev(*a, **k)

    monkeypatch.setattr(asc, "start_targets_grid", count_grid)
    monkeypatch.setattr(api, "grin_solve_batch_jax", count_dev)
    rng = np.random.default_rng(5)
    mu = rng.uniform(3.0, 25.0, size=(2, 3))
    gov = AutoscaleGovernor(mu, dvfs=DVFS)
    for e in range(4):
        gov.observe([22.0, 11.0], 4.0)
        dec = gov.decide(now=4.0 * (e + 1))
        assert len(grid_calls) == len(dev_calls) == e + 1
        assert grid_calls[e][0] == dec.n_candidates == 3 * 3 + 1
    assert gov.solve_calls == 4


# --------------------------------------------------- governor behavior

def _gov(mu, **kw):
    return AutoscaleGovernor(mu, dvfs=DVFS,
                             config=GovernorConfig(hysteresis=0.0), **kw)


def test_governor_scaleses_to_load():
    rng = np.random.default_rng(11)
    mu = rng.uniform(8.0, 25.0, size=(2, 3))
    gov = _gov(mu)
    for _ in range(8):                       # trickle load: shed capacity
        gov.observe([4.0, 2.0], 1.0)
        low = gov.decide()
    assert low.freqs.sum() < 3.0             # below all-pools-at-f=1
    assert low.x_cap >= 1.25 * 6.0 - 1e-6
    for _ in range(12):                      # then a surge: scale back out
        gov.observe([60.0, 40.0], 1.0)
        high = gov.decide()
    assert high.freqs.sum() > low.freqs.sum()
    assert (high.freqs > 0).sum() >= (low.freqs > 0).sum()


def test_governor_respects_min_active_and_power_cap():
    rng = np.random.default_rng(13)
    mu = rng.uniform(8.0, 25.0, size=(2, 3))
    free = _gov(mu)
    for _ in range(10):
        free.observe([50.0, 30.0], 1.0)
        unc = free.decide()
    # a cap strictly between the uncapped draw and the single-pool floor
    # is binding but satisfiable: the governor must stay under it without
    # ever declaring an emergency
    cap = 0.6 * unc.power_pred
    gov = _gov(mu, budget=BudgetSpec(power_cap=cap))
    for _ in range(10):
        gov.observe([50.0, 30.0], 1.0)
        dec = gov.decide()
        assert (dec.freqs > 0).sum() >= gov.config.min_active
        assert dec.action != "emergency"
    assert dec.power_pred <= cap + 1e-9
    assert dec.power_pred < unc.power_pred


def test_utilization_scaler_steps_and_parks():
    naive = UtilizationScaler(3, DVFS)
    for _ in range(30):
        naive.decide({"util": 0.05})
    assert (naive.freqs == 0).sum() == 2     # parked down to min_active
    assert naive.freqs.max() == DVFS.levels[0]
    for _ in range(30):
        naive.decide({"util": 0.99})
    assert (naive.freqs > 0).all()
    assert naive.freqs.max() == DVFS.levels[-1]


# --------------------------------------- live-core application + caching

def test_set_frequencies_bumps_mu_token_and_invalidates_cache():
    """Regression (PR 5 stale-class-weight mirror): a DVFS mu-rescale must
    bump the mu version token so a warm cache can never serve a target
    solved at the old frequencies."""
    rng = np.random.default_rng(17)
    mu = rng.uniform(1.0, 30.0, size=(2, 3))
    mix = np.array([6, 5])
    core = SchedulerCore("grin", mu).reset(n_tasks=mix)
    t0 = core._target_for(mix).copy()
    tok0 = core._mu_token
    assert core.resolves == 1
    core._target_for(mix)
    assert core.resolves == 1                 # warm hit at f=1
    core.set_frequencies([1.0, 1.0, 0.05])    # pool 2 to a crawl
    assert core._mu_token > tok0
    t1 = core._target_for(mix)
    assert core.resolves == 2                 # NOT served the stale target
    # the fresh solve ran against the rescaled matrix
    np.testing.assert_array_equal(
        t1, grin_block_solve(core.mu, mix).N.astype(t1.dtype))
    assert np.array_equal(t0.sum(axis=1), t1.sum(axis=1))
    np.testing.assert_allclose(core.mu[:, 2], mu[:, 2] * 0.05)
    np.testing.assert_allclose(core.mu[:, :2], mu[:, :2])
    with pytest.raises(ValueError):
        core.set_frequencies([1.0, -1.0, 1.0])
    with pytest.raises(ValueError):
        core.set_frequencies([1.0, 1.0])


def test_frequencies_compose_with_topology_events():
    rng = np.random.default_rng(19)
    mu = rng.uniform(1.0, 30.0, size=(2, 3))
    core = SchedulerCore("grin", mu)
    core.set_frequencies([0.5, 1.0, 1.25])
    core.pool_lost(0)
    np.testing.assert_allclose(core.frequencies, [1.0, 1.25])
    np.testing.assert_allclose(core.nominal_mu, mu[:, 1:])
    core.pool_added(mu[:, 0], frequency=0.75)
    np.testing.assert_allclose(core.frequencies, [1.0, 1.25, 0.75])
    np.testing.assert_allclose(core.mu[:, 2], mu[:, 0] * 0.75)
    core.set_frequencies([1.0, 1.0, 1.0])
    np.testing.assert_allclose(
        core.mu, np.column_stack([mu[:, 1], mu[:, 2], mu[:, 0]]))


def test_apply_to_core_parks_and_unparks():
    rng = np.random.default_rng(23)
    mu = rng.uniform(5.0, 25.0, size=(2, 3))
    gov = _gov(mu)
    core = SchedulerCore("grin", mu)
    live = [0, 1, 2]
    for _ in range(8):
        gov.observe([3.0, 2.0], 1.0)
        dec = gov.decide()
        live = gov.apply_to_core(core, dec, live)
        assert core.l == len(live) == (dec.freqs > 0).sum()
        np.testing.assert_allclose(core.frequencies,
                                   [dec.freqs[p] for p in live])
    assert core.l < 3                         # it did park something
    for _ in range(12):
        gov.observe([55.0, 35.0], 1.0)
        dec = gov.decide()
        live = gov.apply_to_core(core, dec, live)
    assert core.l == len(live) == (dec.freqs > 0).sum() > 1
    core.reset(n_tasks=np.array([4, 3]))
    assert core.route(0) in range(core.l)     # still routable end to end


# ------------------------------------ decision traces on the fault fabric

def test_decisions_to_events_realize_and_compose():
    rng = np.random.default_rng(31)
    mu = rng.uniform(5.0, 25.0, size=(2, 3))
    gov = _gov(mu)
    lam = [([3.0, 2.0], 6), ([60.0, 40.0], 6), ([10.0, 6.0], 6)]
    t = 0.0
    for rate, n in lam:
        for _ in range(n):
            t += 2.0
            gov.observe(rate, 2.0)
            gov.decide(now=t)
    events = decisions_to_events(gov.decisions, 3)
    assert events                              # the load swing forced action
    sc = FaultScenario(events=events, refresh_targets=True)
    real = sc.realize(3)                       # validator accepts the trace
    assert (np.diff(real.times) > 0).all()
    # composition with an outage: product schedule still validates, crash
    # wins while down, governor frequency restored after recovery
    outage = (PoolEvent(t * 0.4, 0, 0.0), PoolEvent(t * 0.6, 0, 1.0))
    combined = compose_event_streams(events, outage, 3)
    FaultScenario(events=combined).realize(3)
    down = [e for e in combined if e.pool == 0 and e.time >= t * 0.4
            and e.time < t * 0.6]
    assert down and down[0].scale == 0.0


# ----------------------------------------------------- fluid-loop runner

def test_run_autoscaled_conserves_tasks():
    rng = np.random.default_rng(37)
    mu = rng.uniform(5.0, 25.0, size=(2, 3))
    times = np.sort(rng.uniform(0.0, 60.0, size=2500))
    types = rng.integers(0, 2, size=2500)
    for ctrl in (StaticScaler(3), UtilizationScaler(3, DVFS), _gov(mu)):
        r = run_autoscaled(mu, times, types, ctrl, dvfs=DVFS, epoch=3.0,
                           queue_slots=200)
        backlog_left = 2500 - r.served - r.dropped
        assert 0 <= r.dropped < 2500
        assert -1e-6 <= backlog_left <= 200 + 1e-6
        assert r.energy > 0 and r.goodput > 0
        assert r.freq_trace.shape == (len(r.times), 3)
