"""Block-move GrIn: closed-form block deltas, Lemma-8 monotonicity, parity
of the batched device solver against the host sweep solver, grid solving,
row-sum repair, and the Pallas gain kernel's bit-exact agreement with its
jnp reference."""
import numpy as np
import pytest
from _prop import given, st

from repro.core import (delta_x_add, delta_x_add_block, delta_x_remove,
                        delta_x_remove_block, grin_block_solve, grin_solve,
                        grin_solve_batch_jax, random_affinity_matrix,
                        system_throughput)
from repro.kernels.grin_moves import (block_move_gains_pallas,
                                      block_move_gains_ref, block_move_scores,
                                      kernel_mode)
from repro.sched import (SchedulerCore, solve_targets_grid_jax,
                         solve_targets_jax)
from repro.sched.api import _repair_targets, round_placements_jax


# ------------------------------------------------------------ block deltas

@given(st.integers(0, 10_000))
def test_block_move_deltas_exact(seed):
    """Moving m tasks at once changes X_sys by exactly
    dminus_block[src] + dplus_block[dst] (the closed form the solver and the
    Pallas kernel score); m=1 reduces to the paper's eq. 33-36."""
    rng = np.random.default_rng(seed)
    k, l = rng.integers(2, 5, size=2)
    mu = random_affinity_matrix(rng, k, l)
    N = rng.integers(0, 9, size=(k, l))
    p = rng.integers(k)
    if N[p].sum() == 0:
        N[p, 0] = 4
    src = rng.choice(np.flatnonzero(N[p] > 0))
    m = int(rng.integers(1, N[p, src] + 1))
    dst = (src + 1) % l
    x0 = system_throughput(N, mu)
    N2 = N.copy()
    N2[p, src] -= m
    N2[p, dst] += m
    delta = (delta_x_remove_block(N, mu, p, m)[src]
             + delta_x_add_block(N, mu, p, m)[dst])
    assert system_throughput(N2, mu) - x0 == pytest.approx(delta, abs=1e-9)
    if m == 1:
        assert delta == pytest.approx(
            delta_x_remove(N, mu, p)[src] + delta_x_add(N, mu, p)[dst],
            abs=1e-12)


@given(st.integers(0, 5_000))
def test_host_block_solver_monotone_and_local_max(seed):
    """Lemma 8 for blocks: every accepted block move STRICTLY increases
    X_sys, and the fixed point admits no improving single move (the ladder
    includes m=1, so block fixed points == single-move local maxima)."""
    rng = np.random.default_rng(seed)
    k, l = rng.integers(2, 5, size=2)
    mu = random_affinity_matrix(rng, k, l)
    nt = rng.integers(1, 30, size=k)
    res = grin_block_solve(mu, nt)
    assert res.converged
    assert np.all(res.N.sum(axis=1) == nt) and np.all(res.N >= 0)
    h = np.asarray(res.history)
    assert len(h) == res.moves
    if len(h) > 1:
        assert np.all(np.diff(h) > 0)          # strict per-move increase
    for p in range(k):
        dplus = delta_x_add(res.N, mu, p)
        dminus = delta_x_remove(res.N, mu, p)
        for s in range(l):
            if res.N[p, s] == 0:
                continue
            for d in range(l):
                if s != d:
                    assert dminus[s] + dplus[d] <= 1e-9


# ------------------------------------------------- batched device solver

def test_block_batch_reaches_single_move_quality():
    """Property: block-move GrIn's X_sys is within tolerance of the host
    sweep solver's on every instance, measured in float64 from the returned
    integer placements."""
    for seed, (k, l, total) in [(0, (3, 3, 30)), (1, (4, 5, 200)),
                                (2, (2, 4, 64))]:
        rng = np.random.default_rng(seed)
        mu = random_affinity_matrix(rng, k, l)
        mixes = rng.multinomial(total, [1.0 / k] * k, size=16)
        tb, _ = solve_targets_jax(mu, mixes)
        for mix, Nb in zip(mixes, tb):
            xb = system_throughput(Nb, mu)
            xh = grin_solve(mu, mix).x_sys
            assert xb >= 0.95 * xh, (seed, mix)


def test_block_batch_fixed_points_are_single_move_local_maxima():
    rng = np.random.default_rng(7)
    mu = random_affinity_matrix(rng, 3, 4)
    mixes = rng.multinomial(45, [1 / 3] * 3, size=8)
    N, xs, conv, moves = grin_solve_batch_jax(mu, mixes)
    assert np.asarray(conv).all()
    for Nb in np.asarray(N, dtype=np.int64):
        for p in range(3):
            dplus = delta_x_add(Nb, mu, p)
            dminus = delta_x_remove(Nb, mu, p)
            for s in range(4):
                if Nb[p, s] == 0:
                    continue
                for d in range(4):
                    if s != d:
                        assert dminus[s] + dplus[d] <= 1e-6


def test_block_batch_per_instance_mus():
    """(B, k, l) per-instance affinities: each instance solves under its own
    mu (the grid-solving substrate)."""
    rng = np.random.default_rng(3)
    mus = np.stack([random_affinity_matrix(rng, 3, 3) for _ in range(4)])
    mixes = np.tile([8, 8, 8], (4, 1))
    N, xs, conv, _ = grin_solve_batch_jax(mus, mixes)
    for m, Nb, x in zip(mus, np.asarray(N), np.asarray(xs)):
        assert system_throughput(Nb, m) == pytest.approx(float(x), rel=1e-3)
        assert system_throughput(Nb, m) >= 0.95 * grin_solve(m, [8, 8, 8]).x_sys
    with pytest.raises(ValueError, match="n_tasks_batch"):
        grin_solve_batch_jax(mus[0], np.array([1, 2, 3]))
    with pytest.raises(ValueError, match="mu must be"):
        grin_solve_batch_jax(mus[:2], mixes)


def test_convergence_flags_and_scaled_cap():
    """A fixed max_moves=4096 cap would return silently-unconverged
    placements for populations above it; the cap scales with sum(n_tasks)
    and the solver exposes a converged flag."""
    rng = np.random.default_rng(0)
    mu = random_affinity_matrix(rng, 3, 3)
    big = np.array([4000, 4000, 4000])      # > 4096 total: old cap territory
    N, _, conv, mv = grin_solve_batch_jax(mu, big[None])
    assert bool(np.asarray(conv)[0])
    assert np.asarray(N).sum() == big.sum()
    assert int(np.asarray(mv)[0]) < 200     # O(log N)-ish, not O(N), moves
    # block solver: a starved move budget reports non-convergence on an
    # instance that verifiably needs several moves
    mu2 = random_affinity_matrix(np.random.default_rng(1), 4, 6)
    mix2 = np.random.default_rng(2).multinomial(600, [0.25] * 4, size=1)
    _, _, conv, mv = grin_solve_batch_jax(mu2, mix2)
    assert bool(np.asarray(conv)[0]) and int(np.asarray(mv)[0]) >= 2
    _, _, conv, _ = grin_solve_batch_jax(mu2, mix2, max_moves=1)
    assert not bool(np.asarray(conv)[0])


# -------------------------------------------------- row-sum repair / grids

def test_solve_targets_repairs_float_row_drift():
    """float32 accumulation + .round() can violate
    row sums on large mixes; the device rounding flags such rows and the
    host's largest-remainder repair restores them."""
    mixes = np.array([[7, 5]])

    def rounded(raw):
        counts, drifted = round_placements_jax(raw, mixes.astype(np.float32))
        targets = np.asarray(counts).astype(np.int64)
        _repair_targets(targets, np.asarray(drifted), raw, mixes)
        return targets

    drifted = np.array([[[3.4, 3.4], [2.5, 2.4]]], np.float32)  # sums (6, 4)
    np.testing.assert_array_equal(rounded(drifted).sum(axis=2), mixes)
    # already-consistent rows round through unchanged
    clean = np.array([[[4.0, 3.0], [2.0, 3.0]]], np.float32)
    np.testing.assert_array_equal(rounded(clean), clean)
    # end to end: huge mixes keep exact row sums
    rng = np.random.default_rng(1)
    mu = random_affinity_matrix(rng, 3, 4)
    big = rng.multinomial(30_000, [1 / 3] * 3, size=3)
    targets, _ = solve_targets_jax(mu, big)
    np.testing.assert_array_equal(targets.sum(axis=2), big)


def test_solve_targets_grid_matches_per_mu_batches():
    rng = np.random.default_rng(5)
    mus = np.stack([random_affinity_matrix(rng, 3, 3) for _ in range(3)])
    mixes = rng.multinomial(24, [1 / 3] * 3, size=5)
    targets, xs, conv = solve_targets_grid_jax(mus, mixes)
    assert targets.shape == (3, 5, 3, 3) and xs.shape == (3, 5)
    assert conv.all()
    np.testing.assert_array_equal(
        targets.sum(axis=3), np.broadcast_to(mixes, (3, 5, 3)))
    for g, m in enumerate(mus):
        t_flat, x_flat = solve_targets_jax(m, mixes)
        np.testing.assert_array_equal(targets[g], t_flat)
        np.testing.assert_allclose(xs[g], x_flat, rtol=1e-6)
    with pytest.raises(ValueError, match="matching"):
        solve_targets_grid_jax(mus[0], mixes)


def test_elastic_what_if_grids():
    rng = np.random.default_rng(4)
    mu = rng.uniform(1, 30, size=(3, 3))
    core = SchedulerCore("grin", mu)
    mixes = np.array([[6, 7, 5], [3, 3, 3]])
    out = core.elastic_what_if(mixes, added_columns=np.array([[40., 40., 40.]]))
    assert out["base"].shape == (2,)
    assert out["pool_lost"].shape == (3, 2)
    assert out["pool_added"].shape == (1, 2)
    # losing a pool can never help; adding a uniformly fast pool never hurts
    assert (out["pool_lost"] <= out["base"][None, :] + 1e-6).all()
    assert (out["pool_added"] >= out["base"][None, :] - 1e-4).all()
    # base targets were warmed into the cache under the current mu
    r0 = core.resolves
    core.notify_type_counts([3, 3, 3])
    core.route(0)
    assert core.resolves == r0
    # pinned-mix default + guards
    core.notify_type_counts([6, 7, 5])
    assert core.elastic_what_if()["base"].shape == (1,)
    with pytest.raises(ValueError, match="statelessly"):
        SchedulerCore("jsq", mu).elastic_what_if(mixes)
    with pytest.raises(ValueError, match="no pinned"):
        SchedulerCore("grin", mu).elastic_what_if()


# ----------------------------------------------------- Pallas gain kernel

def test_gain_kernel_bit_matches_reference():
    """Acceptance (ISSUE PR3): the Pallas kernel's gains and in-kernel move
    selection are BIT-identical to the jnp reference (same ops, same
    order), and the selection implements the documented rule: direction by
    steepest m=1 move, block size by best gain along that direction."""
    rng = np.random.default_rng(0)
    for b, k, l, m in [(5, 3, 3, 6), (16, 4, 6, 11), (1, 2, 2, 2)]:
        N = rng.integers(0, 20, size=(b, k, l)).astype(np.float32)
        mu = rng.uniform(1, 30, size=(b, k, l)).astype(np.float32)
        sizes = (2.0 ** np.arange(m - 1, -1, -1)).astype(np.float32)
        ref5 = np.asarray(block_move_gains_ref(N, mu, sizes))
        ref = ref5.reshape(b, -1)
        g, bi, bg, base = block_move_gains_pallas(N, mu, sizes,
                                                  interpret=True)
        np.testing.assert_array_equal(np.asarray(g), ref)
        g2, bi2, bg2, base2 = block_move_scores(N, mu, sizes,
                                                use_kernel=False)
        np.testing.assert_array_equal(np.asarray(g2), ref)
        np.testing.assert_array_equal(np.asarray(bi2), np.asarray(bi))
        np.testing.assert_array_equal(np.asarray(bg2), np.asarray(bg))
        np.testing.assert_array_equal(np.asarray(base2), np.asarray(base))
        # selection semantics, recomputed independently in NumPy: direction
        # by steepest m=1 move; size by the longest ladder prefix whose
        # doubling slopes stay >= max(second-best m=1 gain, 0)
        dirs = k * l * l
        g1 = ref5[:, -1].reshape(b, dirs)
        d1 = np.argmax(g1, axis=1)
        np.testing.assert_array_equal(np.asarray(base),
                                      g1[np.arange(b), d1])
        masked = g1.copy()
        masked[np.arange(b), d1] = -np.inf
        thresh = np.maximum(masked.max(axis=1), 0.0)
        gasc = ref5.reshape(b, m, dirs)[np.arange(b), :, d1][:, ::-1]
        sizes_asc = 2.0 ** np.arange(m)
        prev_g = np.concatenate([np.zeros((b, 1)), gasc[:, :-1]], axis=1)
        prev_s = np.concatenate([[0.0], sizes_asc[:-1]])
        with np.errstate(invalid="ignore"):
            ok = (gasc - prev_g) / (sizes_asc - prev_s) >= thresh[:, None]
        idx_asc = np.maximum(np.cumprod(ok, axis=1).sum(axis=1) - 1, 0)
        np.testing.assert_array_equal(
            np.asarray(bi), (m - 1 - idx_asc) * dirs + d1)
        np.testing.assert_array_equal(np.asarray(bg),
                                      gasc[np.arange(b), idx_asc])


def test_solver_kernel_path_bit_matches_jnp_path(monkeypatch):
    """The whole batched solve is bit-identical whichever scoring backend
    runs inside the loop (interpret-mode Pallas vs jnp reference)."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(1)
    mu = random_affinity_matrix(rng, 4, 5)
    mixes = rng.multinomial(120, [0.25] * 4, size=6)
    N1, x1, c1, m1 = grin_solve_batch_jax(mu, mixes, use_kernel=False)
    N2, x2, c2, m2 = grin_solve_batch_jax(mu, mixes, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(N1), np.asarray(N2))
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))


def test_kernel_runs_interpreted_only_when_asked(monkeypatch):
    """Off-TPU the auto choice is the jnp path; `use_kernel=True` asks for
    the compiled kernel and fails there instead of quietly interpreting;
    REPRO_PALLAS_INTERPRET=1 is the one way into interpret mode."""
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert kernel_mode() == "jnp-reference"
    assert kernel_mode(False) == "jnp-reference"
    assert kernel_mode(True) == "pallas-compiled"
    N = np.ones((2, 2, 2), np.float32)
    sizes = np.array([2.0, 1.0], np.float32)
    with pytest.raises(ValueError, match="interpret"):
        block_move_scores(N, N, sizes, use_kernel=True)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert kernel_mode() == kernel_mode(True) == "pallas-interpret"
    assert kernel_mode(False) == "jnp-reference"
    ref = block_move_scores(N, N, sizes, use_kernel=False)
    pal = block_move_scores(N, N, sizes, use_kernel=True)
    for r, p_ in zip(ref, pal):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(p_))
