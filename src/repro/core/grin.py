"""GrIn (Greedy-Increase) near-optimal placement for k task types x l
processor types (paper Sec. 4.2, Algorithms 1-2, Lemma 8).

A move relocates one p-type task from processor `src` to `dst`. Because the
two columns are disjoint, the exact throughput change is

    dX = dminus[p, src] + dplus[p, dst]

with (paper eq. 33-36, with the remove-delta sign fixed so that dminus is the
CHANGE in X_j caused by the removal — the paper's Lemma-8 prose and Algorithm 2
line 7 disagree on this sign; the math below is the self-consistent version):

    dplus[p, j]  = (mu[p, j] - X_j) / (col_j + 1)
    dminus[p, j] = (X_j - mu[p, j]) / (col_j - 1)     (col_j > 1)
                 = -mu[p, j]                          (col_j == 1, column empties)

GrIn accepts a move only when dX > 0, hence X_sys strictly increases per move
(Lemma 8) and the algorithm terminates at a local maximum. Per-sweep cost is
O(k*l) using the top-2 trick to resolve the src != dst constraint.

Block moves: relocating m same-type tasks between two disjoint columns also
has an exact closed-form delta (`delta_x_add_block`/`delta_x_remove_block`),
so a whole doubling ladder of block sizes can be scored in one vectorized
pass. Each step picks the steepest SINGLE move's direction (the same choice
plain GrIn makes) and then the gain-maximizing ladder size along it —
collapsing O(N) single moves into O(log N)-ish block moves while preserving
Lemma 8 monotonicity (every accepted block strictly increases X_sys).
Convergence is declared on the m=1 signal, so the block solver's fixed
points are exactly the single-move local maxima.

Three implementations: NumPy single-move (host scheduler), NumPy block-move
(reference mirror of the device solver, with a per-move X_sys history), and
pure-JAX (jit/vmap-able): `grin_solve_jax` (single-move steepest ascent) and
`grin_solve_batch_jax` (block-move, batched over (mu, mix) instances — the
production path for on-device target grids).
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.throughput import (column_throughputs, delta_x_add,
                                   delta_x_add_block, delta_x_remove,
                                   delta_x_remove_block, system_throughput,
                                   system_throughput_jax)

_TOL = 1e-12
# float32 solvers: accept only gains clearly above accumulated rounding
# noise (relative to X_sys), else noise-level "improvements" can 2-cycle
# forever. ~64 ULP at float32. The block solver converges at a finer
# threshold: as the production path it polishes through the gain band the
# single-move baseline stops in (still ~16 ULP above observed noise; a
# noise cycle would only burn iterations until the move cap and report
# converged=False, never corrupt the placement).
_TOL32 = 4e-6
_TOL32_BLOCK = 1e-6


def grin_init(mu: np.ndarray, n_tasks: np.ndarray) -> np.ndarray:
    """Algorithm 1: initial placement from the max-per-column structure."""
    mu = np.asarray(mu, dtype=np.float64)
    n_tasks = np.asarray(n_tasks, dtype=np.int64)
    k, l = mu.shape
    N = np.zeros((k, l), dtype=np.int64)
    # U: 1 at the row achieving the max of each column.
    top_row = np.argmax(mu, axis=0)
    for row in range(k):
        cols = np.where(top_row == row)[0]
        left = int(n_tasks[row])
        if left == 0:
            continue
        if len(cols) > 1:
            # One task to each claimed column (fastest first), remainder to the
            # slowest claimed column (Alg. 1 lines 6-13).
            order = cols[np.argsort(-mu[row, cols])]
            for c in order:
                if left == 0:
                    break
                N[row, c] += 1
                left -= 1
            N[row, order[-1]] += left
        elif len(cols) == 1:
            N[row, cols[0]] = left
        else:
            # Row claims no column: start from its best-fit processor; the
            # greedy loop redistributes (Alg. 1 lines 18-21).
            N[row, int(np.argmax(mu[row]))] = left
    return N


def _best_move_for_row(N: np.ndarray, mu: np.ndarray, p: int):
    """Best (gain, src, dst) move of one p-type task; gain may be <= 0."""
    dplus = delta_x_add(N, mu, p)
    dminus = delta_x_remove(N, mu, p)  # +inf where N[p, j] == 0? -> -inf there
    feas = N[p] > 0
    if not feas.any():
        return 0.0, -1, -1
    dminus = np.where(feas, dminus, -np.inf)
    # top-2 of each to satisfy src != dst in O(l)
    src_order = np.argsort(-dminus)[:2]
    dst_order = np.argsort(-dplus)[:2]
    best = (-np.inf, -1, -1)
    for s in src_order:
        if not np.isfinite(dminus[s]):
            continue
        for d in dst_order:
            if s == d:
                continue
            gain = dminus[s] + dplus[d]
            if gain > best[0]:
                best = (gain, int(s), int(d))
    return best


@dataclasses.dataclass
class GrInResult:
    N: np.ndarray
    x_sys: float
    moves: int
    sweeps: int


def grin_solve(mu: np.ndarray, n_tasks: np.ndarray,
               max_sweeps: int = 10_000) -> GrInResult:
    """Algorithm 2 with repeated row sweeps until a local maximum."""
    mu = np.asarray(mu, dtype=np.float64)
    n_tasks = np.asarray(n_tasks, dtype=np.int64)
    k, _ = mu.shape
    N = grin_init(mu, n_tasks)
    moves = 0
    sweeps = 0
    while sweeps < max_sweeps:
        sweeps += 1
        moved = False
        for p in range(k):
            gain, src, dst = _best_move_for_row(N, mu, p)
            if src >= 0 and gain > _TOL:
                N[p, src] -= 1
                N[p, dst] += 1
                moves += 1
                moved = True
        if not moved:
            break
    return GrInResult(N=N, x_sys=system_throughput(N, mu), moves=moves,
                      sweeps=sweeps)


_LADDER_CAP = 24        # 2^23 tasks: far above any closed population here


def _ladder(total: int) -> list[int]:
    """Doubling ladder of block sizes covering populations up to `total`,
    LARGEST FIRST so first-occurrence argmax ties prefer the biggest block."""
    n_sizes = max(1, min(_LADDER_CAP, int(np.ceil(np.log2(max(total, 2))))
                         + 1))
    return [1 << i for i in range(n_sizes - 1, -1, -1)]


@dataclasses.dataclass
class GrInBlockResult:
    N: np.ndarray
    x_sys: float
    moves: int
    converged: bool
    history: list       # X_sys after each accepted block move (monotone)


def grin_block_solve(mu: np.ndarray, n_tasks: np.ndarray,
                     max_moves: int = 100_000) -> GrInBlockResult:
    """Host block-move GrIn, mirroring the device solver's selection rule:
    the move DIRECTION (p, src, dst) is the steepest single move (identical
    to plain GrIn's choice, so the trajectory is a conservative acceleration
    of the single-move one) and the block SIZE is the largest doubling-
    ladder entry whose prefix of doubling slopes (average marginal gain per
    size-doubling) stays >= max(second-best single-move gain, 0) — the
    run-length guard that stops a block from overshooting past the point
    where the single-move path would have switched direction.

    Terminates when no single move improves — the same fixed-point class as
    Algorithm 2 — and records X_sys after every accepted block move, pinning
    the Lemma-8 monotonicity property in tests.
    """
    mu = np.asarray(mu, dtype=np.float64)
    n_tasks = np.asarray(n_tasks, dtype=np.int64)
    k, l = mu.shape
    N = grin_init(mu, n_tasks)
    sizes = _ladder(int(n_tasks.sum()))[::-1]     # ascending: 1, 2, 4, ...
    history: list[float] = []
    moves = 0
    converged = False
    while moves < max_moves:
        best = (-np.inf, -1, -1, -1)              # m=1 gain, p, src, dst
        runner = -np.inf
        for p in range(k):
            if not (N[p] >= 1).any():
                continue
            dplus = delta_x_add_block(N, mu, p, 1)
            dminus = np.where(N[p] >= 1, delta_x_remove_block(N, mu, p, 1),
                              -np.inf)
            gain = dminus[:, None] + dplus[None, :]
            np.fill_diagonal(gain, -np.inf)
            flat = np.sort(gain, axis=None)
            if flat[-1] > best[0]:
                runner = max(runner, best[0], flat[-2])
                idx = int(np.argmax(gain))
                best = (flat[-1], p, idx // l, idx % l)
            else:
                runner = max(runner, flat[-1])
        gain, p, src, dst = best
        if gain <= _TOL:
            converged = True
            break
        thresh = max(runner, 0.0)
        m_best, g_best, g_prev, m_prev = 1, gain, gain, 1
        for m in sizes[1:]:                       # ascending from 2
            if N[p, src] < m:
                break
            g_m = (delta_x_remove_block(N, mu, p, m)[src]
                   + delta_x_add_block(N, mu, p, m)[dst])
            if (g_m - g_prev) / (m - m_prev) < thresh:
                break
            m_best, g_best = m, g_m
            g_prev, m_prev = g_m, m
        N[p, src] -= m_best
        N[p, dst] += m_best
        moves += 1
        history.append(system_throughput(N, mu))
    return GrInBlockResult(N=N, x_sys=system_throughput(N, mu), moves=moves,
                           converged=converged, history=history)


# ---------------------------------------------------------------------------
# Pure-JAX GrIn: steepest-ascent variant inside lax.while_loop. Used where the
# solver must live inside a jitted pipeline (vectorized policy sweeps, elastic
# re-solve on device). Semantics: repeatedly apply the single best improving
# move across ALL rows until none exists. Reaches a local max of the same
# objective; may take a different path than the sweep variant.
# ---------------------------------------------------------------------------

def _deltas_jax(N: jnp.ndarray, mu: jnp.ndarray):
    colsum = N.sum(axis=0)                                   # (l,)
    X = jnp.where(colsum > 0, (mu * N).sum(0) / jnp.maximum(colsum, 1), 0.0)
    dplus = (mu - X[None, :]) / (colsum[None, :] + 1.0)      # (k, l)
    single = colsum[None, :] <= 1
    dm_reg = (X[None, :] - mu) / jnp.maximum(colsum[None, :] - 1.0, 1.0)
    dminus = jnp.where(single, -mu, dm_reg)
    dminus = jnp.where(N > 0, dminus, -jnp.inf)              # infeasible removes
    return dplus, dminus


def _grin_init_jax(mu: jnp.ndarray, n_tasks: jnp.ndarray) -> jnp.ndarray:
    """Algorithm 1 init (vectorized): (k, l) float32 placement."""
    k, l = mu.shape
    top_row = jnp.argmax(mu, axis=0)                         # (l,)
    claims = (top_row[None, :] == jnp.arange(k)[:, None])    # (k, l) bool
    n_claimed = claims.sum(axis=1)                           # (l,) -> per row
    # Rows with no claim fall back to their best-fit column.
    bf = jax.nn.one_hot(jnp.argmax(mu, axis=1), l, dtype=bool)
    eff = jnp.where((n_claimed == 0)[:, None], bf, claims)
    # Seed one task on every claimed column, remainder on the slowest claimed.
    mu_masked = jnp.where(eff, mu, jnp.inf)
    slowest = jnp.argmin(mu_masked, axis=1)                  # (k,)
    nt = jnp.asarray(n_tasks, dtype=jnp.float32)
    # Seed at most n_tasks[row] ones per row over claimed columns, fastest
    # first; the remainder goes to the slowest claimed column (Alg. 1).
    order = jnp.argsort(-jnp.where(eff, mu, -jnp.inf), axis=1)
    rank_of_col = jnp.argsort(order, axis=1).astype(jnp.float32)
    seed = (eff & (rank_of_col < nt[:, None])).astype(jnp.float32)
    rem = nt - seed.sum(axis=1)
    return seed + jax.nn.one_hot(slowest, l) * rem[:, None]


def grin_solve_jax(mu: jnp.ndarray, n_tasks: jnp.ndarray,
                   max_moves: int | None = None, return_info: bool = False):
    """jit/vmap-able single-move GrIn; returns the (k, l) placement (float32).

    `max_moves=None` (default) scales the move cap with the population
    (4 * sum(n_tasks) + 64) — the PR 2 fixed cap of 4096 silently returned
    unconverged placements for larger mixes; an explicit int is a HARD cap
    for callers that need bounded work (same contract as
    `grin_solve_batch_jax`). With `return_info=True` (a trace-time static
    flag) returns (N, converged, moves) so callers can detect the cap being
    hit either way.
    """
    mu = jnp.asarray(mu, dtype=jnp.float32)
    k, l = mu.shape
    N0 = _grin_init_jax(mu, n_tasks)
    total = jnp.asarray(n_tasks, dtype=jnp.float32).sum()
    cap = (jnp.int32(max_moves) if max_moves is not None
           else 4 * total.astype(jnp.int32) + 64)

    def body(state):
        N, _, moves = state
        dplus, dminus = _deltas_jax(N, mu)
        # gain[p, s, d] = dminus[p, s] + dplus[p, d], s != d
        gain = dminus[:, :, None] + dplus[:, None, :]
        eye = jnp.eye(l, dtype=bool)[None, :, :]
        gain = jnp.where(eye, -jnp.inf, gain)
        flat = jnp.argmax(gain)
        p, s, d = jnp.unravel_index(flat, (k, l, l))
        g = gain[p, s, d]
        do = g > _TOL32 * (1.0 + system_throughput_jax(N, mu))
        upd = (jax.nn.one_hot(p, k)[:, None]
               * (jax.nn.one_hot(d, l) - jax.nn.one_hot(s, l))[None, :])
        N = jnp.where(do, N + upd, N)
        return N, do, moves + do.astype(jnp.int32)

    def cond(state):
        _, improved, moves = state
        return improved & (moves < cap)

    N, improved, moves = jax.lax.while_loop(
        cond, body, (N0, jnp.array(True), jnp.array(0, jnp.int32)))
    if return_info:
        return N, ~improved, moves
    return N


def grin_x_sys_jax(mu: jnp.ndarray, n_tasks: jnp.ndarray) -> jnp.ndarray:
    return system_throughput_jax(grin_solve_jax(mu, n_tasks), mu)


# ---------------------------------------------------------------------------
# Batched block-move GrIn: the device production path. One lax.while_loop
# advances a whole (mu, mix) batch; each iteration scores EVERY (block size,
# type, src, dst) move for every instance in one vectorized pass (Pallas
# kernel on TPU, jnp reference elsewhere) and applies the
# selected block (steepest-single-move direction, best ladder size along it)
# per instance. Converged instances carry a per-instance mask so they stop
# mutating (and stop counting moves) while the rest of the batch drains; the
# loop exits as soon as all are done.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_sizes", "max_moves",
                                             "kernel", "objective", "grid"))
def _grin_block_core(mus, mixes, Ps, n_sizes, max_moves, kernel, objective,
                     grid=False):
    from repro.core.energy import edp_batch_jax, expected_energy_batch_jax
    from repro.kernels.grin_moves import (OBJ_E_GUARD, OBJ_EDP, OBJ_X,
                                          OBJ_XE, block_move_scores)
    if grid:
        # (G, k, l) matrices x (M, k) mixes: the G*M instances, matrix-major,
        # are built here on the device, so only the factors cross the link
        G, M = mus.shape[0], mixes.shape[0]
        mus, Ps = (jnp.broadcast_to(a[:, None], (G, M) + a.shape[1:])
                   .reshape((G * M,) + a.shape[1:]) for a in (mus, Ps))
        mixes = jnp.broadcast_to(mixes[None], (G,) + mixes.shape).reshape(
            G * M, mixes.shape[1])
    B, k, l = mus.shape
    # Largest size first: argmax ties prefer the biggest improving block.
    sizes = jnp.float32(2) ** jnp.arange(n_sizes - 1, -1, -1)
    with jax.named_scope("grin.init"):
        N0 = jax.vmap(_grin_init_jax)(mus, mixes)
    cap = (jnp.int32(max_moves) if max_moves is not None
           else mixes.sum(axis=1).max().astype(jnp.int32) + 64)

    def scale_for(N, obj):
        """Per-instance objective magnitude the float32 noise threshold is
        relative to: X_sys for throughput objectives, E / EDP for energy."""
        if obj in (OBJ_X, OBJ_XE):
            return jax.vmap(system_throughput_jax)(N, mus)
        if obj == OBJ_EDP:
            return jnp.abs(edp_batch_jax(N, mus, Ps))
        return jnp.abs(expected_energy_batch_jax(N, mus, Ps))

    def run_phase(N0_, moves0, obj):
        def body(state):
            N, active, moves, it = state
            _, bi, bg, base = block_move_scores(
                N, mus, sizes, use_kernel=kernel != "jnp-reference",
                return_gains=False, P=Ps, objective=obj)
            mi, p, s, d = jnp.unravel_index(bi, (n_sizes, k, l, l))
            m = sizes[mi]                                    # (B,)
            # Convergence is the m=1 signal: exhausted => single-move
            # local optimum of the phase objective.
            do = active & (base > _TOL32_BLOCK * (1.0 + scale_for(N, obj)))
            upd = (m[:, None, None]
                   * jax.nn.one_hot(p, k)[:, :, None]
                   * (jax.nn.one_hot(d, l)
                      - jax.nn.one_hot(s, l))[:, None, :])
            N = jnp.where(do[:, None, None], N + upd, N)
            return N, do, moves + do.astype(jnp.int32), it + 1

        def cond(state):
            _, active, _, it = state
            return jnp.any(active) & (it < cap)

        N, active, moves, _ = jax.lax.while_loop(
            cond, body, (N0_, jnp.ones(B, bool), moves0, jnp.int32(0)))
        return N, moves, ~active

    with jax.named_scope("grin.loop"):
        N, moves, conv = run_phase(N0, jnp.zeros(B, jnp.int32), objective)
        if objective == OBJ_XE:
            # Phase 2 of max-X-E: slide along the X plateau (moves whose dX
            # stays within float32 noise of zero) toward lower energy.
            N, moves, conv2 = run_phase(N, moves, OBJ_E_GUARD)
            conv = conv & conv2
    with jax.named_scope("grin.final"):
        xs = jax.vmap(system_throughput_jax)(N, mus)
    return N, xs, conv, moves


_OBJECTIVE_KEYS = ("max-x", "max-x-e", "min-e", "min-edp")


def _objective_id(objective: str) -> int:
    from repro.kernels.grin_moves import OBJ_E, OBJ_EDP, OBJ_X, OBJ_XE
    ids = dict(zip(_OBJECTIVE_KEYS, (OBJ_X, OBJ_XE, OBJ_E, OBJ_EDP)))
    if objective not in ids:
        raise ValueError(f"unknown objective {objective!r}: "
                         + " | ".join(_OBJECTIVE_KEYS))
    return ids[objective]


def _device_f32(x):
    """`x` as a float32 device array with no program run for the cast: a
    host array is cast here and copied as it is (`jnp.asarray` of a float64
    array would copy it and convert it on the device, one program a call);
    a device array is converted where it lives (a no-op when float32)."""
    if isinstance(x, jax.Array):
        return x.astype(jnp.float32)
    return jnp.asarray(np.asarray(x, dtype=np.float32))


def grin_solve_batch_jax(mu, n_tasks_batch, *, n_sizes: int | None = None,
                         max_moves: int | None = None,
                         use_kernel: bool | None = None,
                         objective: str = "max-x", power=None, P=None,
                         grid: bool = False):
    """Block-move GrIn over a batch of instances, in one device call.

    mu: (k, l) shared or (B, k, l) per-instance affinities; n_tasks_batch:
    (B, k) type mixes. Returns (N (B, k, l) float32, x_sys (B,), converged
    (B,) bool, moves (B,) int32). `n_sizes` (the doubling-ladder length) must
    be trace-time static; when omitted it is derived from the concrete mixes.
    `max_moves=None` caps the loop at the batch's max population + 64 — block
    convergence needs O(log N)-ish moves, so hitting the cap (converged
    False) signals a degenerate instance rather than a small budget.
    `use_kernel` picks the Pallas scoring kernel (None: compiled on TPU,
    jnp elsewhere; see `repro.kernels.grin_moves.kernel_mode`).

    `grid=True` solves every matrix of `mu` (G, k, l) against every mix of
    `n_tasks_batch` (M, k): B = G*M instances, matrix-major (instance
    g*M + m), built on the device from the two factors, so no (G*M, ...)
    array is made on the host or crosses the link. `P` is then (k, l) or
    (G, k, l).

    `objective` selects what moves are ranked by (paper Sec. 3.4 /
    arXiv:1607.07763 multi-objective framing), with the power matrix
    P = coeff * mu**alpha from `power` (a PowerModel; default proportional):

      "max-x"   — throughput ascent (the original solver, default)
      "max-x-e" — throughput ascent with energy tie-breaks, then an
                  X-plateau energy polish (GrIn-E)
      "min-e"   — E[E] descent (eq. 19)
      "min-edp" — EDP descent (eq. 21)

    `P` overrides the power matrix the energy objectives score against
    ((k, l) or (B, k, l)), for callers whose mu is NOT the physical rate
    matrix — the priority solvers rank moves under class-weighted
    affinities but watts stay class-blind, so they pass the physical tile
    here instead of letting P derive from the weighted mu.
    """
    from repro.kernels.grin_moves import OBJ_X, kernel_mode
    from repro.obs.profile import span as _obs_span
    with _obs_span("repro.grin.prep"):
        mixes = _device_f32(n_tasks_batch)
        mus = _device_f32(mu)
        if mixes.ndim != 2:
            raise ValueError(f"n_tasks_batch must be (B, k); got "
                             f"{mixes.shape}")
        B, k = mixes.shape
        if grid:
            if mus.ndim != 3 or mus.shape[1] != k:
                raise ValueError(f"mu must be (G, k={k}, l) for a grid; got "
                                 f"{tuple(jnp.shape(mu))}")
        else:
            if mus.ndim == 2:
                mus = jnp.broadcast_to(mus, (B,) + mus.shape)
            if mus.ndim != 3 or mus.shape[:2] != (B, k):
                raise ValueError(f"mu must be (k={k}, l) or (B={B}, k={k}, "
                                 f"l); got {tuple(jnp.shape(mu))}")
        obj = _objective_id(objective)
        if obj == OBJ_X:
            Ps = mus            # unused by the throughput objective
        elif P is not None:
            Ps = jnp.broadcast_to(_device_f32(P), mus.shape)
        else:
            from repro.core.affinity import PROPORTIONAL_POWER
            from repro.core.energy import power_matrix_jax
            Ps = power_matrix_jax(mus, power or PROPORTIONAL_POWER)
        if n_sizes is None:
            n_sizes = len(_ladder(int(np.asarray(n_tasks_batch)
                                      .sum(axis=1).max())))
        kernel = kernel_mode(use_kernel)
    with _obs_span("repro.grin.dispatch"):
        return _grin_block_core(mus, mixes, Ps, int(n_sizes), max_moves,
                                kernel, obj, grid)
