"""Plain reference of DVFS candidate pricing with block-move GrIn.

Semantics (arXiv:1712.03209 Sec. 4.2, Algorithms 1-2, with block moves):
a placement N (k task types x l pools) has system throughput
X = sum_j sum_i mu_ij N_ij / c_j (processor sharing, c_j the column's
tasks, empty columns add 0). Starting from Algorithm 1's placement, each
step takes the steepest single-task move (type p, pool s -> d; first in
(p, s, d) order on ties) and moves the largest block m of the doubling
ladder 1, 2, 4, ... whose every doubling still gains at least the
runner-up single move (and 0) per task; it stops when the best single
move gains no more than `tol * (1 + X)`, the solver's stated tolerance.

A candidate frequency vector f scales pool j's rates by f_j and its power
by f_j ** alpha; f_j = 0 parks pool j. Parked pools are priced the way the
deployment prices them: each pool gets one phantom task of rate W on its
own column when parked and 0.99 W on one extra dummy column, so that no
real task is ever placed on a parked pool; the priced throughput is the
real slice's X, and the energy per task is sum_j W_j / X with
W_j = sum_i P_ij N_ij / c_j.

Every floating-point operation runs in `dtype` (float64 for the reference,
bfloat16 for the control); counts are held in the same dtype.
"""
from __future__ import annotations

import numpy as np

GUARD_W = 1.0e4
GUARD_DUMMY = 0.99


def ladder(total: int) -> list[int]:
    """Doubling block sizes 1, 2, 4, ... covering a population of `total`."""
    n = max(1, min(24, int(np.ceil(np.log2(max(total, 2)))) + 1))
    return [1 << i for i in range(n)]


def init_placement(mu: np.ndarray, mix: np.ndarray) -> np.ndarray:
    """Algorithm 1: each column is claimed by its fastest type (first on
    ties); a type with no claim starts on its own fastest column. A type
    puts one task on each claimed column, fastest first, and the rest on
    its slowest claimed column."""
    mu = np.asarray(mu, np.float64)
    k, l = mu.shape
    N = np.zeros((k, l), np.int64)
    top = np.argmax(mu, axis=0)
    for p in range(k):
        cols = np.flatnonzero(top == p)
        if cols.size == 0:
            cols = np.array([int(np.argmax(mu[p]))])
        order = cols[np.argsort(-mu[p, cols], kind="stable")]
        left = int(mix[p])
        for c in order[:left]:
            N[p, c] += 1
        left -= min(left, order.size)
        slowest = cols[int(np.argmin(mu[p, cols]))]
        N[p, slowest] += left
    return N


def _x_cols(N, mu, dt):
    c = N.sum(axis=0)
    num = (mu * N).sum(axis=0)
    return np.where(c > dt(0), num / np.maximum(c, dt(1)), dt(0)), c


def solve(mu, mix, *, tol: float, dtype=np.float64,
          max_moves: int | None = None) -> np.ndarray:
    """Block-move GrIn from Algorithm 1's placement; returns N in dtype."""
    dt = np.dtype(dtype).type
    mu = np.asarray(mu, np.float64).astype(dtype)
    k, l = mu.shape
    N = init_placement(np.asarray(mu, np.float64), mix).astype(dtype)
    sizes = ladder(int(np.sum(mix)))
    cap = int(np.sum(mix)) + 64 if max_moves is None else max_moves
    diag = np.eye(l, dtype=bool)
    for _ in range(cap):
        X, c = _x_cols(N, mu, dt)
        gains = []
        for m in sizes:
            mm = dt(m)
            add = mm * (mu - X) / (c + mm)
            rem = np.where(c - mm > dt(0.5),
                           mm * (X - mu) / np.maximum(c - mm, dt(1)), -X)
            rem = np.where(N >= mm, rem, dt(-np.inf))
            g = rem[:, :, None] + add[:, None, :]
            gains.append(np.where(diag[None], dt(-np.inf), g))
        g1 = gains[0]
        flat = int(np.argmax(g1))
        p, s, d = np.unravel_index(flat, g1.shape)
        base = g1[p, s, d]
        if not base > dt(tol) * (dt(1) + X.sum()):
            break
        rest = g1.copy()
        rest[p, s, d] = dt(-np.inf)
        thresh = max(rest.max(), dt(0))
        m_best, prev_g, prev_m = 1, dt(0), 0
        for i, m in enumerate(sizes):
            g = gains[i][p, s, d]
            if not (g - prev_g) / dt(m - prev_m) >= thresh:
                break
            m_best, prev_g, prev_m = m, g, m
        N[p, s] -= dt(m_best)
        N[p, d] += dt(m_best)
    return N


def guarded(mu_nominal, freqs, alpha: float):
    """(k + l, l + 1) guarded rate matrix of one candidate, and its
    DVFS-scaled (mu, P) on the real slice (P proportional to mu)."""
    mu_nominal = np.asarray(mu_nominal, np.float64)
    f = np.asarray(freqs, np.float64)
    k, l = mu_nominal.shape
    mu_s = mu_nominal * f[None, :]
    P_s = mu_nominal * f[None, :] ** alpha
    g = np.zeros((k + l, l + 1))
    g[:k, :l] = mu_s
    for j in range(l):
        g[k + j, l] = GUARD_DUMMY * GUARD_W
        g[k + j, j] = GUARD_W if f[j] == 0 else 0.0
    return g, mu_s, P_s


def price(mu_nominal, freqs, mix, *, alpha: float, tol: float,
          dtype=np.float64, max_moves: int | None = None):
    """(real placement (k, l) float64, X, energy per task) of one candidate
    at one mix, every step computed in `dtype`."""
    dt = np.dtype(dtype).type
    k, l = np.shape(mu_nominal)
    g, mu_s, P_s = guarded(mu_nominal, freqs, alpha)
    mix_g = np.concatenate([np.asarray(mix, np.int64), np.ones(l, np.int64)])
    N = solve(g, mix_g, tol=tol, dtype=dtype, max_moves=max_moves)[:k, :l]
    X_j, c = _x_cols(N, mu_s.astype(dtype), dt)
    W_j, _ = _x_cols(N, P_s.astype(dtype), dt)
    X = X_j.sum()
    e = W_j.sum() / X if X > dt(0) else dt(np.inf)
    return N.astype(np.float64), float(X), float(e)
