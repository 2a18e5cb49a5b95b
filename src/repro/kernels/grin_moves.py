"""Batched GrIn block-move gain scoring + argmax (the solver's inner step).

For a batch of placements N (B, k, l) under affinities mu (B, k, l) and a
ladder of block sizes `sizes` (M,), the exact system-throughput change from
moving sizes[m] p-type tasks from column s to a disjoint column d is

    gain[b, m, p, s, d] = R[b, m, p, s] + A[b, m, p, d]

with (closed forms; see `repro.core.throughput.delta_x_{add,remove}_block`)

    A[.., j] = m * (mu[p, j] - X_j) / (c_j + m)
    R[.., j] = m * (X_j - mu[p, j]) / (c_j - m)    (c_j > m)
             = -X_j                                (c_j == m, column drains)
             = -inf                                (N[p, j] < m, infeasible)

plus -inf on the s == d diagonal. Move selection is two chained argmaxes per
instance: the DIRECTION (p, s, d) is the steepest m=1 move — identical to
single-move GrIn's choice, which keeps the block solver's trajectory a
conservative acceleration of the single-move one — and the block SIZE is the
gain-maximizing ladder entry along that direction (sizes are passed largest
first, so ties prefer the biggest block). The m=1 best gain doubles as the
convergence signal: when it is exhausted the state is a single-move local
maximum, exactly the fixed-point class Lemma 8 terminates in.

Layout: the shared bodies work BATCH-LAST — states are (k, l, B), gains
(M, k, l_s, l_d, B) — so the batch sits on the TPU's 128-wide lane axis and
the (l_d, B) trailing tile is lane-dense. Everything is built from ops the
Mosaic compiler lowers: elementwise math, broadcasts, static slices,
leading-axis stacks, max/min reductions, and iota compares. There is no
gather, reversal or cumulative scan: argmax is "first index of the max"
against an iota, and the size-ladder prefix test is a static unrolled loop.

Three entry points:

  * `block_move_gains_ref`  — pure-jnp gain scoring.
  * `block_move_gains_pallas` — Pallas kernel tiled over the batch (lane)
    axis: each grid step scores one (k, l, Bt) slab in VMEM and runs the
    selection in-kernel, with the same body functions as the reference.
  * `block_move_scores` — dispatching wrapper returning
    (gains (B, F), best_idx (B,), best_gain (B,), base_gain (B,)) with
    F = M*k*l*l, best_idx/best_gain the selected move, and base_gain the
    steepest m=1 gain (the convergence signal).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -jnp.inf

# Objectives the scorer can rank moves under (trace-time statics). OBJ_X is
# the original throughput objective; the energy objectives additionally
# take the power matrix P:
#   OBJ_XE      — gains are still dX, but near-tied directions (within
#                 _XE_TIE float32 resolution) break toward the larger energy
#                 drop: "max-X subject to energy" move selection.
#   OBJ_E       — gains are E[E] drops (eq. 19): min-energy descent.
#   OBJ_EDP     — gains are EDP drops (eq. 21): min-EDP descent.
#   OBJ_E_GUARD — E drops restricted to moves whose dX stays within the
#                 _XE_TIE band of zero: the X-plateau energy polish that
#                 follows an OBJ_XE solve (grin-e phase 2).
OBJ_X, OBJ_XE, OBJ_E, OBJ_EDP, OBJ_E_GUARD = 0, 1, 2, 3, 4
_XE_TIE = 4e-6          # float32 near-tie band, matches grin._TOL32
_LANES = 128            # batch tile: one vreg row of lanes


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return os.environ.get("REPRO_PALLAS_INTERPRET", "0") == "1"


def kernel_mode(use_kernel: bool | None = None) -> str:
    """Which scoring path `block_move_scores(use_kernel=...)` takes:
    "jnp-reference" (use_kernel=False, or None off-TPU),
    "pallas-interpret" (only when REPRO_PALLAS_INTERPRET=1 asks for it), or
    "pallas-compiled" (use_kernel=True, or None on a TPU)."""
    if use_kernel is False:
        return "jnp-reference"
    if _interpret():
        return "pallas-interpret"
    if use_kernel or _use_pallas():
        return "pallas-compiled"
    return "jnp-reference"


def _src_to_pairs(x):
    """(..., l, B) -> (..., l_s, l_d, B) with x[..., s, :] at every d: a
    stack of static sublane-slice broadcasts (no reshape across tiles)."""
    return jnp.stack([jnp.broadcast_to(x[..., s:s + 1, :], x.shape)
                      for s in range(x.shape[-2])], axis=-3)


def _eye(l, b):
    """(l_s, l_d, B) bool mask of the s == d diagonal."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (l, b), 0)
    return _src_to_pairs(iota) == iota


def _col_rate(N, R):
    """Per-column average rate sum_p R N / c_j, 0 on empty columns: (l, B)."""
    colsum = N.sum(axis=0)
    return jnp.where(colsum > 0, (R * N).sum(axis=0)
                     / jnp.maximum(colsum, 1.0), 0.0)


def _add_rem(m, Mb, Sb, colsum):
    """Block add/remove closed forms for a (k, l, B) rate tile Mb over the
    (l, B) column averages Sb: ((M, k, l, B), (M, k, l, B))."""
    add = m * (Mb - Sb) / (colsum + m)
    rem = jnp.where(colsum - m > 0.5,
                    m * (Sb - Mb) / jnp.maximum(colsum - m, 1.0), -Sb)
    return add, rem


def _gains_body(N, mu, sizes):
    """Shared math: N, mu (k, l, B) float32; sizes (M, 1, 1, 1) float32 ->
    gain (M, k, l_s, l_d, B). MUST stay op-identical between the reference
    and the kernel body."""
    colsum = N.sum(axis=0)                               # (l, B)
    add, rem = _add_rem(sizes, mu, _col_rate(N, mu), colsum)
    rem = jnp.where(N >= sizes, rem, _NEG)               # infeasible removes
    gain = _src_to_pairs(rem) + add[:, :, None]
    return jnp.where(_eye(*colsum.shape), _NEG, gain)


def _energy_gains_body(N, mu, P, sizes, objective):
    """Energy-aware gain scoring: (gain (M, k, l, l, B), tie | None).

    The per-column power rate W_j = sum_i N_ij P_ij / c_j has the same
    ratio-of-sums shape as X_j, so the block closed forms apply with P in
    mu's seat; with dX and dW pairwise tensors the exact objective deltas are

        dE   = (W + dW) / (X + dX) - W / X                      (eq. 19)
        dEDP = ntot * ((W + dW) / (X + dX)^2 - W / X^2)         (eq. 21)

    and gains are the NEGATED deltas (drops — bigger is better). Infeasible
    moves (src short of m tasks, s == d, or a move that drains the system)
    score -inf. MUST stay op-identical between the jnp reference and the
    Pallas kernel body."""
    colsum = N.sum(axis=0)                               # (l, B)
    X = _col_rate(N, mu)
    W = _col_rate(N, P)
    Xs = X.sum(axis=0, keepdims=True)                    # (1, B)
    Ws = W.sum(axis=0, keepdims=True)
    ntot = colsum.sum(axis=0, keepdims=True)
    addx, remx = _add_rem(sizes, mu, X, colsum)
    addw, remw = _add_rem(sizes, P, W, colsum)
    dX = _src_to_pairs(remx) + addx[:, :, None]          # (M, k, l, l, B)
    dW = _src_to_pairs(remw) + addw[:, :, None]
    feas = _src_to_pairs(N >= sizes) & ~_eye(*colsum.shape)
    X1 = Xs + dX
    ok = feas & (X1 > 0) & (Xs > 0)
    e_drop = jnp.where(ok, Ws / jnp.maximum(Xs, 1e-30)
                       - (Ws + dW) / jnp.maximum(X1, 1e-30), _NEG)
    if objective == OBJ_XE:
        return jnp.where(feas, dX, _NEG), e_drop
    if objective == OBJ_E:
        return e_drop, None
    if objective == OBJ_EDP:
        return jnp.where(ok, ntot * (Ws / jnp.maximum(Xs * Xs, 1e-30)
                                     - (Ws + dW)
                                     / jnp.maximum(X1 * X1, 1e-30)), _NEG), \
            None
    if objective == OBJ_E_GUARD:
        return jnp.where(dX >= -_XE_TIE * (1.0 + Xs), e_drop, _NEG), None
    raise ValueError(f"unknown objective {objective!r}")


def _score_body(objective, N, mu, sizes, P=None):
    """(gain, tie | None) under `objective` (trace-time static)."""
    if objective == OBJ_X:
        return _gains_body(N, mu, sizes), None
    return _energy_gains_body(N, mu, P, sizes, objective)


def _reduce_dirs(x, op):
    """Reduce the (k, l_s, l_d) direction axes of (..., k, l, l, B) to
    (..., 1, B): the leading axes first, then the sublane axis."""
    return op(op(x, axis=(-4, -3)), axis=-2, keepdims=True)


def _select_body(gain, tie=None):
    """Shared move selection on a (M, k, l, l, B) gain tensor whose sizes
    axis is the DESCENDING doubling ladder (2^(M-1), ..., 2, 1). Returns
    (best_idx, best_gain, base_gain), each (1, B).

    Direction (p, s, d): the steepest m=1 move — single-move GrIn's exact
    choice (first index of the max in flattened (p, s, d) order). Size: the
    largest ladder entry whose whole prefix of doubling slopes (average
    marginal gain of each size-doubling, via the cumulative closed forms)
    stays >= max(second-best m=1 direction gain, 0). The slope test is the
    run-length guard: the single-move path keeps choosing this direction
    only while its marginal beats every alternative, so a block whose
    marginals dip below the runner-up would overshoot into a different basin
    (e.g. draining a whole column into a marginally faster one when
    spreading is optimal). base_gain is the m=1 steepest gain — the
    convergence signal.

    With a `tie` tensor (same shape; OBJ_XE) the direction is instead the
    best TIE score among directions whose m=1 gain sits within the _XE_TIE
    float32 band of the steepest — max-X move selection with energy-drop
    tie-breaking. base_gain stays the steepest m=1 gain either way."""
    msz, k, l, _, b = gain.shape
    dirs = k * l * l
    idx = ((jax.lax.broadcasted_iota(jnp.int32, (k, l, l, b), 0) * l
            + jax.lax.broadcasted_iota(jnp.int32, (k, l, l, b), 1)) * l
           + jax.lax.broadcasted_iota(jnp.int32, (k, l, l, b), 2))
    g1 = gain[msz - 1]                                   # m=1 slice
    base = _reduce_dirs(g1, jnp.max)
    score = g1
    if tie is not None:
        near = g1 >= base - _XE_TIE * (1.0 + jnp.abs(base))
        score = jnp.where(near, tie[msz - 1], _NEG)
    top = _reduce_dirs(score, jnp.max)
    d1 = _reduce_dirs(jnp.where(score == top, idx, dirs), jnp.min)
    hit = idx == d1
    thresh = jnp.maximum(_reduce_dirs(jnp.where(hit, _NEG, g1), jnp.max), 0.0)
    gsel = _reduce_dirs(jnp.where(hit, gain, _NEG), jnp.max)   # (M, 1, B)
    # Walk the ladder ascending (sizes 1, 2, 4, ...): keep the longest
    # prefix whose slopes clear the threshold (infeasible -> -inf/nan fails).
    alive, count = None, jnp.zeros(d1.shape, jnp.int32)
    best, prev_g, prev_s = gsel[msz - 1], 0.0, 0.0
    for i in range(msz):
        g = gsel[msz - 1 - i]
        ok = (g - prev_g) / (2.0 ** i - prev_s) >= thresh
        alive = ok if alive is None else alive & ok
        count = count + alive.astype(jnp.int32)
        best = jnp.where(alive, g, best)
        prev_g, prev_s = g, 2.0 ** i
    mi = (msz - 1) - jnp.maximum(count - 1, 0)
    return mi * dirs + d1, best, base


def _to_lanes(x):
    """(B, k, l) -> batch-last (k, l, B) float32."""
    return jnp.transpose(jnp.asarray(x, jnp.float32), (1, 2, 0))


def _gains_to_rows(gain):
    """(M, k, l, l, B) -> (B, F) in (m, p, s, d) flattened order."""
    return jnp.moveaxis(gain, -1, 0).reshape(gain.shape[-1], -1)


@jax.jit
def block_move_gains_ref(N, mu, sizes):
    """Pure-jnp reference: (B, M, k, l, l) move gains."""
    sizes = jnp.asarray(sizes, jnp.float32)
    gain = _gains_body(_to_lanes(N), _to_lanes(mu),
                       sizes.reshape(-1, 1, 1, 1))
    return jnp.moveaxis(gain, -1, 0)


def _kernel(objective, return_gains, *refs):
    n_in = 3 if objective == OBJ_X else 4
    ins, outs = refs[:n_in], refs[n_in:]
    gain, tie = _score_body(objective, *(r[...] for r in ins))
    if return_gains:
        outs[0][...] = gain
        outs = outs[1:]
    for ref, v in zip(outs, _select_body(gain, tie)):
        ref[...] = v


def block_move_gains_pallas(N, mu, sizes, *, interpret: bool = False,
                            return_gains: bool = True,
                            P=None, objective: int = OBJ_X):
    """Pallas path: grid over lane tiles of the batch; returns
    (gains (B, F) | None, best_idx, best_gain, base_gain).

    B rides the lane axis: one tile when B <= _LANES, else B is padded up
    to a _LANES multiple with empty states (colsum 0 -> every move
    infeasible) and the pad is sliced away. With `return_gains=False` the
    gains tensor is never written — the solver loop only consumes the
    selection. Energy objectives (OBJ_XE/E/EDP/E_GUARD) additionally stream
    the power matrix P through VMEM.
    """
    b, k, l = jnp.shape(N)
    sizes = jnp.asarray(sizes, jnp.float32)
    msz = sizes.shape[0]
    inputs = [N, mu]
    if objective != OBJ_X:
        if P is None:
            raise ValueError("energy objectives need the power matrix P")
        inputs.append(jnp.broadcast_to(jnp.asarray(P, jnp.float32),
                                       jnp.shape(N)))
    bt = min(b, _LANES)
    pad = (-b) % bt
    inputs = [jnp.pad(_to_lanes(x), ((0, 0), (0, 0), (0, pad)))
              for x in inputs]
    # kernel argument order follows `_score_body`: N, mu, sizes[, P]
    inputs.insert(2, sizes.reshape(msz, 1, 1, 1))
    bp = b + pad
    kl_spec = pl.BlockSpec((k, l, bt), lambda i: (0, 0, i))
    in_specs = [kl_spec, kl_spec,
                pl.BlockSpec((msz, 1, 1, 1), lambda i: (0, 0, 0, 0))]
    in_specs += [kl_spec] * (len(inputs) - 3)
    row_spec = pl.BlockSpec((1, bt), lambda i: (0, i))
    out_specs = [row_spec] * 3
    out_shape = [jax.ShapeDtypeStruct((1, bp), jnp.int32),
                 jax.ShapeDtypeStruct((1, bp), jnp.float32),
                 jax.ShapeDtypeStruct((1, bp), jnp.float32)]
    if return_gains:
        out_specs.insert(0, pl.BlockSpec((msz, k, l, l, bt),
                                         lambda i: (0, 0, 0, 0, i)))
        out_shape.insert(0, jax.ShapeDtypeStruct((msz, k, l, l, bp),
                                                  jnp.float32))
    out = pl.pallas_call(
        functools.partial(_kernel, objective, return_gains),
        grid=(bp // bt,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="grin_block_move_gains",
    )(*inputs)
    gains = _gains_to_rows(out[0][..., :b]) if return_gains else None
    bi, bg, base = (x[0, :b] for x in out[-3:])
    return gains, bi, bg, base


@functools.partial(jax.jit, static_argnames=("objective", "return_gains"))
def _scores_ref(N, mu, sizes, P, objective, return_gains):
    """jnp path of `block_move_scores`, jitted so that eager callers see the
    same numbers as the solver loop (XLA fuses; eager evaluation rounds
    each op on its own)."""
    sizes = jnp.asarray(sizes, jnp.float32).reshape(-1, 1, 1, 1)
    gain, tie = _score_body(objective, _to_lanes(N), _to_lanes(mu), sizes,
                            None if P is None else _to_lanes(P))
    bi, bg, base = (x[0] for x in _select_body(gain, tie))
    return (_gains_to_rows(gain) if return_gains else None), bi, bg, base


def block_move_scores(N, mu, sizes, *, use_kernel: bool | None = None,
                      return_gains: bool = True,
                      P=None, objective: int = OBJ_X):
    """Score every (block size, type, src, dst) move for a batch of states
    and select the next move per instance.

    `sizes` must be DESCENDING with sizes[-1] == 1 (the solver's doubling
    ladder). Returns (gains (B, F) | None, best_idx (B,), best_gain (B,),
    base_gain (B,)): best_idx indexes the flattened (M, k, l, l) tensor at
    the selected move (steepest m=1 direction, run-length-guarded block size
    along it) and base_gain is the steepest m=1 gain — the convergence
    signal. `objective` switches what the gains measure (throughput, energy
    drop, EDP drop, or throughput with energy tie-breaks — see the OBJ_*
    constants); all energy objectives need `P`. `return_gains=False` skips
    materializing the gains tensor (the solver's hot loop).

    `use_kernel=None` picks the compiled Pallas kernel on TPU and the jnp
    reference elsewhere; `use_kernel=True` asks for the kernel. Interpret
    mode runs only when REPRO_PALLAS_INTERPRET=1 asks for it: off-TPU a
    compiled kernel fails rather than fall back (see `kernel_mode`).
    """
    mode = kernel_mode(use_kernel)
    if mode == "jnp-reference":
        if objective != OBJ_X:
            if P is None:
                raise ValueError("energy objectives need the power matrix P")
            P = jnp.broadcast_to(jnp.asarray(P, jnp.float32), jnp.shape(N))
        return _scores_ref(N, mu, sizes, None if objective == OBJ_X else P,
                           objective, return_gains)
    return block_move_gains_pallas(N, mu, sizes,
                                   interpret=mode == "pallas-interpret",
                                   return_gains=return_gains, P=P,
                                   objective=objective)
