"""Chunked SSD (state-space dual) linear-recurrence Pallas TPU kernel.

Computes, per (batch*head) row with state S in R^{dk x dv}:

    S_t = exp(log_a_t) * S_{t-1} + beta_t * k_t v_t^T ;  y_t = q_t @ S_t

using the chunked parallel form: intra-chunk (attention-like with decay
matrix) on the MXU + inter-chunk state carry in VMEM scratch, which persists
across the sequential chunk grid dimension. Serves Mamba2 (k=B, v=x, q=C) and
mLSTM (k, v, q with sigmoid gates) — see repro.models.linear_scan for the
mapping and repro.kernels.ref.ssd_scan_ref for the oracle.

VMEM working set per step: chunk x (2 dk + dv) + chunk^2 + dk x dv floats —
with chunk=256, dk=dv=128: ~0.6 MiB, far under the ~16 MiB budget; all matmul
dims are 128-aligned for the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(q_ref, k_ref, v_ref, la_ref, b_ref, y_ref, s_ref, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    q = q_ref[...].astype(jnp.float32)          # (c, dk)
    k = k_ref[...].astype(jnp.float32)          # (c, dk)
    v = v_ref[...].astype(jnp.float32)          # (c, dv)
    la = la_ref[...].astype(jnp.float32)        # (c, 1)
    beta = b_ref[...].astype(jnp.float32)       # (c, 1)

    lc = jnp.cumsum(la, axis=0)                 # inclusive cumulative log decay
    lt = lc[-1:, :]                             # total chunk decay (1, 1)

    # intra-chunk: D[t, u] = exp(lc[t] - lc[u]) for u <= t else 0.
    # Mask BEFORE exp: above-diagonal diffs are positive and may overflow.
    diff = lc - lc.reshape(1, chunk)            # (c, c) via broadcast
    tri = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    dmat = jnp.exp(jnp.where(tri, diff, -1e30))
    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * dmat
    y_intra = jax.lax.dot(scores * beta.reshape(1, chunk), v,
                          preferred_element_type=jnp.float32)

    # inter-chunk: y_t += exp(lc[t]) * q_t @ S_prev
    y_inter = jnp.exp(lc) * jax.lax.dot(q, s_ref[...],
                                        preferred_element_type=jnp.float32)
    y_ref[...] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: S = exp(lt) * S + sum_u exp(lt - lc[u]) beta_u k_u v_u^T
    w = jnp.exp(lt - lc) * beta                 # (c, 1)
    s_ref[...] = (jnp.exp(lt) * s_ref[...]
                  + jax.lax.dot_general(k * w, v, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32))


def ssd_scan_pallas(q, k, v, log_a, beta, *, chunk=256, interpret=False):
    """q, k: (BH, S, dk); v: (BH, S, dv); log_a, beta: (BH, S).

    S must be a multiple of `chunk` (ops wrapper pads with log_a=0, beta=0).
    Returns y (BH, S, dv). Final state is recomputed by the wrapper when
    needed (decode handoff) — the kernel streams y only.
    """
    bh, s, dk = k.shape
    dv = v.shape[-1]
    assert s % chunk == 0, "pad sequence to a chunk multiple"
    n = s // chunk
    la2 = log_a[..., None]
    b2 = beta[..., None]

    kernel = functools.partial(_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(bh, n),
        in_specs=[
            pl.BlockSpec((None, chunk, dk), lambda h, c: (h, c, 0)),
            pl.BlockSpec((None, chunk, dk), lambda h, c: (h, c, 0)),
            pl.BlockSpec((None, chunk, dv), lambda h, c: (h, c, 0)),
            pl.BlockSpec((None, chunk, 1), lambda h, c: (h, c, 0)),
            pl.BlockSpec((None, chunk, 1), lambda h, c: (h, c, 0)),
        ],
        out_specs=pl.BlockSpec((None, chunk, dv), lambda h, c: (h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, dv), v.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],  # carried state
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, la2, b2)
