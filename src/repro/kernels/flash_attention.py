"""Flash attention Pallas TPU kernel (causal, GQA, optional sliding window).

TPU adaptation notes (DESIGN.md §6): tiles are sized for VMEM (~16 MiB) and
MXU alignment — block_q x block_k = 128 x 128 by default, head_dim padded to a
multiple of 128 by the ops wrapper. The online-softmax accumulators (acc, m,
l) live in VMEM scratch and persist across the sequential k grid dimension.

Layout: q (BH, Sq, dh), k/v (BKV, Sk, dh); the GQA mapping (q head -> kv head)
is resolved in the BlockSpec index maps, so no repeated KV is materialized.
Fully-masked causal tiles are skipped with @pl.when (no FLOPs wasted).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, block_q: int, block_k: int, seq_k: int,
            causal: bool, window: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # Tile-level skip: causal => skip tiles entirely above the diagonal;
    # window => skip tiles entirely left of the window.
    run = jnp.asarray(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
    if window:
        run = jnp.logical_and(run, k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        q = q_ref[...].astype(jnp.float32)            # (bq, dh)
        k = k_ref[...].astype(jnp.float32)            # (bk, dh)
        v = v_ref[...].astype(jnp.float32)            # (bk, dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < seq_k
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                            # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * corr
                        + jax.lax.dot(p.astype(v.dtype), v,
                                      preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...]
                      / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal=True, window=0,
                           block_q=128, block_k=128, valid_k=None,
                           scale=None, interpret=False):
    """q: (BH, Sq, dh); k, v: (BKV, Sk, dh); BH % BKV == 0 (GQA groups).

    dh should be 128-aligned (ops wrapper pads). Returns (BH, Sq, dh).
    """
    bh, sq, dh = q.shape
    bkv, sk, _ = k.shape
    g = bh // bkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, "pad seq to block multiple"
    nq, nk = sq // block_q, sk // block_k
    scale = (1.0 / (dh ** 0.5)) if scale is None else scale
    valid_k = sk if valid_k is None else valid_k   # true (unpadded) KV length

    kernel = functools.partial(
        _kernel, scale=scale, block_q=block_q, block_k=block_k, seq_k=valid_k,
        causal=causal, window=window)

    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((None, block_q, dh), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((None, block_k, dh), lambda h, i, j, g=g: (h // g, j, 0)),
            pl.BlockSpec((None, block_k, dh), lambda h, i, j, g=g: (h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, dh), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dh), q.dtype),
        # VMEM accumulators persist across the sequential k grid dimension.
        scratch_shapes=[
            pltpu.VMEM((block_q, dh), jnp.float32),   # acc
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),    # running sum l
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
