"""Fused RMSNorm Pallas TPU kernel (bandwidth-bound; one pass over x).

Grid over row tiles; each step loads a (block_rows, D) tile into VMEM,
computes the fp32 root-mean-square and writes the normalized, (1+w)-scaled
tile. D is expected 128-aligned (all assigned d_models are).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    o_ref[...] = (y * (1.0 + w_ref[...].astype(jnp.float32))).astype(o_ref.dtype)


def rmsnorm_pallas(x, w, *, eps=1e-5, block_rows=256, interpret=False):
    """x: (T, D); w: (D,). Returns (T, D)."""
    t, d = x.shape
    block_rows = min(block_rows, t)
    assert t % block_rows == 0, "pad rows to block multiple"
    return pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(t // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x, w)
