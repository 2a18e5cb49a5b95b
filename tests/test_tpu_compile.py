"""Ahead-of-time compiles for a described TPU v5e, with no chip attached.

Interpret mode runs a Pallas kernel's math but not the Mosaic compiler, so a
kernel can pass every interpret-mode test and still be refused by the TPU.
These tests hand the TPU compiler the main path's kernels at real widths and
check that each compiled program holds a Mosaic kernel (`tpu_custom_call`).
They run nothing, so they say nothing about results or times.

The topology is described inside a fixture, never at import time: only one
process at a time may load the TPU library, and the suite runs in several
worker processes.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.grin_moves import OBJ_X, OBJ_XE, block_move_gains_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas

# the solver's production grid (benchmarks/bench_solver.py full size):
# 256 (mu, mix) points, k=4 types, l=6 pools, N=6000 -> a 13-rung ladder
B, K, L, M = 256, 4, 6, 13
# qwen2.5-3b served prefill in kernel layout: batch 2 x 16 query heads over
# 2 KV heads, head_dim 128, sequence padded to the attention block
QWEN_BH, QWEN_BKV, QWEN_DH, QWEN_D = 32, 4, 128, 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described-chip compile cannot be read back from the persistent
        # cache without a chip; keep these compiles out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield t
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("return_gains", [True, False])
@pytest.mark.parametrize("objective", [OBJ_X, OBJ_XE])
def test_gain_kernel_compiles_for_v5e(one_chip, objective, return_gains):
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                            sharding=one_chip)

    def score(N, mu, sizes, P):
        return block_move_gains_pallas(
            N, mu, sizes, interpret=False, return_gains=return_gains,
            P=None if objective == OBJ_X else P, objective=objective)

    text = _compile_text(score, f32((B, K, L)), f32((B, K, L)), f32((M,)),
                         f32((B, K, L)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("objective", [OBJ_X, OBJ_XE])
def test_block_solver_compiles_for_v5e_with_the_kernel(one_chip, objective):
    """The whole batched solve (while loop around the kernel), as
    `grin_solve_batch_jax` runs it on a TPU."""
    from repro.core.grin import _grin_block_core
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                            sharding=one_chip)
    lowered = _grin_block_core.lower(
        f32((B, K, L)), f32((B, K)), f32((B, K, L)), n_sizes=M,
        max_moves=None, kernel="pallas-compiled", objective=objective)
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("block", [1024, 128])
def test_flash_attention_compiles_for_v5e_at_qwen_prefill(one_chip, block):
    bf16 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16,
                             sharding=one_chip)
    attend = functools.partial(flash_attention_pallas, causal=True,
                               block_q=block, block_k=block, valid_k=16,
                               interpret=False)
    text = _compile_text(attend, bf16((QWEN_BH, 1024, QWEN_DH)),
                         bf16((QWEN_BKV, 1024, QWEN_DH)),
                         bf16((QWEN_BKV, 1024, QWEN_DH)))
    assert "tpu_custom_call" in text


def test_rmsnorm_compiles_for_v5e(one_chip):
    bf16 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16,
                             sharding=one_chip)
    norm = functools.partial(rmsnorm_pallas, block_rows=256, interpret=False)
    text = _compile_text(norm, bf16((256, QWEN_D)), bf16((QWEN_D,)))
    assert "tpu_custom_call" in text
