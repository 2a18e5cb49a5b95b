"""Run metadata: make every perf number attributable.

`run_meta()` captures the execution substrate a measurement ran on — jax
backend, device kind and count, Pallas kernel mode (compiled TPU kernel vs
interpret-mode vs the jnp reference), compute dtype, versions.
`benchmarks.common.save_json` attaches it to every BENCH payload and the
engines stamp it onto SimMetrics rows, so a "1.4x faster" claim always says
1.4x faster *where*.
"""
from __future__ import annotations

import platform


def kernel_mode() -> str:
    """Which gain-scoring path `repro.kernels.grin_moves` takes by default:
    "pallas-compiled" (TPU), "pallas-interpret" (REPRO_PALLAS_INTERPRET=1),
    or "jnp-reference"."""
    from repro.kernels.grin_moves import kernel_mode as _mode
    return _mode()


def run_meta() -> dict:
    """Machine-readable substrate block for benchmark payloads / metrics."""
    import jax
    return {
        "jax_backend": jax.default_backend(),
        "jax_version": jax.__version__,
        "device_kind": jax.devices()[0].device_kind,
        "device_count": jax.device_count(),
        "kernel_mode": kernel_mode(),
        "dtype": "float32",              # the engines' device state dtype
        "python": platform.python_version(),
        "platform": platform.system().lower(),
    }


__all__ = ["run_meta", "kernel_mode"]
