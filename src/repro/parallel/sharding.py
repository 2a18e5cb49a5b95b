"""Logical-axis sharding: models annotate with logical names ("dp", "fsdp",
"tp", "sp"); the launcher binds them to mesh axes. Outside a mesh context all
constraints are no-ops, so smoke tests run unmodified on one CPU device.

Bindings:
  single-pod (16, 16)   ("data", "model"):          dp/fsdp -> data, tp/sp -> model
  multi-pod (2, 16, 16) ("pod", "data", "model"):   dp/fsdp -> (pod, data), tp/sp -> model
"""
from __future__ import annotations

import contextlib
import re

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_CTX: dict = {"mesh": None, "rules": {}}

RULES_SINGLE_POD = {"dp": ("data",), "fsdp": ("data",), "tp": ("model",),
                    "sp": ("model",)}
RULES_MULTI_POD = {"dp": ("pod", "data"), "fsdp": ("pod", "data"),
                   "tp": ("model",), "sp": ("model",)}
# Pure ZeRO-3 data parallelism: batch + parameter shards over EVERY chip, no
# tensor parallelism. At train_4k batch sizes this eliminates TP activation
# reduces and head-padding reshards entirely (EXPERIMENTS.md §Perf iter 5).
RULES_PURE_DP_SINGLE = {"dp": ("data", "model"), "fsdp": ("data", "model"),
                        "tp": None, "sp": None}
RULES_PURE_DP_MULTI = {"dp": ("pod", "data", "model"),
                       "fsdp": ("pod", "data", "model"), "tp": None, "sp": None}
# Prefill: batch over the data axis only (prefill_32k has B=32), parameters
# FSDP over the whole fleet, no TP — per-layer bf16 weight gathers cost far
# less than TP activation reduces at 32k tokens (§Perf iter 8).
RULES_PREFILL_SINGLE = {"dp": ("data",), "fsdp": ("data", "model"),
                        "tp": None, "sp": None}
RULES_PREFILL_MULTI = {"dp": ("pod", "data"),
                       "fsdp": ("pod", "data", "model"), "tp": None, "sp": None}


def rules_for_mesh(mesh: Mesh) -> dict:
    return RULES_MULTI_POD if "pod" in mesh.axis_names else RULES_SINGLE_POD


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None, rules: dict | None = None):
    """Bind the ambient mesh + logical-axis rules (trace-time context)."""
    old = dict(_CTX)
    _CTX.update(mesh=mesh, rules=rules or (rules_for_mesh(mesh) if mesh else {}))
    try:
        if mesh is not None:
            with mesh:
                yield
        else:
            yield
    finally:
        _CTX.update(old)


def current_mesh() -> Mesh | None:
    return _CTX["mesh"]


def logical_to_spec(axes) -> P:
    rules = _CTX["rules"]
    parts = []
    for a in axes:
        if a is None:
            parts.append(None)
        else:
            r = rules.get(a)
            parts.append(r if r else None)
    return P(*parts)


def constrain(x, *axes):
    """with_sharding_constraint by logical axis names; no-op without a mesh."""
    mesh = _CTX["mesh"]
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, logical_to_spec(axes)))


# ---------------------------------------------------------------------------
# Parameter partition rules: path-name -> logical axes per dimension.
# Stacked (scanned) parameter subtrees contain a path component matching
# "stack"; their specs get a leading None for the layer axis (possibly two for
# doubly-stacked hybrid groups, resolved by rank difference).
# ---------------------------------------------------------------------------

_PARAM_RULES: list[tuple[str, tuple]] = [
    (r"embed$", ("tp", "fsdp")),            # (V, D); (K, V, D) handled by rank
    (r"lm_head$", ("fsdp", "tp")),
    (r"heads$", (None, "fsdp", "tp")),      # musicgen codebook heads (K, D, V)
    (r"patch_proj$", ("fsdp", "tp")),
    (r"router$", ("fsdp", None)),
    (r"w_in$", ("tp", "fsdp", None)),       # experts (E, D, 2F)
    (r"w_out$", ("tp", None, "fsdp")),      # experts (E, F, D)
    (r"(wqkv|wg|wu|wif|w_ogate|in_proj|w_gates)$", ("fsdp", "tp")),
    (r"(wo|wd|out_proj)$", ("tp", "fsdp")),
    (r"conv_w$", (None, "tp")),
    (r"(conv_b|bqkv|A_log|Dskip|dt_bias)$", ("tp",)),
    (r"ln_inner$", (None, None)),
    (r".*", (None,)),                        # norms, scalars, leftovers
]


def param_logical_axes(path: tuple[str, ...], ndim: int) -> tuple:
    name = path[-1]
    stacked_levels = sum(1 for p in path if "stack" in p)
    for pat, axes in _PARAM_RULES:
        if re.search(pat, name):
            axes = tuple(axes)
            # rank-adjust: pad leading Nones (stacking or extra leading dims)
            if len(axes) < ndim:
                axes = (None,) * (ndim - len(axes)) + axes
            elif len(axes) > ndim:
                axes = axes[len(axes) - ndim:]
            return axes
    return (None,) * ndim


def even_spec(spec: P, shape, mesh: Mesh) -> P:
    """Drop spec axes that do not evenly divide the dimension (argument
    shardings must divide; uneven cases fall back to replication on that dim
    and are recorded by the dry-run via the resulting spec)."""
    parts = []
    for i, ax in enumerate(spec):
        if ax is None:
            parts.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        parts.append(ax if shape[i] % size == 0 else None)
    return P(*parts)


def param_pspec_tree(params_shape_tree, mesh: Mesh | None = None):
    """PartitionSpec pytree for an (abstract) param tree via the rules.
    With `mesh` (or an ambient mesh), non-dividing axes are dropped."""
    mesh = mesh or _CTX["mesh"]

    def spec(path, leaf):
        keys = tuple(getattr(p, "key", getattr(p, "idx", str(p))) for p in path)
        keys = tuple(str(k) for k in keys)
        s = logical_to_spec(param_logical_axes(keys, len(leaf.shape)))
        return even_spec(s, leaf.shape, mesh) if mesh is not None else s
    return jax.tree_util.tree_map_with_path(spec, params_shape_tree)
