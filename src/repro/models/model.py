"""Unified model API over all 10 assigned architectures.

`build_model(cfg)` returns a `Model` whose methods are pure functions:

    init(key) -> params
    forward(params, batch) -> logits (fp32)
    init_cache(batch_size, cache_len) -> cache pytree
    prefill(params, batch) -> (last_logits, cache)
    decode_step(params, tokens, cache, pos) -> (logits, cache)
    input_specs(shape) -> batch of ShapeDtypeStructs (dry-run stand-ins)

Layer stacks are lax.scan-ed over stacked params ("stack_*" subtrees) so the
HLO stays compact for 512-device compiles.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import layers as L
from repro.parallel.sharding import constrain


def _split(key, n):
    return list(jax.random.split(key, n))


# --------------------------------------------------------------- block defs

def _init_dense_block(key, cfg: ModelConfig, moe: bool) -> dict:
    ks = _split(key, 2)
    d = cfg.d_model
    p = {"ln1": jnp.zeros((d,), jnp.dtype(cfg.param_dtype)),
         "ln2": jnp.zeros((d,), jnp.dtype(cfg.param_dtype)),
         "attn": L.init_attention(ks[0], cfg)}
    if moe:
        p["moe"] = L.init_moe(ks[1], cfg)
    else:
        p["mlp"] = L.init_mlp(ks[1], cfg)
    return p


def _apply_dense_block(p, x, cfg, *, positions, mode, cache, want_cache,
                       window=0):
    a, c = L.apply_attention(p["attn"], L.rmsnorm(x, p["ln1"], cfg.norm_eps),
                             cfg, positions=positions, mode=mode,
                             cache=None if cache is None else cache["attn"],
                             want_cache=want_cache, window=window)
    x = x + a
    aux = jnp.asarray(0.0, jnp.float32)
    h = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        m, aux = L.apply_moe(p["moe"], h, cfg)
    else:
        m = L.apply_mlp(p["mlp"], h, cfg)
    x = x + m
    new_cache = None if c is None else {"attn": c}
    return x, new_cache, aux


def _init_mamba_block(key, cfg) -> dict:
    return {"ln": jnp.zeros((cfg.d_model,), jnp.dtype(cfg.param_dtype)),
            "mamba": L.init_mamba(key, cfg)}


def _apply_mamba_block(p, x, cfg, *, mode, cache, want_cache):
    h = L.rmsnorm(x, p["ln"], cfg.norm_eps)
    y, c = L.apply_mamba(p["mamba"], h, cfg, mode=mode,
                         cache=None if cache is None else cache["mamba"],
                         want_cache=want_cache)
    return x + y, (None if c is None else {"mamba": c})


def _init_mlstm_block(key, cfg) -> dict:
    return {"ln": jnp.zeros((cfg.d_model,), jnp.dtype(cfg.param_dtype)),
            "mlstm": L.init_mlstm(key, cfg)}


def _apply_mlstm_block(p, x, cfg, *, mode, cache, want_cache):
    h = L.rmsnorm(x, p["ln"], cfg.norm_eps)
    y, c = L.apply_mlstm(p["mlstm"], h, cfg, mode=mode,
                         cache=None if cache is None else cache["mlstm"],
                         want_cache=want_cache)
    return x + y, (None if c is None else {"mlstm": c})


def _init_slstm_block(key, cfg) -> dict:
    return {"ln": jnp.zeros((cfg.d_model,), jnp.dtype(cfg.param_dtype)),
            "slstm": L.init_slstm(key, cfg)}


def _apply_slstm_block(p, x, cfg, *, mode, cache, want_cache):
    h = L.rmsnorm(x, p["ln"], cfg.norm_eps)
    y, c = L.apply_slstm(p["slstm"], h, cfg, mode=mode,
                         cache=None if cache is None else cache["slstm"],
                         want_cache=want_cache)
    return x + y, (None if c is None else {"slstm": c})


# --------------------------------------------------------------- model

@dataclasses.dataclass
class Model:
    cfg: ModelConfig

    # ---------------- init ----------------
    def init(self, key) -> dict:
        cfg = self.cfg
        pdt = jnp.dtype(cfg.param_dtype)
        ks = _split(key, 8)
        params: dict[str, Any] = {"ln_f": jnp.zeros((cfg.d_model,), pdt)}

        if cfg.family == "audio":
            params["embed"] = (jax.random.normal(
                ks[0], (cfg.n_codebooks, cfg.vocab_size, cfg.d_model)) * 0.02
            ).astype(pdt)
            params["heads"] = (jax.random.normal(
                ks[1], (cfg.n_codebooks, cfg.d_model, cfg.vocab_size)) * 0.02
            ).astype(pdt)
        else:
            params["embed"] = (jax.random.normal(
                ks[0], (cfg.vocab_size, cfg.d_model)) * 0.02).astype(pdt)
            if not cfg.tie_embeddings:
                params["lm_head"] = (jax.random.normal(
                    ks[1], (cfg.d_model, cfg.vocab_size)) * 0.02).astype(pdt)
        if cfg.family == "vlm":
            params["patch_proj"] = L.dense_init(ks[2], (cfg.d_model, cfg.d_model), cfg)

        lkey = ks[3]
        if cfg.family in ("dense", "audio", "vlm"):
            params["stack"] = jax.vmap(
                lambda k: _init_dense_block(k, cfg, moe=False)
            )(jnp.stack(_split(lkey, cfg.n_layers)))
        elif cfg.family == "moe":
            params["stack"] = jax.vmap(
                lambda k: _init_dense_block(k, cfg, moe=True)
            )(jnp.stack(_split(lkey, cfg.n_layers)))
        elif cfg.family == "hybrid":
            g, r = divmod(cfg.n_layers, cfg.attn_every)
            gk = jnp.stack(_split(lkey, g * cfg.attn_every)).reshape(
                g, cfg.attn_every, 2)
            params["stack_groups"] = jax.vmap(jax.vmap(
                lambda k: _init_mamba_block(k, cfg)))(gk)
            params["shared"] = _init_dense_block(ks[4], cfg, moe=False)
            if r:
                params["stack_tail"] = jax.vmap(
                    lambda k: _init_mamba_block(k, cfg)
                )(jnp.stack(_split(ks[5], r)))
        elif cfg.family == "ssm":  # xLSTM
            g = cfg.n_layers // cfg.slstm_every
            m = cfg.slstm_every - 1
            mk = jnp.stack(_split(lkey, g * m)).reshape(g, m, 2)
            params["stack_groups"] = {
                "mlstm": jax.vmap(jax.vmap(
                    lambda k: _init_mlstm_block(k, cfg)))(mk),
                "slstm": jax.vmap(lambda k: _init_slstm_block(k, cfg))(
                    jnp.stack(_split(ks[6], g))),
            }
        else:
            raise ValueError(f"unknown family {cfg.family}")
        return params

    # ---------------- embedding / head ----------------
    def _embed(self, params, batch):
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        if cfg.family == "audio":
            tok = batch["tokens"]                     # (B, K, S)
            embs = [jnp.take(params["embed"][k], tok[:, k], axis=0)
                    for k in range(cfg.n_codebooks)]
            x = sum(embs).astype(dt)
        else:
            x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(dt)
        if cfg.family == "vlm" and "patch_embeds" in batch:
            pe = batch["patch_embeds"].astype(dt) @ params["patch_proj"].astype(dt)
            x = jnp.concatenate([pe, x], axis=1)
        return constrain(x, "dp", None, None)

    def _head(self, params, x):
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
        if cfg.family == "audio":
            logits = jnp.einsum("bsd,kdv->bskv", x, params["heads"].astype(dt))
        elif cfg.tie_embeddings:
            logits = x @ params["embed"].T.astype(dt)
        else:
            logits = x @ params["lm_head"].astype(dt)
        return constrain(logits.astype(jnp.float32), "dp", None, "tp") \
            if cfg.family != "audio" else logits.astype(jnp.float32)

    # ---------------- stack application ----------------
    def _run_stack(self, params, x, *, positions, mode, caches, want_cache):
        """Returns (x, new_caches, aux_sum)."""
        cfg = self.cfg
        aux_total = jnp.asarray(0.0, jnp.float32)

        if cfg.family in ("dense", "moe", "audio", "vlm"):
            def body(carry, xs):
                h, aux = carry
                p, c = xs
                h, nc, a = _apply_dense_block(p, h, cfg, positions=positions,
                                              mode=mode, cache=c,
                                              want_cache=want_cache)
                return (h, aux + a), nc

            (x, aux_total), new = jax.lax.scan(
                body, (x, aux_total),
                (params["stack"], caches["stack"] if caches else None))
            return x, ({"stack": new} if (want_cache or mode == "decode") else None), aux_total

        if cfg.family == "hybrid":
            win = cfg.sliding_window

            def group(carry, xs):
                h, aux = carry
                p, c = xs

                def inner(hh, mxs):
                    mp, mc = mxs
                    hh, nmc = _apply_mamba_block(mp, hh, cfg, mode=mode,
                                                 cache=mc, want_cache=want_cache)
                    return hh, nmc

                h, new_m = jax.lax.scan(
                    inner, h, (p["mamba_stack"], c["mamba"] if c else None))
                h, new_a, a = _apply_dense_block(
                    params["shared"], h, cfg, positions=positions, mode=mode,
                    cache=c["attn"] if c else None, want_cache=want_cache,
                    window=win)
                return (h, aux + a), {"mamba": new_m, "attn": new_a}

            gc = caches["groups"] if caches else None
            (x, aux_total), new_g = jax.lax.scan(
                group, (x, aux_total),
                ({"mamba_stack": params["stack_groups"]}, gc))
            new_t = None
            if "stack_tail" in params:
                def tail(carry, xs):
                    h, aux = carry
                    p, c = xs
                    h, nc = _apply_mamba_block(p, h, cfg, mode=mode, cache=c,
                                               want_cache=want_cache)
                    return (h, aux), nc
                (x, aux_total), new_t = jax.lax.scan(
                    tail, (x, aux_total),
                    (params["stack_tail"], caches["tail"] if caches else None))
            out_c = None
            if want_cache or mode == "decode":
                out_c = {"groups": new_g}
                if new_t is not None:
                    out_c["tail"] = new_t
            return x, out_c, aux_total

        if cfg.family == "ssm":
            def group(carry, xs):
                h, aux = carry
                p, c = xs

                def inner(hh, mxs):
                    mp, mc = mxs
                    hh, nmc = _apply_mlstm_block(mp, hh, cfg, mode=mode,
                                                 cache=mc, want_cache=want_cache)
                    return hh, nmc

                h, new_m = jax.lax.scan(
                    inner, h, (p["mlstm"], c["mlstm"] if c else None))
                h, new_s = _apply_slstm_block(p["slstm"], h, cfg, mode=mode,
                                              cache=c["slstm"] if c else None,
                                              want_cache=want_cache)
                return (h, aux), {"mlstm": new_m, "slstm": new_s}

            gc = caches["groups"] if caches else None
            (x, aux_total), new_g = jax.lax.scan(
                group, (x, aux_total),
                (params["stack_groups"], gc))
            out_c = {"groups": new_g} if (want_cache or mode == "decode") else None
            return x, out_c, aux_total

        raise ValueError(cfg.family)

    # ---------------- public API ----------------
    def forward(self, params, batch, *, want_cache=False):
        cfg = self.cfg
        x = self._embed(params, batch)
        s = x.shape[1]
        positions = jnp.arange(s)
        x, caches, aux = self._run_stack(params, x, positions=positions,
                                         mode="full", caches=None,
                                         want_cache=want_cache)
        logits = self._head(params, x)
        if want_cache:
            return logits, caches, aux
        return logits, aux

    # ---------------- caches / serving ----------------
    def init_cache(self, batch: int, cache_len: int):
        cfg = self.cfg

        def stackify(tree, *ns):
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a, tuple(ns) + a.shape), tree)

        if cfg.family in ("dense", "moe", "audio", "vlm"):
            base = {"attn": L.attention_cache_spec(cfg, batch, cache_len, 0)}
            return {"stack": stackify(base, cfg.n_layers)}
        if cfg.family == "hybrid":
            g, r = divmod(cfg.n_layers, cfg.attn_every)
            mam = {"mamba": L.mamba_cache_spec(cfg, batch)}
            att = {"attn": L.attention_cache_spec(cfg, batch, cache_len,
                                                  cfg.sliding_window)}
            out = {"groups": {"mamba": stackify(mam, g, cfg.attn_every),
                              "attn": stackify(att, g)}}
            if r:
                out["tail"] = stackify(mam, r)
            return out
        if cfg.family == "ssm":
            g = cfg.n_layers // cfg.slstm_every
            m = cfg.slstm_every - 1
            return {"groups": {
                "mlstm": stackify({"mlstm": L.mlstm_cache_spec(cfg, batch)}, g, m),
                "slstm": stackify({"slstm": L.slstm_cache_spec(cfg, batch)}, g),
            }}
        raise ValueError(cfg.family)

    def prefill(self, params, batch, cache_len: int | None = None):
        """Full-sequence pass building the cache; the head is applied ONLY to
        the final position (materializing (B, S, V) logits at 32k would be
        hundreds of GB). `cache_len` pads attention caches with empty slots
        (kpos = -1) so subsequent decode steps have room to append."""
        x = self._embed(params, batch)
        positions = jnp.arange(x.shape[1])
        x, caches, _ = self._run_stack(params, x, positions=positions,
                                       mode="full", caches=None,
                                       want_cache=True)
        logits = self._head(params, x[:, -1:])
        if cache_len is not None:
            caches = _pad_attention_caches(caches, cache_len,
                                           self.cfg.sliding_window)
        return logits, caches

    def decode_step(self, params, tokens, cache, pos):
        """tokens: (B, 1) (audio: (B, K, 1)); pos: scalar int32 = number of
        tokens already processed. Returns (logits_for_new_token, new_cache)."""
        cfg = self.cfg
        x = self._embed(params, {"tokens": tokens})
        x, new_cache, _ = self._run_stack(params, x, positions=pos,
                                          mode="decode", caches=cache,
                                          want_cache=False)
        logits = self._head(params, x)
        return logits, new_cache

    # ---------------- dry-run stand-ins ----------------
    def input_specs(self, shape: ShapeConfig) -> dict:
        """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        sds = jax.ShapeDtypeStruct
        if shape.kind == "train":
            if cfg.family == "audio":
                out = {"tokens": sds((B, cfg.n_codebooks, S), i32),
                       "targets": sds((B, cfg.n_codebooks, S), i32)}
            else:
                out = {"tokens": sds((B, S), i32), "targets": sds((B, S), i32)}
            if cfg.family == "vlm":
                out["patch_embeds"] = sds((B, cfg.n_patches, cfg.d_model),
                                          jnp.dtype(cfg.dtype))
            return out
        if shape.kind == "prefill":
            if cfg.family == "audio":
                out = {"tokens": sds((B, cfg.n_codebooks, S), i32)}
            else:
                out = {"tokens": sds((B, S), i32)}
            if cfg.family == "vlm":
                out["patch_embeds"] = sds((B, cfg.n_patches, cfg.d_model),
                                          jnp.dtype(cfg.dtype))
            return out
        if shape.kind == "decode":
            if cfg.family == "audio":
                return {"tokens": sds((B, cfg.n_codebooks, 1), i32)}
            return {"tokens": sds((B, 1), i32)}
        raise ValueError(shape.kind)


def _pad_attention_caches(caches, cache_len: int, window: int):
    """Pad every attention cache's sequence axis to its target ring size:
    min(window, cache_len) for windowed attention, else cache_len. Empty
    slots carry kpos = -1 (masked out by decode_attention)."""
    target = min(window, cache_len) if window else cache_len

    def pad(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        if name in ("k", "v"):
            cur = leaf.shape[-3]
            if cur < target:
                pads = [(0, 0)] * leaf.ndim
                pads[-3] = (0, target - cur)
                return jnp.pad(leaf, pads)
        elif name == "kpos":
            cur = leaf.shape[-1]
            if cur < target:
                pads = [(0, 0)] * leaf.ndim
                pads[-1] = (0, target - cur)
                return jnp.pad(leaf, pads, constant_values=-1)
        return leaf

    return jax.tree_util.tree_map_with_path(pad, caches)


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)


def count_params(cfg: ModelConfig) -> int:
    import math as _math
    m = build_model(cfg)
    tree = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0)))
    return int(sum(_math.prod(l.shape) for l in jax.tree.leaves(tree)))
