"""Layer library: init + apply for every block kind used by the 10 archs.

Conventions:
  * params are plain nested dicts of jnp arrays (param_dtype, default fp32);
    compute casts to cfg.dtype (default bf16) at use.
  * every `apply_*` works in two modes:
      mode="full"   — whole sequence (train / prefill); returns fresh cache
                      pieces when `want_cache`.
      mode="decode" — one token against a cache; returns updated cache.
  * sharding is annotated via logical axes (repro.parallel.sharding.constrain)
    and is a no-op outside a mesh context.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models.attention import decode_attention
from repro.models.linear_scan import linear_scan_step
from repro.parallel.sharding import constrain


# ---------------------------------------------------------------- utilities

def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def dense_init(key, shape, cfg, scale=None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(_pdtype(cfg))


def rmsnorm(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def rope(x, positions, theta):
    """x: (..., S, H, dh) or (..., H, dh) with matching positions (..., S) /
    scalar. Standard half-split rotation."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs       # (..., S?, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., None, :]                                       # broadcast heads
    sin = sin[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([xf1 * cos - xf2 * sin,
                            xf2 * cos + xf1 * sin], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------- attention

def init_attention(key, cfg: ModelConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 3)
    p = {
        "wqkv": dense_init(ks[0], (d, (h + 2 * kv) * hd), cfg),
        "wo": dense_init(ks[1], (h * hd, d), cfg, scale=1.0 / math.sqrt(h * hd)),
    }
    if cfg.qkv_bias:
        p["bqkv"] = jnp.zeros(((h + 2 * kv) * hd,), _pdtype(cfg))
    return p


def apply_attention(p, x, cfg: ModelConfig, *, positions, mode="full",
                    cache=None, want_cache=False, window=0):
    """x: (B, S, D). positions: (S,) absolute (full) or scalar pos (decode).

    cache (decode or prefill-output): {"k","v": (B, Sc, KV, hd),
    "kpos": (Sc,) int32, "idx": scalar write cursor}.
    """
    b, s, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dt = _dtype(cfg)
    qkv = x @ p["wqkv"].astype(dt)
    if "bqkv" in p:
        qkv = qkv + p["bqkv"].astype(dt)
    q, k, v = jnp.split(qkv, [h * hd, (h + kv) * hd], axis=-1)
    q = constrain(q.reshape(b, s, h, hd), "dp", None, "tp", None)
    k = k.reshape(b, s, kv, hd)
    v = v.reshape(b, s, kv, hd)

    if mode == "decode":
        pos = positions  # scalar: number of tokens already in cache
        q = rope(q, jnp.asarray(pos)[None], cfg.rope_theta)
        k = rope(k, jnp.asarray(pos)[None], cfg.rope_theta)
        # Write into the slot holding the oldest (or empty, kpos=-1) position;
        # correctness only depends on kpos, not slot order, so this covers
        # both append-style full caches and sliding-window ring buffers.
        widx = jnp.argmin(cache["kpos"]).astype(jnp.int32)
        kc = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, widx, 0, 0))
        vc = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, widx, 0, 0))
        kpos = jax.lax.dynamic_update_slice(cache["kpos"],
                                            jnp.asarray(pos)[None].astype(jnp.int32),
                                            (widx,))
        out = decode_attention(q, kc, vc, pos, window=window, kpos=kpos)
        new_cache = {"k": kc, "v": vc, "kpos": kpos, "idx": cache["idx"] + 1}
    else:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        out = ops.flash_attention(q, k, v, causal=True, window=window,
                                  block_q=cfg.attn_chunk_q,
                                  block_k=cfg.attn_chunk_k)
        new_cache = None
        if want_cache:
            sc = min(window, s) if window else s
            new_cache = {
                "k": constrain(k[:, -sc:].astype(dt), "dp", "sp", None, None),
                "v": constrain(v[:, -sc:].astype(dt), "dp", "sp", None, None),
                "kpos": positions[-sc:].astype(jnp.int32),
                "idx": jnp.asarray(s, jnp.int32),
            }
    out = constrain(out, "dp", None, "tp", None)
    y = out.reshape(b, s, h * hd) @ p["wo"].astype(dt)
    return constrain(y, "dp", None, None), new_cache


def attention_cache_spec(cfg: ModelConfig, batch: int, seq_len: int, window: int):
    sc = min(window, seq_len) if window else seq_len
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    dt = _dtype(cfg)
    return {
        "k": jnp.zeros((batch, sc, kv, hd), dt),
        "v": jnp.zeros((batch, sc, kv, hd), dt),
        "kpos": jnp.full((sc,), -1, jnp.int32),
        "idx": jnp.asarray(0, jnp.int32),
    }


# ---------------------------------------------------------------- MLP / MoE

def init_mlp(key, cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"wu": dense_init(ks[1], (d, f), cfg),
         "wd": dense_init(ks[2], (f, d), cfg, scale=1.0 / math.sqrt(f))}
    if cfg.mlp_style == "swiglu":
        p["wg"] = dense_init(ks[0], (d, f), cfg)
    return p


def apply_mlp(p, x, cfg: ModelConfig):
    dt = _dtype(cfg)
    u = x @ p["wu"].astype(dt)
    if "wg" in p:                                   # SwiGLU (3 matrices)
        h = jax.nn.silu(x @ p["wg"].astype(dt)) * u
    else:                                           # GeLU (2 matrices)
        h = jax.nn.gelu(u)
    h = constrain(h, "dp", None, "tp")
    return constrain(h @ p["wd"].astype(dt), "dp", None, None)


def init_moe(key, cfg: ModelConfig) -> dict:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 3)
    return {
        "router": dense_init(ks[0], (d, e), cfg, scale=0.02),
        "w_in": dense_init(ks[1], (e, d, 2 * f), cfg),
        "w_out": dense_init(ks[2], (e, f, d), cfg, scale=1.0 / math.sqrt(f)),
    }


def apply_moe(p, x, cfg: ModelConfig):
    """Top-k routed experts: shard_map-local dispatch + weight-gather FFN.

    Under a mesh, the WHOLE MoE block runs inside shard_map over the dp axes:
    every shard selects its own top-C_local tokens per expert, gathers,
    applies the expert FFN and scatter-adds back — dispatch never crosses
    shards (EXPERIMENTS.md §Perf iter 6: a global-jit dispatch makes XLA
    replicate the top-k/scatter, catastrophically at 256-way dp). Expert
    weights enter replicated (in_specs P()), i.e. one all-gather per layer
    call — the ZeRO-style weight-gather MoE appropriate for 512-wide experts
    (DESIGN.md §5). Returns (y, aux_loss).
    """
    from repro.parallel import sharding as shctx
    mesh = shctx.current_mesh()
    tp = mesh.shape.get("model", 1) if mesh is not None else 1
    if mesh is None or (x.shape[1] == 1 and cfg.n_experts % tp == 0):
        # No mesh (smoke tests), or decode with cleanly TP-sharded experts
        # (a handful of tokens): the global path avoids gathering expert
        # weights per token step (§Perf: moe-1b decode 4.8 GB -> 55 MB).
        # Non-divisible expert counts (E=40, tp=16) keep the shard_map path:
        # their weights are replicated anyway and the global scatter reshards.
        return _apply_moe_local(p, x, cfg)
    from jax.sharding import PartitionSpec as P
    dp = shctx._CTX["rules"].get("dp") or ("data",)

    def local_fn(xl, router, w_in, w_out):
        y, aux = _apply_moe_local(
            {"router": router, "w_in": w_in, "w_out": w_out}, xl, cfg)
        return y, jax.lax.pmean(aux, axis_name=dp)

    y, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(dp, None, None), P(), P(), P()),
        out_specs=(P(dp, None, None), P()), check_vma=False,
    )(x, p["router"], p["w_in"], p["w_out"])
    return y, aux


def _apply_moe_local(p, x, cfg: ModelConfig):
    """Shard-local MoE math (also the no-mesh smoke-test path)."""
    b, s, d = x.shape
    e, k, f = cfg.n_experts, cfg.top_k, cfg.moe_d_ff
    dt = _dtype(cfg)
    t = b * s
    xf = x.reshape(t, d)
    scores = (xf @ p["router"].astype(dt)).astype(jnp.float32)   # (T, E)
    probs = jax.nn.softmax(scores, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)                # (T, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # combine weights (T, E): zero except chosen experts
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)      # (T, K, E)
    comb = (onehot * gate_vals[..., None]).sum(axis=1)           # (T, E)

    # capacity per expert (per shard-local token count under pjit this is the
    # global T; the sharded top_k lowers to a distributed selection).
    # Decode (s == 1) is dropless: a dropped token would corrupt generation.
    if s == 1:
        cap = t
    else:
        cap = max(1, int(math.ceil(t * k / e * cfg.capacity_factor)))
        cap = min(cap, t)
    sel_scores = comb.T                                          # (E, T)
    top_w, top_i = jax.lax.top_k(sel_scores, cap)                # (E, C)
    keep = top_w > 0.0
    xin = jnp.take(xf, top_i.reshape(-1), axis=0).reshape(e, cap, d)
    xin = xin * keep[..., None].astype(dt)   # no constraints: runs in shard_map

    h = jnp.einsum("ecd,edf->ecf", xin, p["w_in"].astype(dt))
    g, u = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(g) * u
    y_e = jnp.einsum("ecf,efd->ecd", h, p["w_out"].astype(dt))   # (E, C, D)
    y_e = y_e * (top_w * keep)[..., None].astype(dt)

    y = jnp.zeros((t, d), dt).at[top_i.reshape(-1)].add(
        y_e.reshape(e * cap, d), mode="drop")
    y = y.reshape(b, s, d)

    # load-balancing aux loss (Switch-style)
    frac_tokens = (onehot.sum(1) > 0).astype(jnp.float32).mean(axis=0)  # (E,)
    frac_probs = probs.mean(axis=0)
    aux = cfg.router_aux_coef * e * jnp.sum(frac_tokens * frac_probs)
    return y, aux


# ---------------------------------------------------------------- Mamba2

def init_mamba(key, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    din = cfg.d_inner
    ds = cfg.ssm_state
    hs = cfg.n_ssm_heads
    conv_ch = din + 2 * ds
    ks = jax.random.split(key, 4)
    return {
        "in_proj": dense_init(ks[0], (d, 2 * din + 2 * ds + hs), cfg),
        "conv_w": dense_init(ks[1], (cfg.ssm_conv_width, conv_ch), cfg, scale=0.5),
        "conv_b": jnp.zeros((conv_ch,), _pdtype(cfg)),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, hs)).astype(_pdtype(cfg)),
        "Dskip": jnp.ones((hs,), _pdtype(cfg)),
        "dt_bias": jnp.full((hs,), -2.0, _pdtype(cfg)),
        "out_proj": dense_init(ks[2], (din, d), cfg, scale=1.0 / math.sqrt(din)),
        "norm_g": jnp.zeros((din,), _pdtype(cfg)),
    }


def _causal_conv_full(u, w, b):
    """u: (B, S, C); depthwise causal conv width W. Returns (B, S, C)."""
    W = w.shape[0]
    pad = jnp.pad(u, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + u.shape[1]] * w[i][None, None, :] for i in range(W))
    return out + b[None, None, :]


def apply_mamba(p, x, cfg: ModelConfig, *, mode="full", cache=None,
                want_cache=False):
    """Mamba2 (SSD) block. cache: {"state": (B,Hs,ds,hd) f32,
    "conv": (B, W-1, conv_ch)}."""
    b, s, d = x.shape
    din, ds, hs, hd = cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads, cfg.ssm_head_dim
    W = cfg.ssm_conv_width
    dt = _dtype(cfg)
    proj = x @ p["in_proj"].astype(dt)
    z, xs, Bc, Cc, dts = jnp.split(
        proj, [din, 2 * din, 2 * din + ds, 2 * din + 2 * ds], axis=-1)
    conv_in = jnp.concatenate([xs, Bc, Cc], axis=-1)             # (B,S,conv_ch)

    if mode == "decode":
        hist = jnp.concatenate([cache["conv"].astype(dt), conv_in], axis=1)
        conv_out = (sum(hist[:, i:i + 1] * p["conv_w"].astype(dt)[i][None, None]
                        for i in range(W)) + p["conv_b"].astype(dt)[None, None])
        new_conv = hist[:, 1:]
    else:
        conv_out = _causal_conv_full(conv_in, p["conv_w"].astype(dt),
                                     p["conv_b"].astype(dt))
        new_conv = None
        if want_cache:
            padded = jnp.pad(conv_in, ((0, 0), (max(W - 1 - s, 0), 0), (0, 0)))
            new_conv = padded[:, -(W - 1):]
    conv_out = jax.nn.silu(conv_out)
    xs, Bc, Cc = jnp.split(conv_out, [din, din + ds], axis=-1)

    xh = xs.reshape(b, s, hs, hd)                                # v
    Bh = jnp.repeat(Bc[:, :, None, :], hs, axis=2)               # k: (B,S,Hs,ds)
    Ch = jnp.repeat(Cc[:, :, None, :], hs, axis=2)               # q
    dtv = jax.nn.softplus(dts.astype(jnp.float32)
                          + p["dt_bias"].astype(jnp.float32))    # (B,S,Hs)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))                 # (Hs,) < 0
    log_a = dtv * A[None, None, :]                               # <= 0

    if mode == "decode":
        y, state = linear_scan_step(Ch[:, 0], Bh[:, 0], xh[:, 0], log_a[:, 0],
                                    dtv[:, 0], cache["state"])
        y = y[:, None]                                           # (B,1,Hs,hd)
        new_cache = {"state": state, "conv": new_conv}
    else:
        y, state = ops.ssd_scan(Ch, Bh, xh, log_a, dtv, chunk=cfg.ssm_chunk)
        new_cache = ({"state": state, "conv": new_conv} if want_cache else None)

    y = y + p["Dskip"].astype(dt)[None, None, :, None] * xh
    y = y.reshape(b, s, din)
    y = rmsnorm(y * jax.nn.silu(z), p["norm_g"], cfg.norm_eps)
    out = y @ p["out_proj"].astype(dt)
    return constrain(out, "dp", None, None), new_cache


def mamba_cache_spec(cfg: ModelConfig, batch: int):
    return {
        "state": jnp.zeros((batch, cfg.n_ssm_heads, cfg.ssm_state,
                            cfg.ssm_head_dim), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv_width - 1,
                           cfg.d_inner + 2 * cfg.ssm_state), _dtype(cfg)),
    }


# ---------------------------------------------------------------- xLSTM

def init_mlstm(key, cfg: ModelConfig) -> dict:
    d, h, hd = cfg.d_model, cfg.n_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wqkv": dense_init(ks[0], (d, 3 * h * hd), cfg),
        "wif": dense_init(ks[1], (d, 2 * h), cfg, scale=0.02),
        "w_ogate": dense_init(ks[2], (d, h * hd), cfg, scale=0.02),
        "wo": dense_init(ks[3], (h * hd, d), cfg, scale=1.0 / math.sqrt(h * hd)),
        "ln_inner": jnp.zeros((h, hd), _pdtype(cfg)),
    }


def apply_mlstm(p, x, cfg: ModelConfig, *, mode="full", cache=None,
                want_cache=False):
    """mLSTM: matrix-memory linear attention with sigmoid forget / input
    gates. cache: {"C": (B,H,hd,hd) f32, "n": (B,H,hd,1) f32}."""
    b, s, d = x.shape
    h, hd = cfg.n_heads, cfg.resolved_head_dim
    dt = _dtype(cfg)
    qkv = x @ p["wqkv"].astype(dt)
    q, k, v = (t.reshape(b, s, h, hd) for t in jnp.split(qkv, 3, axis=-1))
    q = q / math.sqrt(hd)
    gates = (x @ p["wif"].astype(dt)).astype(jnp.float32)
    ig, fg = jnp.split(gates, 2, axis=-1)                        # (B,S,H)
    log_f = jax.nn.log_sigmoid(fg)
    i_in = jax.nn.sigmoid(ig)
    ones = jnp.ones((b, s, h, 1), dt)

    if mode == "decode":
        y, C = linear_scan_step(q[:, 0], k[:, 0], v[:, 0], log_f[:, 0],
                                i_in[:, 0], cache["C"])
        _, n = linear_scan_step(q[:, 0], k[:, 0], ones[:, 0], log_f[:, 0],
                                i_in[:, 0], cache["n"])
        nm = jnp.einsum("bhk,bhkv->bhv", q[:, 0].astype(jnp.float32), n)
        y = (y / jnp.maximum(jnp.abs(nm), 1.0)).astype(dt)[:, None]
        new_cache = {"C": C, "n": n}
    else:
        y, C = ops.ssd_scan(q, k, v, log_f, i_in, chunk=cfg.ssm_chunk)
        nm_seq, n = ops.ssd_scan(q, k, ones, log_f, i_in, chunk=cfg.ssm_chunk)
        y = (y / jnp.maximum(jnp.abs(nm_seq.astype(jnp.float32)), 1.0)).astype(dt)
        new_cache = ({"C": C, "n": n} if want_cache else None)

    y = rmsnorm(y, p["ln_inner"], cfg.norm_eps)
    og = jax.nn.sigmoid(x @ p["w_ogate"].astype(dt)).reshape(b, s, h, hd)
    y = (y * og).reshape(b, s, h * hd)
    return constrain(y @ p["wo"].astype(dt), "dp", None, None), new_cache


def mlstm_cache_spec(cfg: ModelConfig, batch: int):
    h, hd = cfg.n_heads, cfg.resolved_head_dim
    return {"C": jnp.zeros((batch, h, hd, hd), jnp.float32),
            "n": jnp.zeros((batch, h, hd, 1), jnp.float32)}


def init_slstm(key, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    return {"w_gates": dense_init(key, (d, 4 * d), cfg, scale=0.02)}


def apply_slstm(p, x, cfg: ModelConfig, *, mode="full", cache=None,
                want_cache=False):
    """sLSTM with per-channel scalar memory. Recurrent hidden-to-gate weights
    are omitted (R=0) to admit a parallel associative scan on TPU —
    documented adaptation (DESIGN.md §4). cache: {"c","n": (B, D) f32}."""
    b, s, d = x.shape
    dt = _dtype(cfg)
    pre = (x @ p["w_gates"].astype(dt)).astype(jnp.float32)
    ig, fg, zg, og = jnp.split(pre, 4, axis=-1)                  # (B,S,D)
    i = jnp.exp(jnp.clip(ig, -8.0, 8.0))
    f = jax.nn.sigmoid(fg)
    z = jnp.tanh(zg)
    o = jax.nn.sigmoid(og)

    if mode == "decode":
        c = f[:, 0] * cache["c"] + i[:, 0] * z[:, 0]
        n = f[:, 0] * cache["n"] + i[:, 0]
        hcur = (o[:, 0] * c / jnp.maximum(n, 1.0))[:, None]
        new_cache = {"c": c, "n": n}
        return hcur.astype(dt), new_cache

    def op(a, b_):
        (fa, xa), (fb, xb) = a, b_
        return fa * fb, xb + fb * xa

    f_c, c = jax.lax.associative_scan(op, (f, i * z), axis=1)
    f_n, n = jax.lax.associative_scan(op, (f, i), axis=1)
    hseq = o * c / jnp.maximum(n, 1.0)
    new_cache = ({"c": c[:, -1], "n": n[:, -1]} if want_cache else None)
    return hseq.astype(dt), new_cache


def slstm_cache_spec(cfg: ModelConfig, batch: int):
    return {"c": jnp.zeros((batch, cfg.d_model), jnp.float32),
            "n": jnp.zeros((batch, cfg.d_model), jnp.float32)}
