"""Batched serving engine: prefill + decode with a persistent cache.

The engine is the unit the paper's scheduler dispatches: a `prefill` call or a
`decode_run` (n greedy steps) is one "task"; pools (repro.sched.cluster) own
one engine each and serve FCFS — mirroring the paper's real-platform setup
(OpenCL contexts with one queue per device, Sec. 7.1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.model import Model


def _to_serving_dtype(params):
    return jax.tree.map(
        lambda p: p.astype(jnp.bfloat16)
        if jnp.issubdtype(p.dtype, jnp.floating) else p, params)


def init_serving_params(model: Model, key):
    """Random weights straight in the serving dtype: one jitted
    init-and-cast, so no float32 tree ever lands on the device (at 3B
    parameters that tree alone is 12 GB)."""
    return jax.jit(lambda k: _to_serving_dtype(model.init(k)))(key)


class ServeEngine:
    def __init__(self, model: Model, params, max_len: int = 512):
        self.model = model
        self.cfg = model.cfg
        self.max_len = max_len
        # bf16 serving weights (no copy when they already are)
        self.params = _to_serving_dtype(params)
        self._prefill = jax.jit(
            functools.partial(model.prefill, cache_len=max_len))
        self._decode = jax.jit(model.decode_step)

    def prefill(self, batch: dict):
        logits, cache = self._prefill(self.params, batch)
        return logits, cache

    def _greedy_next(self, logits):
        """Greedy token from last-position logits: (emitted, feed) where
        `emitted` is (B,) — or (B, K) for audio codebooks — and `feed` has the
        trailing length-1 axis `decode_step` expects."""
        nxt = jnp.argmax(logits[:, -1], axis=-1)
        if self.cfg.family == "audio":
            return nxt, nxt[:, :, None].astype(jnp.int32)
        return nxt, nxt[:, None].astype(jnp.int32)

    def decode_run(self, first_token, cache, start_pos: int, steps: int):
        """Greedy-decode `steps` tokens. Returns (tokens, cache)."""
        tok = first_token
        out = []
        pos = start_pos
        for _ in range(steps):
            logits, cache = self._decode(self.params, tok, cache,
                                         jnp.asarray(pos, jnp.int32))
            nxt, tok = self._greedy_next(logits)
            out.append(nxt)
            pos += 1
        return jnp.stack(out, axis=1), cache

    def generate(self, batch: dict, steps: int):
        """prefill + greedy decode; returns generated token ids."""
        logits, cache = self.prefill(batch)
        first, feed = self._greedy_next(logits)
        if self.cfg.family == "audio":
            start = batch["tokens"].shape[-1]
        else:
            start = batch["tokens"].shape[1]
            if self.cfg.family == "vlm" and "patch_embeds" in batch:
                start += batch["patch_embeds"].shape[1]
        toks, cache = self.decode_run(feed, cache, start, steps - 1)
        return jnp.concatenate([first[:, None], toks], axis=1)


def request_service_fns(engine: ServeEngine, batch: dict, toks,
                        slowdown: int = 3):
    """Two request classes on two heterogeneous pools, as real work.

    Class 0 is a PREFILL request (one batched prefill — the interactive,
    latency-sensitive class) and class 1 a DECODE request (short prefill +
    a greedy decode run — the batch class). Pool 0 favors prefill, pool 1
    decode; the off-diagonal runs `slowdown` repetitions, giving the 2 x 2
    heterogeneous affinity the paper's CAB/GrIn placement exploits. Returns
    `service_fns` for `repro.sched.virtual.VirtualTimeCluster` — the shared
    service-function set behind `repro.launch.serve --heterogeneous` /
    `--traffic` and `examples/serve_heterogeneous.py`.
    """
    cfg = engine.cfg

    def prefill_task(size):
        logits, _ = engine.prefill(batch)
        jax.block_until_ready(logits)

    def decode_task(size):
        _, cache = engine.prefill(
            {k: (v[:, :4] if k == "tokens" and cfg.family != "audio"
                 else v) for k, v in batch.items()})
        o, _ = engine.decode_run(
            toks[:, :1] if cfg.family != "audio" else toks[:, :, :1],
            cache, 4, 4)
        jax.block_until_ready(o)

    def slow(fn, n):
        return lambda size: [fn(size) for _ in range(n)]

    return [{0: prefill_task, 1: slow(decode_task, slowdown)},
            {0: slow(prefill_task, slowdown), 1: decode_task}]


def with_retries(service_fn, *, max_attempts: int = 3,
                 retryable: tuple = (RuntimeError, OSError),
                 on_wasted=None):
    """Wrap one service fn with transient-failure re-execution.

    The serving analogue of `repro.faults` transient task failures: a
    retryable exception loses the whole attempt (full re-execution — there
    is no mid-request checkpoint in serving), the task re-runs up to
    `max_attempts` times, and every lost attempt is reported through
    `on_wasted(attempt_index)` so a driver can account wasted work against
    goodput. Non-retryable exceptions and exhaustion propagate.
    """
    if max_attempts < 1:
        raise ValueError(f"max_attempts must be >= 1, got {max_attempts}")

    def wrapped(size):
        for attempt in range(max_attempts):
            try:
                return service_fn(size)
            except retryable:
                if on_wasted is not None:
                    on_wasted(attempt)
                if attempt + 1 >= max_attempts:
                    raise
    return wrapped
