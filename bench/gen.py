"""The one traffic generator: every mix under `bench/traffic/` is a JSON file
of parameters whose `kind` names one of the generators in `KINDS` below.
`for_mix` binds a mix to its generator, and the drivers take their traffic
only through it, so no driver names a kind. All draws come from `--seed`
through numpy's seeded generators, so one seed gives one traffic
realization on any machine.

  * `skewed_mixes` — closed re-solve requests: each request is `n_mixes`
    task-type mixes of `tasks_per_mix` tasks, shares drawn Dirichlet(alpha)
    (alpha < 1 skews them toward few types, which forces long drains).
"""
from __future__ import annotations

import functools

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream...) — any integer seed."""
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


def skewed_mixes(traffic: dict, k: int, seed: int, request: int) -> np.ndarray:
    """(n_mixes, k) int64 mixes of request number `request`."""
    rng = rng_for(seed, 1, request)
    shares = rng.dirichlet([traffic["alpha"]] * k, size=traffic["n_mixes"])
    return np.array([rng.multinomial(traffic["tasks_per_mix"], p)
                     for p in shares], dtype=np.int64)


KINDS = {"skewed_mixes": skewed_mixes}


def for_mix(traffic: dict):
    """The generator of a mix, with the mix's parameters bound."""
    if traffic.get("kind") not in KINDS:
        raise SystemExit(f"traffic kind {traffic.get('kind')!r} is not one "
                         f"of {sorted(KINDS)}")
    return functools.partial(KINDS[traffic["kind"]], traffic)
