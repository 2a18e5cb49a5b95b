"""Per-arch smoke tests (reduced configs) + decode consistency + causality."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_shape, shapes_for, smoke_config
from repro.models.model import build_model, count_params

KEY = jax.random.PRNGKey(0)
B, S = 2, 24

EXPECTED_PARAMS_B = {        # advertised sizes (sanity band)
    "zamba2-7b": (6.0, 8.0), "yi-6b": (5.5, 6.5), "qwen2.5-32b": (31, 34),
    "qwen2.5-3b": (2.8, 3.4), "granite-34b": (32, 36), "xlstm-1.3b": (1.0, 1.5),
    "granite-moe-1b-a400m": (1.1, 1.5), "granite-moe-3b-a800m": (3.0, 3.6),
    "musicgen-medium": (1.3, 2.1), "phi-3-vision-4.2b": (3.5, 4.3),
}


def _batch(sc):
    if sc.family == "audio":
        t = jax.random.randint(KEY, (B, sc.n_codebooks, S), 0, sc.vocab_size)
    else:
        t = jax.random.randint(KEY, (B, S), 0, sc.vocab_size)
    batch = {"tokens": t}
    if sc.family == "vlm":
        batch["patch_embeds"] = jax.random.normal(
            KEY, (B, sc.n_patches, sc.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_smoke_forward_and_train_step(arch):
    """Reduced same-family config: one forward on CPU, asserting output
    shapes and no NaNs."""
    sc = smoke_config(ARCHS[arch])
    m = build_model(sc)
    params = m.init(KEY)
    batch = _batch(sc)
    logits, _ = m.forward(params, batch)
    if sc.family == "audio":
        assert logits.shape == (B, S, sc.n_codebooks, sc.vocab_size)
    elif sc.family == "vlm":
        assert logits.shape == (B, S + sc.n_patches, sc.vocab_size)
    else:
        assert logits.shape == (B, S, sc.vocab_size)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_param_counts_match_advertised(arch):
    lo, hi = EXPECTED_PARAMS_B[arch]
    n = count_params(ARCHS[arch]) / 1e9
    assert lo <= n <= hi, f"{arch}: {n:.2f}B outside [{lo}, {hi}]"


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_decode_matches_full_forward(arch):
    """prefill + decode_step == full forward at the last position (fp32)."""
    sc = smoke_config(ARCHS[arch]).with_(dtype="float32")
    if sc.family == "moe":   # train-path capacity drops; use dropless
        sc = sc.with_(capacity_factor=float(sc.n_experts / sc.top_k))
    m = build_model(sc)
    params = m.init(KEY)
    full = _batch(sc)
    toks = full["tokens"]
    pre = dict(full)
    if sc.family == "audio":
        pre["tokens"] = toks[..., :S - 1]
        last = toks[..., S - 1:]
    else:
        pre["tokens"] = toks[:, :S - 1]
        last = toks[:, S - 1:]
    logits_full, _ = m.forward(params, full)
    npre = S - 1 + (sc.n_patches if sc.family == "vlm" else 0)
    _, cache = m.prefill(params, pre, cache_len=npre + 4)
    logits_dec, _ = m.decode_step(params, last, cache,
                                  jnp.asarray(npre, jnp.int32))
    np.testing.assert_allclose(np.asarray(logits_full[:, -1]),
                               np.asarray(logits_dec[:, -1]),
                               atol=2e-4, rtol=2e-3)


@pytest.mark.parametrize("arch", ["yi-6b", "zamba2-7b", "xlstm-1.3b",
                                  "granite-moe-1b-a400m", "musicgen-medium"])
def test_causality(arch):
    """Logits at position t are unchanged by edits to tokens > t.

    MoE uses dropless capacity here: with a capacity LIMIT, the dropped-token
    set depends on the whole batch (future tokens compete for expert slots) —
    the standard non-causality caveat of capacity-based MoE training."""
    sc = smoke_config(ARCHS[arch]).with_(dtype="float32")
    if sc.family == "moe":
        sc = sc.with_(capacity_factor=float(sc.n_experts / sc.top_k))
    m = build_model(sc)
    params = m.init(KEY)
    b1 = _batch(sc)
    b2 = {k: v.copy() for k, v in b1.items()}
    if sc.family == "audio":
        b2["tokens"] = b2["tokens"].at[:, :, -4:].set(
            (b2["tokens"][:, :, -4:] + 1) % sc.vocab_size)
    else:
        b2["tokens"] = b2["tokens"].at[:, -4:].set(
            (b2["tokens"][:, -4:] + 1) % sc.vocab_size)
    l1, _ = m.forward(params, b1)
    l2, _ = m.forward(params, b2)
    t_cut = S - 4
    np.testing.assert_allclose(np.asarray(l1[:, :t_cut - 1]),
                               np.asarray(l2[:, :t_cut - 1]),
                               atol=1e-5, rtol=1e-5)


def test_input_specs_cover_all_cells():
    """input_specs returns ShapeDtypeStructs for every assigned cell."""
    for cfg in ARCHS.values():
        m = build_model(cfg)
        for shp in shapes_for(cfg):
            specs = m.input_specs(shp)
            assert all(isinstance(s, jax.ShapeDtypeStruct)
                       for s in jax.tree.leaves(specs))
            if shp.kind == "train":
                assert "targets" in specs


def test_long_500k_skip_rule():
    """long_500k only for sub-quadratic archs (assignment rule)."""
    for cfg in ARCHS.values():
        names = [s.name for s in shapes_for(cfg)]
        if cfg.family in ("ssm", "hybrid"):
            assert "long_500k" in names, cfg.name
        else:
            assert "long_500k" not in names, cfg.name
