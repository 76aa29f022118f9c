"""Per-architecture smoke tests: reduced variant of each assigned family runs
one forward + one train step on CPU with shape + finiteness assertions, and
decode (cache) consistency vs the full-sequence pass."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_architectures
from repro.configs.shapes import SHAPES
from repro.models import (
    apply_lm,
    encdec_decode,
    encdec_encode,
    encdec_loss,
    init_caches,
    init_dec_caches,
    init_encdec,
    init_lm,
    lm_loss,
    reduced,
)
from repro.optim import adam

KEY = jax.random.PRNGKey(0)
ARCHS = list_architectures()


def _reduced(arch):
    cfg = reduced(get_config(arch))
    if cfg.num_experts:  # avoid capacity-drop nondeterminism in tests
        cfg = cfg.with_(moe_capacity_factor=8.0)
    return cfg


def _batch(cfg, b=2, s=16):
    ks = jax.random.split(KEY, 3)
    out = {
        "tokens": jax.random.randint(ks[0], (b, s), 0, cfg.vocab_size),
        "targets": jax.random.randint(ks[1], (b, s), 0, cfg.vocab_size),
        "weights": jnp.ones((b,)),
    }
    if cfg.family == "vlm":
        out["prefix"] = 0.02 * jax.random.normal(
            ks[2], (b, cfg.num_patches, cfg.d_model))
    if cfg.family == "encdec":
        out["frames"] = 0.02 * jax.random.normal(
            ks[2], (b, cfg.encoder_seq, cfg.d_model))
    return out


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = _reduced(arch)
    b, s = 2, 16
    batch = _batch(cfg, b, s)
    if cfg.family == "encdec":
        params = init_encdec(KEY, cfg)
        enc = encdec_encode(params, cfg, batch["frames"])
        assert enc.shape == (b, cfg.encoder_seq, cfg.d_model)
        logits, _ = encdec_decode(params, cfg, batch["tokens"], enc)
    else:
        params = init_lm(KEY, cfg)
        logits, _, aux = apply_lm(params, cfg, batch["tokens"],
                                  prefix_embeds=batch.get("prefix"))
        assert jnp.isfinite(aux)
    assert logits.shape == (b, s, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all()), f"{arch}: non-finite logits"


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_updates_and_finite(arch):
    cfg = _reduced(arch)
    batch = _batch(cfg)
    init = init_encdec if cfg.family == "encdec" else init_lm
    params = init(KEY, cfg)
    opt = adam(1e-3)
    opt_state = opt.init(params)

    def loss_fn(p):
        if cfg.family == "encdec":
            ls, ws, aux = encdec_loss(p, cfg, batch["frames"],
                                      batch["tokens"], batch["targets"],
                                      batch["weights"])
        else:
            ls, ws, aux = lm_loss(p, cfg, batch["tokens"], batch["targets"],
                                  batch["weights"],
                                  prefix_embeds=batch.get("prefix"))
        return ls / jnp.maximum(ws, 1e-9) + 0.01 * aux

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert bool(jnp.isfinite(loss)), f"{arch}: non-finite loss"
    gnorm = sum(float(jnp.sum(jnp.square(g)))
                for g in jax.tree_util.tree_leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0, f"{arch}: bad grads"
    new_params, _ = opt.update(params, grads, opt_state, jnp.zeros((), jnp.int32))
    delta = sum(float(jnp.sum(jnp.abs(a - b)))
                for a, b in zip(jax.tree_util.tree_leaves(new_params),
                                jax.tree_util.tree_leaves(params)))
    assert delta > 0, f"{arch}: params unchanged"


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_prefill(arch):
    cfg = _reduced(arch)
    b, s = 2, 10
    batch = _batch(cfg, b, s)
    if cfg.family == "encdec":
        params = init_encdec(KEY, cfg)
        enc = encdec_encode(params, cfg, batch["frames"])
        full, _ = encdec_decode(params, cfg, batch["tokens"], enc)
        caches = init_dec_caches(cfg, b, s)
        outs = []
        for i in range(s):
            lg, caches = encdec_decode(
                params, cfg, batch["tokens"][:, i:i + 1], enc, caches=caches,
                positions=jnp.full((b, 1), i, jnp.int32))
            outs.append(lg)
    else:
        params = init_lm(KEY, cfg)
        full, _, _ = apply_lm(params, cfg, batch["tokens"])
        caches = init_caches(cfg, b, s)
        outs = []
        for i in range(s):
            lg, caches, _ = apply_lm(
                params, cfg, batch["tokens"][:, i:i + 1], caches=caches,
                positions=jnp.full((b, 1), i, jnp.int32))
            outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               atol=2e-4, rtol=2e-3)


@pytest.mark.parametrize("arch", ["llama3-8b", "recurrentgemma-9b"])
def test_sliding_window_decode(arch):
    """Windowed attention decode (ring cache) == windowed full pass."""
    cfg = _reduced(arch).with_(window=4)
    if cfg.family == "hybrid":
        cfg = cfg.with_(local_window=4)
    b, s = 1, 12
    params = init_lm(KEY, cfg)
    toks = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    full, _, _ = apply_lm(params, cfg, toks)
    caches = init_caches(cfg, b, s)  # cache len capped at window internally? use s
    outs = []
    for i in range(s):
        lg, caches, _ = apply_lm(params, cfg, toks[:, i:i + 1], caches=caches,
                                 positions=jnp.full((b, 1), i, jnp.int32))
        outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               atol=2e-4, rtol=2e-3)


def test_param_counts_match_citations():
    """Full configs must hit the published parameter counts (±12%)."""
    from repro.launch.steps import param_count

    expected = {
        "grok-1-314b": 314e9,
        "command-r-plus-104b": 104e9,
        "mamba2-1.3b": 1.3e9,
        "yi-9b": 9e9,
        "recurrentgemma-9b": 9e9,
        "whisper-medium": 0.769e9,
        "phi-3-vision-4.2b": 3.8e9,   # LM backbone (vision tower is stubbed)
        "llama3-8b": 8e9,
        "gemma-2b": 2.5e9,
        "deepseek-v2-236b": 236e9,
        "mellum2-12b-a2.5b": 12e9,
    }
    for arch, target in expected.items():
        n = param_count(get_config(arch))
        assert abs(n - target) / target < 0.12, (
            f"{arch}: {n/1e9:.2f}B vs expected {target/1e9:.1f}B")


def test_vlm_prefix_positions_excluded_from_loss():
    cfg = _reduced("phi-3-vision-4.2b")
    b, s = 2, 16
    batch = _batch(cfg, b, s)
    params = init_lm(KEY, cfg)
    ls, ws, _ = lm_loss(params, cfg, batch["tokens"], batch["targets"],
                        batch["weights"], prefix_embeds=batch["prefix"])
    # weight sum excludes the patch-prefix positions
    assert float(ws) == b * (s - cfg.num_patches)
