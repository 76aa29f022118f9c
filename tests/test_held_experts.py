"""The held-expert layer, and window and rope per layer kind, at a tiny
size on the CPU: shares of the experts add up to the uncut layer, no
token is dropped however the router leans, YaRN's frequencies follow the
published formula, and each layer kind gets its own window and rope."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import block_pattern, reduced
from repro.models import layers as L
from repro.models.config import ModelConfig, Rope

KEY = jax.random.PRNGKey(0)
E, K, D, F = 8, 3, 32, 16


def _cfg(held=E, **kw):
    return ModelConfig(name="t", family="moe", num_layers=1, d_model=D,
                       vocab_size=64, mlp="moe", num_experts=E,
                       experts_held=held, moe_top_k=K, moe_d_ff=F,
                       moe_group_size=24, **kw)


def _uncut(seed=0):
    p = L.init_moe(jax.random.PRNGKey(seed), _cfg())
    # the router spread wide enough that ties do not decide a choice
    return dict(p, router={"w": 4.0 * p["router"]["w"]})


def _share(p, experts):
    """The layer as the chip holding ``experts`` sees it: those experts'
    weights first, the router's columns in the same order (the layer
    holds its first experts)."""
    order = np.concatenate([experts, np.setdiff1d(np.arange(E), experts)])
    return {"router": {"w": p["router"]["w"][:, order]},
            **{n: p[n][experts] for n in ("w_gate", "w_up", "w_down")}}


def _per_token(p, x):
    """A per-token loop: every token through each of its top-k experts,
    weighted by its renormalised gate."""
    xs = np.asarray(x, np.float64).reshape(-1, D)
    w = {n: np.asarray(p[n], np.float64)
         for n in ("w_gate", "w_up", "w_down")}
    router = np.asarray(p["router"]["w"], np.float64)
    out = np.zeros_like(xs)
    for t, v in enumerate(xs):
        logits = v @ router
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        top = np.argsort(-probs, kind="stable")[:K]
        gates = probs[top] / probs[top].sum()
        for e, g in zip(top, gates):
            h = v @ w["w_gate"][e]
            act = h / (1 + np.exp(-h)) * (v @ w["w_up"][e])
            out[t] += g * (act @ w["w_down"][e])
    return out.reshape(x.shape)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_shares_add_up_to_the_uncut_layer(use_pallas):
    """Four chips holding two experts each give, summed, what the layer
    holding all eight gives, and that is the per-token loop's result."""
    p = _uncut()
    x = jax.random.normal(KEY, (2, 20, D))
    with jax.default_matmul_precision("highest"):
        whole, _, cnt = L.apply_held_moe(p, x, _cfg(use_pallas=use_pallas))
        parts = [L.apply_held_moe(_share(p, np.arange(2 * j, 2 * j + 2)), x,
                                  _cfg(2, use_pallas=use_pallas))
                 for j in range(4)]
    total = sum(out for out, _, _ in parts)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(whole), _per_token(p, x),
                               rtol=1e-4, atol=1e-4)
    # every (token, choice) pair was computed by exactly one share
    assert int(cnt["expert_pairs"]) == 2 * 20 * K
    assert sum(int(c["expert_pairs"]) for _, _, c in parts) == 2 * 20 * K


@pytest.mark.parametrize("use_pallas", [False, True])
def test_no_token_dropped_under_imbalance(use_pallas):
    """Every token's first choice forced onto expert 0: the layer still
    matches the per-token loop (the one-hot path's capacity would drop
    most of them), and expert 0's load is every token."""
    p = _uncut(1)
    bias = np.zeros((D, E), np.float32)
    bias[:, 0] = 50.0
    p["router"]["w"] = p["router"]["w"] + bias
    x = jnp.abs(jax.random.normal(KEY, (3, 16, D))) + 0.5
    with jax.default_matmul_precision("highest"):
        out, _, cnt = L.apply_held_moe(p, x, _cfg(use_pallas=use_pallas))
    np.testing.assert_allclose(np.asarray(out), _per_token(p, x),
                               rtol=1e-4, atol=1e-4)
    assert int(cnt["expert_pairs_max"]) == 3 * 16


def test_padded_rows_route_nowhere():
    p = _uncut(2)
    x = jax.random.normal(KEY, (3, 8, D))
    out, _, cnt = L.apply_held_moe(p, x, _cfg(), num_valid=jnp.int32(2))
    assert int(cnt["expert_pairs"]) == 2 * 8 * K
    assert float(jnp.abs(out[2]).max()) == 0.0
    full, _, _ = L.apply_held_moe(p, x, _cfg())
    np.testing.assert_allclose(np.asarray(out[:2]), np.asarray(full[:2]),
                               rtol=1e-6, atol=1e-6)


def test_held_count_is_validated():
    for bad in (0, E + 1):
        with pytest.raises(ValueError, match="experts_held"):
            _cfg(bad).validate()
    _cfg(1).validate()


def test_yarn_follows_the_published_formula():
    """Mellum2's full layers: theta 500000, factor 16 over 8192 original
    positions, beta_fast 32, beta_slow 1, head_dim 128.  The correction
    range, written out: dim ln(8192 / (2 pi beta)) / (2 ln 500000) is
    18.08 for beta 32 and 34.98 for beta 1, floored and ceiled to 18 and
    35: pairs below 18 keep their frequency, pairs from 35 on are divided
    by 16, and the ramp between is linear; the attention factor is the published 1.2772588722239782 =
    0.1 ln 16 + 1."""
    spec = dict(get_config("mellum2-12b-a2.5b").rope_by_kind)["attn"]
    inv, factor = L.rope_frequencies(128, spec)
    assert factor == pytest.approx(1.2772588722239782, abs=0)
    assert factor == pytest.approx(0.1 * math.log(16) + 1, rel=1e-15)
    plain = 500000.0 ** (-np.arange(64) / 64)
    low, high = 18, 35
    ramp = np.clip((np.arange(64) - low) / (high - low), 0, 1)
    want = plain / 16 * ramp + plain * (1 - ramp)
    np.testing.assert_allclose(np.asarray(inv), want, rtol=2e-6)
    assert np.asarray(inv)[18] == pytest.approx(plain[18], rel=2e-6)
    assert np.asarray(inv)[35] == pytest.approx(plain[35] / 16, rel=2e-6)
    # the sliding layers' rope is plain, factor 1
    inv0, f0 = L.rope_frequencies(128, Rope(500000.0))
    np.testing.assert_allclose(np.asarray(inv0), plain, rtol=2e-6)
    assert f0 == 1.0


def test_yarn_scales_the_rotation():
    """cos and sin carry the attention factor: a rotated vector's norm is
    the factor times the input's."""
    spec = dict(get_config("mellum2-12b-a2.5b").rope_by_kind)["attn"]
    x = jax.random.normal(KEY, (1, 5, 2, 128))
    y = L.rope(x, jnp.arange(5)[None], spec)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=-1),
                               1.2772588722239782 * np.linalg.norm(
                                   np.asarray(x), axis=-1), rtol=1e-5)


def test_windows_and_rope_per_kind():
    cfg = get_config("mellum2-12b-a2.5b")
    kinds = block_pattern(cfg)
    assert kinds == ("local", "local", "local", "attn")
    assert [cfg.window_for(k) for k in kinds] == [1024, 1024, 1024, None]
    ropes = [cfg.rope_for(k) for k in kinds]
    assert [r.yarn_factor for r in ropes] == [None, None, None, 16.0]
    assert {r.theta for r in ropes} == {500000.0}
    assert cfg.num_layers // len(kinds) == 7
    # the one-hot path's models keep one kind of layer
    assert block_pattern(get_config("grok-1-314b")) == ("attn",)
    tiny = reduced(cfg)
    assert tiny.num_layers == 4 and tiny.local_window == 8
    assert tiny.experts_held <= tiny.num_experts


def test_trainer_counts_the_pairs_of_its_round():
    """Mellum2 at a reduced size through ``repro.api`` (Experiment ->
    MeshTrainer -> lm_workload with the kernels): after one uniform round
    the trainer's counters hold what the model counts on that round's
    rows, summed over the workers, and the largest load."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import chip_smoke
    from repro.api import MeshBackend
    from repro.models import lm_loss

    cfg = reduced(get_config("mellum2-12b-a2.5b"))
    trainer, _, log = chip_smoke.train(
        cfg, seq_len=32, steps=1, seed=0, backend=MeshBackend(),
        batching="uniform")
    pairs, most = 0, 0
    for batch in log.round0:
        n = batch["tokens"].shape[0]
        *_, cnt = lm_loss(log.params0, cfg, batch["tokens"],
                          batch["targets"], jnp.ones((n,)), counters=True)
        pairs += int(cnt["expert_pairs"])
        most = max(most, int(cnt["expert_pairs_max"]))
    assert trainer.model_counters == {"expert_pairs": pairs,
                                      "expert_pairs_max": most}
    assert 0 < pairs <= len(log.round0) * 2 * 32 * 4 * cfg.moe_top_k
