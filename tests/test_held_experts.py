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


def _per_token(p, x, k=K):
    """A per-token loop: every token through each of its top-k experts
    that the layer holds, weighted by its renormalised gate."""
    xs = np.asarray(x, np.float64).reshape(-1, D)
    w = {n: np.asarray(p[n], np.float64)
         for n in ("w_gate", "w_up", "w_down")}
    router = np.asarray(p["router"]["w"], np.float64)
    out = np.zeros_like(xs)
    for t, v in enumerate(xs):
        logits = v @ router
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        top = np.argsort(-probs, kind="stable")[:k]
        gates = probs[top] / probs[top].sum()
        for e, g in zip(top, gates):
            if e >= len(w["w_gate"]):
                continue
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


# The ladder: 2 of 16 experts held, top-2, groups of 32 tokens, so a
# group fills 8 rows on average and its buffer takes 16, 32 or 64 rows.
E2, K2, H2, G2 = 16, 2, 2, 32
RUNGS = (16, 32, 64)
# tokens forced onto both held experts, by group of (2, 64) tokens: none;
# a quarter of groups 1 and 3; all of groups 0 and 2
FORCED = {0: {}, 1: {1: 8, 3: 8}, 2: {0: G2, 2: G2}}


def _ladder_cfg(use_pallas):
    return ModelConfig(name="t", family="moe", num_layers=1, d_model=D,
                       vocab_size=64, mlp="moe", num_experts=E2,
                       experts_held=H2, moe_top_k=K2, moe_d_ff=F,
                       moe_group_size=G2, use_pallas=use_pallas)


def _ladder_case(rung):
    """Weights and tokens whose groups fill up to ``RUNGS[rung]`` rows:
    input feature 0 is 0 but for the forced tokens, and the router sends
    it to the held experts."""
    p = L.init_moe(jax.random.PRNGKey(4), _ladder_cfg(False))
    w = 4.0 * np.asarray(p["router"]["w"])
    w[0, :H2] = 60.0
    x = np.array(jax.random.normal(KEY, (2, 64, D)))
    groups = x.reshape(-1, G2, D)
    groups[..., 0] = 0.0
    for gi, n in FORCED[rung].items():
        groups[gi, :n, 0] = 1.0
    return dict(p, router={"w": jnp.asarray(w)}), jnp.asarray(
        groups.reshape(x.shape))


def _filled(p, x):
    """Pairs on held experts per group, counted with numpy."""
    logits = np.asarray(x, np.float64).reshape(-1, D) @ np.asarray(
        p["router"]["w"], np.float64)
    top = np.argsort(-logits, axis=-1, kind="stable")[:, :K2]
    return (top < H2).sum(-1).reshape(-1, G2).sum(-1)


def _dense(p, x):
    """The per-token loop's sum, vectorised so JAX can differentiate it:
    every token through every held expert, weighted by its renormalised
    gate where it chose that expert, and the layer's balance loss."""
    xs = x.reshape(-1, D)
    probs = jax.nn.softmax(xs @ p["router"]["w"], axis=-1)
    gates, experts = jax.lax.top_k(probs, K2)
    gates = gates / gates.sum(-1, keepdims=True)
    chosen = jax.nn.one_hot(experts, E2)
    weight = (chosen * gates[..., None]).sum(1)[:, :H2]
    h = jnp.einsum("td,edf->tef", xs, p["w_gate"])
    u = jnp.einsum("td,edf->tef", xs, p["w_up"])
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(h) * u, p["w_down"])
    aux = (probs.mean(0) * chosen.sum(1).mean(0)).sum() * E2
    return (y * weight[..., None]).sum(1).reshape(x.shape), aux


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("rung", [0, 1, 2])
def test_each_rung_matches_the_full_buffer_and_the_loop(rung, use_pallas,
                                                        monkeypatch):
    """Routings whose fullest group lands in each rung of the ladder: the
    layer's result, balance loss, counters and gradients (to the input,
    the router and the three expert weights) are those of one buffer of
    the most rows a group can fill and of a per-token loop, and
    ``expert_groups_full`` counts exactly the groups forced to the top."""
    cfg = _ladder_cfg(use_pallas)
    assert L.held_rungs(G2, K2, H2, E2) == RUNGS
    p, x = _ladder_case(rung)
    filled = _filled(p, x)
    assert max(filled) > (0, *RUNGS)[rung] and max(filled) <= RUNGS[rung]
    ct = jax.random.normal(jax.random.PRNGKey(5), x.shape)

    def loss(layer, p_, x_):
        out, aux, cnt = layer(p_, x_)
        return (out * ct).sum() + aux, (out, aux, cnt)

    def evaluate(layer):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(
                lambda p_, x_: loss(layer, p_, x_), argnums=(0, 1),
                has_aux=True)(p, x)

    (_, (out, aux, cnt)), grads = evaluate(
        lambda p_, x_: L.apply_held_moe(p_, x_, cfg))
    monkeypatch.setattr(L, "held_rungs", lambda g, k, held, e: (g * min(
        k, held),))
    (_, (out1, aux1, cnt1)), grads1 = evaluate(
        lambda p_, x_: L.apply_held_moe(p_, x_, cfg))
    (_, (out2, aux2, _)), grads2 = evaluate(
        lambda p_, x_: (*_dense(p_, x_), None))

    assert int(cnt["expert_groups_full"]) == sum(
        n == G2 for n in FORCED[rung].values())
    assert int(cnt["expert_rows_max"]) == max(filled)
    assert int(cnt["expert_pairs"]) == int(cnt1["expert_pairs"]) == sum(
        filled)
    assert int(cnt["expert_pairs_max"]) == int(cnt1["expert_pairs_max"])
    assert int(cnt1["expert_groups_full"]) == len(filled)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out1),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out), _per_token(p, x, K2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(aux), float(aux1), rtol=1e-6)
    np.testing.assert_allclose(float(aux), float(aux2), rtol=1e-5)
    for got, full, loop in zip(jax.tree.leaves(grads),
                               jax.tree.leaves(grads1),
                               jax.tree.leaves(grads2)):
        scale = float(jnp.abs(loop).max())
        assert float(jnp.abs(got - full).max()) <= 1e-6 * scale
        assert float(jnp.abs(got - loop).max()) <= 1e-5 * scale


def test_one_rung_where_half_the_experts_are_held():
    """Twice the average fill reaches the most a group can fill once half
    the experts are held: the ladder is that one buffer."""
    assert L.held_rungs(4096, 8, 64, 64) == (32768,)
    assert L.held_rungs(4096, 8, 32, 64) == (32768,)
    assert L.held_rungs(4096, 8, 8, 64) == (8192, 16384, 32768)
    # rounded to 8 rows, and to 256-row tiles past one tile
    assert L.held_rungs(24, 3, 2, 8) == (40, 48)
    assert L.held_rungs(1000, 8, 8, 64) == (2048, 4096, 8000)


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
    pairs, most, rows, full = 0, 0, 0, 0
    for batch in log.round0:
        n = batch["tokens"].shape[0]
        *_, cnt = lm_loss(log.params0, cfg, batch["tokens"],
                          batch["targets"], jnp.ones((n,)), counters=True)
        pairs += int(cnt["expert_pairs"])
        most = max(most, int(cnt["expert_pairs_max"]))
        rows = max(rows, int(cnt["expert_rows_max"]))
        full += int(cnt["expert_groups_full"])
    assert trainer.model_counters == {"expert_pairs": pairs,
                                      "expert_pairs_max": most,
                                      "expert_rows_max": rows,
                                      "expert_groups_full": full}
    assert 0 < pairs <= len(log.round0) * 2 * 32 * 4 * cfg.moe_top_k
