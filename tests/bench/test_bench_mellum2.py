"""Mellum2 in the benchmark, at a tiny size on the CPU: the architecture
module's weights are the program's layout, its reference agrees with the
program's loss and gradients, a tiny copy of the cell runs through the
harness to ``correct`` with its counters read, the counts the metrics
divide by are pinned to hand counts, and the expert kernels' readers read
a trace and fall silent without one."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _tiny
from _tiny import ROOT, tiny_root, write_json

from bench import harness, trace_reduce, work  # noqa: E402
from bench.inputs import make_params  # noqa: E402

MELLUM = harness.load_arch(ROOT, "mellum2")
CONF = json.loads((ROOT / "bench" / "configs" / "mellum2-4l.json").read_text())
SEQ = 4096
# published widths cut to a CPU's size; the layer kinds, routing and rope
# as in the file
TINY = dict(
    CONF, name="tiny-mellum", source="test", hidden_size=64,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    moe_intermediate_size=32, num_router_experts=8, num_experts=3,
    num_experts_per_tok=2, vocab_size=256, sliding_window=48,
    rope_parameters={
        "full_attention": dict(CONF["rope_parameters"]["full_attention"],
                               original_max_position_embeddings=64),
        "sliding_attention": CONF["rope_parameters"]["sliding_attention"]})


def test_weights_have_the_program_layout():
    from repro.models import init_lm

    cfg = MELLUM.program_config(TINY)
    want = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg))
    got = make_params(MELLUM, TINY, 1)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_program_config_keeps_the_published_widths():
    cfg = MELLUM.program_config(CONF)
    assert (cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (
        2304, 32, 4, 128)
    assert (cfg.num_experts, cfg.experts_held, cfg.moe_top_k,
            cfg.moe_d_ff) == (64, 8, 8, 896)
    assert cfg.block_pattern == ("local", "local", "local", "attn")
    assert (cfg.local_window, cfg.window) == (1024, None)
    rope = dict(cfg.rope_by_kind)
    assert rope["local"].yarn_factor is None
    assert (rope["attn"].yarn_factor, rope["attn"].yarn_original_len,
            rope["attn"].yarn_attention_factor) == (16.0, 8192,
                                                    1.2772588722239782)
    assert (cfg.num_layers, cfg.vocab_size) == (4, 12288)
    inv, factor = MELLUM.inv_frequencies(CONF, "full_attention")
    from repro.models.layers import rope_frequencies

    inv_p, factor_p = rope_frequencies(128, rope["attn"])
    np.testing.assert_allclose(np.asarray(inv), np.asarray(inv_p),
                               rtol=1e-6)
    assert factor == factor_p


@pytest.mark.parametrize("use_pallas", [False, True])
def test_program_matches_the_reference(use_pallas):
    """The program's ``lm_loss`` and its gradients against the module's
    ``row_losses``: two valid rows and a padded one, windowed and YaRN
    layers, three of the router's eight experts held."""
    from repro.models import lm_loss

    cfg = MELLUM.program_config(TINY).with_(use_pallas=use_pallas)
    params = make_params(MELLUM, TINY, 2**31 + 5)
    ks = jax.random.split(jax.random.PRNGKey(3))
    tokens = jax.random.randint(ks[0], (3, 128), 0, 256)
    targets = jax.random.randint(ks[1], (3, 128), 0, 256)
    mask = jnp.array([1.0, 1.0, 0.0])

    def program(p):
        ls, ws, _, cnt = lm_loss(p, cfg, tokens, targets, mask,
                                 num_valid=jnp.int32(2), counters=True)
        return ls, (ws, cnt)

    def reference(p):
        return MELLUM.row_losses(p, TINY, tokens, targets, None, mask,
                                 jnp.float32)

    with jax.default_matmul_precision("highest"):
        (ls, (ws, cnt)), g = jax.value_and_grad(program, has_aux=True)(
            params)
        (lr, wr), gr = jax.value_and_grad(reference, has_aux=True)(params)
    assert float(ws) == float(wr) == 256
    assert float(ls) == pytest.approx(float(lr), rel=1e-6)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gr)):
        scale = float(jnp.abs(b).max())
        assert float(jnp.abs(a - b).max()) <= 1e-5 * scale + 1e-7
    # the valid rows' pairs on held experts, over the four layers
    assert 0 < int(cnt["expert_pairs"]) <= 4 * 2 * 128 * 2
    assert int(cnt["expert_pairs_max"]) <= 2 * 128


def test_tiny_mellum_cell_runs_through_the_harness(tmp_path):
    """A tiny copy of the cell, added to a root as files, runs through the
    harness to ``correct`` with nothing compiled in the window."""
    root = tiny_root(tmp_path)
    write_json(root / "bench" / "configs" / "tiny-mellum.json", TINY)
    write_json(root / "bench" / "limits" / "tiny.mellum.json",
               {"limits": _tiny.TINY_LIMITS})
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "tiny-mellum", "source": "test",
        "file": "bench/configs/tiny-mellum.json", "reduced": [],
        "why": "test"})
    spec["workloads"].append({"name": "tiny.mellum", "config": "tiny-mellum",
                              "traffic": "tiny-het3", "chips": 1,
                              "why": "test"})
    write_json(root / "BENCHMARK.json", spec)

    out = harness.run_cell(root, "tiny.mellum", 2**31 + 4321, 0.3, False,
                           t_start=0.0, require_tpu=False)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert out["window_compiles"] == {"compiles": 0, "traces": 0}


def test_hand_counts_mellum():
    """340,349,184 parameters: per layer the attention projections
    21,233,664, the router 147,456, eight experts 49,545,216 and two
    scales; the embedding and the head 28,311,552 each."""
    attn = 2 * 2304 * 4096 + 2 * 2304 * 512
    assert MELLUM.attention_params(CONF) == attn == 21_233_664
    assert 8 * MELLUM.expert_params(CONF) == 49_545_216
    layer = attn + 2304 * 64 + 49_545_216 + 2 * 2304
    assert MELLUM.param_count(CONF) == 4 * layer + 2 * 12288 * 2304 + 2304
    assert MELLUM.param_count(CONF) == 340_349_184
    sizes = []

    def walk(t):
        for v in t.values():
            walk(v) if isinstance(v, dict) else sizes.append(np.prod(v))

    walk(MELLUM.param_shapes(CONF))
    assert sum(sizes) == 340_349_184
    # one expert-equivalent a token: 8 choices x 8 held / 64
    assert MELLUM.expected_pairs(CONF, 1) == 1.0
    per_token = 4 * (attn + 2304 * 64 + 3 * 2304 * 896) + 2304 * 12288
    full = 12 * 128 * 32 * SEQ * (SEQ + 1) // 2
    win = 12 * 128 * 32 * (1024 * 1025 // 2 + 3072 * 1024)
    flops = MELLUM.train_flops_per_position(CONF, SEQ)
    assert flops == pytest.approx(6 * per_token + (full + 3 * win) / SEQ)
    assert round(flops / 1e9, 3) == 1.064


def test_kernel_work_mellum():
    """Attention over each layer's kind and window; the expert kernels
    over the expected pairs, valid tokens x 8 x 8/64 a layer."""
    rows = 3
    got = MELLUM.kernel_work(CONF, SEQ, rows)
    full = work.flash_work(SEQ, 32, 4, 128, rows)
    win = work.flash_work(SEQ, 32, 4, 128, rows, window=1024)
    assert got["attention"] == pytest.approx(
        (full[0] + 3 * win[0], full[1] + 3 * win[1]))
    pairs = rows * SEQ            # a layer
    d, f, w = 2304, 896, 8 * 2304 * 896
    assert got["gmm"] == pytest.approx((
        4 * 2 * 3 * 2 * d * f * pairs,
        4 * 2 * (3 * w + 3 * (d + f) * pairs) * 4))
    assert got["tgmm"] == pytest.approx((
        4 * 3 * 2 * d * f * pairs, 4 * (3 * w + 3 * (d + f) * pairs) * 4))


def _run(trace):
    return harness.Run(conf=CONF, arch=MELLUM, traffic={"seq_len": SEQ},
                       chips=1, device_kind="TPU v5 lite", setup_s=1.0,
                       window_s=2.0,
                       rounds=[harness.Round([1, 2, 3], [1, 2, 3], [], 0, 1),
                               harness.Round([1, 2, 3], [1, 2, 3], [], 0, 1)],
                       peak_bytes=0, trace=trace)


def test_expert_readers():
    roofline = harness.load_reader(ROOT, "expert_roofline")
    share = harness.load_reader(ROOT, "expert_time_share")
    summary = trace_reduce.TraceSummary(
        window_s=2.0, chips=1, busy_s=1.6,
        kernel_s={"attention": 0.5, "gmm": 0.3, "tgmm": 0.1},
        kernel_events={}, collective_s=[0.0], op_s={}, idle_s={},
        round_kernel_s=[None, {"attention": 0.25, "gmm": 0.15,
                               "tgmm": 0.05}])
    assert share(_run(summary)) == pytest.approx(100 * 0.4 / 1.6)
    p = _run(summary).peaks
    least = sum(max(f / p.flops_bf16, b / p.hbm_bytes_s) for f, b in (
        MELLUM.kernel_work(CONF, SEQ, 6)[s] for s in ("gmm", "tgmm")))
    assert roofline(_run(summary)) == pytest.approx(100 * least / 0.2)
    # a program without the expert kernels: the readers fall silent
    silent = trace_reduce.TraceSummary(
        window_s=2.0, chips=1, busy_s=1.6, kernel_s={"attention": 0.5},
        kernel_events={}, collective_s=[0.0], op_s={}, idle_s={},
        round_kernel_s=[{"attention": 0.25}])
    assert roofline(_run(silent)) is None and share(_run(silent)) is None
    assert roofline(_run(None)) is None and share(_run(None)) is None
