"""The benchmark harness on the CPU: cells, traffic, metrics and
architectures found by name from files added beside the others, a tiny
cell's whole run through the harness, and the entry's refusal to run
without a TPU."""

from __future__ import annotations

import filecmp
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

import _tiny
from _tiny import ROOT, tiny_root, write_json

from bench import harness, program  # noqa: E402


def test_added_files_are_found_by_name(tmp_path):
    """A configuration, a traffic mix, limits and a per-layer metric added
    as new files are found by the names BENCHMARK.json gives them, with
    no file of the benchmark edited."""
    root = tiny_root(tmp_path)
    (root / "bench" / "metrics" / "rows_per_round.py").write_text(
        "def read(run):\n"
        "    return sum(map(sum, (r.batches for r in run.rounds))) "
        "/ len(run.rounds)\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({
        "name": "rows_per_round", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "round dispatch",
        "moves": "tokens_per_s", "workloads": ["tiny.dense"]})
    write_json(root / "BENCHMARK.json", spec)

    cell = harness.load_cell(root, "tiny.dense")
    assert cell["config"] == _tiny.TINY_DENSE
    assert cell["traffic"] == _tiny.TINY_TRAFFIC
    assert cell["limits"]["limits"] == _tiny.TINY_LIMITS
    assert "rows_per_round" in {m["name"] for m in cell["per_layer"]}
    assert "rows_per_round" not in {
        m["name"] for m in harness.load_cell(root, "tiny.vlm")["per_layer"]}
    # cell-specific metrics keep to their cells
    assert "collective_share" not in {m["name"] for m in cell["per_layer"]}
    run = harness.Run(conf=cell["config"], arch=cell["arch"],
                      traffic=cell["traffic"], chips=1,
                      device_kind="TPU v5 lite", setup_s=1.0, window_s=2.0,
                      rounds=[harness.Round([1, 2, 3], [1, 2, 3], [], 0, 1),
                              harness.Round([2, 2, 2], [2, 2, 2], [], 0, 1)],
                      peak_bytes=0)
    assert harness.load_reader(root, "rows_per_round")(run) == 6.0
    # nothing that was there before was touched
    cmp = filecmp.dircmp(ROOT / "bench", root / "bench",
                         ignore=["__pycache__"])
    assert not cmp.diff_files and not cmp.left_only


def test_every_metric_has_a_reader():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.load_reader(ROOT, m["name"])), m["name"]
    for w in spec["workloads"]:
        cell = harness.load_cell(ROOT, w["name"])
        for fn in ("param_shapes", "init_leaf", "row_losses",
                   "program_config", "param_count",
                   "train_flops_per_position", "kernel_work"):
            assert callable(getattr(cell["arch"], fn)), (w["name"], fn)
        assert set(cell["limits"]["limits"]) == {
            "loss_gap.1", "loss_gap.2", "loss_gap.3", "grad_gap",
            "delta_gap"}


def test_reachable_batches_follow_the_controller_bounds():
    traffic = dict(_tiny.TINY_TRAFFIC, b0=4,
                   controller={"kind": "p", "b_min": 2, "b_max": 6})
    assert list(harness.reachable_batches(traffic)) == [2, 3, 4, 5, 6]
    traffic["controller"] = {"kind": "p", "b_min": 1}
    assert list(harness.reachable_batches(traffic)) == list(range(1, 11))


@pytest.mark.parametrize("cell", ["tiny.dense", "tiny.vlm"])
def test_tiny_cell_runs_through_the_harness(tmp_path, cell):
    """Set-up, window and check of a tiny cell on the CPU (the kernel in
    interpret mode): every round counted, nothing compiled in the window,
    and the checked steps agree with the reference."""
    root = tiny_root(tmp_path)
    out = harness.run_cell(root, cell, 2**31 + 12345, 0.5, False,
                           t_start=0.0, require_tpu=False)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert out["window_compiles"] == {"compiles": 0, "traces": 0}
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    assert out["metrics"]["tokens_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]


def test_checked_steps_take_the_window_path(tmp_path):
    """Every bucket the controller can reach runs before the checked
    steps, so they trace nothing and re-run nothing for timing: the path
    the window's rounds take."""
    root = tiny_root(tmp_path)
    su = harness.set_up(root, "tiny.dense", 7, require_tpu=False)
    assert su.checked_traces == 0
    assert len(su.plan) == su.spec["traffic"]["checked_steps"]
    # every bucket a worker can reach was traced before them, once
    trainer = su.session.trainer
    reach = harness.reachable_batches(su.spec["traffic"])
    assert program.traces(trainer) == len(
        {trainer.bucket_for(0, b) for b in reach})


def test_entry_refuses_a_host_without_tpu(capsys):
    sys.path.insert(0, str(ROOT / "bench"))
    import run

    assert jax.devices()[0].platform == "cpu"
    assert run.main(["--workload", "phi3v-het3-dyn-4k", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) != 0
    captured = capsys.readouterr()
    assert '"correct"' not in captured.out
    assert "no TPU" in captured.err


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's own files
    (no program) exits non-zero and prints no result."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for path in spec["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, *spec["command"][1:], "--workload",
         spec["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


# An architecture that the benchmark does not have: the dense decoder with
# every layer's attention windowed.  Written into a benchmark root as a new
# file, beside a configuration that names it; the window reaches the
# program through ``program_config`` and the reference through the shared
# helpers, and the work counts keep only the pairs inside the window.
TINY_WINDOW_MODULE = '''
"""The dense decoder with every layer's attention windowed to
``sliding_window`` positions."""

from pathlib import Path

import jax
import jax.numpy as jnp

from bench import work
from bench.harness import load_arch
from bench.reference import causal_attention, rms_norm, rope

decoder = load_arch(Path(__file__).resolve().parents[2], "decoder")
param_shapes = decoder.param_shapes
init_leaf = decoder.init_leaf
param_count = decoder.param_count


def _dims(conf):
    h = conf["num_attention_heads"]
    return (h, conf["num_key_value_heads"], conf["head_dim"],
            conf["sliding_window"])


def row_losses(params, conf, tokens, targets, prefix, row_w, dtype):
    h, hkv, dh, window = _dims(conf)
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    r, s = tokens.shape
    x = p["embed"]["table"][tokens]
    layers = p["groups"]["b0"]
    for i in range(conf["num_hidden_layers"]):
        lp = jax.tree.map(lambda a: a[i], layers)
        y = rms_norm(x, lp["norm1"]["scale"], eps)
        q = (y @ lp["attn"]["wq"]["w"]).reshape(r, s, h, dh)
        k = (y @ lp["attn"]["wk"]["w"]).reshape(r, s, hkv, dh)
        v = (y @ lp["attn"]["wv"]["w"]).reshape(r, s, hkv, dh)
        o = causal_attention(rope(q, theta), rope(k, theta), v, window)
        x = x + o.reshape(r, s, h * dh) @ lp["attn"]["wo"]["w"]
        y = rms_norm(x, lp["norm2"]["scale"], eps)
        m = lp["mlp"]
        x = x + (jax.nn.silu(y @ m["w_gate"]["w"]) * (y @ m["w_up"]["w"])
                 ) @ m["w_down"]["w"]
    x = rms_norm(x, p["final_norm"]["scale"], eps)
    logits = (x @ p["lm_head"]["w"]).astype(jnp.float32)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0]
    w = jnp.broadcast_to(row_w[:, None], nll.shape)
    return jnp.sum(nll * w), jnp.sum(w)


def program_config(conf):
    return decoder.program_config(conf).with_(window=conf["sliding_window"])


def train_flops_per_position(conf, seq):
    h, _, dh, window = _dims(conf)
    attn = (conf["num_hidden_layers"] * 12 * dh * h
            * work.attention_pairs(seq, window))
    return 6 * decoder.matmul_params(conf) + attn / seq


def kernel_work(conf, seq, valid_rows):
    h, hkv, dh, window = _dims(conf)
    rows = valid_rows * conf["num_hidden_layers"]
    return {"attention": work.flash_work(seq, h, hkv, dh, rows, window)}
'''
TINY_WINDOW = dict(_tiny.TINY_DENSE, name="tiny-window", model="tiny_window",
                   sliding_window=48)


def test_an_architecture_is_added_as_files(tmp_path):
    """A windowed architecture, its configuration, traffic and limits
    added to a root as new files run through the harness to ``correct``,
    with no file that the benchmark has changed; the window reaches the
    program, the reference and the work counts."""
    root = tiny_root(tmp_path)
    (root / "bench" / "arch" / "tiny_window.py").write_text(
        TINY_WINDOW_MODULE)
    write_json(root / "bench" / "configs" / "tiny-window.json", TINY_WINDOW)
    write_json(root / "bench" / "limits" / "tiny.window.json",
               {"limits": _tiny.TINY_LIMITS})
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "tiny-window", "source": "test",
        "file": "bench/configs/tiny-window.json", "reduced": [],
        "why": "test"})
    spec["workloads"].append({"name": "tiny.window", "config": "tiny-window",
                              "traffic": "tiny-het3", "chips": 1,
                              "why": "test"})
    write_json(root / "BENCHMARK.json", spec)
    cmp = filecmp.dircmp(ROOT / "bench", root / "bench",
                         ignore=["__pycache__"])
    assert not cmp.diff_files and not cmp.left_only
    assert not any(sub.diff_files or sub.left_only
                   for sub in cmp.subdirs.values())

    cell = harness.load_cell(root, "tiny.window")
    arch, conf = cell["arch"], cell["config"]
    assert arch.program_config(conf).window == 48
    seq = cell["traffic"]["seq_len"]
    decoder = harness.load_arch(root, "decoder")
    assert arch.kernel_work(conf, seq, 1)["attention"][0] < \
        decoder.kernel_work(conf, seq, 1)["attention"][0]
    assert arch.train_flops_per_position(conf, seq) < \
        decoder.train_flops_per_position(conf, seq)

    out = harness.run_cell(root, "tiny.window", 2**31 + 4321, 0.3, False,
                           t_start=0.0, require_tpu=False)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert out["window_compiles"] == {"compiles": 0, "traces": 0}
