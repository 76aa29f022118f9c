"""The correctness check at a tiny size on the CPU: the reference agrees
with the program, each fault planted in the timed path turns ``correct``
false, and the control (the reference in bfloat16 put in the program's
place) reads above the limits that sound runs keep to.  The weights of
the benchmark's configurations are pinned leaf by leaf, and the reference's
windowed attention is checked against a brute-force mask."""

from __future__ import annotations

import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _tiny
from _tiny import ROOT, tiny_root

from bench import faults, fit, harness  # noqa: E402
from bench.inputs import make_params  # noqa: E402
from bench.reference import (Reference, causal_attention, compare,  # noqa
                             norm_gap)

DECODER = harness.load_arch(ROOT, "decoder")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("conf", [_tiny.TINY_DENSE, _tiny.TINY_VLM])
def test_weights_have_the_program_layout(conf):
    """The benchmark's weights are the tree the program's own init makes:
    same paths, shapes and dtypes."""
    from repro.models import init_lm

    cfg = DECODER.program_config(conf)
    want = jax.eval_shape(lambda k: init_lm(k, cfg), jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: make_params(DECODER, conf, 3))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), got) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), want)
    assert jax.tree.leaves(DECODER.param_shapes(conf),
                           is_leaf=lambda x: isinstance(x, tuple)) == [
        a.shape for a in jax.tree.leaves(got)]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_planted_fault_is_not_correct(root, fault):
    """Each fault planted under the timed path: a state left unchanged,
    half of each batch left out, a wrong valid-row count, a worker's
    gradient left out of the combine."""
    plant = faults.plant(fault)
    try:
        out = harness.run_cell(root, "tiny.dense", 7, 0.2, False,
                               t_start=0.0, require_tpu=False, plant=plant)
    finally:
        plant.undo()
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_control_fails_where_the_program_passes(root):
    """The reference in bfloat16 in the program's place fails a limit that
    the program keeps to on the same rows and weights."""
    su = harness.set_up(root, "tiny.dense", 11, require_tpu=False)
    spec, plan, prog = su.spec, su.plan, su.prog
    del su
    ref = Reference(spec, 11).run(plan)
    control = Reference(spec, 11, dtype="bfloat16").run(plan)
    limits = spec["limits"]["limits"]
    sound, ctl = compare(prog, ref), compare(control, ref)
    assert all(sound[k] <= limits[k] for k in limits)
    assert any(ctl[k] > limits[k] for k in limits)


def test_control_script_reads_sound_control_and_faults(root, tmp_path):
    """``bench/control.py``, which takes the readings behind a cell's
    limits on the chip, at the tiny size: one line per reading, and the
    fault and the control reading above the program."""
    import json
    import sys

    sys.path.insert(0, str(_tiny.ROOT / "bench"))
    import control

    out = tmp_path / "readings.jsonl"
    assert control.main([
        "--workload", "tiny.dense", "--seeds", "5", "--control-seeds", "5",
        "--fault-seeds", "5", "--faults", "dropped_worker",
        "--out", str(out), "--root", str(root), "--cpu"]) == 0
    rows = {r["kind"]: r["gaps"] for r in map(json.loads, open(out))}
    assert set(rows) == {"sound", "control", "dropped_worker"}
    assert rows["dropped_worker"]["grad_gap"] > 100 * rows["sound"]["grad_gap"]
    assert rows["control"]["delta_gap"] > 100 * rows["sound"]["delta_gap"]


def test_norm_gap_takes_the_worst_leaf_against_a_floor():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    prog = {"a": 1.1, "b": 2.0, "c": 0.5}
    gap, leaf = norm_gap(prog, ref)
    assert leaf == "c" and gap == pytest.approx(0.5 / 1.0)
    gap, leaf = norm_gap(prog, ref, ["a", "b"])
    assert leaf == "a" and gap == pytest.approx(0.1)
    gap, _ = norm_gap({"a": float("nan"), "b": 2.0, "c": 0.0}, ref)
    assert gap != gap   # a NaN is never within a limit


# The first 16 hex digits of each leaf's sha256 at seed 2**31 + 15, as the
# weights were made before their shapes and init moved into
# bench/arch/decoder.py: same paths, same bits.
PINNED_WEIGHTS = {
    "phi3v-1l": {
        "embed/table": "7eb100be60f09852",
        "final_norm/scale": "83343ed0f6123c1b",
        "groups/b0/attn/wk/w": "856768b3756c824d",
        "groups/b0/attn/wo/w": "99ea0b1311c817ad",
        "groups/b0/attn/wq/w": "7b58be4184d45dc3",
        "groups/b0/attn/wv/w": "5afd3175dfa93444",
        "groups/b0/mlp/w_down/w": "a5dd930b1aa77d8e",
        "groups/b0/mlp/w_gate/w": "0776944974c43255",
        "groups/b0/mlp/w_up/w": "622ab870bebbac05",
        "groups/b0/norm1/scale": "83343ed0f6123c1b",
        "groups/b0/norm2/scale": "83343ed0f6123c1b",
        "lm_head/w": "ba10492a67002bc9"},
    "yi9b-1l": {
        "embed/table": "bc322c2aaa7496d3",
        "final_norm/scale": "3035aac5fb87474c",
        "groups/b0/attn/wk/w": "85cc901a2e4a6c9f",
        "groups/b0/attn/wo/w": "82b8bac3cd810edc",
        "groups/b0/attn/wq/w": "4d61e133fba2f354",
        "groups/b0/attn/wv/w": "3b32590ecaec01ea",
        "groups/b0/mlp/w_down/w": "a5971085fadd8e01",
        "groups/b0/mlp/w_gate/w": "e4ebab4566783277",
        "groups/b0/mlp/w_up/w": "594eba4e37b7ef5d",
        "groups/b0/norm1/scale": "3035aac5fb87474c",
        "groups/b0/norm2/scale": "3035aac5fb87474c",
        "lm_head/w": "e310e079533a8011"},
}


@pytest.mark.parametrize("name", sorted(PINNED_WEIGHTS))
def test_weights_are_pinned(name):
    conf = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())
    arch = harness.load_arch(ROOT, conf["model"])
    flat, _ = jax.tree_util.tree_flatten_with_path(
        make_params(arch, conf, 2**31 + 15))
    got = {}
    for path, leaf in flat:
        key = "/".join(str(getattr(k, "key", k)) for k in path)
        got[key] = hashlib.sha256(np.asarray(leaf).tobytes()).hexdigest()[:16]
        del leaf
    del flat
    assert got == PINNED_WEIGHTS[name]


def test_fit_shapes_are_the_weights_made():
    """``bench/fit.py`` compiles the step of ``yi9b-het3-dyn-4k`` on the
    tree the run's weights have, through the configuration's module."""
    spec = harness.load_cell(ROOT, "yi9b-het3-dyn-4k")
    arch, conf = spec["arch"], spec["config"]
    want = jax.eval_shape(lambda: make_params(arch, conf, 1))
    got = fit.param_structs(arch, conf)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.leaves(jax.tree.map(lambda a: (a.shape, a.dtype), got)) \
        == jax.tree.leaves(jax.tree.map(lambda a: (a.shape, a.dtype), want))


def brute_force_attention(q, k, v, window):
    """Softmax attention over an explicit (S, S) mask, one head at a time,
    in float64 numpy."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    r, s, h, dh = q.shape
    rep = h // k.shape[2]
    qi, ki = np.meshgrid(np.arange(s), np.arange(s), indexing="ij")
    keep = ki <= qi
    if window is not None:
        keep &= qi - ki < window
    out = np.zeros_like(q)
    for b in range(r):
        for j in range(h):
            logits = q[b, :, j] @ k[b, :, j // rep].T / np.sqrt(dh)
            logits = np.where(keep, logits, -np.inf)
            p = np.exp(logits - logits.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[b, :, j] = p @ v[b, :, j // rep]
    return out


@pytest.mark.parametrize("s,window", [(40, None), (40, 1), (40, 7),
                                      (40, 40), (1100, 300)])
def test_blocked_attention_against_a_brute_force_mask(s, window):
    """Causal attention in query blocks (1100 spans three blocks of 512),
    with and without a window, grouped KV heads."""
    ks = jax.random.split(jax.random.PRNGKey(s), 3)
    q = jax.random.normal(ks[0], (1, s, 4, 8), jnp.float32)
    k = jax.random.normal(ks[1], (1, s, 2, 8), jnp.float32)
    v = jax.random.normal(ks[2], (1, s, 2, 8), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = causal_attention(q, k, v, window)
    np.testing.assert_allclose(np.asarray(got),
                               brute_force_attention(q, k, v, window),
                               rtol=1e-5, atol=1e-5)
