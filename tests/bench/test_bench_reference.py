"""The correctness check at a tiny size on the CPU: the reference agrees
with the program, each fault planted in the timed path turns ``correct``
false, and the control (the reference in bfloat16 put in the program's
place) reads above the limits that sound runs keep to."""

from __future__ import annotations

import jax
import pytest

import _tiny
from _tiny import tiny_root

from bench import faults, harness, program  # noqa: E402
from bench.inputs import make_params, param_shapes  # noqa: E402
from bench.reference import Reference, compare, norm_gap  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("conf", [_tiny.TINY_DENSE, _tiny.TINY_VLM])
def test_weights_have_the_program_layout(conf):
    """The benchmark's weights are the tree the program's own init makes:
    same paths, shapes and dtypes."""
    from repro.models import init_lm

    cfg = program.program_config(conf)
    want = jax.eval_shape(lambda k: init_lm(k, cfg), jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: make_params(conf, 3))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), got) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), want)
    assert jax.tree.leaves(param_shapes(conf),
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
    ref = Reference(spec["config"], spec["traffic"], 11).run(plan)
    control = Reference(spec["config"], spec["traffic"], 11,
                        dtype="bfloat16").run(plan)
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
