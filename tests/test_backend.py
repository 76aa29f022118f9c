"""Execution backends (DESIGN.md §11): SimBackend golden equivalence,
MeshBackend ragged padding+masking gradient exactness, bucket-ladder
recompile bounds, and mesh end-to-end runs on the 1-device CPU mesh."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.api import (
    AddWorker,
    ClusterSpec,
    Experiment,
    MeshBackend,
    RemoveWorker,
    SimBackend,
    TrainConfig,
    paper_workload,
)
from repro.core import bucket_ladder, bucket_up, combine_weighted
from repro.het.simulator import WorkerSpec
from repro.launch.mesh import make_data_mesh
from repro.optim import sgd
from repro.train.mesh import MeshTrainer, dilation_from_specs

GROWTH = 1.25


def _experiment(backend=None, workload="linreg", **cfg_kw):
    cfg = dict(b0=16, microbatch=4, batching="dynamic", max_steps=12, seed=0)
    cfg.update(cfg_kw)
    return Experiment(
        workload=paper_workload(workload),
        cluster=ClusterSpec.hlevel(39, 6, workload="mnist-cnn",
                                   backend=backend),
        optimizer=sgd(0.05),
        config=TrainConfig(**cfg),
    )


# ----------------------------------------------------------- bucket ladder


class TestBucketLadder:
    @given(st.integers(1, 3000), st.integers(1, 16), st.integers(1, 64))
    def test_rung_covers_quantizes_and_anchors(self, b, quantum, base):
        r = bucket_up(b, base=base, growth=GROWTH, quantum=quantum)
        assert r >= b
        assert r % quantum == 0
        assert r >= base

    @given(st.integers(1, 1500), st.integers(1, 1500))
    def test_rungs_monotone(self, a, b):
        lo, hi = min(a, b), max(a, b)
        assert bucket_up(lo, base=8) <= bucket_up(hi, base=8)

    @given(st.integers(1, 200), st.integers(1, 2000),
           st.sampled_from([1, 2, 4, 8]))
    def test_recompile_count_is_logarithmic(self, b_min, span, quantum):
        """Sweeping EVERY batch in [b_min, b_max] visits at most
        ceil(log1.25(b_max/b_min)) + 1 distinct bucket shapes — the
        recompile bound of the mesh backend (acceptance criterion)."""
        b_max = b_min + span
        seen = {bucket_up(b, base=8, growth=GROWTH, quantum=quantum)
                for b in range(b_min, b_max + 1)}
        bound = math.ceil(math.log(b_max / b_min, GROWTH)) + 1
        assert len(seen) <= bound

    @given(st.integers(2, 4096))
    def test_ladder_length_logarithmic(self, b_max):
        rungs = bucket_ladder(b_max, base=1, growth=GROWTH, quantum=1)
        assert rungs[-1] >= b_max
        assert all(y >= x * GROWTH for x, y in zip(rungs, rungs[1:]))
        assert len(rungs) <= math.ceil(
            math.log(rungs[-1] / rungs[0], GROWTH)) + 1

    def test_validation(self):
        with pytest.raises(ValueError):
            bucket_up(0)
        with pytest.raises(ValueError):
            bucket_up(4, quantum=0)
        with pytest.raises(ValueError):
            bucket_up(4, growth=1.0)


# ------------------------------------------- ragged padding+masking grads


class _RecordingSource:
    """Wraps a workload's next_batch, recording what each call returned so
    the test can build the unpadded reference from the SAME examples."""

    def __init__(self, next_batch):
        self.next_batch = next_batch
        self.fetched = []

    def __call__(self, worker, n):
        batch = self.next_batch(worker, n)
        self.fetched.append(batch)
        return batch


_RIG = None


def ragged_rig():
    """One MeshTrainer reused across property examples so the jit cache
    persists (recompiles stay ladder-bounded across the whole sweep).
    Module-level lazy singleton rather than a fixture: the hypothesis stub
    (and real hypothesis health checks) don't mix fixtures with @given."""
    global _RIG
    if _RIG is None:
        wl = paper_workload("linreg")
        src = _RecordingSource(wl.next_batch)
        trainer = MeshTrainer(
            mesh=make_data_mesh(),
            num_workers=4,
            init_params=wl.init,
            loss_and_grad=wl.loss_and_grad,
            next_batch=src,
            optimizer=sgd(0.05),
            cfg=TrainConfig(b0=16, microbatch=4, batching="uniform",
                            max_steps=5),
        )
        _RIG = (trainer, wl, src)
    return _RIG


class TestRaggedGradients:
    # the first example of each bucket shape compiles: no per-example deadline
    @settings(max_examples=10, deadline=None)
    @given(st.lists(st.integers(1, 37), min_size=2, max_size=4))
    def test_padded_masked_equals_unpadded_combine(self, batches):
        """THE correctness property of the mesh backend: for an arbitrary
        ragged split {b_k}, bucketed padding + masking + weighted_psum +
        lambda-combine gives the same gradient as the unpadded
        combine_weighted reference over the same examples (allclose, fp32).
        """
        trainer, wl, src = ragged_rig()
        mesh_grads, ref_grads = [], []
        for k, b in enumerate(batches):
            src.fetched.clear()
            g_mesh, ls, ws, _t = trainer._measured_worker_grad(k, b)
            assert ws == pytest.approx(b)  # mask weight == real examples
            (padded,) = src.fetched
            # unpadded reference: the same first b examples, no padding rows
            sliced = jax.tree_util.tree_map(lambda x: x[:b], padded)
            (ls_ref, ws_ref, _aux), g_sum = wl.loss_and_grad(
                trainer.params, sliced, jnp.ones((b,), jnp.float32))
            assert float(ws_ref) == pytest.approx(b)
            assert ls == pytest.approx(float(ls_ref), rel=1e-5)
            ref_grads.append(jax.tree_util.tree_map(
                lambda g: g / b, g_sum))
            mesh_grads.append(g_mesh)
        combined_mesh = combine_weighted(mesh_grads, batches)
        combined_ref = combine_weighted(ref_grads, batches)
        for lm, lr in zip(jax.tree_util.tree_leaves(combined_mesh),
                          jax.tree_util.tree_leaves(combined_ref)):
            np.testing.assert_allclose(np.asarray(lm), np.asarray(lr),
                                       rtol=1e-5, atol=1e-6)

    def test_recompiles_stay_ladder_bounded(self):
        """After the whole property sweep above, total XLA traces are still
        bounded by the ladder over the max bucket ever used."""
        trainer, _, _ = ragged_rig()
        if not any(trainer.worker_buckets):
            pytest.skip("property sweep did not run")
        top = max(max(b) for b in trainer.worker_buckets if b)
        ladder = bucket_ladder(top, base=trainer.bucket_base, growth=GROWTH,
                               quantum=trainer.quantum)
        assert trainer.accum_traces <= len(ladder)


# -------------------------------------------------------- golden: sim path


class TestSimBackendGolden:
    def test_default_backend_is_sim_and_histories_match(self):
        """ClusterSpec(backend=None) and explicit SimBackend() produce
        bit-for-bit identical seeded histories (the golden guarantee)."""
        out_a = _experiment(backend=None).run()
        out_b = _experiment(backend=SimBackend()).run()
        assert [r.loss for r in out_a["history"]] == \
               [r.loss for r in out_b["history"]]
        assert [r.batches for r in out_a["history"]] == \
               [r.batches for r in out_b["history"]]
        assert out_a["sim_time"] == out_b["sim_time"]
        assert out_a["final_batches"] == out_b["final_batches"]


# -------------------------------------------------------- mesh end-to-end


class TestMeshBackend:
    def test_experiment_runs_ragged_with_bounded_compiles(self):
        exp = _experiment(backend=MeshBackend(dilation=[3.0, 1.5, 1.0]),
                          max_steps=10)
        session = exp.session()
        init_batches = list(session.trainer.batches)  # probe-derived plan
        out = session.run()
        trainer = session.trainer
        assert out["steps"] == 10
        # ragged: the probe-calibrated static init + dilated measurements
        # give non-uniform per-worker batches
        assert any(len(set(rec.batches)) > 1 for rec in out["history"])
        # Σb_k invariant holds under the controller
        assert sum(out["final_batches"]) == sum(out["history"][0].batches)
        # measured per-worker times recorded each round
        assert all(rec.worker_times and min(rec.worker_times) > 0
                   for rec in out["history"])
        # acceptance criterion: <= ceil(log1.25(bmax/bmin)) + 1 compiles per
        # worker (distinct bucket shapes; the jit cache only shrinks that)
        seen = [[rec.batches[k] for rec in out["history"]]
                + [exp.config.b0, init_batches[k]]   # probe + initial plan
                for k in range(trainer.k)]
        for k, buckets in enumerate(trainer.worker_buckets):
            b_min, b_max = min(seen[k]), max(seen[k])
            bound = (math.ceil(math.log(b_max / b_min, GROWTH)) + 1
                     if b_max > b_min else 1)
            assert len(buckets) <= bound, (k, sorted(buckets), b_min, b_max)
        # loss moved: real SGD happened
        assert out["final_loss"] < out["history"][0].loss

    def test_compiled_step_is_the_rounds_program(self):
        """``compiled_step`` hands back the program a worker's rounds ran:
        no fresh trace, and the same gradient on the same rows."""
        session = _experiment(backend=MeshBackend(), max_steps=2).session()
        session.run()
        trainer = session.trainer
        bucket = max(trainer.worker_buckets[0])
        batch = trainer.next_batch(0, bucket)
        traces = trainer.accum_traces
        compiled = trainer.compiled_step(0, batch)
        assert trainer.accum_traces == traces
        assert compiled.as_text()
        mask = jnp.ones((bucket,), jnp.float32)
        got = compiled(trainer.params, batch, mask)
        want = trainer._exec[0].gradfn(trainer.params, batch, mask)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_membership_events_on_mesh(self):
        cluster = ClusterSpec.hlevel(39, 6, backend=MeshBackend()) \
            .with_schedule(RemoveWorker(step=3, worker=0),
                           AddWorker(step=6, spec=WorkerSpec(cores=12)))
        exp = Experiment(
            workload=paper_workload("linreg"),
            cluster=cluster,
            optimizer=sgd(0.05),
            config=TrainConfig(b0=8, microbatch=4, batching="dynamic",
                               max_steps=9),
        )
        out = exp.run()
        assert out["steps"] == 9
        assert [(s, kind) for s, kind, _ in out["membership_log"]] == \
               [(3, "remove"), (6, "add")]
        assert len(out["final_batches"]) == 3
        # the global batch survives both membership events
        assert sum(out["final_batches"]) == sum(out["history"][0].batches)

    def test_asp_converges_like_sim(self):
        """Mesh ASP (DESIGN.md §12): the measured-time event queue drives
        staleness-weighted updates, and the closed loop lands on the same
        allocation *ordering* as the golden sim-ASP run of the identical
        experiment (slowest declared worker smallest batch).  The CNN, not
        linreg: a linreg step takes about 0.1 ms on a CPU, under the jitter
        of dispatch, so its measured times carry no batch-size signal."""
        def experiment(backend):
            return _experiment(backend=backend, workload="mnist-cnn",
                               sync="asp", max_steps=18)

        out_sim = experiment(SimBackend()).run()
        out_mesh = experiment(MeshBackend(dilation="from-spec")).run()
        assert out_mesh["steps"] == 18
        # staleness recorded per update (ints, bounded by in-flight workers)
        stale = [r.straggler_waste for r in out_mesh["history"]]
        assert all(0 <= s < 3 * len(out_mesh["final_batches"])
                   for s in stale)
        assert max(stale) >= 1          # genuinely asynchronous updates
        # Σb_k invariant holds through controller resizes
        assert sum(out_mesh["final_batches"]) == \
            sum(out_mesh["history"][0].batches)
        # converged ordering matches the sim golden run: hlevel(39, 6)
        # declares worker 0 slowest and worker 2 fastest, and the emulated
        # dilation makes the mesh loop chase the same imbalance
        b_sim, b_mesh = out_sim["final_batches"], out_mesh["final_batches"]
        assert b_sim[0] == min(b_sim) and b_sim[-1] == max(b_sim)
        assert b_mesh[0] == min(b_mesh) and b_mesh[-1] == max(b_mesh)
        assert b_mesh[0] < b_mesh[-1]
        # normalized shares land in the same neighborhood (loose: toy-scale
        # dispatch overhead makes the mesh allocation more extreme)
        s, m = sum(b_sim), sum(b_mesh)
        l1 = sum(abs(a / s - b / m) for a, b in zip(b_sim, b_mesh))
        assert l1 < 0.8
        # real SGD happened on stale params and still learned
        assert out_mesh["final_loss"] < out_mesh["history"][0].loss

    def test_checkpoint_roundtrip_bit_identical(self, tmp_path):
        """Mesh Session.save/restore: a fresh session restored from the
        checkpoint carries bit-identical controller + measurement state
        (EWMA, rate model, bucket ladders, engine counters) and continues
        training (DESIGN.md §12 payload)."""
        path = str(tmp_path / "ckpt")

        def experiment():
            return _experiment(backend=MeshBackend(dilation=[3.0, 1.5, 1.0]),
                               max_steps=10)

        s1 = experiment().session()
        for i, _rec in enumerate(s1):
            if i == 5:
                break
        s1.save(path)

        def state(sess):
            # compare the product state surface itself, so fields added to
            # exec_state_dict are automatically covered by this test
            t = sess.trainer
            return {
                "step": t.step_idx,
                "batches": list(t.batches),
                "controller": t.controller.state_dict(),
                "exec": t.exec_state_dict(),
                "engine": (t.engine.version, list(t.engine.read_version)),
            }

        s2 = experiment().session()
        s2.restore(path)
        assert state(s2) == state(s1)     # bit-identical, not approx
        for leaf_a, leaf_b in zip(jax.tree_util.tree_leaves(s1.params),
                                  jax.tree_util.tree_leaves(s2.params)):
            np.testing.assert_array_equal(np.asarray(leaf_a),
                                          np.asarray(leaf_b))
        out = s2.run()                    # continues to max_steps
        assert out["steps"] == 10
        assert s2.trainer.step_idx == 10

    def test_restore_rejects_backend_kind_mismatch(self, tmp_path):
        """A sim checkpoint refuses to load into a mesh session (and vice
        versa) with a clear error instead of silently mismatched state."""
        sim_path = str(tmp_path / "sim-ckpt")
        sim_sess = _experiment(backend=SimBackend(), max_steps=2).session()
        sim_sess.run()
        sim_sess.save(sim_path)
        mesh_sess = _experiment(backend=MeshBackend(), max_steps=2).session()
        with pytest.raises(ValueError, match="backend"):
            mesh_sess.restore(sim_path)
        mesh_path = str(tmp_path / "mesh-ckpt")
        mesh_sess.run()
        mesh_sess.save(mesh_path)
        sim_sess2 = _experiment(backend=SimBackend(), max_steps=2).session()
        with pytest.raises(ValueError, match="backend"):
            sim_sess2.restore(mesh_path)

    def test_dilation_validation(self):
        with pytest.raises(ValueError, match="dilation"):
            _experiment(backend=MeshBackend(dilation="nope")).build()
        with pytest.raises(ValueError, match="dilation"):
            _experiment(backend=MeshBackend(dilation=[1.0])).build()

    @pytest.mark.slow
    @pytest.mark.subprocess
    def test_concurrent_slices_on_debug_mesh(self):
        """Concurrent slice dispatch needs a multi-device data axis, and the
        tier-1 suite runs on ONE device — so the 8-fake-device coverage
        (disjoint slices, max-of-workers BSP, mesh ASP, membership replans,
        checkpoint bit-equivalence) runs in a fresh interpreter where the
        XLA device-count flag can still be set (DESIGN.md §12)."""
        import os
        import subprocess
        import sys

        here = os.path.dirname(os.path.abspath(__file__))
        proc = subprocess.run(
            [sys.executable, os.path.join(here, "mesh_slice_runner.py")],
            capture_output=True, text=True, timeout=900)
        assert proc.returncode == 0, \
            f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
        assert "mesh_slice_runner: OK" in proc.stdout

    def test_dilation_from_specs_reference_is_stable(self):
        specs = [WorkerSpec(cores=4), WorkerSpec(cores=11),
                 WorkerSpec(cores=24)]
        dil, for_spec = dilation_from_specs(specs)
        assert dil[2] == 1.0 and dil[0] > dil[1] > 1.0
        # a later joiner is dilated against the SAME reference worker
        assert for_spec(specs[2]) == 1.0
        assert for_spec(WorkerSpec(cores=4)) == pytest.approx(dil[0])
